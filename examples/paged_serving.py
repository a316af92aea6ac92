"""Paged-KV generative serving: block pool, prefix caching, tensor
parallelism.

What this shows (docs/serving.md "Paged KV & prefix caching"):

1. train a tiny GPT, then serve it through the PAGED memory tier
   (``zoo.gpt.gpt_paged_spec`` + ``PagedGenerativeServer``): K/V live
   in fixed-size token blocks from a preallocated pool (one array a
   layer, written in place and read where it lies), each request
   holds a block table grown at decode-step boundaries — capacity is
   proportional to tokens actually held, not ``max_slots x max_seq``;
2. the HBM sizing math: the same budget a small dense deployment
   preallocates, spent as a block pool (``kv_hbm_bytes=``), and the
   pool accounting in ``memory_report()``;
3. prefix caching: a repeated system prompt prefills only its SUFFIX —
   the shared full blocks are chain-hashed, refcounted and reused, so
   repeat TTFT approaches one decode step;
4. greedy output bit-identical to the unbatched dense reference
   (``greedy_decode``) — paged vs dense is a memory-layout change,
   not a numerics change;
5. tensor-parallel serving (``tp=2`` when 2+ devices are visible):
   params + every KV leaf sharded over the model mesh axis, same tokens.
"""
import numpy as np

from deeplearning4j_tpu.autodiff import TrainingConfig
from deeplearning4j_tpu.dataset import DeviceCachedIterator
from deeplearning4j_tpu.learning.updaters import Adam
from deeplearning4j_tpu.serving.generative import greedy_decode
from deeplearning4j_tpu.serving.paged import PagedGenerativeServer
from deeplearning4j_tpu.zoo.gpt import (GPTConfig, build_gpt,
                                        gpt_generative_spec,
                                        gpt_paged_spec)

VOCAB, SEQ, MSL = 96, 16, 32
cfg = GPTConfig(vocab_size=VOCAB, hidden_size=32, num_layers=2,
                num_heads=2, intermediate_size=64, max_seq_len=MSL)

# -- 1. train briefly on random token sequences -------------------------
sd = build_gpt(cfg, batch=4, seq_len=SEQ, seed=0)
sd.training_config = TrainingConfig(
    updater=Adam(1e-3),
    data_set_feature_mapping=["input_ids"],
    data_set_label_mapping=["targets"])
rng = np.random.default_rng(0)
ids = rng.integers(0, VOCAB, (8, SEQ)).astype(np.int32)
tgt = rng.integers(0, VOCAB, (8, SEQ)).astype(np.int32)
hist = sd.fit(DeviceCachedIterator([ids], [tgt], batch_size=4),
              epochs=2)
print(f"trained 2 epochs; final loss "
      f"{hist.loss_curve.losses[-1]:.4f}")

# -- 2. the paged server: a dense deployment's budget as a block pool ---
dense_spec = gpt_generative_spec(sd, cfg)     # reference + sizing only
paged_spec = gpt_paged_spec(sd, cfg)
dense_bytes = 2 * int(np.prod(dense_spec.kv_shape(4, MSL))) * 4
server = PagedGenerativeServer(paged_spec, max_slots=8, block_size=8,
                               kv_hbm_bytes=dense_bytes,
                               max_seq_len=MSL, warmup=True)
rep = server.memory_report()
print(f"pool: {rep['num_blocks']} blocks x {rep['block_size']} tokens "
      f"({rep['kv_bytes_per_block'] / 1024:.1f} KiB/block) from the "
      f"same {dense_bytes / 1024:.0f} KiB a 4-slot dense slab "
      f"preallocates — serving {server.max_slots} slots")

# -- 3. prefix caching: the repeated system prompt prefills its suffix --
system = (np.arange(9, dtype=np.int32) * 5) % VOCAB   # 1 full block
questions = [rng.integers(0, VOCAB, int(rng.integers(2, 8)))
             .astype(np.int32) for _ in range(4)]
prompts = [np.concatenate([system, q]) for q in questions]
budgets = [6, 9, 4, 8]
handles = [server.submit(p, max_new_tokens=n)
           for p, n in zip(prompts, budgets)]
streamed = [list(h.tokens(timeout=120)) for h in handles]
paged_rec = server.metrics.to_record()["paged"]
print(f"prefix cache: hit rate {paged_rec['prefix_hit_rate']:.0%}, "
      f"{paged_rec['prefix_blocks_hit']} shared blocks reused across "
      f"{len(prompts)} requests with one system prompt")

# -- 4. bit-identical to the unbatched dense reference ------------------
for i, (p, n) in enumerate(zip(prompts, budgets)):
    ref = greedy_decode(dense_spec, p, n, max_seq_len=MSL)
    assert streamed[i] == ref, (i, streamed[i], ref)
print("all paged generations == dense unbatched greedy_decode")
print(server.metrics.stats())
server.shutdown()

# -- 5. tensor parallel: same tokens from a sharded server --------------
import jax

if len(jax.devices()) >= 2:
    tp_server = PagedGenerativeServer(paged_spec, max_slots=4,
                                      block_size=8, max_seq_len=MSL,
                                      tp=2, warmup=False)
    got = [tp_server.submit(p, max_new_tokens=n).result(timeout=120)
           for p, n in zip(prompts, budgets)]
    tp_server.shutdown()
    assert got == streamed
    print(f"tp=2 over {len(jax.devices())} devices: params + KV "
          f"sharded, greedy tokens identical")
else:
    print("single device visible: skipping the tp=2 leg")
