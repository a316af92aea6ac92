"""EvaByte's two programs alone on the chip, at the cell's size, without
a server: a 512-byte prefill chunk behind 20,480 cached positions (ten
windows turned: an empty exact window, 1,280 summaries) and behind
1,536 positions of the first window, and one decode step of 8 lanes
(two lanes some 21k and 9k positions in, six chat lanes) with the
summary table 88 entries wide. Each is run five times under a profiler
capture; prints one JSON line a program: its wall ms a run and its 45
largest device operations in ms a run (PERF.md section 5's breakdown of
``eva_mixed_closed``, PR 35). Run on the chip from the root of a
checkout: ``PYTHONPATH=. python experiments/eva_programs_profile.py``."""
import json
import os
import shutil
import tempfile
import time

import jax
import jax.numpy as jnp
import numpy as np

from benchmark import harness, reduce
from benchmark.adapters import evabyte as adapter
from deeplearning4j_tpu.zoo.evabyte import evabyte_paged_decode_fns

BS, MAXB, LANES, RUNS = 16, 2048, 8, 5
RING, ENTRIES = 128, 128


def main():
    cell = harness.Cell(os.getcwd(), "eva_mixed_closed")
    harness.place_compile_cache(cell.root)
    pc = adapter.program_config(cell.config)
    params = adapter.program_params(cell.config, 1234567891)
    prefill_fn, decode_fn = evabyte_paged_decode_fns(pc, BS, MAXB)
    pre = jax.jit(prefill_fn, donate_argnums=(1, 2))
    dec = jax.jit(decode_fn, donate_argnums=(1, 2))

    def side(blocks):
        return tuple(jnp.zeros((blocks, BS, pc.num_heads * pc.head_dim),
                               jnp.bfloat16) for _ in range(pc.num_layers))

    nb = 1 + LANES * RING
    kc, vc = (side(nb), side(nb)), (side(nb), side(nb))
    rng = np.random.default_rng(0)

    def traced(name, fn, io):
        nonlocal kc, vc
        out = fn(params, kc, vc, io)
        kc, vc = out[:2]
        jax.block_until_ready(out[2])
        d = os.path.join(tempfile.gettempdir(), f"prof_{name}")
        shutil.rmtree(d, ignore_errors=True)
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        jax.profiler.start_trace(d, profiler_options=opts)
        with jax.profiler.TraceAnnotation(reduce.WINDOW_EVENT):
            t = time.perf_counter()
            for _ in range(RUNS):
                out = fn(params, kc, vc, io)
                kc, vc = out[:2]
            jax.block_until_ready(out[2])
            ms = (time.perf_counter() - t) / RUNS * 1000
        jax.profiler.stop_trace()
        ops = reduce.top_device_ops(reduce.load(d), 45)
        print(json.dumps({"name": name, "ms": ms, "ops_ms_per_run": [
            [k, round(v / RUNS * 1000, 3)] for k, v in ops]}), flush=True)

    ring = jnp.arange(1, RING + 1, dtype=jnp.int32)
    for hist in (20480, 1536):
        traced(f"prefill512_hist{hist}", pre, {
            "tokens": jnp.asarray(rng.integers(0, pc.vocab_size, 512),
                                  jnp.int32),
            "length": jnp.int32(512), "hist": jnp.int32(hist),
            "table.exact": ring, "write_block.exact": jnp.repeat(
                jnp.arange(1, 33, dtype=jnp.int32), BS),
            "table.summary": ring,
            "write_block.summary": jnp.full(512 // pc.chunk, 5, jnp.int32)})
    lanes = jnp.arange(1, LANES + 1, dtype=jnp.int32)
    traced("decode_summary88", dec, {
        "tokens": jnp.zeros(LANES, jnp.int32),
        "positions": jnp.asarray([21000, 8700, 30, 60, 90, 300, 700, 1200],
                                 jnp.int32),
        "active": jnp.ones(LANES, bool),
        "tables.exact": jnp.tile(ring[None], (LANES, 1)),
        "write_block.exact": lanes, "write_off": jnp.zeros(LANES, jnp.int32),
        "tables.summary": jnp.tile(ring[None, :88], (LANES, 1)),
        "write_block.summary": jnp.zeros(LANES, jnp.int32)})


if __name__ == "__main__":
    main()
