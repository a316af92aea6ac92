"""GLM-4.7-Flash's two programs alone on the chip, at the cell's size,
without a server: a 512-token prefill chunk behind 8,192 and behind
2,048 cached rows, and one decode step of 8 lanes at 1,024 table entries
(two lanes past 14k positions, two past 8k, four chat lanes). Each is
run five times under a profiler capture; prints one JSON line a program:
its wall ms a run and its 45 largest device operations in ms a run
(PERF.md section 5's breakdown of ``glm_mixed_closed``, PR 32). Run on
the chip from the root of a checkout: ``PYTHONPATH=. python
experiments/glm_programs_profile.py``."""
import json
import os
import shutil
import tempfile
import time

import jax
import jax.numpy as jnp
import numpy as np

from benchmark import harness, reduce
from benchmark.adapters import glm4_moe_lite as adapter
from deeplearning4j_tpu.zoo.glm_moe_lite import glm_moe_lite_paged_decode_fns

BS, MAXB, LANES, RUNS = 16, 1024, 8, 5


def main():
    cell = harness.Cell(os.getcwd(), "glm_mixed_closed")
    harness.place_compile_cache(cell.root)
    pc = adapter.program_config(cell.config)
    params = adapter.program_params(cell.config, 1234567891)
    prefill_fn, decode_fn = glm_moe_lite_paged_decode_fns(pc, BS, MAXB)
    pre = jax.jit(prefill_fn, donate_argnums=(1, 2))
    dec = jax.jit(decode_fn, donate_argnums=(1, 2))
    kc = tuple(jnp.zeros((1 + LANES * MAXB, BS, pc.leaf_width), jnp.bfloat16)
               for _ in range(pc.num_layers))
    rng = np.random.default_rng(0)
    table = jnp.arange(1, MAXB + 1, dtype=jnp.int32)

    def traced(name, fn, io):
        nonlocal kc
        out = fn(params, kc, (), io)
        kc = out[0]
        jax.block_until_ready(out[2])
        d = os.path.join(tempfile.gettempdir(), f"prof_{name}")
        shutil.rmtree(d, ignore_errors=True)
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        jax.profiler.start_trace(d, profiler_options=opts)
        with jax.profiler.TraceAnnotation(reduce.WINDOW_EVENT):
            t = time.perf_counter()
            for _ in range(RUNS):
                out = fn(params, kc, (), io)
                kc = out[0]
            jax.block_until_ready(out[2])
            ms = (time.perf_counter() - t) / RUNS * 1000
        jax.profiler.stop_trace()
        ops = reduce.top_device_ops(reduce.load(d), 45)
        print(json.dumps({"name": name, "ms": ms, "ops_ms_per_run": [
            [k, round(v / RUNS * 1000, 3)] for k, v in ops]}), flush=True)

    for hist in (8192, 2048):
        traced(f"prefill512_hist{hist}", pre, {
            "tokens": jnp.asarray(rng.integers(0, pc.vocab_size, 512),
                                  jnp.int32),
            "length": jnp.int32(512), "hist": jnp.int32(hist),
            "table": table})
    traced("decode1024", dec, {
        "tokens": jnp.zeros(LANES, jnp.int32),
        "positions": jnp.asarray([14500, 14400, 9000, 8300, 30, 60, 90, 120],
                                 jnp.int32),
        "active": jnp.ones(LANES, bool),
        "tables": jnp.tile(table[None], (LANES, 1)),
        "write_block": jnp.arange(1, LANES + 1, dtype=jnp.int32),
        "write_off": jnp.zeros(LANES, jnp.int32)})


if __name__ == "__main__":
    main()
