"""The lowered text of every paged program a serving cell warms, as a
hash a program: a refactor that must not change a compiled program
(ROADMAP D2, step 1) is accepted when every hash equals the parent's.
The server is stood up through the benchmark's own adapter and its own
``warmup``, with ``AOTDispatch.lower`` wrapped to keep ``as_text()`` and
hand back a stand-in for the executable, so nothing is compiled or run
and no chip is needed (the weights are drawn at the cell's real size:
some 12 GB of host memory, a minute or two; with ``shapes`` for the
directory the weights are their shapes alone, ``jax.eval_shape`` of the
adapter's own draw, and a cell at its real size lowers in a few hundred
MB: compare two trees in the same mode). Run on the CPU from the root
of a checkout, once in the parent's and once in the change's:
``JAX_PLATFORMS=cpu PYTHONPATH=. python experiments/lowered_text.py
<cell> [<dir for the texts> | shapes]``; prints one JSON object."""
import hashlib
import json
import os
import sys

import jax

from benchmark import harness
from deeplearning4j_tpu.compilecache import aot
from deeplearning4j_tpu.monitor import memstats


class _NotCompiled:
    """Stands where ``warmup`` expects a lowered program: its
    ``compile()`` gives no executable, and none is ever called."""

    def compile(self):
        return None


def main(cell_name: str, out_dir: str = "") -> None:
    texts = {}
    real_lower = aot.AOTDispatch.lower

    def lower(self, *args, **kw):
        io = args[self.ph_arg]
        key = self.jit_fn.__name__ + ":" + ",".join(
            f"{n}{tuple(v.shape)}" for n, v in sorted(io.items()))
        texts[key] = real_lower(self, *args, **kw).as_text()
        return _NotCompiled()

    aot.AOTDispatch.lower = lower
    memstats.capture_plan = lambda *a, **k: None    # wants an executable
    cell = harness.Cell(os.getcwd(), cell_name)
    if out_dir == "shapes":
        out_dir, draw = "", cell.adapter.program_params
        cell.adapter.program_params = lambda cfg, seed: jax.eval_shape(
            lambda: draw(cfg, seed))
        memstats.check_headroom = lambda *a, **k: None
    server = cell.adapter.build_server(cell.config, cell.traffic["server"],
                                       seed=1234567891)
    server.shutdown(drain=False)
    out = {}
    for i, (key, text) in enumerate(sorted(texts.items())):
        out[key] = {"sha256": hashlib.sha256(text.encode()).hexdigest(),
                    "bytes": len(text)}
        if out_dir:
            os.makedirs(out_dir, exist_ok=True)
            with open(os.path.join(out_dir, f"{i:02d}.txt"), "w") as f:
                f.write(key + "\n" + text)
    print(json.dumps(out, indent=1, sort_keys=True))


if __name__ == "__main__":
    main(*sys.argv[1:3])
