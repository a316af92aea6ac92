"""``lowered_text.py`` at the tests' tiny sizes: the lowered text of every
paged program that the three tiny serving cells of ``tests/benchmarking``
warm (GPT-2, SmallThinker, GLM-4.7-Flash; four programs each), as a hash
a program. The real cells draw 6-12 GB of weights, which the sandbox's CPU
may not hold; these draw kilobytes, and a change to ``serving/paged`` that
alters what a family's programs are handed, or to a family's block,
changes these texts as it changes the real ones. Run on the CPU from the
root of a checkout, once in the parent's and once in the change's, and
compare: ``JAX_PLATFORMS=cpu PYTHONPATH=. python
experiments/lowered_text_tiny.py``; prints one JSON object. PR 35: 12
hashes equal to the parent's."""
import hashlib, json, os, sys
sys.path.insert(0, os.path.join(os.getcwd(), "tests", "benchmarking"))
sys.path.insert(0, os.path.join(os.getcwd(), "tests"))
import importlib
from deeplearning4j_tpu.compilecache import aot
from deeplearning4j_tpu.monitor import memstats
texts = {}
real_lower = aot.AOTDispatch.lower
class _NotCompiled:
    def compile(self): return None
def lower(self, *args, **kw):
    io = args[self.ph_arg]
    key = CUR + ":" + self.jit_fn.__name__ + ":" + ",".join(f"{n}{tuple(v.shape)}" for n, v in sorted(io.items()))
    texts[key] = real_lower(self, *args, **kw).as_text()
    return _NotCompiled()
aot.AOTDispatch.lower = lower
memstats.capture_plan = lambda *a, **k: None
import bench_tiny, test_run_glm_cpu, test_run_st_cpu
cells = [("gpt2", bench_tiny.TINY, bench_tiny.CHAT["server"]),
         ("smallthinker", test_run_st_cpu.TINY, test_run_st_cpu.MIX["server"]),
         ("glm4_moe_lite", test_run_glm_cpu.TINY, test_run_glm_cpu.MIX["server"])]
for fam, cfg, server in cells:
    CUR = fam
    adapter = importlib.import_module("benchmark.adapters." + fam)
    srv = adapter.build_server(cfg, server, seed=1234567891)
    srv.shutdown(drain=False)
print(json.dumps({k: hashlib.sha256(t.encode()).hexdigest()[:12] for k, t in sorted(texts.items())}, indent=0))
