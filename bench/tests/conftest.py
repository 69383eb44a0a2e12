"""The benchmark's own tests: on the CPU, at tiny sizes.

    JAX_PLATFORMS=cpu python -m pytest bench/tests -q
"""
import json
import os
import sys

os.environ.setdefault("JAX_PLATFORMS", "cpu")
BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH)
DATA = os.path.join(BENCH, "tests", "data")
for p in (os.path.join(ROOT, "src"), BENCH):
    if p not in sys.path:
        sys.path.insert(0, p)


def data(name: str) -> dict:
    with open(os.path.join(DATA, name)) as f:
        return json.load(f)


def tiny_spec(model="tiny.json", mix="tiny-agent.json", cell="tiny-cell.json",
              **cell_overrides):
    """A Spec at test size with the benchmark's own metric list."""
    import harness
    import loadgen
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    c = data(cell)
    c.update(cell_overrides)
    m = data(model)
    return harness.Spec(workload="tiny", chips=1, model=m,
                        arch=harness.load_arch(m),
                        mix=loadgen.Mix.from_json(data(mix)), cell=c,
                        end_to_end=bench["end_to_end"],
                        per_layer=bench["per_layer"])


def llama():
    """The llama architecture module, as the harness loads it."""
    import harness
    return harness.load_arch({"bench_arch": "bench/archs/llama.py"})


def pytest_configure(config):
    import jax
    jax.config.update("jax_enable_compilation_cache", False)
