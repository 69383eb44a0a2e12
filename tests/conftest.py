"""Shared test configuration.

Installs the deterministic ``hypothesis`` fallback (_hypothesis_fallback.py)
when the real package is missing, so airgapped environments still collect and
run the property-test modules.

JAX's persistent compilation cache stays off for the test suite: only the
chip entry points (``chip_smoke.py``, ``repro.launch.serve``) turn it on, at
``JAX_COMPILATION_CACHE_DIR`` or ``<repo>/.jax_cache``. (It was once tried
here to hide slow XLA CPU compiles and dropped after deserialized CPU
executables with donated buffers misbehaved under an older jax; the suite
has not been soaked with it on jax 0.9.)
"""
import importlib.util
import os
import sys


def _ensure_hypothesis() -> None:
    if importlib.util.find_spec("hypothesis") is not None:
        return
    path = os.path.join(os.path.dirname(__file__), "_hypothesis_fallback.py")
    spec = importlib.util.spec_from_file_location("hypothesis", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    mod.install(mod)               # single registration point for sys.modules


_ensure_hypothesis()
