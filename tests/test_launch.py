"""Entry points on the CPU: the serving CLI's stream path in-process, and
``chip_smoke.py``'s serve-and-compare phase at ``smoke()`` size plus its
refusal to run without a TPU."""
import importlib.util
import os
import sys

import jax
import numpy as np
import pytest

from repro import configs
from repro.launch import serve as serve_cli
from repro.serve import api

jax.config.update("jax_platform_name", "cpu")

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(scope="module")
def chip_smoke():
    spec = importlib.util.spec_from_file_location(
        "chip_smoke", os.path.join(REPO, "chip_smoke.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.fixture
def no_compile_cache(monkeypatch):
    """The entry point turns the persistent compile cache on; the tests keep
    it off."""
    monkeypatch.setattr(serve_cli, "enable_compile_cache", lambda: "")


@pytest.mark.parametrize("extra", [[],
                                   ["--replicas", "2", "--trace", "fleet"]])
def test_serve_cli_stream_runs_to_completion(extra, monkeypatch, capsys,
                                             no_compile_cache):
    """``python -m repro.launch.serve --arch smollm-360m --smoke --stream``
    through its own ``main``: every request of the trace completes."""
    monkeypatch.setattr(sys, "argv", [
        "serve", "--arch", "smollm-360m", "--smoke", "--stream",
        "--requests", "8", "--show-stream", "1", *extra])
    serve_cli.main()
    out = capsys.readouterr().out
    assert "8 completed / 0 shed / 0 rejected of 8 requests" in out


def test_chip_smoke_serve_and_compare_at_smoke_size(chip_smoke):
    cfg = configs.get_config(chip_smoke.ARCH).smoke()
    params, bias = serve_cli.init_model(cfg, seed=0)
    rng = np.random.default_rng(0)
    warmup = [api.ServeRequest(
        rid=0, tokens=rng.integers(0, cfg.vocab_size, 16).astype(np.int32),
        params=api.SamplingParams(max_new_tokens=2))]
    trace = chip_smoke.smoke_trace(cfg, seed=0, requests=8, prefix=32,
                                   unit=16, out_unit=4)
    assert sum(not r.params.is_greedy for r in trace) == 2
    assert min(len(r.tokens) for r in trace) == 16
    assert max(len(r.tokens) for r in trace) == 96
    res = chip_smoke.serve_and_compare(params, cfg, bias, slots=4,
                                       max_cache=128, prefill_chunk=16,
                                       trace=trace, warmup=warmup,
                                       clock=chip_smoke.CompileClock(jax))
    assert res["requests"] == 6                  # the greedy ones
    assert res["max_delta"] < chip_smoke.TOLERANCE
    assert res["tokens"] == sum(r.params.max_new_tokens for r in trace)


def test_chip_smoke_refuses_without_tpu(chip_smoke, capsys):
    assert jax.devices()[0].platform != "tpu"
    assert chip_smoke.main([]) != 0
    assert '"ok"' not in capsys.readouterr().out
