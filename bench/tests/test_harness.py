"""A whole run of the harness at test size on the CPU: sound, with the
timed path broken underneath, and with the int8 control in the program's
place; for the llama module and for a second architecture, an MoE stack
whose module lives beside its test configuration. The chip check is
skipped (``require_tpu=False``); everything after it is the run the chip
makes."""
import hashlib
import os
import subprocess
import sys
import time

import jax
import numpy as np
import pytest

import harness
from conftest import ROOT, data, llama, tiny_spec

SEED = 3_000_000_017


def run(spec, seed=SEED, seconds=3.0):
    line, sv, w = harness.run_cell(spec, seed, seconds, False,
                                   time.perf_counter(), require_tpu=False)
    return line, sv, w


@pytest.fixture(scope="module")
def sound():
    return run(tiny_spec())


def test_sound_run_is_correct(sound):
    line, sv, _ = sound
    assert line["correct"], line["checks"]
    assert list(line)[-1] == "checks"
    assert set(line["metrics"]) == {"ttft_mean_ms", "itl_p95_ms",
                                    "output_tok_s", "goodput_req_s",
                                    "setup_s"}
    assert line["attempted"] > 0 and line["failed"] == 0
    assert line["checks"]["checked_tokens"]["value"] >= 40


def test_per_layer_readers_without_a_trace(sound):
    """Host-clock and counter metrics read from the run's records; the
    device-trace ones find nothing to read and return nothing."""
    run = sound[1].run
    got = {m["name"]: harness.reader(m["name"])(run)
           for m in run.spec.per_layer}
    for name in ("queue_wait_p90_ms", "tick_wall_ms", "mfu"):
        assert got[name] > 0
    assert 0 < got["prefix_hit_rate"] < 100
    assert got["preempted_share"] >= 0
    for name in ("decode_tick_ms", "prefill_chunk_ms", "decode_roofline",
                 "device_idle_share"):
        assert got[name] is None


def test_token_altered_where_produced_fails(monkeypatch):
    """The decode tick emits a wrong token on every lane, every fifth
    tick: the run must not come out correct."""
    from repro.serve import engine
    real, calls = engine._decode_tick, []

    def broken(*a, **k):
        out = real(*a, **k)
        calls.append(1)
        if len(calls) % 5:
            return out
        nxt = (out[0] + 1) % 256
        return (nxt, nxt) + tuple(out[2:])
    monkeypatch.setattr(engine, "_decode_tick", broken)
    line, _, _ = run(tiny_spec())
    assert not line["correct"]
    c = line["checks"]["mean_logit_gap"]
    assert c["value"] > c["limit"]


@pytest.mark.parametrize("model, cell", [
    ("small.json", "small-cell.json"), ("tiny-moe.json", "tiny-moe-cell.json")])
def test_int8_control_in_the_programs_place_fails(model, cell):
    """The control, the reference at int8 put in the program's place, read
    on a sound run's own sample at the test size where the two separate:
    the program keeps within the mean-gap limit and the control does not.
    The MoE stack runs through the same harness, with its own module named
    from its file; its served weights are the program's parameter bytes
    (``served_weights`` stops the run otherwise)."""
    spec = tiny_spec(model=model, cell=cell)
    assert spec.arch.__file__.endswith(spec.model["bench_arch"])
    line, sv, w = run(spec, seconds=10.0)
    assert line["correct"], line["checks"]
    sample = harness.sample_checked(sv.run, SEED,
                                    spec.cell["check"]["served_tokens"])
    ctl = np.concatenate(harness.logit_gaps(spec, w, sample, quant="int8"))
    assert ctl.mean() > spec.cell["check"]["mean_logit_gap"]


@pytest.mark.parametrize("name", ["tiny.json", "tiny-moe.json"])
def test_program_tree_is_the_programs_own(name):
    from repro.models import model
    m = data(name)
    arch = harness.load_arch(m)
    ours = jax.eval_shape(lambda: arch.program_params(arch.make_weights(m, 1)))
    theirs = jax.eval_shape(lambda: model.init_params(
        jax.random.PRNGKey(0), arch.program_config(m)))
    assert jax.tree.structure(ours) == jax.tree.structure(theirs)
    for a, b in zip(jax.tree.leaves(ours), jax.tree.leaves(theirs)):
        assert (a.shape, a.dtype) == (b.shape, b.dtype)


@pytest.mark.parametrize("bench_arch", [None, "bench/archs/nothing.py"])
def test_configuration_without_its_module_stops(tmp_path, bench_arch):
    """No default and no fallback: a configuration that names no module,
    or a path that is no file, stops with an error that names it."""
    import json
    bench = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
    bench["configs"][0]["file"] = "model.json"
    m = data("tiny.json")
    m.pop("bench_arch")
    if bench_arch:
        m["bench_arch"] = bench_arch
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bench))
    (tmp_path / "model.json").write_text(json.dumps(m))
    with pytest.raises(SystemExit, match=bench_arch or "'bench_arch'"):
        harness.load_spec(bench["workloads"][0]["name"], root=str(tmp_path))


def test_llama_weights_are_unchanged():
    """The served weights of ``tiny.json`` at one seed, and the program's
    tree over them, as the benchmark drew them before the architecture
    modules: a later edit of the draw moves every reading."""
    w = llama().make_weights(data("tiny.json"), SEED)
    h = hashlib.sha256()
    for k in sorted(w):
        h.update(k.encode())
        h.update(np.asarray(w[k]).tobytes())
    assert h.hexdigest() == ("0a6742b560fee405e71a92b42ede2f61"
                             "5ea3ab30d059d5c3f9b69e0c342abc4b")
    h = hashlib.sha256()
    for path, leaf in jax.tree_util.tree_flatten_with_path(
            llama().program_params(w))[0]:
        h.update(jax.tree_util.keystr(path).encode())
        h.update(np.asarray(leaf).tobytes())
    assert h.hexdigest() == ("ee5b3fd5769b2198d01f6f31f7e397ea"
                             "8974c69209484ef43740b62cdb72f40e")


def test_weights_follow_the_seed():
    m = data("tiny.json")
    a, b, c = (llama().make_weights(m, s) for s in (7, 7, 2**31 + 7))
    assert np.array_equal(a["wq"], b["wq"])
    assert not np.array_equal(a["wq"], c["wq"])
    assert a["wq"].dtype == jax.numpy.bfloat16


def test_off_a_tpu_it_exits_nonzero_and_prints_no_result():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, os.path.join(ROOT, "bench", "run.py"), "--workload",
         "smollm-360m.agent", "--seed", "1", "--seconds", "1", "--trace",
         "0"], capture_output=True, text=True, env=env, timeout=300)
    assert p.returncode != 0
    assert '"metrics"' not in p.stdout
