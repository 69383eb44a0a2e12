"""The traffic generator: a schedule fixed by rank across seeds, fixed
arrival counts, the context cap, the mix's derivation from its published
statistics, and no dependence on the CPU-pinned old benchmark."""
import json
import os

import numpy as np
import pytest

import loadgen
from conftest import BENCH

SPANS = [(0.0, 20.0, 2.0), (20.0, 51.0, 2.0), (71.0, 60.0, 2.0)]


def mix(name):
    with open(os.path.join(BENCH, "traffic", f"{name}.json")) as f:
        return loadgen.Mix.from_json(json.load(f))


def test_schedule_is_the_same_for_every_seed_but_the_tokens():
    m = mix("agent")
    runs = [loadgen.schedule(m, np.random.default_rng(s), 49152, SPANS)
            for s in (1, 2, 3_000_000_001)]
    for r in runs[1:]:
        assert [q.due for q in r] == [q.due for q in runs[0]]
        assert [len(q.prompt) for q in r] == [len(q.prompt) for q in runs[0]]
        assert [q.out_len for q in r] == [q.out_len for q in runs[0]]
    assert not np.array_equal(runs[0][0].prompt, runs[1][0].prompt)
    counts = [sum(20.0 <= q.due < 71.0 for q in r) for r in runs]
    assert counts == [round(2.0 * 51.0)] * 3
    # a fixed order, not sorted: long and short requests mix along the span
    lens = [len(q.prompt) for q in runs[0] if 20.0 <= q.due < 71.0]
    assert lens != sorted(lens) and lens != sorted(lens, reverse=True)


def test_prompts_share_the_prefix_and_fit_the_context():
    m = mix("agent")
    sched = loadgen.schedule(m, np.random.default_rng(5), 49152, SPANS)
    for q in sched:
        assert np.array_equal(q.prompt[:m.prefix_tokens],
                              sched[0].prompt[:m.prefix_tokens])
        assert len(q.prompt) > m.prefix_tokens and q.out_len >= 1
        assert len(q.prompt) + q.out_len <= m.context


def test_mix_follows_its_published_statistics():
    """The generator's parameters are the cited trace's means cut by the
    stated scale, and the schedule's means keep them (the tail that the
    context cuts aside)."""
    m = mix("agent")
    d = m.derivation
    mean_in = d["scale"] * d["mean_input_tokens"]
    assert m.prefix_tokens == round(mean_in * d["cached_share"])
    assert m.prompt["mean"] == pytest.approx(mean_in - m.prefix_tokens)
    assert m.output["mean"] == pytest.approx(d["scale"]
                                             * d["mean_output_tokens"])
    assert m.prefix_tokens + m.prompt["mean"] + m.output["mean"] \
        <= m.context < 2 * (m.prefix_tokens + m.prompt["mean"]
                            + m.output["mean"])
    sched = loadgen.schedule(m, np.random.default_rng(0), 49152,
                             [(0.0, 1000.0, 2.0)])
    own = np.mean([len(q.prompt) - m.prefix_tokens for q in sched])
    assert 0.9 * m.prompt["mean"] < own < 1.02 * m.prompt["mean"] + 1
    out = np.mean([q.out_len for q in sched])
    assert abs(out - m.output["mean"]) < 1.0
    cached = m.prefix_tokens / np.mean([len(q.prompt) for q in sched])
    assert abs(cached - d["cached_share"]) < 0.02


def test_quantiles_keep_the_tail():
    u = loadgen.strata(4000, 7)
    assert sorted(u) == sorted((np.arange(4000) + 0.5) / 4000)
    x = loadgen.quantile({"dist": "exponential", "mean": 440.5}, u)
    assert abs(np.mean(x) - 440.5) < 0.01 * 440.5
    assert np.quantile(x, 0.99) > 4.5 * 440.5            # the tail is kept
    with pytest.raises(ValueError):
        loadgen.quantile({"dist": "uniform"}, u)


def test_nothing_imports_the_cpu_pinned_benchmark():
    for dirpath, _, files in os.walk(BENCH):
        for f in files:
            if f.endswith(".py") and f != "test_loadgen.py":
                with open(os.path.join(dirpath, f)) as fh:
                    text = fh.read()
                assert "serve_engine" not in text and "benchmarks" not in text
