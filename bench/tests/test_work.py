"""The yardstick's arithmetic: required bytes and FLOPs against the hand
reckoning, and the roofline's choice of bound."""
import numpy as np
import pytest

import harness
import work
from conftest import data, llama, ROOT


# a multi-head (not grouped) model at its published widths, openbmb/MiniCPM-2B,
# to check the arithmetic where every head keeps its own K/V
MHA = {"num_hidden_layers": 40, "hidden_size": 2304,
       "num_attention_heads": 36, "num_key_value_heads": 36, "head_dim": 64,
       "intermediate_size": 5760, "vocab_size": 122753,
       "tie_word_embeddings": True, "torch_dtype": "bfloat16"}


def config(name):
    import json
    import os
    if name == "mha":
        return MHA
    with open(os.path.join(ROOT, "bench", "configs", f"{name}.json")) as f:
        return json.load(f)


@pytest.mark.parametrize("name,params,kv", [
    ("smollm-360m", 723_767_040, 40_960),
    ("mha", 5_450_135_040, 368_640)])
def test_bytes_match_hand_reckoning(name, params, kv):
    c = config(name)
    m = llama().work(c)
    assert m.param_bytes == params
    assert m.kv_bytes_per_token == kv
    if "bytes" in c:
        assert params == c["bytes"]["params"]
        assert kv == c["bytes"]["kv_per_token"]
        pool = (c["engine"]["pages"] - 1) * c["engine"]["page_size"] * kv
        assert pool <= c["bytes"]["pool"]


@pytest.mark.parametrize("name", ["smollm-360m", "mha", "tiny"])
def test_param_count_is_the_served_weights(name):
    c = data("tiny.json") if name == "tiny" else config(name)
    m = llama().work(c)
    mats = sum(int(np.prod(s)) for s in llama().shapes(c).values())
    norms = (2 * m.layers + 1) * m.d_model
    assert m.param_bytes == 2 * mats + 4 * norms


def tick(*contexts):
    return harness.Step(0.0, 0.0, list(contexts), [])


def test_decode_tick_counts_only_emitting_lanes():
    m = llama().work(config("smollm-360m"))
    f0, b0 = m.decode_tick(tick())
    assert (f0, b0) == (0, m.param_bytes)      # padded/inactive lanes: nothing
    f1, b1 = m.decode_tick(tick(1000))
    f2, b2 = m.decode_tick(tick(1000, 1000))
    assert f2 - f1 == f1 - f0 and b2 - b1 == b1 - b0
    assert b1 - b0 == 1000 * 40_960
    # 2 x (matmul params incl. the head) + attention over the real context
    mat = 32 * (960 * 960 * 2 + 960 * 320 * 2 + 3 * 960 * 2560) + 49152 * 960
    assert f1 == 2 * mat + 4 * 32 * 15 * 64 * 1000


def test_moe_counts_every_expert_stored_and_the_reached_ones_done():
    """The MoE module's work: every expert and the float32 router stored,
    each token through its ``k`` experts and the router; the parameter
    bytes are the served tree's."""
    import jax
    c = data("tiny-moe.json")
    arch = harness.load_arch(c)
    m, dense = arch.work(c), llama().work(c)
    L, d, f = c["num_hidden_layers"], c["hidden_size"], c["intermediate_size"]
    E, k = c["num_local_experts"], c["num_experts_per_tok"]
    expert = 3 * d * f
    assert m.param_bytes == dense.param_bytes \
        + 4 * L * (E - 1) * expert + 4 * L * d * E     # float32, as stated
    tree = jax.eval_shape(lambda: arch.program_params(arch.make_weights(c, 1)))
    assert m.param_bytes == sum(x.size * x.dtype.itemsize
                                for x in jax.tree.leaves(tree))
    assert m.token_flops(10, True) == dense.token_flops(10, True) \
        + 2 * L * (d * E + (k - 1) * expert)
    assert m.kv_bytes_per_token == dense.kv_bytes_per_token
    f, b = m.decode_tick(tick(10))
    assert f == m.token_flops(10, True)
    assert b == m.param_bytes - 4 * L * (E - k) * expert \
        + 10 * m.kv_bytes_per_token


def test_prefill_counts_each_position_once():
    m = llama().work(config("mha"))
    whole = m.prefill_flops(0, 300, True)
    split = m.prefill_flops(0, 100, False) + m.prefill_flops(100, 200, True)
    assert whole == split
    assert whole == sum(m.token_flops(p + 1, False) for p in range(300)) \
        + 2 * m.head_params


def test_least_time_names_its_bound():
    peak = work.peaks("TPU v5 lite")
    assert work.least_time(1e12, 1e6, peak)[1] == "compute"
    t, bound = work.least_time(1e6, 819e9, peak)
    assert bound == "memory" and t == pytest.approx(1.0)
    with pytest.raises(KeyError):
        work.peaks("TPU v9 imaginary")
