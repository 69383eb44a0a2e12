"""Compiles for a described TPU v5e chip at smollm-360m's published widths.

The TPU compiler is installed with jax even where no chip is attached: these
tests hand it shapes (never arrays) on a described ``v5e:2x2`` topology and
fail where the chip's compiler would refuse the program. The decode tick and
the prefill chunk run at full width with the depth cut to two layers (a
scan over layers compiles one layer body either way). The two paged Pallas
kernels are refused today: their K/V block ``(1, page, 1, D)`` over
``(P, page, Hkv, D)`` pages breaks the (8, 128) tiling rule. They are strict
expected failures, so the layout fix has to flip them.

The topology is described inside module fixtures, never at import: only the
test worker that runs this file may load the TPU library.
"""
import dataclasses
import os
from functools import partial

import jax
import jax.numpy as jnp
import pytest

from repro import configs
from repro.models import model
from repro.serve import decode
from repro.serve import engine as eng_mod

SLOTS, MAX_CACHE, PAGE, CHUNK = 16, 2048, 16, 128
TILING_REFUSAL = ("K/V block (1, page, 1, D) over (P, page, Hkv, D) pages "
                  "breaks the TPU (8, 128) block tiling rule")


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    from jax.sharding import SingleDeviceSharding
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def cfg():
    return dataclasses.replace(configs.get_config("smollm-360m"), num_layers=2)


@pytest.fixture(scope="module")
def shapes(cfg, one_chip):
    """Engine state shapes: params, a fully provisioned page pool, and the
    per-tick slot arrays, each placed on the described chip."""
    def on_chip(tree):
        return jax.tree.map(lambda x: jax.ShapeDtypeStruct(
            x.shape, x.dtype, sharding=one_chip), tree)

    num_pages = SLOTS * (MAX_CACHE // PAGE) + 1
    return {
        "params": on_chip(jax.eval_shape(
            lambda: model.init_params(jax.random.PRNGKey(0), cfg))),
        "pool": on_chip(jax.eval_shape(lambda: model.init_slot_cache_paged(
            cfg, SLOTS, MAX_CACHE, num_pages, PAGE))),
        "spec": on_chip(jax.eval_shape(lambda: decode.null_spec(SLOTS))),
        "i32": lambda *s: jax.ShapeDtypeStruct(s, jnp.int32,
                                               sharding=one_chip),
        "bool": lambda *s: jax.ShapeDtypeStruct(s, jnp.bool_,
                                                sharding=one_chip),
    }


def test_decode_tick_compiles(cfg, shapes):
    s = shapes
    compiled = eng_mod._decode_tick.lower(
        s["params"], cfg, s["pool"], s["i32"](SLOTS, 1), s["bool"](SLOTS),
        s["i32"](SLOTS, MAX_CACHE // PAGE), None, None, s["spec"],
        s["i32"](SLOTS), None, return_logits=True).compile()
    assert compiled.memory_analysis().temp_size_in_bytes > 0


def test_prefill_chunk_compiles(cfg, shapes):
    s = shapes
    compiled = eng_mod._prefill_chunk.lower(
        s["params"], cfg, {"tokens": s["i32"](1, CHUNK)}, s["pool"],
        s["i32"](MAX_CACHE // PAGE), s["i32"](), s["i32"](), s["i32"](),
        None).compile()
    assert compiled.memory_analysis().temp_size_in_bytes > 0


def _paged_kernel_shapes(one_chip, sq=None):
    """Real-width paged-kernel operands: 32 lanes, 15 query / 5 KV heads of
    64, 16-token pages, 256 pages per lane, bf16."""
    b, h, hk, d, maxp = 32, 15, 5, 64, 256
    q_shape = (b, h, d) if sq is None else (b, sq, h, d)
    pages = (b * maxp + 1, PAGE, hk, d)
    sds = partial(jax.ShapeDtypeStruct, sharding=one_chip)
    return (sds(q_shape, jnp.bfloat16), sds(pages, jnp.bfloat16),
            sds(pages, jnp.bfloat16), sds((b, maxp), jnp.int32),
            sds((b,), jnp.int32))


@pytest.mark.xfail(strict=True, raises=ValueError, reason=TILING_REFUSAL)
def test_paged_attention_compiles(one_chip):
    from repro.kernels.paged_attention import paged_attention
    jax.jit(paged_attention).lower(*_paged_kernel_shapes(one_chip)).compile()


@pytest.mark.xfail(strict=True, raises=ValueError, reason=TILING_REFUSAL)
def test_paged_attention_verify_compiles(one_chip):
    from repro.kernels.paged_attention import paged_attention_verify
    jax.jit(paged_attention_verify).lower(
        *_paged_kernel_shapes(one_chip, sq=4)).compile()
