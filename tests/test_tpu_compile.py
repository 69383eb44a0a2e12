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

Two more compiles hold the page pool's storage: at the benchmark cell's
engine sizes (32 slots x 2048 tokens, 4,097 pages of 16) the decode tick and
the prefill chunk read each layer's pool in place and write it once, after the
layer scan, into the donated pool — so no instruction copies or updates an
array as large as one layer's pool, and the program's scratch memory stays
below the pool's size. Those compiles take four layers: with two, the whole
pool fits the chip's fast memory and the compiler stages it there and back.
"""
import dataclasses
import os
import re
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


def _engine_shapes(cfg, one_chip, slots, num_pages):
    """Engine state shapes: params, a page pool, and the per-tick slot
    arrays, each placed on the described chip."""
    def on_chip(tree):
        return jax.tree.map(lambda x: jax.ShapeDtypeStruct(
            x.shape, x.dtype, sharding=one_chip), tree)

    return {
        "params": on_chip(jax.eval_shape(
            lambda: model.init_params(jax.random.PRNGKey(0), cfg))),
        "pool": on_chip(jax.eval_shape(lambda: model.init_slot_cache_paged(
            cfg, slots, MAX_CACHE, num_pages, PAGE))),
        "spec": on_chip(jax.eval_shape(lambda: decode.null_spec(slots))),
        "i32": lambda *s: jax.ShapeDtypeStruct(s, jnp.int32,
                                               sharding=one_chip),
        "bool": lambda *s: jax.ShapeDtypeStruct(s, jnp.bool_,
                                                sharding=one_chip),
    }


@pytest.fixture(scope="module")
def shapes(cfg, one_chip):
    """A fully provisioned pool over ``SLOTS`` slots."""
    return _engine_shapes(cfg, one_chip, SLOTS, SLOTS * (MAX_CACHE // PAGE) + 1)


def _compile_decode_tick(cfg, s, slots, **static):
    return eng_mod._decode_tick.lower(
        s["params"], cfg, s["pool"], s["i32"](slots, 1), s["bool"](slots),
        s["i32"](slots, MAX_CACHE // PAGE), None, None, s["spec"],
        s["i32"](slots), None, **static).compile()


def _compile_prefill_chunk(cfg, s, chunk):
    return eng_mod._prefill_chunk.lower(
        s["params"], cfg, {"tokens": s["i32"](1, chunk)}, s["pool"],
        s["i32"](MAX_CACHE // PAGE), s["i32"](), s["i32"](), s["i32"](),
        None).compile()


def test_decode_tick_compiles(cfg, shapes):
    compiled = _compile_decode_tick(cfg, shapes, SLOTS, return_logits=True)
    assert compiled.memory_analysis().temp_size_in_bytes > 0


def test_prefill_chunk_compiles(cfg, shapes):
    compiled = _compile_prefill_chunk(cfg, shapes, CHUNK)
    assert compiled.memory_analysis().temp_size_in_bytes > 0


# -- the page pool is read in place and written once -------------------------
CELL_SLOTS, CELL_PAGES, CELL_CHUNK, CELL_LAYERS = 32, 4097, 256, 4
_BYTES = {"pred": 1, "s8": 1, "u8": 1, "bf16": 2, "f16": 2, "s16": 2,
          "f32": 4, "s32": 4, "u32": 4, "f64": 8, "s64": 8}
_INSTR = re.compile(r"\s*(?:ROOT )?%(\S+) = (\w+)\[([\d,]*)\]\S* ([\w-]+)\(")
_MOVES = ("copy", "copy-done", "dynamic-slice", "dynamic-update-slice")


def _pool_moves(hlo: str, num_pages: int, min_bytes: int) -> list[str]:
    """Instructions of the optimized HLO that copy, slice out or update a
    page pool: an array with a dim of ``num_pages`` pages (or a multiple, a
    stack of layers flattened) of at least ``min_bytes``. Plain instructions,
    and fusions by the op at their root; instructions inside a fusion are not
    buffers of their own and are judged through the fusion."""
    comps, roots, fused, cur = {}, {}, set(), None
    for line in hlo.splitlines():
        if line and not line[0].isspace() and line.rstrip().endswith("{"):
            cur = line.split()[1 if line.startswith("ENTRY") else 0]
            comps[cur.lstrip("%")] = []
            continue
        m = _INSTR.match(line)
        if cur is None or not m:
            continue
        name, dt, dims, op = m.groups()
        if line.lstrip().startswith("ROOT"):
            roots[cur.lstrip("%")] = op
        callee = re.search(r"calls=%?([\w.\-]+)", line) if op == "fusion" \
            else None
        if callee:
            fused.add(callee.group(1))
        dims = [int(d) for d in filter(None, dims.split(","))]
        if not any(d % num_pages == 0 for d in dims):
            continue
        n = 1
        for d in dims:
            n *= d
        comps[cur.lstrip("%")].append(
            (name, op, n * _BYTES.get(dt, 4), callee and callee.group(1)))
    return [f"{name}: {callee or op} {nbytes} B"
            for comp, instrs in comps.items() if comp not in fused
            for name, op, nbytes, callee in instrs
            if nbytes >= min_bytes
            and (roots.get(callee) if callee else op) in _MOVES]


@pytest.fixture(scope="module")
def cell_cfg():
    return dataclasses.replace(configs.get_config("smollm-360m"),
                               num_layers=CELL_LAYERS)


@pytest.fixture(scope="module")
def cell_shapes(cell_cfg, one_chip):
    return _engine_shapes(cell_cfg, one_chip, CELL_SLOTS, CELL_PAGES)


def _pool_bytes(s):
    """(one layer's K pool, the whole pool) in bytes."""
    leaves = jax.tree.leaves(s["pool"]["layers"])
    layer = leaves[0].size // leaves[0].shape[0] * leaves[0].dtype.itemsize
    return layer, sum(x.size * x.dtype.itemsize for x in leaves)


@pytest.mark.parametrize("step", ["decode_tick", "prefill_chunk"])
def test_pool_written_in_place(cell_cfg, cell_shapes, step):
    """No pool-sized copy or update, and less scratch than the pool: each
    layer reads its pool as it lies, and one row write after the layer scan
    lands every layer's new K/V in the donated pool."""
    if step == "decode_tick":
        compiled = _compile_decode_tick(cell_cfg, cell_shapes, CELL_SLOTS)
    else:
        compiled = _compile_prefill_chunk(cell_cfg, cell_shapes, CELL_CHUNK)
    layer, pool = _pool_bytes(cell_shapes)
    assert _pool_moves(compiled.as_text(), CELL_PAGES, layer) == []
    assert compiled.memory_analysis().temp_size_in_bytes < pool


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
