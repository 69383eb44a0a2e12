"""The paged KV pool's storage: for each KV head, one contiguous row of
page*D values per page, (page, D) row-major inside it — (Hkv, P, page*D).

Every reader and writer addresses the same logical (page, offset, head, dim)
it did when the pool was stored as (P, page, Hkv, D). These tests pin that:
``write_pages`` (the one write after a layer scan) against per-position
writes on the page view; the gather with the new positions laid in against
write-then-gather; a decode tick and then a prefill chunk reading back, page
by page, what a dense slot-row cache holds at the same positions; and the
copy-on-write fork, the warm-state export/import and the Pallas kernels'
(P, page, Hkv, D) view each round-tripping pages bitwise.
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import configs
from repro.models import layers, model
from repro.serve import engine as eng_mod
from repro.serve.api import SamplingParams, ServeRequest

jax.config.update("jax_platform_name", "cpu")

L, P, PAGE, HK, HD = 2, 13, 4, 2, 3
TABLE = np.array([[1, 2, 3, 4], [5, 6, 7, 0], [8, 9, 10, 11]], np.int32)
MAXP = TABLE.shape[1]


def _cfg():
    """A config whose K/V heads match the hand-made pools below."""
    return dataclasses.replace(configs.get_config("smollm-360m").smoke(),
                               num_kv_heads=HK, head_dim=HD)


@pytest.fixture(scope="module")
def dense():
    cfg = configs.get_config("smollm-360m").smoke()
    return cfg, model.init_params(jax.random.PRNGKey(0), cfg)


def _rows(view):
    """(..., P, page, Hkv, D) page view -> the (..., Hkv, P, page*D) rows."""
    view = np.moveaxis(view, -2, -4)
    return view.reshape(*view.shape[:-2], -1)


def _reference_writes(pool, new, p0, active):
    """Per-position writes on the (L, P, page, Hkv, D) view — the pool's
    semantics before it was stored as rows: lane b's row j lands at its
    table's page for position p0[b]+j (the null page where the table has
    none); inactive lanes and positions past the table write nothing."""
    view = np.array(layers.page_view(jnp.asarray(pool), _cfg()))
    for b in range(new.shape[1]):
        for j in range(new.shape[2]):
            pos = p0[b] + j
            if active[b] and pos < MAXP * PAGE:
                view[:, TABLE[b, pos // PAGE], pos % PAGE] = new[:, b, j]
    return view


@pytest.mark.parametrize("t", [1, 3, 8, 11])
@pytest.mark.parametrize("active", [(True, True, True), (True, False, True)])
def test_write_pages_matches_per_position_writes(t, active):
    """Aligned and unaligned spans, a span running past the table, a lane
    whose table ends in the null page, an inactive lane."""
    rng = np.random.default_rng(t)
    pool = rng.standard_normal((L, HK, P, PAGE * HD)).astype(np.float32)
    new = rng.standard_normal((L, 3, t, HK, HD)).astype(np.float32)
    p0 = np.array([2, 4, 14], np.int32)
    got = layers.write_pages(jnp.asarray(pool), jnp.asarray(new),
                             jnp.asarray(TABLE), jnp.asarray(p0),
                             jnp.asarray(active))
    want = _reference_writes(pool, new, p0, active)
    # page 0 is the null page: it absorbs don't-care writes in any order
    np.testing.assert_array_equal(
        np.asarray(layers.page_view(got, _cfg()))[:, 1:], want[:, 1:])


@pytest.mark.parametrize("t", [1, 5])
def test_overlay_equals_write_then_gather(t):
    """What a layer attends over: the pages gathered with its own new
    positions laid in is the tensor write-then-gather builds."""
    rng = np.random.default_rng(7 + t)
    pool = rng.standard_normal((L, HK, P, PAGE * HD)).astype(np.float32)
    new = rng.standard_normal((L, 3, t, HK, HD)).astype(np.float32)
    p0 = np.array([2, 4, 9], np.int32)
    written = _reference_writes(pool, new, p0, (True, True, True))
    cache = {"k": jnp.asarray(pool), "v": jnp.asarray(pool)}
    for layer in range(L):
        gk, _ = layers._paged_kv({**cache, "layer": jnp.asarray(layer)},
                                 jnp.asarray(TABLE), jnp.asarray(new[layer]),
                                 jnp.asarray(new[layer]), jnp.asarray(p0),
                                 _cfg())
        want = written[layer][TABLE].reshape(3, -1, HK, HD)
        np.testing.assert_array_equal(np.asarray(gk), want)


def test_decode_tick_then_prefill_chunk_reads_back_dense_rows(dense):
    """Slot 0 holds a spliced prompt and decodes one token; slot 1 then
    prefills a whole-context prompt as one chunk. Page by page, each slot's
    pages hold what a dense slot-row cache holds at the same positions."""
    cfg, params = dense
    max_cache, page, plen = 32, 4, 10
    maxp = max_cache // page
    rng = np.random.default_rng(0)
    prompt_a = rng.integers(0, cfg.vocab_size, (1, plen)).astype(np.int32)
    prompt_b = rng.integers(0, cfg.vocab_size, (1, max_cache)).astype(np.int32)
    table = np.zeros((2, maxp), np.int32)
    table[0, :3] = [4, 2, 7]                  # slot 0: 12 positions
    table[1] = np.arange(9, 9 + maxp)         # slot 1: the whole context
    pool = model.init_slot_cache_paged(cfg, 2, max_cache, 9 + maxp, page)

    # dense slot rows: slot 0 prefilled then decoded at its own depth
    _, one_a = model.prefill(params, cfg, {"tokens": prompt_a},
                             model.init_cache(cfg, 1, max_cache))
    rows = model.init_cache(cfg, 2, max_cache)
    rows = {"layers": jax.tree.map(lambda full, a: full.at[:, 0].set(a[:, 0]),
                                   rows["layers"], one_a["layers"]),
            "pos": jnp.asarray([plen, 0], jnp.int32)}
    tok = jnp.asarray([[3], [5]], jnp.int32)
    lg_rows, rows = model.decode_step(params, cfg, {"token": tok}, rows)

    # the paged pool: splice slot 0, one decode tick, then slot 1's chunk
    pool = model.insert_slot_cache_paged(pool, one_a, cfg, jnp.asarray(0),
                                         jnp.asarray(table[0]))
    pool = {"layers": pool["layers"], "pos": jnp.asarray([plen, 0], jnp.int32)}
    active = jnp.asarray([True, False])
    lg_pool, pool = model.decode_step(params, cfg, {"token": tok}, pool,
                                      table=jnp.asarray(table), active=active)
    np.testing.assert_array_equal(np.asarray(lg_pool)[0],
                                  np.asarray(lg_rows)[0])
    _, pool = model.prefill_chunk(
        params, cfg, {"tokens": jnp.asarray(prompt_b)}, pool,
        jnp.asarray(table[1]), jnp.asarray(0, jnp.int32),
        jnp.asarray(max_cache - 1, jnp.int32), jnp.asarray(1, jnp.int32))
    _, one_b = model.prefill(params, cfg, {"tokens": jnp.asarray(prompt_b)},
                             model.init_cache(cfg, 1, max_cache))

    for n in ("k", "v"):
        view = np.asarray(layers.page_view(pool["layers"][0][0][n], cfg))
        slot0 = view[:, table[0]].reshape(cfg.num_layers, -1, *view.shape[3:])
        np.testing.assert_array_equal(
            slot0[:, :plen + 1],
            np.asarray(rows["layers"][0][0][n])[:, 0, :plen + 1])
        slot1 = view[:, table[1]].reshape(cfg.num_layers, -1, *view.shape[3:])
        np.testing.assert_array_equal(
            slot1, np.asarray(one_b["layers"][0][0][n])[:, 0])


def test_copy_on_write_fork_copies_one_page(dense):
    cfg, _ = dense
    pool = model.init_slot_cache_paged(cfg, 2, 32, 9, 4)
    rng = np.random.default_rng(1)
    pool = {"layers": jax.tree.map(
                lambda a: jnp.asarray(rng.standard_normal(a.shape), a.dtype),
                pool["layers"]), "pos": pool["pos"]}
    forked = model.copy_page_paged(pool, cfg, jnp.asarray(3), jnp.asarray(6))
    for n in ("k", "v"):
        before = np.asarray(layers.page_view(pool["layers"][0][0][n], cfg))
        after = np.asarray(layers.page_view(forked["layers"][0][0][n], cfg))
        np.testing.assert_array_equal(after[:, 6], before[:, 3])
        keep = [p for p in range(9) if p != 6]
        np.testing.assert_array_equal(after[:, keep], before[:, keep])


def _paths(forest):
    """Each exported forest entry's full token path -> its physical page."""
    paths = []
    for e in forest:
        pre = paths[e["parent"]] if e["parent"] >= 0 else ()
        paths.append(pre + tuple(e["tokens"]))
    return {p: e["page"] for p, e in zip(paths, forest)}


def test_warm_state_export_import_round_trips_pages(dense):
    cfg, params = dense
    ecfg = eng_mod.EngineConfig(num_slots=2, max_cache=64, page_size=4,
                                prefill_chunk=8, pin_pages=16)
    eng = eng_mod.Engine(params, cfg, ecfg)
    prefix = np.arange(3, 17, dtype=np.int32)
    eng.run([ServeRequest(rid=i, tokens=np.concatenate([prefix, [40 + i]]),
                          params=SamplingParams(max_new_tokens=3), arrival=i)
             for i in range(3)])
    old = _paths(eng.alloc.export_pinned())
    meta, kv = eng.export_warm_state()
    assert old and kv[0].shape == (cfg.num_layers, 4, cfg.num_kv_heads,
                                   cfg.head_dim)
    fresh = eng_mod.Engine(params, cfg, ecfg)
    assert fresh.import_warm_state(meta, kv) == len(old)
    new = _paths(fresh.alloc.export_pinned())
    assert new.keys() == old.keys()
    for path, page in old.items():
        saved = eng._gather_page_kv(page)
        assert any(np.abs(a).sum() > 0 for a in saved)
        for a, b in zip(saved, fresh._gather_page_kv(new[path])):
            np.testing.assert_array_equal(a, b)


def test_pallas_view_round_trips_pages():
    """The Pallas kernels read the (P, page, Hkv, D) view of the row pool:
    the view is the page-major pool the rows were cut from, and the kernel
    reads the same values through it."""
    from repro.kernels.paged_attention import paged_attention
    cfg = _cfg()
    rng = np.random.default_rng(2)
    paged = rng.standard_normal((2, P, PAGE, HK, HD)).astype(np.float32)
    rows = jnp.asarray(_rows(paged))
    np.testing.assert_array_equal(np.asarray(layers.page_view(rows, cfg)),
                                  paged)
    q = jnp.asarray(rng.standard_normal((3, 2 * HK, HD)), jnp.float32)
    lengths = jnp.asarray([5, 9, 16], jnp.int32)
    via_view = paged_attention(q, layers.page_view(rows[0], cfg),
                               layers.page_view(rows[1], cfg),
                               jnp.asarray(TABLE), lengths, interpret=True)
    direct = paged_attention(q, jnp.asarray(paged[0]), jnp.asarray(paged[1]),
                             jnp.asarray(TABLE), lengths, interpret=True)
    np.testing.assert_array_equal(np.asarray(via_view), np.asarray(direct))
