"""Pallas kernel validation: shape/dtype sweeps vs the pure-jnp oracles
(interpret=True executes the kernel bodies on CPU)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels.flash_attention import attention_ref, flash_attention
from repro.kernels.grid_step import grid_step, grid_step_ref
from repro.kernels.moe_gmm import gmm_ref, moe_gmm
from repro.kernels.paged_attention import paged_attention, paged_attention_ref

jax.config.update("jax_platform_name", "cpu")


def _paged_fixture(key, b, h, hk, d, num_pages, page, maxp, seed):
    """Random page pools + a valid block table with ragged per-row page counts
    (lengths anywhere in [1, maxp*page], pages covering exactly ceil(len/page))."""
    q = jax.random.normal(key, (b, h, d), jnp.float32)
    kp = jax.random.normal(jax.random.fold_in(key, 1),
                           (num_pages, page, hk, d), jnp.float32)
    vp = jax.random.normal(jax.random.fold_in(key, 2),
                           (num_pages, page, hk, d), jnp.float32)
    rng = np.random.default_rng(seed)
    lengths = rng.integers(1, maxp * page + 1, size=b)
    free = list(rng.permutation(np.arange(1, num_pages)))   # page 0 = null
    table = np.zeros((b, maxp), np.int32)
    for i in range(b):
        for j in range(-(-int(lengths[i]) // page)):
            table[i, j] = free.pop()
    return q, kp, vp, jnp.asarray(table), jnp.asarray(lengths, jnp.int32)


@pytest.mark.parametrize("b,h,hk,d", [
    (3, 4, 4, 32),      # MHA
    (2, 8, 2, 64),      # GQA 4:1
    (2, 8, 1, 128),     # MQA
])
@pytest.mark.parametrize("page,maxp", [(8, 4), (16, 3)])
@pytest.mark.parametrize("seed", [0, 1])
def test_paged_attention_matches_ref(b, h, hk, d, page, maxp, seed):
    """Block-table gather inside the kernel == dense-gather oracle to <= 1e-5,
    across GQA head ratios and ragged page counts."""
    key = jax.random.PRNGKey(seed)
    q, kp, vp, table, lengths = _paged_fixture(
        key, b, h, hk, d, num_pages=b * maxp + 1, page=page, maxp=maxp,
        seed=seed)
    out = paged_attention(q, kp, vp, table, lengths, interpret=True)
    ref = paged_attention_ref(q, kp, vp, table, lengths)
    np.testing.assert_allclose(out, ref, rtol=1e-5, atol=1e-5)


def test_paged_attention_bf16(bdims=(2, 8, 2, 64)):
    b, h, hk, d = bdims
    key = jax.random.PRNGKey(3)
    q, kp, vp, table, lengths = _paged_fixture(
        key, b, h, hk, d, num_pages=b * 4 + 1, page=8, maxp=4, seed=3)
    q, kp, vp = (x.astype(jnp.bfloat16) for x in (q, kp, vp))
    out = paged_attention(q, kp, vp, table, lengths, interpret=True)
    ref = paged_attention_ref(q, kp, vp, table, lengths)
    np.testing.assert_allclose(out.astype(jnp.float32),
                               ref.astype(jnp.float32), rtol=5e-2, atol=5e-2)


def test_paged_attention_ignores_dirty_null_page():
    """Unmapped table entries point at page 0; its contents must never leak
    into the output (the engine uses it as the write trash can)."""
    b, h, hk, d, page, maxp = 2, 4, 2, 32, 8, 3
    key = jax.random.PRNGKey(5)
    q, kp, vp, table, lengths = _paged_fixture(
        key, b, h, hk, d, num_pages=b * maxp + 1, page=page, maxp=maxp, seed=5)
    clean = paged_attention(q, kp, vp, table, lengths, interpret=True)
    dirty_k = kp.at[0].set(1e4)
    dirty_v = vp.at[0].set(-1e4)
    dirty = paged_attention(q, dirty_k, dirty_v, table, lengths, interpret=True)
    np.testing.assert_array_equal(np.asarray(clean), np.asarray(dirty))


@pytest.mark.parametrize("b,h,hk,s,d", [
    (2, 4, 2, 256, 64),     # GQA
    (1, 8, 1, 512, 128),    # MQA, larger head_dim
    (2, 4, 4, 128, 32),     # MHA, small
    (1, 2, 2, 384, 64),     # non-power-of-two kv blocks (384 = 3*128)
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("causal", [True, False])
def test_flash_attention_matches_ref(b, h, hk, s, d, dtype, causal):
    key = jax.random.PRNGKey(0)
    q = jax.random.normal(key, (b, h, s, d), dtype)
    k = jax.random.normal(jax.random.fold_in(key, 1), (b, hk, s, d), dtype)
    v = jax.random.normal(jax.random.fold_in(key, 2), (b, hk, s, d), dtype)
    out = flash_attention(q, k, v, causal=causal, bq=64, bkv=128, interpret=True)
    ref = attention_ref(q, k, v, causal=causal)
    tol = 5e-2 if dtype == jnp.bfloat16 else 5e-5
    np.testing.assert_allclose(out.astype(jnp.float32),
                               ref.astype(jnp.float32), rtol=tol, atol=tol)


@pytest.mark.parametrize("e,c,d,f", [(8, 64, 32, 64), (4, 128, 128, 256),
                                     (6, 32, 64, 32), (3, 96, 96, 96)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_moe_gmm_matches_ref(e, c, d, f, dtype):
    key = jax.random.PRNGKey(1)
    x = jax.random.normal(key, (e, c, d), dtype)
    w = jax.random.normal(jax.random.fold_in(key, 1), (e, d, f), dtype)
    sizes = jax.random.randint(jax.random.fold_in(key, 2), (e,), 0, c + 1)
    xm = jnp.where(jnp.arange(c)[None, :, None] < sizes[:, None, None], x, 0)
    out = moe_gmm(xm, w, sizes, bc=32, bf=32, bd=32, interpret=True)
    ref = gmm_ref(xm, w, sizes)
    tol = 2e-1 if dtype == jnp.bfloat16 else 2e-4
    np.testing.assert_allclose(out.astype(jnp.float32),
                               ref.astype(jnp.float32), rtol=tol, atol=tol)


def test_moe_gmm_empty_groups_are_zero():
    e, c, d, f = 4, 32, 16, 16
    x = jnp.ones((e, c, d))
    w = jnp.ones((e, d, f))
    sizes = jnp.asarray([0, 32, 0, 16])
    out = moe_gmm(x * (jnp.arange(c)[None, :, None] < sizes[:, None, None]),
                  w, sizes, bc=16, bf=16, bd=16, interpret=True)
    assert float(jnp.abs(out[0]).max()) == 0.0
    assert float(jnp.abs(out[2]).max()) == 0.0
    assert float(jnp.abs(out[1]).max()) > 0.0


@pytest.mark.parametrize("h,w,band", [(16, 32, 8), (40, 32, 8), (33, 16, 8),
                                      (64, 128, 16), (8, 256, 8)])
def test_grid_step_matches_ref(h, w, band):
    key = jax.random.PRNGKey(2)
    lab = jax.random.randint(key, (h, w), 0, 50, jnp.int32)
    cond = (jax.random.uniform(jax.random.fold_in(key, 1), (h, w)) < 0.6) \
        .astype(jnp.int32)
    lab = lab * cond
    out = grid_step(lab, cond, band=band, interpret=True)
    ref = grid_step_ref(lab, cond)
    assert bool(jnp.all(out == ref))


def test_grid_step_reaches_fixpoint_like_components():
    """Iterating the kernel floods each conductor component with its max label."""
    cond = jnp.zeros((16, 16), jnp.int32).at[2, 2:10].set(1).at[8:14, 5].set(1)
    lab = jnp.zeros((16, 16), jnp.int32).at[2, 3].set(7).at[10, 5].set(9)
    for _ in range(20):
        lab = grid_step(lab, cond, interpret=True)
    assert bool(jnp.all(jnp.where(cond.at[8:14, 5].set(0) == 1, lab == 7, True)))
    assert bool(jnp.all(jnp.where(jnp.zeros_like(cond).at[8:14, 5].set(1) == 1,
                                  lab == 9, True)))


@pytest.mark.parametrize("kernel", ["paged_attention", "flash_attention",
                                    "moe_gmm", "grid_step"])
def test_kernels_compile_by_default_and_refuse_off_tpu(kernel):
    """No wrapper picks the interpreter by itself: without ``interpret=True``
    each one compiles its kernel, which off a TPU raises instead of silently
    interpreting."""
    key = jax.random.PRNGKey(0)
    calls = {
        "paged_attention": lambda: paged_attention(
            *_paged_fixture(key, 2, 4, 2, 32, num_pages=9, page=8, maxp=4,
                            seed=0)),
        "flash_attention": lambda: flash_attention(
            *(jax.random.normal(key, (1, 2, 128, 64)),) * 3),
        "moe_gmm": lambda: moe_gmm(jnp.ones((2, 32, 32)), jnp.ones((2, 32, 32)),
                                   bc=32, bf=32, bd=32),
        "grid_step": lambda: grid_step(jnp.ones((16, 32), jnp.int32),
                                       jnp.ones((16, 32), jnp.int32)),
    }
    with pytest.raises(ValueError, match="interpret mode"):
        jax.block_until_ready(calls[kernel]())
