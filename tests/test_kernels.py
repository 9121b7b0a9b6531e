"""Pallas kernel validation: interpret-mode execution vs the pure-jnp
oracles, swept over shapes and dtypes."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ops, ref
from repro.kernels.brsgd_stats import (_BLOCK_BYTES, _VMEM_BUDGET, _tiling,
                                       brsgd_partials_pallas,
                                       brsgd_stats_pallas,
                                       cwise_median_pallas,
                                       fused_stats_pallas,
                                       masked_mean_pallas,
                                       select_mean_pallas,
                                       trimmed_mean_pallas)

SHAPES = [(4, 64), (8, 100), (20, 257), (20, 2048), (32, 5000), (7, 33),
          (64, 128), (3, 1)]
DTYPES = [jnp.float32, jnp.bfloat16]


@pytest.mark.parametrize("m,d", SHAPES)
@pytest.mark.parametrize("dtype", DTYPES)
def test_brsgd_stats_kernel_vs_ref(m, d, dtype):
    rng = np.random.default_rng(m * 1000 + d)
    G = jnp.asarray(rng.normal(size=(m, d)) * 3).astype(dtype)
    med, mean, sc, l1 = brsgd_stats_pallas(G, d_blk=512)
    med_r, mean_r, sc_r, l1_r = ref.brsgd_stats_ref(G)
    tol = 1e-5 if dtype == jnp.float32 else 5e-2
    np.testing.assert_allclose(np.asarray(med), np.asarray(med_r), atol=tol)
    np.testing.assert_allclose(np.asarray(mean), np.asarray(mean_r), atol=tol)
    np.testing.assert_allclose(np.asarray(sc), np.asarray(sc_r), atol=1e-5)
    np.testing.assert_allclose(np.asarray(l1), np.asarray(l1_r),
                               rtol=1e-4, atol=tol * d)


@pytest.mark.parametrize("m,d", SHAPES)
def test_masked_mean_kernel_vs_ref(m, d):
    rng = np.random.default_rng(m + d)
    G = jnp.asarray(rng.normal(size=(m, d)).astype("f4"))
    mask = jnp.asarray(rng.random(m) > 0.4)
    out = masked_mean_pallas(G, mask, d_blk=512)
    np.testing.assert_allclose(np.asarray(out),
                               np.asarray(ref.masked_mean_ref(G, mask)),
                               rtol=1e-5, atol=1e-6)


def test_masked_mean_empty_mask_is_safe():
    G = jnp.ones((4, 10))
    out = masked_mean_pallas(G, jnp.zeros((4,), bool))
    assert bool(jnp.all(jnp.isfinite(out)))


@pytest.mark.parametrize("m", [2, 3, 4, 5, 8, 16, 20, 33, 64])
def test_cwise_median_kernel_odd_even_workers(m):
    rng = np.random.default_rng(m)
    G = jnp.asarray(rng.normal(size=(m, 300)).astype("f4"))
    np.testing.assert_allclose(np.asarray(cwise_median_pallas(G, d_blk=128)),
                               np.median(np.asarray(G), axis=0), atol=1e-6)


def test_kernel_blocking_invariance():
    """Different d_blk tilings give identical results, down to the
    derived block (None: 21,504 columns at m = 12 f32, three blocks and
    a ragged fourth)."""
    rng = np.random.default_rng(7)
    G = jnp.asarray(rng.normal(size=(12, 70_000)).astype("f4"))
    outs = [brsgd_stats_pallas(G, d_blk=b)
            for b in (64, 256, 1000, 4096, 32768, None)]
    for o in outs[1:]:
        for a, b in zip(outs[0], o):
            # different tilings reduce in different orders -> f32 rounding
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=1e-5, atol=1e-5)


def test_ops_wrappers_pallas_matches_jnp_path():
    rng = np.random.default_rng(3)
    G = jnp.asarray(rng.normal(size=(16, 700)).astype("f4"))
    mask = jnp.asarray(rng.random(16) > 0.5)
    for a, b in zip(ops.brsgd_stats(G, use_pallas=True),
                    ops.brsgd_stats(G, use_pallas=False)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-5)
    np.testing.assert_allclose(
        np.asarray(ops.masked_mean(G, mask, use_pallas=True)),
        np.asarray(ops.masked_mean(G, mask, use_pallas=False)), rtol=1e-5)
    np.testing.assert_allclose(
        np.asarray(ops.cwise_median(G, use_pallas=True)),
        np.asarray(ops.cwise_median(G, use_pallas=False)), atol=1e-6)


@pytest.mark.parametrize("B,H,Q,K,wlo", [(2, 3, 8, 8, 0.1),
                                         (1, 2, 32, 16, 0.3),
                                         (2, 1, 64, 64, 0.5),
                                         (1, 1, 16, 32, 0.05)])
def test_wkv6_chunk_kernel_vs_sequential_oracle(B, H, Q, K, wlo):
    """Pallas WKV6 chunk kernel (interpret mode) == per-token recurrence."""
    from repro.kernels.wkv6 import wkv6_chunk_pallas, wkv6_chunk_ref
    rng = np.random.default_rng(B * 100 + Q)
    mk = lambda: jnp.asarray(rng.normal(size=(B, H, Q, K)).astype("f4"))
    r, k, v = mk(), mk(), mk()
    w = jnp.asarray(rng.uniform(wlo, 0.999, size=(B, H, Q, K)).astype("f4"))
    u = jnp.asarray(rng.normal(size=(H, K)).astype("f4"))
    S = jnp.asarray(rng.normal(size=(B, H, K, K)).astype("f4"))
    y1, S1 = wkv6_chunk_pallas(r, k, v, w, u, S)
    y2, S2 = wkv6_chunk_ref(r, k, v, w, u, S)
    scale = max(1.0, float(jnp.abs(y2).max()))
    np.testing.assert_allclose(np.asarray(y1) / scale, np.asarray(y2) / scale,
                               atol=2e-5)
    np.testing.assert_allclose(np.asarray(S1), np.asarray(S2),
                               rtol=1e-3, atol=1e-3)


@pytest.mark.parametrize("B,H,Hkv,S,D,win", [
    (1, 2, 2, 64, 16, 0),      # MHA causal
    (2, 4, 2, 128, 32, 0),     # GQA
    (1, 2, 1, 100, 16, 0),     # ragged S (padding path)
    (1, 2, 2, 256, 16, 64),    # sliding window
    (1, 1, 1, 48, 8, 16),      # small + window
])
def test_flash_attention_kernel_vs_oracle(B, H, Hkv, S, D, win):
    from repro.kernels.flash_attention import (flash_attention,
                                               flash_attention_ref)
    rng = np.random.default_rng(S + D)
    q = jnp.asarray(rng.normal(size=(B, H, S, D)).astype("f4"))
    k = jnp.asarray(rng.normal(size=(B, Hkv, S, D)).astype("f4"))
    v = jnp.asarray(rng.normal(size=(B, Hkv, S, D)).astype("f4"))
    out = flash_attention(q, k, v, window=win, qb=32, kb=32)
    ref = flash_attention_ref(q, k, v, window=win)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-4, atol=2e-5)


def test_flash_attention_bf16_and_blocking_invariance():
    from repro.kernels.flash_attention import (flash_attention,
                                               flash_attention_ref)
    rng = np.random.default_rng(1)
    q = jnp.asarray(rng.normal(size=(1, 2, 64, 16))).astype(jnp.bfloat16)
    k = jnp.asarray(rng.normal(size=(1, 2, 64, 16))).astype(jnp.bfloat16)
    v = jnp.asarray(rng.normal(size=(1, 2, 64, 16))).astype(jnp.bfloat16)
    ref = flash_attention_ref(q, k, v)
    for qb, kb in ((16, 16), (32, 64), (64, 32)):
        out = flash_attention(q, k, v, qb=qb, kb=kb)
        np.testing.assert_allclose(np.asarray(out, np.float32),
                                   np.asarray(ref, np.float32),
                                   rtol=5e-2, atol=5e-2)


@pytest.mark.parametrize("m,d", [(8, 100), (20, 257), (7, 33), (64, 128)])
def test_brsgd_partials_kernel_matches_stats_kernel(m, d):
    """The [d]-output-free partials pass == the full stats pass."""
    rng = np.random.default_rng(m * 7 + d)
    G = jnp.asarray((rng.normal(size=(m, d)) * 2).astype("f4"))
    _, _, sc_full, l1_full = brsgd_stats_pallas(G, d_blk=64)
    sc, l1 = brsgd_partials_pallas(G, d_blk=64)
    np.testing.assert_array_equal(np.asarray(sc), np.asarray(sc_full))
    np.testing.assert_allclose(np.asarray(l1), np.asarray(l1_full),
                               rtol=1e-6, atol=1e-6)


@pytest.mark.parametrize("beta,threshold", [(0.5, 0.0), (0.25, 0.0),
                                            (1.0, 1e9), (0.5, 1e-8)])
def test_select_mean_kernel_matches_two_pass(beta, threshold):
    """Fused select+masked-mean pass == brsgd_select + masked_mean,
    including the empty-C1∩C2 fallback (threshold 1e-8)."""
    from repro.core.engine import brsgd_select
    rng = np.random.default_rng(int(beta * 100))
    G = jnp.asarray(rng.normal(size=(16, 700)).astype("f4"))
    scores, l1 = brsgd_partials_pallas(G, d_blk=256)
    agg, w = select_mean_pallas(G, scores, l1, beta, threshold, d_blk=256)
    st = brsgd_select(scores, l1, beta, threshold)
    np.testing.assert_array_equal(np.asarray(w),
                                  np.asarray(st.selected, np.float32))
    want = masked_mean_pallas(G, st.selected, d_blk=256)
    np.testing.assert_allclose(np.asarray(agg), np.asarray(want),
                               rtol=1e-6, atol=1e-6)


@pytest.mark.parametrize("m,d", [(8, 100), (20, 257), (7, 33), (10, 64)])
@pytest.mark.parametrize("trim_frac", [0.0, 0.1, 0.25, 0.45])
def test_trimmed_mean_kernel_vs_ref(m, d, trim_frac):
    rng = np.random.default_rng(m + d)
    G = jnp.asarray((rng.normal(size=(m, d)) * 3).astype("f4"))
    out = trimmed_mean_pallas(G, trim_frac, d_blk=64)   # forces padding
    np.testing.assert_allclose(np.asarray(out),
                               np.asarray(ref.trimmed_mean_ref(G, trim_frac)),
                               rtol=1e-5, atol=1e-5)


def test_masked_mean_float_weights():
    """The kernel accepts continuous weights (engine weighted combine)."""
    rng = np.random.default_rng(5)
    G = jnp.asarray(rng.normal(size=(6, 90)).astype("f4"))
    w = jnp.asarray(rng.random(6).astype("f4") * 0.2)    # Σw < 1
    out = masked_mean_pallas(G, w, d_blk=32)
    want = (np.asarray(w) @ np.asarray(G)) / np.asarray(w).sum()
    np.testing.assert_allclose(np.asarray(out), want, rtol=1e-5, atol=1e-6)


def test_score_constant_column_counts_everyone():
    """A constant column splits into {all >= mean}: everyone scores 1
    (the ragged-block cases below hold constant columns too)."""
    G = jnp.ones((6, 10))
    _, _, sc, l1 = brsgd_stats_pallas(G, d_blk=4)   # forces padding
    np.testing.assert_array_equal(np.asarray(sc), np.full(6, 10.0))
    np.testing.assert_allclose(np.asarray(l1), np.zeros(6), atol=1e-6)


RAGGED_KERNELS = ("brsgd_stats", "fused_stats", "select_mean", "masked_mean",
                  "cwise_median", "trimmed_mean")


def _kernel_vs_ref(kernel, G, d_blk):
    """[(what, got, want, exact)] of one kernel against the jnp oracle."""
    if kernel == "brsgd_stats":
        got = brsgd_stats_pallas(G, d_blk=d_blk)
        want = ref.brsgd_stats_ref(G)
        return [(n, a, b, n in ("median", "scores")) for n, a, b in
                zip(("median", "mean", "scores", "l1"), got, want)]
    if kernel == "fused_stats":
        got = fused_stats_pallas(G, ref.STAT_NAMES, d_blk=d_blk)
        want = ref.fused_stats_ref(G, ref.STAT_NAMES)
        return [(n, got[n], want[n], n == "scores") for n in ref.STAT_NAMES]
    if kernel == "select_mean":
        from repro.core.engine import brsgd_select
        scores, l1 = brsgd_partials_pallas(G, d_blk=d_blk)
        agg, w = select_mean_pallas(G, scores, l1, 0.5, 0.0, d_blk=d_blk)
        sel = brsgd_select(scores, l1, 0.5, 0.0).selected
        return [("weights", w, sel.astype(jnp.float32), True),
                ("aggregate", agg, ref.masked_mean_ref(G, sel), False)]
    if kernel == "masked_mean":
        mask = jnp.arange(G.shape[0]) % 3 != 1
        return [("mean", masked_mean_pallas(G, mask, d_blk=d_blk),
                 ref.masked_mean_ref(G, mask), False)]
    if kernel == "cwise_median":
        return [("median", cwise_median_pallas(G, d_blk=d_blk),
                 ref.cwise_median_ref(G), True)]
    return [("trimmed", trimmed_mean_pallas(G, 0.25, d_blk=d_blk),
             ref.trimmed_mean_ref(G, 0.25), False)]


def _assert_kernel_matches_ref(kernel, G, d_blk, atol):
    for what, got, want, exact in _kernel_vs_ref(kernel, G, d_blk):
        if exact:
            np.testing.assert_array_equal(np.asarray(got), np.asarray(want),
                                          err_msg=what)
        else:
            np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                       rtol=1e-4, atol=atol, err_msg=what)


# (d_blk, d): just under, at and just over a whole number of blocks, a
# tail of whole lanes, tails of whole and partial chunks (4096: chunks of
# 4,096), and one block wider than G
RAGGED = [(1024, 3071), (1024, 3072), (1024, 3073), (1024, 3200),
          (4096, 8191), (4096, 9692), (None, 1000)]


@pytest.mark.parametrize("d_blk,d", RAGGED)
@pytest.mark.parametrize("m", [5, 8])
@pytest.mark.parametrize("kernel", RAGGED_KERNELS)
def test_kernel_ragged_last_block_vs_ref(kernel, m, d_blk, d):
    """Every kernel that shares ``_tiling`` keeps the garbage past d in
    a ragged last block out of its results: scores, weights and medians
    exact, sums to f32 rounding.  Every seventh column, and the last, is
    constant (all workers tie: everyone scores 1 there)."""
    rng = np.random.default_rng(m * 10_000 + d)
    G = rng.normal(size=(m, d)).astype("f4") * 3
    G[:, ::7] = 1.25
    G[:, -1] = -0.5
    _assert_kernel_matches_ref(kernel, jnp.asarray(G), d_blk, 1e-4)


@pytest.mark.parametrize("d", [65_535, 65_536, 65_537])
@pytest.mark.parametrize("kernel", RAGGED_KERNELS)
def test_kernel_derived_block_ragged_vs_ref(kernel, d):
    """The same at the derived block (32,768 columns at m = 8 f32): d
    just under, at and just over two blocks."""
    rng = np.random.default_rng(d)
    G = rng.normal(size=(8, d)).astype("f4")
    G[:, ::7] = 1.25
    G = jnp.asarray(G)
    assert _tiling(G, None, 1)[:2] == (32_768, -(-d // 32_768))
    _assert_kernel_matches_ref(kernel, G, None, 1e-3)


@pytest.mark.parametrize("m,dtype", [(8, jnp.bfloat16), (8, jnp.float32),
                                     (2, jnp.float32), (64, jnp.float32),
                                     (4, jnp.bfloat16), (20, jnp.bfloat16)])
@pytest.mark.parametrize("row_outs", [0, 1, 2])
def test_tiling_derives_block_from_shape(m, dtype, row_outs):
    """The block follows m, the dtype and the kernel's VMEM need: about
    1 MiB of G per grid step, within the VMEM budget, whole chunks."""
    d = 15_730_944
    G = jax.ShapeDtypeStruct((m, d), dtype)
    block, grid, chunk = _tiling(G, None, row_outs)
    item = jnp.dtype(dtype).itemsize
    rows = -(-m * item // 32) * 32 // item
    assert grid == -(-d // block) and block % chunk == 0
    assert 2 * (rows * item + row_outs * 4) * block <= _VMEM_BUDGET
    assert _BLOCK_BYTES // 4 <= m * item * block <= _BLOCK_BYTES
    if (m, dtype) == (8, jnp.bfloat16):
        assert (block, grid) == (65_536, 241)


@pytest.mark.parametrize("d_blk,d,want", [(64, 3000, (1024, 3)),
                                          (1000, 1000, (1024, 1)),
                                          (4096, 100, (1024, 1)),
                                          (None, 1000, (1024, 1)),
                                          (None, 70_000, (32_768, 3))])
def test_tiling_override_and_single_block(d_blk, d, want):
    """Blocks are whole 1,024-column groups: an override rounds up to
    them, and a leaf narrower than one block is one block of d rounded
    up to them."""
    G = jax.ShapeDtypeStruct((8, d), jnp.float32)
    block, grid, chunk = _tiling(G, d_blk, 1)
    assert (block, grid) == want and block % chunk == 0
