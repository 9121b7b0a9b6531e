"""Fused statistics pipeline: one-sort/one-pass parity with the
per-stat oracles, the counting (rank-select) quantile, and the
shared-row dense attack path.

The contract under test (DESIGN.md §Perf): for ANY subset of
``ref.STAT_NAMES`` the fused pass — jnp reference (one shared bitonic
sorted-rows pass) or Pallas kernel (one HBM read) — produces exactly
the statistics the independent per-stat references produce, including
on N-D worker-axis views (blocked scope keeps the worker axis mid-leaf
and never reshapes across model-sharded dims).
"""
import itertools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.base import ByzantineConfig
from repro.core import engine, threat
from repro.kernels import ops, ref
from repro.kernels.brsgd_stats import fused_stats_pallas

SUBSETS = [tuple(c) for r in range(1, len(ref.STAT_NAMES) + 1)
           for c in itertools.combinations(ref.STAT_NAMES, r)]


def _oracle_stats(G, needs):
    """Independent per-stat references (the pre-fusion implementations)."""
    Gf = np.asarray(G, np.float32)
    med = np.median(Gf, axis=0)
    out = {}
    if "scores" in needs:
        out["scores"] = np.asarray(ref.majority_score_ref(G))
    if "l1" in needs:
        out["l1"] = np.abs(Gf - med).sum(axis=1)
    if "d2med" in needs:
        out["d2med"] = ((Gf - med) ** 2).sum(axis=1)
    if "gram" in needs:
        out["gram"] = Gf @ Gf.T
    return out


@pytest.mark.parametrize("needs", SUBSETS,
                         ids=["+".join(s) for s in SUBSETS])
def test_fused_ref_every_subset_matches_per_stat_oracles(rng, needs):
    m, d = 8, 300
    G = jnp.asarray((rng.normal(size=(m, d)) * 2).astype("f4"))
    got = ref.fused_stats_ref(G, needs)
    want = _oracle_stats(G, needs)
    assert set(got) == set(needs)
    for k in needs:
        np.testing.assert_allclose(np.asarray(got[k]), want[k],
                                   rtol=1e-5, atol=1e-4, err_msg=k)


@pytest.mark.parametrize("needs", SUBSETS,
                         ids=["+".join(s) for s in SUBSETS])
def test_fused_pallas_every_subset_matches_ref(rng, needs):
    """The one-HBM-read kernel == the one-sort reference, through a
    ragged block (1,024 columns holding G's 130: the garbage past d is
    zeroed and masked, so it adds nothing to any statistic)."""
    m, d = 7, 130
    G = jnp.asarray((rng.normal(size=(m, d)) * 3).astype("f4"))
    got = fused_stats_pallas(G, needs, d_blk=64)
    want = ref.fused_stats_ref(G, needs)
    for k in needs:
        np.testing.assert_allclose(np.asarray(got[k]), np.asarray(want[k]),
                                   rtol=1e-4, atol=1e-4, err_msg=k)
    # scores are 0/1 sums: integer-exact through the ragged block
    if "scores" in needs:
        np.testing.assert_array_equal(np.asarray(got["scores"]),
                                      np.asarray(want["scores"]))


@pytest.mark.parametrize("d", [2047, 2048, 2049, 2176, 3000])
def test_fused_pallas_ragged_widths_every_subset(rng, d):
    """Blocks of 1,024: just under, at and just over two blocks, a tail
    of whole lanes, and one of 952 columns; constant columns (every
    worker scores 1 there) among them, the last one included.  Each
    statistic alone and all four together (every subset: above)."""
    m = 8
    G = (rng.normal(size=(m, d)) * 3).astype("f4")
    G[:, ::5] = 0.75
    G[:, -1] = 2.0
    G = jnp.asarray(G)
    for needs in [(n,) for n in ref.STAT_NAMES] + [ref.STAT_NAMES]:
        got = fused_stats_pallas(G, needs, d_blk=1024)
        want = ref.fused_stats_ref(G, needs)
        for k in needs:
            if k == "scores":
                np.testing.assert_array_equal(np.asarray(got[k]),
                                              np.asarray(want[k]))
            else:
                np.testing.assert_allclose(np.asarray(got[k]),
                                           np.asarray(want[k]),
                                           rtol=1e-4, atol=1e-4, err_msg=k)


def test_ops_fused_stats_dispatch_parity(rng):
    G = jnp.asarray(rng.normal(size=(8, 500)).astype("f4"))
    a = ops.fused_stats(G, tuple(ref.STAT_NAMES), use_pallas=True, d_blk=128)
    b = ops.fused_stats(G, tuple(ref.STAT_NAMES), use_pallas=False)
    for k in ref.STAT_NAMES:
        np.testing.assert_allclose(np.asarray(a[k]), np.asarray(b[k]),
                                   rtol=1e-4, atol=1e-4, err_msg=k)


@pytest.mark.parametrize("shape,axis", [((3, 6, 5), 1), ((6, 4), 0),
                                        ((2, 3, 6, 2), 2), ((5, 6), 1)])
def test_fused_stats_nd_worker_axis_views(rng, shape, axis):
    """Blocked-scope worker views: the worker axis sits mid-leaf and the
    non-worker dims are never reshaped — stats must equal the flattened
    worker-major [m, cols] execution."""
    G = jnp.asarray(rng.normal(size=shape).astype("f4"))
    m = shape[axis]
    got = engine.leaf_stats(G, frozenset(ref.STAT_NAMES), m, axis=axis)
    flat = jnp.moveaxis(G, axis, 0).reshape(m, -1)
    want = engine.leaf_stats(flat, frozenset(ref.STAT_NAMES), m)
    for k in ref.STAT_NAMES:
        np.testing.assert_allclose(np.asarray(got[k]), np.asarray(want[k]),
                                   rtol=1e-5, atol=1e-4, err_msg=k)


def test_sorted_worker_rows_matches_sort(rng):
    for m in (2, 3, 4, 7, 8, 20, 33):
        G = jnp.asarray(rng.normal(size=(m, 40)).astype("f4"))
        rows = ref.sorted_worker_rows(G)
        np.testing.assert_array_equal(
            np.stack([np.asarray(r) for r in rows]),
            np.sort(np.asarray(G), axis=0))
        np.testing.assert_array_equal(
            np.asarray(ref.median_from_sorted(rows)),
            np.median(np.asarray(G), axis=0))


# ---------------------------------------------------------------------------
# counting quantile (the O(m) replicated selection)
# ---------------------------------------------------------------------------

def test_rank_select_equals_sort_with_duplicates(rng):
    for m in range(2, 34):
        x = jnp.asarray(rng.integers(0, 4, m).astype("f4"))  # heavy ties
        s = np.sort(np.asarray(x))
        for k in range(m):
            assert float(ref.rank_select(x, k)) == s[k], (m, k)
    e = jnp.full((9,), 2.5)
    assert float(ref.rank_select(e, 4)) == 2.5


def test_counting_quantile_matches_jnp_nearest(rng):
    """The rank-select lower quartile reproduces jnp.quantile(...,
    method='nearest') — including the half-down tie rule at virtual
    index .5 — for every worker count the repo runs."""
    for m in range(2, 66):
        l1 = jnp.asarray(rng.normal(size=m).astype("f4") * 10)
        want = float(jnp.quantile(l1, 0.25, method="nearest"))
        got = float(ref.rank_select(l1, ref.quantile_nearest_index(0.25, m)))
        assert got == want, m


def test_brsgd_thresholds_sort_free_regression(rng):
    """brsgd_thresholds == the seed's jnp.sort/jnp.quantile formulation
    on the same inputs (the selection semantics may never drift)."""
    import math
    for m in (2, 3, 8, 16, 20, 64):
        scores = jnp.asarray(rng.integers(0, 50, m).astype("f4"))
        l1 = jnp.asarray(rng.random(m).astype("f4"))
        for beta in (0.25, 0.5, 1.0):
            kth, T = ref.brsgd_thresholds(scores, l1, beta, 0.0)
            k = max(1, math.ceil(beta * m))
            assert float(kth) == float(jnp.sort(scores)[m - k]), (m, beta)
            assert float(T) == float(jnp.quantile(l1, 0.25,
                                                  method="nearest")), m


# ---------------------------------------------------------------------------
# shared-row dense attacks
# ---------------------------------------------------------------------------

def test_shared_row_attacks_match_general_vmap_path(rng):
    """For worker-independent rules the one-evil-row broadcast must be
    bit-identical to vmapping the rule over all m rows."""
    import dataclasses
    G = jnp.asarray(rng.normal(size=(12, 40)).astype("f4"))
    key = jax.random.PRNGKey(7)
    shared = [n for n in threat.registered()
              if threat.get_spec(n).scope == "gradient"
              and threat.get_spec(n).shared_row]
    assert set(shared) == {"negation", "alie", "ipm"}
    for name in shared:
        cfg = ByzantineConfig(attack=name, alpha=0.25, negation_factor=5.0)
        spec = threat.get_spec(name)
        got = threat.apply_dense(G, key, cfg)
        byz = np.asarray(got[:3])
        np.testing.assert_array_equal(byz[1:], np.tile(byz[:1], (2, 1)))
        threat._REGISTRY[name] = dataclasses.replace(spec, shared_row=False)
        try:
            want = threat.apply_dense(G, key, cfg)
        finally:
            threat._REGISTRY[name] = spec
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def test_shared_row_rejected_for_data_scope():
    with pytest.raises(ValueError):
        threat.AttackSpec("bad", scope="data", shared_row=True,
                          corrupt_labels=lambda y, n: y)


# ---------------------------------------------------------------------------
# benchmark schema guard
# ---------------------------------------------------------------------------

def test_committed_bench_file_passes_check_bench():
    import os
    import sys
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    sys.path.insert(0, os.path.join(repo, "benchmarks"))
    try:
        import check_bench
    finally:
        sys.path.pop(0)
    assert check_bench.check(os.path.join(repo, "BENCH_agg.json")) == []
