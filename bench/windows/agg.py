"""Aggregation-round window: the master's robust aggregation of m worker
gradients of a whole model.  One round is one jitted
``core.engine.aggregate_local(G, <aggregator>)`` over the worker-major
matrix G [m, d], d the configuration's parameter count.

Set-up makes G on the device from the seed: a shared direction plus
per-worker noise for the honest rows, N(0, std²) for the ⌊αm⌋ Byzantine
rows (the first ones, the paper's gaussian attack).  Every value is put
on a 2⁻⁵ grid (honest |g| < 8, Byzantine |g| < 4096), so it is exact in
bfloat16 and every sum of at most m = 8 values is exact in float32 in
any order: a selection or aggregate that differs from the reference's is
a fault, never a rounding tie.

Every round's selection, and the whole aggregate of the last round and
of one round drawn from the seed, are compared with the plain float32
BrSGD of ``reference/brsgd.py``.
"""
from __future__ import annotations

import time

import numpy as np

from bench import common, counts
from bench.reference import brsgd as ref

GRID = 32.0
HONEST_MAX = 255.0 / GRID
BYZ_MAX = 4096.0
TILE = 2048
SPANS = ("dispatch", "sync")
SAMPLE_FROM = 16       # the round whose aggregate is kept is drawn from 0..15


def columns(cfg: dict, tr: dict) -> int:
    """d, the columns of G: the whole model's parameters, or
    (``"columns": "layer_bucket"``) every parameter of one decoder layer,
    norms included: the bucket the program's blocked scope aggregates."""
    kind = tr.get("columns", "model")
    if kind == "model":
        return counts.n_params(cfg)
    if kind == "layer_bucket":
        return counts.layer_matmul_params(cfg) + counts.layer_norm_params(cfg)
    raise ValueError(f"unknown columns {kind!r}")


def chunk_columns(d: int, max_cols: int = 1 << 22) -> int:
    """Columns generated per loop step: the largest whole number of tiles
    that divides d and is at most ``max_cols`` (d itself when small)."""
    if d <= max_cols or d % TILE:
        return d
    tiles = d // TILE
    best = 1
    for t in range(1, max_cols // TILE + 1):
        if tiles % t == 0:
            best = t
    return best * TILE


def make_G(key, m: int, n_byz: int, d: int, std: float, dtype):
    """G [m, d] in ``dtype``, made on the device in one jitted call."""
    import jax
    import jax.numpy as jnp
    chunk = chunk_columns(d)

    @jax.jit
    def gen(key):
        def body(c, G):
            k1, k2, k3 = jax.random.split(jax.random.fold_in(key, c), 3)
            shared = jax.random.normal(k1, (1, chunk), jnp.float32)
            honest = shared + 0.1 * jax.random.normal(k2, (m - n_byz, chunk),
                                                      jnp.float32)
            honest = jnp.clip(jnp.round(honest * GRID) / GRID,
                              -HONEST_MAX, HONEST_MAX)
            byz = jax.random.normal(k3, (n_byz, chunk), jnp.float32) * std
            byz = jnp.clip(jnp.round(byz * GRID) / GRID, -BYZ_MAX, BYZ_MAX)
            rows = jnp.concatenate([byz, honest]).astype(dtype)
            return jax.lax.dynamic_update_slice_in_dim(G, rows, c * chunk, axis=1)

        return jax.lax.fori_loop(0, d // chunk, body, jnp.zeros((m, d), dtype))

    return gen(key)


def byz_config(tr: dict):
    common.program_on_path()
    from repro.configs import ByzantineConfig
    return ByzantineConfig(aggregator=tr["aggregator"], attack=tr["attack"],
                           alpha=tr["alpha"], beta=tr["beta"],
                           gaussian_std=tr["attack_std"])


def entry_for(tr: dict):
    """The jitted round the window drives: (aggregate [d], selected [m])."""
    common.program_on_path()
    import jax
    from repro.core import engine
    bcfg = byz_config(tr)

    @jax.jit
    def round_(G):
        agg, st = engine.aggregate_local(G, bcfg, return_state=True)
        return agg, st.selected

    return round_


def setup(ctx: dict) -> dict:
    import jax.numpy as jnp
    cfg, tr, seed = ctx["config"], ctx["traffic"], ctx["seed"]
    m, d = tr["m"], columns(cfg, tr)
    n_byz = int(tr["alpha"] * m)
    G = make_G(common.prng_key(seed, 1), m, n_byz, d, tr["attack_std"],
               jnp.dtype(tr["dtype"]))
    entry = entry_for(tr)
    out, sel = entry(G)                       # compiles: set-up
    out.block_until_ready()
    del out, sel
    return {"G": G, "entry": entry, "m": m, "d": d, "n_byz": n_byz, "tr": tr,
            "sample_at": int(common.np_rng(seed, 4).integers(0, SAMPLE_FROM))}


def measure(state: dict, seconds: float) -> dict:
    G, entry = state["G"], state["entry"]
    sample_at = state["sample_at"]
    sels, sample = [], None
    out = None
    t0 = time.perf_counter()
    deadline = t0 + seconds
    while True:
        out = None          # free the last round's aggregate before the next
        with common.annotate("dispatch"):
            out, sel = entry(G)
        with common.annotate("sync"):
            out.block_until_ready()
        if len(sels) == sample_at:
            sample = out
        sels.append(sel)
        te = time.perf_counter()
        if te >= deadline:
            break
    window_s = te - t0
    n = len(sels)
    state["answers"] = {n - 1: out}
    if sample is not None:
        state["answers"][sample_at] = sample
    state["sels"] = sels
    m, d, dt = state["m"], state["d"], state["tr"]["dtype"]
    return {
        "attempted": n, "failed": 0, "window_s": window_s, "rounds": n,
        "m": m, "d": d, "dtype": dt,
        "metrics": {"agg_round_ms": (window_s / n * 1e3, "ms")},
    }


def reference_kept(state: dict, quant=None):
    scores, l1 = ref.stats(state["G"], quant=quant)
    return ref.select(scores, l1, state["tr"]["beta"])


def check(state: dict, result: dict) -> dict:
    """Every round's selection, and the whole aggregate of the last round
    and of one round drawn from the seed, against the plain BrSGD."""
    kept = reference_kept(state)
    sels = np.stack([np.asarray(s, bool) for s in state.pop("sels")])
    wrong = np.any(sels != kept[None, :], axis=1)
    gap = 0.0
    for r, out in state.pop("answers").items():
        g = ref.max_gap(state["G"], kept, out)
        wrong[r] |= g > 0
        gap = max(gap, g)
    result["failed"] = int(wrong.sum())
    return {
        "selection_mismatches": int((sels != kept[None, :]).sum()),
        "byzantine_selected": int(sels[:, :state["n_byz"]].sum()),
        "aggregate_max_gap": gap,
    }
