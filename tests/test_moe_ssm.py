"""MoE dispatch and SSM/RWKV recurrence correctness."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.base import MoESpec, RWKVSpec, SSMSpec
from repro.models import mamba2 as M2
from repro.models import moe as MOE
from repro.models import rwkv6 as R6
from repro.models.params import init_params


# ---------------------------------------------------------------------------
# MoE
# ---------------------------------------------------------------------------

def _moe_setup(E=4, k=2, d=32, f=64, T=48, **kw):
    spec = MoESpec(n_experts=E, top_k=k, d_ff_expert=f, **kw)
    p = init_params(MOE.moe_defs(d, spec), jax.random.PRNGKey(0))
    x = 0.5 * jax.random.normal(jax.random.PRNGKey(1), (2, T // 2, d), jnp.float32)
    return spec, p, x


def test_moe_lossless_capacity_matches_dense_oracle():
    spec, p, x = _moe_setup()
    out, aux, cnt = MOE.moe_ffn(p, x, spec)
    ref = MOE.ref_dense_moe(p, x, spec)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-3, atol=2e-3)
    assert bool(jnp.isfinite(aux))
    assert float(cnt["pairs"]) == x.shape[0] * x.shape[1] * spec.top_k


def test_moe_router_normalized_topk():
    spec, p, x = _moe_setup()
    w, ids, aux = MOE.route(p["router"], x.reshape(-1, x.shape[-1]), spec,
                            x.shape[1])
    assert w.shape == (x.shape[0] * x.shape[1], spec.top_k)
    np.testing.assert_allclose(np.asarray(w.sum(-1)), 1.0, rtol=1e-4)
    assert int(ids.max()) < spec.n_experts


def test_moe_shared_experts_added():
    spec = MoESpec(n_experts=4, top_k=1, d_ff_expert=16, n_shared=2)
    p = init_params(MOE.moe_defs(8, spec), jax.random.PRNGKey(0))
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 5, 8), jnp.float32)
    out, _, _ = MOE.moe_ffn(p, x, spec)
    ref = MOE.ref_dense_moe(p, x, spec)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-3, atol=2e-3)


def _share(p, first, n):
    """The parameters a layer holding experts [first, first + n) has."""
    return {k: (v[first:first + n] if k in ("w_in", "w_gate", "w_out") else v)
            for k, v in p.items()}


def test_moe_expert_shares_add_up_to_the_whole_layer():
    """Two layers holding experts 0-1 and 2-3 of 4: their outputs add up
    to the uncut layer's, with the shared experts counted once."""
    spec, p, x = _moe_setup(n_shared=1)
    whole, _, cnt = MOE.moe_ffn(p, x, spec)
    shared = MOE.shared_mlp(p, x, "silu")
    parts, pairs = 0.0, 0.0
    for first in (0, 2):
        sub = dataclasses.replace(spec, expert_first=first, n_held=2)
        out, _, c = MOE.moe_ffn(_share(p, first, 2), x, sub)
        parts = parts + (out - shared)
        pairs += float(c["pairs"])
    np.testing.assert_allclose(np.asarray(parts + shared), np.asarray(whole),
                               rtol=1e-5, atol=1e-5)
    assert pairs == float(cnt["pairs"]) == x.shape[0] * x.shape[1] * spec.top_k


def test_moe_share_matches_the_oracle_of_its_experts():
    spec, p, x = _moe_setup(E=8, k=3, n_shared=1)
    sub = dataclasses.replace(spec, expert_first=4, n_held=2)
    out, _, _ = MOE.moe_ffn(_share(p, 4, 2), x, sub)
    ref = MOE.ref_dense_moe(_share(p, 4, 2), x, sub)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=1e-5, atol=1e-5)


def test_moe_imbalanced_routing_drops_nothing():
    """Every token routed to the same two experts: all of its pairs are
    computed, and the result is the dense oracle's."""
    spec, p, x = _moe_setup(E=4, k=2, norm_topk_prob=False)
    # one feature that every token holds, which the router reads as a
    # strong vote for experts 0 and 1
    x = x.at[..., 0].set(2.0)
    p = dict(p, router=p["router"].at[0, :2].set(20.0).at[0, 2:].set(-20.0))
    w, ids, _ = MOE.route(p["router"], x.reshape(-1, x.shape[-1]), spec, x.shape[1])
    assert set(np.unique(np.asarray(ids))) == {0, 1}
    out, _, cnt = MOE.moe_ffn(p, x, spec)
    T = x.shape[0] * x.shape[1]
    assert float(cnt["pairs"]) == T * spec.top_k
    assert float(cnt["load_max"]) == pytest.approx(2.0)   # 2 of 4 experts busy
    ref = MOE.ref_dense_moe(p, x, spec)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=1e-5, atol=1e-5)


def test_moe_dispatch_groups_pairs_by_held_expert():
    spec = MoESpec(n_experts=4, top_k=2, expert_first=1, n_held=2)
    ids = jnp.asarray([[1, 3], [2, 1], [0, 2]])
    w = jnp.full((3, 2), 0.5, jnp.float32)
    order, inv, weight, sizes = MOE.dispatch(ids, w, spec)
    assert np.asarray(sizes).tolist() == [2, 2]           # experts 1 and 2
    # pairs (token, choice): expert 1 holds (0,0), (1,1); expert 2 (1,0), (2,1)
    assert np.asarray(order).tolist()[:4] == [0, 3, 2, 5]
    assert np.asarray(order)[np.asarray(inv)].tolist() == list(range(6))
    assert np.asarray(weight).tolist() == [0.5] * 4 + [0.0] * 2
    x = jnp.arange(3.0)[:, None] * jnp.ones((1, 2))
    rows = MOE.to_pairs(x, order, inv, 2)
    assert np.asarray(rows[:, 0]).tolist()[:4] == [0.0, 1.0, 1.0, 2.0]
    np.testing.assert_array_equal(MOE.to_tokens(rows, order, inv, 2), 2 * x)


@pytest.mark.parametrize("fn", ["to_pairs", "to_tokens"])
def test_moe_pair_maps_transpose_each_other(fn):
    """Each map's gradient is the other map, as autodiff of a plain
    gather would give it."""
    ids = jnp.asarray([[3, 0], [2, 1], [0, 3]])
    order = jnp.argsort(ids.reshape(-1), stable=True)
    inv = jnp.argsort(order)
    plain = {"to_pairs": lambda x: x[order // 2],
             "to_tokens": lambda y: jnp.zeros((3, 4)).at[order // 2].add(y)}[fn]
    shape = (3, 4) if fn == "to_pairs" else (6, 4)
    x = jax.random.normal(jax.random.PRNGKey(0), shape)
    mapped = lambda x: getattr(MOE, fn)(x, order, inv, 2)
    np.testing.assert_allclose(mapped(x), plain(x), rtol=1e-6)
    ct = jax.random.normal(jax.random.PRNGKey(1), plain(x).shape)
    np.testing.assert_allclose(jax.vjp(mapped, x)[1](ct)[0],
                               jax.vjp(plain, x)[1](ct)[0], rtol=1e-6)


def test_moe_rows_past_the_groups_never_reach_a_token(monkeypatch):
    """A TPU leaves the grouped products' rows past the last group
    unwritten: filled with NaN here, the layer's output and gradients
    are still the oracle's."""
    spec, p, x = _moe_setup(E=8, k=2, n_shared=1, expert_first=2, n_held=3)

    def loss(fn, p, x):
        return jnp.sum(jnp.sin(fn(p, x, spec)[0] if fn is MOE.moe_ffn
                               else fn(p, x, spec)))

    want = jax.value_and_grad(lambda p, x: loss(MOE.ref_dense_moe, p, x),
                              argnums=(0, 1))(p, x)
    grouped = MOE._grouped

    def unwritten(x, w, sizes):
        out = grouped(x, w, sizes)
        past = jnp.arange(out.shape[0]) >= jnp.sum(sizes)
        return jnp.where(past[:, None], jnp.nan, out)

    monkeypatch.setattr(MOE, "_grouped", unwritten)
    got = jax.value_and_grad(lambda p, x: loss(MOE.moe_ffn, p, x),
                             argnums=(0, 1))(p, x)
    for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        assert bool(jnp.all(jnp.isfinite(a)))
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-4, atol=1e-5)


@pytest.mark.parametrize("rows,k,n,weight_grad,want", [
    # DeepSeek-V2-Lite's experts (2,048 x 1,408) over a 4,096-token
    # sequence's 24,576 pairs: whole-width forward tiles, 512 x 512
    # weight-gradient tiles
    (24576, 2048, 1408, False, "512,512,1408"),
    (24576, 1408, 2048, False, "512,128,2048"),
    (24576, 2048, 1408, True, "512,512,512"),
    (24576, 1408, 2048, True, "512,512,512"),
    # rows that are no whole number of row tiles (decode's few tokens)
    (48, 2048, 1408, False, None),
    # a whole-width tile past the VMEM budget (dbrx's 10,752-wide experts)
    (24576, 6144, 10752, False, None),
])
def test_moe_grouped_product_tiles(rows, k, n, weight_grad, want):
    assert MOE._tiling(rows, k, n, weight_grad) == want


def test_moe_router_without_renormalisation_keeps_probabilities():
    spec, p, x = _moe_setup(norm_topk_prob=False, routed_scaling_factor=2.0)
    xf = x.reshape(-1, x.shape[-1])
    w, ids, _ = MOE.route(p["router"], xf, spec, x.shape[1])
    probs = jax.nn.softmax(xf @ p["router"], axis=-1)
    want = 2.0 * jnp.take_along_axis(probs, ids, axis=-1)
    np.testing.assert_allclose(np.asarray(w), np.asarray(want), rtol=1e-6)
    assert float(jnp.max(w.sum(-1) / 2.0)) < 1.0


def test_moe_sequence_balance_loss_by_hand():
    """DeepSeek-V2's sequence-wise loss: alpha * mean over sequences of
    sum_e (count_e * E / (S * k)) * mean_s p_e."""
    spec, p, x = _moe_setup(balance="seq", aux_loss=0.1)
    xf = x.reshape(-1, x.shape[-1])
    B, S, E, k = x.shape[0], x.shape[1], spec.n_experts, spec.top_k
    _, ids, aux = MOE.route(p["router"], xf, spec, S)
    probs = np.asarray(jax.nn.softmax(xf @ p["router"], axis=-1)).reshape(B, S, E)
    ids = np.asarray(ids).reshape(B, S * k)
    want = 0.0
    for b in range(B):
        count = np.bincount(ids[b], minlength=E) * E / (S * k)
        want += np.sum(count * probs[b].mean(0)) / B
    assert float(aux) == pytest.approx(0.1 * want, rel=1e-5)


# ---------------------------------------------------------------------------
# Mamba2 (SSD)
# ---------------------------------------------------------------------------

def _ssd_sequential(xh, dt, A, Bc, Cc):
    """O(S·N·P) reference recurrence."""
    Bsz, S, H, Pd = xh.shape
    N = Bc.shape[-1]
    S_state = np.zeros((Bsz, H, N, Pd), np.float64)
    ys = []
    for t in range(S):
        dA = np.exp(np.asarray(dt[:, t], np.float64) * np.asarray(A, np.float64))
        dBx = np.einsum("bn,bh,bhp->bhnp", np.asarray(Bc[:, t], np.float64),
                        np.asarray(dt[:, t], np.float64),
                        np.asarray(xh[:, t], np.float64))
        S_state = S_state * dA[:, :, None, None] + dBx
        ys.append(np.einsum("bn,bhnp->bhp", np.asarray(Cc[:, t], np.float64),
                            S_state))
    return np.stack(ys, 1), S_state


@pytest.mark.parametrize("S,chunk", [(16, 4), (20, 8), (7, 16), (32, 32)])
def test_ssd_chunked_matches_sequential(S, chunk):
    rng = np.random.default_rng(0)
    Bsz, H, Pd, N = 2, 3, 4, 5
    xh = jnp.asarray(rng.normal(size=(Bsz, S, H, Pd)).astype("f4"))
    dt = jnp.asarray(rng.uniform(0.1, 0.9, size=(Bsz, S, H)).astype("f4"))
    A = jnp.asarray(rng.uniform(-1.0, -0.1, size=(H,)).astype("f4"))
    Bc = jnp.asarray(rng.normal(size=(Bsz, S, N)).astype("f4"))
    Cc = jnp.asarray(rng.normal(size=(Bsz, S, N)).astype("f4"))
    y, Sf = M2._ssd_chunked(xh, dt, A, Bc, Cc, chunk)
    y_ref, S_ref = _ssd_sequential(xh, dt, A, Bc, Cc)
    np.testing.assert_allclose(np.asarray(y), y_ref, rtol=1e-3, atol=1e-3)
    np.testing.assert_allclose(np.asarray(Sf), S_ref, rtol=1e-3, atol=1e-3)


def test_mamba2_decode_matches_forward():
    spec = SSMSpec(state_dim=8, head_dim=8, chunk=4, conv_width=3)
    D, B, S = 16, 2, 10
    p = init_params(M2.mamba2_defs(D, spec), jax.random.PRNGKey(0))
    x = 0.5 * jax.random.normal(jax.random.PRNGKey(1), (B, S, D), jnp.float32)
    y_full, _ = M2.mamba2_forward(p, spec, x)
    di = spec.expand * D
    conv = jnp.zeros((B, spec.conv_width - 1, di + 2 * spec.state_dim))
    H = di // spec.head_dim
    ssm = jnp.zeros((B, H, spec.state_dim, di // H), jnp.float32)
    for t in range(S):
        y_t, (conv, ssm) = M2.mamba2_decode(p, spec, x[:, t:t + 1], conv, ssm)
        np.testing.assert_allclose(np.asarray(y_t[:, 0]),
                                   np.asarray(y_full[:, t]),
                                   rtol=2e-3, atol=2e-3)


def test_causal_conv_state_stitching():
    """Streaming conv over two halves == one-shot conv."""
    rng = np.random.default_rng(1)
    kern = jnp.asarray(rng.normal(size=(4, 6)).astype("f4"))
    bias = jnp.asarray(rng.normal(size=(6,)).astype("f4"))
    x = jnp.asarray(rng.normal(size=(2, 12, 6)).astype("f4"))
    full, _ = M2._causal_conv(x, kern, bias)
    h1, st = M2._causal_conv(x[:, :5], kern, bias)
    h2, _ = M2._causal_conv(x[:, 5:], kern, bias, state=st)
    np.testing.assert_allclose(np.asarray(jnp.concatenate([h1, h2], 1)),
                               np.asarray(full), rtol=1e-5, atol=1e-5)


# ---------------------------------------------------------------------------
# RWKV6
# ---------------------------------------------------------------------------

def test_rwkv_timemix_decode_matches_full():
    spec = RWKVSpec(head_dim=8, decay_lora=8, mix_lora=4)
    D, B, S = 16, 2, 9
    p = init_params(R6.rwkv6_defs(D, 32, spec), jax.random.PRNGKey(0))
    x = 0.5 * jax.random.normal(jax.random.PRNGKey(1), (B, S, D), jnp.float32)
    y_full, _ = R6.rwkv6_timemix(p, spec, x)
    last = None
    state = jnp.zeros((B, D // spec.head_dim, spec.head_dim, spec.head_dim),
                      jnp.float32)
    for t in range(S):
        y_t, (last, state) = R6.rwkv6_timemix(p, spec, x[:, t:t + 1],
                                              last_x=last, state=state)
        np.testing.assert_allclose(np.asarray(y_t[:, 0]),
                                   np.asarray(y_full[:, t]),
                                   rtol=2e-3, atol=2e-3)


def test_rwkv_decay_in_unit_interval():
    spec = RWKVSpec(head_dim=8, decay_lora=8, mix_lora=4)
    D = 16
    p = init_params(R6.rwkv6_defs(D, 32, spec), jax.random.PRNGKey(0))
    x = jax.random.normal(jax.random.PRNGKey(1), (1, 5, D), jnp.float32)
    dec = (p["decay_base"].astype(jnp.float32)
           + jnp.tanh(x @ p["decay_A"]) @ p["decay_B"])
    w = jnp.exp(-jnp.exp(dec))
    assert float(w.min()) > 0.0 and float(w.max()) < 1.0


@pytest.mark.parametrize("S,Q,wlo", [(32, 8, 0.05), (35, 16, 0.05),
                                     (64, 64, 0.3), (128, 32, 0.2),
                                     (16, 4, 0.02)])
def test_wkv_chunked_matches_scan(S, Q, wlo):
    """Chunked-parallel WKV6 (§Perf) == per-token scan across chunk
    sizes, ragged tails, and decay regimes."""
    rng = np.random.default_rng(S * 100 + Q)
    B, H, K = 2, 3, 8
    mk = lambda: jnp.asarray(rng.normal(size=(B, S, H, K)).astype("f4"))
    r, k, v = mk(), mk(), mk()
    w = jnp.asarray(rng.uniform(wlo, 0.999, size=(B, S, H, K)).astype("f4"))
    u = jnp.asarray(rng.normal(size=(H, K)).astype("f4"))
    S0 = jnp.asarray(rng.normal(size=(B, H, K, K)).astype("f4"))
    y1, Sf1 = R6._wkv_scan(r, k, v, w, u, S0)
    y2, Sf2 = R6._wkv_chunked(r, k, v, w, u, S0, Q)
    scale = max(1.0, float(jnp.abs(y1).max()))
    np.testing.assert_allclose(np.asarray(y2) / scale, np.asarray(y1) / scale,
                               atol=1e-5)
    np.testing.assert_allclose(np.asarray(Sf2), np.asarray(Sf1),
                               rtol=1e-4, atol=1e-4)


def test_rwkv_timemix_chunked_config_matches():
    """rwkv6_timemix with spec.chunk>0 == chunk=0 on the same params."""
    import dataclasses
    spec0 = RWKVSpec(head_dim=8, decay_lora=8, mix_lora=4, chunk=0)
    spec1 = dataclasses.replace(spec0, chunk=8)
    D, B, S = 16, 2, 20
    p = init_params(R6.rwkv6_defs(D, 32, spec0), jax.random.PRNGKey(0))
    x = 0.5 * jax.random.normal(jax.random.PRNGKey(1), (B, S, D), jnp.float32)
    y0, (lx0, st0) = R6.rwkv6_timemix(p, spec0, x)
    y1, (lx1, st1) = R6.rwkv6_timemix(p, spec1, x)
    np.testing.assert_allclose(np.asarray(y1), np.asarray(y0),
                               rtol=1e-3, atol=1e-4)
    np.testing.assert_allclose(np.asarray(st1), np.asarray(st0),
                               rtol=1e-3, atol=1e-4)


def test_wkv_scan_state_accumulates():
    """With decay w=1 and r=e_j the scan output reproduces cumulative
    k·v sums (hand-checkable recurrence)."""
    B, S, H, K = 1, 4, 1, 3
    r = jnp.tile(jnp.eye(K)[0][None, None, None], (B, S, H, 1))
    k = jnp.ones((B, S, H, K))
    v = jnp.cumsum(jnp.ones((B, S, H, K)), axis=1)   # 1,2,3,4
    w = jnp.ones((B, S, H, K))
    u = jnp.zeros((H, K))
    y, Sf = R6._wkv_scan(r, k, v, w, u, jnp.zeros((B, H, K, K)))
    # y_t = r·S_t where S_t = sum_{s<t} k_s v_s^T  -> column sums 0,1,3,6
    np.testing.assert_allclose(np.asarray(y[0, :, 0, 0]),
                               np.asarray([0.0, 1.0, 3.0, 6.0]), atol=1e-5)
