"""Mixture-of-Experts FFN: token-choice top-k routing over every routed
expert, and dropless dispatch to the experts this layer holds.

Expert parallelism: a layer shared by several chips gives each of them a
range of its experts, ``[expert_first, expert_first + n_held)`` of
``n_experts``.  The router keeps its full width and its top-k; the layer
computes what its own experts contribute for the (token, expert) pairs
routed to them, and the shared experts once.  Pairs routed to experts
held elsewhere contribute nothing here.  With ``n_held`` 0 the layer
holds every expert.

Dispatch drops no tokens.  The T·k pairs are sorted by expert, so the
pairs of each held expert form one contiguous group (those of experts
held elsewhere go last), and three grouped matrix products
(``jax.lax.ragged_dot``) run over the groups.  On a TPU the grouped
product is a kernel whose work follows the group sizes, not the buffer.
The buffer holds all T·k pairs, the worst case, so shapes stay static
however unbalanced the routing.  Each pair's weighted output is then
added back to its token (a gather and a sum over the token's k pairs).
"""
from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp
from jax.custom_batching import sequential_vmap
from jax.experimental.xla_metadata import set_xla_metadata

from ..configs.base import MoESpec
from .layers import activate
from .params import ParamDef


def moe_defs(d_model: int, m: MoESpec) -> dict:
    e, f = m.held, m.d_ff_expert
    d = {
        "router": ParamDef((d_model, m.n_experts), ("embed", None)),
        "w_in": ParamDef((e, d_model, f), ("experts", "embed", "ff")),
        "w_gate": ParamDef((e, d_model, f), ("experts", "embed", "ff")),
        "w_out": ParamDef((e, f, d_model), ("experts", "ff", "embed")),
    }
    if m.n_shared:
        d["shared_in"] = ParamDef((d_model, m.n_shared * f), ("embed", "ff"))
        d["shared_gate"] = ParamDef((d_model, m.n_shared * f), ("embed", "ff"))
        d["shared_out"] = ParamDef((m.n_shared * f, d_model), ("ff", "embed"))
    return d


def route(router_w, x, m: MoESpec, seq_len: int):
    """x [T, d]: B = T / seq_len sequences of seq_len tokens, in order.
    Returns (weights [T,k], expert ids [T,k], balance loss)."""
    logits = (x.astype(jnp.float32) @ router_w.astype(jnp.float32))
    probs = jax.nn.softmax(logits, axis=-1)                  # [T,E]
    w, ids = jax.lax.top_k(probs, m.top_k)                   # [T,k]
    if m.norm_topk_prob:
        w = w / jnp.maximum(jnp.sum(w, axis=-1, keepdims=True), 1e-9)
    w = w * m.routed_scaling_factor
    T, E, k = x.shape[0], m.n_experts, m.top_k
    if m.balance == "seq":
        # DeepSeek-V2: per sequence, each expert's share of the pairs
        # (scaled so that an even spread reads 1) times its mean
        # probability, summed over experts, averaged over sequences
        B = T // seq_len
        ce = jnp.zeros((B, E)).at[jnp.arange(B)[:, None],
                                  ids.reshape(B, seq_len * k)].add(1.0)
        ce = ce / (seq_len * k / E)
        me = jnp.mean(probs.reshape(B, seq_len, E), axis=1)
        return w, ids, jnp.mean(jnp.sum(ce * me, axis=-1)) * m.aux_loss
    if m.balance != "switch":
        raise ValueError(f"unknown balance loss {m.balance!r}")
    # Switch load balance + router z-loss
    me = jnp.mean(probs, axis=0)                             # mean prob per expert
    ce = jnp.zeros((E,)).at[ids.reshape(-1)].add(1.0) / (T * k)
    aux = E * jnp.sum(me * ce) * m.aux_loss
    z = jnp.mean(jax.scipy.special.logsumexp(logits, axis=-1) ** 2) * m.router_zloss
    return w, ids, aux + z


def dispatch(ids, w, m: MoESpec):
    """The T·k pairs (pair t·k + j: token t's j-th choice) sorted by held
    expert, those held elsewhere last.  Returns ``order`` (sorted position
    -> pair), its inverse ``inv`` (pair -> sorted position), each sorted
    pair's weight (0 where its expert is held elsewhere), and the number
    of pairs of every held expert ([n_held] int32, the groups of the
    grouped products)."""
    local = ids.reshape(-1) - m.expert_first
    held = (local >= 0) & (local < m.held)
    group = jnp.where(held, local, m.held)
    # a counting sort, stable: a pair's place is its group's offset plus
    # the pairs of its group before it
    onehot = jax.nn.one_hot(group, m.held + 1, dtype=jnp.int32)
    before = jnp.sum((jnp.cumsum(onehot, axis=0) - 1) * onehot, axis=-1)
    counts = jnp.sum(onehot, axis=0)
    inv = (jnp.cumsum(counts) - counts)[group] + before
    order = jnp.zeros_like(inv).at[inv].set(jnp.arange(inv.shape[0], dtype=inv.dtype))
    weight = jnp.where(held, w.reshape(-1), 0.0)[order]
    return order, inv, weight, counts[:m.held]


# Rows move between tokens and sorted pairs by gathers alone: the two
# maps below are each other's transpose (the pairs are a permutation of
# k copies of the tokens), so neither pass scatters.
@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def to_pairs(x, order, inv, k: int):
    """[T, d] token rows -> [T·k, d] rows of the sorted pairs."""
    return x[order // k]


@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def to_tokens(y, order, inv, k: int):
    """[T·k, d] rows of the sorted pairs -> [T, d], each token's k rows
    summed."""
    return jnp.sum(y[inv].reshape(-1, k, y.shape[-1]), axis=1)


to_pairs.defvjp(lambda x, order, inv, k: (to_pairs(x, order, inv, k), (order, inv)),
                lambda k, res, dy: (to_tokens(dy, *res, k), None, None))
to_tokens.defvjp(lambda y, order, inv, k: (to_tokens(y, order, inv, k), (order, inv)),
                 lambda k, res, dy: (to_pairs(dy, *res, k), None, None))


# The TPU compiler's grouped-product kernel takes its tiles (rows,
# contracted width, output width) from the ``ragged_dot_tiling``
# attribute where one is set.  Its own choice takes 128 or 512 output
# columns a tile, and reads every row of the input once for each tile of
# columns: at the widths of an expert (2,048 x 1,408) that made the
# products cost about three times the bytes of a tile as wide as the
# output, and that cost follows the routed pairs.  The forward products
# take the whole output width in a tile, the weight gradients tiles of
# 512 x 512; shapes these tiles do not fit keep the compiler's choice.
ROW_TILE = 512
_VMEM_BUDGET = 14 * 2**20       # bytes of tiles; the compiler refused 21 MiB


def _tiling(rows: int, k: int, n: int, weight_grad: bool):
    """The ``ragged_dot_tiling`` of a product over ``rows`` grouped rows
    that contracts ``k`` and gives ``n`` columns ([groups, k, n] for a
    weight gradient), or None for the compiler's own."""
    if rows % ROW_TILE:
        return None
    if weight_grad:
        return f"{ROW_TILE},{min(k, 512)},{min(n, 512)}"
    for tk in (512, 256, 128):
        # the row and weight tiles twice (one in flight), the output tile twice
        vmem = 4 * (2 * (ROW_TILE * tk + tk * n) + 2 * ROW_TILE * n)
        if k % tk == 0 and vmem <= _VMEM_BUDGET:
            return f"{ROW_TILE},{tk},{n}"
    return None


def _ragged_dot(x, w, sizes, tiling):
    if tiling is None:
        return jax.lax.ragged_dot(x, w, sizes)
    with set_xla_metadata(ragged_dot_tiling=tiling):
        return jax.lax.ragged_dot(x, w, sizes)


def _grouped(x, w, sizes):
    """Rows of x grouped by expert times their expert's matrix.  Rows
    past the last group are left unspecified (a TPU leaves them
    unwritten)."""
    return _ragged_dot(x, w, sizes, _tiling(x.shape[0], *w.shape[1:], False))


def _weight_grad(x, dy, w, sizes):
    """The gradient of ``_grouped(x, w, sizes)`` in w: each group's
    x^T dy, over its own rows alone."""
    tiling = _tiling(x.shape[0], *w.shape[1:], True)
    _, vjp = jax.vjp(lambda w: _ragged_dot(x, w, sizes, tiling), w)
    return vjp(dy)[0]


def _in_groups(x, sizes):
    """x with the rows past the last group zeroed (a select: those rows
    may hold anything)."""
    rows = jnp.arange(x.shape[0]) < jnp.sum(sizes)
    return jnp.where(rows[:, None], x, jnp.zeros((), x.dtype))


def _grouped_mlp(xs, w_in, w_gate, w_out, sizes, activation):
    h = _grouped(xs, w_in, sizes)
    g = _grouped(xs, w_gate, sizes)
    y = _grouped(activate(g, activation) * h, w_out, sizes)
    return _in_groups(y, sizes), (h, g)


# ``ragged_dot`` has no batching rule for unbatched weights, and the
# training step maps the loss over its workers: each worker's grouped
# products run in turn under that map (``sequential_vmap``), forward and
# backward alike.  The backward is written out so that the rows past the
# last group, which a TPU leaves unwritten, never reach a token.
@functools.partial(jax.custom_vjp, nondiff_argnums=(5,))
def expert_mlp(xs, w_in, w_gate, w_out, sizes, activation: str):
    """The held experts' gated MLPs over rows grouped by expert:
    ``sizes[e]`` consecutive rows go to held expert e, and the rows past
    their sum come out as zeros."""
    return _expert_fwd(xs, w_in, w_gate, w_out, sizes, activation)[0]


def _expert_fwd(xs, w_in, w_gate, w_out, sizes, activation):
    y, hg = sequential_vmap(functools.partial(_grouped_mlp, activation=activation))(
        xs, w_in, w_gate, w_out, sizes)
    return y, (xs, w_in, w_gate, w_out, sizes, *hg)


def _expert_bwd_one(xs, w_in, w_gate, w_out, sizes, h, g, dy, activation):
    a, act_vjp = jax.vjp(lambda h, g: activate(g, activation) * h, h, g)
    dw_out = _weight_grad(a, dy, w_out, sizes)
    dh, dg = act_vjp(_grouped(dy, jnp.swapaxes(w_out, 1, 2), sizes))
    dx = (_grouped(dh, jnp.swapaxes(w_in, 1, 2), sizes)
          + _grouped(dg, jnp.swapaxes(w_gate, 1, 2), sizes))
    return (_in_groups(dx, sizes), _weight_grad(xs, dh, w_in, sizes),
            _weight_grad(xs, dg, w_gate, sizes), dw_out)


def _expert_bwd(activation, res, dy):
    grads = sequential_vmap(functools.partial(_expert_bwd_one, activation=activation))(
        *res, dy)
    return (*grads, None)


expert_mlp.defvjp(_expert_fwd, _expert_bwd)


def shared_mlp(p, x, activation: str):
    hs = x @ p["shared_in"]
    hs = activate(x @ p["shared_gate"], activation) * hs
    return hs @ p["shared_out"]


def moe_ffn(p, x, m: MoESpec, activation: str = "silu"
            ) -> Tuple[jax.Array, jax.Array, dict]:
    """x: [B, S, d].  Returns (out [B,S,d], balance loss, counters):
    ``pairs``, the pairs that held experts computed, and ``load_max``,
    the largest held expert's pairs over the held experts' mean."""
    B, S, d = x.shape
    xf = x.reshape(B * S, d)
    with jax.named_scope("router"):
        w, ids, aux = route(p["router"], xf, m, S)
    with jax.named_scope("dispatch"):
        order, inv, weight, sizes = dispatch(ids, w, m)
        xs = to_pairs(xf, order, inv, m.top_k)               # [T*k, d]
    with jax.named_scope("experts"):
        ys = expert_mlp(xs, p["w_in"], p["w_gate"], p["w_out"], sizes, activation)
    with jax.named_scope("dispatch"):
        out = to_tokens(ys * weight[:, None].astype(ys.dtype), order, inv, m.top_k)
    if m.n_shared:
        with jax.named_scope("shared_experts"):
            out = out + shared_mlp(p, xf, activation)
    pairs = jnp.sum(sizes).astype(jnp.float32)
    load_max = jnp.max(sizes).astype(jnp.float32) / jnp.maximum(pairs / m.held, 1e-9)
    return out.reshape(B, S, d), aux, {"pairs": pairs, "load_max": load_max}


def ref_dense_moe(p, x, m: MoESpec, activation: str = "silu"):
    """Oracle: every held expert on every token, combined with the router
    weights of the pairs routed to it.  O(T·E·d·f) — tests only."""
    B, S, d = x.shape
    xf = x.reshape(B * S, d)
    w, ids, _ = route(p["router"], xf, m, S)
    h = jnp.einsum("td,edf->tef", xf, p["w_in"])
    g = jnp.einsum("td,edf->tef", xf, p["w_gate"])
    y = jnp.einsum("tef,efd->ted", activate(g, activation) * h, p["w_out"])
    onehot = jax.nn.one_hot(ids - m.expert_first, m.held, dtype=y.dtype)
    combine = jnp.einsum("tk,tke->te", w.astype(y.dtype), onehot)
    out = jnp.einsum("te,ted->td", combine, y)
    if m.n_shared:
        out = out + shared_mlp(p, xf, activation)
    return out.reshape(B, S, d)
