"""Plain DeepSeek-V2 language model, as one chip's share of it: forward,
next-token loss and gradient, written from the published description
(Hugging Face ``DeepseekV2ForCausalLM``, ``modeling_deepseek.py``) in
straightforward ``jax.numpy``.

- Pre-norm decoder layers: ``x += attn(rms(x))``, ``x += ffn(rms(x))``.
  The first ``first_k_dense_replace`` layers have a dense FFN, the
  others the MoE FFN.
- MLA without a query low-rank projection (``q_lora_rank`` null): each
  head's query is ``h W_q``, split into its ``qk_nope_head_dim`` and
  ``qk_rope_head_dim`` parts.  The latent ``c = rms(h W_kv_a)`` (epsilon
  ``rms_norm_eps``) gives each head's no-rope key and its value through
  ``W_kv_b``; the rope key ``h W_kv_a'`` is one head that every head
  shares.
- YaRN rotary embedding (``DeepseekV2YarnRotaryEmbedding``): inverse
  frequencies blended between θ^(-2i/d) and that over ``factor`` along
  a linear ramp between the correction dimensions of ``beta_fast`` and
  ``beta_slow``; cos and sin scaled by mscale(mscale)/mscale(all_dim).
  Each rope head is de-interleaved (``view(d/2, 2).transpose``) before
  ``rotate_half``.  A configuration whose ``rope_scaling`` is null
  takes plain rotary embedding.
- Scores ``q·k`` scaled by (nope + rope)^-1/2, times mscale(all_dim)^2
  under YaRN, causal softmax in float32, values, output projection.
- MoEGate: a float32 softmax over every routed expert's logit, greedy
  top-k; with ``norm_topk_prob`` the weights are renormalised, otherwise
  scaled by ``routed_scaling_factor``.  The sequence-wise balance loss:
  alpha × mean over sequences of Σ_e (pairs_e · E / (S·k)) · mean_s p_e.
- Experts: ``down(silu(gate(x)) * up(x))``; the shared experts are one
  such MLP of width ``n_shared_experts × moe_intermediate_size``.
- Final RMS norm, untied head; loss: mean cross-entropy of every
  position predicting the next token, plus the layers' balance losses.

Departures from the published code:

- The chip share.  The router has ``n_routed_experts × expert_parallel``
  outputs, and this layer holds experts 0 .. ``n_routed_experts`` − 1 of
  them: the pairs routed to an expert held elsewhere contribute nothing.
  The held experts run as one dense ``einsum`` over every token, each
  token's output weighted by the gate's weight where the expert is among
  its top-k and by 0 elsewhere.
- The vocabulary is the configuration's slice: ids, logits and loss are
  over ``vocab_size`` rows.
- The parameter layout is the program's: ``kv_a_proj_with_mqa`` is held
  as ``w_dkv`` (latent) and ``w_krope`` (rope key), ``kv_b_proj`` as
  ``w_uk`` and ``w_uv``, ``q_proj`` as [D, H, nope + rope] and
  ``o_proj`` as [H, v, D].  The products are the same.
- The published model adds the balance loss to the gradient alone
  (``AddAuxiliaryLoss``); here it is added to the loss's value too, as
  the program reports it.  The gradient is the same.
- The rotary tables are computed in float64 and rounded to float32.

Parameters are a dict of float32 arrays (layers stacked on a leading
axis); :func:`init` makes them from a key with the convention of
``qwen3.init``.  Every matrix product takes its ``precision`` and every
activation the ``dtype`` it is given: float32 at ``HIGHEST`` is the
reference, and bfloat16 at ``DEFAULT`` the control of a lower precision.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

from bench.reference.qwen3 import (HIGHEST, _is_shape, _rms, adamw_step,  # noqa: F401
                                   add_scaled, leaf_norms, scale, zeros_like)


def _attn_shapes(cfg: dict, n: int) -> dict:
    D, H, R = cfg["hidden_size"], cfg["num_attention_heads"], cfg["kv_lora_rank"]
    dn, dr, dv = (cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"],
                  cfg["v_head_dim"])
    return {"kv_norm": (n, R), "w_dkv": (n, D, R), "w_krope": (n, D, dr),
            "w_uk": (n, R, H, dn), "w_uq": (n, D, H, dn + dr),
            "w_uv": (n, R, H, dv), "wo": (n, H, dv, D)}


def shapes(cfg: dict) -> dict:
    """The parameter layout: {name: shape}, nested like the program's."""
    V, D, F = cfg["vocab_size"], cfg["hidden_size"], cfg["intermediate_size"]
    Fe, E = cfg["moe_intermediate_size"], cfg["n_routed_experts"]
    Fs = cfg["n_shared_experts"] * Fe
    n_dense = cfg["first_k_dense_replace"]
    n_moe = cfg["num_hidden_layers"] - n_dense
    return {
        "embed": (V, D),
        "final_norm": (D,),
        "lm_head": (D, V),
        "seg_0": {"attn": _attn_shapes(cfg, n_dense),
                  "ln1": (n_dense, D), "ln2": (n_dense, D),
                  "mlp": {"w_gate": (n_dense, D, F), "w_in": (n_dense, D, F),
                          "w_out": (n_dense, F, D)}},
        "seg_1": {"attn": _attn_shapes(cfg, n_moe),
                  "ln1": (n_moe, D), "ln2": (n_moe, D),
                  "moe": {"router": (n_moe, D, E * cfg["expert_parallel"]),
                          "shared_gate": (n_moe, D, Fs), "shared_in": (n_moe, D, Fs),
                          "shared_out": (n_moe, Fs, D),
                          "w_gate": (n_moe, E, D, Fe), "w_in": (n_moe, E, D, Fe),
                          "w_out": (n_moe, E, Fe, D)}},
    }


def shapes_struct(cfg: dict, dtype=jnp.float32) -> dict:
    """The layout as ``jax.ShapeDtypeStruct`` leaves."""
    return jax.tree.map(lambda s: jax.ShapeDtypeStruct(s, dtype), shapes(cfg),
                        is_leaf=_is_shape)


def init(cfg: dict, key, dtype=jnp.float32) -> dict:
    """Norm weights 1; every matrix N(0, initializer_range²), leaf i drawn
    from ``fold_in(key, i)`` in tree order."""
    paths, tdef = jax.tree_util.tree_flatten_with_path(shapes(cfg), is_leaf=_is_shape)
    std = cfg["initializer_range"]
    leaves = []
    for i, (path, shape) in enumerate(paths):
        name = jax.tree_util.keystr(path)
        if "norm" in name or "ln" in name:
            leaves.append(jnp.ones(shape, dtype))
        else:
            leaves.append((jax.random.normal(jax.random.fold_in(key, i), shape,
                                             jnp.float32) * std).astype(dtype))
    return jax.tree.unflatten(tdef, leaves)


# ---- rotary embedding ------------------------------------------------------

def yarn_get_mscale(factor: float, mscale: float) -> float:
    return 1.0 if factor <= 1 else 0.1 * mscale * math.log(factor) + 1.0


def _correction_dim(n_rot: float, dim: int, base: float, max_pos: int) -> float:
    return (dim * math.log(max_pos / (n_rot * 2 * math.pi))) / (2 * math.log(base))


def rotary_tables(cfg: dict, seq: int):
    """(cos, sin) [seq, rope dim] as float32, ``rotate_half`` layout."""
    dim, base = cfg["qk_rope_head_dim"], float(cfg["rope_theta"])
    freq_extra = 1.0 / (base ** (np.arange(0, dim, 2, dtype=np.float64) / dim))
    rs = cfg["rope_scaling"]
    att = 1.0
    if rs is None:
        inv = freq_extra
    else:
        factor = rs["factor"]
        freq_inter = 1.0 / (factor * base ** (np.arange(0, dim, 2, dtype=np.float64) / dim))
        orig = rs["original_max_position_embeddings"]
        low = max(math.floor(_correction_dim(rs["beta_fast"], dim, base, orig)), 0)
        high = min(math.ceil(_correction_dim(rs["beta_slow"], dim, base, orig)), dim - 1)
        if low == high:
            high += 0.001
        ramp = np.clip((np.arange(dim // 2, dtype=np.float64) - low) / (high - low), 0, 1)
        mask = 1.0 - ramp
        inv = freq_inter * (1 - mask) + freq_extra * mask
        att = (yarn_get_mscale(factor, rs["mscale"])
               / yarn_get_mscale(factor, rs["mscale_all_dim"]))
    freqs = np.outer(np.arange(seq, dtype=np.float64), inv)
    emb = np.concatenate([freqs, freqs], axis=-1)
    return (np.cos(emb) * att).astype(np.float32), (np.sin(emb) * att).astype(np.float32)


def softmax_scale(cfg: dict) -> float:
    s = (cfg["qk_nope_head_dim"] + cfg["qk_rope_head_dim"]) ** -0.5
    rs = cfg["rope_scaling"]
    if rs is not None and rs.get("mscale_all_dim"):
        m = yarn_get_mscale(rs["factor"], rs["mscale_all_dim"])
        s = s * m * m
    return s


def _apply_rotary(x, cos, sin):
    """x [B, S, heads, d]: de-interleave each head, then rotate_half."""
    B, S, h, d = x.shape
    xf = x.astype(jnp.float32).reshape(B, S, h, d // 2, 2)
    xf = jnp.swapaxes(xf, -1, -2).reshape(B, S, h, d)
    rot = jnp.concatenate([-xf[..., d // 2:], xf[..., :d // 2]], axis=-1)
    out = xf * cos[None, :, None, :] + rot * sin[None, :, None, :]
    return out.astype(x.dtype)


# ---- layers ----------------------------------------------------------------

def _attention(cfg, x, p, precision):
    eps, H = cfg["rms_norm_eps"], cfg["num_attention_heads"]
    dn = cfg["qk_nope_head_dim"]
    B, S, _ = x.shape
    mm = functools.partial(jnp.einsum, precision=precision)
    h = _rms(x, p["ln1"], eps)
    a = p["attn"]
    q = mm("bsd,dhk->bshk", h, a["w_uq"])
    q_nope, q_pe = q[..., :dn], q[..., dn:]
    c = _rms(mm("bsd,dr->bsr", h, a["w_dkv"]), a["kv_norm"], eps)
    k_pe = mm("bsd,dk->bsk", h, a["w_krope"])[:, :, None, :]
    k_nope = mm("bsr,rhk->bshk", c, a["w_uk"])
    v = mm("bsr,rhk->bshk", c, a["w_uv"])
    cos, sin = (jnp.asarray(t) for t in rotary_tables(cfg, S))
    q_pe = _apply_rotary(q_pe, cos, sin)
    k_pe = _apply_rotary(k_pe, cos, sin)
    qs = jnp.concatenate([q_nope, q_pe], axis=-1)
    ks = jnp.concatenate([k_nope, jnp.broadcast_to(k_pe, (B, S, H, k_pe.shape[-1]))],
                         axis=-1)
    scores = mm("bshk,bthk->bhst", qs, ks) * np.float32(softmax_scale(cfg))
    causal = np.tril(np.ones((S, S), bool))
    scores = jnp.where(causal, scores.astype(jnp.float32), -jnp.inf)
    probs = jax.nn.softmax(scores, axis=-1).astype(x.dtype)
    att = mm("bhst,bthk->bshk", probs, v)
    return x + mm("bshk,hkd->bsd", att, a["wo"])


def _mlp(x, w_in, w_gate, w_out, precision):
    mm = functools.partial(jnp.einsum, precision=precision)
    up = mm("bsd,df->bsf", x, w_in)
    gate = mm("bsd,df->bsf", x, w_gate)
    return mm("bsf,fd->bsd", jax.nn.silu(gate) * up, w_out)


def gate(cfg: dict, x, router, precision):
    """MoEGate: (weights [B,S,k], expert ids [B,S,k], balance loss)."""
    B, S, _ = x.shape
    k, E = cfg["num_experts_per_tok"], router.shape[-1]
    logits = jnp.einsum("bsd,de->bse", x.astype(jnp.float32),
                        router.astype(jnp.float32), precision=precision)
    scores = jax.nn.softmax(logits, axis=-1)
    w, ids = jax.lax.top_k(scores, k)
    if k > 1 and cfg["norm_topk_prob"]:
        w = w / (jnp.sum(w, axis=-1, keepdims=True) + 1e-20)
    else:
        w = w * cfg["routed_scaling_factor"]
    counts = jax.vmap(lambda i: jnp.zeros((E,)).at[i].add(1.0))(ids.reshape(B, S * k))
    ce = counts / (S * k / E)
    aux = jnp.mean(jnp.sum(ce * jnp.mean(scores, axis=1), axis=-1)) * cfg["aux_loss_alpha"]
    return w, ids, aux


def _moe(cfg, x, p, precision):
    """The MoE FFN of this chip's held experts plus the shared experts."""
    mm = functools.partial(jnp.einsum, precision=precision)
    m = p["moe"]
    w, ids, aux = gate(cfg, x, m["router"], precision)
    held = cfg["n_routed_experts"]
    combine = jnp.sum(w.astype(x.dtype)[..., None]
                      * jax.nn.one_hot(ids, held, dtype=x.dtype), axis=-2)
    up = mm("bsd,edf->bsef", x, m["w_in"])
    gt = mm("bsd,edf->bsef", x, m["w_gate"])
    y = mm("bsef,efd->bsed", jax.nn.silu(gt) * up, m["w_out"])
    out = jnp.einsum("bse,bsed->bsd", combine, y, precision=precision)
    shared = _mlp(x, m["shared_in"], m["shared_gate"], m["shared_out"], precision)
    return out + shared, aux


def _dense_layer(cfg, x, p, precision):
    x = _attention(cfg, x, p, precision)
    h = _rms(x, p["ln2"], cfg["rms_norm_eps"])
    return x + _mlp(h, p["mlp"]["w_in"], p["mlp"]["w_gate"], p["mlp"]["w_out"],
                    precision), jnp.zeros((), jnp.float32)


def _moe_layer(cfg, x, p, precision):
    x = _attention(cfg, x, p, precision)
    out, aux = _moe(cfg, _rms(x, p["ln2"], cfg["rms_norm_eps"]), p, precision)
    return x + out, aux


def logits(cfg: dict, params: dict, tokens, precision=HIGHEST, remat=False):
    """tokens [B, S] -> (logits [B, S, V] in the parameters' dtype, the
    sum of the layers' balance losses).  ``remat`` recomputes each layer
    in the backward pass instead of keeping its activations."""
    x = params["embed"][tokens]
    aux = jnp.zeros((), jnp.float32)
    for seg, layer in (("seg_0", _dense_layer), ("seg_1", _moe_layer)):
        def body(x, p, layer=layer):
            x, a = layer(cfg, x, p, precision)
            return x, a
        if remat:
            body = jax.checkpoint(body)
        x, a = jax.lax.scan(body, x, params[seg])
        aux = aux + jnp.sum(a)
    x = _rms(x, params["final_norm"], cfg["rms_norm_eps"])
    return jnp.einsum("bsd,dv->bsv", x, params["lm_head"], precision=precision), aux


def loss(cfg: dict, params: dict, tokens, precision=HIGHEST, remat=False):
    """Mean next-token cross-entropy over tokens [B, S], plus the balance
    losses."""
    lg, aux = logits(cfg, params, tokens, precision, remat)
    logp = jax.nn.log_softmax(lg[:, :-1].astype(jnp.float32), axis=-1)
    ll = jnp.take_along_axis(logp, tokens[:, 1:, None], axis=-1)[..., 0]
    return -jnp.mean(ll) + aux


KEYS = ("vocab_size", "hidden_size", "intermediate_size", "moe_intermediate_size",
        "num_hidden_layers", "first_k_dense_replace", "num_attention_heads",
        "kv_lora_rank", "qk_nope_head_dim", "qk_rope_head_dim", "v_head_dim",
        "n_routed_experts", "expert_parallel", "num_experts_per_tok",
        "n_shared_experts", "norm_topk_prob", "routed_scaling_factor",
        "aux_loss_alpha", "rms_norm_eps", "rope_theta")


def _items(cfg: dict) -> tuple:
    if cfg.get("q_lora_rank") or cfg["hidden_act"] != "silu" or not cfg["seq_aux"]:
        raise ValueError("the reference computes MLA without a query low-rank "
                         "projection, silu experts and the sequence-wise loss")
    rs = cfg["rope_scaling"]
    return (tuple((k, cfg[k]) for k in KEYS),
            None if rs is None else tuple(sorted(rs.items())))


@functools.partial(jax.jit, static_argnames=("items", "precision", "dtype", "remat"))
def _loss_and_grad(params, tokens, items, precision, dtype, remat=False):
    cfg = dict(items[0], rope_scaling=None if items[1] is None else dict(items[1]))
    low = jax.tree.map(lambda p: p.astype(dtype), params)
    val, g = jax.value_and_grad(
        lambda p: loss(cfg, p, tokens, precision, remat))(low)
    return val, jax.tree.map(lambda x: x.astype(jnp.float32), g)


def loss_and_grad(cfg: dict, params: dict, tokens, precision=HIGHEST,
                  dtype=jnp.float32, remat=False):
    """(loss, float32 gradient) of the mean loss over tokens [B, S], with
    the parameters and activations in ``dtype``."""
    return _loss_and_grad(params, jnp.asarray(tokens), _items(cfg), precision,
                          jnp.dtype(dtype), remat)
