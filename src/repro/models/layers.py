"""Shared transformer layers: RMSNorm, RoPE, activations, GQA / MLA /
sliding-window attention (train + prefill + single-token decode), MLPs.

All functions are pure; parameters come in as pytrees declared by the
``*_defs`` functions in terms of :class:`repro.models.params.ParamDef`.
"""
from __future__ import annotations

import math
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from ..configs.base import AttentionSpec, ModelConfig, YarnSpec
from .params import ParamDef

NEG_INF = -1e30


# ---------------------------------------------------------------------------
# basics
# ---------------------------------------------------------------------------

def rms_norm(x, gamma, eps=1e-5):
    dt = x.dtype
    x = x.astype(jnp.float32)
    x = x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)
    return (x * gamma.astype(jnp.float32)).astype(dt)


def activate(x, kind: str):
    if kind == "silu":
        return jax.nn.silu(x)
    if kind == "gelu":
        return jax.nn.gelu(x)
    if kind == "relu2":          # squared ReLU (nemotron / rwkv channel-mix)
        r = jax.nn.relu(x)
        return r * r
    raise ValueError(f"unknown activation {kind!r}")


def yarn_mscale(factor: float, mscale: float) -> float:
    """YaRN's attention-temperature factor: 0.1·mscale·ln(factor) + 1."""
    return 1.0 if factor <= 1.0 else 0.1 * mscale * math.log(factor) + 1.0


def _yarn_correction_range(y: YarnSpec, dim: int, theta: float):
    """The rotary dimensions (as pair indices) between which YaRN ramps
    from plain to interpolated frequencies: the pairs that turn
    ``beta_fast`` and ``beta_slow`` times over the original length."""
    def corr(n_rot):
        return (dim * math.log(y.original_max_position / (n_rot * 2 * math.pi))
                / (2 * math.log(theta)))
    lo = math.floor(corr(y.beta_fast))
    hi = math.ceil(corr(y.beta_slow))
    return max(lo, 0), min(hi, dim - 1)


def rope_freqs(head_dim: int, theta: float, scaling: Optional[YarnSpec] = None):
    if scaling is None:
        return 1.0 / (theta ** (jnp.arange(0, head_dim, 2, dtype=jnp.float32) / head_dim))
    # YaRN: high frequencies (below the ramp) keep their plain value, low
    # ones (above it) are divided by the factor, linear blend between
    base = theta ** (np.arange(0, head_dim, 2, dtype=np.float64) / head_dim)
    lo, hi = _yarn_correction_range(scaling, head_dim, theta)
    ramp = np.clip((np.arange(head_dim // 2) - lo) / max(hi - lo, 0.001), 0.0, 1.0)
    keep = 1.0 - ramp
    inv = (1.0 / (scaling.factor * base)) * (1.0 - keep) + (1.0 / base) * keep
    return jnp.asarray(inv, jnp.float32)


def apply_rope(x, positions, theta: float, scaling: Optional[YarnSpec] = None,
               interleaved: bool = False):
    """x: [..., S, H, D] (D even), positions: [..., S].

    ``interleaved`` takes the rotated pairs as (2i, 2i+1) and returns
    them de-interleaved, (i, i + D/2): DeepSeek-V2's layout.  Queries and
    keys go through the same map, so their products are those of the
    published model."""
    d = x.shape[-1]
    inv = rope_freqs(d, theta, scaling)              # [D/2]
    ang = positions[..., None].astype(jnp.float32) * inv     # [..., S, D/2]
    cos, sin = jnp.cos(ang)[..., None, :], jnp.sin(ang)[..., None, :]
    if scaling is not None:
        f = (yarn_mscale(scaling.factor, scaling.mscale)
             / yarn_mscale(scaling.factor, scaling.mscale_all_dim))
        if f != 1.0:
            cos, sin = cos * f, sin * f
    xf = x.astype(jnp.float32)
    if interleaved:
        x1, x2 = xf[..., 0::2], xf[..., 1::2]
    else:
        x1, x2 = jnp.split(xf, 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1)
    return out.astype(x.dtype)


# ---------------------------------------------------------------------------
# dense MLP
# ---------------------------------------------------------------------------

def mlp_defs(d_model: int, d_ff: int, gated: bool) -> dict:
    d = {
        "w_in": ParamDef((d_model, d_ff), ("embed", "ff")),
        "w_out": ParamDef((d_ff, d_model), ("ff", "embed")),
    }
    if gated:
        d["w_gate"] = ParamDef((d_model, d_ff), ("embed", "ff"))
    return d


def mlp(p, x, activation: str):
    h = x @ p["w_in"]
    if "w_gate" in p:
        h = activate(x @ p["w_gate"], activation) * h
    else:
        h = activate(h, activation)
    return h @ p["w_out"]


# ---------------------------------------------------------------------------
# attention (GQA, optional qk-norm / sliding window) — batched full-seq form
# ---------------------------------------------------------------------------

def gqa_defs(d_model: int, a: AttentionSpec) -> dict:
    d = {
        "wq": ParamDef((d_model, a.n_heads, a.head_dim), ("embed", "heads", "hd")),
        "wk": ParamDef((d_model, a.n_kv_heads, a.head_dim), ("embed", "kv", "hd")),
        "wv": ParamDef((d_model, a.n_kv_heads, a.head_dim), ("embed", "kv", "hd")),
        "wo": ParamDef((a.n_heads, a.head_dim, d_model), ("heads", "hd", "embed")),
    }
    if a.qk_norm:
        d["q_norm"] = ParamDef((a.head_dim,), (None,), init="ones")
        d["k_norm"] = ParamDef((a.head_dim,), (None,), init="ones")
    return d


def _causal_window_mask(sq: int, skv: int, window: int, q_offset: int = 0):
    """[sq, skv] boolean mask.  q position i attends to kv position j iff
    j <= i and (window == 0 or i - j < window)."""
    qpos = jnp.arange(sq) + q_offset
    kpos = jnp.arange(skv)
    m = kpos[None, :] <= qpos[:, None]
    if window:
        m &= (qpos[:, None] - kpos[None, :]) < window
    return m


def _sdpa(q, k, v, mask):
    """q [B,S,H,D], k/v [B,T,Hkv,D] with H multiple of Hkv.

    §Perf traffic layout: the S×T score tensor is touched in as few
    passes as possible — max WITHOUT the mask (masked entries are real
    qk products of the same scale, so exp(l - m_all) stays in [0,1]),
    one fused mask+exp producing bf16 weights, and the 1/Σ normalizer
    folded into the small [B,S,H,D] output instead of a full S×T divide.
    """
    B, S, H, D = q.shape
    Hkv = k.shape[2]
    G = H // Hkv
    q = q.reshape(B, S, Hkv, G, D)
    logits = jnp.einsum("bshgd,bthd->bhgst", q, k,
                        preferred_element_type=jnp.float32)
    logits = logits * (1.0 / np.sqrt(D))
    m = jax.lax.stop_gradient(jnp.max(logits, axis=-1, keepdims=True))
    p = jnp.where(mask, jnp.exp(logits - m), 0.0)
    l = jnp.sum(p, axis=-1)                                  # [B,Hkv,G,S] f32
    out = jnp.einsum("bhgst,bthd->bshgd", p.astype(v.dtype), v,
                     preferred_element_type=jnp.float32)
    out = out / jnp.maximum(l, 1e-30).transpose(0, 3, 1, 2)[..., None]
    return out.reshape(B, S, H, D).astype(v.dtype)


def gqa_attention(p, a: AttentionSpec, x, positions, mask=None):
    """Full-sequence attention.  x: [B,S,d]; positions: [S] or [B,S]."""
    q = jnp.einsum("bsd,dhk->bshk", x, p["wq"])
    k = jnp.einsum("bsd,dhk->bshk", x, p["wk"])
    v = jnp.einsum("bsd,dhk->bshk", x, p["wv"])
    if a.qk_norm:
        q = rms_norm(q, p["q_norm"])
        k = rms_norm(k, p["k_norm"])
    if positions.ndim == 1:
        positions = positions[None, :]
    q = apply_rope(q, positions, a.rope_theta)
    k = apply_rope(k, positions, a.rope_theta)
    if mask is None:
        mask = _causal_window_mask(x.shape[1], x.shape[1], a.window)
    out = _sdpa(q, k, v, mask)
    return jnp.einsum("bshk,hkd->bsd", out, p["wo"]), (k, v)


def _decode_pos(pos, B: int):
    """Broadcast a scalar or per-slot ``[B]`` position vector to [B].

    Continuous batching gives every batch slot its own absolute position
    (requests join mid-stream); single-request decode passes a scalar.
    """
    return jnp.broadcast_to(jnp.asarray(pos, jnp.int32).reshape(-1), (B,))


def gqa_decode(p, a: AttentionSpec, x, cache_k, cache_v, pos):
    """Single-token decode.  x: [B,1,d]; cache_k/v: [B,T,Hkv,D] rolling or
    absolute buffer; ``pos``: scalar absolute position of the new token,
    or a per-slot ``[B]`` vector (continuous batching — every slot decodes
    at its own position).

    With a sliding window the cache length T == window and entries are a
    ring buffer indexed pos % window; otherwise T is the max seq len.
    """
    B, _, _ = x.shape
    T = cache_k.shape[1]
    q = jnp.einsum("bsd,dhk->bshk", x, p["wq"])
    k = jnp.einsum("bsd,dhk->bshk", x, p["wk"])
    v = jnp.einsum("bsd,dhk->bshk", x, p["wv"])
    if a.qk_norm:
        q = rms_norm(q, p["q_norm"])
        k = rms_norm(k, p["k_norm"])
    posb = _decode_pos(pos, B)
    posv = posb[:, None]                             # [B,1]
    q = apply_rope(q, posv, a.rope_theta)
    k = apply_rope(k, posv, a.rope_theta)
    slot = posb % T if a.window else posb            # [B]
    cache_k = cache_k.at[jnp.arange(B), slot].set(k[:, 0])
    cache_v = cache_v.at[jnp.arange(B), slot].set(v[:, 0])
    # validity: slots holding tokens <= pos and within window, per batch
    idx = jnp.arange(T)
    if a.window:
        # slot j holds absolute position: the most recent write <= pos
        age = (slot[:, None] - idx[None, :]) % T
        valid = age < jnp.minimum(posb + 1, T)[:, None]
    else:
        valid = idx[None, :] <= posb[:, None]
    mask = valid[:, None, None, None, :]             # [B,1,1,1,T] -> bhgst
    out = _sdpa(q, cache_k, cache_v, mask)
    return jnp.einsum("bshk,hkd->bsd", out, p["wo"]), (cache_k, cache_v)


# ---------------------------------------------------------------------------
# MLA (DeepSeek-V2 / MiniCPM3): low-rank latent KV, decoupled RoPE key
# ---------------------------------------------------------------------------

def mla_defs(d_model: int, a: AttentionSpec) -> dict:
    qk_head = a.qk_nope_dim + a.qk_rope_dim
    d: dict = {}
    if a.q_lora_rank:
        d["w_dq"] = ParamDef((d_model, a.q_lora_rank), ("embed", "qlora"))
        d["q_norm"] = ParamDef((a.q_lora_rank,), (None,), init="ones")
        d["w_uq"] = ParamDef((a.q_lora_rank, a.n_heads, qk_head), ("qlora", "heads", "hd"))
    else:
        d["w_uq"] = ParamDef((d_model, a.n_heads, qk_head), ("embed", "heads", "hd"))
    d["w_dkv"] = ParamDef((d_model, a.kv_lora_rank), ("embed", "kvlora"))
    d["kv_norm"] = ParamDef((a.kv_lora_rank,), (None,), init="ones")
    d["w_krope"] = ParamDef((d_model, a.qk_rope_dim), ("embed", None))
    d["w_uk"] = ParamDef((a.kv_lora_rank, a.n_heads, a.qk_nope_dim), ("kvlora", "heads", "hd"))
    d["w_uv"] = ParamDef((a.kv_lora_rank, a.n_heads, a.v_head_dim), ("kvlora", "heads", "hd"))
    d["wo"] = ParamDef((a.n_heads, a.v_head_dim, d_model), ("heads", "hd", "embed"))
    return d


def _mla_rope(a: AttentionSpec, x, positions):
    return apply_rope(x, positions, a.rope_theta, a.rope_scaling,
                      a.rope_interleaved)


def mla_softmax_scale(a: AttentionSpec):
    """(qk_nope + qk_rope)^-1/2, times YaRN's mscale(mscale_all_dim)^2
    where the rotary embedding is YaRN-scaled."""
    scale = 1.0 / jnp.sqrt(a.qk_nope_dim + a.qk_rope_dim)
    y = a.rope_scaling
    if y is not None and y.mscale_all_dim:
        m = yarn_mscale(y.factor, y.mscale_all_dim)
        scale = scale * (m * m)
    return scale


def _mla_q(p, a: AttentionSpec, x, positions, eps):
    if a.q_lora_rank:
        cq = rms_norm(x @ p["w_dq"], p["q_norm"], eps)
        q = jnp.einsum("bsr,rhk->bshk", cq, p["w_uq"])
    else:
        q = jnp.einsum("bsd,dhk->bshk", x, p["w_uq"])
    q_nope, q_rope = q[..., :a.qk_nope_dim], q[..., a.qk_nope_dim:]
    q_rope = _mla_rope(a, q_rope, positions)
    return q_nope, q_rope


def mla_attention(p, a: AttentionSpec, x, positions, mask=None, eps=1e-5):
    """Full-sequence MLA.  Returns output and the latent cache pieces.
    ``eps`` is the model's RMS-norm epsilon (the latent norms')."""
    B, S, _ = x.shape
    if positions.ndim == 1:
        positions = positions[None, :]
    q_nope, q_rope = _mla_q(p, a, x, positions, eps)
    c_kv = rms_norm(x @ p["w_dkv"], p["kv_norm"], eps)      # [B,S,R]
    k_rope = _mla_rope(a, (x @ p["w_krope"])[:, :, None, :],
                       positions)                            # [B,S,1,Dr]
    k_nope = jnp.einsum("bsr,rhk->bshk", c_kv, p["w_uk"])
    v = jnp.einsum("bsr,rhk->bshk", c_kv, p["w_uv"])
    scale = mla_softmax_scale(a)
    k_rope_sq = k_rope.squeeze(2)                            # [B,S,Dr]
    logits = (jnp.einsum("bshk,bthk->bhst", q_nope, k_nope) +
              jnp.einsum("bshk,btk->bhst", q_rope, k_rope_sq)
              ).astype(jnp.float32) * scale
    if mask is None:
        mask = _causal_window_mask(S, S, a.window)
    logits = jnp.where(mask[None, None], logits, NEG_INF)
    w = jax.nn.softmax(logits, axis=-1).astype(v.dtype)
    out = jnp.einsum("bhst,bthk->bshk", w, v)
    return jnp.einsum("bshk,hkd->bsd", out, p["wo"]), (c_kv, k_rope.squeeze(2))


def mla_decode(p, a: AttentionSpec, x, cache_c, cache_kr, pos, eps=1e-5):
    """Weight-absorbed single-token MLA decode.

    cache_c: [B,T,R] latent; cache_kr: [B,T,Dr] rope key; ``pos``:
    scalar or per-slot ``[B]`` absolute positions (see gqa_decode).
    score_h(t) = q_nope_h · (c_t W_uk,h) + q_rope_h · k_rope_t
               = (W_uk,h^T q_nope_h) · c_t + q_rope_h · k_rope_t
    out_h = Σ_t w_t (c_t W_uv,h)  = (Σ_t w_t c_t) W_uv,h   (absorbed)
    """
    B = x.shape[0]
    posb = _decode_pos(pos, B)
    posv = posb[:, None]                                     # [B,1]
    q_nope, q_rope = _mla_q(p, a, x, posv, eps)              # [B,1,H,*]
    c_new = rms_norm(x @ p["w_dkv"], p["kv_norm"], eps)      # [B,1,R]
    kr_new = _mla_rope(a, (x @ p["w_krope"])[:, :, None, :],
                       posv).squeeze(2)                      # [B,1,Dr]
    cache_c = cache_c.at[jnp.arange(B), posb].set(c_new[:, 0])
    cache_kr = cache_kr.at[jnp.arange(B), posb].set(kr_new[:, 0])
    q_abs = jnp.einsum("bshk,rhk->bshr", q_nope, p["w_uk"])  # [B,1,H,R]
    scale = mla_softmax_scale(a)
    logits = (jnp.einsum("bshr,btr->bhst", q_abs, cache_c) +
              jnp.einsum("bshk,btk->bhst", q_rope, cache_kr)).astype(jnp.float32)
    logits = logits * scale
    valid = (jnp.arange(cache_c.shape[1])[None, :] <= posb[:, None])
    logits = jnp.where(valid[:, None, None, :], logits, NEG_INF)
    w = jax.nn.softmax(logits, axis=-1).astype(cache_c.dtype)
    ctx = jnp.einsum("bhst,btr->bshr", w, cache_c)           # [B,1,H,R]
    out = jnp.einsum("bshr,rhk->bshk", ctx, p["w_uv"])
    return jnp.einsum("bshk,hkd->bsd", out, p["wo"]), (cache_c, cache_kr)
