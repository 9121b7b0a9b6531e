"""Model assembly: config -> param defs -> forward / loss / decode.

Layers are grouped into homogeneous *segments* (identical block
structure) and executed with ``lax.scan`` over stacked parameters, so
the HLO stays compact for 512-device dry-run compiles:

  dense/vlm/audio : [("dense", L)]
  deepseek-v2     : [("dense", 1), ("moe", 59)]
  dbrx            : [("moe", 40)]
  rwkv6           : [("rwkv", 32)]
  zamba2          : [("hybrid", 9 units x (6 mamba + shared attn block))]
                    (shared attention params live outside the stack)
"""
from __future__ import annotations

from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from ..configs.base import ModelConfig
from . import layers as L
from . import mamba2 as M2
from . import moe as MOE
from . import rwkv6 as R6
from .params import ParamDef, shard_hint, tree_map_defs


class Segment(NamedTuple):
    kind: str      # dense | moe | rwkv | hybrid
    n: int         # scan length (layers, or units for hybrid)


def segments(cfg: ModelConfig):
    if cfg.arch_type == "ssm" and cfg.rwkv is not None:
        return [Segment("rwkv", cfg.n_layers)]
    if cfg.hybrid_attn_every:
        assert cfg.n_layers % cfg.hybrid_attn_every == 0
        return [Segment("hybrid", cfg.n_layers // cfg.hybrid_attn_every)]
    if cfg.is_moe:
        segs = []
        if cfg.n_dense_layers:
            segs.append(Segment("dense", cfg.n_dense_layers))
        segs.append(Segment("moe", cfg.n_layers - cfg.n_dense_layers))
        return segs
    return [Segment("dense", cfg.n_layers)]


# ---------------------------------------------------------------------------
# parameter definitions
# ---------------------------------------------------------------------------

def _attn_defs(cfg: ModelConfig):
    a = cfg.attention
    return L.mla_defs(cfg.d_model, a) if a.kind == "mla" else L.gqa_defs(cfg.d_model, a)


def _block_defs(cfg: ModelConfig, kind: str) -> dict:
    D = cfg.d_model
    norm = lambda: ParamDef((D,), (None,), init="ones")
    if kind == "dense":
        return {"ln1": norm(), "attn": _attn_defs(cfg), "ln2": norm(),
                "mlp": L.mlp_defs(D, cfg.d_ff, gated=cfg.activation != "relu2")}
    if kind == "moe":
        return {"ln1": norm(), "attn": _attn_defs(cfg), "ln2": norm(),
                "moe": MOE.moe_defs(D, cfg.moe)}
    if kind == "rwkv":
        return {"ln1": norm(), "tm": R6.rwkv6_defs(D, cfg.d_ff, cfg.rwkv),
                "ln2": norm()}
    if kind == "mamba":
        return {"ln": norm(), "m": M2.mamba2_defs(D, cfg.ssm)}
    raise ValueError(kind)


def _stack(defs, n: int, axis_name="layers"):
    return tree_map_defs(
        lambda d: ParamDef((n,) + d.shape, (axis_name,) + d.axes, d.init, d.scale),
        defs)


def param_defs(cfg: ModelConfig) -> dict:
    D = cfg.d_model
    defs: dict = {
        "embed": ParamDef((cfg.vocab, D), ("vocab", "embed"), init="normal"),
        "final_norm": ParamDef((D,), (None,), init="ones"),
    }
    if not cfg.tie_embeddings:
        defs["lm_head"] = ParamDef((D, cfg.vocab), ("embed", "vocab"))
    for i, seg in enumerate(segments(cfg)):
        if seg.kind == "hybrid":
            unit = _stack(_block_defs(cfg, "mamba"), cfg.hybrid_attn_every, "sub")
            defs[f"seg_{i}"] = _stack(unit, seg.n, "units")
            defs["shared_attn"] = _block_defs(cfg, "dense")
        else:
            defs[f"seg_{i}"] = _stack(_block_defs(cfg, seg.kind), seg.n)
    return defs


# ---------------------------------------------------------------------------
# block bodies (full-sequence form)
# ---------------------------------------------------------------------------

def _attention(cfg, p, x, positions):
    if cfg.attention.kind == "mla":
        with jax.named_scope("mla"):
            out, kv = L.mla_attention(p, cfg.attention, x, positions,
                                      eps=cfg.rms_eps)
    else:
        out, kv = L.gqa_attention(p, cfg.attention, x, positions)
    return out, kv


def _kv_entry(cfg, kv):
    """Full-seq attention cache pieces, keyed like ``_attn_cache_defs``."""
    if cfg.attention.kind == "mla":
        return {"c": kv[0], "kr": kv[1]}
    return {"k": kv[0], "v": kv[1]}


def _zero_stats(kind: str) -> dict:
    """A block's scalars that the scan carries: the balance loss, and in
    an MoE segment the routing counters."""
    z = jnp.zeros((), jnp.float32)
    if kind == "moe":
        return {"aux": z, "moe_pairs": z, "moe_load_max": z}
    return {"aux": z}


def _add_stats(acc: dict, new: dict) -> dict:
    """Sums over layers, but the largest load is a maximum."""
    out = dict(acc)
    for k, v in new.items():
        if k not in out:
            out[k] = v
        elif k == "moe_load_max":
            out[k] = jnp.maximum(out[k], v)
        else:
            out[k] = out[k] + v
    return out


def _dense_block(cfg, p, x, positions):
    h, kv = _attention(cfg, p["attn"], L.rms_norm(x, p["ln1"], cfg.rms_eps), positions)
    x = x + h
    x = x + L.mlp(p["mlp"], L.rms_norm(x, p["ln2"], cfg.rms_eps), cfg.activation)
    return x, _zero_stats("dense"), _kv_entry(cfg, kv)


def _moe_block(cfg, p, x, positions):
    h, kv = _attention(cfg, p["attn"], L.rms_norm(x, p["ln1"], cfg.rms_eps), positions)
    x = x + h
    with jax.named_scope("moe"):
        out, aux, cnt = MOE.moe_ffn(p["moe"], L.rms_norm(x, p["ln2"], cfg.rms_eps),
                                    cfg.moe, cfg.activation)
    stats = {"aux": aux, "moe_pairs": cnt["pairs"], "moe_load_max": cnt["load_max"]}
    return x + out, stats, _kv_entry(cfg, kv)


def _rwkv_block(cfg, p, x, positions):
    h, (tm_x, wkv) = R6.rwkv6_timemix(p["tm"], cfg.rwkv,
                                      L.rms_norm(x, p["ln1"], cfg.rms_eps))
    x = x + h
    h, cm_x = R6.rwkv6_channelmix(p["tm"], L.rms_norm(x, p["ln2"], cfg.rms_eps))
    return (x + h, _zero_stats("rwkv"),
            {"wkv": wkv, "tm_x": tm_x, "cm_x": cm_x})


def _mamba_block(cfg, p, x):
    h, (conv, ssm) = M2.mamba2_forward(p["m"], cfg.ssm,
                                       L.rms_norm(x, p["ln"], cfg.rms_eps))
    return x + h, {"conv": conv, "ssm": ssm}


_SP_SPEC = P(None, "model", None)  # sequence-parallel activation layout


def _run_segment(cfg, seg: Segment, p_stack, shared, x, positions, remat=False,
                 param_hook=None, collect_cache=False):
    """Scan a stacked segment over x.  Returns (x, stats, cache_ys), stats
    being the blocks' scalars over the segment (``_add_stats``).

    ``param_hook(p_layer, layer_idx)`` is applied to each scanned
    layer-slice of the parameter stack — identity by default.  The
    blocked aggregation mode injects its gather/robust-aggregate
    custom-VJP barrier here, so per-worker layer gradients are
    aggregated inside the backward scan and the full G matrix never
    materializes (DESIGN.md §2); ``layer_idx`` (f32 scalar) lets the
    barrier fold the layer position into its attack key so injected
    noise decorrelates across the scanned layers, not just across
    segments.

    ``collect_cache=True`` (fused prefill, DESIGN.md §Serve) stacks
    each layer's full-sequence cache pieces as scan ys — the stacked
    leading axis matches the ``cache_defs`` layout.  Training keeps
    ys=None so no cache memory rides along the backward pass.
    """

    def body(carry, idx_p):
        idx, p_l = idx_p
        x, stats = carry
        if param_hook is not None:
            p_l = param_hook(p_l, idx)
        x = shard_hint(x, _SP_SPEC)
        if seg.kind == "dense":
            x, a, ent = _dense_block(cfg, p_l, x, positions)
        elif seg.kind == "moe":
            x, a, ent = _moe_block(cfg, p_l, x, positions)
        elif seg.kind == "rwkv":
            x, a, ent = _rwkv_block(cfg, p_l, x, positions)
        elif seg.kind == "hybrid":
            def sub(xc, p_m):
                xc, st = _mamba_block(cfg, p_m, xc)
                return xc, (st if collect_cache else None)
            x, m_ent = jax.lax.scan(sub, x, p_l)
            x, a, a_ent = _dense_block(cfg, shared, x, positions)
            ent = {"mamba": m_ent, "attn": a_ent}
        else:
            raise ValueError(seg.kind)
        return (x, _add_stats(stats, a)), (ent if collect_cache else None)

    if remat:
        body = jax.checkpoint(body, prevent_cse=False)
    (x, stats), ys = jax.lax.scan(body, (x, _zero_stats(seg.kind)),
                                  (jnp.arange(seg.n, dtype=jnp.float32), p_stack))
    return x, stats, ys


# ---------------------------------------------------------------------------
# public forward / loss
# ---------------------------------------------------------------------------

def embed_inputs(cfg: ModelConfig, params, tokens, prefix_embed=None):
    x = params["embed"][tokens]
    if prefix_embed is not None:
        x = jnp.concatenate([prefix_embed.astype(x.dtype), x], axis=1)
    return x


def forward(cfg: ModelConfig, params, tokens, prefix_embed=None, remat=False,
            seg_hooks=None, top_hook=None):
    """tokens [B,S_tok] (+ optional prefix [B,P,D]) -> logits [B,S,V], aux.

    Blocked-aggregation hooks: ``seg_hooks["seg_i"]`` is applied to each
    scanned layer slice of segment i; ``top_hook`` once to the
    non-stacked bucket (embed / final_norm / lm_head / shared_attn).
    """
    logits, stats = _forward(cfg, params, tokens, prefix_embed, remat,
                             seg_hooks, top_hook)
    return logits, stats["aux"]


def _forward(cfg, params, tokens, prefix_embed, remat, seg_hooks, top_hook):
    """:func:`forward` with every block scalar: (logits, stats)."""
    if top_hook is not None:
        top = {k: v for k, v in params.items() if not k.startswith("seg_")}
        top = top_hook(top)
        params = {**params, **top}
    x = embed_inputs(cfg, params, tokens, prefix_embed)
    S = x.shape[1]
    positions = jnp.arange(S)
    stats = _zero_stats("dense")
    for i, seg in enumerate(segments(cfg)):
        hook = (seg_hooks or {}).get(f"seg_{i}")
        x, st, _ = _run_segment(cfg, seg, params[f"seg_{i}"],
                                params.get("shared_attn"), x, positions, remat,
                                hook)
        stats = _add_stats(stats, st)
    x = L.rms_norm(x, params["final_norm"], cfg.rms_eps)
    head = params["embed"].T if cfg.tie_embeddings else params["lm_head"]
    with jax.named_scope("lm_head"):
        logits = x @ head
        logits = shard_hint(logits, P(None, None, "model"))
    return logits, stats


def loss_fn(cfg: ModelConfig, params, batch, remat=False, seg_hooks=None,
            top_hook=None):
    """Next-token cross-entropy over the token positions (prefix embeds
    from modality frontends are context only), plus the balance loss.
    The metrics hold ``ce``, ``aux`` and, in an MoE model, the routing
    counters ``moe_pairs`` and ``moe_load_max``."""
    tokens = batch["tokens"]
    logits, stats = _forward(cfg, params, tokens, batch.get("prefix_embed"),
                             remat, seg_hooks, top_hook)
    aux = stats["aux"]
    Pfx = logits.shape[1] - tokens.shape[1]
    # logits at position Pfx+t predict tokens[t+1]
    with jax.named_scope("lm_head"):
        pred = logits[:, Pfx:-1]
        tgt = tokens[:, 1:]
        logp = jax.nn.log_softmax(pred.astype(jnp.float32), axis=-1)
        ll = jnp.take_along_axis(logp, tgt[..., None], axis=-1)[..., 0]
        mask = batch.get("loss_mask")
        if mask is not None:
            mask = mask[:, 1:]
            ce = -jnp.sum(ll * mask) / jnp.maximum(jnp.sum(mask), 1.0)
        else:
            ce = -jnp.mean(ll)
    return ce + aux, {"ce": ce, **stats}


# ---------------------------------------------------------------------------
# decode path (single new token against cache/state)
# ---------------------------------------------------------------------------

def _attn_cache_defs(cfg: ModelConfig, batch: int, seq_len: int):
    a = cfg.attention
    T = min(a.window, seq_len) if a.window else seq_len
    if a.kind == "mla":
        return {"c": ((batch, T, a.kv_lora_rank), ("batch", "seq", None)),
                "kr": ((batch, T, a.qk_rope_dim), ("batch", "seq", None))}
    return {"k": ((batch, T, a.n_kv_heads, a.head_dim), ("batch", "seq", "kv", "hd")),
            "v": ((batch, T, a.n_kv_heads, a.head_dim), ("batch", "seq", "kv", "hd"))}


def _mamba_cache_defs(cfg: ModelConfig, batch: int):
    di, H = M2.dims(cfg.d_model, cfg.ssm)
    N, W = cfg.ssm.state_dim, cfg.ssm.conv_width
    Pd = di // H
    return {"conv": ((batch, W - 1, di + 2 * N), ("batch", None, "inner")),
            "ssm": ((batch, H, N, Pd), ("batch", "heads", None, None))}


def cache_defs(cfg: ModelConfig, batch: int, seq_len: int) -> dict:
    """Shapes+logical axes of the decode cache, mirroring param stacking."""
    out: dict = {}
    for i, seg in enumerate(segments(cfg)):
        if seg.kind in ("dense", "moe"):
            out[f"seg_{i}"] = {
                k: ((seg.n,) + s, ("layers",) + ax)
                for k, (s, ax) in _attn_cache_defs(cfg, batch, seq_len).items()}
        elif seg.kind == "rwkv":
            D = cfg.d_model
            H, K = D // cfg.rwkv.head_dim, cfg.rwkv.head_dim
            out[f"seg_{i}"] = {
                "wkv": ((seg.n, batch, H, K, K), ("layers", "batch", "heads", None, None)),
                "tm_x": ((seg.n, batch, 1, D), ("layers", "batch", None, None)),
                "cm_x": ((seg.n, batch, 1, D), ("layers", "batch", None, None)),
            }
        elif seg.kind == "hybrid":
            sub = {k: ((seg.n, cfg.hybrid_attn_every) + s, ("units", "sub") + ax)
                   for k, (s, ax) in _mamba_cache_defs(cfg, batch).items()}
            attn = {k: ((seg.n,) + s, ("units",) + ax)
                    for k, (s, ax) in _attn_cache_defs(cfg, batch, seq_len).items()}
            out[f"seg_{i}"] = {"mamba": sub, "attn": attn}
    return out


def init_cache(cfg: ModelConfig, batch: int, seq_len: int, dtype=jnp.bfloat16):
    defs = cache_defs(cfg, batch, seq_len)
    return jax.tree.map(
        lambda sd: jnp.zeros(sd[0], dtype), defs,
        is_leaf=lambda x: isinstance(x, tuple) and isinstance(x[0], tuple))


def _attn_decode(cfg, p, x, cache, pos):
    a = cfg.attention
    if a.kind == "mla":
        with jax.named_scope("mla"):
            out, (c, kr) = L.mla_decode(p, a, x, cache["c"], cache["kr"], pos,
                                        eps=cfg.rms_eps)
        return out, {"c": c, "kr": kr}
    out, (k, v) = L.gqa_decode(p, a, x, cache["k"], cache["v"], pos)
    return out, {"k": k, "v": v}


def decode_step(cfg: ModelConfig, params, cache, token, pos):
    """token [B,1] int32; pos scalar int32 (absolute position) or a
    per-slot ``[B]`` vector — continuous batching decodes every slot at
    its own position (recurrent families ignore pos entirely).

    Returns (logits [B,1,V], new cache).  One new token, O(1) or O(T)
    work per layer depending on the block family.
    """
    x = params["embed"][token]

    new_cache: dict = {}
    for i, seg in enumerate(segments(cfg)):
        p_stack = params[f"seg_{i}"]
        c_stack = cache[f"seg_{i}"]
        if seg.kind in ("dense", "moe"):
            def body(x, pc):
                p_l, c_l = pc
                h, c_new = _attn_decode(cfg, p_l["attn"],
                                        L.rms_norm(x, p_l["ln1"], cfg.rms_eps), c_l, pos)
                x = x + h
                hin = L.rms_norm(x, p_l["ln2"], cfg.rms_eps)
                if seg.kind == "moe":
                    with jax.named_scope("moe"):
                        out, _, _ = MOE.moe_ffn(p_l["moe"], hin, cfg.moe,
                                                cfg.activation)
                    x = x + out
                else:
                    x = x + L.mlp(p_l["mlp"], hin, cfg.activation)
                return x, c_new
            x, c_new = jax.lax.scan(body, x, (p_stack, c_stack))
        elif seg.kind == "rwkv":
            def body(x, pc):
                p_l, c_l = pc
                h, (tm_x, wkv) = R6.rwkv6_timemix(
                    p_l["tm"], cfg.rwkv, L.rms_norm(x, p_l["ln1"], cfg.rms_eps),
                    last_x=c_l["tm_x"], state=c_l["wkv"].astype(jnp.float32))
                x = x + h
                h, cm_x = R6.rwkv6_channelmix(
                    p_l["tm"], L.rms_norm(x, p_l["ln2"], cfg.rms_eps),
                    last_x=c_l["cm_x"])
                x = x + h
                return x, {"wkv": wkv.astype(c_l["wkv"].dtype), "tm_x": tm_x,
                           "cm_x": cm_x}
            x, c_new = jax.lax.scan(body, x, (p_stack, c_stack))
        elif seg.kind == "hybrid":
            shared = params["shared_attn"]
            def body(x, pc):
                p_u, c_u = pc
                def sub(x, pm_cm):
                    p_m, c_m = pm_cm
                    h, (conv, ssm) = M2.mamba2_decode(
                        p_m["m"], cfg.ssm, L.rms_norm(x, p_m["ln"], cfg.rms_eps),
                        c_m["conv"], c_m["ssm"].astype(jnp.float32))
                    return x + h, {"conv": conv, "ssm": ssm.astype(c_m["ssm"].dtype)}
                x, m_new = jax.lax.scan(sub, x, (p_u["mamba"] if "mamba" in p_u else p_u,
                                                 c_u["mamba"]))
                h, a_new = _attn_decode(cfg, shared["attn"],
                                        L.rms_norm(x, shared["ln1"], cfg.rms_eps),
                                        c_u["attn"], pos)
                x = x + h
                x = x + L.mlp(shared["mlp"], L.rms_norm(x, shared["ln2"], cfg.rms_eps),
                              cfg.activation)
                return x, {"mamba": m_new, "attn": a_new}
            x, c_new = jax.lax.scan(body, x, (p_stack, c_stack))
        else:
            raise ValueError(seg.kind)
        new_cache[f"seg_{i}"] = c_new

    x = L.rms_norm(x, params["final_norm"], cfg.rms_eps)
    head = params["embed"].T if cfg.tie_embeddings else params["lm_head"]
    return x @ head, new_cache


# ---------------------------------------------------------------------------
# fused prefill: one dispatch writes the whole prompt into the cache
# ---------------------------------------------------------------------------

def _seq_write(buf, ent, window: int):
    """Write full-seq attention entries into a decode cache buffer.

    buf: [stack..., B, T, ...] (seq at axis 2); ent: [stack..., B, S, ...].
    Non-windowed buffers take positions 0..S-1 directly; windowed ring
    buffers keep the last min(S, T) positions at slot = pos % T, exactly
    where ``gqa_decode`` would have left them after S sequential steps.
    """
    T, S = buf.shape[2], ent.shape[2]
    if not window and S > T:
        raise ValueError(f"prompt length {S} exceeds cache length {T}")
    keep = min(S, T)
    slots = np.arange(S - keep, S) % T
    return buf.at[:, :, slots].set(ent[:, :, S - keep:].astype(buf.dtype))


def _write_entries(cfg, seg: Segment, bufs, ent, S: int):
    w = cfg.attention.window
    if seg.kind in ("dense", "moe"):
        return {k: _seq_write(bufs[k], ent[k], w) for k in bufs}
    if seg.kind == "rwkv":
        return {k: ent[k].astype(bufs[k].dtype) for k in bufs}
    if seg.kind == "hybrid":
        return {"mamba": {k: ent["mamba"][k].astype(bufs["mamba"][k].dtype)
                          for k in bufs["mamba"]},
                "attn": {k: _seq_write(bufs["attn"][k], ent["attn"][k], w)
                         for k in bufs["attn"]}}
    raise ValueError(seg.kind)


def prefill_cache(cfg: ModelConfig, params, tokens, cache, prefix_embed=None):
    """Fused prefill: ONE dispatch computes the full-sequence logits AND
    writes the whole prompt's KV/state into the decode cache — replaces
    the O(prompt_len)-dispatch teacher-forced loop (ISSUE 8 satellite).

    tokens: [B,S] with B matching the cache batch dim.  Returns
    (logits [B,S,V], cache') positioned so ``decode_step`` continues at
    pos = S.  Attention families write per-position K/V (windowed ring
    buffers get the last ``window`` positions); recurrent families
    (rwkv / mamba) replace their O(1) states with the final-position
    state the full-sequence forward already computes.
    """
    x = embed_inputs(cfg, params, tokens, prefix_embed)
    S = x.shape[1]
    positions = jnp.arange(S)
    new_cache: dict = {}
    for i, seg in enumerate(segments(cfg)):
        x, _, ent = _run_segment(cfg, seg, params[f"seg_{i}"],
                                 params.get("shared_attn"), x, positions,
                                 collect_cache=True)
        new_cache[f"seg_{i}"] = _write_entries(cfg, seg, cache[f"seg_{i}"], ent, S)
    x = L.rms_norm(x, params["final_norm"], cfg.rms_eps)
    head = params["embed"].T if cfg.tie_embeddings else params["lm_head"]
    return x @ head, new_cache
