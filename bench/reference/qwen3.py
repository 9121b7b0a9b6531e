"""Plain Qwen3 language model: forward, next-token loss, gradient and an
AdamW step, written from the published description (Hugging Face
``Qwen3ForCausalLM``) in straightforward ``jax.numpy``.

- Pre-norm decoder layers: ``x += attn(rms(x))``, ``x += mlp(rms(x))``.
- Attention: grouped-query (query head h reads key/value head
  h // (H / K)), RMS norm of every query and key head over the head
  dimension (epsilon ``qk_norm_eps``) before rotary embedding, rotary
  embedding on the two halves of each head (``rotate_half``), causal
  softmax scaled by 1/sqrt(Dh).
- MLP: ``down(silu(gate(x)) * up(x))``.
- Final RMS norm; the head is the transposed embedding when tied.
- Loss: mean cross-entropy of every position predicting the next token.

Parameters are a dict of float32 arrays in the layout below (layers
stacked on a leading axis); :func:`init` makes them from a key.  Every
matrix product takes its ``precision`` and every activation the
``dtype`` it is given: float32 at ``HIGHEST`` is the reference, and
bfloat16 at ``DEFAULT`` the control of a lower precision.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

HIGHEST = jax.lax.Precision.HIGHEST


def shapes(cfg: dict) -> dict:
    """The parameter layout: {name: shape}, nested like the program's."""
    V, D, F = cfg["vocab_size"], cfg["hidden_size"], cfg["intermediate_size"]
    L, H = cfg["num_hidden_layers"], cfg["num_attention_heads"]
    K, Dh = cfg["num_key_value_heads"], cfg["head_dim"]
    out = {
        "embed": (V, D),
        "final_norm": (D,),
        "seg_0": {
            "attn": {"k_norm": (L, Dh), "q_norm": (L, Dh),
                     "wk": (L, D, K, Dh), "wo": (L, H, Dh, D),
                     "wq": (L, D, H, Dh), "wv": (L, D, K, Dh)},
            "ln1": (L, D), "ln2": (L, D),
            "mlp": {"w_gate": (L, D, F), "w_in": (L, D, F),
                    "w_out": (L, F, D)},
        },
    }
    if not cfg["tie_word_embeddings"]:
        out["lm_head"] = (D, V)
    return out


def _is_shape(x) -> bool:
    return isinstance(x, tuple) and all(isinstance(i, int) for i in x)


def shapes_struct(cfg: dict, dtype=jnp.float32) -> dict:
    """The layout as ``jax.ShapeDtypeStruct`` leaves."""
    return jax.tree.map(lambda s: jax.ShapeDtypeStruct(s, dtype), shapes(cfg),
                        is_leaf=_is_shape)


def init(cfg: dict, key, dtype=jnp.float32) -> dict:
    """Norm weights 1; every matrix N(0, initializer_range²)."""
    tree = shapes(cfg)
    paths, tdef = jax.tree_util.tree_flatten_with_path(tree, is_leaf=_is_shape)
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


def _rms(x, w, eps):
    xf = x.astype(jnp.float32)
    y = xf * jax.lax.rsqrt(jnp.mean(xf * xf, axis=-1, keepdims=True) + eps)
    return (y * w.astype(jnp.float32)).astype(x.dtype)


def _rope(x, theta: float):
    """x [B, S, heads, Dh]; positions 0..S-1."""
    S, Dh = x.shape[1], x.shape[-1]
    inv = 1.0 / (theta ** (np.arange(0, Dh, 2, dtype=np.float64) / Dh))
    ang = np.arange(S, dtype=np.float64)[:, None] * inv[None, :]
    cos = np.concatenate([np.cos(ang)] * 2, axis=-1)[None, :, None, :]
    sin = np.concatenate([np.sin(ang)] * 2, axis=-1)[None, :, None, :]
    xf = x.astype(jnp.float32)
    half = Dh // 2
    rot = jnp.concatenate([-xf[..., half:], xf[..., :half]], axis=-1)
    out = xf * jnp.asarray(cos, jnp.float32) + rot * jnp.asarray(sin, jnp.float32)
    return out.astype(x.dtype)


def _layer(cfg, x, p, precision):
    eps = cfg["rms_norm_eps"]
    H, K = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    B, S, _ = x.shape
    mm = functools.partial(jnp.einsum, precision=precision)
    h = _rms(x, p["ln1"], eps)
    q = mm("bsd,dhk->bshk", h, p["attn"]["wq"])
    k = mm("bsd,dhk->bshk", h, p["attn"]["wk"])
    v = mm("bsd,dhk->bshk", h, p["attn"]["wv"])
    qk_eps = cfg["qk_norm_eps"]
    q = _rope(_rms(q, p["attn"]["q_norm"], qk_eps), cfg["rope_theta"])
    k = _rope(_rms(k, p["attn"]["k_norm"], qk_eps), cfg["rope_theta"])
    k = jnp.repeat(k, H // K, axis=2)
    v = jnp.repeat(v, H // K, axis=2)
    scores = mm("bshk,bthk->bhst", q, k) / np.sqrt(q.shape[-1]).astype(np.float32)
    causal = np.tril(np.ones((S, S), bool))
    scores = jnp.where(causal, scores.astype(jnp.float32), -jnp.inf)
    probs = jax.nn.softmax(scores, axis=-1).astype(x.dtype)
    att = mm("bhst,bthk->bshk", probs, v)
    x = x + mm("bshk,hkd->bsd", att, p["attn"]["wo"])
    h = _rms(x, p["ln2"], eps)
    up = mm("bsd,df->bsf", h, p["mlp"]["w_in"])
    gate = mm("bsd,df->bsf", h, p["mlp"]["w_gate"])
    return x + mm("bsf,fd->bsd", jax.nn.silu(gate) * up, p["mlp"]["w_out"])


def logits(cfg: dict, params: dict, tokens, precision=HIGHEST, remat=False):
    """tokens [B, S] -> logits [B, S, V] in the parameters' dtype.
    ``remat`` recomputes each layer in the backward pass instead of
    keeping its activations (the same operations, less memory)."""
    x = params["embed"][tokens]

    def body(x, p):
        return _layer(cfg, x, p, precision), None

    if remat:
        body = jax.checkpoint(body)

    x, _ = jax.lax.scan(body, x, params["seg_0"])
    x = _rms(x, params["final_norm"], cfg["rms_norm_eps"])
    head = (params["embed"].T if cfg["tie_word_embeddings"]
            else params["lm_head"])
    return jnp.einsum("bsd,dv->bsv", x, head, precision=precision)


def loss(cfg: dict, params: dict, tokens, precision=HIGHEST, remat=False):
    """Mean next-token cross-entropy over every position of tokens [B, S]."""
    lg = logits(cfg, params, tokens, precision, remat)[:, :-1]
    lg = lg.astype(jnp.float32) if lg.dtype == jnp.float32 else lg
    logp = jax.nn.log_softmax(lg, axis=-1)
    ll = jnp.take_along_axis(logp, tokens[:, 1:, None], axis=-1)[..., 0]
    return -jnp.mean(ll.astype(jnp.float32))


@functools.partial(jax.jit, static_argnames=("cfg_items", "precision", "dtype",
                                             "remat"))
def _loss_and_grad(params, tokens, cfg_items, precision, dtype, remat=False):
    cfg = dict(cfg_items)
    low = jax.tree.map(lambda p: p.astype(dtype), params)
    val, g = jax.value_and_grad(
        lambda p: loss(cfg, p, tokens, precision, remat))(low)
    return val, jax.tree.map(lambda x: x.astype(jnp.float32), g)


def _items(cfg: dict) -> tuple:
    keys = ("vocab_size", "hidden_size", "intermediate_size",
            "num_hidden_layers", "num_attention_heads", "num_key_value_heads",
            "head_dim", "rms_norm_eps", "qk_norm_eps", "rope_theta",
            "tie_word_embeddings")
    return tuple((k, cfg[k]) for k in keys)


def loss_and_grad(cfg: dict, params: dict, tokens, precision=HIGHEST,
                  dtype=jnp.float32, remat=False):
    """(loss, float32 gradient) of the mean loss over tokens [B, S], with
    the parameters and activations in ``dtype``."""
    return _loss_and_grad(params, jnp.asarray(tokens), _items(cfg), precision,
                          jnp.dtype(dtype), remat)


def global_norm(tree) -> jax.Array:
    return jnp.sqrt(sum(jnp.sum(jnp.square(x.astype(jnp.float32)))
                        for x in jax.tree.leaves(tree)))


def leaf_norms(tree) -> jax.Array:
    """[n_leaves] float32: the l2 norm of every leaf, in tree order."""
    return jnp.stack([jnp.sqrt(jnp.sum(jnp.square(x.astype(jnp.float32))))
                      for x in jax.tree.leaves(tree)])


@functools.partial(jax.jit, static_argnames=("hp_items",),
                   donate_argnums=(0, 2))
def _adamw(params, grads, state, t, hp_items):
    hp = dict(hp_items)
    if hp["grad_clip"] > 0:
        n = global_norm(grads)
        scale = jnp.minimum(1.0, hp["grad_clip"] / jnp.maximum(n, 1e-9))
        grads = jax.tree.map(lambda g: g * scale, grads)
    b1, b2 = hp["b1"], hp["b2"]
    c1 = 1.0 - b1 ** (t + 1.0)
    c2 = 1.0 - b2 ** (t + 1.0)
    m = jax.tree.map(lambda m, g: b1 * m + (1 - b1) * g, state["m"], grads)
    v = jax.tree.map(lambda v, g: b2 * v + (1 - b2) * g * g, state["v"], grads)
    params = jax.tree.map(
        lambda p, m, v: p - hp["lr"] * ((m / c1) / (jnp.sqrt(v / c2) + hp["eps"])
                                        + hp["weight_decay"] * p),
        params, m, v)
    return params, {"m": m, "v": v}, leaf_norms(grads)


def adamw_step(params, grads, state, t: int, hp: dict):
    """AdamW with global-norm clipping (decoupled weight decay, bias
    correction).  Returns (params, state, the per-leaf norms of the
    clipped gradient the update used)."""
    items = tuple(sorted((k, float(hp[k])) for k in
                         ("lr", "b1", "b2", "eps", "weight_decay", "grad_clip")))
    return _adamw(params, grads, state, jnp.float32(t), items)


def zeros_like(tree):
    return jax.tree.map(lambda p: jnp.zeros(p.shape, jnp.float32), tree)


@jax.jit
def scale(tree, w):
    return jax.tree.map(lambda x: x * w, tree)


@functools.partial(jax.jit, donate_argnums=0)
def add_scaled(acc, tree, w):
    """acc + w·tree, leaf by leaf, in acc's place."""
    return jax.tree.map(lambda a, x: a + x * w, acc, tree)
