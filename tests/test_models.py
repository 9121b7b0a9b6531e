"""Per-architecture smoke tests: reduced variant of each assigned family
runs one forward/train step on CPU with correct shapes and no NaNs, and
the decode path agrees with the full-sequence forward."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import ARCHS, get_config
from repro.models import params as PM
from repro.models import transformer as TF

ARCH_IDS = list(ARCHS)


@pytest.fixture(scope="module")
def built():
    """Init reduced params once per arch (module scope for speed)."""
    cache = {}

    def get(name):
        if name not in cache:
            cfg = ARCHS[name].reduced()
            params = PM.init_params(TF.param_defs(cfg), jax.random.PRNGKey(0))
            cache[name] = (cfg, params)
        return cache[name]

    return get


def _batch(cfg, B=2, S=16, seed=0):
    key = jax.random.PRNGKey(seed)
    out = {"tokens": jax.random.randint(key, (B, S), 0, cfg.vocab)}
    if cfg.n_prefix_tokens:
        out["prefix_embed"] = 0.02 * jax.random.normal(
            key, (B, cfg.n_prefix_tokens, cfg.d_model), jnp.float32)
    return out


@pytest.mark.parametrize("name", ARCH_IDS)
def test_reduced_config_limits(name):
    cfg = ARCHS[name].reduced()
    assert cfg.d_model <= 512 and cfg.n_layers <= 4
    assert cfg.moe.n_experts <= 4


@pytest.mark.parametrize("name", ARCH_IDS)
def test_forward_shapes_and_finite(built, name):
    cfg, params = built(name)
    batch = _batch(cfg)
    logits, aux = TF.forward(cfg, params, batch["tokens"],
                             batch.get("prefix_embed"))
    S = batch["tokens"].shape[1] + cfg.n_prefix_tokens
    assert logits.shape == (2, S, cfg.vocab)
    assert bool(jnp.isfinite(logits).all())
    assert bool(jnp.isfinite(aux))


@pytest.mark.parametrize("name", ARCH_IDS)
def test_one_train_step_reduces_loss_direction(built, name):
    """loss_fn gradient step with a small lr must not produce NaN and the
    loss on the SAME batch must not increase (descent direction)."""
    cfg, params = built(name)
    batch = _batch(cfg, B=2, S=16)
    loss0, _ = TF.loss_fn(cfg, params, batch)
    grads = jax.grad(lambda p: TF.loss_fn(cfg, p, batch)[0])(params)
    params2 = jax.tree.map(lambda p, g: p - 1e-2 * g, params, grads)
    loss1, _ = TF.loss_fn(cfg, params2, batch)
    assert bool(jnp.isfinite(loss0)) and bool(jnp.isfinite(loss1))
    assert float(loss1) < float(loss0) + 1e-4, name


@pytest.mark.parametrize("name", ARCH_IDS)
def test_decode_matches_forward(built, name):
    """Token-by-token decode logits == full-sequence forward logits (MoE
    dispatch drops no tokens, so a T=B*S prefill and a T=B decode route
    alike)."""
    cfg, params = built(name)
    if cfg.n_prefix_tokens:
        pytest.skip("prefix-embed archs prefill differently (tested via fwd)")
    B, S = 2, 12
    toks = jax.random.randint(jax.random.PRNGKey(1), (B, S), 0, cfg.vocab)
    full, _ = TF.forward(cfg, params, toks)

    cache = TF.init_cache(cfg, B, S, jnp.float32)
    outs = []
    for t in range(S):
        logits, cache = TF.decode_step(cfg, params, cache, toks[:, t:t + 1],
                                       jnp.int32(t))
        outs.append(logits.reshape(B, -1))
    dec = jnp.stack(outs, axis=1)
    np.testing.assert_allclose(np.asarray(dec), np.asarray(full),
                               rtol=2e-2, atol=2e-2)


def test_sliding_window_mask_limits_context():
    from repro.models.layers import _causal_window_mask
    m = np.asarray(_causal_window_mask(8, 8, window=3))
    assert m[5, 5] and m[5, 3] and not m[5, 2] and not m[3, 5]


def test_window_variant_selected_for_long500k():
    from repro.configs import get_shape
    from repro.launch.specs import variant_for_shape
    cfg = get_config("qwen3-0.6b")
    v = variant_for_shape(cfg, get_shape("long_500k"))
    assert v.attention.window == 8192
    # MLA/ssm archs keep their native path
    v2 = variant_for_shape(get_config("minicpm3-4b"), get_shape("long_500k"))
    assert v2.attention.window == 0
    v3 = variant_for_shape(get_config("rwkv6-7b"), get_shape("long_500k"))
    assert v3.attention.kind == "none"


def test_windowed_decode_ring_buffer_matches_forward():
    """Sliding-window decode with a rolling cache == windowed forward."""
    cfg = get_config("qwen3-0.6b").reduced()
    cfg = dataclasses.replace(
        cfg, attention=dataclasses.replace(cfg.attention, window=4))
    params = PM.init_params(TF.param_defs(cfg), jax.random.PRNGKey(0))
    B, S = 1, 10
    toks = jax.random.randint(jax.random.PRNGKey(2), (B, S), 0, cfg.vocab)
    full, _ = TF.forward(cfg, params, toks)
    cache = TF.init_cache(cfg, B, S, jnp.float32)   # T = window = 4
    for t in range(S):
        logits, cache = TF.decode_step(cfg, params, cache, toks[:, t:t + 1],
                                       jnp.int32(t))
        np.testing.assert_allclose(np.asarray(logits.reshape(-1)),
                                   np.asarray(full[0, t]), rtol=2e-2, atol=2e-2)


@pytest.mark.parametrize("name", ["deepseek-v2-236b", "dbrx-132b"])
def test_full_config_param_counts(name):
    """Full (non-reduced) configs match the published scale."""
    cfg = get_config(name)
    n = PM.count_params(TF.param_defs(cfg))
    expected = {"deepseek-v2-236b": 236e9, "dbrx-132b": 132e9}[name]
    assert 0.75 * expected < n < 1.35 * expected, f"{name}: {n:.3e}"


def test_param_specs_cover_every_leaf():
    """pspec_tree yields a PartitionSpec for every ParamDef leaf."""
    import jax.sharding as shd
    from repro.launch.mesh import make_mesh
    # a fake mesh over 1 device still produces specs
    mesh = make_mesh((1, 1), ("data", "model"))
    for name in ARCH_IDS:
        defs = TF.param_defs(get_config(name))
        specs = PM.pspec_tree(defs, mesh)
        n_defs = len(jax.tree.leaves(defs, is_leaf=PM.is_param_def))
        n_specs = len(jax.tree.leaves(
            specs, is_leaf=lambda x: isinstance(x, shd.PartitionSpec)))
        assert n_defs == n_specs
