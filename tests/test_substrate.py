"""Substrate tests: optimizers, checkpointing, data pipeline, configs,
hlo accounting, serving cache specs."""
import json
import pathlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import ARCHS, SHAPES, ByzantineConfig, TrainConfig, get_config
from repro.checkpoint import ckpt
from repro.data.pipeline import ImageWorkerPipeline, LMWorkerPipeline
from repro.models import params as PM
from repro.models import transformer as TF
from repro.optim import get_optimizer


# ---------------------------------------------------------------------------
# optimizers
# ---------------------------------------------------------------------------

def _tcfg(opt, **kw):
    return TrainConfig(model=ARCHS["qwen3-0.6b"].reduced(), optimizer=opt, **kw)


def test_sgd_update_math():
    opt = get_optimizer(_tcfg("sgd", lr=0.1, grad_clip=0.0))
    p = {"w": jnp.asarray([1.0, 2.0])}
    g = {"w": jnp.asarray([0.5, -1.0])}
    new, _ = opt.update(g, opt.init(p), p, jnp.int32(0))
    np.testing.assert_allclose(np.asarray(new["w"]), [0.95, 2.1], rtol=1e-6)


def test_momentum_accumulates():
    opt = get_optimizer(_tcfg("momentum", lr=1.0, momentum=0.5, grad_clip=0.0))
    p = {"w": jnp.zeros(2)}
    g = {"w": jnp.ones(2)}
    st = opt.init(p)
    p, st = opt.update(g, st, p, jnp.int32(0))   # v=1, p=-1
    p, st = opt.update(g, st, p, jnp.int32(1))   # v=1.5, p=-2.5
    np.testing.assert_allclose(np.asarray(p["w"]), [-2.5, -2.5], rtol=1e-6)


def test_adamw_first_step_is_lr_sized():
    opt = get_optimizer(_tcfg("adamw", lr=1e-2, weight_decay=0.0, grad_clip=0.0))
    p = {"w": jnp.zeros(3)}
    g = {"w": jnp.asarray([1.0, -2.0, 0.5])}
    new, st = opt.update(g, opt.init(p), p, jnp.int32(0))
    # bias-corrected first Adam step = -lr * sign(g) (+eps effects)
    np.testing.assert_allclose(np.asarray(new["w"]),
                               [-1e-2, 1e-2, -1e-2], rtol=1e-3)
    assert st["m"]["w"].dtype == jnp.float32


def test_grad_clip_global_norm():
    from repro.optim.optimizers import clip_by_global_norm
    g = {"a": jnp.asarray([3.0]), "b": jnp.asarray([4.0])}
    c = clip_by_global_norm(g, 1.0)   # norm 5 -> scale 0.2
    np.testing.assert_allclose(np.asarray(c["a"]), [0.6], rtol=1e-6)
    np.testing.assert_allclose(np.asarray(c["b"]), [0.8], rtol=1e-6)


# ---------------------------------------------------------------------------
# checkpoint
# ---------------------------------------------------------------------------

def test_checkpoint_roundtrip(tmp_path):
    cfg = ARCHS["qwen3-0.6b"].reduced()
    params = PM.init_params(TF.param_defs(cfg), jax.random.PRNGKey(0))
    ckpt.save(str(tmp_path), params, step=7, extra={"note": "t"})
    like = jax.tree.map(jnp.zeros_like, params)
    restored, step = ckpt.restore(str(tmp_path), like)
    assert step == 7
    for a, b in zip(jax.tree.leaves(params), jax.tree.leaves(restored)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_checkpoint_latest_and_missing(tmp_path):
    assert ckpt.latest_step(str(tmp_path / "nope")) is None
    t = {"w": jnp.ones(3)}
    ckpt.save(str(tmp_path), t, step=1)
    ckpt.save(str(tmp_path), t, step=5)
    assert ckpt.latest_step(str(tmp_path)) == 5
    with pytest.raises(FileNotFoundError):
        ckpt.restore(str(tmp_path / "nope"), t)


def test_checkpoint_shape_mismatch_raises(tmp_path):
    ckpt.save(str(tmp_path), {"w": jnp.ones(3)}, step=0)
    with pytest.raises(ValueError):
        ckpt.restore(str(tmp_path), {"w": jnp.ones(4)})


# ---------------------------------------------------------------------------
# data pipeline
# ---------------------------------------------------------------------------

def test_lm_pipeline_shapes_and_determinism():
    cfg = ARCHS["qwen3-0.6b"].reduced()
    pipe = LMWorkerPipeline(cfg, n_workers=4, batch_per_worker=3, seq_len=16)
    b1, b2 = pipe.batch(0), pipe.batch(0)
    assert b1["tokens"].shape == (4, 3, 16)
    np.testing.assert_array_equal(b1["tokens"], b2["tokens"])
    assert (pipe.batch(1)["tokens"] != b1["tokens"]).any()
    assert b1["tokens"].min() >= 0 and b1["tokens"].max() < cfg.vocab


def test_lm_pipeline_label_flip_hits_byzantine_workers_only():
    cfg = ARCHS["qwen3-0.6b"].reduced()
    byz = ByzantineConfig(attack="label_flip", alpha=0.5)
    clean = LMWorkerPipeline(cfg, 4, 2, 8, byz=None).batch(0)["tokens"]
    flip = LMWorkerPipeline(cfg, 4, 2, 8, byz=byz).batch(0)["tokens"]
    np.testing.assert_array_equal(flip[2:], clean[2:])
    np.testing.assert_array_equal(flip[:2], cfg.vocab - 1 - clean[:2])


def test_vlm_pipeline_provides_prefix_embed():
    cfg = ARCHS["phi-3-vision-4.2b"].reduced()
    pipe = LMWorkerPipeline(cfg, 2, 2, 8)
    b = pipe.batch(0)
    assert b["prefix_embed"].shape == (2, 2, cfg.n_prefix_tokens, cfg.d_model)


def test_image_pipeline_splits_and_flips():
    byz = ByzantineConfig(attack="label_flip", alpha=0.25)
    pipe = ImageWorkerPipeline(n_workers=4, n_per_worker=32, byz=byz)
    b = pipe.batch(0, batch_per_worker=8)
    assert b["images"].shape[:2] == (4, 8)
    assert b["labels"].min() >= 0 and b["labels"].max() <= 9


# ---------------------------------------------------------------------------
# configs / registry
# ---------------------------------------------------------------------------

def test_registry_has_all_assigned():
    assert set(ARCHS) == {
        "deepseek-v2-236b", "deepseek-v2-lite", "phi-3-vision-4.2b", "nemotron-4-15b",
        "musicgen-large", "minicpm3-4b", "dbrx-132b", "zamba2-2.7b",
        "qwen3-0.6b", "qwen3-1.7b", "rwkv6-7b"}
    assert set(SHAPES) == {"train_4k", "prefill_32k", "decode_32k", "long_500k"}


def test_assigned_config_dims_match_spec():
    c = get_config("deepseek-v2-236b")
    assert (c.n_layers, c.d_model, c.vocab) == (60, 5120, 102400)
    assert c.moe.n_experts == 160 and c.moe.top_k == 6 and c.moe.n_shared == 2
    assert c.attention.kind == "mla" and c.attention.kv_lora_rank == 512
    c = get_config("nemotron-4-15b")
    assert (c.d_model, c.d_ff, c.vocab) == (6144, 24576, 256000)
    assert c.activation == "relu2" and c.attention.n_kv_heads == 8
    c = get_config("dbrx-132b")
    assert c.moe.n_experts == 16 and c.moe.top_k == 4
    c = get_config("zamba2-2.7b")
    assert c.hybrid_attn_every > 0 and c.ssm is not None
    c = get_config("rwkv6-7b")
    assert c.attention.kind == "none" and c.rwkv is not None
    c = get_config("qwen3-0.6b")
    assert c.attention.qk_norm and c.attention.n_kv_heads == 8


def test_unknown_arch_raises():
    with pytest.raises(KeyError):
        get_config("gpt-99")


def test_shapes_registry_values():
    assert SHAPES["train_4k"].seq_len == 4096
    assert SHAPES["train_4k"].global_batch == 256
    assert SHAPES["long_500k"].seq_len == 524288
    assert SHAPES["long_500k"].global_batch == 1
    assert SHAPES["decode_32k"].mode == "decode"


# ---------------------------------------------------------------------------
# hlo accounting
# ---------------------------------------------------------------------------

def test_module_stats_scan_trip_multiplication():
    from repro.launch.hlo_stats import module_stats

    def f(x, w):
        def body(c, _):
            return jnp.tanh(c @ w), None
        y, _ = jax.lax.scan(body, x, None, length=10)
        return y

    s = jax.ShapeDtypeStruct((64, 64), jnp.float32)
    txt = jax.jit(f).lower(s, s).compile().as_text()
    st = module_stats(txt)
    want = 10 * 2 * 64 ** 3
    assert want <= st["flops"] <= 1.2 * want
    assert st["unknown_trip_whiles"] == 0
    assert st["bytes"] >= 10 * 2 * 64 * 64 * 4   # >= in+out per iteration


def test_module_stats_counts_plain_dot():
    from repro.launch.hlo_stats import module_stats
    s = jax.ShapeDtypeStruct((32, 128), jnp.float32)
    w = jax.ShapeDtypeStruct((128, 16), jnp.float32)
    txt = jax.jit(lambda a, b: a @ b).lower(s, w).compile().as_text()
    st = module_stats(txt)
    want = 2 * 32 * 128 * 16
    assert want <= st["flops"] <= 1.1 * want + 1e4


def test_collective_bytes_synthetic_hlo():
    from repro.launch.hlo_stats import collective_bytes
    hlo = """
HloModule test, entry_computation_layout={()->f32[]}

ENTRY %main (p: f32[128]) -> f32[128] {
  %p = f32[128]{0} parameter(0)
  %ag = f32[512]{0} all-gather(%p), replica_groups=[4,4], dimensions={0}
  %ar = f32[128]{0} all-reduce(%p), replica_groups=[4,4], to_apply=%add
  ROOT %out = f32[128]{0} add(%p, %ar)
}
"""
    out = collective_bytes(hlo)
    assert out["all-gather"] == 512 * 4 * 3 / 4
    assert out["all-reduce"] == 2 * 128 * 4 * 3 / 4


# ---------------------------------------------------------------------------
# serving specs
# ---------------------------------------------------------------------------

def test_cache_specs_match_cache_defs():
    """Every cache leaf gets a PartitionSpec of matching rank."""
    from jax.sharding import PartitionSpec as P
    from repro.launch.mesh import make_mesh
    from repro.serving.engine import cache_specs

    mesh = make_mesh((1, 1), ("data", "model"))
    for name in ("qwen3-0.6b", "minicpm3-4b", "zamba2-2.7b", "rwkv6-7b"):
        cfg = get_config(name).reduced()
        defs = TF.cache_defs(cfg, batch=4, seq_len=32)
        specs = cache_specs(cfg, 4, 32, mesh, shard_seq=False)
        d_leaves = jax.tree.leaves(
            defs, is_leaf=lambda x: isinstance(x, tuple) and isinstance(x[0], tuple))
        s_leaves = jax.tree.leaves(specs, is_leaf=lambda x: isinstance(x, P))
        assert len(d_leaves) == len(s_leaves)
        for (shape, _), spec in zip(d_leaves, s_leaves):
            assert len(spec) == len(shape)


def test_roofline_active_params_moe():
    from repro.launch.roofline import active_params
    cfg = get_config("dbrx-132b")
    total = PM.count_params(TF.param_defs(cfg))
    act = active_params(cfg)
    # dbrx: 16 experts top-4 -> active well under half of total
    assert act < 0.5 * total
    assert act > 0.05 * total
    dense = get_config("qwen3-0.6b")
    assert active_params(dense) == PM.count_params(TF.param_defs(dense))
