"""DeepSeek-V2's attention pieces in the program: YaRN rotary
frequencies and softmax scale against the published formulas worked by
hand, the rope pair layout, the latent norms' epsilon, plain RoPE left
as it was, and a CPU smoke of ``launch/train.py --arch deepseek-v2-lite``."""
import dataclasses
import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import deepseek_v2_236b, get_config
from repro.models import layers as L
from repro.models import params as PM

LITE = get_config("deepseek-v2-lite").attention.rope_scaling


def test_yarn_frequencies_by_hand():
    """DeepSeek-V2-Lite's rope head of 64, theta 10,000, factor 40 over
    4,096 positions: the pair that turns 32 times in 4,096 positions is
    64 ln(4096 / (32·2π)) / (2 ln 10^4) = 10.47 -> 10, the one that turns
    once 22.51 -> 23.  Pairs up to 10 keep theta^(-2i/64), pairs from 23
    on are divided by 40, and pair i between blends them with weight
    (i - 10) / 13 on the divided one."""
    assert (LITE.factor, LITE.beta_fast, LITE.beta_slow) == (40.0, 32.0, 1.0)
    assert L._yarn_correction_range(LITE, 64, 10_000.0) == (10, 23)
    got = np.asarray(L.rope_freqs(64, 10_000.0, LITE), np.float64)
    plain = 10_000.0 ** (-np.arange(0, 64, 2) / 64)
    for i in (0, 5, 10):
        assert got[i] == pytest.approx(plain[i], rel=1e-6)
    for i in (23, 27, 31):
        assert got[i] == pytest.approx(plain[i] / 40, rel=1e-6)
    r = (15 - 10) / 13
    assert got[15] == pytest.approx(plain[15] * (1 - r) + plain[15] / 40 * r, rel=1e-6)


def test_yarn_mscale_and_softmax_scale_by_hand():
    # 0.1 · 0.707 · ln 40 + 1 = 1.26080...
    m = 0.1 * 0.707 * math.log(40) + 1
    assert L.yarn_mscale(40.0, 0.707) == pytest.approx(1.2608043, rel=1e-6) == m
    assert L.yarn_mscale(1.0, 0.707) == 1.0
    a = get_config("deepseek-v2-lite").attention
    assert float(L.mla_softmax_scale(a)) == pytest.approx(192 ** -0.5 * m * m, rel=1e-6)
    # mscale equals mscale_all_dim: the cos/sin tables are not scaled
    x = jnp.ones((1, 3, 1, 64))
    pos = jnp.zeros((1, 3), jnp.int32)
    np.testing.assert_array_equal(np.asarray(L.apply_rope(x, pos, 1e4, LITE)),
                                  np.asarray(x))
    plain = dataclasses.replace(a, rope_scaling=None)
    assert float(L.mla_softmax_scale(plain)) == pytest.approx(192 ** -0.5, rel=1e-6)


def _apply_rope_before(x, positions, theta):
    """``apply_rope`` as it was before YaRN and the pair layout."""
    d = x.shape[-1]
    inv = 1.0 / (theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d))
    ang = positions[..., None].astype(jnp.float32) * inv
    cos, sin = jnp.cos(ang)[..., None, :], jnp.sin(ang)[..., None, :]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1)
    return out.astype(x.dtype)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_plain_rope_unchanged_bit_for_bit(dtype):
    x = jax.random.normal(jax.random.PRNGKey(0), (2, 37, 4, 128)).astype(dtype)
    pos = jnp.broadcast_to(jnp.arange(37) * 131, (2, 37))
    for theta in (10_000.0, 1_000_000.0):
        want = jax.jit(_apply_rope_before, static_argnums=2)(x, pos, theta)
        got = jax.jit(L.apply_rope, static_argnums=2)(x, pos, theta)
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def test_interleaved_pairs_are_deinterleaved_then_rotated():
    x = jax.random.normal(jax.random.PRNGKey(1), (1, 9, 2, 8))
    pos = jnp.arange(9)[None]
    deint = jnp.concatenate([x[..., 0::2], x[..., 1::2]], axis=-1)
    got = L.apply_rope(x, pos, 10_000.0, LITE, interleaved=True)
    want = L.apply_rope(deint, pos, 10_000.0, LITE)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def test_mla_latent_norm_takes_the_models_epsilon():
    """A latent of mean square near 1e-6 tells an epsilon of 1e-6 from
    the old fixed 1e-5; the model passes its own ``rms_eps``."""
    from repro.models import transformer as TF
    cfg = get_config("deepseek-v2-lite").reduced()
    assert cfg.rms_eps == 1e-6
    p = PM.init_params(L.mla_defs(cfg.d_model, cfg.attention), jax.random.PRNGKey(0))
    p = dict(p, w_dkv=p["w_dkv"] * 1e-3)
    x = jax.random.normal(jax.random.PRNGKey(1), (1, 6, cfg.d_model))
    pos = jnp.arange(6)
    out = TF._attention(cfg, p, x, pos)[0]
    want = L.mla_attention(p, cfg.attention, x, pos, eps=1e-6)[0]
    old = L.mla_attention(p, cfg.attention, x, pos, eps=1e-5)[0]
    np.testing.assert_array_equal(np.asarray(out), np.asarray(want))
    assert float(jnp.max(jnp.abs(out - old))) > 1e-3 * float(jnp.max(jnp.abs(out)))


def test_deepseek_v2_configs_state_their_router():
    lite, big = get_config("deepseek-v2-lite"), get_config("deepseek-v2-236b")
    assert (lite.n_layers, lite.d_model, lite.d_ff, lite.vocab) == (27, 2048, 10_944, 102_400)
    assert (lite.moe.n_experts, lite.moe.top_k, lite.moe.n_shared,
            lite.moe.d_ff_expert) == (64, 6, 2, 1408)
    for c, scale in ((lite, 1.0), (big, 16.0)):
        assert not c.moe.norm_topk_prob and c.moe.routed_scaling_factor == scale
        assert c.moe.balance == "seq" and c.attention.rope_interleaved
    assert "group-limited" in deepseek_v2_236b.__doc__


def test_launch_train_deepseek_v2_lite_reduced(monkeypatch, capsys):
    from repro.launch import compile_cache, train
    monkeypatch.setattr(compile_cache, "enable", lambda: None)
    train.main(["--arch", "deepseek-v2-lite", "--reduced", "--steps", "3",
                "--mesh", "1x1", "--seq", "16", "--aggregator", "mean"])
    out = capsys.readouterr().out
    losses = [float(ln.split("loss=")[1].split()[0]) for ln in out.splitlines()
              if ln.startswith("step") and "loss=" in ln]
    assert len(losses) == 3 and all(np.isfinite(losses))
