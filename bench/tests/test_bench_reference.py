"""The plain references of ``bench/reference`` against the program, on
the CPU at a small size."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench import common
from bench.reference import brsgd, qwen3
from bench.tests.conftest import tiny_config, tiny_traffic
from bench.windows import agg, train


@pytest.fixture(scope="module")
def cfg():
    return tiny_config("qwen3-1.7b-7L")


def test_qwen3_loss_and_gradient_match_the_program(cfg):
    from repro.models import transformer as TF
    params = qwen3.init(cfg, jax.random.PRNGKey(0))
    toks = train.token_batch(cfg["vocab_size"], 5, 0, (2, 24))
    mcfg = train.model_config(cfg)
    want, g_want = jax.value_and_grad(
        lambda p: TF.loss_fn(mcfg, p, {"tokens": jnp.asarray(toks)})[0])(params)
    got, g_got = qwen3.loss_and_grad(cfg, params, toks)
    assert float(got) == pytest.approx(float(want), rel=1e-5)
    for a, b in zip(jax.tree.leaves(g_got), jax.tree.leaves(g_want)):
        np.testing.assert_allclose(a, b, rtol=2e-3, atol=1e-6)
    np.testing.assert_allclose(qwen3.leaf_norms(g_got), qwen3.leaf_norms(g_want),
                               rtol=1e-4)


def test_reference_layout_is_the_programs(cfg):
    from repro.models import params as PM
    from repro.models import transformer as TF
    have = jax.tree.map(lambda d: tuple(d.shape),
                        TF.param_defs(train.model_config(cfg)),
                        is_leaf=PM.is_param_def)
    assert have == qwen3.shapes(cfg)


def test_adamw_matches_the_program(cfg):
    from repro.optim.optimizers import adamw
    hp = cfg["optimizer"]
    p = qwen3.init(cfg, jax.random.PRNGKey(1))
    g = jax.tree.map(lambda x: x * 3.0 + 0.01, qwen3.init(cfg, jax.random.PRNGKey(2)))
    opt = adamw(hp["lr"], b1=hp["b1"], b2=hp["b2"], eps=hp["eps"],
                weight_decay=hp["weight_decay"], grad_clip=hp["grad_clip"])
    want_p, want_s = opt.update(g, opt.init(p), p, jnp.int32(0))
    state = {"m": qwen3.zeros_like(p), "v": qwen3.zeros_like(p)}
    got_p, got_s, norms = qwen3.adamw_step(jax.tree.map(jnp.copy, p), g, state,
                                           0, hp)
    for a, b in zip(jax.tree.leaves(got_p), jax.tree.leaves(want_p)):
        np.testing.assert_allclose(a, b, rtol=1e-6, atol=1e-7)
    np.testing.assert_allclose(norms * (1 - hp["b1"]),
                               qwen3.leaf_norms(want_s["m"]), rtol=1e-5)


@pytest.mark.parametrize("use_pallas", [False, True])
def test_brsgd_matches_the_program(use_pallas):
    from repro.core import engine
    tr = tiny_traffic("agg-brsgd-m8-gauss")
    m, n_byz, d = tr["m"], int(tr["alpha"] * tr["m"]), 3 * agg.TILE
    G = agg.make_G(common.prng_key(9), m, n_byz, d, tr["attack_std"],
                   jnp.dtype(tr["dtype"]))
    out, st = engine.aggregate_local(G, agg.byz_config(tr), return_state=True,
                                     use_pallas=use_pallas)
    kept = brsgd.select(*brsgd.stats(G, block=2048), tr["beta"])
    assert (np.asarray(st.selected) == kept).all()
    assert not kept[:n_byz].any() and kept.sum() == 4
    assert brsgd.max_gap(G, kept, out, block=2048) == 0.0


def test_brsgd_tie_goes_to_the_side_at_or_above_the_mean():
    G = jnp.asarray([[0.0, 1.0], [0.0, 3.0], [2.0, 5.0], [2.0, 7.0]])
    scores, _l1 = brsgd.stats(G)
    # column 0: mean 1, two rows above and two below: the upper side wins
    # column 1: mean 4, rows 2 and 3 above: a tie, the upper side wins
    assert scores.tolist() == [0, 0, 2, 2]
    assert brsgd.lower_quartile_index(8) == 2 and brsgd.lower_quartile_index(4) == 1
