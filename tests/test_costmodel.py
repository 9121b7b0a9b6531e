"""Cost-model + layout-autotuner tests: Cost algebra, the analytic
feature/contract formulas pinned against the committed bench files, the
planner's crossover behavior, and engine ``layout="auto"`` parity
(multi-device subprocess)."""
import json
import pathlib
import textwrap

import pytest

from conftest import run_multidevice
from repro.analysis import costmodel as cm

REPO = pathlib.Path(__file__).resolve().parents[1]


# ---------------------------------------------------------------------------
# Cost algebra + profiles (pure, no jax)
# ---------------------------------------------------------------------------

def test_cost_algebra():
    a = cm.compute(100.0, 40.0) + cm.collective("all_gather", 32.0, 2)
    b = cm.collective("all_gather", 8.0, 1) + cm.collective("all_reduce", 16.0, 1)
    s = a + b
    assert s.flops == 100.0 and s.hbm_bytes == 40.0
    # collective() bytes are per-call × count (per-step payload totals)
    assert s.coll_bytes == {"all_gather": 72.0, "all_reduce": 16.0}
    assert s.coll_count == {"all_gather": 3.0, "all_reduce": 1.0}
    assert s.total_coll_bytes == 88.0
    doubled = 2 * s
    assert doubled.flops == 200.0
    assert doubled.coll_bytes["all_gather"] == 144.0
    assert s * 0.5 == 0.5 * s
    assert cm.ZERO + a == a
    d = s.to_dict()
    assert d["flops"] == 100.0 and d["coll_bytes"]["all_reduce"] == 16.0


def test_profile_roofline_vs_additive():
    prof = cm.HardwareProfile("x", flops=100.0, hbm_bw=10.0, coll_bw=1.0)
    c = cm.compute(200.0, 50.0) + cm.collective("all_gather", 3.0)
    # max-term roofline: collective term 3/1 + 1 latency hop dominates
    t_roof = prof.time_s(c)
    add = cm.HardwareProfile("y", flops=100.0, hbm_bw=10.0, coll_bw=1.0,
                             additive=True)
    assert add.time_s(c) > t_roof      # additive stacks all three terms
    assert t_roof >= 3.0 / 1.0
    with pytest.raises(KeyError):
        cm.get_profile("no-such-profile")


def test_trim_stack_threshold_matches_kernels():
    from repro.kernels import ref
    assert cm.TRIM_STACK_MIN_M == ref._TRIM_STACK_MIN_M


def test_trimmed_mean_refuse_cliff_is_m_driven():
    # below the stack threshold the trimmed column rule re-fuses row
    # lists; the cliff's feature split is the fusion-cone op count, so
    # it moves with m and NOT with d
    f32 = cm.compute_features("trimmed_mean", 32, 10_000, elastic=False)
    f33 = cm.compute_features("trimmed_mean", 33, 10_000, elastic=False)
    assert f32["refuse_s"] + f32["refuse_b"] > 0
    assert f33["refuse_s"] + f33["refuse_b"] == 0 and f33["sort"] > 0
    big_d = cm.compute_features("trimmed_mean", 32, 160_000, elastic=False)
    small_d = cm.compute_features("trimmed_mean", 32, 10_000, elastic=False)
    assert (big_d["refuse_b"] > 0) == (small_d["refuse_b"] > 0)


# ---------------------------------------------------------------------------
# planner behavior
# ---------------------------------------------------------------------------

def test_plan_deterministic_and_crossover():
    leaves = [(256, "f32"), (1_000, "f32"), (40_000, "f32"),
              (100_000, "f32")]
    p1 = cm.plan_layouts("krum", 8, leaves)
    p2 = cm.plan_layouts("krum", 8, leaves)
    assert p1 == p2
    # tiny leaves stay on the latency-cheap gather; big leaves take the
    # bandwidth-cheap a2a (tpu_v5e crossover ~3.5k f32 elements at m=8)
    assert p1.layouts[0] == "gather" and p1.layouts[1] == "gather"
    assert p1.layouts[2] == "a2a" and p1.layouts[3] == "a2a"
    assert not p1.fast_path


def test_plan_monotone_in_numel():
    # once a leaf size flips to a2a, every larger leaf stays a2a
    sizes = [2 ** k for k in range(4, 22)]
    picks = [cm.plan_layouts("brsgd", 8, [(n, "f32")]).layouts[0]
             for n in sizes]
    flips = sum(1 for a, b in zip(picks, picks[1:]) if a != b)
    assert flips <= 1 and picks[-1] == "a2a"


def test_plan_mean_fast_path_and_elastic():
    leaves = [(40_000, "f32")]
    p = cm.plan_layouts("mean", 8, leaves)
    assert p.fast_path and p.layouts == ("gather",)
    # elastic mean can't take the replicated pmean shortcut
    pe = cm.plan_layouts("mean", 8, leaves, elastic=True)
    assert not pe.fast_path
    pn = cm.plan_layouts("mean", 8, leaves, fast_paths=False)
    assert not pn.fast_path


def test_plan_zero_size_leaf_ties_to_gather():
    p = cm.plan_layouts("krum", 8, [(0, "f32")])
    assert p.layouts == ("gather",)


def test_expected_collectives_mixed_plan():
    from repro.core import engine
    spec = engine.get_spec("krum")
    want = engine.expected_collectives(spec, "auto", 3,
                                       plan=("a2a", "gather", "a2a"))
    # a2a: chunk a2a + unchunk all_gather per leaf; gather: one gather
    assert want == {"all_gather": 3, "all_to_all": 2}
    mean = engine.get_spec("mean")
    assert engine.expected_collectives(
        mean, "auto", 2, plan=("a2a", "a2a")) == \
        {"all_gather": 0, "all_to_all": 0}
    saved, engine.LAST_PLAN = engine.LAST_PLAN, None
    try:
        with pytest.raises(ValueError):
            engine.expected_collectives(spec, "auto", 2)
    finally:
        engine.LAST_PLAN = saved


# ---------------------------------------------------------------------------
# pinned against the committed bench files
# ---------------------------------------------------------------------------

def _bench(name):
    return json.loads((REPO / name).read_text())


def test_predicted_contracts_match_committed_matrix_exactly():
    errors = cm.validate_contracts(_bench("BENCH_contracts.json"))
    assert errors == [], "\n".join(errors)


def test_drift_gate_passes_on_committed_bench():
    errors = cm.validate_rows(_bench("BENCH_agg.json"))
    assert errors == [], "\n".join(errors)


def test_drift_gate_catches_perturbed_row():
    bench = _bench("BENCH_agg.json")
    victim = next(r for r in bench["rows"]
                  if r["layout"] == "local" and r["aggregator"] == "krum")
    victim["us_per_call"] *= 40.0
    errors = cm.validate_rows(bench)
    assert any("krum/local" in e and "drifts" in e for e in errors), errors


def test_pick_check_passes_and_catches_regression():
    bench = _bench("BENCH_agg.json")
    assert cm.validate_pick(bench) == []
    # if the planned layout regresses far past the best measured one,
    # the acceptance band fails
    for r in bench["rows"]:
        if r["layout"] == "a2a" and r["aggregator"] == "krum":
            r["us_per_call"] *= 10.0
    errors = cm.validate_pick(bench)
    assert any("krum" in e and "acceptance band" in e for e in errors), \
        errors


def test_check_bench_rejects_bad_fits(tmp_path):
    import sys
    sys.path.insert(0, str(REPO / "benchmarks"))
    import check_bench as cb
    bench = _bench("BENCH_agg.json")
    bench["fits"]["brsgd"]["m_exp"] = float("nan")
    bad = tmp_path / "BENCH_agg.json"
    bad.write_text(json.dumps(bench))
    errs = cb.check(str(bad))
    assert any("fits[brsgd]" in e for e in errs), errs
    bench = _bench("BENCH_agg.json")
    bench["elastic_overhead"]["median"] = 0.0
    bad.write_text(json.dumps(bench))
    errs = cb.check(str(bad))
    assert any("elastic_overhead[median]" in e for e in errs), errs


def test_autotune_cli_passes_in_process(capsys):
    from repro.launch import autotune
    assert autotune.main([]) == 0
    out = capsys.readouterr().out
    assert "all checks passed" in out


# ---------------------------------------------------------------------------
# engine layout="auto" parity (8 host devices, subprocess)
# ---------------------------------------------------------------------------

def test_auto_layout_matches_forced_layouts():
    """Uniform plans are bit-identical to the forced layouts; the mixed
    plan agrees numerically; elastic auto rounds run for select and
    column specs."""
    code = textwrap.dedent("""
        import jax, jax.numpy as jnp, numpy as np
        from jax.sharding import Mesh, PartitionSpec as P
        from repro.compat import shard_map
        from repro.configs import ByzantineConfig
        from repro.core import engine

        mesh = Mesh(np.array(jax.devices()).reshape(8), ("data",))
        m = 8
        rng = np.random.default_rng(0)
        big = jnp.asarray(rng.normal(size=(8, 40000)).astype(np.float32))
        big = big.at[6].mul(10.0)
        tiny = jnp.asarray(rng.normal(size=(8, 64)).astype(np.float32))
        tiny = tiny.at[6].mul(10.0)
        grads = {"big": big, "tiny": tiny}
        specs = {"big": P("data"), "tiny": P("data")}

        def run(layout, agg, plan=None, valid=None):
            cfg = ByzantineConfig(aggregator=agg)
            def f(g):
                out, _ = engine.aggregate_sharded(
                    g, cfg, axes=("data",), layout=layout, plan=plan,
                    valid=valid)
                return out
            fn = shard_map(f, mesh=mesh, in_specs=(specs,),
                           out_specs=specs)
            return jax.jit(fn)(grads)

        for agg in ("krum", "median", "brsgd"):
            auto = run("auto", agg)
            assert engine.LAST_PLAN.layouts == ("a2a", "gather"), \\
                (agg, engine.LAST_PLAN)
            for forced in ("gather", "a2a"):
                u = run("auto", agg, plan=(forced,) * 2)
                f_ = run(forced, agg)
                for k in ("big", "tiny"):
                    assert np.array_equal(np.asarray(u[k]),
                                          np.asarray(f_[k])), \\
                        (agg, forced, k)
            ga, aa = run("gather", agg), run("a2a", agg)
            for k in ("big", "tiny"):
                a = np.asarray(auto[k])
                ok = (np.allclose(a, np.asarray(ga[k]), rtol=1e-5,
                                  atol=1e-6)
                      or np.allclose(a, np.asarray(aa[k]), rtol=1e-5,
                                     atol=1e-6))
                assert ok, (agg, k)

        # mean fast path: auto == forced layouts == pmean exactly
        for forced in ("gather", "a2a"):
            assert all(np.array_equal(np.asarray(a), np.asarray(b))
                       for a, b in zip(run("auto", "mean").values(),
                                       run(forced, "mean").values()))

        # elastic rounds through auto (select + column specs)
        valid = jnp.array([1, 1, 1, 1, 1, 1, 0, 0], jnp.float32)
        for agg in ("krum", "median"):
            r = run("auto", agg, valid=valid)
            assert all(np.isfinite(np.asarray(v)).all()
                       for v in r.values()), agg
        print("AUTO-PARITY-OK")
    """)
    assert "AUTO-PARITY-OK" in run_multidevice(code)


def test_auto_layout_e2e_step_matches_forced():
    """build_train_step with the default agg_layout="auto": resolves a
    mixed plan and the loss trajectory is bit-identical to forced a2a
    (every lint-arch leaf that matters is past the crossover)."""
    code = textwrap.dedent("""
        import jax, jax.numpy as jnp, numpy as np
        from repro.configs import ARCHS, TrainConfig, ByzantineConfig
        from repro.training.step import build_train_step, resolve_strategy
        from repro.models import transformer as TF, params as PM
        from repro.launch.mesh import make_mesh
        from repro.data.pipeline import LMWorkerPipeline
        from repro.core import engine

        mesh = make_mesh((8,), ("data",))
        cfg = ARCHS["qwen3-0.6b"].reduced()

        def run(agg_layout, steps=2):
            bcfg = ByzantineConfig(aggregator="brsgd", attack="gaussian",
                                   alpha=0.25)
            tcfg = TrainConfig(model=cfg, byzantine=bcfg,
                               optimizer="sgd", lr=0.1, grad_clip=0.0,
                               agg_layout=agg_layout)
            bundle = build_train_step(tcfg, mesh)
            psh, osh, bsh = bundle.shardings(mesh)
            key = jax.random.PRNGKey(0)
            params = jax.device_put(
                PM.init_params(TF.param_defs(cfg), key), psh)
            opt = ()
            pipe = LMWorkerPipeline(cfg, 8, 2, 32, byz=bcfg)
            losses = []
            with mesh:
                for s in range(steps):
                    batch = {k: jax.device_put(jnp.asarray(v), bsh[k])
                             for k, v in pipe.batch(s).items()}
                    params, opt, met = bundle.step_fn(
                        params, opt, batch, jnp.int32(s),
                        jax.random.fold_in(key, s))
                    losses.append(float(met["loss"]))
            return losses

        assert resolve_strategy(TrainConfig(model=cfg)) == \\
            ("global", "auto")
        auto = run("auto")
        plan = engine.LAST_PLAN
        assert plan is not None and set(plan.layouts) == \\
            {"a2a", "gather"}, plan
        assert all(np.isfinite(auto)), auto
        a2a = run("a2a")
        assert auto == a2a, (auto, a2a)
        print("E2E-AUTO-OK")
    """)
    assert "E2E-AUTO-OK" in run_multidevice(code)
