"""Faults planted under the timed path of a training cell, for the tests
that see ``correct`` come out false.  Each ``plant_*`` takes a setter
(pytest's ``monkeypatch.setattr`` or plain ``setattr``) and breaks the
program underneath the harness."""
import jax


def _replace_step(setattr_, make_step):
    """Swap ``build_train_step`` for one whose bundle carries
    ``make_step(raw)``, raw being the program's unjitted step."""
    from repro.training import step as S
    real = S.build_train_step

    def broken_build(tcfg, mesh, jit=True):
        bundle = real(tcfg, mesh)
        raw = real(tcfg, mesh, jit=False).step_fn
        return bundle._replace(step_fn=make_step(raw))
    setattr_(S, "build_train_step", broken_build)


def plant_state_unchanged(setattr_):
    """A step that returns its parameters and optimizer state unchanged."""
    _replace_step(setattr_, lambda raw: jax.jit(
        lambda p, o, b, s, k: (p, o, raw(p, o, b, s, k)[2])))


def plant_half_batch(setattr_):
    """Half of each worker's batch left out, the mean taken over the rest."""
    def make(raw):
        def step(p, o, b, s, k):
            half = b["tokens"].shape[1] // 2
            return raw(p, o, {"tokens": b["tokens"][:, :half]}, s, k)
        return jax.jit(step, donate_argnums=(0, 1))
    _replace_step(setattr_, make)


def plant_exchange_left_out(setattr_):
    """The exchange between workers left out: each worker keeps its own
    gradient."""
    from repro.training import step as S
    setattr_(S, "robust_aggregate", lambda grads, cfg, axes, **kw: (grads, None))


TRAIN_FAULTS = {"state_unchanged": plant_state_unchanged,
                "half_of_the_batch": plant_half_batch}
MULTI_WORKER_FAULTS = {**TRAIN_FAULTS,
                       "exchange_left_out": plant_exchange_left_out}
