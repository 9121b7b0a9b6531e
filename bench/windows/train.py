"""Training window: the jitted step of ``training.step.build_train_step``,
built as ``launch.train.main`` builds it, driven step by step with a
host wait on each step's loss (as ``train.main`` syncs each step), or with
a fixed number of steps sent ahead of that wait where the traffic says so.

Set-up makes the weights on the device from the seed (one jitted call,
float32, in the step's shardings), fills a ring of token batches on the
host, and drives the step through its first three steps with the
window's own call and feed.  Those steps compile the step and give the
readings that ``correct`` compares with the plain reference:

- each step's loss;
- the norm of every leaf of the first gradient as the optimizer got it,
  read back from AdamW's first moment after one step (m₁ = (1-β₁)·g₀);
- the norm of every leaf of the parameters' change after three steps.

The window then continues the same step on the same state.
"""
from __future__ import annotations

import collections
import time

import numpy as np

from bench import common
from bench.reference import qwen3 as ref

N_CHECKED = 3          # steps the reference follows
RING = 8               # token batches generated ahead of the window
SPANS = ("feed", "step", "sync")


def model_config(cfg: dict):
    """The program's ModelConfig for a configuration file."""
    common.program_on_path()
    from repro.configs.base import AttentionSpec, ModelConfig
    return ModelConfig(
        name=cfg["name"], arch_type="dense",
        n_layers=cfg["num_hidden_layers"], d_model=cfg["hidden_size"],
        d_ff=cfg["intermediate_size"], vocab=cfg["vocab_size"],
        attention=AttentionSpec(
            kind="gqa", n_heads=cfg["num_attention_heads"],
            n_kv_heads=cfg["num_key_value_heads"], head_dim=cfg["head_dim"],
            qk_norm=True, rope_theta=float(cfg["rope_theta"])),
        activation=cfg["hidden_act"], tie_embeddings=cfg["tie_word_embeddings"],
        rms_eps=cfg["rms_norm_eps"], dtype=cfg["torch_dtype"],
        source=cfg["source"])


def token_batch(vocab: int, seed: int, step: int, shape) -> np.ndarray:
    """One batch of the synthetic corpus (a copy of the arithmetic of the
    program's ``data.synthetic.TokenStream``): a noisy bigram chain,
    next = (cur·31 + 1) mod V with probability 0.8, else uniform."""
    rng = common.np_rng(seed, 2, step)
    rows = int(np.prod(shape[:-1]))
    seq = shape[-1]
    toks = np.empty((rows, seq), np.int32)
    cur = rng.integers(0, vocab, size=rows)
    toks[:, 0] = cur
    for t in range(1, seq):
        follow = rng.random(rows) < 0.8
        rand = rng.integers(0, vocab, size=rows)
        cur = np.where(follow, (cur * 31 + 1) % vocab, rand)
        toks[:, t] = cur
    return toks.reshape(shape)


def _rel_gaps(prog, want):
    """|prog - want| / max(want, median(want)) per leaf."""
    prog, want = np.asarray(prog, np.float64), np.asarray(want, np.float64)
    return np.abs(prog - want) / np.maximum(want, np.median(want))


def compare(prog: dict, want: dict) -> dict:
    """The numbers ``correct`` is decided by, from two sets of readings
    ({"loss": [3], "grad": [leaves], "change": [leaves]})."""
    pl, wl = np.asarray(prog["loss"], np.float64), np.asarray(want["loss"], np.float64)
    loss_gap = float(np.max(np.abs(pl - wl) / np.abs(wl)))
    grad_gap = float(np.max(_rel_gaps(prog["grad"], want["grad"])))
    # leaves whose reference gradient is nought to rounding move under
    # Adam by round-off alone: leave them out of the change
    g = np.asarray(want["grad"], np.float64)
    moved = g >= 1e-3 * np.median(g)
    ch = _rel_gaps(np.asarray(prog["change"])[moved],
                   np.asarray(want["change"])[moved])
    return {"loss_gap": loss_gap if np.isfinite(loss_gap) else float("inf"),
            "grad_norm_gap": grad_gap if np.isfinite(grad_gap) else float("inf"),
            "update_norm_gap": float(np.max(ch)) if np.all(np.isfinite(ch))
            else float("inf")}


class Run:
    """The compiled step with its state, from set-up to the check."""

    def __init__(self, ctx: dict):
        common.program_on_path()
        import jax
        import jax.numpy as jnp
        from jax.sharding import NamedSharding, PartitionSpec as P

        from repro.configs import ByzantineConfig, TrainConfig
        from repro.launch.mesh import n_workers
        from repro.launch.train import build_mesh
        from repro.models import params as PM
        from repro.models import transformer as TF
        from repro.training.step import build_train_step

        self.cfg, self.tr = ctx["config"], ctx["traffic"]
        cfg, tr = self.cfg, self.tr
        self.hp = cfg["optimizer"]
        mcfg = model_config(cfg)
        bcfg = ByzantineConfig(aggregator=tr["aggregator"], attack=tr["attack"],
                               alpha=tr["alpha"])
        tcfg = TrainConfig(model=mcfg, byzantine=bcfg, optimizer="adamw",
                           lr=self.hp["lr"], weight_decay=self.hp["weight_decay"],
                           grad_clip=self.hp["grad_clip"],
                           agg_layout=tr["agg_layout"], agg_scope=tr["agg_scope"],
                           remat=tr["remat"])
        self.mesh = build_mesh(tr["mesh"])
        self.bundle = build_train_step(tcfg, self.mesh)
        self.m = n_workers(self.mesh, self.bundle.scope)
        if self.m != tr["m"]:
            raise ValueError(f"mesh {tr['mesh']} gives {self.m} workers, the "
                             f"traffic states m={tr['m']}")
        psh, osh, bsh = self.bundle.shardings(self.mesh)
        have = jax.tree.map(lambda d: tuple(d.shape), TF.param_defs(mcfg),
                            is_leaf=PM.is_param_def)
        if have != ref.shapes(cfg):
            raise ValueError("the program's parameter layout differs from the "
                             "reference's")
        rep = NamedSharding(self.mesh, P())
        self.bsh = bsh["tokens"]
        self.make_params = jax.jit(lambda k: ref.init(cfg, k), out_shardings=psh)
        self.make_opt = jax.jit(
            lambda: {"m": ref.zeros_like(ref.shapes_struct(cfg)),
                     "v": ref.zeros_like(ref.shapes_struct(cfg))},
            out_shardings=osh)
        self.norms = jax.jit(ref.leaf_norms, out_shardings=rep)
        # the reference runs on the first chip alone
        self.dev0 = ctx["devices"][0] if ctx.get("devices") else jax.devices()[0]
        self.make_ref_params = jax.jit(
            lambda k: ref.init(cfg, k),
            out_shardings=jax.sharding.SingleDeviceSharding(self.dev0))
        self.ref_change = jax.jit(
            lambda p, p0: ref.leaf_norms(jax.tree.map(jnp.subtract, p, p0)))
        self.change = jax.jit(
            lambda p, p0: ref.leaf_norms(jax.tree.map(jnp.subtract, p, p0)),
            out_shardings=rep)
        self.shape = (self.m, tr["batch_per_worker"], tr["seq"])
        self.tokens_per_step = int(np.prod(self.shape))
        self.params = self.opt = None

    def start(self, seed: int):
        """Weights, optimizer state and token ring of one seed."""
        self.seed = seed
        self.key_w = common.prng_key(seed, 1)
        self.key = common.prng_key(seed, 3)
        self.ring = [token_batch(self.cfg["vocab_size"], seed, i, self.shape)
                     for i in range(RING)]
        self.step = 0
        self.params = self.make_params(self.key_w)
        self.opt = self.make_opt()

    def feed(self, i: int):
        import jax
        return {"tokens": jax.device_put(self.ring[i % RING], self.bsh)}

    def run_step(self, batch):
        import jax
        import jax.numpy as jnp
        self.params, self.opt, met = self.bundle.step_fn(
            self.params, self.opt, batch, jnp.int32(self.step),
            jax.random.fold_in(self.key, self.step))
        self.step += 1
        return met

    def first_steps(self) -> dict:
        """Steps 0..N_CHECKED-1 through the window's call and feed, and
        the readings the reference is compared with."""
        losses, grad = [], None
        for i in range(N_CHECKED):
            met = self.run_step(self.feed(i))
            losses.append(float(met["loss"]))
            if i == 0:
                grad = np.asarray(self.norms(self.opt["m"])) / (1 - self.hp["b1"])
        p0 = self.make_params(self.key_w)
        change = np.asarray(self.change(self.params, p0))
        del p0
        return {"loss": losses, "grad": grad, "change": change}

    def free(self):
        self.params = self.opt = None

    def reference(self, precision=None, dtype=None, keep_rows=None) -> dict:
        """The plain reference's readings over the same weights and
        tokens, on the first chip.  The gradient of a step is that of the
        mean loss over every worker's rows (each row has the same number
        of positions), taken in blocks of the traffic's
        ``reference_rows`` rows, recomputing each layer in the backward
        pass where it sets ``reference_remat``, so that it fits.  ``precision``/``dtype``
        give the control of a lower precision: the parameters are held in
        ``dtype`` (rounded after every update) and the forward and
        backward passes run in it.  ``keep_rows`` keeps that many leading
        rows of the step's batch, workers in order (a fault: half of the
        batch left out, or only the first worker's rows where the
        exchange between workers is left out)."""
        import jax
        import jax.numpy as jnp
        precision = ref.HIGHEST if precision is None else precision
        dtype = jnp.dtype(jnp.float32 if dtype is None else dtype)
        store = (lambda t: t) if dtype == jnp.float32 else self.rounder(dtype)
        p = store(self.make_ref_params(self.key_w))
        host_state, losses, grad = None, [], None
        for i in range(N_CHECKED):
            rows = self.ring[i].reshape(-1, self.shape[-1])[:keep_rows]
            loss, g = self.ref_loss_and_grad(p, rows, precision, dtype)
            losses.append(loss)
            # AdamW's state waits on the host while the gradient is taken,
            # so that the reference fits beside it at the cell's batch
            state = ({"m": ref.zeros_like(p), "v": ref.zeros_like(p)}
                     if host_state is None else jax.device_put(host_state, self.dev0))
            p, state, gn = ref.adamw_step(p, g, state, i, self.hp)
            p = store(p)
            del g
            if i == 0:
                grad = np.asarray(gn)
            host_state = jax.device_get(state) if i + 1 < N_CHECKED else None
            del state
        p0 = store(self.make_ref_params(self.key_w))
        change = np.asarray(self.ref_change(p, p0))
        return {"loss": losses, "grad": grad, "change": change}

    def ref_loss_and_grad(self, p, rows, precision, dtype):
        """(mean loss, its float32 gradient) over ``rows`` [n, S], in
        blocks of ``reference_rows`` rows, each weighted by its share."""
        import jax
        block = self.tr.get("reference_rows", len(rows))
        total, g = 0.0, None
        for s in range(0, len(rows), block):
            part = rows[s:s + block]
            w = len(part) / len(rows)
            loss, gb = ref.loss_and_grad(self.cfg, p, jax.device_put(part, self.dev0),
                                         precision, dtype,
                                         self.tr.get("reference_remat", False))
            total += w * float(loss)
            g = (gb if len(part) == len(rows)
                 else ref.scale(gb, w) if g is None else ref.add_scaled(g, gb, w))
            del gb
        return total, g

    def rounder(self, dtype):
        """Rounds every leaf to ``dtype``'s precision and range, kept in
        its own dtype.  ``reduce_precision`` and not a pair of casts: the
        TPU compiler may drop a cast pair as excess precision."""
        import jax
        import jax.numpy as jnp
        fi = jnp.finfo(dtype)
        return jax.jit(lambda t: jax.tree.map(
            lambda x: jax.lax.reduce_precision(x, exponent_bits=fi.nexp,
                                               mantissa_bits=fi.nmant), t),
            donate_argnums=0)


# ---- the window interface the harness calls --------------------------------

def setup(ctx: dict) -> dict:
    run = Run(ctx)
    run.start(ctx["seed"])
    readings = run.first_steps()
    return {"run": run, "readings": readings}


def measure(state: dict, seconds: float) -> dict:
    """Steps until ``seconds`` have passed, each ended by a wait on its
    loss.  The traffic's ``dispatch_ahead_steps`` (0 where it names none)
    is how many steps are sent before the wait on the oldest, so that the
    chip stays fed while the host stands still.  When the time is up
    nothing more is sent, every step sent is waited for, and the clock is
    read after that.  A step's time is the interval between its end and
    the end of the one before."""
    run = state["run"]
    ahead = int(run.tr.get("dispatch_ahead_steps", 0))
    pending, times, losses = collections.deque(), [], []
    i = N_CHECKED
    t0 = last = time.perf_counter()
    deadline = t0 + seconds

    def wait_oldest():
        nonlocal last
        loss = pending.popleft()
        with common.annotate("sync"):
            loss.block_until_ready()
        te = time.perf_counter()
        times.append(te - last)
        losses.append(loss)
        last = te

    while last < deadline:
        with common.annotate("feed"):
            batch = run.feed(i)
        with common.annotate("step"):
            pending.append(run.run_step(batch)["loss"])
        i += 1
        while len(pending) > ahead:
            wait_oldest()
    while pending:
        wait_oldest()
    window_s = last - t0
    n = len(times)
    failed = int(sum(not np.isfinite(float(x)) for x in losses))
    # cells on one chip and on four report apart, so that each keeps a
    # bound of its own spread
    prefix = run.tr.get("metric_prefix", "train")
    return {
        "attempted": n, "failed": failed, "window_s": window_s,
        "steps": n, "tokens": n * run.tokens_per_step,
        "seq": run.shape[-1],
        "metrics": {
            f"{prefix}_tokens_per_s": (n * run.tokens_per_step / window_s, "tokens/s"),
            f"{prefix}_step_p90_ms": (float(np.percentile(times, 90)) * 1e3, "ms"),
        },
    }


def check(state: dict, result: dict) -> dict:
    """{number: value} compared after the window, with the program's
    state freed."""
    run = state["run"]
    run.free()
    return compare(state["readings"], run.reference())
