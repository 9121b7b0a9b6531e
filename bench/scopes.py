"""Device time of the training step by layer, read from the chip trace
through the program's own named scopes.

A TPU trace names each op by its HLO instruction (``%fusion.253 =
(f32[...]) fusion(...)``) and carries no ``op_name``.  The compiled step
does: every instruction's ``metadata={op_name="jit(step)/..."}`` holds the
``jax.named_scope``s it was traced under (``loss``, ``lm_head``,
``aggregate``, ``optimizer``, in ``training/step.py``,
``models/transformer.py`` and ``core/blocked.py``), and autodiff wraps the
backward ops' path in ``transpose(...)``.  So the cell's step is rebuilt
as the train window builds it, lowered with abstract arguments of the
window's shapes, shardings and dtypes, and compiled through the
persistent cache with the metadata in the cache key (a traced run's
first compile of a build is a whole one); each instruction is mapped to
a layer by its path, first rule that holds:

  1. ``aggregate`` if the path holds the scope ``aggregate``;
  2. ``optimizer`` if it holds ``optimizer``;
  3. ``backward`` if it holds ``loss`` and ``transpose(``;
  4. ``forward`` if it holds ``loss``;
  5. ``other``.

``lm_head`` is a flag apart.  Trace events join the map by instruction
name, checked against the result shape in the event's text.  A layer's
time is the union of its events' intervals on each chip (a ``while`` and
the ops of its body are both events), averaged over the chips, per step.
Where more than ``JOIN_LIMIT`` of the traced busy time fails to join,
nothing is read: a stale map shows as missing, never as wrong.
"""
from __future__ import annotations

import re

from bench import common, trace

LAYERS = ("forward", "backward", "optimizer", "aggregate", "other")
JOIN_LIMIT = 0.01

_INSTR = re.compile(r"^\s*(?:ROOT\s+)?%?([\w.\-]+) = (.*)$")
_OP_NAME = re.compile(r'op_name="([^"]*)"')
_ARRAY = re.compile(r"(\w+)\[([\d,]*)\]")
_MODULE = re.compile(r"^HloModule ([\w.\-]+)", re.M)
_WRAP = re.compile(r"^(?:\w+\()+")


def scope_names(path: str) -> set:
    """The scope names of an ``op_name`` path, each taken out of the
    transformations that wrap it (``vmap(transpose(jvp(loss)))`` ->
    ``loss``)."""
    return {_WRAP.sub("", part).rstrip(")") for part in path.split("/")}


def layer_of(path: str) -> str:
    """The layer of an instruction from its ``op_name`` path."""
    names = scope_names(path)
    if "aggregate" in names:
        return "aggregate"
    if "optimizer" in names:
        return "optimizer"
    if "loss" in names:
        return "backward" if "transpose(" in path else "forward"
    return "other"


def is_lm_head(path: str) -> bool:
    return "lm_head" in scope_names(path)


def result_type(rhs: str) -> str:
    """The result type at the head of an instruction's right-hand side
    (``(f32[8]{0}, s32[]) fusion(...)`` -> ``(f32[8]{0}, s32[])``)."""
    if not rhs.startswith("("):
        return rhs.split(" ", 1)[0]
    depth = 0
    for i, c in enumerate(rhs):
        depth += c == "("
        depth -= c == ")"
        if depth == 0:
            return rhs[:i + 1]
    return rhs


def arrays(type_text: str) -> tuple:
    """(dtype, dims) of every array in a type, layouts left out."""
    return tuple(_ARRAY.findall(type_text))


def instructions(text: str) -> dict:
    """{instruction: (op_name path, right-hand side)} of a compiled
    module's text, every computation included."""
    out = {}
    for line in text.splitlines():
        m = _INSTR.match(line)
        if m:
            op = _OP_NAME.search(m.group(2))
            out[m.group(1)] = (op.group(1) if op else "", m.group(2))
    return out


def parse_hlo(text: str) -> tuple:
    """(module name, {instruction: (layer, lm_head, arrays)})."""
    mod = _MODULE.search(text)
    return (mod.group(1) if mod else None), {
        name: (layer_of(path), is_lm_head(path), arrays(result_type(rhs)))
        for name, (path, rhs) in instructions(text).items()}


def compiled_step_text(config: dict, traffic: dict) -> str:
    """The compiled HLO of a train cell's step, built as the train window
    builds it and lowered with abstract arguments of its shapes,
    shardings and dtypes."""
    import jax
    import jax.numpy as jnp
    run = common.window(traffic["window"]).Run(
        {"config": config, "traffic": traffic})
    psh, osh, _bsh = run.bundle.shardings(run.mesh)

    def abstract(fn, shardings, *args):
        shapes = jax.eval_shape(fn, *args)
        return jax.tree.map(lambda s, sh: jax.ShapeDtypeStruct(
            s.shape, s.dtype, sharding=sh), shapes, shardings)

    key = common.prng_key(0, 3)
    params = abstract(run.make_params, psh, key)
    opt = abstract(run.make_opt, osh)
    batch = {"tokens": jax.ShapeDtypeStruct(run.shape, jnp.int32,
                                            sharding=run.bsh)}
    step_key = jax.eval_shape(jax.random.fold_in, key, 0)
    lowered = run.bundle.step_fn.lower(
        params, opt, batch, jax.ShapeDtypeStruct((), jnp.int32), step_key)
    # the persistent cache leaves metadata out of its key by default, so
    # a hit could bring back the op_name paths of another build of the
    # same step (one without the scopes): keep them in the key here
    flag = "jax_compilation_cache_include_metadata_in_key"
    was = getattr(jax.config, flag)
    jax.config.update(flag, True)
    try:
        return lowered.compile().as_text()
    finally:
        jax.config.update(flag, was)


def _hlo_module(e) -> str | None:
    for k, v in e.stats:
        if k == "hlo_module":
            return str(v)
    return None


def split(summary, steps: int, module: str | None, op_map: dict) -> dict:
    """ms per step of each layer, of ``lm_head``, and of ``agg_exposed``
    (aggregation time in which no other op runs on the chip), with
    ``busy``, ``unjoined`` (ms) and ``join_miss`` (share of busy time).
    A layer that no instruction of the map is in (a program without its
    scope) is left out, and so is ``agg_exposed`` with ``aggregate``."""
    keys = (*LAYERS, "lm_head", "agg_exposed", "busy", "unjoined")
    tot = dict.fromkeys(keys, 0.0)
    for evs in summary.ops.values():
        # a backend that names each op's module (the CPU) keeps the
        # step's own; a TPU trace names none, and every op counts
        evs = [e for e in evs if _hlo_module(e) in (None, module)]
        by_layer = {k: [] for k in LAYERS}
        head, joined, not_agg = [], [], []
        for e in evs:
            hit = op_map.get(e.short)
            if hit is None or (" = " in e.name and
                               arrays(result_type(e.name.split(" = ", 1)[1]))
                               != hit[2]):
                not_agg.append(e)
                continue
            layer, lm, _ = hit
            by_layer[layer].append(e)
            joined.append(e)
            if lm:
                head.append(e)
            if layer != "aggregate":
                not_agg.append(e)
        busy = trace.busy_s(evs)
        tot["busy"] += busy
        tot["unjoined"] += busy - trace.busy_s(joined)
        for k in LAYERS:
            tot[k] += trace.busy_s(by_layer[k])
        tot["lm_head"] += trace.busy_s(head)
        # aggregation time that no other op covers
        tot["agg_exposed"] += (trace.busy_s(by_layer["aggregate"] + not_agg)
                               - trace.busy_s(not_agg))
    named = {"other", "busy", "unjoined"}
    for layer, lm, _ in op_map.values():
        named |= {layer, "lm_head"} if lm else {layer}
    if "aggregate" in named:
        named.add("agg_exposed")
    n = len(summary.ops) * steps
    out = {k: v * 1e3 / n for k, v in tot.items() if k in named}
    out["join_miss"] = out["unjoined"] / out["busy"] if out["busy"] else 1.0
    return out


def layers(ctx) -> dict | None:
    """:func:`split` of a traced train cell, or None where the window
    counted no steps or too much fails to join.  The step is compiled
    once per run: the split is kept in the readers' shared ``ctx``."""
    w = ctx["window"]
    if "steps" not in w or not w["steps"]:
        return None
    if "scopes" not in ctx:
        module, op_map = parse_hlo(compiled_step_text(ctx["config"],
                                                      ctx["traffic"]))
        ctx["scopes"] = split(ctx["trace"], w["steps"], module, op_map)
    got = ctx["scopes"]
    return got if got["join_miss"] <= JOIN_LIMIT else None


def read(ctx, key: str) -> float | None:
    got = layers(ctx)
    return None if got is None else got.get(key)
