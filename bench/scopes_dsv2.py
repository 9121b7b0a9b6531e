"""Device time per training step under the program's named scopes of
DeepSeek-V2's layers, read from the chip trace as ``scopes.py`` reads
the step's layers: the cell's step is compiled with its scope paths
(``scopes.compiled_step_text``), each trace event joins its instruction
by name and result shape, and a scope's time is the union of its events'
intervals on each chip, averaged over the chips, per step.  An event
counts for every scope on its path, so ``moe`` holds ``dispatch`` and
``experts``; forward, backward and recomputation are all counted.

  mla       attention: projections, YaRN rope, scores and output
  moe       the MoE layer's FFN: router, dispatch, experts, shared experts
  dispatch  the sort and gather of the routed pairs, and the scatter-add
            that combines them
  experts   the grouped products of the held experts (the compiler's
            ``ragged-dot`` kernels, which carry no scope path) and the
            activation between them

Where more than ``scopes.JOIN_LIMIT`` of the traced busy time fails to
join, nothing is read; a scope that no instruction is under (a program
without it) is left out.
"""
from __future__ import annotations

from bench import scopes, trace

SCOPES = ("mla", "moe", "dispatch", "experts")


# The TPU compiler rewrites every grouped product (``ragged_dot``) into
# kernels of its own whose op_name is this and holds no scope path; the
# program's only grouped products are the held experts'.
GROUPED = "ragged-dot"


def scopes_of(path: str) -> frozenset:
    if path.startswith(GROUPED):
        return frozenset({"moe", "experts"})
    return frozenset(scopes.scope_names(path)) & frozenset(SCOPES)


def parse_hlo(text: str) -> dict:
    """{instruction: (the scopes of SCOPES on its path, result arrays)}."""
    return {name: (scopes_of(path), scopes.arrays(scopes.result_type(rhs)))
            for name, (path, rhs) in scopes.instructions(text).items()}


def split(summary, steps: int, op_map: dict) -> dict:
    """ms per step under each scope that the map names, with ``busy``
    and ``unjoined`` (ms) and ``join_miss`` (share of busy time)."""
    named = set().union(*(s for s, _ in op_map.values())) if op_map else set()
    tot = dict.fromkeys((*named, "busy", "unjoined"), 0.0)
    for evs in summary.ops.values():
        by_scope = {k: [] for k in named}
        joined = []
        for e in evs:
            hit = op_map.get(e.short)
            if hit is None or (" = " in e.name and scopes.arrays(
                    scopes.result_type(e.name.split(" = ", 1)[1])) != hit[1]):
                continue
            joined.append(e)
            for k in hit[0]:
                by_scope[k].append(e)
        busy = trace.busy_s(evs)
        tot["busy"] += busy
        tot["unjoined"] += busy - trace.busy_s(joined)
        for k in named:
            tot[k] += trace.busy_s(by_scope[k])
    n = len(summary.ops) * steps
    out = {k: v * 1e3 / n for k, v in tot.items()}
    out["join_miss"] = out["unjoined"] / out["busy"] if out["busy"] else 1.0
    return out


def read(ctx, scope: str) -> float | None:
    """ms per step under ``scope`` in a traced train cell, or None where
    the window counted no steps, too much fails to join, or the program
    has no such scope.  The step is compiled once per run: the split is
    kept in the readers' shared ``ctx``."""
    w = ctx["window"]
    if "steps" not in w or not w["steps"]:
        return None
    if "dsv2_scopes" not in ctx:
        text = scopes.compiled_step_text(ctx["config"], ctx["traffic"])
        ctx["dsv2_scopes"] = split(ctx["trace"], w["steps"], parse_hlo(text))
    got = ctx["dsv2_scopes"]
    return got.get(scope) if got["join_miss"] <= scopes.JOIN_LIMIT else None
