"""Trace-time contract extraction: walk a closed jaxpr and record every
collective with its axes, payload and manual-axis context.

This is the ONE jaxpr-walking implementation in the repo — the ad-hoc
walkers the pin tests in tests/test_engine.py (gather-count) and
tests/test_blocked.py (barrier no-fallback) used to carry are migrated
onto :func:`extract` / :func:`trace`.

The walk recurses through every higher-order primitive generically
(``pjit``, ``scan``, ``while``, ``cond`` branches, ``custom_vjp`` /
``custom_jvp`` call jaxprs, ``remat``): any equation parameter that is
a Jaxpr/ClosedJaxpr (or a tuple/list of them) is entered.  Two
primitives get special handling:

  * ``shard_map`` — establishes the manual-axis context.  Its
    ``manual_axes`` parameter names the manual mesh axes; the rest stay
    under GSPMD inside the region.  Collectives recorded inside
    carry that context, which is what the ``no-collective-over-auto-
    axis`` rule (the PR-5 XLA SPMD crash class) reads.
  * ``scan``/``while`` — multiply the trip count into every op of the
    body (``scan`` declares ``length``; ``while`` trips are unknown at
    trace time and are counted once, noted in ``notes``).
"""
from __future__ import annotations

import numpy as np

from .contract import KIND_FROM_PRIM, CollectiveContract, CollectiveOp

_LOOP_PRIMS = {"scan"}


def _source(eqn) -> str:
    try:
        from jax._src import source_info_util
        return str(source_info_util.summarize(eqn.source_info))
    except Exception:
        return ""


def _axis_names(params) -> tuple:
    """Mesh axis names a collective runs over (``axes``/``axis_name``
    param; positional vmap axes — ints — are dropped)."""
    raw = params.get("axes", params.get("axis_name", ()))
    if raw is None:
        return ()
    if not isinstance(raw, (tuple, list)):
        raw = (raw,)
    return tuple(a for a in raw if isinstance(a, str))


def _var_payload(v):
    """(shape, dtype str, bytes) of one jaxpr atom, 0 for non-numeric
    avals (tokens, extended dtypes without a byte width)."""
    aval = v.aval
    shape = tuple(getattr(aval, "shape", ()))
    dt = getattr(aval, "dtype", None)
    try:
        nbytes = int(np.prod(shape, dtype=np.int64)) * np.dtype(dt).itemsize
    except Exception:
        return shape, str(dt), 0.0
    return shape, str(np.dtype(dt)), float(nbytes)


def _sub_jaxprs(val):
    """Yield raw Jaxprs inside one eqn param value."""
    if hasattr(val, "jaxpr"):           # ClosedJaxpr
        yield val.jaxpr
    elif hasattr(val, "eqns"):          # raw Jaxpr
        yield val
    elif isinstance(val, (tuple, list)):
        for v in val:
            yield from _sub_jaxprs(v)


class _Walk:
    def __init__(self):
        self.ops = []
        self.notes = {}
        self.n_eqns = 0     # global collective-call counter -> op.group

    def walk(self, jaxpr, mult=1.0, manual=(), auto=(), in_sm=False):
        prev = None         # (prim, axes, source) of the preceding eqn
        for eqn in jaxpr.eqns:
            name = eqn.primitive.name
            key, prev = prev, None

            if name == "shard_map":
                mesh = eqn.params.get("mesh")
                names = tuple(getattr(mesh, "axis_names", ()))
                in_manual = eqn.params.get("manual_axes", frozenset(names))
                man = tuple(a for a in names if a in in_manual)
                auto_axes = tuple(sorted(a for a in names
                                         if a not in in_manual))
                for sub in _sub_jaxprs(eqn.params.get("jaxpr")):
                    self.walk(sub, mult, man, auto_axes, True)
                continue

            kind = KIND_FROM_PRIM.get(name)
            if kind is not None:
                axes = _axis_names(eqn.params)
                # one record per payload operand: a psum of a stats dict
                # binds one eqn per leaf, back to back from one source
                # line, and rules reason per-array (shape/dtype).
                # ``group`` ties the operands of ONE such call back
                # together — the masked-psum-validity rule reasons about
                # a whole stats psum at once.
                prev = (name, axes, _source(eqn))
                if prev != key:
                    self.n_eqns += 1
                gid = self.n_eqns - 1
                outs = eqn.outvars if kind != "reduce_scatter" \
                    else eqn.invars
                for v in (outs or eqn.outvars):
                    shape, dt, nbytes = _var_payload(v)
                    self.ops.append(CollectiveOp(
                        kind=kind, axes=axes, shape=shape, dtype=dt,
                        bytes=nbytes, count=mult, manual_axes=manual,
                        auto_axes=auto, in_shard_map=in_sm,
                        source=_source(eqn), ir="jaxpr", group=gid))
                continue

            sub_mult = mult
            if name in _LOOP_PRIMS:
                sub_mult = mult * float(eqn.params.get("length", 1))
            elif name == "while":
                self.notes["unknown_trip_whiles"] = \
                    self.notes.get("unknown_trip_whiles", 0) + 1
            for val in eqn.params.values():
                for sub in _sub_jaxprs(val):
                    self.walk(sub, sub_mult, manual, auto, in_sm)


def extract(closed_jaxpr, meta=None) -> CollectiveContract:
    """Contract of a (closed) jaxpr — pjit/scan/custom_vjp/shard_map
    regions are entered recursively, trip counts multiplied through."""
    w = _Walk()
    jx = getattr(closed_jaxpr, "jaxpr", closed_jaxpr)
    w.walk(jx)
    return CollectiveContract(ops=tuple(w.ops), meta=dict(meta or {}),
                              notes=w.notes)


def trace(fn, *args, meta=None, **kwargs) -> CollectiveContract:
    """``jax.make_jaxpr`` + :func:`extract` in one call.  ``args`` may
    be ShapeDtypeStructs — nothing is executed."""
    import jax
    return extract(jax.make_jaxpr(fn)(*args, **kwargs), meta=meta)
