#!/usr/bin/env python3
"""Compile a cell's timed program at its real size for a described TPU
v5e, with no chip attached, and print its memory and what its HLO holds:

  JAX_PLATFORMS=cpu python3 bench/rehearse.py --workload <cell> [--batch 1,2,3]

For a train cell, ``--batch`` tries those batches per worker (the
largest that compiles is the one a deployment would run); for an agg
cell, the round and the generator of G are compiled.  One JSON line per
compile.  The compiler refuses a program that does not fit the chip's
memory, so nothing here needs chip time.  Sizes only: a compile is not a
run and gives no time.
"""
from __future__ import annotations

import argparse
import json
import os
import pathlib
import sys

if __package__ in (None, ""):
    sys.path[0] = str(pathlib.Path(__file__).resolve().parents[1])

from bench import common  # noqa: E402

GIB = 2 ** 30


def describe(compiled) -> dict:
    ma = compiled.memory_analysis()
    text = compiled.as_text()
    return {"argument_GiB": ma.argument_size_in_bytes / GIB,
            "output_GiB": ma.output_size_in_bytes / GIB,
            "alias_GiB": ma.alias_size_in_bytes / GIB,
            "temp_GiB": ma.temp_size_in_bytes / GIB,
            "pallas_calls": text.count("tpu_custom_call"),
            **{k.replace("-", "_"): text.count(k)
               for k in ("all-to-all", "all-gather", "all-reduce")}}


def topology(chips: int):
    from jax.experimental import topologies
    return topologies.get_topology_desc(
        platform="tpu", topology_name="v5e:2x2").devices[:chips]


def train(cell: dict, cfg: dict, tr: dict, batch: int) -> dict:
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import AxisType, Mesh, NamedSharding, PartitionSpec as P

    from bench.reference import qwen3
    from bench.windows import train as W
    from repro.configs import ByzantineConfig, TrainConfig
    from repro.launch.mesh import n_workers
    from repro.training.step import build_train_step

    shape = tuple(int(x) for x in tr["mesh"].split("x"))
    mesh = Mesh(np.array(topology(cell["chips"])).reshape(shape),
                ("data", "model")[:len(shape)],
                axis_types=(AxisType.Auto,) * len(shape))
    hp = cfg["optimizer"]
    tcfg = TrainConfig(
        model=W.model_config(cfg),
        byzantine=ByzantineConfig(aggregator=tr["aggregator"],
                                  attack=tr["attack"], alpha=tr["alpha"]),
        optimizer="adamw", lr=hp["lr"], weight_decay=hp["weight_decay"],
        grad_clip=hp["grad_clip"], agg_layout=tr["agg_layout"],
        agg_scope=tr["agg_scope"], remat=tr["remat"])
    bundle = build_train_step(tcfg, mesh)
    m = n_workers(mesh, bundle.scope)
    psh, _osh, bsh = bundle.shardings(mesh)
    params = jax.tree.map(
        lambda s, sh: jax.ShapeDtypeStruct(s.shape, jnp.float32, sharding=sh),
        qwen3.shapes_struct(cfg), psh)
    rep = NamedSharding(mesh, P())
    key = jax.eval_shape(lambda: jax.random.PRNGKey(0))
    compiled = bundle.step_fn.lower(
        params, {"m": params, "v": params},
        {"tokens": jax.ShapeDtypeStruct((m, batch, tr["seq"]), jnp.int32,
                                        sharding=bsh["tokens"])},
        jax.ShapeDtypeStruct((), jnp.int32, sharding=rep),
        jax.ShapeDtypeStruct(key.shape, key.dtype, sharding=rep)).compile()
    return {"batch_per_worker": batch, **describe(compiled)}


def agg(cell: dict, cfg: dict, tr: dict) -> dict:
    import jax
    import jax.numpy as jnp
    from jax.sharding import SingleDeviceSharding

    from bench.windows import agg as A
    from repro.kernels import ops
    # the kernels' compiled path, as a TPU process selects it at import
    ops._INTERPRET = False
    ops._USE_PALLAS_DEFAULT = True
    one = SingleDeviceSharding(topology(1)[0])
    d = A.columns(cfg, tr)
    G = jax.ShapeDtypeStruct((tr["m"], d), jnp.dtype(tr["dtype"]), sharding=one)
    out = {"round": describe(A.entry_for(tr).lower(G).compile())}
    gen = jax.jit(lambda k: A.make_G(k, tr["m"], int(tr["alpha"] * tr["m"]), d,
                                     tr["attack_std"], jnp.dtype(tr["dtype"])))
    key = jax.ShapeDtypeStruct((2,), jnp.uint32, sharding=one)
    out["generator"] = describe(gen.lower(key).compile())
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__,
                                 formatter_class=argparse.RawTextHelpFormatter)
    ap.add_argument("--workload", required=True)
    ap.add_argument("--batch", default="")
    args = ap.parse_args(argv)
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    common.program_on_path()
    import jax
    jax.config.update("jax_enable_compilation_cache", False)
    cell = common.cell(args.workload)
    cfg, tr = common.config(cell["config"]), common.traffic(cell["traffic"])
    if tr["window"] == "agg":
        print(json.dumps(agg(cell, cfg, tr)), flush=True)
        return 0
    for b in [int(x) for x in args.batch.split(",") if x] or [tr["batch_per_worker"]]:
        try:
            print(json.dumps(train(cell, cfg, tr, b)), flush=True)
        except Exception as e:   # a refusal is the answer for this batch
            print(json.dumps({"batch_per_worker": b, "refused": str(e)[:400]}),
                  flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
