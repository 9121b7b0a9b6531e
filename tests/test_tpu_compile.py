"""The aggregation kernels compile for a TPU v5e chip.

Nothing runs: each kernel is lowered with ``interpret=False`` for a
described (not attached) v5e topology and handed to the TPU compiler,
which refuses what the chip would refuse — block shapes off the (8, 128)
tiling, unsupported Mosaic ops, too much VMEM.  Each kernel compiles at
the block it derives from the shape.  Widths: one qwen3-0.6b MLP matrix
(1024 x 3072, whole blocks), a width under one block and no multiple of
128 (a2a chunks have arbitrary widths), and two past several blocks
with a ragged last one: whole lanes (300,032) and not (300,001).

The topology is described inside a module fixture, never at import:
only one process may load the TPU library, and every test worker
imports this file.
"""
import importlib
import math
import os
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels import ref

K = importlib.import_module("repro.kernels.brsgd_stats")

KERNELS = {
    "fused_stats": lambda G: K.fused_stats_pallas(G, ref.STAT_NAMES,
                                                  interpret=False),
    "brsgd_stats": lambda G: K.brsgd_stats_pallas(G, interpret=False),
    "cwise_median": lambda G: K.cwise_median_pallas(G, interpret=False),
    "select_mean": lambda G: K.select_mean_pallas(
        G, jnp.ones(G.shape[0]), jnp.ones(G.shape[0]), 0.5, 0.0,
        interpret=False),
    "masked_mean": lambda G: K.masked_mean_pallas(
        G, jnp.ones(G.shape[0]), interpret=False),
    "trimmed_mean": lambda G: K.trimmed_mean_pallas(G, 0.25,
                                                    interpret=False),
}


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        t = topologies.get_topology_desc(platform="tpu",
                                         topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip cannot be read back from the
    # persistent cache without one: keep these compiles out of it
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield t
    jax.config.update("jax_enable_compilation_cache", was)


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.mark.parametrize("d", [1024 * 3072, 1000, 300_032, 300_001])
@pytest.mark.parametrize("m", [4, 8])
@pytest.mark.parametrize("kernel", sorted(KERNELS))
def test_kernel_compiles_for_v5e(one_chip, kernel, m, d):
    G = jax.ShapeDtypeStruct((m, d), jnp.float32, sharding=one_chip)
    compiled = jax.jit(KERNELS[kernel]).lower(G).compile()
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("d", [65_536, 65_537, 15_730_944])
@pytest.mark.parametrize("m", [4, 8])
@pytest.mark.parametrize("kernel", sorted(KERNELS))
def test_kernel_compiles_bf16_for_v5e(one_chip, kernel, m, d):
    """bf16 G, as the benchmark's bucket: one whole block at m = 8, one
    block and one column, and the bucket's 240 blocks and a ragged 241st
    of 2,304 columns."""
    G = jax.ShapeDtypeStruct((m, d), jnp.bfloat16, sharding=one_chip)
    compiled = jax.jit(KERNELS[kernel]).lower(G).compile()
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("rule", ["mean", "brsgd", "median", "trimmed_mean",
                                  "krum", "multi_krum", "geomedian"])
def test_aggregate_local_compiles_for_v5e(one_chip, monkeypatch, rule):
    """Every rule of the engine's local executor compiles with the
    kernels at a ragged width."""
    from repro.configs.base import ByzantineConfig
    from repro.core import engine
    from repro.kernels import ops
    monkeypatch.setattr(ops, "_INTERPRET", False)
    G = jax.ShapeDtypeStruct((8, 300_001), jnp.bfloat16, sharding=one_chip)
    cfg = ByzantineConfig(aggregator=rule)
    jax.jit(lambda g: engine.aggregate_local(g, cfg, use_pallas=True)) \
        .lower(G).compile()


def _bucket_round_hlo(one_chip, monkeypatch):
    from repro.configs.base import ByzantineConfig
    from repro.core import engine
    from repro.kernels import ops
    monkeypatch.setattr(ops, "_INTERPRET", False)
    monkeypatch.setattr(ops, "_USE_PALLAS_DEFAULT", True)
    G = jax.ShapeDtypeStruct((8, 15_730_944), jnp.bfloat16, sharding=one_chip)
    cfg = ByzantineConfig(aggregator="brsgd", beta=0.5)

    def round_(g):
        agg, st = engine.aggregate_local(g, cfg, return_state=True)
        return agg, st.selected

    return jax.jit(round_).lower(G).compile().as_text()


def test_bucket_round_names_its_kernels(one_chip, monkeypatch):
    """The BrSGD round over one qwen3-0.6b layer's bucket, compiled as a
    TPU process runs it, holds exactly one instruction named after each
    of its two Pallas passes, each the kernel's custom call: the names
    the benchmark's kernel readers match in the chip trace."""
    text = _bucket_round_hlo(one_chip, monkeypatch)
    instrs = re.findall(r"^\s*(?:ROOT )?%([\w.\-]+) = .*?\s([\w\-]+)\(", text,
                        re.M)
    for kernel in ("fused_stats", "select_mean"):
        hits = [(n, op) for n, op in instrs if kernel in n]
        assert len(hits) == 1 and hits[0][1] == "custom-call", (kernel, hits)


def _elements(shape: str) -> int:
    """Elements of an HLO shape's largest array (a tuple's largest)."""
    dims = re.findall(r"\w+\[([\d,]*)\]", shape)
    return max((math.prod(int(x) for x in d.split(",") if x) for d in dims),
               default=0)


def test_bucket_round_reads_G_only_in_its_kernels(one_chip, monkeypatch):
    """In the compiled round over G [8, 15,730,944] bf16 (no whole number
    of blocks), nothing but the two Pallas custom calls reads an operand
    of [d] elements or more: G is not padded or copied, and the
    aggregate leaves ``select_mean`` finished, with no division or
    reshape pass after it."""
    text = _bucket_round_hlo(one_chip, monkeypatch)
    d = 15_730_944
    entry = text[text.index("\nENTRY "):]
    entry = entry[:entry.index("\n}")]
    defs = re.findall(r"^\s*(?:ROOT )?%([\w.\-]+) = (\(.*?\)|\S+) "
                      r"([\w\-]+)\((.*?)\)", entry, re.M)
    size = {name: _elements(shape) for name, shape, _op, _args in defs}
    kernels, readers = [], []
    for name, _shape, op, args in defs:
        big = [a for a in re.findall(r"%([\w.\-]+)", args)
               if size.get(a, 0) >= d]
        if op == "custom-call":
            kernels.append(name)
        elif big and op not in ("get-tuple-element", "tuple"):
            readers.append((name, op, big))
    assert sorted(kernels) == ["fused_stats.1", "select_mean.1"], kernels
    assert readers == [], readers
    root = re.search(r"ROOT %\S+ = .*? tuple\(%([\w.\-]+)", entry)
    agg = re.search(rf"%{re.escape(root.group(1))} = \S+ "
                    r"get-tuple-element\(%([\w.\-]+)\)", entry)
    assert agg and agg.group(1) == "select_mean.1", root.group(0)
