"""Compile the main path for a described TPU v5e (2x2) without a chip.

The TPU compiler is installed wherever libtpu is, and compiles for a
topology that is only described: what Mosaic or XLA would refuse on the
chip (block shapes, layouts, memory) fails here, at no chip time. Nothing
runs, so these tests say nothing about results or speed.

The topology is described inside a module fixture, never at import: one
process at a time may load the TPU library, and a worker that loads it
keeps it until it exits. Keep every such compile in this one file.
"""
import dataclasses
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import NamedSharding, PartitionSpec as P, SingleDeviceSharding

from repro.configs.base import ParallelConfig
from repro.configs.dlrm import CONFIG as DLRM_TABLE2, dlrm_v2
from repro.core import CollectiveEngine
from repro.core.hw_spec import TPU_V5E, TPU_V5E_MEASURED, hw_for_devices
from repro.kernels import fused_reduce, matmul, ops, quantize
from repro.models import dlrm as dlrm_mod
from repro.models.common import Builder
from repro.parallel.ops import ParCtx

ONE_CHIP_ROWS = 1_000_000


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _mesh(topo, shape, axes):
    n = int(np.prod(shape))
    return jax.sharding.Mesh(
        np.array(topo.devices[:n]).reshape(shape), axes,
        axis_types=(jax.sharding.AxisType.Auto,) * len(axes))


def _compile(fn, *shapes):
    return jax.jit(fn).lower(*shapes).compile()


def test_topology_is_v5e(topo):
    assert hw_for_devices(topo.devices) is TPU_V5E_MEASURED


def test_quantize_dequantize_compile(one_chip):
    n_blocks = (4 << 20) // 4 // quantize.QUANT_BLOCK   # a 4 MiB segment
    x = jax.ShapeDtypeStruct((n_blocks, quantize.QUANT_BLOCK), jnp.float32,
                             sharding=one_chip)
    c = _compile(lambda v: quantize.quantize_blocks(v, interpret=False), x)
    assert "tpu_custom_call" in c.as_text()
    q = jax.ShapeDtypeStruct(x.shape, jnp.int8, sharding=one_chip)
    s = jax.ShapeDtypeStruct((n_blocks,), jnp.float32, sharding=one_chip)
    c = _compile(lambda a, b: quantize.dequantize_blocks(a, b,
                                                         interpret=False),
                 q, s)
    assert "tpu_custom_call" in c.as_text()


@pytest.mark.parametrize("n", [256, 300 * 256, 1 << 20])
def test_quantize_padded_sizes_compile(one_chip, n):
    """Every size the codec pads to keeps the scale tiling Mosaic needs."""
    flat = jax.ShapeDtypeStruct((n,), jnp.float32, sharding=one_chip)
    c = _compile(lambda v: ops.quantize_int8(v, interpret=False), flat)
    assert "tpu_custom_call" in c.as_text()


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_fused_combine_compiles(one_chip, dtype):
    x = jax.ShapeDtypeStruct((8192, fused_reduce.LANES), dtype,
                             sharding=one_chip)
    c = _compile(lambda a, b: fused_reduce.fused_combine(
        a, b, op="add", interpret=False), x, x)
    assert "tpu_custom_call" in c.as_text()


def test_matmul_dlrm_fc1_compiles(one_chip):
    x = jax.ShapeDtypeStruct((256, 3200), jnp.float32, sharding=one_chip)
    w = jax.ShapeDtypeStruct((3200, 2048), jnp.float32, sharding=one_chip)
    c = _compile(lambda a, b: matmul.matmul_tiled(
        a, b, bm=256, bn=256, bk=128, interpret=False), x, w)
    assert "tpu_custom_call" in c.as_text()


def test_gather_reads_table_in_place(one_chip):
    """The lookup at one chip's DLRM share: 100 tables of 1,000,000 rows
    (allocated 128-aligned) x 32. The table reaches the kernel as a
    bitcast, so the program needs no temporary the size of a table."""
    v = -(-ONE_CHIP_ROWS // dlrm_mod.ROW_ALIGN) * dlrm_mod.ROW_ALIGN
    tab = jax.ShapeDtypeStruct((100, v, 32), jnp.float32, sharding=one_chip)
    idx = jax.ShapeDtypeStruct((100, 256), jnp.int32, sharding=one_chip)
    c = _compile(lambda t, i: ops.embedding_gather(t, i, interpret=False),
                 tab, idx)
    assert "tpu_custom_call" in c.as_text()
    mem = c.memory_analysis()
    assert mem.temp_size_in_bytes < 100 * 32 * 4 * 1024, mem


@pytest.mark.parametrize("use_pallas", [False, True])
def test_dlrm_one_chip_forward_fits(topo, monkeypatch, use_pallas):
    """Table 2 widths at one chip's share: the init and the forward each
    compile within the chip's HBM."""
    # the program picks interpret mode from jax.default_backend(), which
    # sees the CPU here: compile the kernel as the chip would run it
    monkeypatch.setattr(ops, "_interpret", lambda: False)
    cfg = dataclasses.replace(DLRM_TABLE2, rows_per_table=ONE_CHIP_ROWS)
    mesh = _mesh(topo, (1, 1, 1), ("pod", "data", "model"))
    init = dlrm_mod.dlrm_initializer(cfg, mesh).lower(
        jax.ShapeDtypeStruct((2,), jnp.uint32)).compile()
    im = init.memory_analysis()
    assert im.output_size_in_bytes + im.temp_size_in_bytes \
        < TPU_V5E.hbm_bytes, im

    pshapes = dlrm_mod.dlrm_params(
        Builder("shape", mesh=mesh, dtype=jnp.float32), cfg, 1)
    specs = dlrm_mod.dlrm_specs(cfg, 1)
    ctx = ParCtx(engine=CollectiveEngine(mesh), pcfg=ParallelConfig(),
                 mesh=mesh)
    fwd = jax.jit(jax.shard_map(
        lambda p, i: dlrm_mod.dlrm_forward(p, i, ctx, use_pallas),
        mesh=mesh, in_specs=(specs, P(None, None)),
        out_specs=P(None, None), check_vma=False))
    idx = jax.ShapeDtypeStruct((256, cfg.n_tables), jnp.int32,
                               sharding=NamedSharding(mesh, P()))
    c = fwd.lower(pshapes, idx).compile()
    mem = c.memory_analysis()
    assert mem.argument_size_in_bytes < TPU_V5E.hbm_bytes, mem
    assert mem.argument_size_in_bytes + mem.temp_size_in_bytes \
        < TPU_V5E.hbm_bytes, mem
    assert ("tpu_custom_call" in c.as_text()) == use_pallas


@pytest.mark.parametrize("collective", ["allreduce", "alltoall",
                                        "reduce_scatter", "allgather"])
def test_microcode_collectives_compile_on_four_chips(topo, collective):
    """The 4 MiB picks the engine makes on the chip (its measured spec:
    unsegmented allreduce and reduce-scatter) compile for four chips."""
    mesh = _mesh(topo, (4,), ("x",))
    eng = CollectiveEngine(mesh, backend="microcode")
    fn = getattr(eng, collective)
    g = jax.jit(jax.shard_map(
        lambda v: fn(v[0], "x")[None], mesh=mesh, in_specs=P("x"),
        out_specs=P("x"), check_vma=False))
    x = jax.ShapeDtypeStruct((4, (4 << 20) // 4), jnp.float32,
                             sharding=NamedSharding(mesh, P("x")))
    assert "collective-permute" in g.lower(x).compile().as_text()


def _split_by_conditional(hlo):
    """Parsed HLO -> (opcodes reached from the entry without entering a
    `conditional`, names of the gathers, scatters and sorts inside one)."""
    comps = hlo["comps"]
    outside, inside, seen = set(), [], set()
    todo = [(hlo["entry"], False)]
    while todo:
        comp, in_cond = todo.pop()
        if (comp, in_cond) in seen:
            continue
        seen.add((comp, in_cond))
        for ins in comps.get(comp, ()):
            if in_cond and ins["opcode"] in ("gather", "scatter", "sort"):
                inside.append(ins["name"])
            elif not in_cond:
                outside.add(ins["opcode"])
            todo += [(c, in_cond or ins["opcode"] == "conditional")
                     for c in ins["callees"]]
    return outside, inside


def test_dlrm_dcnv2_step_compiles_on_four_chips(topo):
    """DLRM-DCNv2 at the benchmark's cut (the five 40M-row tables at 18M:
    12.06 GB of packed tables per chip) and batch 2048 on a 2x2, with the
    engine priced as on the chip: the serving step compiles, holds the
    tables and its temporaries in each chip's HBM, and hands the bags to
    their owners through the engine's collective-permutes."""
    v2 = dlrm_v2()
    cfg = dataclasses.replace(v2, table_rows=tuple(
        18_000_000 if r == 40_000_000 else r for r in v2.rows))
    mesh = _mesh(topo, (1, 1, 4), ("pod", "data", "model"))
    pshapes = dlrm_mod.dlrm_params(
        Builder("shape", mesh=mesh, dtype=jnp.float32), cfg, 4)
    specs = dlrm_mod.dlrm_specs(cfg, 4)
    ctx = ParCtx(engine=CollectiveEngine(mesh, hw=TPU_V5E_MEASURED),
                 pcfg=ParallelConfig(), mesh=mesh)
    fwd = jax.jit(jax.shard_map(
        lambda p, i, d: dlrm_mod.dlrm_forward(p, i, ctx, dense=d, cfg=cfg),
        mesh=mesh, in_specs=(specs, P(None, None), P("model", None)),
        out_specs=P("model", None), check_vma=False))
    ids = jax.ShapeDtypeStruct((2048, cfg.ids_per_query), jnp.int32,
                               sharding=NamedSharding(mesh, P()))
    dense = jax.ShapeDtypeStruct(
        (2048, cfg.dense_features), jnp.float32,
        sharding=NamedSharding(mesh, P("model", None)))
    with jax.default_matmul_precision("highest"):
        c = fwd.lower(pshapes, ids, dense).compile()
    mem = c.memory_analysis()
    assert 12.0e9 < mem.argument_size_in_bytes < TPU_V5E.hbm_bytes, mem
    assert mem.argument_size_in_bytes + mem.temp_size_in_bytes \
        < TPU_V5E.hbm_bytes, mem
    text = c.as_text()
    assert "collective-permute" in text
    assert ctx.engine.metrics.get("dlrm.bag_collective_bytes") == \
        2048 * 26 * 128 * 4
    assert ctx.engine.metrics.get("dlrm.bag_rows_gathered") == 164_864
    # the chip's fusions of the bags' gathers, scatters and sorts (the
    # overflow path's too) class as the benchmark's `lookup`
    sys.path.insert(0, os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    from bench import opclass, scopes
    from bench.drivers.dlrm_dcnv2_serve import scope_class
    paths = scopes.scope_map(text)
    ops = [ins["name"] for comp in opclass.parse_hlo(text)["comps"].values()
           for ins in comp if ins["opcode"] in ("gather", "scatter", "sort")]
    assert ops
    assert {n: paths[n] for n in ops if scope_class(paths[n]) != "lookup"} \
        == {}
    # the compacted bags run outside the overflow's `conditional` (whose
    # span the trace would count beside its body's ops), and only the
    # masked bags inside it
    outside, inside = _split_by_conditional(opclass.parse_hlo(text))
    assert {"sort", "gather"} <= outside
    assert inside and all("dlrm.bag_overflow" in paths[n] for n in inside)
