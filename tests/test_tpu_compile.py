"""Compile-only checks against a described TPU v5e (no chip attached).

The TPU compiler is installed with JAX, so every Pallas kernel and the
main-path XLA programs can be compiled for a v5e that is described, not
present. This catches what interpret mode cannot: tile-misaligned
slices, unsupported 8-bit vector ops, SMEM layout mismatches, VMEM
overflow, and programs that do not fit one chip's HBM.

The topology is described inside a module-scoped fixture, never at
import: only one process may load the TPU library at a time, and every
test worker imports this file. Nothing here runs a kernel.
"""
from __future__ import annotations

import os
import re
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import AxisType, Mesh, NamedSharding
from jax.sharding import PartitionSpec as P
from jax.sharding import SingleDeviceSharding

from repro.core.hll import HLLConfig
from repro.engine import plans
from repro.engine.sharded import build_ingest_step
from repro.kernels import ops, registry, tiles
from repro.kernels.ertl_stats import ertl_stats
from repro.kernels.hip_delta import hip_delta_rows
from repro.kernels.hll_accumulate import hll_accumulate
from repro.kernels.hll_estimate import hll_estimate_stats
from repro.kernels.hll_propagate import hll_propagate
from repro.kernels.intersection_stats import intersection_stats
from repro.kernels.packing import row_width
from repro.kernels.union_estimate import union_estimate_stats

# the collective kinds the benchmark's trace reader counts (bench/ sits
# beside tests/ at the repo root)
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(
    __file__))))
from bench.collectives import KINDS as COLLECTIVES  # noqa: E402

#: HBM of one v5e chip.
V5E_HBM_BYTES = 16 * 2**30
#: the sharded engine's mesh axis
SHARD_AXIS = "sketch"

KERNELS = ("accumulate", "propagate", "union_estimate", "intersection_stats",
           "estimate", "ertl_stats", "hip_delta")
CASES = [(k, layout, p) for k in KERNELS for layout in ("byte", "packed")
         for p in (8, 10) if not (k == "hip_delta" and layout == "packed")]


@pytest.fixture(scope="module")
def v5e_2x2():
    """A described v5e:2x2 host (four chips), with the compile cache off."""
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — no TPU compiler installed
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip is written to the persistent cache
    # but cannot be read back without one
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield topo
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def one_chip(v5e_2x2):
    """One device of the described v5e:2x2 host."""
    return SingleDeviceSharding(v5e_2x2.devices[0])


@pytest.fixture(scope="module")
def four_chips(v5e_2x2):
    """The sharded engine's 1-D mesh over all four described chips."""
    return Mesh(np.asarray(v5e_2x2.devices), (SHARD_AXIS,),
                axis_types=(AxisType.Auto,))


def _max_rows(cfg: HLLConfig, layout: str) -> int:
    """Rows of the largest panel the pallas VMEM bound admits."""
    rows = tiles.PANEL_VMEM_BYTES // row_width(cfg.r, layout)
    return rows - rows % tiles.tile_rows(jnp.uint8)


def _kernel_call(kind: str, cfg: HLLConfig, layout: str, v: int):
    """(fn, arg dtypes/shapes) compiling one kernel at a v-row panel."""
    w = row_width(cfg.r, layout)
    panel = ((v, w), jnp.uint8)
    edges = ((4096,), jnp.int32)
    if kind == "accumulate":
        return (lambda r, a, k, m: hll_accumulate(
                    r, a, k, m, p=cfg.p, layout=layout, interpret=False),
                [panel, edges, ((4096,), jnp.uint32), ((4096,), jnp.bool_)])
    if kind == "propagate":
        return (lambda r, s, d: hll_propagate(r, s, d, layout=layout,
                                              interpret=False),
                [panel, edges, edges])
    if kind == "union_estimate":
        return (lambda r, i, m: union_estimate_stats(r, i, m, layout=layout,
                                                     interpret=False),
                [panel, ((64, 16), jnp.int32), ((64, 16), jnp.bool_)])
    if kind == "intersection_stats":
        return (lambda r, pr: intersection_stats(r, pr, cfg.q, layout=layout,
                                                 interpret=False),
                [panel, ((256, 2), jnp.int32)])
    if kind == "estimate":
        return (lambda r: hll_estimate_stats(r, layout=layout,
                                             interpret=False), [panel])
    if kind == "ertl_stats":
        pairs = ((4096, w), jnp.uint8)
        return (lambda a, b: ertl_stats(a, b, cfg.q, layout=layout,
                                        interpret=False), [pairs, pairs])
    return (lambda a, b: hip_delta_rows(a, b, interpret=False),
            [panel, panel])


def _compile(fn, shapes, sharding):
    args = [jax.ShapeDtypeStruct(s, d, sharding=sharding) for s, d in shapes]
    return jax.jit(fn).lower(*args).compile()


@pytest.mark.parametrize("kind,layout,p", CASES)
def test_pallas_kernel_compiles_at_vmem_bound(one_chip, kind, layout, p):
    """Each kernel compiles for v5e (not interpreted) at the largest panel
    ``registry.resolve`` admits."""
    cfg = HLLConfig(p=p)
    v = _max_rows(cfg, layout)
    registry.resolve("pallas", cfg, layout=layout, rows=v)
    fn, shapes = _kernel_call(kind, cfg, layout, v)
    compiled = _compile(fn, shapes, one_chip)
    assert compiled.as_text().count("tpu_custom_call") >= 1


@pytest.mark.parametrize("layout", ["byte", "packed"])
def test_vmem_bound_refuses_one_step_larger(layout):
    """A panel one row tile past the bound fails at resolve, naming it."""
    cfg = HLLConfig(p=10)
    v = _max_rows(cfg, layout)
    registry.resolve("pallas", cfg, layout=layout, rows=v)
    with pytest.raises(ValueError, match=str(tiles.PANEL_VMEM_BYTES)):
        registry.resolve("pallas", cfg, layout=layout,
                         rows=v + tiles.tile_rows(jnp.uint8))
    registry.resolve("ref", cfg, layout=layout, rows=v + 32)  # no bound


def test_vmem_bound_refuses_engine_open():
    """``engine.open`` with impl='pallas' refuses an oversized table
    instead of compiling it or switching to ref."""
    from repro import engine
    cfg = HLLConfig(p=8)
    n = _max_rows(cfg, "byte") + tiles.tile_rows(jnp.uint8)
    with pytest.raises(ValueError, match="VMEM"):
        engine.open(n, cfg, impl="pallas", layout="byte")


# Graph500 scale 20, edgefactor 16 at p=8: the chip smoke's main phase.
SCALE20_ROWS = 1 << 20
SCALE20_DIRECTED = 1 << 25  # shape bucket of both orientations of ~16M edges


def _fits(compiled) -> int:
    m = compiled.memory_analysis()
    total = m.argument_size_in_bytes + m.temp_size_in_bytes
    assert total < V5E_HBM_BYTES, (m.argument_size_in_bytes,
                                   m.temp_size_in_bytes)
    return total


def test_ref_ingest_plan_fits_one_chip(one_chip):
    cfg = HLLConfig(p=8)
    block = 2 * 32768  # both orientations of one INGEST_BLOCK
    shapes = [((SCALE20_ROWS, cfg.r), jnp.uint8), ((block,), jnp.int32),
              ((block,), jnp.uint32), ((block,), jnp.bool_)]
    compiled = _compile(
        lambda r, a, k, m: ops.accumulate(r, a, k, cfg, mask=m, impl="ref"),
        shapes, one_chip)
    _fits(compiled)


def test_ref_propagate_plan_fits_one_chip(one_chip):
    cfg = HLLConfig(p=8)
    plan = plans.build_propagate_plan(registry.resolve("ref", cfg))
    e = ((SCALE20_DIRECTED,), jnp.int32)
    shapes = [((SCALE20_ROWS, cfg.r), jnp.uint8), e, e,
              ((SCALE20_DIRECTED,), jnp.bool_)]
    args = [jax.ShapeDtypeStruct(s, d, sharding=one_chip) for s, d in shapes]
    compiled = plan.lower(*args).compile()
    assert _fits(compiled) > SCALE20_DIRECTED * cfg.r  # the u8[E, r] gather
    np.testing.assert_equal(compiled.as_text().count("tpu_custom_call"), 0)


def test_routing_extend_plan_compiles_at_scale20_bucket(one_chip):
    """One 65,536-slot slice written into the 2^25-slot routing."""
    plan = plans.build_routing_extend_plan()
    s = 2 * 32768  # 2 * INGEST_BLOCK
    shapes = [((SCALE20_DIRECTED,), jnp.int32), ((SCALE20_DIRECTED,),
              jnp.int32), ((SCALE20_DIRECTED,), jnp.bool_), ((), jnp.int32),
              ((s,), jnp.int32), ((s,), jnp.int32), ((s,), jnp.bool_)]
    args = [jax.ShapeDtypeStruct(sh, d, sharding=one_chip)
            for sh, d in shapes]
    compiled = plan.lower(*args).compile()
    assert _fits(compiled) >= 9 * SCALE20_DIRECTED  # the routing's bytes


# Graph500 scale 24 at p=10, row-sharded over the four chips: the sharded
# service cell's table (2^24 x 1,024 B = 17.2 GB, 4.29 GB a chip).
SCALE24_ROWS = 1 << 24
_BYTES = {"pred": 1, "u8": 1, "s8": 1, "u16": 2, "s16": 2, "bf16": 2,
          "f16": 2, "u32": 4, "s32": 4, "f32": 4}


def _collectives(text: str) -> list[tuple[str, int]]:
    """(HLO line, result bytes) of every collective op in a compiled text."""
    kind = re.compile(r"[\s)](%s)(-start)?\(" % "|".join(COLLECTIVES))
    out = []
    for line in text.splitlines():
        _, eq, rest = line.strip().partition(" = ")
        hit = kind.search(rest) if eq else None
        if hit is None:
            continue
        nbytes = 0
        for dt, dims in re.findall(r"(\w+)\[([\d,]*)\]",
                                   rest[:hit.start() + 1]):
            nbytes += _BYTES.get(dt, 4) * int(np.prod(
                [int(d) for d in dims.split(",") if d] or [1]))
        out.append((line.strip(), nbytes))
    return out


def _sharded_table(mesh, cfg):
    return jax.ShapeDtypeStruct((SCALE24_ROWS, cfg.r), jnp.uint8,
                                sharding=NamedSharding(mesh,
                                                       P(SHARD_AXIS, None)))


@pytest.mark.parametrize("sets,pairs", [(8, 8), (1024, 8), (1024, 1024)])
def test_sharded_mixed_plan_gathers_rows_not_the_panel(four_chips, sets,
                                                       pairs):
    """The service's fused union + intersection program on the 4-way
    row-sharded scale-24 table: it fits each chip, and what crosses chips
    is the gathered rows (a masked local gather per shard, summed), never
    the panel."""
    cfg = HLLConfig(p=10)
    kernels = registry.resolve("ref", cfg, rows=SCALE24_ROWS)
    plan = plans.build_mixed_plan(cfg, kernels, ("union", "intersection"),
                                  "ie", 30)
    rep = NamedSharding(four_chips, P())
    args = [_sharded_table(four_chips, cfg)] + [
        jax.ShapeDtypeStruct(s, d, sharding=rep) for s, d in (
            ((sets, 3), jnp.int32), ((sets, 3), jnp.bool_),
            ((pairs, 2), jnp.int32), ((pairs,), jnp.bool_))]
    compiled = plan.lower(*args).compile()
    assert _fits(compiled) >= SCALE24_ROWS // 4 * cfg.r
    found = _collectives(compiled.as_text())
    assert found, "a gather from a sharded table crosses chips"
    gathered = (sets * 3 + 2 * pairs) * cfg.r
    for line, nbytes in found:
        assert str(SCALE24_ROWS // 4) not in line and \
            str(SCALE24_ROWS) not in line, line
    # union lanes pad 3 -> 4 in the tiled layout: at most 4/3 of the rows
    assert sum(b for _, b in found) <= gathered * 4 // 3, found


@pytest.mark.parametrize("cap", [1 << 14, 1 << 15, 1 << 16])
def test_sharded_ingest_step_at_scale24(four_chips, cap):
    """The donated sharded accumulate compiles at scale-24, p=10 widths for
    every routed capacity one 32,768-edge chunk can need, in place, with
    no collective."""
    cfg = HLLConfig(p=10)
    kernels = registry.resolve("ref", cfg, rows=SCALE24_ROWS)
    step = build_ingest_step(four_chips, kernels, cfg, "ref")
    sh = NamedSharding(four_chips, P(SHARD_AXIS, None))
    args = [_sharded_table(four_chips, cfg)] + [
        jax.ShapeDtypeStruct((4, cap), d, sharding=sh)
        for d in (jnp.int32, jnp.uint32, jnp.bool_)]
    compiled = step.lower(*args).compile()
    m = compiled.memory_analysis()
    assert _fits(compiled) < SCALE24_ROWS // 4 * cfg.r + (64 << 20)
    assert m.alias_size_in_bytes == SCALE24_ROWS // 4 * cfg.r  # donated
    assert _collectives(compiled.as_text()) == []
