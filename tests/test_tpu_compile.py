"""The served kernels compile for a TPU v5e, at the served widths.

Nothing here runs on a chip: each test hands the TPU compiler a program
for a *described* ``v5e:2x2`` topology (shapes only, no arrays) and checks
that Mosaic accepts the Pallas kernel and that the kernel is really in the
compiled program (``tpu_custom_call``).  Interpret mode and the CPU oracle
cannot show this — three of the served kernel variants passed every CPU
parity test while Mosaic refused them.

Widths are the served ones: T = D = 16 slots and detections, 2,048 lanes
per chip in 128-lane kernel blocks, 4-frame chunks for the chunk kernel.
The topology is described inside a fixture, never at import: only one
process may load the TPU library, and every xdist worker imports this file.
"""
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, SingleDeviceSharding

from repro.core import SortConfig, SortEngine, hungarian
from repro.core import cost as cost_mod
from repro.kernels import chunk, frame, ops, ref
from repro.sharding import lanes as lanes_mod
from repro.sharding.specs import lane_dim_spec

T = D = 16
LANES = 2048
BLOCK_S = 128
FRAMES = 4
EMBED = 8
CLASSES = 3
COLLECTIVES = ("all-reduce", "all-gather", "all-to-all",
               "collective-permute", "reduce-scatter")


@pytest.fixture(scope="module")
def topo():
    """A described v5e:2x2 host, with the persistent compile cache off:
    a compile for a described chip cannot be read back here, so a cached
    entry would only warn on the next run."""
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    was_on = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        yield topologies.get_topology_desc(platform="tpu",
                                           topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    finally:
        jax.config.update("jax_enable_compilation_cache", was_on)
        compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _frame_case(sd, variant):
    f32, i32 = jnp.float32, jnp.int32
    args = (sd((7, T, LANES), f32), sd((49, T, LANES), f32),
            sd((D, 4, LANES), f32), sd((D, LANES), f32),
            sd((T, LANES), f32), sd((1, LANES), f32))
    kw = {}
    if variant == "trk_to_det":
        kw["trk_to_det"] = sd((T, LANES), i32)
    if variant == "multiclass":
        kw.update(det_class=sd((D, LANES), i32), trk_cls=sd((T, LANES), i32),
                  det_embed=sd((D, EMBED, LANES), f32),
                  trk_embed=sd((EMBED, T, LANES), f32),
                  cost=cost_mod.iou_embed(embed_dim=EMBED),
                  num_classes=CLASSES)
    return frame.fused_frame, args, kw


def _chunk_case(sd, variant):
    f32, i32 = jnp.float32, jnp.int32
    e = EMBED if variant == "multiclass" else 0
    state = ref.ChunkState(sd((7, T, LANES), f32), sd((49, T, LANES), f32),
                           *[sd((T, LANES), i32)] * 7,
                           sd((1, LANES), i32), sd((1, LANES), i32),
                           sd((e, T, LANES), f32))
    args = (state, sd((FRAMES, D, 4, LANES), f32),
            sd((FRAMES, D, LANES), f32), sd((FRAMES, 1, LANES), f32),
            sd((FRAMES, 1, LANES), i32))
    kw = {"assoc": "greedy"}
    if variant in ("trk_to_det", "multiclass"):
        kw.update(assoc="hungarian", trk_to_det=sd((FRAMES, T, LANES), i32))
    if variant == "multiclass":
        kw.update(det_class=sd((FRAMES, D, LANES), i32),
                  det_embed=sd((FRAMES, D, EMBED, LANES), f32),
                  cost=cost_mod.iou_embed(embed_dim=EMBED),
                  num_classes=CLASSES)
    return chunk.fused_chunk, args, kw


@pytest.mark.parametrize("kernel,variant", [
    ("frame", "greedy"), ("frame", "trk_to_det"), ("frame", "multiclass"),
    ("chunk", "greedy"), ("chunk", "trk_to_det"), ("chunk", "multiclass"),
])
def test_served_kernel_compiles_for_v5e(one_chip, kernel, variant):
    def sd(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    build = _frame_case if kernel == "frame" else _chunk_case
    fn, args, kw = build(sd, variant)
    arrays = {k: v for k, v in kw.items()
              if isinstance(v, jax.ShapeDtypeStruct)}
    static = {k: v for k, v in kw.items() if k not in arrays}
    compiled = jax.jit(
        lambda *a, **k: fn(*a, block_s=BLOCK_S, **static, **k)
    ).lower(*args, **arrays).compile()
    assert "tpu_custom_call" in compiled.as_text()
    mem = compiled.memory_analysis()
    state_bytes = (7 + 49) * T * LANES * 4
    assert mem.argument_size_in_bytes >= state_bytes
    assert mem.output_size_in_bytes >= state_bytes


def test_chunk_kernel_op_is_named_fused_chunk_for_v5e(one_chip):
    """The chunk kernel's custom call carries the name ``fused_chunk``
    that the kernel passes to ``pallas_call``: profiles of the served
    path tell the kernel's device time from the rest of the chunk
    program by this op name."""
    def sd(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    fn, args, kw = _chunk_case(sd, "greedy")
    hlo = jax.jit(lambda *a: fn(*a, block_s=BLOCK_S, **kw)
                  ).lower(*args).compile().as_text()
    calls = [line.split(" = ", 1)[0].split()[-1]
             for line in hlo.splitlines() if "tpu_custom_call" in line
             and " = " in line]
    assert calls and all(re.fullmatch(r"%fused_chunk(\.\d+)?", c)
                         for c in calls), calls


def test_lane_hungarian_solver_indexes_by_select_for_v5e(one_chip):
    """The JV solver behind every Hungarian engine, vmapped over 2,048
    lanes: no gather or scatter in its compiled loops, which the TPU would
    run one lane index at a time on every Dijkstra and augment step."""
    def sd(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    compiled = jax.jit(
        lambda c, r, k: hungarian.solve_masked_lane(c, r, k, max(T, D))
    ).lower(sd((D, T, LANES), jnp.float32), sd((D, LANES), jnp.bool_),
            sd((T, LANES), jnp.bool_)).compile()
    hlo = compiled.as_text()
    assert " while(" in hlo
    assert re.findall(r" (gather|scatter)\(", hlo) == []


def test_sharded_service_chunk_compiles_for_four_chips(topo, monkeypatch):
    """The SERVICE engine's chunk program over a 4-chip ("lanes",) mesh,
    as ``StreamScheduler(mesh=...)`` builds it: the fused frame kernel is
    in it, and no collective is, since lanes never talk to each other."""
    monkeypatch.setattr(ops, "_on_tpu", lambda: True)  # kernels, not oracle
    mesh = Mesh(np.asarray(topo.devices), (lanes_mod.LANE_AXIS,))
    eng = SortEngine(SortConfig(max_trackers=T, max_detections=D,
                                use_kernels=True))   # configs' SERVICE
    eng._block_s = BLOCK_S              # what the engine picks on a TPU
    lanes = len(topo.devices) * LANES
    sharding = lanes_mod.LaneSharding(eng, mesh, lanes)
    abstract = jax.eval_shape(sharding.init)
    state = jax.tree.map(
        lambda a, spec: jax.ShapeDtypeStruct(
            a.shape, a.dtype, sharding=NamedSharding(mesh, spec)),
        abstract, lanes_mod.state_pspecs(abstract))

    def operand(shape, dtype):
        spec = lane_dim_spec(len(shape), 1)
        return jax.ShapeDtypeStruct(shape, dtype,
                                    sharding=NamedSharding(mesh, spec))

    chunk_fn = sharding.shard_chunk(eng.run_chunk_ragged)
    compiled = jax.jit(chunk_fn).lower(
        state, operand((FRAMES, lanes, D, 4), jnp.float32),
        operand((FRAMES, lanes, D), jnp.bool_),
        operand((FRAMES, lanes), jnp.bool_),
        operand((FRAMES, lanes), jnp.bool_)).compile()
    hlo = compiled.as_text()
    assert "tpu_custom_call" in hlo
    assert [c for c in COLLECTIVES if c in hlo] == []


def test_mesh_chunk_program_returns_the_state_as_placed(topo, monkeypatch):
    """``mot15-sort``'s chunk program (chunk kernel, Hungarian pre-pass)
    over a 4-chip ("lanes",) mesh, with the state placed as
    ``LaneSharding`` places it: the chip's compiler returns every state
    leaf with the sharding it went in with, the zero-size ``embed`` block
    included, so the next chunk runs the same program and nothing
    compiles again; the kernel is in it and no collective is."""
    monkeypatch.setattr(ops, "_on_tpu", lambda: True)
    mesh = Mesh(np.asarray(topo.devices), (lanes_mod.LANE_AXIS,))
    eng = SortEngine(SortConfig(max_trackers=T, max_detections=D,
                                use_kernels=True, chunk_kernel=True,
                                assoc="hungarian"))
    eng._block_s = BLOCK_S
    lanes = len(topo.devices) * LANES
    sharding = lanes_mod.LaneSharding(eng, mesh, lanes)
    abstract = jax.eval_shape(sharding.init)
    placed = lanes_mod._placement(abstract, lanes_mod.state_pspecs(abstract),
                                  mesh)
    state = jax.tree.map(lambda a, s: jax.ShapeDtypeStruct(
        a.shape, a.dtype, sharding=s), abstract, placed)
    assert state.embed.shape[0] == 0

    def operand(shape, dtype):
        spec = lane_dim_spec(len(shape), 1)
        return jax.ShapeDtypeStruct(shape, dtype,
                                    sharding=NamedSharding(mesh, spec))

    compiled = jax.jit(sharding.shard_chunk(eng.run_chunk_ragged)).lower(
        state, operand((FRAMES, lanes, D, 4), jnp.float32),
        operand((FRAMES, lanes, D), jnp.bool_),
        operand((FRAMES, lanes), jnp.bool_),
        operand((FRAMES, lanes), jnp.bool_)).compile()
    state_in = jax.tree.leaves(compiled.input_shardings[0][0])
    state_out = jax.tree.leaves(compiled.output_shardings[0])
    assert len(state_in) == len(state_out) == len(jax.tree.leaves(placed))
    for leaf, a, b, want in zip(jax.tree.leaves(abstract), state_in,
                                state_out, jax.tree.leaves(placed)):
        assert a.is_equivalent_to(want, leaf.ndim), leaf.shape
        assert b.is_equivalent_to(want, leaf.ndim), leaf.shape
    hlo = compiled.as_text()
    assert "tpu_custom_call" in hlo
    assert [c for c in COLLECTIVES if c in hlo] == []
