"""The main path's Pallas kernels compile for a TPU v5e at real widths.

Every other test runs the kernels in interpret mode, which accepts
constructs the chip's compiler (Mosaic) refuses.  Here each kernel is
lowered and compiled for one chip of a described ``v5e:2x2`` topology —
no chip attached, nothing runs — and the compiled program must hold the
kernel (``tpu_custom_call``).  D = 2^20 is a real flat-model width.

The topology is described inside a module-scoped fixture, never while a
module is imported: only one process at a time may load the TPU library,
and every test worker imports every test file.
"""

import functools
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, SingleDeviceSharding
from jax.sharding import PartitionSpec as P

from repro.core import engine
from repro.core.trace import PlacementPlan
from repro.kernels import ps_update, replay_ring
from repro.optim import UpdateSpec, flatten

D = 1 << 20          # flat parameter width
K = 4                # ring depth
C = 8                # gradients per update


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("TPU_LOG_DIR", "disabled")
        try:
            desc = topologies.get_topology_desc(platform="tpu",
                                                topology_name="v5e:2x2")
        except Exception as e:           # no TPU compiler in this install
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
        # a compile for a described chip cannot be read back from the
        # persistent cache without that chip: keep it out of the cache
        enabled = jax.config.jax_enable_compilation_cache
        jax.config.update("jax_enable_compilation_cache", False)
        compilation_cache.reset_cache()
        try:
            yield desc
        finally:
            jax.config.update("jax_enable_compilation_cache", enabled)


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _sds(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _assert_kernel(fn, *args):
    text = jax.jit(fn).lower(*args).compile().as_text()
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("ring_dtype", [jnp.float32, jnp.bfloat16],
                         ids=["fp32", "bf16"])
@pytest.mark.parametrize("mode", ["combine", "sequential"])
@pytest.mark.parametrize("optimizer", ["sgd", "momentum"])
def test_ring_apply_compiles(one_chip, optimizer, mode, ring_dtype):
    spec = UpdateSpec(optimizer=optimizer)
    f32 = functools.partial(_sds, dtype=jnp.float32, sharding=one_chip)
    state = None if optimizer == "sgd" else f32((D,))
    residue = f32((D,)) if ring_dtype == jnp.bfloat16 else None
    fn = functools.partial(replay_ring.ring_apply, spec=spec, mode=mode,
                           interpret=False)
    _assert_kernel(fn, _sds((K, D), ring_dtype, one_chip), state, residue,
                   f32((C, D)), f32((C,)), f32((C,)),
                   _sds((2,), jnp.int32, one_chip))


@pytest.mark.parametrize("ring_dtype", [jnp.float32, jnp.bfloat16],
                         ids=["fp32", "bf16"])
def test_ring_apply_whatif_compiles(one_chip, ring_dtype):
    spec = UpdateSpec(optimizer="momentum")
    f32 = functools.partial(_sds, dtype=jnp.float32, sharding=one_chip)
    residue = f32((D,)) if ring_dtype == jnp.bfloat16 else None
    fn = functools.partial(replay_ring.ring_apply_whatif, spec=spec,
                           interpret=False)
    _assert_kernel(fn, _sds((K, D), ring_dtype, one_chip), f32((D,)),
                   residue, f32((D,)), f32((D,)), f32((C,)), f32((C,)),
                   _sds((2 + C,), jnp.int32, one_chip))


@pytest.mark.parametrize("optimizer", ["sgd", "momentum", "adagrad"])
def test_ps_apply_combine_compiles(one_chip, optimizer):
    spec = UpdateSpec(optimizer=optimizer)
    f32 = functools.partial(_sds, dtype=jnp.float32, sharding=one_chip)
    state = None if optimizer == "sgd" else f32((D,))
    fn = functools.partial(ps_update.ps_apply, spec=spec, mode="combine",
                           interpret=False)
    _assert_kernel(fn, f32((D,)), state, f32((C, D)), f32((C,)), f32((C,)))


def _while_bodies(text):
    """The text of each while-loop body computation of an HLO module."""
    bodies = set(re.findall(r"body=%?([\w.\-]+)", text))
    return [c for c in re.split(r"\n(?=\S)", text)
            if c.split(" ", 1)[0].lstrip("%") in bodies]


def _assert_ring_in_place(compiled, ring_shape, row_bytes):
    """The compiled replay scan holds the what-if kernel, its loop makes
    no copy of the whole ring, and it needs less scratch than one row."""
    text = compiled.as_text()
    assert re.search(r'%replay_ring_whatif[.\d]* = .*custom_call_target='
                     r'"tpu_custom_call"', text)
    bodies = _while_bodies(text)
    assert bodies
    ring = re.escape("bf16[" + ",".join(map(str, ring_shape)) + "]")
    for body in bodies:
        for line in body.splitlines():
            assert not re.search(r"%copy[\w.\-]* = " + ring, line), line
    assert compiled.memory_analysis().temp_size_in_bytes < row_bytes


@pytest.mark.parametrize("K,c", [(6, 1), (3, 30)], ids=["K6-c1", "K3-c30"])
def test_whatif_scan_updates_ring_in_place(topo, one_chip, monkeypatch, K, c):
    """The what-if replay scan, compiled whole at D = 2^24: the kernel
    updates the ring the loop carries in place.  The carry keeps the
    kernel's (rows, 128) tiles and the kernel takes the ring as one
    aliased operand, so no copy of the (K, rows, 128) ring is made per
    event — on one chip and in the sharded (SPMD) body on four."""
    # off the chip the engine picks interpret mode; compile the chip's path
    monkeypatch.setattr(replay_ring, "default_interpret", lambda: False)
    D, steps = 1 << 24, 16
    spec = UpdateSpec(optimizer="momentum")
    layout = flatten.layout_of({"w": jax.ShapeDtypeStruct((D,),
                                                          jnp.float32)})
    W = replay_ring.padded_width(D)
    lanes = (W // 128, 128)
    # uncached: a scan traced for the chip stays out of the
    # cache the CPU tests use
    fn = engine._make_scan_fn.__wrapped__(
        None, spec, "combine", c, K, layout, ring_impl="pallas",
        ring_dtype="bf16", whatif=True)
    f32 = functools.partial(_sds, dtype=jnp.float32, sharding=one_chip)
    i32 = functools.partial(_sds, dtype=jnp.int32, sharding=one_chip)
    carry = (_sds((K,) + lanes, jnp.bfloat16, one_chip), f32(lanes),
             f32(lanes))
    xs = {"ts": i32((steps, c)), "prev": i32((steps,)),
          "slot": i32((steps,)), "lrs": f32((steps, c))}
    _assert_ring_in_place(fn.lower(carry, xs, (f32(lanes), f32(lanes)))
                          .compile(), (K,) + lanes, 2 * W)

    # the SPMD body: one PS shard per chip of the described 2x2 host
    S = len(topo.devices)
    mesh = Mesh(np.array(topo.devices).reshape(S, 1), ("ps", "learner"))
    monkeypatch.setattr(engine.mesh_lib, "make_sim_mesh",
                        lambda ps, learners: mesh)
    Wl = engine._spmd_local_width(D, S, "pallas")
    lanes = (Wl // 128, 128)
    keys = ("lrs", "prev", "slot", "ts")
    fn = engine._make_spmd_scan_fn.__wrapped__(
        None, spec, "combine", c, K, layout,
        PlacementPlan(shards=S, learners=1, c=c), keys, ring_impl="pallas",
        ring_dtype="bf16", whatif=True)
    ps, rep = NamedSharding(mesh, P("ps")), NamedSharding(mesh, P())
    carry = (_sds((S, K) + lanes, jnp.bfloat16, ps),
             _sds((S,) + lanes, jnp.float32, ps),
             _sds((S,) + lanes, jnp.float32, ps))
    xs = {"ts": _sds((steps, c, S), jnp.int32,
                     NamedSharding(mesh, P(None, None, "ps"))),
          "prev": _sds((steps,), jnp.int32, rep),
          "slot": _sds((steps,), jnp.int32, rep),
          "lrs": _sds((steps, c), jnp.float32, rep)}
    aux = tuple(_sds((S,) + lanes, jnp.float32, ps) for _ in range(2))
    _assert_ring_in_place(fn.lower(carry, xs, aux).compile(),
                          (1, K) + lanes, 2 * Wl)
