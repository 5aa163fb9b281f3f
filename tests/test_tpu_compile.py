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
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core import engine
from repro.kernels import ps_update, replay_ring
from repro.optim import UpdateSpec, flatten

D = 1 << 20          # flat parameter width
K = 4                # ring depth
C = 8                # gradients per update


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("TPU_LOG_DIR", "disabled")
        try:
            topo = topologies.get_topology_desc(platform="tpu",
                                                topology_name="v5e:2x2")
        except Exception as e:           # no TPU compiler in this install
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
        # a compile for a described chip cannot be read back from the
        # persistent cache without that chip: keep it out of the cache
        enabled = jax.config.jax_enable_compilation_cache
        jax.config.update("jax_enable_compilation_cache", False)
        compilation_cache.reset_cache()
        try:
            yield SingleDeviceSharding(topo.devices[0])
        finally:
            jax.config.update("jax_enable_compilation_cache", enabled)


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


def test_whatif_scan_names_its_kernel_and_relayout(one_chip, monkeypatch):
    """The what-if replay scan, compiled whole: its kernel instruction is
    named after the ``pallas_call`` and the ring's reshapes to the
    kernel's tiling and back carry their scopes in ``op_name``, so a
    profile can find them."""
    # off the chip the engine picks interpret mode; compile the chip's path
    monkeypatch.setattr(replay_ring, "default_interpret", lambda: False)
    steps = 16
    W = replay_ring.padded_width(D)
    layout = flatten.layout_of({"w": jax.ShapeDtypeStruct((D,),
                                                          jnp.float32)})
    # uncached: a scan traced for the chip stays out of the
    # cache the CPU tests use
    fn = engine._make_scan_fn.__wrapped__(
        None, UpdateSpec(optimizer="momentum"), "combine", C, K, layout,
        ring_impl="pallas", ring_dtype="bf16", whatif=True)
    f32 = functools.partial(_sds, dtype=jnp.float32, sharding=one_chip)
    i32 = functools.partial(_sds, dtype=jnp.int32, sharding=one_chip)
    carry = (_sds((K, W), jnp.bfloat16, one_chip), f32((W,)), f32((W,)))
    xs = {"ts": i32((steps, C)), "prev": i32((steps,)),
          "slot": i32((steps,)), "lrs": f32((steps, C))}
    text = fn.lower(carry, xs, (f32((W,)), f32((W,)))).compile().as_text()
    assert re.search(r'%replay_ring_whatif[.\d]* = .*custom_call_target='
                     r'"tpu_custom_call"', text)
    rows = W // 128
    for shape, scope in ((f"bf16[{K},{rows},128]", "replay_ring.to_tiles"),
                         (f"bf16[{K},{W}]", "replay_ring.from_tiles")):
        assert re.search(re.escape(f"= {shape}") + r"\{.*op_name=\"[^\"]*"
                         + re.escape(f"{engine.SCAN_SCOPE}/") + r"[^\"]*"
                         + re.escape(f"/{scope}/"), text), scope
