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

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels import ps_update, replay_ring
from repro.optim import UpdateSpec

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

