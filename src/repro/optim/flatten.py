"""Pytree ⇄ single flat fp32 buffer (the Pallas backend's layout).

The fused ``ps_update`` kernel wants ONE contiguous (D,) parameter vector so
the whole model updates in a single ``pallas_call`` — one grid, one HBM pass
— instead of a Python loop of per-leaf launches.  These helpers concatenate
every leaf (ravelled, cast to fp32) and split/reshape/cast back afterwards.
"""

from __future__ import annotations

import dataclasses
from typing import List, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np


@dataclasses.dataclass(frozen=True)
class TreeLayout:
    """Static description of a flattened pytree (shapes, dtypes, offsets)."""

    treedef: object
    shapes: Tuple[Tuple[int, ...], ...]
    dtypes: Tuple[object, ...]
    sizes: Tuple[int, ...]

    @property
    def total(self) -> int:
        return int(sum(self.sizes))


def layout_of(tree) -> TreeLayout:
    leaves, treedef = jax.tree_util.tree_flatten(tree)
    return TreeLayout(
        treedef=treedef,
        shapes=tuple(tuple(l.shape) for l in leaves),
        dtypes=tuple(l.dtype for l in leaves),
        sizes=tuple(int(np.prod(l.shape)) if l.shape else 1 for l in leaves))


def tree_to_flat(tree) -> jax.Array:
    """Concatenate all leaves into one fp32 (D,) vector."""
    leaves = jax.tree_util.tree_leaves(tree)
    return jnp.concatenate(
        [l.reshape(-1).astype(jnp.float32) for l in leaves])


def stack_grads_flat(grads: Sequence) -> jax.Array:
    """c gradient pytrees → one (c, D) fp32 matrix."""
    return jnp.stack([tree_to_flat(g) for g in grads])


def batched_tree_to_flat(tree) -> jax.Array:
    """Pytree whose leaves share a leading batch axis → (B, D) fp32 (the
    vmapped-gradient counterpart of :func:`stack_grads_flat`)."""
    leaves = jax.tree_util.tree_leaves(tree)
    b = leaves[0].shape[0]
    return jnp.concatenate(
        [l.reshape(b, -1).astype(jnp.float32) for l in leaves], axis=1)


def batched_flat_to_tree(flat: jax.Array, layout: TreeLayout):
    """(B, D) matrix → tree with a leading (B,) axis on every leaf — the
    batched inverse used on ring-buffer gathers (one slice/reshape per leaf
    instead of per (row, leaf))."""
    b = flat.shape[0]
    out: List = []
    off = 0
    for shape, dtype, size in zip(layout.shapes, layout.dtypes, layout.sizes):
        out.append(flat[:, off:off + size].reshape((b,) + shape)
                   .astype(dtype))
        off += size
    return jax.tree_util.tree_unflatten(layout.treedef, out)


def pad_flat(flat: jax.Array, width: int) -> jax.Array:
    """Zero-pad the last axis of a flat buffer out to ``width`` (a kernel
    tile multiple or S·Dp shard width).  Trailing zeros are inert through
    sgd/momentum/adagrad events — padding is pure layout, and slicing
    ``[..., :D]`` is its exact inverse."""
    d = flat.shape[-1]
    if width == d:
        return flat
    return jnp.pad(flat, [(0, 0)] * (flat.ndim - 1) + [(0, width - d)])


def shard_pack(flat: jax.Array, shards: int, width: int) -> jax.Array:
    """(D,) flat buffer → (S, Dp) per-shard rows, zero-padding the last
    shard to the equal width Dp = ⌈D/S⌉ (core/topology.py layout).  Zeros
    are inert through sgd/momentum/adagrad events, so packing is pure
    layout: flattening the rows and slicing ``[..., :D]`` inverts it."""
    d = flat.shape[-1]
    return jnp.pad(flat, [(0, 0)] * (flat.ndim - 1)
                   + [(0, shards * width - d)]).reshape(
        flat.shape[:-1] + (shards, width))


def flat_to_tree(flat: jax.Array, layout: TreeLayout):
    """Split a (D,) vector back into the original tree (leaf dtypes restored)."""
    out: List = []
    off = 0
    for shape, dtype, size in zip(layout.shapes, layout.dtypes, layout.sizes):
        out.append(flat[off:off + size].reshape(shape).astype(dtype))
        off += size
    return jax.tree_util.tree_unflatten(layout.treedef, out)
