"""Shared helpers for the UCT Pallas kernels.

Memory layout (TPU adaptation of the paper's SRAM banking, §IV-B):

The paper stores the UCT as a compact adjacency list in per-level SRAM
banks sized for single-cycle access.  On TPU the analogue is VMEM
residency with VPU-aligned rows: every ``[X, Fp]`` edge-statistic array is
packed into ``[X*Fp/128, 128]`` int32 so that

  * a node's Fp-edge block lives in ONE 128-lane VMEM row (Fp is a power
    of two <= 128, so blocks never straddle rows) — one vector load plays
    the role of the paper's one-cycle bank read;
  * the selection comparator is a masked 128-lane argmax — the VPU-native
    replacement of the paper's CLUT comparator tree (§IV-D), which has no
    TPU analogue;
  * updates are full-row read-modify-writes (no sub-lane dynamic stores,
    which Mosaic lowers poorly).

Node-indexed ``[X]`` arrays are packed into ``[ceil(X/128), 128]`` rows and
accessed with the same row RMW discipline.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

LANES = 128


def padded_x(x: int, fp: int) -> int:
    """Smallest X' >= x with X'*Fp a multiple of 128."""
    step = max(1, LANES // fp)
    return ((x + step - 1) // step) * step


def pack_edges(arr, fp: int):
    """[X, Fp] -> [Xp*Fp/128, 128] (row-aligned node blocks)."""
    x = arr.shape[0]
    xp_ = padded_x(x, fp)
    if xp_ != x:
        arr = jnp.concatenate(
            [arr, jnp.zeros((xp_ - x, fp), arr.dtype)], axis=0)
    return arr.reshape(xp_ * fp // LANES, LANES)


def unpack_edges(packed, x: int, fp: int):
    return packed.reshape(-1, fp)[:x]


def pack_nodes(arr):
    """[X] -> [ceil(X/128), 128]."""
    x = arr.shape[0]
    xp_ = ((x + LANES - 1) // LANES) * LANES
    if xp_ != x:
        arr = jnp.concatenate([arr, jnp.zeros((xp_ - x,), arr.dtype)])
    return arr.reshape(xp_ // LANES, LANES)


def unpack_nodes(packed, x: int):
    return packed.reshape(-1)[:x]


# ---- arena packing: a leading [G] slot axis over the same row layout ----
#
# The arena kernels block-map one slot per grid program, so the packed
# layout just gains a leading G axis: [G, X, Fp] -> [G, X*Fp/128, 128].
# vmap of the single-tree helpers keeps the two layouts one definition.

def pack_edges_arena(arr, fp: int):
    """[G, X, Fp] -> [G, Xp*Fp/128, 128]."""
    return jax.vmap(lambda a: pack_edges(a, fp))(arr)


def unpack_edges_arena(packed, x: int, fp: int):
    return jax.vmap(lambda a: unpack_edges(a, x, fp))(packed)


def pack_nodes_arena(arr):
    """[G, X] -> [G, ceil(X/128), 128]."""
    return jax.vmap(pack_nodes)(arr)


def unpack_nodes_arena(packed, x: int):
    return jax.vmap(lambda a: unpack_nodes(a, x))(packed)


# ---- in-kernel access helpers (all row-granular) -------------------------

def canonical_index(i):
    """dynamic_slice / dslice starts must all share one dtype, and literal
    starts (the 0 hidden in a full slice) canonicalize to jax's index
    dtype — i64 under JAX_ENABLE_X64, i32 otherwise.  Traced starts must
    follow, or mixed index tuples fail to trace under the x64 CI leg."""
    dt = jnp.int64 if jax.config.jax_enable_x64 else jnp.int32
    return jnp.asarray(i, dt)


def lane_iota():
    """[1, 128] lane indices (2-D: 1-D iota does not lower on TPU)."""
    return jax.lax.broadcasted_iota(jnp.int32, (1, LANES), 1)


def load_row(ref, row):
    """One row of a 2-D ref as [1, n] (a 128-lane row for packed arrays)."""
    return ref[pl.ds(canonical_index(row), 1), :]


def store_row(ref, row, val):
    ref[pl.ds(canonical_index(row), 1), :] = val


def extract_lane(vec, lane):
    """vec[0, lane] for a [1, n] vector and a traced lane index.

    A masked sum over the lanes, not a dynamic slice: Mosaic has no
    lowering for ``dynamic_slice`` on a vector.  Exactly one lane is
    kept and the rest are zero, so the sum is the lane's value bit for
    bit (finite values)."""
    hit = jax.lax.broadcasted_iota(jnp.int32, vec.shape, 1) == lane
    return jnp.sum(jnp.where(hit, vec, jnp.zeros_like(vec)), dtype=vec.dtype)


def sload(ref, idx):
    """Scalar load from a packed node array."""
    return extract_lane(load_row(ref, idx // LANES), idx % LANES)


def sadd(ref, idx, inc):
    """Scalar add via full-row RMW (vectorized select, no sub-lane store)."""
    row_i = idx // LANES
    row = load_row(ref, row_i)
    upd = jnp.where(lane_iota() == (idx % LANES), inc, 0).astype(row.dtype)
    store_row(ref, row_i, row + upd)
