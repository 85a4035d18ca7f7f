"""Shared helpers for the UCT Pallas kernels.

Memory layout (TPU adaptation of the paper's SRAM banking, §IV-B):

The paper stores the UCT as a compact adjacency list in per-level SRAM
banks sized for single-cycle access.  On TPU the analogue is VMEM
residency with VPU-aligned rows: the arena stores every edge-statistic
array as ``[X*Fp/128, 128]`` int32 rows, rounded up to whole (8, 128)
tiles (core/tree.PackedTree), and the kernels block-map those rows into
VMEM as they are, so that

  * a node's Fp-edge block lives in ONE 128-lane VMEM row (Fp is a power
    of two <= 128, so blocks never straddle rows) — one vector load plays
    the role of the paper's one-cycle bank read;
  * the selection comparator is a masked 128-lane argmax — the VPU-native
    replacement of the paper's CLUT comparator tree (§IV-D), which has no
    TPU analogue;
  * updates are full-row read-modify-writes (no sub-lane dynamic stores,
    which Mosaic lowers poorly).

Node-indexed arrays and the ln table are stored as ``[ceil(n/128), 128]``
rows and accessed with the same row RMW discipline.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.core.tree import LANES  # noqa: F401  (the kernels' row width)

# Each grid program block-maps its slot's whole UCT into VMEM (ROADMAP S5
# would bound the copy by the live rows).  Mosaic's default scoped limit,
# 16 MiB, holds one Pong slot's blocks but not four double-buffered, nor
# one Gomoku slot's, once the arena reaches the kernels as it is stored in
# HBM; a TPU v5e has 128 MiB of VMEM.
VMEM_LIMIT_BYTES = 100 << 20


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
