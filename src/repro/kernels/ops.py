"""jit'd wrappers exposing the Pallas kernels through the same API as
repro.core.intree's arena entry points, so the unified executor stack
(core.executor) can swap the kernels in freely (executor="pallas"); a
single tree is the G=1 arena.

The arena entry points (`select_arena` / `backup_arena`) drive the
[G]-grid kernels: one launch covers every tree slot, inactive slots no-op
inside the kernel (no where_trees post-select needed), and the expansion
assignment post-pass runs vmapped on the jit path exactly as the jax
arena executor does.

The platform the program is lowered for picks how the kernels run: on
the CPU backend (arrays on CPU, e.g. the test suite under
JAX_PLATFORMS=cpu) they run in the Pallas interpreter, on TPU they are
compiled by Mosaic.  The choice is made at lowering time by
``lax.platform_dependent``, so it follows the device the arrays are
committed to, inside or outside an enclosing jit.  Any other platform
fails to lower — there is no silent fallback to the interpreter or to
the jnp path.
"""

from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp

from repro.core import intree
from repro.core.tree import TreeConfig, UCTree
from repro.kernels import uct_backup, uct_select


def _per_platform(kernel, cfg: TreeConfig, *args, **static):
    """Run `kernel(cfg, *args, **static)` interpreted when lowered for CPU
    and compiled when lowered for TPU; lowering for any other platform is
    an error."""
    return jax.lax.platform_dependent(
        *args,
        cpu=functools.partial(kernel, cfg, interpret=True, **static),
        tpu=functools.partial(kernel, cfg, interpret=False, **static))


@functools.partial(jax.jit, static_argnames=("cfg", "p"))
def select_arena(cfg: TreeConfig, arena: UCTree, active, p: int):
    """Arena Selection; mirrors intree.select_arena.  Returns
    (arena', sel[G, ...]).  The kernel freezes inactive slots itself, so
    the returned arena needs no mask post-select; their sel rows are dead
    data the host ignores (same contract as the jax arena path)."""
    evl, no, pn, pa, depths, leaves = _per_platform(
        uct_select.select_arena, cfg, arena, jnp.asarray(active, jnp.int32),
        p=p)
    arena = dataclasses.replace(arena, edge_VL=evl, node_O=no)
    _, sel = jax.vmap(
        lambda t, n, a, d, l: intree._assign_expansions(cfg, t, n, a, d, l, p)
    )(arena, pn, pa, depths, leaves)
    return arena, sel


@functools.partial(jax.jit, static_argnames=("cfg", "alternating_signs"))
def backup_arena(cfg: TreeConfig, arena: UCTree, active, sel, sim_nodes,
                 values_fx, alternating_signs: bool = False):
    """Arena BackUp; mirrors intree.backup_arena (fault-free path)."""
    p = sel.leaves.shape[1]
    en, ew, evl, nn, no = _per_platform(
        uct_backup.backup_arena, cfg, arena, jnp.asarray(active, jnp.int32),
        sel.path_nodes, sel.path_actions,
        jnp.asarray(sel.depths), jnp.asarray(sel.leaves),
        jnp.asarray(sel.expand_action), jnp.asarray(sim_nodes, jnp.int32),
        jnp.asarray(values_fx, jnp.int32),
        p=p, alternating=alternating_signs)
    return dataclasses.replace(
        arena, edge_N=en, edge_W=ew, edge_VL=evl, node_N=nn, node_O=no)
