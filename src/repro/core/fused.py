"""Fused K-superstep device dispatch — the serving loop without the
per-phase host round-trip.

The BSP superstep in repro.service.pool returns to Python between every
phase of every superstep (select → sel_to_host → insert → device_get →
host expand → finalize → backup); at small/medium G that dispatch
overhead, not kernel time, bounds throughput.  The paper's 35× in-tree
speedup comes from keeping tree state in SRAM and crossing the CPU/FPGA
boundary rarely — this module applies the same lesson to the XLA
dispatch boundary: ONE compiled ``lax.while_loop`` program runs

    select → insert → device expand (env twin) → device simulate →
    finalize → backup

for up to K supersteps, with the sim-state buffer device-resident for
the whole dispatch (fused rows cost zero H2D copies).  It escapes to the
host early only when

  * an expansion needs the env (``resolvable_device`` says no) — the
    loop exits **post-insert**, carrying the SelectionResult and the
    freshly assigned node ids so the host can complete that superstep
    through the ordinary ExpansionEngine path; everything the device
    already did (virtual loss, node_O, insert) equals the normal
    post-selection state, or
  * a move-commit boundary is hit (per-slot search budget exhausted,
    arena full, or a no-growth superstep) — the loop stops **after**
    the triggering superstep completes so the host can commit moves.

Bit-identity contract: supersteps are grouping-independent — every
phase inside the loop is the same jitted op the phase-by-phase path
calls, the env/sim device twins are bit-equal to their host twins (see
repro.envs.device), and escape points always coincide with the places
the K=1 path would have gone to host anyway.  tests/test_executor_matrix
enrolls fused runs against the sequential numpy oracle.

Requires ``not cfg.expand_all`` (prior-producing expansion keeps the
host path) and device twins on both env and sim backend (probes in
repro.envs.device).  The program is cached per
(cfg, variant, p, K, env, sim, alternating) — env/sim participate by
identity, so hold onto the same objects across dispatches.

Multi-device serving (core/sharded.py): the program itself is
placement-agnostic — jit dispatch follows the COMMITTED device of the
arena operand, so an executor whose trees were placed with
models.sharding.put_on_device runs its fused program on that device
with no code here changing.  The one cached program (per static key)
specializes per input sharding, which is how D shards share a compile
while each runs device-locally; ArenaPool.fused_dispatch drives one
call per shard.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Any, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import fixedpoint as fx
from repro.core import intree
from repro.core.tree import NULL, PackedTree, TreeConfig

# escape reasons surfaced to the pool/scheduler accounting
ESC_RAN_K = 0    # ran all K supersteps, no boundary hit
ESC_COMMIT = 1   # a slot hit a move-commit boundary (stops after that
                 # superstep completes; host runs _commit_moves as usual)
ESC_EXPAND = 2   # an expansion was unresolvable on device (exits
                 # post-insert; host completes that superstep)

ESCAPE_NAMES = {ESC_RAN_K: "ran_k", ESC_COMMIT: "commit",
                ESC_EXPAND: "expand"}


@dataclasses.dataclass
class FusedDispatch:
    """Host-side result of one fused dispatch (all arrays numpy)."""

    n: int                      # complete supersteps executed on device
    escape: str                 # "ran_k" | "commit" | "expand"
    size_pre: np.ndarray        # [Ge] arena size before the most recent
                                # insert (== size after superstep n)
    sizes: np.ndarray           # [Ge] arena size after the dispatch
    states: np.ndarray          # [Ge, X, *S] the device ST buffer
    sel_dev: Optional[Any]      # device SelectionResult (escape=="expand")
    sel_host: Optional[dict]    # its host transfer
    new_nodes: Optional[np.ndarray]  # [Ge, p, Fp] (escape=="expand")

    @property
    def nbytes(self) -> int:
        """Bytes read back from the device to build this result."""
        arrays = [self.size_pre, self.sizes, self.states]
        if self.new_nodes is not None:
            arrays += [self.new_nodes, *self.sel_host.values()]
        return sum(np.asarray(a).nbytes for a in arrays)


def _zero_sel(Ge: int, p: int, D: int) -> intree.SelectionResult:
    z = jnp.zeros((Ge, p), jnp.int32)
    zn = jnp.full((Ge, p, D), NULL, jnp.int32)
    return intree.SelectionResult(
        path_nodes=zn, path_actions=zn, depths=z, leaves=z,
        expand_action=jnp.full((Ge, p), NULL, jnp.int32),
        n_insert=z, insert_base=z)


@functools.partial(jax.jit, static_argnums=(0, 1, 2, 3, 4, 5, 6))
def _fused_program(cfg: TreeConfig, variant: str, p: int, K: int,
                   env, sim, alternating: bool,
                   arena: PackedTree, states, active, budget_left):
    """The compiled dispatch.  Static args make the cache key; arena,
    the ST buffer, the active mask [Ge] and per-slot remaining budgets
    [Ge] are traced."""
    if variant == "pallas":
        from repro.kernels import ops as kops  # lazy: core stays import-light

        select = lambda a: kops.select_arena(cfg, a, active, p)
        backup = lambda a, s, n, v: kops.backup_arena(
            cfg, a, active, s, n, v, alternating)
    else:
        select = lambda a: intree.select_arena(cfg, a, active, p, variant)
        backup = lambda a, s, n, v: intree.backup_arena(
            cfg, a, active, s, n, v, alternating)

    Ge = states.shape[0]
    state_tail = states.shape[2:]
    resolvable = getattr(env, "resolvable_device", None)

    def body(c):
        arena = c["arena"]
        size_pre = arena.size                       # [Ge] pre-insert sizes

        # -- Selection + Node Insertion (identical jitted phase ops) ----
        arena, sel = select(arena)
        arena, new_nodes = intree.insert_arena(cfg, arena, active, sel)

        # -- device expansion: resolve new nodes with the env twin ------
        leaves = sel.leaves                         # [Ge, p]
        leaf_states = jax.vmap(lambda st, lv: st[lv])(c["states"], leaves)
        ea = sel.expand_action
        expanding = (ea >= 0) & active[:, None]
        flat_states = leaf_states.reshape((Ge * p,) + state_tail)
        flat_a = jnp.maximum(ea, 0).reshape(-1)     # total fn: clamp masked
        if resolvable is None:
            esc_expand = jnp.asarray(False)
        else:
            ok = resolvable(flat_states, flat_a).reshape(Ge, p)
            esc_expand = jnp.any(expanding & ~ok)
        nxt, term = env.step_device(flat_states, flat_a)
        term = term.reshape(Ge, p)
        na = env.num_actions_device(nxt).astype(jnp.int32).reshape(Ge, p)
        nxt = nxt.reshape((Ge, p) + state_tail)
        nid = new_nodes[:, :, 0]                    # single-expand: lane 0
        wid = jnp.where(expanding, nid, cfg.X)      # out-of-range -> drop
        states2 = jax.vmap(
            lambda st, ids, rows: st.at[ids].set(rows, mode="drop")
        )(c["states"], wid, nxt)

        # -- Simulation on device (values only) -------------------------
        sim_nodes = jnp.where(expanding, nid, leaves)
        exp3 = expanding.reshape((Ge, p) + (1,) * len(state_tail))
        sim_states = jnp.where(exp3, nxt, leaf_states)
        vals = sim.evaluate_device(sim_states.reshape((Ge * p,) + state_tail))
        values_fx = fx.encode(vals, xp=jnp).reshape(Ge, p)

        # -- finalize + BackUp ------------------------------------------
        fin_nodes = jnp.where(expanding, nid, NULL)
        arena_fin = intree.finalize_arena(
            arena, fin_nodes, jnp.where(expanding, na, 0),
            jnp.where(expanding, term.astype(jnp.int32), 0),
            jnp.full((Ge, p), NULL, jnp.int32),
            jnp.zeros((Ge, p, cfg.Fp), jnp.int32))
        arena_done = backup(arena_fin, sel, sim_nodes, values_fx)

        # -- move-commit boundary (mirrors pool._commit_moves) ----------
        budget2 = c["budget_left"] - active.astype(jnp.int32)
        size_after = arena_done.size
        boundary = active & ((budget2 <= 0) | (size_after >= cfg.X)
                             | (size_after == size_pre))
        hit = jnp.any(boundary)

        done = dict(
            arena=arena_done, states=states2, n=c["n"] + 1,
            budget_left=budget2, size_pre=size_pre,
            stop=hit,
            esc=jnp.where(hit, jnp.int32(ESC_COMMIT), jnp.int32(ESC_RAN_K)),
            sel=c["sel"], new_nodes=c["new_nodes"])
        escaped = dict(
            arena=arena, states=c["states"], n=c["n"],
            budget_left=c["budget_left"], size_pre=size_pre,
            stop=jnp.asarray(True), esc=jnp.asarray(ESC_EXPAND, jnp.int32),
            sel=sel, new_nodes=new_nodes)
        return jax.tree.map(
            lambda e, d: jnp.where(esc_expand, e, d), escaped, done)

    c0 = dict(
        arena=arena, states=states, n=jnp.asarray(0, jnp.int32),
        budget_left=jnp.asarray(budget_left, jnp.int32),
        size_pre=arena.size, stop=jnp.asarray(False),
        esc=jnp.asarray(ESC_RAN_K, jnp.int32),
        sel=_zero_sel(Ge, p, cfg.D),
        new_nodes=jnp.full((Ge, p, cfg.Fp), NULL, jnp.int32))
    out = jax.lax.while_loop(
        lambda c: (~c["stop"]) & (c["n"] < K), body, c0)
    return (out["arena"], out["states"], out["n"], out["esc"],
            out["size_pre"], out["sel"], out["new_nodes"])


@dataclasses.dataclass
class PendingDispatch:
    """Device outputs of a queued fused program, NOT yet read to host.
    submit_supersteps returns one; collect_supersteps blocks on it and
    builds the FusedDispatch.  Everything here is a device array still in
    flight under JAX async dispatch — holding the handle costs nothing."""

    arena_size: Any      # [Ge] device sizes after the dispatch
    states_out: Any      # [Ge, X, *S] device ST buffer
    n: Any               # scalar: complete supersteps (int once waited)
    esc: Any             # scalar: escape code (int once waited)
    size_pre: Any        # [Ge] size before the most recent insert
    sel: Any             # device SelectionResult
    new_nodes: Any       # [Ge, p, Fp] device id block


def submit_supersteps(cfg: TreeConfig, variant: str, trees: PackedTree,
                      active, p: int, K: int, env, sim, states,
                      budget_left, alternating: bool):
    """Queue up to K fused supersteps WITHOUT any host read.  Returns
    (new_trees, PendingDispatch), which wait_supersteps blocks on and
    collect_supersteps reads back.  ``states`` is the [Ge, X, *S] ST
    image of the dispatched rows (uploaded once; new-node states come
    back in FusedDispatch.states — node ids are allocated contiguously,
    so the rows [size-at-dispatch-start, size_pre) are exactly the
    device-resolved expansions the host tables are missing).  The
    overlap mode stages one gang's dispatch here while another gang's
    host half runs."""
    arena, states_out, n, esc, size_pre, sel, new_nodes = _fused_program(
        cfg, variant, p, K, env, sim, bool(alternating),
        trees, jnp.asarray(states), jnp.asarray(active, bool),
        jnp.asarray(budget_left, jnp.int32))
    return arena, PendingDispatch(
        arena_size=arena.size, states_out=states_out, n=n, esc=esc,
        size_pre=size_pre, sel=sel, new_nodes=new_nodes)


def wait_supersteps(pend: PendingDispatch) -> None:
    """Block until a staged fused program has run: read its two escape
    scalars to the host, in place (collect_supersteps then reads the
    rest, with the device already done)."""
    pend.n, pend.esc = int(pend.n), int(pend.esc)


def collect_supersteps(pend: PendingDispatch) -> FusedDispatch:
    """Blocking half: fetch the escape scalars and host views of a
    staged fused dispatch and build the FusedDispatch."""
    n = int(pend.n)
    esc = int(pend.esc)
    expand = esc == ESC_EXPAND
    disp = FusedDispatch(
        n=n, escape=ESCAPE_NAMES[esc],
        size_pre=np.asarray(jax.device_get(pend.size_pre)),
        sizes=np.asarray(jax.device_get(pend.arena_size)),
        states=np.asarray(jax.device_get(pend.states_out)),
        sel_dev=pend.sel if expand else None,
        sel_host=None, new_nodes=None)
    if expand:
        from repro.core.executor import _sel_to_host

        disp.sel_host = _sel_to_host(pend.sel)
        disp.new_nodes = np.asarray(jax.device_get(pend.new_nodes))
    return disp
