"""Batched in-tree operations (the accelerator, paper §IV) in pure JAX.

This module is the jit'd TPU-native replacement of the paper's FPGA
in-tree-operation accelerator.  Three entry points mirror the accelerator's
three functions:

  select_batch   — Selection + virtual-loss apply for p workers
                   (paper: worker distributor + subtree pipelines);
  insert_batch   — Node Insertion (paper §IV-E);
  backup_batch   — BackUp from memoized paths (paper §IV-E memoization
                   buffer: Selection returns the traversed-edge refs so
                   BackUp never re-walks the tree).

Sequential-equivalence: the FPGA pipeline admits one worker per stage, so
worker k observes the virtual loss of workers < k — exactly the sequential
CPU program.  Here selection runs a `fori_loop` over workers (each a
masked D-step descent); every arithmetic step goes through the shared
fixed-point scoring spec, so outputs are bit-identical to
ref_sequential.py (tested).  Insertion and BackUp are *fully vectorized*:
their updates are integer scatter-adds, which commute exactly, so
vectorized == sequential — this is the TPU's win over the FPGA design,
which still serializes BackUp through pipeline stages.

A `relaxed=True` selection variant applies all virtual loss once per
superstep *after* all workers choose (single vectorized pass, no serial
chain).  This is a beyond-paper optimization: it trades the intra-superstep
worker-repulsion of WU-UCT for a ~p× shorter dependency chain; its effect
on search diversity is measured in benchmarks/bench_diversity.py.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Any

import jax
import jax.numpy as jnp

from repro.core import fixedpoint as fx
from repro.core import scoring
from repro.core.tree import (
    NULL, PackedTree, TreeConfig, edge_add, edge_get, edge_row, edge_set,
    node_add, node_get, node_set, where_trees,
)


@jax.tree_util.register_dataclass
@dataclasses.dataclass(frozen=True)
class SelectionResult:
    path_nodes: Any     # [p, D] i32, NULL-padded
    path_actions: Any   # [p, D] i32
    depths: Any         # [p] i32
    leaves: Any         # [p] i32
    expand_action: Any  # [p] i32: action, NULL, or -2 (expand-all claim)
    n_insert: Any       # [p] i32
    insert_base: Any    # [p] i32: first node id this worker will insert


def _scores_at(cfg: TreeConfig, tree: PackedTree, node, edge_VL, node_O):
    """Scores of the edges of `node` ([...] ids -> [..., Fp])."""
    node_N = node_get(tree.node_N, node)[..., None]
    node_O = node_get(node_O, node)[..., None]
    ns = scoring.log_index(cfg, node_N, node_O, xp=jnp)
    return scoring.edge_scores_fx(
        cfg,
        child=edge_row(tree, tree.child, node),
        edge_N=edge_row(tree, tree.edge_N, node),
        edge_W=edge_row(tree, tree.edge_W, node),
        edge_VL=edge_row(tree, edge_VL, node),
        edge_P=edge_row(tree, tree.edge_P, node),
        node_N=node_N,
        node_O=node_O,
        num_actions=node_get(tree.num_actions, node)[..., None],
        log_ns=node_get(tree.log_table, ns),
        xp=jnp,
    )


def _is_leaf_at(cfg: TreeConfig, tree: PackedTree, node, depth):
    return scoring.is_leaf(
        cfg,
        num_expanded=node_get(tree.num_expanded, node),
        num_actions=node_get(tree.num_actions, node),
        terminal=node_get(tree.terminal, node),
        depth=depth,
        xp=jnp,
    )


@functools.partial(jax.jit, static_argnums=(0, 2, 3))
def select_batch(cfg: TreeConfig, tree: PackedTree, p: int,
                 relaxed: bool = False):
    """Selection for p workers.  Returns (tree', SelectionResult)."""
    D = cfg.D
    i32 = jnp.int32

    def descend(j, carry):
        edge_VL, node_O, pn, pa, depths, leaves = carry
        if not relaxed:
            node_O = node_add(tree, node_O, tree.root, 1)

        def level(d, st):
            node, depth, edge_VL, node_O, pn, pa = st
            leaf = _is_leaf_at(cfg, tree, node, depth)
            active = (~leaf) & (d == depth)
            s = _scores_at(cfg, tree, node, edge_VL, node_O)
            a = scoring.argmax_first(s, xp=jnp)
            inc = jnp.where(active, i32(1), i32(0))
            if not relaxed:
                edge_VL = edge_add(tree, edge_VL, node, a, inc)
            nxt = edge_get(tree, tree.child, node, a)
            pn = pn.at[j, d].set(jnp.where(active, node, pn[j, d]))
            pa = pa.at[j, d].set(jnp.where(active, a, pa[j, d]))
            node = jnp.where(active, nxt, node)
            if not relaxed:
                node_O = node_add(tree, node_O, node, inc)
            depth = depth + inc
            return node, depth, edge_VL, node_O, pn, pa

        node, depth, edge_VL, node_O, pn, pa = jax.lax.fori_loop(
            0, D, level, (tree.root, i32(0), edge_VL, node_O, pn, pa)
        )
        depths = depths.at[j].set(depth)
        leaves = leaves.at[j].set(node)
        return edge_VL, node_O, pn, pa, depths, leaves

    pn = jnp.full((p, D), NULL, dtype=i32)
    pa = jnp.full((p, D), NULL, dtype=i32)
    depths = jnp.zeros(p, dtype=i32)
    leaves = jnp.zeros(p, dtype=i32)
    edge_VL, node_O = tree.edge_VL, tree.node_O
    edge_VL, node_O, pn, pa, depths, leaves = jax.lax.fori_loop(
        0, p, descend, (edge_VL, node_O, pn, pa, depths, leaves)
    )
    if relaxed:
        # Beyond-paper: one-shot VL/O application after all choices; scores
        # above read only the pre-superstep statistics (no serial chain).
        edge_VL, node_O = _apply_virtual_loss(tree, edge_VL, node_O, pn, pa,
                                              leaves)

    tree = dataclasses.replace(tree, edge_VL=edge_VL, node_O=node_O)
    return _assign_expansions(cfg, tree, pn, pa, depths, leaves, p)


def _apply_virtual_loss(tree: PackedTree, edge_VL, node_O, pn, pa, leaves):
    """Virtual loss and in-flight counts of whole paths, added at once."""
    idx_n = jnp.where(pn != NULL, pn, tree.X)
    edge_VL = edge_add(tree, edge_VL, idx_n, pa, 1)
    node_O = node_add(tree, node_O, idx_n, 1)
    return edge_VL, node_add(tree, node_O, leaves, 1)


def _segment_rank(keys, p):
    """r[j] = #{i < j : keys[i] == keys[j]} — stable within-group rank."""
    i32 = jnp.int32
    sidx = jnp.argsort(keys, stable=True)
    sk = keys[sidx]
    pos = jnp.arange(p, dtype=i32)
    new_run = jnp.concatenate([jnp.array([True]), sk[1:] != sk[:-1]])
    run_start = jax.lax.associative_scan(jnp.maximum, jnp.where(new_run, pos, i32(0)))
    r_sorted = pos - run_start
    return jnp.zeros(p, dtype=i32).at[sidx].set(r_sorted)


@functools.partial(jax.jit, static_argnums=(0, 2))
def select_batch_wavefront(cfg: TreeConfig, tree: PackedTree, p: int):
    """Beyond-paper selection: level-synchronous wavefront with rank-based
    repulsion.

    The faithful path serializes workers (chain length p*D) to reproduce
    the FPGA pipeline's virtual-loss ordering.  Here all p workers advance
    one level per step (chain length D).  Workers that meet at the same
    node are spread across that node's top-scoring edges by their stable
    within-group rank — a deterministic, vectorized surrogate for the
    repulsion virtual loss provides across a superstep.  Virtual loss / O
    counters are applied once at the end (cross-superstep semantics are
    preserved exactly; intra-superstep repulsion is rank-based instead of
    VL-based).  Diversity impact vs the faithful path is measured in
    benchmarks (bench_diversity).
    """
    D, X = cfg.D, tree.X
    i32 = jnp.int32
    w = jnp.arange(p, dtype=i32)

    def level(d, st):
        nodes, depth, pn, pa = st
        leaf = _is_leaf_at(cfg, tree, nodes, depth)
        active = (~leaf) & (depth == d)
        s = _scores_at(cfg, tree, nodes, tree.edge_VL, tree.node_O)  # [p, Fp]
        order = jnp.argsort(-s, axis=-1, stable=True)       # best-first, ties by lane
        n_valid = jnp.maximum(jnp.sum(s > fx.FX_NEG_INF, axis=-1), 1).astype(i32)
        rank = _segment_rank(jnp.where(active, nodes, X + w), p)
        a = jnp.take_along_axis(
            order, (rank % n_valid)[:, None], axis=-1)[:, 0].astype(i32)
        pn = pn.at[w, d].set(jnp.where(active, nodes, pn[:, d]))
        pa = pa.at[w, d].set(jnp.where(active, a, pa[:, d]))
        nodes = jnp.where(active, edge_get(tree, tree.child, nodes, a), nodes)
        depth = depth + jnp.where(active, i32(1), i32(0))
        return nodes, depth, pn, pa

    pn = jnp.full((p, D), NULL, dtype=i32)
    pa = jnp.full((p, D), NULL, dtype=i32)
    nodes, depths, pn, pa = jax.lax.fori_loop(
        0, D, level, (jnp.broadcast_to(tree.root, (p,)), jnp.zeros(p, i32), pn, pa)
    )
    leaves = nodes
    edge_VL, node_O = _apply_virtual_loss(tree, tree.edge_VL, tree.node_O,
                                          pn, pa, leaves)
    tree = dataclasses.replace(tree, edge_VL=edge_VL, node_O=node_O)
    return _assign_expansions(cfg, tree, pn, pa, depths, leaves, p)


def _assign_expansions(cfg, tree, pn, pa, depths, leaves, p):
    """BSP expansion-assignment post-pass (worker order), shared by all
    selection variants."""
    i32 = jnp.int32

    def assign(j, carry):
        pending, claimed, budget, ea, ni = carry
        leaf = leaves[j]
        can = (node_get(tree.terminal, leaf) == 0) & (depths[j] < cfg.D)
        n_act = node_get(tree.num_actions, leaf)
        n_exp = node_get(tree.num_expanded, leaf)
        if cfg.expand_all:
            ok = (
                can
                & (node_get(claimed, leaf) == 0)
                & (n_exp == 0)
                & (n_act > 0)
                & (budget >= n_act)
            )
            ea = ea.at[j].set(jnp.where(ok, i32(-2), i32(NULL)))
            ni = ni.at[j].set(jnp.where(ok, n_act, i32(0)))
            claimed = node_add(tree, claimed, leaf,
                               jnp.where(ok, i32(1), i32(0)))
            budget = budget - jnp.where(ok, n_act, i32(0))
        else:
            a = n_exp + node_get(pending, leaf)
            ok = can & (a < n_act) & (budget >= 1)
            ea = ea.at[j].set(jnp.where(ok, a, i32(NULL)))
            ni = ni.at[j].set(jnp.where(ok, i32(1), i32(0)))
            pending = node_add(tree, pending, leaf,
                               jnp.where(ok, i32(1), i32(0)))
            budget = budget - jnp.where(ok, i32(1), i32(0))
        return pending, claimed, budget, ea, ni

    pending = jnp.zeros_like(tree.num_expanded)   # per-node counts, packed
    claimed = jnp.zeros_like(tree.num_expanded)
    ea = jnp.full(p, NULL, dtype=i32)
    ni = jnp.zeros(p, dtype=i32)
    budget0 = jnp.asarray(cfg.X, i32) - tree.size
    _, _, _, ea, ni = jax.lax.fori_loop(
        0, p, assign, (pending, claimed, budget0, ea, ni)
    )
    # dtype pinned: cumsum of i32 widens to i64 under JAX_ENABLE_X64
    insert_base = tree.size + jnp.cumsum(ni, dtype=i32) - ni
    return tree, SelectionResult(pn, pa, depths, leaves, ea, ni, insert_base)


@functools.partial(jax.jit, static_argnums=(0,))
def insert_batch(cfg: TreeConfig, tree: PackedTree, sel: SelectionResult):
    """Node Insertion for all workers at once (vectorized scatter).

    Returns (tree', new_nodes[p, Fp] NULL-padded).  Distinctness of target
    edges is guaranteed by the assignment post-pass (the paper's
    'all workers expand different nodes' invariant), so scatters never
    collide except the commutative num_expanded counts.
    """
    p = sel.leaves.shape[0]
    X, Fp = tree.X, tree.Fp
    i32 = jnp.int32
    lane = jnp.arange(Fp, dtype=i32)[None, :]                     # [1, Fp]
    single = (sel.expand_action[:, None] >= 0)                    # [p, 1]
    allmode = (sel.expand_action[:, None] == -2)                  # [p, 1]
    act = jnp.where(single, sel.expand_action[:, None], lane)     # [p, Fp]
    valid = (single & (lane == 0)) | (allmode & (lane < sel.n_insert[:, None]))
    nid = sel.insert_base[:, None] + jnp.where(single, 0, lane)   # [p, Fp]
    leaf = jnp.broadcast_to(sel.leaves[:, None], (p, Fp))

    li = jnp.where(valid, leaf, X)
    ci = jnp.where(valid, nid, X)
    child = edge_set(tree, tree.child, li, act, jnp.where(valid, nid, NULL))
    leaf_depth = node_get(tree.node_depth, sel.leaves)[:, None]   # [p, 1]
    node_depth = node_set(tree, tree.node_depth, ci,
                          jnp.broadcast_to(leaf_depth + 1, (p, Fp)))
    num_actions = node_set(tree, tree.num_actions, ci, i32(cfg.F))
    num_expanded = node_add(tree, tree.num_expanded, li,
                            jnp.where(valid, i32(1), i32(0)))
    size = tree.size + jnp.sum(sel.n_insert, dtype=i32)
    new_nodes = jnp.where(valid, nid, NULL)
    tree = dataclasses.replace(
        tree, child=child, node_depth=node_depth,
        num_actions=num_actions, num_expanded=num_expanded, size=size)
    return tree, new_nodes


@jax.jit
def finalize_expansion_batch(
    tree: PackedTree,
    nodes,          # [k] i32 (NULL-padded ok)
    num_actions,    # [k] i32
    terminal,       # [k] i32
    prior_parent=None,   # [k2] i32 parent ids (NULL-padded ok)
    priors_fx=None,      # [k2, Fp] i32
):
    na = node_set(tree, tree.num_actions, nodes, num_actions)
    tm = node_set(tree, tree.terminal, nodes, terminal)
    ep = tree.edge_P
    if priors_fx is not None:
        lane = jnp.arange(tree.Fp, dtype=jnp.int32)
        ep = edge_set(tree, ep, prior_parent[:, None], lane, priors_fx)
    return dataclasses.replace(tree, num_actions=na, terminal=tm, edge_P=ep)


@functools.partial(jax.jit, static_argnums=(0, 5, 6))
def backup_batch(
    cfg: TreeConfig,
    tree: PackedTree,
    sel: SelectionResult,
    sim_nodes,      # [p] i32
    values_fx,      # [p] i32 Qm.16
    alternating_signs: bool = False,
    with_mask: bool = False,
    dropped=None,   # [p] bool — straggler/failed workers (recover-only)
):
    """BackUp for all p workers — one vectorized scatter-add pass.

    Exact integer arithmetic makes the scatter order-free, so this equals
    the sequential program bit-for-bit while touching each path edge once.

    Fault tolerance (distributed.fault.BSPFaultPolicy): a `dropped` worker
    gets a VL-recovery-only backup — its virtual loss and in-flight
    counters are removed exactly as if it had never been dispatched, but
    it contributes no visit counts or reward.  The UCT quiescence
    invariants (VL == 0, O == 0) therefore survive worker loss.
    """
    p, D = sel.path_nodes.shape
    X = tree.X
    i32 = jnp.int32
    on_path = sel.path_nodes != NULL                              # [p, D]
    expanded = (sel.expand_action >= 0) & jnp.asarray(not cfg.expand_all)
    sim_depth = sel.depths + jnp.where(expanded, 1, 0)            # [p]

    if with_mask:
        alive = ~jnp.asarray(dropped)
    else:
        alive = jnp.ones((p,), bool)

    d_idx = jnp.arange(D, dtype=i32)[None, :]
    if alternating_signs:
        sign = jnp.where((sim_depth[:, None] - d_idx) % 2 == 1, i32(-1), i32(1))
    else:
        sign = jnp.ones((p, D), dtype=i32)

    rinc = jnp.where(on_path, i32(1), i32(0))                 # recovery
    ninc = rinc * jnp.where(alive, i32(1), i32(0))[:, None]   # accumulation
    winc = ninc * sign * values_fx[:, None]
    li = jnp.where(on_path, sel.path_nodes, X)
    ai = sel.path_actions

    edge_N = edge_add(tree, tree.edge_N, li, ai, ninc)
    edge_W = edge_add(tree, tree.edge_W, li, ai, winc)
    edge_VL = edge_add(tree, tree.edge_VL, li, ai, -rinc)
    node_N = node_add(tree, tree.node_N, li, ninc)
    node_O = node_add(tree, tree.node_O, li, -rinc)
    node_N = node_add(tree, node_N, sel.leaves,
                      jnp.where(alive, i32(1), i32(0)))
    node_O = node_add(tree, node_O, sel.leaves, i32(-1))

    # Expansion edges (single-expand mode): seed the sim node's in-edge.
    live_exp = expanded & alive
    e_leaf = jnp.where(live_exp, sel.leaves, X)
    e_sign = jnp.where(
        jnp.asarray(alternating_signs) & ((sim_depth - sel.depths) % 2 == 1),
        i32(-1), i32(1))
    e_inc = jnp.where(live_exp, i32(1), i32(0))
    edge_N = edge_add(tree, edge_N, e_leaf, sel.expand_action, e_inc)
    edge_W = edge_add(tree, edge_W, e_leaf, sel.expand_action,
                      e_inc * e_sign * values_fx)
    node_N = node_add(tree, node_N, jnp.where(live_exp, sim_nodes, X), i32(1))

    return dataclasses.replace(
        tree, edge_N=edge_N, edge_W=edge_W, edge_VL=edge_VL,
        node_N=node_N, node_O=node_O)


@jax.jit
def best_root_action(tree: PackedTree):
    """Robust-child action choice at the MCTS step boundary."""
    lane = jnp.arange(tree.Fp, dtype=jnp.int32)
    n = edge_row(tree, tree.edge_N, tree.root)
    ok = ((lane < node_get(tree.num_actions, tree.root))
          & (edge_row(tree, tree.child, tree.root) != NULL))
    return jnp.argmax(jnp.where(ok, n, -1)).astype(jnp.int32)


# --------------------------------------------------------------------------
# Arena entry points (service layer): every op vmapped over G stacked trees
# --------------------------------------------------------------------------
#
# The arena (tree.init_arena, one PackedTree) carries G independent searches
# in one pytree; these wrappers run the single-tree ops above on every slot
# in ONE device program.  `active` is a [G] bool mask: the op still executes
# on idle slots (a uniform program, no ragged dispatch) but where_trees
# discards their tree updates, so an idle slot's statistics are untouched
# and its SelectionResult rows are dead data the host must ignore.
#
# Per-slot semantics are exactly the single-tree semantics — vmap adds a
# batch axis without changing any per-element arithmetic — so the arena
# inherits the reference-executor bit-compatibility of select/insert/backup
# (asserted end-to-end in tests/test_service.py).  The Pallas kernels have
# their own arena entry points (kernels.ops.select_arena/backup_arena, a
# [G]-grid launch instead of vmap) behind the same executor contract.

@functools.partial(jax.jit, static_argnums=(0, 3, 4))
def select_arena(cfg: TreeConfig, arena: PackedTree, active, p: int,
                 variant: str = "faithful"):
    """Selection for p workers on every slot.  Returns (arena', sel[G,...])."""
    if variant == "wavefront":
        fn = lambda t: select_batch_wavefront(cfg, t, p)
    else:
        fn = lambda t: select_batch(cfg, t, p, variant == "relaxed")
    new, sel = jax.vmap(fn)(arena)
    return where_trees(active, new, arena), sel


@functools.partial(jax.jit, static_argnums=(0,))
def insert_arena(cfg: TreeConfig, arena: PackedTree, active, sel):
    """Node Insertion on every slot.  Returns (arena', new_nodes[G, p, Fp])."""
    new, nodes = jax.vmap(lambda t, s: insert_batch(cfg, t, s))(arena, sel)
    return where_trees(active, new, arena), nodes


@jax.jit
def finalize_arena(arena: PackedTree, nodes, num_actions, terminal,
                   prior_parent, priors_fx):
    """finalize_expansion_batch per slot.  All inputs carry a leading [G]
    axis; idle/short slots are NULL-padded rows (finalize is NULL-safe), so
    no active mask is needed."""
    return jax.vmap(finalize_expansion_batch)(
        arena, nodes, num_actions, terminal, prior_parent, priors_fx)


@functools.partial(jax.jit, static_argnums=(0, 6, 7))
def backup_arena(cfg: TreeConfig, arena: PackedTree, active, sel, sim_nodes,
                 values_fx, alternating_signs: bool = False,
                 with_mask: bool = False, dropped=None):
    """BackUp on every slot ([G, p] sim nodes / values).  With
    `with_mask`, `dropped` is a [G, p] straggler mask: dropped workers get
    the VL-recovery-only backup of backup_batch."""
    if with_mask:
        new = jax.vmap(
            lambda t, s, n, v, d: backup_batch(
                cfg, t, s, n, v, alternating_signs, True, d)
        )(arena, sel, sim_nodes, values_fx, jnp.asarray(dropped))
    else:
        new = jax.vmap(
            lambda t, s, n, v: backup_batch(cfg, t, s, n, v, alternating_signs)
        )(arena, sel, sim_nodes, values_fx)
    return where_trees(active, new, arena)


@jax.jit
def best_root_action_arena(arena: PackedTree):
    """Robust-child action for every slot.  Returns [G] i32."""
    return jax.vmap(best_root_action)(arena)
