"""Unified in-tree executor stack: one protocol, every backend, any G.

Before this module the repo carried two parallel executor hierarchies:
single-tree executors in core.mcts (stateless, tree passed in and out,
Pallas variant included) and arena executors in service.arena (stateful
over G stacked slots, Pallas gated out because the old kernels managed
their own grid).  Mirsoleimani et al.'s *Structured Parallel Programming
for MCTS* argues for exactly one execution abstraction across
parallelization patterns — this module is that collapse:

  InTreeExecutor        — the protocol.  Every implementation drives G >= 1
                          tree slots through the device phases (Selection /
                          Insertion / finalize / BackUp) under a [G] active
                          mask.  TreeParallelMCTS is the G=1 client,
                          SearchService the G>1 client; both share this
                          dispatch instead of duplicating it.
  ReferenceExecutor     — the paper's CPU-only master process: one
                          sequential numpy MutableTree per slot, looped on
                          host.  Correctness oracle and CPU baseline.
  JaxExecutor           — stacked trees + vmapped jit ops ("faithful",
                          "relaxed", "wavefront" variants).
  PallasExecutor        — the arena-native [G]-grid kernels
                          (kernels.uct_select / uct_backup): Selection and
                          BackUp in one kernel launch per phase for all
                          slots, insertion/finalize on the vmapped jit path
                          (host-coupled scatters), straggler-masked backups
                          on the jit fallback.  Bit-compatible with the
                          reference per slot.

Slot compaction: `gather_sub` extracts the active slots into a dense
sub-executor (padded to a power of two so the jit/kernel program cache
stays bounded) and `scatter_sub` writes the results back — the service
scheduler uses this at low occupancy so idle slots stop costing masked
device work (ROADMAP item).  Per-slot arithmetic is position-independent,
so compaction never changes what a slot computes.

Persistent compaction sessions: the paper's accelerator wins by keeping
the tree device-resident across supersteps (§IV), and BENCH_service.json
showed that re-gathering the sub-arena every superstep costs more than
the masked work it saves.  `open_session` wraps gather/scatter in a
CompactionSession that keeps the dense sub-arena resident: the gather
happens once, supersteps accumulate in the sub-executor with
dirty-tracking, and the scatter back into the full arena is deferred to
session close or an explicit `sync` (snapshot reads).  Membership
changes (admission / eviction / reroot rewrites) invalidate the session
— the pool closes and reopens it — so a stable active set pays one
gather + one scatter total instead of one per superstep.
"""

from __future__ import annotations

import dataclasses
from typing import Optional, Protocol

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import intree, ref_sequential as ref
from repro.core.tree import (
    NULL, ROW_KEYS, PackedTree, TreeConfig, UCTree, arena_set_slot,
    arena_slot, init_arena, init_rows, init_tree, pack_rows, pack_tree,
    pad_rows, read_slot_rows, reset_slot_rows, row_bucket, to_jax,
    unpack_rows, unpack_tree, write_slot_rows,
)
from repro.obs.trace import NULL_TRACER

EXECUTOR_NAMES = ("reference", "faithful", "relaxed", "wavefront", "pallas")


class InTreeExecutor(Protocol):
    """The in-tree accelerator contract (paper §IV, lifted to G slots).

    All array arguments follow the stacked convention: `active` is a [G]
    bool mask, selection results / sim nodes / values carry a leading [G]
    axis, and finalize takes the fixed-width NULL-padded per-slot rows of
    HostExpansion.padded_finalize_args.  Inactive slots must come back
    bit-frozen from every phase.
    """

    cfg: TreeConfig
    G: int

    def reset_slot(self, g: int, root_num_actions: int) -> int: ...
    def selection(self, active: np.ndarray, p: int): ...
    def insert(self, active: np.ndarray, sel) -> np.ndarray: ...
    def finalize(self, nodes, num_actions, terminal, prior_parent,
                 priors_fx) -> None: ...
    def backup(self, active, sel, sim_nodes, values_fx, alternating: bool,
               dropped=None) -> None: ...
    # OPTIONAL fused fast path (device executors only — the reference
    # executor keeps the phase-by-phase oracle): queue up to K supersteps
    # as one compiled program, then read it back; see repro.core.fused.
    # Absence of the attribute means "host path only" (probe with
    # hasattr).
    def run_supersteps_submit(self, active, p: int, K: int, env, sim,
                              states, budget_left, alternating: bool): ...
    def run_supersteps_collect(self, pend): ...
    def sel_to_host(self, sel) -> dict: ...
    def best_actions(self) -> np.ndarray: ...
    def sizes(self) -> np.ndarray: ...
    def slot_rows(self, g: int) -> dict: ...
    def slot_snapshot(self, g: int) -> dict: ...
    def write_slot(self, g: int, arrays: dict) -> None: ...
    def block(self) -> None: ...
    def release(self) -> None: ...
    def gather_sub(self, slot_idx: np.ndarray, Gc: int) -> "InTreeExecutor": ...
    def scatter_sub(self, sub: "InTreeExecutor", slot_idx: np.ndarray) -> None: ...
    def open_session(self, slot_idx: np.ndarray, Gc: int,
                     tracer=None, tid: int = 0) -> "CompactionSession": ...
    # single-tree compat surface (the G=1 client's `tree` property and
    # snapshot/action helpers used throughout tests and examples)
    def init(self, root_num_actions: int): ...
    def get_tree(self, g: int = 0): ...
    def set_tree(self, tree, g: int = 0) -> None: ...
    def snapshot(self, tree) -> dict: ...
    def best_action(self, tree) -> int: ...


class CompactionSession:
    """Device-resident dense sub-arena spanning one fixed active set.

    Built on any InTreeExecutor's gather_sub/scatter_sub, so every backend
    (reference / faithful / relaxed / wavefront / pallas) gets persistent
    compaction through the same object.  Lifecycle:

      open   — ONE gather_sub copies the active slots into `sub` (dense,
               pow2-padded); the session then stays resident.
      dirty  — `mark_superstep` records that `sub` holds updates the full
               arena has not seen; `sync` scatters them back WITHOUT
               closing (snapshot reads force this), after which `sub`
               keeps accumulating.
      close  — final sync + the session refuses further use.  The owning
               pool closes on any membership change (admit / evict) or
               content rewrite of a member slot (reroot / reset), since a
               host-side write to the full arena would make `sub` stale.

    `matches` is the reuse test: same slot set, same padded width, still
    open.  A stable active set therefore pays one gather and one scatter
    total, however many supersteps it stays stable — the serving analogue
    of the paper keeping the tree SRAM-resident across supersteps.
    """

    def __init__(self, parent: "InTreeExecutor", slot_idx: np.ndarray,
                 Gc: int, tracer=None, tid: int = 0):
        self.parent = parent
        self.slot_idx = np.asarray(slot_idx, np.int32).copy()
        self.Gc = int(Gc)
        # obs: gather/scatter spans on the owning pool's trace track.
        # When tracing is live the gather/scatter are fenced with
        # block_until_ready so the copy cost is attributed to the span
        # instead of leaking into whichever phase next touches the arena.
        self.trace = NULL_TRACER if tracer is None else tracer
        self.tid = tid
        with self.trace.span("compact-gather", cat="compact", tid=tid,
                             slots=len(self.slot_idx), Gc=self.Gc):
            self.sub = parent.gather_sub(self.slot_idx, self.Gc)
            if self.trace.enabled:
                self.sub.block()
        self.dirty = False
        self.open = True
        self.supersteps = 0

    @property
    def A(self) -> int:
        return len(self.slot_idx)

    def matches(self, slot_idx: np.ndarray, Gc: int) -> bool:
        return (self.open and self.Gc == int(Gc)
                and len(slot_idx) == self.A
                and bool(np.array_equal(self.slot_idx, slot_idx)))

    def owns(self, g: int) -> bool:
        return self.open and bool(np.any(self.slot_idx == g))

    def mark_superstep(self):
        assert self.open, "superstep on a closed CompactionSession"
        self.dirty = True
        self.supersteps += 1

    def sync(self) -> bool:
        """Scatter pending sub-arena updates back; True if one happened."""
        if self.dirty:
            with self.trace.span("compact-scatter", cat="compact",
                                 tid=self.tid, slots=len(self.slot_idx)):
                self.parent.scatter_sub(self.sub, self.slot_idx)
                if self.trace.enabled:
                    self.parent.block()
            self.dirty = False
            return True
        return False

    def close(self) -> bool:
        """Final sync; the session is unusable afterwards.  True if the
        close actually scattered."""
        scattered = self.sync() if self.open else False
        self.open = False
        return scattered


def _sel_to_host(sel) -> dict:
    """One Receive-buffer transfer: device selection result -> host numpy."""
    if isinstance(sel, dict):
        return sel
    d = {
        "path_nodes": sel.path_nodes, "path_actions": sel.path_actions,
        "depths": sel.depths, "leaves": sel.leaves,
        "expand_action": sel.expand_action, "n_insert": sel.n_insert,
        "insert_base": sel.insert_base,
    }
    return {k: np.asarray(v) for k, v in jax.device_get(d).items()}


class JaxExecutor:
    """Vmapped jit in-tree operations over G stacked trees.

    The arena lives on the device as one PackedTree (core/tree.py), in the
    rows the Pallas kernels read, for every variant; the host-side slot
    access below speaks the logical [R, Fp] / [R] layout and converts with
    a numpy reshape.

    `device` commits the arena to one specific device (multi-device
    serving: core/sharded.py builds one executor per shard).  Every op —
    eager and jit — then follows the committed placement, and the host
    uploads (active masks, finalize rows, sim states) stay uncommitted
    so XLA moves them to the arena's device automatically.  None keeps
    the historical default-device placement.
    """

    def __init__(self, cfg: TreeConfig, G: int, variant: str = "faithful",
                 _trees: Optional[PackedTree] = None, device=None):
        if variant not in ("faithful", "relaxed", "wavefront"):
            raise NotImplementedError(
                f"JaxExecutor variant {variant!r}: the vmappable jit paths "
                "are faithful/relaxed/wavefront (the arena-native Pallas "
                "kernels are PallasExecutor / executor='pallas')")
        self.cfg, self.G, self.variant = cfg, G, variant
        self._fused_variant = variant
        self.device = device
        self._warm_buckets: set = set()
        self.trees = init_arena(cfg, G) if _trees is None else _trees
        if device is not None and _trees is None:
            from repro.models.sharding import put_on_device
            self.trees = put_on_device(self.trees, device)

    # -- device phases -------------------------------------------------
    def selection(self, active: np.ndarray, p: int):
        self.trees, sel = intree.select_arena(
            self.cfg, self.trees, jnp.asarray(active), p, self.variant)
        return sel

    def insert(self, active: np.ndarray, sel):
        return self.insert_host(self.insert_dev(active, sel))

    def insert_dev(self, active: np.ndarray, sel):
        """Dispatch Node Insertion and return the DEVICE id block without
        reading it back — the overlap mode stages a gang's select+insert
        asynchronously and defers the (blocking) host read to
        insert_host() when that gang's host half actually starts."""
        self.trees, new_nodes = intree.insert_arena(
            self.cfg, self.trees, jnp.asarray(active), sel)
        return new_nodes

    def insert_host(self, new_nodes):
        """Blocking half of insert(): fetch the staged [G, p, Fp] id block
        to host.  insert() == insert_host(insert_dev(...)) bit-exactly."""
        return np.asarray(jax.device_get(new_nodes))

    def finalize(self, nodes, num_actions, terminal, prior_parent, priors_fx):
        self.trees = intree.finalize_arena(
            self.trees, jnp.asarray(nodes), jnp.asarray(num_actions),
            jnp.asarray(terminal), jnp.asarray(prior_parent),
            jnp.asarray(priors_fx))

    def backup(self, active, sel, sim_nodes, values_fx, alternating: bool,
               dropped=None):
        if dropped is not None:
            self.trees = intree.backup_arena(
                self.cfg, self.trees, jnp.asarray(active), sel,
                jnp.asarray(sim_nodes), jnp.asarray(values_fx), alternating,
                True, np.asarray(dropped))
        else:
            self.trees = intree.backup_arena(
                self.cfg, self.trees, jnp.asarray(active), sel,
                jnp.asarray(sim_nodes), jnp.asarray(values_fx), alternating)
        # No fence: JAX async dispatch overlaps the backup with the host
        # side of the next superstep; readers (sizes/best_actions/
        # snapshots) block on the value they fetch, and the obs layer
        # fences per-phase via block() when tracing.

    # -- fused multi-superstep dispatch --------------------------------
    def run_supersteps_submit(self, active, p: int, K: int, env, sim,
                              states, budget_left, alternating: bool):
        """Queue up to K fused supersteps in one compiled program (see
        repro.core.fused) and return a PendingDispatch of device outputs
        WITHOUT any host read.  Mutates self.trees."""
        from repro.core import fused

        self.trees, pend = fused.submit_supersteps(
            self.cfg, self._fused_variant, self.trees, np.asarray(active),
            p, K, env, sim, states, budget_left, alternating)
        return pend

    def run_supersteps_collect(self, pend):
        """Blocking half: fetch the escape scalars / host views of a
        staged dispatch as a FusedDispatch."""
        from repro.core import fused

        return fused.collect_supersteps(pend)

    # -- host-side slot access -----------------------------------------
    # Row-bounded (core.tree, "Row-bounded slot access"): the bucket of
    # rows comes from the slot's size on the device.  Reading the sizes
    # again after the pool's own sizes() read of the same arena is free
    # (a jax.Array keeps its host copy).
    def _bucket(self, rows: int) -> int:
        """The bucket covering `rows`.  A bucket's first use compiles its
        read, write and reset programs together, and the first of all
        the best-action program too.  The arena is not donated, so it
        stays as it was; each result is waited for and dropped before
        the next program runs, so at most one spare arena exists."""
        R = row_bucket(rows, self.cfg.X)
        if R in self._warm_buckets:
            return R
        if not self._warm_buckets:
            jax.block_until_ready(intree.best_root_action_arena(self.trees))
        self._warm_buckets.add(R)
        g = np.int32(0)
        jax.block_until_ready(read_slot_rows(self.trees, g, R))
        fresh = pack_rows(self.cfg, init_rows(self.cfg.Fp, R, 1, np), R)
        jax.block_until_ready(write_slot_rows(self.trees, g, fresh))
        jax.block_until_ready(reset_slot_rows(self.trees, g, np.int32(1), R))
        return R

    def reset_slot(self, g: int, root_num_actions: int) -> int:
        """Reset slot g to a fresh root on the device; only scalars
        cross.  Returns the rows reset (covering the old size)."""
        R = self._bucket(self.sizes()[g])
        self.trees = reset_slot_rows(self.trees, np.int32(g),
                                     np.int32(root_num_actions), R)
        return R

    def sel_to_host(self, sel) -> dict:
        return _sel_to_host(sel)

    def best_actions(self) -> np.ndarray:
        return np.asarray(jax.device_get(
            intree.best_root_action_arena(self.trees)))

    def sizes(self) -> np.ndarray:
        return np.asarray(jax.device_get(self.trees.size))

    def slot_rows(self, g: int) -> dict:
        """Slot g's first R rows (R covering its size), `size` and
        `root`, copied down in one device_get."""
        R = self._bucket(self.sizes()[g])
        blocks = read_slot_rows(self.trees, np.int32(g), R)
        return unpack_rows(self.cfg, jax.device_get(blocks), R)

    def slot_snapshot(self, g: int) -> dict:
        return pad_rows(self.cfg, self.slot_rows(g))

    def write_slot(self, g: int, arrays: dict):
        """Write the rows of `arrays` (a bucket of them, or all X, at or
        above the slot's old size), `size` and `root` into slot g in one
        program.  Rows from len(arrays) up to the bucket get their initial
        values, which they hold already."""
        R = self._bucket(len(arrays["child"]))
        self.trees = write_slot_rows(self.trees, np.int32(g),
                                     pack_rows(self.cfg, arrays, R))

    def block(self):
        """Wait for every queued write to the arena (tracing fences)."""
        jax.block_until_ready(self.trees)

    def release(self):
        """Drop the arena's device arrays (cold-pool retirement).  The
        executor is unusable afterwards — a retired pool builds a fresh
        one on resurrection instead of reviving this object."""
        self.trees = None

    # -- compaction (gather active slots into a dense sub-arena) -------
    def _spawn(self, trees: PackedTree, Gc: int) -> "JaxExecutor":
        # gathered trees inherit the parent's committed placement, so the
        # sub-executor records the same device without a fresh device_put
        return JaxExecutor(self.cfg, Gc, self.variant, _trees=trees,
                           device=self.device)

    def gather_sub(self, slot_idx: np.ndarray, Gc: int) -> "JaxExecutor":
        idx = np.asarray(slot_idx, np.int32)
        pad = np.full(Gc - len(idx), idx[0], np.int32)  # masked-off filler
        gidx = jnp.asarray(np.concatenate([idx, pad]))
        return self._spawn(jax.tree.map(lambda a: a[gidx], self.trees), Gc)

    def scatter_sub(self, sub: "JaxExecutor", slot_idx: np.ndarray):
        idx = jnp.asarray(np.asarray(slot_idx, np.int32))
        a = len(slot_idx)
        self.trees = jax.tree.map(
            lambda full, s: full.at[idx].set(s[:a]), self.trees, sub.trees)

    def open_session(self, slot_idx: np.ndarray, Gc: int,
                     tracer=None, tid: int = 0) -> CompactionSession:
        return CompactionSession(self, slot_idx, Gc, tracer=tracer, tid=tid)

    # -- single-tree compat surface (G=1 driver / tests) ---------------
    # Trees cross in the logical layout, as numpy on the way out.
    def init(self, root_num_actions: int) -> UCTree:
        return init_tree(self.cfg, root_num_actions, xp=np)

    def get_tree(self, g: int = 0) -> UCTree:
        return unpack_tree(arena_slot(self.trees, g))

    def set_tree(self, tree: UCTree, g: int = 0):
        self.trees = arena_set_slot(self.trees, g, to_jax(pack_tree(tree)))

    def snapshot(self, tree) -> dict:
        return {k: np.asarray(v) for k, v in dataclasses.asdict(
            jax.device_get(tree)).items()}

    def best_action(self, tree) -> int:
        return int(intree.best_root_action(to_jax(pack_tree(tree))))


class PallasExecutor(JaxExecutor):
    """Arena-native Pallas kernels behind the same executor contract.

    Selection and BackUp run as ONE [G]-grid kernel launch each (per-slot
    VMEM blocks, scalar-prefetched root/size/active, idle slots no-op in
    the kernel).  Insertion and finalize stay on the vmapped jit path —
    they are host-coupled scatters, not the SRAM-resident hot loop the
    paper accelerates.  Straggler-masked backups (fault policy) fall back
    to the jit masked path; the kernel covers the fault-free superstep.
    """

    def __init__(self, cfg: TreeConfig, G: int,
                 _trees: Optional[PackedTree] = None, device=None):
        super().__init__(cfg, G, "faithful", _trees=_trees, device=device)
        self._fused_variant = "pallas"
        from repro.kernels import ops as kops  # lazy: keeps core import-light
        self._kops = kops

    def selection(self, active: np.ndarray, p: int):
        self.trees, sel = self._kops.select_arena(
            self.cfg, self.trees, jnp.asarray(active), p)
        return sel

    def backup(self, active, sel, sim_nodes, values_fx, alternating: bool,
               dropped=None):
        if dropped is not None:
            return super().backup(active, sel, sim_nodes, values_fx,
                                  alternating, dropped)
        self.trees = self._kops.backup_arena(
            self.cfg, self.trees, jnp.asarray(active), sel,
            jnp.asarray(sim_nodes), jnp.asarray(values_fx), alternating)
        # no fence — same async-dispatch contract as JaxExecutor.backup

    def _spawn(self, trees: PackedTree, Gc: int) -> "PallasExecutor":
        return PallasExecutor(self.cfg, Gc, _trees=trees, device=self.device)


class ReferenceExecutor:
    """The paper's CPU-only master process: one sequential numpy
    MutableTree per slot, looped on host.

    Same interface and same stacked [G, ...] host-array convention as the
    device executors so every client is executor-agnostic; inactive slots
    produce zero rows the driver never reads.
    """

    def __init__(self, cfg: TreeConfig, G: int, _trees: Optional[list] = None):
        self.cfg, self.G = cfg, G
        self.trees = (
            [ref.MutableTree.from_tree(init_tree(cfg, xp=np))
             for _ in range(G)] if _trees is None else _trees)

    # -- phases --------------------------------------------------------
    def selection(self, active: np.ndarray, p: int) -> dict:
        cfg = self.cfg
        out = {
            "path_nodes": np.full((self.G, p, cfg.D), NULL, np.int32),
            "path_actions": np.full((self.G, p, cfg.D), NULL, np.int32),
            "depths": np.zeros((self.G, p), np.int32),
            "leaves": np.zeros((self.G, p), np.int32),
            "expand_action": np.full((self.G, p), NULL, np.int32),
            "n_insert": np.zeros((self.G, p), np.int32),
            "insert_base": np.zeros((self.G, p), np.int32),
        }
        for g in np.flatnonzero(active):
            t = self.trees[g]
            sel = ref.selection_phase(cfg, t, p)
            ni = sel["n_insert"]
            sel["insert_base"] = t.size + np.cumsum(ni) - ni
            for k, v in sel.items():
                out[k][g] = v
        return out

    def insert(self, active: np.ndarray, sel: dict) -> np.ndarray:
        p = sel["leaves"].shape[1]
        new_nodes = np.full((self.G, p, self.cfg.Fp), NULL, np.int32)
        for g in np.flatnonzero(active):
            slot_sel = {k: v[g] for k, v in sel.items()}
            new_nodes[g] = ref.insert_phase(self.cfg, self.trees[g], slot_sel)
        return new_nodes

    # async split: numpy has no device, so "dev" computes and "host" is
    # identity — the overlap schedule runs unchanged on the oracle
    def insert_dev(self, active: np.ndarray, sel: dict) -> np.ndarray:
        return self.insert(active, sel)

    def insert_host(self, new_nodes: np.ndarray) -> np.ndarray:
        return new_nodes

    def finalize(self, nodes, num_actions, terminal, prior_parent, priors_fx):
        for g in range(self.G):
            ref.finalize_expansion(
                self.trees[g], nodes[g], num_actions[g], terminal[g],
                prior_parent[g], priors_fx[g])

    def backup(self, active, sel, sim_nodes, values_fx, alternating: bool,
               dropped=None):
        for g in np.flatnonzero(active):
            slot_sel = {k: v[g] for k, v in sel.items()}
            ref.backup_phase(self.cfg, self.trees[g], slot_sel,
                             sim_nodes[g], values_fx[g], alternating,
                             None if dropped is None else dropped[g])

    # -- host-side slot access -----------------------------------------
    # The device executors' row-bounded surface, on host arrays.
    def reset_slot(self, g: int, root_num_actions: int) -> int:
        R = row_bucket(self.trees[g].size, self.cfg.X)
        self.trees[g] = ref.MutableTree.from_tree(
            init_tree(self.cfg, root_num_actions, xp=np))
        return R

    def sel_to_host(self, sel) -> dict:
        return sel

    def best_actions(self) -> np.ndarray:
        return np.array([ref.best_root_action(self.cfg, t)
                         for t in self.trees], np.int32)

    def sizes(self) -> np.ndarray:
        return np.array([t.size for t in self.trees], np.int32)

    def slot_rows(self, g: int) -> dict:
        t = self.trees[g]
        R = row_bucket(t.size, self.cfg.X)
        rows = {k: getattr(t, k)[:R].copy() for k in ROW_KEYS}
        rows["size"], rows["root"] = np.int32(t.size), np.int32(t.root)
        return rows

    def slot_snapshot(self, g: int) -> dict:
        return {k: np.asarray(v) for k, v in
                dataclasses.asdict(self.trees[g].to_tree()).items()}

    def write_slot(self, g: int, arrays: dict):
        self.trees[g] = ref.MutableTree.from_tree(
            UCTree(**pad_rows(self.cfg, arrays)))

    def block(self):
        pass

    def release(self):
        self.trees = None

    # -- compaction -----------------------------------------------------
    # MutableTrees mutate in place, so the sub-executor shares the slot
    # objects and scatter is a re-link; compaction is a no-op cost-wise on
    # the host oracle but keeps the scheduler executor-agnostic.
    def gather_sub(self, slot_idx: np.ndarray, Gc: int) -> "ReferenceExecutor":
        idx = list(np.asarray(slot_idx))
        shared = [self.trees[g] for g in idx]
        shared += [self.trees[idx[0]]] * (Gc - len(idx))  # masked-off filler
        return ReferenceExecutor(self.cfg, Gc, _trees=shared)

    def scatter_sub(self, sub: "ReferenceExecutor", slot_idx: np.ndarray):
        for i, g in enumerate(np.asarray(slot_idx)):
            self.trees[g] = sub.trees[i]

    def open_session(self, slot_idx: np.ndarray, Gc: int,
                     tracer=None, tid: int = 0) -> CompactionSession:
        return CompactionSession(self, slot_idx, Gc, tracer=tracer, tid=tid)

    # -- single-tree compat surface ------------------------------------
    def init(self, root_num_actions: int):
        return ref.MutableTree.from_tree(
            init_tree(self.cfg, root_num_actions, xp=np))

    def get_tree(self, g: int = 0):
        return self.trees[g]

    def set_tree(self, tree, g: int = 0):
        self.trees[g] = (tree if isinstance(tree, ref.MutableTree)
                         else ref.MutableTree.from_tree(tree))

    def snapshot(self, tree) -> dict:
        return {k: np.asarray(v) for k, v in
                dataclasses.asdict(tree.to_tree()).items()}

    def best_action(self, tree) -> int:
        return ref.best_root_action(self.cfg, tree)


def make_intree_executor(cfg: TreeConfig, G: int, name: str,
                         n_shards: int = 1,
                         devices: Optional[list] = None) -> InTreeExecutor:
    """Executor factory shared by TreeParallelMCTS (G=1) and the service
    pools.  `n_shards > 1` partitions the G slots across D per-device
    child executors behind one ShardedExecutor (core/sharded.py): slot g
    lives on shard g // (G // D), each shard's arena committed to its own
    device (`devices`, defaulting to launch.mesh.serving_devices).  The
    per-slot computation is position- and device-independent, so sharding
    never changes what a slot computes."""
    if n_shards > 1:
        from repro.core.sharded import make_sharded_executor
        return make_sharded_executor(cfg, G, name, n_shards, devices)
    device = devices[0] if devices else None
    if name == "reference":
        return ReferenceExecutor(cfg, G)
    if name == "pallas":
        return PallasExecutor(cfg, G, device=device)
    return JaxExecutor(cfg, G, name, device=device)
