"""Tree-Parallel MCTS BSP driver (paper Alg. 2 / Fig. 2).

One superstep =
  1. Selection + Node Insertion on the accelerator          (device)
  2. Receive buffer: node indices s, s' -> host              (O(p) transfer)
  3. ST reads, 1-step simulations, ST writes                 (host, sync-free)
  4. Simulation phase (software rollout or NN/LM inference)  (host/device)
  5. barrier; Send buffer: rewards -> accelerator            (O(p) transfer)
  6. BackUp on the accelerator                               (device)

The driver is executor-agnostic: the in-tree operations run on the
sequential numpy reference (the paper's CPU-only baseline), the batched
jit ops, the arena-native Pallas kernels, or the beyond-paper wavefront
variant — selected by name through the unified executor stack
(core.executor), of which this driver is the G=1 client (the service
scheduler is the G>1 client of the very same dispatch).  All executors
are bit-compatible with the reference except "wavefront"/"relaxed"
(documented intra-superstep semantics change).

Phase wall-times are recorded per superstep so the benchmark harness can
reproduce the paper's Fig. 4 (in-tree latency) and Fig. 5 (system
throughput + breakdown) directly from driver telemetry.
"""

from __future__ import annotations

import dataclasses
import time
from typing import Any, Protocol

import numpy as np

from repro.core import fixedpoint as fx
from repro.core.executor import (
    InTreeExecutor, ReferenceExecutor, make_intree_executor,
)
from repro.core.expand import (  # noqa: F401  (re-export: long-standing home)
    ExpansionEngine, HostExpansion, encode_prior_rows, host_expand_phase,
)
from repro.core.state_table import StateTable
from repro.core.tree import NULL, TreeConfig, UCTree


# --------------------------------------------------------------------------
# Environment / simulation-backend interfaces
# --------------------------------------------------------------------------

class Environment(Protocol):
    """Host-side environment.  States are fixed-shape numpy arrays so they
    can live in the ST.  Action index `a` at a node means "the a-th legal
    action of that node's state" (stable per state)."""

    state_shape: tuple
    state_dtype: Any
    max_actions: int

    def initial_state(self, seed: int) -> np.ndarray: ...
    def num_actions(self, state: np.ndarray) -> int: ...
    def step(self, state: np.ndarray, a: int) -> tuple[np.ndarray, float, bool]: ...


class SimulationBackend(Protocol):
    """Maps a batch of states to values (and optionally priors).  This is
    the paper's Simulation phase: software rollout (Pong) or DNN inference
    (Gomoku).  The LM zoo plugs in here via LMSimBackend."""

    def evaluate(self, states: np.ndarray) -> tuple[np.ndarray, np.ndarray | None]: ...


class RolloutBackend:
    """Software simulation until termination (paper's OpenAI-gym path)."""

    def __init__(self, env, max_steps: int = 200, seed: int = 0, discount: float = 1.0):
        self.env, self.max_steps, self.discount = env, max_steps, discount
        self.rng = np.random.RandomState(seed)

    def evaluate(self, states):
        vals = np.zeros(len(states), dtype=np.float32)
        for i, s in enumerate(states):
            v, g, cur = 0.0, 1.0, s
            for _ in range(self.max_steps):
                k = self.env.num_actions(cur)
                if k == 0:
                    break
                cur, r, term = self.env.step(cur, int(self.rng.randint(k)))
                v += g * r
                g *= self.discount
                if term:
                    break
            vals[i] = v
        return vals, None


# --------------------------------------------------------------------------
# In-tree executors — the unified stack lives in core.executor; re-exported
# here for the long-standing import surface (repro.core.make_executor etc.)
# --------------------------------------------------------------------------

def make_executor(cfg: TreeConfig, name: str) -> InTreeExecutor:
    """Single-tree executor: the G=1 instance of the unified stack."""
    return make_intree_executor(cfg, 1, name)


# --------------------------------------------------------------------------
# Host expansion phase — lives in core.expand (HostExpansion /
# host_expand_phase / ExpansionEngine are re-exported above: the engine is
# shared by this G=1 driver and service/scheduler.py, which batches every
# slot's pending expansions into one VectorEnv.step_batch call)
# --------------------------------------------------------------------------

# --------------------------------------------------------------------------
# Driver
# --------------------------------------------------------------------------

@dataclasses.dataclass
class StepStats:
    supersteps: int = 0
    sim_requests: int = 0
    t_select: float = 0.0
    t_insert: float = 0.0
    t_backup: float = 0.0
    t_transfer: float = 0.0
    t_st: float = 0.0
    t_sim: float = 0.0

    @property
    def t_intree(self) -> float:
        # Paper Fig. 4 metric: Selection + Expansion(tree half) + BackUp
        # + host<->accel transfer + ST operations.
        return self.t_select + self.t_insert + self.t_backup + self.t_transfer + self.t_st

    @property
    def t_total(self) -> float:
        return self.t_intree + self.t_sim


class TreeParallelMCTS:
    """The full system of Fig. 2 on one host — the G=1 client of the
    unified executor stack (`m.tree` views slot 0 of the executor's
    arena; assigning it writes the slot back)."""

    def __init__(
        self,
        cfg: TreeConfig,
        env: Environment,
        sim: SimulationBackend,
        p: int,
        executor: str = "faithful",
        alternating_signs: bool = False,
        seed: int = 0,
        expansion: str = "loop",
    ):
        self.cfg, self.env, self.sim, self.p = cfg, env, sim, p
        self.alternating_signs = alternating_signs
        self.exec = make_intree_executor(cfg, 1, executor)
        self.expander = ExpansionEngine(env, expansion)
        self.st = StateTable(cfg.X, env.state_shape, env.state_dtype)
        # fixed finalize width (the arena finalize takes one shape per slot)
        self.K = p * cfg.Fp if cfg.expand_all else p
        self.reset(seed)

    @property
    def tree(self):
        return self.exec.get_tree(0)

    @tree.setter
    def tree(self, t):
        self.exec.set_tree(t, 0)

    def reset(self, seed: int = 0):
        s0 = self.env.initial_state(seed)
        self.tree = self.exec.init(self.env.num_actions(s0))
        self.st.flush(s0)
        self.root_state = s0
        self.stats = StepStats()

    # -- one BSP superstep (Alg. 2) ------------------------------------
    def superstep(self, fault_injector=None):
        """One BSP superstep.  `fault_injector(p) -> done[p] bool` models
        simulation workers that miss the barrier (stragglers/failures);
        with a BSPFaultPolicy-style mask, missing workers get a
        VL-recovery-only backup (see intree.backup_batch) so the tree
        invariants survive worker loss."""
        cfg, p, st = self.cfg, self.p, self.st
        active = np.ones(1, bool)
        t0 = time.perf_counter()
        sel_dev = self.exec.selection(active, p)
        self.exec.block()
        t1 = time.perf_counter()
        sel = self.exec.sel_to_host(sel_dev)           # [1, p, ...]
        slot_sel = {k: v[0] for k, v in sel.items()}
        t2 = time.perf_counter()

        # Node Insertion (tree half, accelerator)
        new_nodes = self.exec.insert(active, sel_dev)  # [1, p, Fp] numpy
        t3 = time.perf_counter()

        # --- host: ST reads + 1-step sims + ST writes (sync-free) ---
        t4 = time.perf_counter()
        hx = self.expander.expand([(0, st, slot_sel, new_nodes[0])])[0]
        sim_nodes = hx.sim_nodes
        t5 = time.perf_counter()

        # --- Simulation phase ---
        values, priors = self.sim.evaluate(hx.sim_states)
        t6 = time.perf_counter()

        # --- barrier; Send buffer -> accelerator; finalize + BackUp ---
        if hx.fin_nodes:   # saturated/terminal supersteps insert nothing
            nodes, na, term, pp, pf = hx.padded_finalize_args(
                self.K, p, cfg.Fp, priors)
            self.exec.finalize(nodes[None], na[None], term[None], pp[None],
                               pf[None])
        values_fx = np.asarray(fx.encode(values), np.int32)
        dropped = None
        if fault_injector is not None:
            done = np.asarray(fault_injector(p), bool)
            dropped = ~done
            if not dropped.any():
                dropped = None
        t7 = time.perf_counter()
        self.exec.backup(
            active, sel_dev, sim_nodes[None].astype(np.int32),
            values_fx[None], self.alternating_signs,
            None if dropped is None else dropped[None])
        self.exec.block()
        t8 = time.perf_counter()

        s = self.stats
        s.supersteps += 1
        s.sim_requests += p
        s.t_select += t1 - t0
        s.t_transfer += (t2 - t1) + (t7 - t6)
        s.t_insert += t3 - t2
        s.t_st += t5 - t4
        s.t_sim += t6 - t5
        s.t_backup += t8 - t7
        return slot_sel

    # -- one MCTS step (paper Fig. 1): build tree to X nodes, act, flush
    def run_step(self, max_supersteps: int = 10_000, reuse_subtree: bool = False):
        """reuse_subtree=True replaces the paper's full Tree Flush with a
        statistics-preserving re-root (core.reroot, beyond-paper): every
        simulation spent under the chosen action carries into the next
        step.  Requires a jax executor (host reroot feeds jnp arrays)."""
        size0 = int(np.asarray(self._size()))
        steps = 0
        while int(np.asarray(self._size())) < self.cfg.X and steps < max_supersteps:
            self.superstep()
            steps += 1
            new_size = int(np.asarray(self._size()))
            if new_size == size0:  # saturated (all leaves terminal/at depth cap)
                break
            size0 = new_size
        a = self.exec.best_action(self.tree)
        new_root_state, reward, term = self.env.step(self.root_state, a)
        snap = self.exec.snapshot(self.tree) if reuse_subtree else None
        self.root_state = new_root_state
        if reuse_subtree and not term and not isinstance(
                self.exec, ReferenceExecutor):
            from repro.core import reroot
            new_root = int(snap["child"][int(snap["root"]), a])
            if new_root != NULL:
                import jax.numpy as jnp
                self.tree, old2new = reroot.reroot_tree(
                    self.cfg, snap, new_root, jnp)
                self.st.compact(old2new)
                return a, reward, term
        # paper-faithful full flush
        k = 0 if term else self.env.num_actions(new_root_state)
        self.tree = self.exec.init(max(k, 1))
        self.st.flush(new_root_state)
        return a, reward, term

    def _size(self):
        return self.exec.sizes()[0]

    def close(self):
        """Release expansion-engine resources (process pool, if any)."""
        self.expander.close()
