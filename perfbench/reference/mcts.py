"""Plain sequential reference of one served search request.

A straightforward numpy implementation of the semantics the search
service states for one request: p-worker tree-parallel MCTS in
bulk-synchronous supersteps, WU-UCT virtual loss, UCT or PUCT edge
scores in Qm.16 fixed point, robust-child move choice, and subtree reuse
between the moves of a request.  It imports nothing of the program.

One superstep, for workers j = 0 .. p-1 in order:

  selection   descend from the root while the node is not a leaf, taking
              the first edge of highest score; each step adds one
              in-flight visit to the edge and the child, so worker j sees
              the virtual loss of workers < j;
  assignment  in worker order, each leaf that can grow claims its
              expansion: the next unexpanded action (partial mode), or
              all legal actions at once (expand-all mode), within the
              node budget X - size;
  insertion   new node ids in worker order, children in action order;
  expansion   one environment step per inserted child;
  simulation  one evaluation per worker: of the new child for a single
              expansion, else of the leaf (expand-all: the leaf, whose
              priors seed its new edges);
  backup      every edge on the path gains a visit and the value
              (sign-alternated for two-player games) and loses its
              virtual loss.

A move commits after `budget` supersteps, or when the tree holds X
nodes, or after a superstep that inserted nothing; the action is the
root edge with the most visits (first of equals), and the chosen
child's subtree becomes the next move's tree.

`quantum` rounds every simulation value and prior to a multiple of
2**-quantum before the fixed-point encoding: 16 is the stated Qm.16
precision, a smaller number is the control computed in less.
"""

from __future__ import annotations

import numpy as np

NULL = -1
FRAC_BITS = 16
FX_SCALE = np.float32(1 << FRAC_BITS)
FX_INV_SCALE = np.float32(1.0 / (1 << FRAC_BITS))
FX_FORCE_EXPLORE = np.int32(1 << 28)
FX_NEG_INF = np.int32(-(1 << 30))
FX_MAX = np.float32((1 << 27) - 1)
FX_MIN = np.float32(-(1 << 27))
EXPAND_ALL = -2


def encode(x, quantum: int = FRAC_BITS) -> np.ndarray:
    """float32 -> Qm.16 int32, round half to even, clamped to the band of
    real scores.  With quantum < 16 the value is first rounded to a
    multiple of 2**-quantum."""
    x = np.asarray(x, np.float32)
    if quantum < FRAC_BITS:
        q = np.float32(1 << quantum)
        x = (np.round(x * q) / q).astype(np.float32)
    fx = np.round(x * FX_SCALE)
    return np.clip(fx, FX_MIN, FX_MAX).astype(np.int32)


def log_table(X: int) -> np.ndarray:
    """ln(n) for n < 2X+4, computed in float64 and rounded to float32."""
    n = np.arange(2 * X + 4, dtype=np.float64)
    with np.errstate(divide="ignore"):
        t = np.log(n)
    t[0] = 0.0
    return t.astype(np.float32)


class Search:
    """The tree of one request, with its state table."""

    def __init__(self, cfg: dict, state_shape, root_state, root_actions):
        self.X, self.F, self.D = cfg["X"], cfg["F"], cfg["D"]
        self.Fp = 1 << max(0, (self.F - 1).bit_length())
        self.beta = np.float32(cfg["beta"])
        self.puct = cfg["score_fn"] == "puct"
        self.expand_all = bool(cfg["expand_all"])
        self.partial = cfg["leaf_mode"] == "partial"
        if cfg["vl_mode"] != "wu":
            raise NotImplementedError("the reference states WU-UCT only")
        self.log = log_table(self.X)
        self.lane = np.arange(self.Fp, dtype=np.int32)
        X, Fp = self.X, self.Fp
        self.child = np.full((X, Fp), NULL, np.int32)
        self.eN = np.zeros((X, Fp), np.int32)
        self.eW = np.zeros((X, Fp), np.int32)
        self.eVL = np.zeros((X, Fp), np.int32)
        self.eP = np.zeros((X, Fp), np.int32)
        self.nN = np.zeros(X, np.int32)
        self.nO = np.zeros(X, np.int32)
        self.n_exp = np.zeros(X, np.int32)
        self.n_act = np.zeros(X, np.int32)
        self.term = np.zeros(X, np.int32)
        self.states = np.zeros((X,) + tuple(state_shape), np.float32)
        self.reset(root_state, root_actions)

    def reset(self, root_state, root_actions: int):
        for a in (self.eN, self.eW, self.eVL, self.eP):
            a[:] = 0
        self.child[:] = NULL
        for a in (self.nN, self.nO, self.n_exp, self.n_act, self.term):
            a[:] = 0
        self.n_act[0] = root_actions
        self.states[0] = root_state
        self.size = 1

    # ---- scores ----
    def scores(self, node: int) -> np.ndarray:
        f32, i32 = np.float32, np.int32
        child = self.child[node]
        valid = (self.lane < self.n_act[node]) & (child != NULL)
        ne = self.eN[node] + self.eVL[node]
        ns = min(int(self.nN[node]) + int(self.nO[node]), 2 * self.X + 3)
        ne_safe = np.maximum(ne, i32(1)).astype(f32)
        q = (self.eW[node].astype(f32) * FX_INV_SCALE) / ne_safe
        if self.puct:
            q = np.where(ne == 0, f32(0.0), q)
            sqrt_ns = np.sqrt(f32(ns))
            p_f = self.eP[node].astype(f32) * FX_INV_SCALE
            u = self.beta * p_f * sqrt_ns / (f32(1.0) + ne.astype(f32))
            base = encode(q + u)
        else:
            u = self.beta * np.sqrt(self.log[ns] / ne_safe)
            base = encode(q + u)
            base = np.where(ne == 0, FX_FORCE_EXPLORE, base)
        return np.where(valid, base, FX_NEG_INF)

    def is_leaf(self, node: int, depth: int) -> bool:
        ne, na = self.n_exp[node], self.n_act[node]
        open_node = ne < na if self.partial else ne == 0
        return bool(open_node or self.term[node] or depth >= self.D
                    or na == 0)

    # ---- one superstep ----
    def superstep(self, p: int, env, evaluate, alternating: bool,
                  quantum: int = FRAC_BITS) -> None:
        D = self.D
        paths = []
        for _ in range(p):
            node, depth = 0, 0
            self.nO[0] += 1
            pn, pa = [], []
            while not self.is_leaf(node, depth):
                a = int(np.argmax(self.scores(node)))
                self.eVL[node, a] += 1
                pn.append(node)
                pa.append(a)
                node = int(self.child[node, a])
                self.nO[node] += 1
                depth += 1
            paths.append((pn, pa, depth, node))

        # expansion assignment, worker order, within the node budget
        budget = self.X - self.size
        pending, claimed = {}, set()
        plan = []
        for pn, pa, depth, leaf in paths:
            ea = NULL
            if not self.term[leaf] and depth < D:
                if self.expand_all:
                    k = int(self.n_act[leaf])
                    if (leaf not in claimed and self.n_exp[leaf] == 0
                            and k > 0 and budget >= k):
                        claimed.add(leaf)
                        ea = EXPAND_ALL
                        budget -= k
                else:
                    a = int(self.n_exp[leaf]) + pending.get(leaf, 0)
                    if a < self.n_act[leaf] and budget >= 1:
                        pending[leaf] = pending.get(leaf, 0) + 1
                        ea = a
                        budget -= 1
            plan.append(ea)

        # insertion + host expansion
        sim_states, sim_nodes, prior_of = [], [], []
        for (pn, pa, depth, leaf), ea in zip(paths, plan):
            sim_node = leaf
            if ea == EXPAND_ALL:
                states, terms, legal = env.children(self.states[leaf])
                k = len(states)
                ids = np.arange(self.size, self.size + k)
                self.child[leaf, :k] = ids
                self.n_exp[leaf] += k
                self.states[ids] = states
                self.term[ids] = terms
                self.n_act[ids] = legal
                self.size += k
            elif ea != NULL:
                nid = self.size
                self.size += 1
                self.child[leaf, ea] = nid
                self.n_exp[leaf] += 1
                s2, term = env.step(self.states[leaf], ea)
                self.states[nid] = s2
                self.term[nid] = int(term)
                self.n_act[nid] = 0 if term else env.num_actions(s2)
                sim_node = nid
            sim_nodes.append(sim_node)
            sim_states.append(self.states[sim_node])
            prior_of.append(leaf if ea == EXPAND_ALL else NULL)

        values, priors = evaluate(np.stack(sim_states))
        values_fx = encode(values, quantum)
        for j, leaf in enumerate(prior_of):
            if leaf != NULL:
                row = np.zeros(self.Fp, np.float32)
                row[: priors.shape[1]] = priors[j]
                self.eP[leaf] = encode(row, quantum)

        for j, ((pn, pa, depth, leaf), ea) in enumerate(zip(paths, plan)):
            v = values_fx[j]
            single = ea >= 0 and not self.expand_all
            sim_depth = depth + (1 if single else 0)
            for d in range(depth):
                node, a = pn[d], pa[d]
                sign = -1 if alternating and (sim_depth - d) % 2 else 1
                self.eN[node, a] += 1
                self.eW[node, a] += np.int32(sign * int(v))
                self.nN[node] += 1
                self.eVL[node, a] -= 1
                self.nO[node] -= 1
            self.nN[leaf] += 1
            self.nO[leaf] -= 1
            if single:
                sign = -1 if alternating and (sim_depth - depth) % 2 else 1
                self.eN[leaf, ea] += 1
                self.eW[leaf, ea] += np.int32(sign * int(v))
                self.nN[sim_nodes[j]] += 1

    # ---- move commit ----
    def best_action(self) -> int:
        ok = (self.lane < self.n_act[0]) & (self.child[0] != NULL)
        return int(np.argmax(np.where(ok, self.eN[0], -1)))

    def reroot(self, new_root: int) -> None:
        """Keep the subtree under `new_root`, statistics and states with
        it, renumbered in breadth-first order."""
        order = [new_root]
        for n in order:
            for c in self.child[n]:
                if c != NULL:
                    order.append(int(c))
        idx = np.asarray(order, np.int64)
        old2new = np.full(self.X, NULL, np.int64)
        old2new[idx] = np.arange(len(idx))
        k = len(idx)
        ch = self.child[idx]
        ch = np.where(ch != NULL, old2new[np.clip(ch, 0, None)], NULL)
        for name in ("eN", "eW", "eVL", "eP", "nN", "nO", "n_exp",
                     "n_act", "term", "states"):
            arr = getattr(self, name)
            kept = arr[idx]
            arr[:k] = kept
            arr[k: self.size] = 0
        self.child[:k] = ch
        self.child[k: self.size] = NULL
        self.size = k


def serve(cfg: dict, p: int, env, evaluate, seed: int, budget: int,
          moves: int, alternating: bool = False,
          quantum: int = FRAC_BITS):
    """Serve one request from `seed`: returns (actions, root visit counts
    per move).  `env` gives initial_state(seed), step(state, a) ->
    (state, terminal), num_actions(state) and, for expand-all,
    children(state) -> (states, terminal, legal actions) of every action
    in order; `evaluate(states)` gives (values, priors or None)."""
    s0 = env.initial_state(seed)
    na = env.num_actions(s0)
    actions, counts = [], []
    if na == 0:
        return actions, counts
    t = Search(cfg, s0.shape, s0, na)
    state = s0
    F = cfg["F"]
    for _ in range(moves):
        done, prev = 0, t.size
        while True:
            t.superstep(p, env, evaluate, alternating, quantum)
            done += 1
            size = t.size
            if done >= budget or size >= t.X or size == prev:
                break
            prev = size
        a = t.best_action()
        actions.append(a)
        counts.append([int(c) for c in t.eN[0][:F]])
        state, term = env.step(state, a)
        if term or len(actions) >= moves:
            break
        new_root = int(t.child[0, a])
        if new_root != NULL:
            t.reroot(new_root)
        else:
            t.reset(state, max(env.num_actions(state), 1))
    return actions, counts
