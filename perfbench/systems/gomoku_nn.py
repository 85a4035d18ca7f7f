"""The Gomoku configuration's system: `GomokuEnv` with a seeded
policy-value network (`NNSimBackend`) behind the microbatching
`SimServer` and the transposition cache (`CachedSimBackend`), searched
with expand-all PUCT on the classic phase pipeline.

Precision.  The configuration states the network in float32 (`net.dtype`,
the only type this system runs).  `NNSimBackend` has no precision setting
of its own, and JAX's default on a TPU computes float32 convolutions and
products in one bfloat16 pass, so the process runs at matmul precision
`highest`: what float32 means on the chip.  That setting holds for every
JAX computation of the process.

Inputs.  A request's seed picks its opening: `opening_stones` stones
placed on random empty cells, players alternating, drawn from the seed
(the program's `GomokuEnv` starts every game on the empty board, so this
subclass only replaces `initial_state`).  The weights are made from the
run's seed on the device, in one jitted call, and handed to the program.

`correct` compares three things:

  tree        a sample of the served requests replayed in the plain
              reference search, fed at each state with the evaluation
              the program served for it: requests whose actions or root
              visit counts differ, or that reach a state the program
              never evaluated, are counted;
  net         every state the replay consumed, and up to `check_rows`
              more of the window's evaluations drawn from the seed,
              against the reference network in float32 at `highest`
              precision: the widest gap of a value, and of a prior;
              so no evaluation the tree check takes from the program
              goes unchecked;
  disagree    evaluations of a state that differ from its first one,
              over the whole run: the network and the transposition
              cache must answer a state the same way each time.

The served evaluations are read by a tap around `NNSimBackend`: it
forwards `dispatch`/`finalize` unchanged and keeps each state's outputs.
"""

from __future__ import annotations

import numpy as np

from perfbench.reference import gomoku as ref
from perfbench.reference import mcts


def opening(seed: int, stones: int) -> list[int]:
    """Action indices of a request's opening, from its seed."""
    rng = np.random.default_rng(int(seed))
    return [int(rng.integers(ref.CELLS - i)) for i in range(stones)]


def weights_key(seed: int):
    import jax

    word = np.random.SeedSequence(int(seed)).generate_state(1)[0]
    return jax.random.PRNGKey(int(word))


class Tap:
    """Forwards the network backend and keeps what it served, per
    state: the first (value, priors) of each, and how many later
    evaluations of a state disagreed with its first."""

    def __init__(self, inner):
        self.inner = inner
        self.served: dict = {}
        self.order: list = []
        self.consumed: dict = {}     # keys the replay read, in order
        self.repeats = 0             # evaluations of a state seen before
        self.disagree = 0

    def dispatch(self, states):
        return self.inner.dispatch(states)

    def finalize(self, token, states):
        values, priors = self.inner.finalize(token, states)
        for i in range(len(states)):
            key = states[i].tobytes()
            got = self.served.get(key)
            if got is None:
                self.served[key] = (values[i], priors[i].copy())
                self.order.append(key)
            else:
                self.repeats += 1
                if (got[0] != values[i]
                        or not np.array_equal(got[1], priors[i])):
                    self.disagree += 1
        return values, priors

    def evaluate(self, states):
        return self.finalize(self.dispatch(states), states)

    def lookup(self, states):
        """The served evaluations of `states`, each noted as consumed."""
        keys = [np.asarray(s, np.float32).tobytes() for s in states]
        rows = [self.served.get(k) for k in keys]
        if any(r is None for r in rows):
            raise KeyError("a state the program never evaluated")
        self.consumed.update(dict.fromkeys(keys))
        return (np.array([r[0] for r in rows], np.float32),
                np.stack([r[1] for r in rows]))


class System:
    def __init__(self, config: dict, seed: int):
        import jax

        from repro.envs import GomokuEnv
        from repro.envs.policy_net import NNSimBackend
        from repro.sim import CachedSimBackend, SimServer

        self.config = config
        net, sim = config["net"], config["sim"]
        stones = config["opening_stones"]
        if net["dtype"] != "float32":
            raise ValueError(f"the network runs in float32 only, not "
                             f"{net['dtype']!r}")
        # float32 on the chip: without this a TPU computes float32
        # products in one bfloat16 pass
        jax.config.update("jax_default_matmul_precision", "highest")

        class OpeningGomoku(GomokuEnv):
            def initial_state(self, seed: int = 0):
                s = super().initial_state(seed)
                for a in opening(seed, stones):
                    s, _, _ = self.step(s, a)
                return s

        self.env = OpeningGomoku()
        make = jax.jit(ref.init_params, static_argnums=(1, 2))
        self.params = jax.block_until_ready(
            make(weights_key(seed), net["channels"], net["value_hidden"]))
        self.tap = Tap(NNSimBackend(self.env, self.params))
        self.sim = CachedSimBackend(
            SimServer(self.tap, max_batch=sim["max_batch"],
                      default_priority=sim["priority"]),
            capacity=sim["cache_capacity"])
        self.window_from = 0

    def client_options(self) -> dict:
        return {"sim_backend": self.sim}

    def mark_window(self) -> None:
        """Evaluations from here on are the window's."""
        self.window_from = len(self.tap.order)

    def _env(self):
        stones = self.config["opening_stones"]

        class Env(ref.GomokuEnv):
            def initial_state(self, seed: int = 0):
                s = super().initial_state(seed)
                for a in opening(seed, stones):
                    s, _ = self.step(s, a)
                return s

        return Env()

    def replay(self, spec: dict):
        c = self.config
        return mcts.serve(c["tree"], c["service"]["p"], self._env(),
                          self.tap.lookup, spec["seed"], spec["budget"],
                          spec["moves"], c["service"]["alternating_signs"])

    def tree_mismatch(self, sample: list, log) -> int:
        bad = 0
        for spec, res in sample:
            got = (list(res.actions),
                   [[int(v) for v in vc] for vc in res.visit_counts])
            try:
                want = self.replay(spec)
            except KeyError as e:
                log(f"request {spec['uid']}: {e}")
                bad += 1
                continue
            bad += want != got
        return bad

    def net_rows(self, rng: np.random.Generator):
        """Every state the replay consumed, then up to `check_rows` of
        the window's evaluations drawn from the seed: (states, served
        values, served priors)."""
        window = self.tap.order[self.window_from:]
        n = min(len(window), self.config["check_rows"])
        pick = sorted(rng.choice(len(window), n, replace=False)) if n else []
        keys = list(dict.fromkeys(
            list(self.tap.consumed) + [window[i] for i in pick]))
        if not keys:
            return (np.zeros((0, ref.WORDS), np.float32),
                    np.zeros(0, np.float32),
                    np.zeros((0, ref.CELLS), np.float32))
        rows = [self.tap.served[k] for k in keys]
        return (np.stack([np.frombuffer(k, np.float32) for k in keys]),
                np.array([r[0] for r in rows], np.float32),
                np.stack([r[1] for r in rows]))

    def gaps(self, states, v, p):
        rv, rp = ref.evaluate(self.params, states)
        if not len(states):
            return 0.0, 0.0
        return (float(np.max(np.abs(v - rv))),
                float(np.max(np.abs(p - rp))))

    def check(self, sample: list, log, rng=None) -> dict:
        rng = np.random.default_rng(0) if rng is None else rng
        mismatch = self.tree_mismatch(sample, log)
        states, v, p = self.net_rows(rng)
        log(f"net check: {len(states)} served evaluations, "
            f"{len(self.tap.consumed)} of them consumed by the replay, of "
            f"{len(self.tap.order) - self.window_from} in the window; "
            f"{self.tap.disagree} of {self.tap.repeats} re-evaluations "
            f"(padding copies included) differed from the first")
        vg, pg = self.gaps(states, v, p)
        return {"tree_mismatch": mismatch, "value_gap": vg, "prior_gap": pg,
                "eval_disagree": self.tap.disagree}

    def control(self, sample: list, log, rng=None) -> dict:
        """The reference network in the program's place, one precision
        step below float32 at `highest`: three bfloat16 passes; and, for
        the record, one."""
        rng = np.random.default_rng(0) if rng is None else rng
        states, _, _ = self.net_rows(rng)
        if not len(states):
            return {}
        rv, rp = ref.evaluate(self.params, states, "highest")
        out = {}
        for mode, tag in (("bf16x3", ""), ("bf16", "_bf16")):
            v, p = ref.evaluate(self.params, states, mode)
            out["value_gap" + tag] = float(np.max(np.abs(v - rv)))
            out["prior_gap" + tag] = float(np.max(np.abs(p - rp)))
        return out


def build(config: dict, seed: int) -> System:
    return System(config, seed)
