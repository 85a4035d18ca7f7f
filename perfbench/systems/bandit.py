"""The Pong configuration's system: the synthetic bandit tree
(`BanditTreeEnv`) with its hash value (`BanditValueBackend`), both with
device twins, so the pallas executor runs the fused K-superstep program.

`correct` replays a sample of the served requests in the plain reference
(reference/mcts.py over reference/bandit.py) and counts those whose
actions or root visit counts differ at any move.
"""

from __future__ import annotations

from perfbench.reference import bandit, mcts


class System:
    def __init__(self, config: dict, seed: int):
        from repro.envs import BanditTreeEnv, BanditValueBackend

        tree = config["tree"]
        self.config = config
        self.env = BanditTreeEnv(fanout=tree["F"],
                                 terminal_depth=config["terminal_depth"])
        self.sim = BanditValueBackend()

    def client_options(self) -> dict:
        return {"sim": self.sim}

    def replay(self, spec: dict, quantum: int = mcts.FRAC_BITS):
        c = self.config
        env = bandit.BanditEnv(c["tree"]["F"], c["terminal_depth"])
        return mcts.serve(c["tree"], c["service"]["p"], env, bandit.values,
                          spec["seed"], spec["budget"], spec["moves"],
                          c["service"]["alternating_signs"], quantum)

    def numbers(self, sample: list, quantum: int = mcts.FRAC_BITS) -> dict:
        """Compared numbers over `sample` [(spec, result)]."""
        bad = 0
        for spec, res in sample:
            want = self.replay(spec, quantum)
            got = (list(res.actions),
                   [[int(v) for v in vc] for vc in res.visit_counts])
            bad += want != got
        return {"tree_mismatch": bad}

    def check(self, sample: list, log, rng=None) -> dict:
        return self.numbers(sample)

    def control(self, sample: list, log, rng=None) -> dict:
        """The reference in the program's place with its statistics one
        step narrower: values rounded to 8 fractional bits."""
        return self.numbers(sample, quantum=8)


def build(config: dict, seed: int) -> System:
    return System(config, seed)
