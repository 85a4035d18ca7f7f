"""`correct` at sizes a test run holds: sound runs of the program pass,
the control (the reference one precision step below) fails, and so does
the program broken underneath in each way a cell can break.

These drive the harness's whole run but its look for a chip, on the CPU,
with the trees cut to a few thousand nodes and one-second windows."""

import numpy as np
import pytest

from perfbench import harness, spec

SMALL = {
    "pong.selfplay": {"tree": {"X": 1024}, "check_simulations": 2048},
    "pong.analysis": {"tree": {"X": 1024}, "check_simulations": 2048},
    "gomoku6.selfplay": {"tree": {"X": 2048}, "budget": 4,
                         "check_simulations": 512, "check_rows": 2048},
}
SEED = 2 ** 31 + 977

# The Gomoku cell is not among BENCHMARK.json's cells: its moves/s spread
# too widely between runs on one chip to be held to a bound (PERF.md).
# Its entries, as a benchmark that lists it would hold them:
GOMOKU = {
    "configs": [{
        "name": "gomoku6", "source": "https://arxiv.org/abs/2208.11208",
        "file": "perfbench/configs/gomoku6.json", "reduced": [],
        "why": "expand-all PUCT with a policy-value network behind a "
               "microbatching server"}],
    "workloads": [{
        "name": "gomoku6.selfplay", "config": "gomoku6",
        "traffic": "selfplay", "chips": 1,
        "why": "2 closed-loop callers on G=1, 8 moves x 400 sims per "
               "request: host expansion and network simulation"}],
}


def bench() -> dict:
    b = spec.benchmark()
    for key, entries in GOMOKU.items():
        b[key] = b[key] + entries
    return b


def run(cell, **kw):
    lines = []
    out = harness.run_cell(cell, SEED, 1.0, False, require_tpu=False,
                           overrides=SMALL[cell], log=lines.append,
                           bench=bench(), **kw)
    return out, lines


def failing(numbers: dict, limits: dict) -> list:
    return [k for k in limits if k in numbers and numbers[k] > limits[k]]


@pytest.mark.parametrize("cell", sorted(SMALL))
def test_sound_run_is_correct_and_control_is_not(cell):
    out, lines = run(cell, keep=True)
    assert out["correct"], (out["checks"], lines)
    assert out["attempted"] > 0 and out["failed"] == 0
    assert out["metrics"]["moves_per_s"]["value"] > 0
    assert not any("traces=" in ln and "traces=0 " not in ln
                   for ln in lines), lines
    limits = {k: c["limit"] for k, c in out["checks"].items()}
    control = out["_system"].control(out["_sample"], lambda m: None)
    assert failing(control, limits), (control, limits)


def _unchanged_backup(monkeypatch):
    """A step that returns its state unchanged: BackUp leaves the tree
    as it found it."""
    from repro.kernels import ops

    monkeypatch.setattr(ops, "backup_arena",
                        lambda cfg, arena, *a, **k: arena)


def _half_batch(monkeypatch):
    """Half of each simulation batch left out: every other row's value
    reads as 0."""
    from repro.envs import BanditValueBackend
    from repro.envs.policy_net import NNSimBackend

    def halve(v):
        n = v.shape[-1]
        keep = np.arange(n) % 2 == 0
        if isinstance(v, np.ndarray):
            return np.where(keep, v, np.float32(0.0)).astype(v.dtype)
        import jax.numpy as jnp
        return jnp.where(jnp.asarray(keep), v, jnp.float32(0.0))

    dev, host = (BanditValueBackend.evaluate_device,
                 BanditValueBackend.evaluate)
    fin = NNSimBackend.finalize
    monkeypatch.setattr(BanditValueBackend, "evaluate_device",
                        lambda self, s: halve(dev(self, s)))
    monkeypatch.setattr(BanditValueBackend, "evaluate",
                        lambda self, s: (halve(host(self, s)[0]), None))

    def finalize(self, token, states):
        v, p = fin(self, token, states)
        return halve(v), p

    monkeypatch.setattr(NNSimBackend, "finalize", finalize)


def _altered_answer(monkeypatch):
    """An answer altered where it is produced: the committed move is the
    next action over from the one the search chose."""
    from repro.core.executor import JaxExecutor

    best = JaxExecutor.best_actions

    def shifted(self):
        return (best(self) + 1) % 2

    monkeypatch.setattr(JaxExecutor, "best_actions", shifted)


@pytest.mark.parametrize("fault", [_unchanged_backup, _half_batch,
                                   _altered_answer],
                         ids=lambda f: f.__name__.strip("_"))
@pytest.mark.parametrize("cell", ["pong.selfplay", "gomoku6.selfplay"])
def test_broken_program_is_not_correct(cell, fault, monkeypatch):
    fault(monkeypatch)
    out, lines = run(cell)
    assert not out["correct"], (out["checks"], lines)
