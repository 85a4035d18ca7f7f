"""Fig. 4 analogue: in-tree operation latency per MCTS iteration vs p.

Paper: FPGA accelerator vs CPU master process, Pong (F=6, D=9) and Gomoku
(F=36, D=5), p in 8..128.  Here: batched-jit accelerator (+ wavefront
beyond-paper variant + the arena-native Pallas kernels) vs the sequential
CPU reference.  The simulation backend is a null stub so only in-tree time
(Selection + Expansion tree-half + BackUp + transfers + ST) is measured,
exactly the paper's Fig. 4 metric.  The kernels run compiled on a TPU
and in the Pallas interpreter on the CPU backend (kernels/ops.py picks by
platform), so a CPU run's kernel numbers time the interpreter, not the
chip.
"""

from __future__ import annotations

from benchmarks.common import NullSim, csv_line, run_supersteps
from repro.core import TreeConfig
from repro.envs import BanditTreeEnv

# reduced X keeps the CPU reference tractable; F/D are the paper's.
PONG = TreeConfig(X=4096, F=6, D=9)
GOMOKU = TreeConfig(X=4096, F=36, D=5, beta=5.0, score_fn="puct",
                    leaf_mode="unexpanded", expand_all=True)

EXECUTORS = ("reference", "faithful", "wavefront", "pallas")


def run(n_steps=6, ps=(8, 32, 128), smoke: bool = False):
    rows = []
    benches = (("pong", PONG, 6, 12), ("gomoku", GOMOKU, 36, 8))
    if smoke:
        n_steps, ps = 2, (4,)
        benches = (("pong", TreeConfig(X=256, F=6, D=9), 6, 12),)
    for bench, cfg, fanout, depth in benches:
        env = BanditTreeEnv(fanout=fanout, terminal_depth=depth)
        for p in ps:
            base = None
            for ex in EXECUTORS:
                stats, _ = run_supersteps(cfg, env, NullSim(), p, ex, n_steps)
                us = stats.t_intree / stats.supersteps * 1e6
                if ex == "reference":
                    base = us
                speedup = base / us if base else 1.0
                csv_line(f"fig4_intree_{bench}_p{p}_{ex}", us,
                         f"speedup_vs_cpu={speedup:.2f}")
                rows.append((bench, p, ex, us, speedup))
    return rows


if __name__ == "__main__":
    run()
