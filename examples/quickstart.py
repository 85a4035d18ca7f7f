"""Quickstart: Tree-Parallel MCTS with the accelerated in-tree operations.

Builds the paper's system (Fig. 2) on a deterministic toy environment:
p parallel workers, UCT statistics on the accelerator (batched jit ops —
swap executor="pallas" for the Pallas kernels), environment states in the
host State Table, BSP supersteps, one full MCTS step with Tree Flush.

  PYTHONPATH=src python examples/quickstart.py
"""

import numpy as np

from repro.core import TreeConfig, TreeParallelMCTS, RolloutBackend
from repro.envs import BanditTreeEnv
from repro.launch.compile_cache import use_compile_cache


def main():
    use_compile_cache()
    env = BanditTreeEnv(fanout=6, terminal_depth=12)
    cfg = TreeConfig(
        X=1024,          # node budget per MCTS step (tree-flush boundary)
        F=6,             # fanout = action-space size
        D=9,             # tree height limit
        vl_mode="wu",    # WU-UCT visit-count virtual loss (paper default)
    )
    sim = RolloutBackend(env, max_steps=32, seed=0)

    mcts = TreeParallelMCTS(cfg, env, sim, p=16, executor="faithful")
    total = 0.0
    for step in range(5):
        action, reward, terminal = mcts.run_step(max_supersteps=30)
        total += reward
        s = mcts.stats
        print(f"step {step}: action={action} reward={reward:+.3f} "
              f"supersteps={s.supersteps} "
              f"intree={s.t_intree:.3f}s sim={s.t_sim:.3f}s")
        if terminal:
            break
    print(f"total reward: {total:+.3f}")


if __name__ == "__main__":
    main()
