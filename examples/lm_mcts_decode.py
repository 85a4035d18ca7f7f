"""Token-level MCTS decoding with an LM simulation backend, fully served.

The paper's Gomoku benchmark replaces rollouts with DNN inference; this
example pushes that to its modern conclusion: the simulation backend is a
language model's serve path, and MCTS plans over next-token actions —
the tree machinery (UCT on accelerator, ST on host) is untouched.

The workload runs through the production stack end to end: the decode is
one multi-move SearchRequest on a SearchClient at priority class
"interactive", tokens stream out of SearchHandle.moves() as each reroot
commits, and simulation batches flow through repro.sim — a SimServer
microbatches the tree's leaf rows, and LMContinuationBackend scores each
row's greedy continuation by mean token log-prob, decoding ALL rows
concurrently through one ContinuousBatcher pool (serving/batcher.py)
instead of the historical per-row forward loop.

  PYTHONPATH=src python examples/lm_mcts_decode.py --tokens 6
"""

import argparse

import jax

from repro import configs
from repro.core import TreeConfig
from repro.launch.compile_cache import use_compile_cache
from repro.models import lm
from repro.service import SearchClient, SearchRequest
from repro.sim import LMContinuationBackend, LMTreeEnv, SimServer


def main():
    use_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="llama3.2-1b")
    ap.add_argument("--tokens", type=int, default=6)
    ap.add_argument("--p", type=int, default=4)
    ap.add_argument("--pool-size", type=int, default=8,
                    help="ContinuousBatcher decode pool (LM microbatch)")
    args = ap.parse_args()

    cfg = configs.get_config(args.arch, smoke=True)
    params = lm.init_params(cfg, jax.random.PRNGKey(0))
    env = LMTreeEnv(cfg, params)
    sim = SimServer(LMContinuationBackend(env, pool_size=args.pool_size),
                    max_batch=args.p, default_priority="interactive")
    tree_cfg = TreeConfig(X=96, F=env.F, D=4)

    with SearchClient(env, sim_backend=sim, G=1, p=args.p,
                      executor="faithful", default_cfg=tree_cfg) as client:
        handle = client.submit(SearchRequest(
            uid=0, seed=0, budget=8, moves=args.tokens))
        state = env.initial_state(0)
        seq = [int(state[1])]
        for ev in handle.moves():
            state, _, term = env.step(state, ev.action)
            seq.append(int(state[int(state[0])]))
            print(f"token {ev.move_index}: planned action {ev.action}; "
                  f"sequence so far {seq}")
            if term:
                break
    print("decoded:", seq)


if __name__ == "__main__":
    main()
