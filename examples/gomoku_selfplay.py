"""Gomoku self-play with DNN simulation (paper benchmark b, end to end).

Replicates the paper's Gomoku setup: 6x6 board, expand-all, PUCT with a
policy-value network as the Simulation phase — then closes the loop by
training the network on the self-play targets (AlphaZero-style).

Served through the full client stack: every game is one multi-move
SearchRequest on a SearchClient, the G game slots run concurrently in
one arena, and the network runs behind the sim-serving subsystem
(repro.sim) — a SimServer microbatches all slots' inference rows into
fixed-shape batches (the paper Fig. 5 batching) at priority class
"self-play", with a transposition cache in front so re-expanded
positions skip inference entirely.

  PYTHONPATH=src python examples/gomoku_selfplay.py --games 2 --p 8
"""

import argparse

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import TreeConfig
from repro.envs import GomokuEnv
from repro.envs.policy_net import NNSimBackend, apply, init_params
from repro.launch.compile_cache import use_compile_cache
from repro.service import SearchClient, SearchRequest
from repro.sim import CachedSimBackend, SimServer

CFG = TreeConfig(X=384, F=36, D=5, beta=5.0, score_fn="puct",
                 leaf_mode="unexpanded", expand_all=True)


def play_games(env, params, n_games, p, G=4, budget=8, max_batch=64,
               cache_capacity=4096, uid_base=0):
    """Self-play n_games concurrently through one SearchClient; returns
    (states, value targets, winners) replayed from the committed moves."""
    sim = CachedSimBackend(
        SimServer(NNSimBackend(env, params), max_batch=max_batch,
                  default_priority="self-play"),
        capacity=cache_capacity)
    client = SearchClient(env, sim_backend=sim, G=G, p=p,
                          executor="faithful", default_cfg=CFG,
                          alternating_signs=True)
    try:
        handles = [client.submit(
            SearchRequest(uid=uid_base + g, seed=g, budget=budget,
                          moves=env.max_actions))
            for g in range(n_games)]
        results = [h.result() for h in handles]
    finally:
        client.close()
    buf_s, buf_z, winners = [], [], []
    for g, res in enumerate(results):
        s = env.initial_state(g)
        states, players = [], []
        for a in res.actions:
            states.append(s.copy())
            players.append(s[0])
            s, _, term = env.step(s, a)
            if term:
                break
        winner = s[2]
        buf_s += states
        buf_z += [0.0 if winner == 0 else (1.0 if pl == winner else -1.0)
                  for pl in players]
        winners.append(winner)
    return buf_s, buf_z, winners


def train_net(params, states, z, lr=1e-2, epochs=30):
    boards = np.stack([st[3:39].reshape(6, 6) * st[0] for st in states])
    targets = jnp.asarray(z, jnp.float32)

    def loss_fn(p):
        v, _ = apply(p, jnp.asarray(boards, jnp.float32))
        return jnp.mean((v - targets) ** 2)

    g = jax.jit(jax.value_and_grad(loss_fn))
    for _ in range(epochs):
        l, grads = g(params)
        params = jax.tree.map(lambda a, b: a - lr * b, params, grads)
    return params, float(l)


def main():
    use_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--games", type=int, default=2)
    ap.add_argument("--p", type=int, default=8)
    ap.add_argument("--G", type=int, default=4,
                    help="concurrent game slots per self-play round")
    ap.add_argument("--max-batch", type=int, default=64,
                    help="SimServer microbatch size")
    args = ap.parse_args()

    env = GomokuEnv()
    params = init_params(jax.random.PRNGKey(0))
    buf_s, buf_z = [], []
    for rnd in range(args.games):
        states, z, winners = play_games(
            env, params, n_games=1, p=args.p, G=args.G,
            max_batch=args.max_batch, uid_base=rnd * args.G)
        buf_s += states
        buf_z += z
        params, loss = train_net(params, buf_s, buf_z)
        print(f"game {rnd}: {len(states)} moves, "
              f"winner={winners[0]:+.0f}, value-loss={loss:.4f}")
    print("self-play loop complete")


if __name__ == "__main__":
    main()
