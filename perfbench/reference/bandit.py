"""Plain reference of the synthetic bandit tree and its value function.

The environment of the Pong configuration: a deterministic F-ary tree
whose states carry a 24-bit hash of their action history, and whose
value is a fixed function of that hash.  State: float32[8] =
[depth, hash, terminal, legal actions, 0, 0, 0, 0].
"""

from __future__ import annotations

import numpy as np

_M64 = (1 << 64) - 1


def mix(h: int, a: int) -> int:
    """splitmix-style mix of a hash and an action, cut to 24 bits so it
    is exact in a float32 state word."""
    x = (int(h) ^ ((int(a) + 0x9E3779B97F4A7C15 + (int(h) << 6)) & _M64)) \
        & _M64
    x = (x * 0xBF58476D1CE4E5B9) & _M64
    x ^= x >> 31
    return int(x & 0xFFFFFF)


class BanditEnv:
    def __init__(self, fanout: int, terminal_depth: int):
        self.F, self.terminal_depth = fanout, terminal_depth

    def _legal(self, depth: int) -> int:
        return 0 if depth >= self.terminal_depth else self.F

    def initial_state(self, seed: int) -> np.ndarray:
        s = np.zeros(8, np.float32)
        s[1] = mix(seed, 12345)
        s[3] = self._legal(0)
        return s

    def num_actions(self, state) -> int:
        return int(state[3])

    def step(self, state, a: int):
        d, h = int(state[0]), int(state[1])
        if not 0 <= a < self._legal(d):
            raise ValueError(f"illegal action {a} at depth {d}")
        h2, d2 = mix(h, a), d + 1
        term = d2 >= self.terminal_depth
        s = np.zeros(8, np.float32)
        s[0], s[1], s[2], s[3] = d2, h2, float(term), self._legal(d2)
        return s, term


def values(states) -> tuple:
    """Value in [-1, 1) of each state: (mix(hash, 4242) mod 2000 - 1000)
    / 1000, as an exact float32 subtraction and one rounded multiply."""
    v = np.array([mix(int(h), 4242) % 2000 for h in np.asarray(states)[:, 1]],
                 np.float32)
    return (v - np.float32(1000.0)) * np.float32(1e-3), None
