"""Plain reference of Gomoku 6x6, four in a row, and its policy-value
network.

State: float32[108] = [player to move (+1/-1), terminal, winner
(+1/-1/0), 36 cells row-major (0 empty, +1, -1), 69 zero words].  Action
a is the a-th empty cell in row-major order.

The network is two 3x3 convolutions (32 channels, ReLU), a policy head
(1x1 convolution to 2 channels, then a dense layer to 36 logits) and a
value head (dense 64, ReLU, dense 1, tanh), over the board seen from the
player to move.  `evaluate` turns its outputs into what the search uses:
the value of the player to move (the exact game value at a terminal
state) and a softmax over the legal cells, in legal order.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

BOARD, WIN, WORDS = 6, 4, 108
CELLS = BOARD * BOARD


def _windows() -> np.ndarray:
    """Cell indices of every run of WIN cells on the board."""
    out = []
    for r in range(BOARD):
        for c in range(BOARD):
            for dr, dc in ((0, 1), (1, 0), (1, 1), (1, -1)):
                rr, cc = r + (WIN - 1) * dr, c + (WIN - 1) * dc
                if 0 <= rr < BOARD and 0 <= cc < BOARD:
                    out.append([(r + i * dr) * BOARD + c + i * dc
                                for i in range(WIN)])
    return np.array(out)


WINDOWS = _windows()


class GomokuEnv:
    def initial_state(self, seed: int = 0) -> np.ndarray:
        s = np.zeros(WORDS, np.float32)
        s[0] = 1.0
        return s

    def num_actions(self, state) -> int:
        if state[1]:
            return 0
        return int(np.count_nonzero(state[3: 3 + CELLS] == 0))

    def step(self, state, a: int):
        s = np.array(state, np.float32, copy=True)
        if s[1]:
            raise ValueError("step on a terminal state")
        empty = np.flatnonzero(s[3: 3 + CELLS] == 0)
        cell = int(empty[a])
        player = s[0]
        s[3 + cell] = player
        board = s[3: 3 + CELLS].reshape(BOARD, BOARD)
        if wins(board, *divmod(cell, BOARD), player):
            s[1], s[2] = 1.0, player
        elif len(empty) == 1:
            s[1], s[2] = 1.0, 0.0
        s[0] = -player
        return s, bool(s[1])

    def children(self, state):
        """Every successor of a non-terminal state, in action order:
        (states [k, 108], terminal [k], legal actions [k]).  A state that
        is not terminal holds no run of WIN, so a child wins exactly when
        some run of WIN cells is all the mover's."""
        empty = np.flatnonzero(state[3: 3 + CELLS] == 0)
        k = len(empty)
        player = state[0]
        s = np.repeat(np.asarray(state, np.float32)[None], k, axis=0)
        s[np.arange(k), 3 + empty] = player
        win = (s[:, 3 + WINDOWS] == player).all(axis=2).any(axis=1)
        term = win | (k == 1)
        s[:, 1] = term
        s[:, 2] = np.where(win, player, 0.0)
        s[:, 0] = -player
        return s, term, np.where(term, 0, k - 1)


def wins(board, r: int, c: int, player) -> bool:
    """Does the stone at (r, c) complete a run of WIN or more?"""
    for dr, dc in ((0, 1), (1, 0), (1, 1), (1, -1)):
        n = 1
        for sgn in (1, -1):
            rr, cc = r + sgn * dr, c + sgn * dc
            while (0 <= rr < BOARD and 0 <= cc < BOARD
                   and board[rr, cc] == player):
                n += 1
                rr, cc = rr + sgn * dr, cc + sgn * dc
        if n >= WIN:
            return True
    return False


def init_params(key, channels: int, hidden: int):
    """He-normal weights, one key per tensor, in float32 (the served
    type)."""
    k = jax.random.split(key, 6)
    he = jax.nn.initializers.he_normal()
    return {
        "c1": he(k[0], (3, 3, 2, channels), jnp.float32),
        "c2": he(k[1], (3, 3, channels, channels), jnp.float32),
        "pol": he(k[2], (1, 1, channels, 2), jnp.float32),
        "pol_w": he(k[3], (2 * CELLS, CELLS), jnp.float32),
        "val_w1": he(k[4], (channels * CELLS, hidden), jnp.float32),
        "val_w2": he(k[5], (hidden, 1), jnp.float32),
    }


# How a product is computed: "highest" is float32 at full precision;
# "bf16x3" and "bf16" spell out the step below it that a TPU offers,
# three or one bfloat16 passes with float32 accumulation, so that they
# read the same on any device.
PASSES = {"bf16x3": ((0, 0), (0, 1), (1, 0)), "bf16": ((0, 0),)}


def _split(x):
    """x = hi + lo: hi keeps the top 16 bits of each float32 (exact in
    bfloat16), lo the rest, rounded to bfloat16.  Bit masking, not a
    round trip through bfloat16, which the compiler may fold away."""
    bits = jax.lax.bitcast_convert_type(x, jnp.uint32)
    hi = jax.lax.bitcast_convert_type(bits & jnp.uint32(0xFFFF0000),
                                      jnp.float32)
    return hi.astype(jnp.bfloat16), (x - hi).astype(jnp.bfloat16)


def _product(op, mode: str):
    """op(a, b, **kw) in the given precision, accumulated in float32."""
    if mode == "highest":
        return lambda a, b: op(a, b, precision=jax.lax.Precision.HIGHEST,
                               preferred_element_type=jnp.float32)

    def passes(a, b):
        ah, bh = _split(a), _split(b)
        return sum(op(ah[i], bh[j], preferred_element_type=jnp.float32)
                   for i, j in PASSES[mode])

    return passes


@functools.partial(jax.jit, static_argnames=("mode",))
def forward(params, boards, mode: str = "highest"):
    """boards [B, 6, 6], +1 = the player to move.  Returns (values [B],
    logits [B, 36]) in float32, every product computed as `mode` says."""
    dn = ("NHWC", "HWIO", "NHWC")
    conv = _product(functools.partial(
        jax.lax.conv_general_dilated, window_strides=(1, 1),
        padding="SAME", dimension_numbers=dn), mode)
    dot = _product(jnp.dot, mode)
    x = jnp.stack([(boards > 0), (boards < 0)], axis=-1).astype(jnp.float32)
    x = jax.nn.relu(conv(x, params["c1"]))
    x = jax.nn.relu(conv(x, params["c2"]))
    pol = conv(x, params["pol"])
    B = boards.shape[0]
    logits = dot(pol.reshape(B, -1), params["pol_w"])
    v = jax.nn.relu(dot(x.reshape(B, -1), params["val_w1"]))
    return jnp.tanh(dot(v, params["val_w2"]))[:, 0], logits


def outputs(states, values, logits):
    """Search inputs from raw network outputs: (value of the player to
    move, priors over legal cells in legal order, padded to 36)."""
    states = np.asarray(states, np.float32)
    values = np.asarray(values, np.float32)
    logits = np.asarray(logits, np.float32)
    B = len(states)
    cells = states[:, 3: 3 + CELLS]
    term = states[:, 1] != 0
    legal = (cells == 0) & ~term[:, None]
    priors = np.zeros((B, CELLS), np.float32)
    for i in range(B):
        idx = np.flatnonzero(legal[i])
        if len(idx):
            z = logits[i, idx].astype(np.float64)
            e = np.exp(z - z.max())
            priors[i, : len(idx)] = e / e.sum()
    me, w = states[:, 0], states[:, 2]
    exact = np.where(w == 0, 0.0, np.where(w == me, 1.0, -1.0))
    return np.where(term, exact, values).astype(np.float32), priors


def evaluate(params, states, mode: str = "highest", block: int = 4096):
    """Reference outputs for a batch of states, computed in blocks."""
    states = np.asarray(states, np.float32)
    vals, logs = [], []
    for lo in range(0, len(states), block):
        s = states[lo: lo + block]
        boards = (s[:, 3: 3 + CELLS] * s[:, 0:1]).reshape(-1, BOARD, BOARD)
        v, lg = forward(params, jnp.asarray(boards), mode=mode)
        vals.append(np.asarray(v))
        logs.append(np.asarray(lg))
    if not vals:
        return np.zeros(0, np.float32), np.zeros((0, CELLS), np.float32)
    return outputs(states, np.concatenate(vals), np.concatenate(logs))
