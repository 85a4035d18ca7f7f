"""Policy-value network for Gomoku (the paper's DNN Simulation backend).

Small AlphaZero-style convnet in raw JAX (no flax): two 3x3 conv blocks,
a policy head (1x1 conv -> 36 logits) and a value head (tanh scalar).
Used by NNSimBackend (batch-p inference = the paper's "batch-1 DNN
inference per worker" aggregated across workers — the batching the paper's
Fig. 5 says would increase its speedup further) and by the self-play
training example.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

_BOARD = 6
_CELLS = _BOARD * _BOARD


def init_params(rng: jax.Array, channels: int = 32) -> dict:
    k = jax.random.split(rng, 6)
    he = jax.nn.initializers.he_normal()
    return {
        "c1": he(k[0], (3, 3, 2, channels), jnp.float32),
        "c2": he(k[1], (3, 3, channels, channels), jnp.float32),
        "pol": he(k[2], (1, 1, channels, 2), jnp.float32),
        "pol_w": he(k[3], (2 * _CELLS, _CELLS), jnp.float32),
        "val_w1": he(k[4], (channels * _CELLS, 64), jnp.float32),
        "val_w2": he(k[5], (64, 1), jnp.float32),
    }


def apply(params: dict, boards: jax.Array):
    """boards: [B, 6, 6] canonicalized (+1 = player to move).
    Returns (values [B], logits [B, 36])."""
    x = jnp.stack([(boards > 0).astype(jnp.float32),
                   (boards < 0).astype(jnp.float32)], axis=-1)  # [B,6,6,2]
    dn = jax.lax.conv_dimension_numbers(x.shape, params["c1"].shape,
                                        ("NHWC", "HWIO", "NHWC"))
    x = jax.nn.relu(jax.lax.conv_general_dilated(
        x, params["c1"], (1, 1), "SAME", dimension_numbers=dn))
    x = jax.nn.relu(jax.lax.conv_general_dilated(
        x, params["c2"], (1, 1), "SAME", dimension_numbers=dn))
    pol = jax.lax.conv_general_dilated(
        x, params["pol"], (1, 1), "SAME", dimension_numbers=dn)
    logits = pol.reshape(pol.shape[0], -1) @ params["pol_w"]
    v = jax.nn.relu(x.reshape(x.shape[0], -1) @ params["val_w1"])
    values = jnp.tanh(v @ params["val_w2"])[:, 0]
    return values, logits


@jax.jit
def policy_value_forward(params, boards):
    """The forward as one jitted program, under a stable name: a
    profile's "XLA Modules" line shows it as `jit_policy_value_forward`."""
    return apply(params, boards)


class NNSimBackend:
    """DNN inference simulation backend (paper Gomoku benchmark).

    evaluate() returns values from the player-to-move perspective and
    priors over *legal actions in legal order* (the driver's action
    indexing), padded to max_actions.

    The forward pass is exposed as a non-blocking ``dispatch``/
    ``finalize`` split (mirroring core.expand's submit/collect): dispatch
    starts the jitted forward and returns the in-flight device arrays
    without a host sync; finalize device_gets them and runs the host
    post-processing.  ``evaluate`` is dispatch + finalize back to back —
    repro.sim.server.SimServer uses the split to keep device inference in
    flight across microbatch assembly.
    """

    def __init__(self, env, params: dict):
        self.env, self.params = env, params

    def dispatch(self, states: np.ndarray):
        """Start the forward for a batch; JAX dispatch is async, so this
        returns immediately with the in-flight (values, logits) arrays."""
        B = len(states)
        boards = states[:, 3 : 3 + _CELLS].reshape(B, _BOARD, _BOARD)
        canon = boards * states[:, 0:1][:, :, None]
        return policy_value_forward(self.params,
                                    jnp.asarray(canon, jnp.float32))

    def finalize(self, token, states: np.ndarray):
        """Block on a dispatched forward and post-process: terminal rows
        get their exact game value (no priors); the rest get a masked
        softmax over legal cells, compacted into legal order.

        One vectorized numpy pass over all rows (the historical per-row
        Python loop was O(B) on the hot simulation path).  Each row's
        result is a pure function of that row alone — masked max, exp,
        and a fixed-width 36-cell row sum — which is the property the
        serving layer's bit-identity guarantees rest on (batch
        composition, caching, and padding can never change a row's
        result).  Values are unchanged from the loop; priors agree up to
        summation-grouping ulps (the loop summed the gathered legal
        values, this sums the fixed-width masked row)."""
        values, logits = jax.device_get(token)
        B = len(states)
        cells = states[:, 3 : 3 + _CELLS]
        term = states[:, 1] != 0
        legal = (cells == 0) & ~term[:, None]
        z = np.where(legal, logits, np.float32(-np.inf))
        m = z.max(axis=1)
        mm = np.where(np.isfinite(m), m, np.float32(0.0))
        ez = np.exp(z - mm[:, None])          # exact 0.0 at masked cells
        denom = ez.sum(axis=1)
        soft = ez / np.where(denom > 0, denom, np.float32(1.0))[:, None]
        pri = np.zeros((B, self.env.max_actions), np.float32)
        # scatter each legal cell's mass to its legal-order column
        pos = np.cumsum(cells == 0, axis=1) - 1
        ii, jj = np.nonzero(legal)
        pri[ii, pos[ii, jj]] = soft[ii, jj]
        w, me = states[:, 2], states[:, 0]
        tv = np.where(w == 0, np.float32(0.0),
                      np.where(w == me, np.float32(1.0), np.float32(-1.0)))
        vals = np.where(term, tv, values).astype(np.float32, copy=False)
        return vals, pri

    def evaluate(self, states: np.ndarray):
        return self.finalize(self.dispatch(states), states)
