"""Edge scores are the same integers on every backend (core/scoring.py).

Claims:
  * `nearest_quotient` / `nearest_root` turn a float32 quotient or root
    up to two ulps off into the nearest one, under jit and inside a
    Pallas kernel (interpreted here), as a TPU's divide and sqrt need;
  * jitted UCT and PUCT scores equal numpy's bit for bit on random
    counts, sums and priors: XLA's reassociation of a product with a
    broadcast scalar does not reach the PUCT numerator.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.experimental import pallas as pl

from repro.core import scoring
from repro.core.tree import TreeConfig

SHAPE = (64, 128)


def _ulps(v, k):
    """v moved k ulps (k < 0: down)."""
    for _ in range(abs(k)):
        v = np.nextafter(v, np.float32(np.inf if k > 0 else -np.inf))
    return v.astype(np.float32)


def _operands(seed):
    rng = np.random.default_rng(seed)
    ne = rng.integers(1, 4000, SHAPE).astype(np.float32)
    # a Qm.16 sum over a count, as the score's Q term divides
    a = ((rng.uniform(-1, 1, SHAPE) * ne * 65536).astype(np.int32)
         .astype(np.float32) * np.float32(2 ** -16))
    x = rng.integers(0, 2 ** 20, SHAPE).astype(np.float32)
    x[0, :8] = [0, 1, 2, 3, 4, 96003, 96004, 2 ** 20 - 1]
    return a, ne, x


def _jit(fn):
    return jax.jit(fn)


def _kernel(fn):
    def body(*refs):
        *ins, out = refs
        out[...] = fn(*(r[...] for r in ins))
    return pl.pallas_call(body, interpret=True,
                          out_shape=jax.ShapeDtypeStruct(SHAPE, jnp.float32))


@pytest.mark.parametrize("wrap", [_jit, _kernel], ids=["jit", "pallas"])
@pytest.mark.parametrize("k", [-2, -1, 0, 1, 2])
def test_nearest_quotient_and_root_from_two_ulps_off(wrap, k):
    a, ne, x = _operands(seed=3 + k)
    quot = wrap(lambda a, b, q: scoring.nearest_quotient(a, b, q, jnp))
    root = wrap(lambda x, s: scoring.nearest_root(x, s, jnp))
    want_q, want_s = a / ne, np.sqrt(x)
    start_s = np.where(x == 0, np.float32(0), _ulps(want_s, k))
    np.testing.assert_array_equal(
        np.asarray(quot(a, ne, _ulps(want_q, k))), want_q)
    np.testing.assert_array_equal(np.asarray(root(x, start_s)), want_s)


@pytest.mark.parametrize("score_fn,beta", [("puct", 5.0), ("uct", 1.4)])
def test_jitted_scores_equal_numpy(score_fn, beta):
    cfg = TreeConfig(X=48_000, F=36, D=5, beta=beta, vl_mode="wu",
                     score_fn=score_fn, leaf_mode="unexpanded",
                     expand_all=score_fn == "puct")
    rng = np.random.default_rng(11)
    R = SHAPE[0] * 4
    edge_N = rng.integers(0, 400, (R, 128)).astype(np.int32)
    edge_VL = rng.integers(0, 3, (R, 128)).astype(np.int32)
    edge_W = (rng.uniform(-1, 1, (R, 128)) * (edge_N + edge_VL)
              * 65536).astype(np.int32)
    edge_P = rng.integers(0, 65536 // 8, (R, 128)).astype(np.int32)
    node_N = rng.integers(0, 20_000, (R, 1)).astype(np.int32)
    node_O = rng.integers(0, 16, (R, 1)).astype(np.int32)
    log_ns = np.log(np.maximum(node_N + node_O, 1)).astype(np.float32)
    child = np.where(rng.random((R, 128)) < 0.9, 1, -1).astype(np.int32)
    num_actions = rng.integers(100, 129, (R, 1)).astype(np.int32)

    def scores(xp, *arrays):
        n, vl, w, p, nn, no, ln, c, na = arrays
        return scoring.edge_scores_fx(
            cfg, child=c, edge_N=n, edge_W=w, edge_VL=vl, edge_P=p,
            node_N=nn, node_O=no, num_actions=na, xp=xp, log_ns=ln)

    arrays = (edge_N, edge_VL, edge_W, edge_P, node_N, node_O, log_ns,
              child, num_actions)
    got = jax.jit(lambda *a: scores(jnp, *a))(*arrays)
    np.testing.assert_array_equal(np.asarray(got), scores(np, *arrays))
