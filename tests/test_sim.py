"""Tests for the simulation serving subsystem (repro.sim).

Pins the invariants the serving stack's bit-identity guarantees rest on:

  * NNSimBackend's vectorized evaluate matches a per-row reference with
    the same masked-softmax semantics, bit for bit, and each row's
    result is independent of batch composition;
  * SimServer returns the same per-row results regardless of how rows
    were split across submits / padded / coalesced, packs microbatches
    in priority order, and genuinely defers finalize to collect();
  * SimCache hits are bit-identical to the cold evaluate that populated
    them, the LRU bound holds, and hit/miss/evict counters land in the
    registry;
  * LMContinuationBackend is deterministic and pool-size invariant.
"""

import numpy as np
import pytest

from repro.envs import GomokuEnv
from repro.obs.metrics import MetricsRegistry
from repro.sim import (CachedSimBackend, PRIORITY_CLASSES, SimCache,
                       SimServer)

pytestmark = pytest.mark.filterwarnings("ignore::DeprecationWarning")


# ---------------------------------------------------------------- helpers

def _gomoku_states(n, seed=0, max_plies=36):
    """n mid-game Gomoku states from random playouts (terminal rows kept:
    the backend's terminal-override path must be exercised too)."""
    env = GomokuEnv()
    rng = np.random.default_rng(seed)
    out = []
    while len(out) < n:
        s = env.initial_state(0)
        for _ in range(int(rng.integers(0, max_plies + 1))):
            k = env.num_actions(s)
            if k == 0:
                break
            s, _, _ = env.step(s, int(rng.integers(k)))
        out.append(s)
    return np.stack(out)


@pytest.fixture(scope="module")
def nn_backend():
    import jax

    from repro.envs.policy_net import NNSimBackend, init_params

    env = GomokuEnv()
    return NNSimBackend(env, init_params(jax.random.PRNGKey(0), channels=8))


class _RecordingBackend:
    """evaluate-only fake: records every batch it sees; value = row sum
    (a pure per-row function, so padding/composition cannot leak)."""

    def __init__(self, n_actions=0):
        self.batches = []
        self.n_actions = n_actions

    def evaluate(self, states):
        states = np.asarray(states)
        self.batches.append(states.copy())
        vals = states.sum(axis=1).astype(np.float32)
        if not self.n_actions:
            return vals, None
        pri = np.tile(vals[:, None], (1, self.n_actions)).astype(np.float32)
        return vals, pri


class _SplitBackend(_RecordingBackend):
    """dispatch/finalize fake: counts phase transitions so tests can pin
    that SimServer dispatches on submit and finalizes only at collect."""

    def __init__(self, n_actions=0):
        super().__init__(n_actions)
        self.dispatched = 0
        self.finalized = 0

    def dispatch(self, states):
        self.dispatched += 1
        return np.asarray(states).copy()

    def finalize(self, token, states):
        self.finalized += 1
        return super().evaluate(states)

    def evaluate(self, states):  # pragma: no cover - split is preferred
        raise AssertionError("server should use the dispatch/finalize split")


# ------------------------------------------------- NNSimBackend semantics

def test_vectorized_evaluate_matches_rowwise_reference(nn_backend):
    """The one-pass numpy evaluate == a per-row reference with identical
    masked-softmax semantics (fixed-width 36-cell reductions)."""
    import jax

    from repro.envs.policy_net import policy_value_forward

    states = _gomoku_states(48, seed=1)
    vals, pris = nn_backend.evaluate(states)

    values, logits = jax.device_get(
        policy_value_forward(
            nn_backend.params,
            np.asarray([st[3:39].reshape(6, 6) * st[0] for st in states],
                       np.float32)))
    for i, st in enumerate(states):
        term = st[1] != 0
        if term:
            w, me = st[2], st[0]
            exp_v = np.float32(0.0 if w == 0 else (1.0 if w == me else -1.0))
            exp_p = np.zeros(36, np.float32)
        else:
            exp_v = np.float32(values[i])
            legal = st[3:39] == 0
            z = np.where(legal, logits[i], np.float32(-np.inf))
            ez = np.exp(z - z.max())
            soft = ez / ez.sum()
            exp_p = np.zeros(36, np.float32)
            exp_p[: legal.sum()] = soft[legal]
        assert vals[i] == exp_v, i
        np.testing.assert_array_equal(pris[i], exp_p, err_msg=str(i))


def test_evaluate_row_independent_of_batch_composition(nn_backend):
    states = _gomoku_states(16, seed=2)
    vals, pris = nn_backend.evaluate(states)
    perm = np.random.default_rng(0).permutation(len(states))
    pvals, ppris = nn_backend.evaluate(states[perm])
    np.testing.assert_array_equal(pvals, vals[perm])
    np.testing.assert_array_equal(ppris, pris[perm])


# ------------------------------------------------------------- SimServer

def test_server_split_submits_match_one_shot(nn_backend):
    states = _gomoku_states(24, seed=3)
    ref_v, ref_p = nn_backend.evaluate(states)

    srv = SimServer(nn_backend, max_batch=8)
    t1 = srv.submit(states[:5])
    t2 = srv.submit(states[5:16])
    t3 = srv.submit(states[16:])
    for t, sl in ((t1, slice(0, 5)), (t2, slice(5, 16)), (t3, slice(16, 24))):
        v, p = srv.collect(t)
        np.testing.assert_array_equal(v, ref_v[sl])
        np.testing.assert_array_equal(p, ref_p[sl])


def test_server_pads_partial_batches_to_fixed_shape():
    be = _RecordingBackend()
    srv = SimServer(be, max_batch=8)
    states = np.arange(3 * 4, dtype=np.float32).reshape(3, 4)
    v, p = srv.collect(srv.submit(states))
    assert p is None
    np.testing.assert_array_equal(v, states.sum(axis=1))
    (batch,) = be.batches
    assert batch.shape == (8, 4)                      # padded to max_batch
    np.testing.assert_array_equal(batch[3:], np.tile(states[0], (5, 1)))


def test_server_priority_order_within_microbatch():
    be = _RecordingBackend()
    srv = SimServer(be, max_batch=16)
    rows = {c: np.full((2, 3), i, np.float32)
            for i, c in enumerate(PRIORITY_CLASSES)}
    # submit in REVERSE priority order; the flush must reorder
    tickets = {c: srv.submit(rows[c], priority=c)
               for c in reversed(PRIORITY_CLASSES)}
    srv.collect(tickets["interactive"])
    (batch,) = be.batches
    np.testing.assert_array_equal(
        batch[:6], np.concatenate([rows[c] for c in PRIORITY_CLASSES]))
    for c in PRIORITY_CLASSES:                         # all rows landed
        v, _ = srv.collect(tickets[c])
        np.testing.assert_array_equal(v, rows[c].sum(axis=1))


def test_server_dispatches_on_submit_finalizes_on_collect():
    be = _SplitBackend()
    srv = SimServer(be, max_batch=4)
    t = srv.submit(np.ones((9, 2), np.float32))       # 2 full batches + 1
    assert (be.dispatched, be.finalized) == (2, 0)
    srv.collect(t)                                    # partial flush + finalize
    assert (be.dispatched, be.finalized) == (3, 3)


def test_server_rejects_unknown_priority_and_double_collect():
    srv = SimServer(_RecordingBackend(), max_batch=4)
    with pytest.raises(ValueError, match="priority"):
        srv.submit(np.zeros((1, 2), np.float32), priority="bulk")
    with pytest.raises(ValueError, match="priority"):
        SimServer(_RecordingBackend(), default_priority="bulk")
    t = srv.submit(np.zeros((2, 2), np.float32))
    srv.collect(t)
    t.filled = 0                                      # forged ticket
    with pytest.raises(RuntimeError, match="collect"):
        srv.collect(t)


def test_server_metrics():
    reg = MetricsRegistry()
    srv = SimServer(_RecordingBackend(), max_batch=4, metrics=reg)
    srv.collect(srv.submit(np.zeros((6, 2), np.float32)))
    assert reg.get("sim_server_batches_total").value == 2
    assert reg.get("sim_server_rows_total", priority="batch").value == 6
    assert reg.get("sim_server_partial_flushes_total").value == 1
    assert reg.get("sim_server_queue_depth").value == 0


# -------------------------------------------------------------- SimCache

def test_cache_lru_bound_and_eviction_counter():
    reg = MetricsRegistry()
    cache = SimCache(capacity=4, metrics=reg)
    keys = [SimCache.key(np.full(3, i, np.float32)) for i in range(6)]
    for i, k in enumerate(keys):
        cache.put(k, float(i), None)
    assert len(cache) == 4
    assert reg.get("sim_cache_evictions_total").value == 2
    assert cache.get(keys[0]) is None and cache.get(keys[1]) is None
    assert cache.get(keys[2]) == (np.float32(2.0), None)
    cache.put(SimCache.key(np.full(3, 9, np.float32)), 9.0, None)
    # key 2 was just touched -> key 3 is now the LRU victim
    assert cache.get(keys[3]) is None
    assert cache.get(keys[2]) is not None
    assert reg.get("sim_cache_entries").value == 4


def test_cached_backend_warm_hits_bit_identical(nn_backend):
    states = _gomoku_states(16, seed=4)
    ref_v, ref_p = nn_backend.evaluate(states)

    reg = MetricsRegistry()
    cached = CachedSimBackend(SimServer(nn_backend, max_batch=8),
                              capacity=64, metrics=reg)
    cold_v, cold_p = cached.evaluate(states)
    warm_v, warm_p = cached.evaluate(states)
    for v, p in ((cold_v, cold_p), (warm_v, warm_p)):
        np.testing.assert_array_equal(v, ref_v)
        np.testing.assert_array_equal(p, ref_p)
    assert reg.get("sim_cache_misses_total").value == 16
    assert reg.get("sim_cache_hits_total").value == 16


def test_cached_backend_mixed_hit_miss_batch():
    be = _RecordingBackend(n_actions=2)
    cached = CachedSimBackend(be, capacity=64)
    a = np.arange(8, dtype=np.float32).reshape(4, 2)
    cached.evaluate(a)
    b = np.arange(4, 12, dtype=np.float32).reshape(4, 2)  # rows 0,1 cached
    v, p = cached.evaluate(b)
    np.testing.assert_array_equal(v, b.sum(axis=1))
    np.testing.assert_array_equal(p, np.tile(v[:, None], (1, 2)))
    assert len(be.batches) == 2
    assert be.batches[1].shape == (2, 2)              # only the misses went in


# -------------------------------------------- LM continuation determinism

def test_lm_backend_deterministic_and_pool_invariant():
    import jax

    from repro import configs
    from repro.models import lm
    from repro.sim import LMContinuationBackend, LMTreeEnv

    cfg = configs.get_config("llama3.2-1b", smoke=True)
    params = lm.init_params(cfg, jax.random.PRNGKey(0))
    env = LMTreeEnv(cfg, params, fanout=4, horizon=2)
    states = np.stack([env.initial_state(s) for s in range(5)])

    ref, _ = LMContinuationBackend(env, pool_size=4).evaluate(states)
    again, _ = LMContinuationBackend(env, pool_size=4).evaluate(states)
    np.testing.assert_array_equal(again, ref)
    # NOTE: pool_size is NOT composition-free — the LM forward's batch
    # shape changes its reductions, which can flip a greedy argmax and
    # take a different continuation.  The serving guarantee is fixed-
    # config determinism (pinned above), not pool-size invariance.
    reuse = LMContinuationBackend(env, pool_size=4)
    first, _ = reuse.evaluate(states)
    second, _ = reuse.evaluate(states)           # batcher state fully drains
    np.testing.assert_array_equal(first, ref)
    np.testing.assert_array_equal(second, ref)
