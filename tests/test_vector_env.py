"""VectorEnv.step_batch vs scalar env.step: bit-exact equivalence.

The expansion engine (core.expand) replaces the per-worker env.step loop
with one step_batch call and promises bit-identical trees — which holds
iff step_batch is bit-identical to looping step.  Pinned two ways:

  * seeded-random batch sweeps over every env (always run, no deps);
  * hypothesis property tests driving the same oracle over generated
    (seed, walk, action) batches (skipped when hypothesis is absent,
    as in the minimal container; CI installs it).

States are generated by random walks from initial_state — envs only
guarantee behaviour on reachable states.  Terminal and illegal-action
edges are covered explicitly: stepping a terminal state or an
out-of-range action must assert in batch mode exactly as in scalar mode.
"""

import numpy as np
import pytest

from repro.envs import (
    BanditTreeEnv, GomokuEnv, PongLiteEnv, PoolVectorEnv, has_fused_step,
    has_vector_env,
)

ENVS = {
    "bandit": lambda: BanditTreeEnv(fanout=5, terminal_depth=6),
    "bandit_varying": lambda: BanditTreeEnv(fanout=6, terminal_depth=8,
                                            varying_fanout=True),
    "gomoku": lambda: GomokuEnv(),
    "ponglite": lambda: PongLiteEnv(max_t=24),
}


def _walk(env, rng, seed, n_steps):
    """A reachable state: n random legal steps from initial_state(seed),
    stopping early at terminal."""
    s = env.initial_state(seed)
    for _ in range(n_steps):
        k = env.num_actions(s)
        if k == 0:
            break
        s, _, _ = env.step(s, int(rng.randint(k)))
    return s


def _batch_of_states(env, rng, n, max_walk=16, nonterminal=True):
    states = []
    while len(states) < n:
        s = _walk(env, rng, int(rng.randint(100)), int(rng.randint(max_walk)))
        if nonterminal and env.num_actions(s) == 0:
            continue
        states.append(s)
    return np.stack(states)


def _assert_batch_matches_scalar(env, states, actions):
    ns, rew, term = env.step_batch(states, actions)
    assert ns.dtype == states.dtype and ns.shape == states.shape
    for i in range(len(actions)):
        s2, r2, t2 = env.step(states[i], int(actions[i]))
        np.testing.assert_array_equal(ns[i], s2, err_msg=f"row {i} state")
        assert float(rew[i]) == r2, (i, float(rew[i]), r2)
        assert bool(term[i]) == t2, i
    na = env.num_actions_batch(ns)
    for i in range(len(actions)):
        assert int(na[i]) == env.num_actions(ns[i]), i


@pytest.mark.parametrize("name", sorted(ENVS))
def test_envs_implement_vector_protocol(name):
    assert has_vector_env(ENVS[name]())


@pytest.mark.parametrize("name", sorted(ENVS))
def test_step_batch_matches_scalar_random_sweep(name):
    env, rng = ENVS[name](), np.random.RandomState(sum(map(ord, name)))
    for round_ in range(4):
        states = _batch_of_states(env, rng, 64)
        actions = np.array([rng.randint(env.num_actions(s)) for s in states])
        _assert_batch_matches_scalar(env, states, actions)


@pytest.mark.parametrize("name", sorted(ENVS))
def test_terminal_transitions_match(name):
    """Batches whose rows *become* terminal: flags, rewards and the
    num_actions of the terminal successor all match the scalar path."""
    env, rng = ENVS[name](), np.random.RandomState(7)
    states = _batch_of_states(env, rng, 48, max_walk=60)
    actions = np.array([rng.randint(env.num_actions(s)) for s in states])
    ns, _, term = env.step_batch(states, actions)
    _assert_batch_matches_scalar(env, states, actions)
    na = env.num_actions_batch(ns)
    assert (na[np.asarray(term, bool)] == 0).all()


@pytest.mark.parametrize("name", sorted(ENVS))
def test_illegal_action_asserts(name):
    """Out-of-range action in any row must trip the same assertion the
    scalar env raises (expansion feeds actions straight from selection —
    an illegal index is a driver bug worth failing loudly on)."""
    env, rng = ENVS[name](), np.random.RandomState(11)
    states = _batch_of_states(env, rng, 8)
    actions = np.array([rng.randint(env.num_actions(s)) for s in states])
    actions[3] = env.num_actions(states[3])  # first illegal index
    with pytest.raises(AssertionError):
        env.step_batch(states, actions)


@pytest.mark.parametrize("name", ["gomoku", "ponglite"])
def test_stepping_terminal_state_asserts(name):
    """Envs that forbid stepping terminal states must forbid it batched."""
    env, rng = ENVS[name](), np.random.RandomState(13)
    term = None
    for seed in range(200):
        s = _walk(env, rng, seed, 120)
        if env.num_actions(s) == 0:
            term = s
            break
    assert term is not None, "no terminal state reached"
    live = _batch_of_states(env, rng, 3)
    batch = np.stack([live[0], term, live[1]])
    with pytest.raises(AssertionError):
        env.step_batch(batch, np.zeros(3, np.int64))


def test_pool_fallback_matches_scalar():
    """PoolVectorEnv serves the same protocol from scalar-env workers,
    bit-identically and in batch order."""
    env, rng = ENVS["gomoku"](), np.random.RandomState(17)
    states = _batch_of_states(env, rng, 24)
    actions = np.array([rng.randint(env.num_actions(s)) for s in states])
    with PoolVectorEnv(env, workers=2) as pv:
        ns, rew, term = pv.step_batch(states, actions)
        na = pv.num_actions_batch(ns)
    ns2, rew2, term2 = env.step_batch(states, actions)
    np.testing.assert_array_equal(ns, ns2)
    np.testing.assert_array_equal(rew, rew2)
    np.testing.assert_array_equal(term, term2)
    np.testing.assert_array_equal(na, env.num_actions_batch(ns2))


class _PlatformProbeEnv:
    """Scalar env whose action count reports the JAX platform its worker
    process runs on: 1 when pinned to the CPU backend, 0 otherwise."""

    def num_actions(self, state):
        import jax
        return int(jax.config.jax_platforms == "cpu"
                   and jax.default_backend() == "cpu")


def test_pool_workers_are_pinned_to_cpu():
    """Expansion workers never reach for the parent's accelerator: the
    pool initializer pins their JAX to the CPU backend."""
    with PoolVectorEnv(_PlatformProbeEnv(), workers=2) as pv:
        na = pv.num_actions_batch(np.zeros((4, 1)))
        assert pv._pool is not None          # the batch went to workers
    np.testing.assert_array_equal(na, np.ones(4, np.int64))


def test_pool_fused_step_matches_two_call_form():
    """step_and_count_batch (ONE pooled round-trip) is bit-identical to
    step_batch followed by num_actions_batch."""
    env, rng = ENVS["gomoku"](), np.random.RandomState(23)
    states = _batch_of_states(env, rng, 24)
    actions = np.array([rng.randint(env.num_actions(s)) for s in states])
    with PoolVectorEnv(env, workers=2) as pv:
        assert has_fused_step(pv)
        ns, rew, term, na = pv.step_and_count_batch(states, actions)
        calls_fused = pv.batch_calls
        ns2, rew2, term2 = pv.step_batch(states, actions)
        na2 = pv.num_actions_batch(ns2)
        calls_split = pv.batch_calls - calls_fused
    assert (calls_fused, calls_split) == (1, 2)   # the IPC halving
    np.testing.assert_array_equal(ns, ns2)
    np.testing.assert_array_equal(rew, rew2)
    np.testing.assert_array_equal(term, term2)
    np.testing.assert_array_equal(na, na2)
    assert na.dtype == na2.dtype


def test_pool_submit_collect_posts_states_once():
    """The overlap mode's async split: submit_batch posts the states
    across the IPC boundary ONCE and returns immediately; collect only
    reaps the worker futures (no second post).  The pair is bit-identical
    to the blocking fused call it decomposes."""
    from repro.envs.vector import has_async_step

    env, rng = ENVS["gomoku"](), np.random.RandomState(29)
    states = _batch_of_states(env, rng, 24)
    actions = np.array([rng.randint(env.num_actions(s)) for s in states])
    with PoolVectorEnv(env, workers=2) as pv:
        assert has_async_step(pv)
        pend = pv.submit_batch(states, actions)
        assert pv.batch_calls == 1      # counted at submit...
        ns, rew, term, na = pv.collect(pend)
        assert pv.batch_calls == 1      # ...and collect adds NO round-trip
        ns2, rew2, term2, na2 = pv.step_and_count_batch(states, actions)
        assert pv.batch_calls == 2      # the blocking wrapper: one more
    np.testing.assert_array_equal(ns, ns2)
    np.testing.assert_array_equal(rew, rew2)
    np.testing.assert_array_equal(term, term2)
    np.testing.assert_array_equal(na, na2)
    assert na.dtype == na2.dtype


def test_pool_submit_overlaps_with_host_work():
    """Two batches can be in flight at once: submitting B before
    collecting A neither blocks nor perturbs either result — the
    property the gang pipeline's expansion overlap rests on."""
    env, rng = ENVS["gomoku"](), np.random.RandomState(31)
    sa = _batch_of_states(env, rng, 16)
    aa = np.array([rng.randint(env.num_actions(s)) for s in sa])
    sb = _batch_of_states(env, rng, 16)
    ab = np.array([rng.randint(env.num_actions(s)) for s in sb])
    with PoolVectorEnv(env, workers=2) as pv:
        pa = pv.submit_batch(sa, aa)
        pb = pv.submit_batch(sb, ab)         # in flight alongside pa
        ra = pv.collect(pa)
        rb = pv.collect(pb)
        assert pv.batch_calls == 2
    for got, (s, a) in ((ra, (sa, aa)), (rb, (sb, ab))):
        ns, rew, term = env.step_batch(s, a)
        np.testing.assert_array_equal(got[0], ns)
        np.testing.assert_array_equal(got[1], rew)
        np.testing.assert_array_equal(got[2], term)
        np.testing.assert_array_equal(got[3], env.num_actions_batch(ns))


def test_engine_pool_mode_uses_one_round_trip_per_superstep():
    """The expansion engine drives the fused call: one pooled batch
    round-trip per expand(), not two (ROADMAP PR 3 limit resolved)."""
    from repro.core import StateTable, TreeConfig
    from repro.core.expand import ExpansionEngine
    from repro.core.tree import NULL

    env = ENVS["bandit"]()
    eng = ExpansionEngine(env, "pool")
    try:
        st_ = StateTable(32, env.state_shape, env.state_dtype)
        s0 = env.initial_state(0)
        st_.flush(s0)
        p, cfg = 2, TreeConfig(X=32, F=5, D=6)
        sel = {
            "leaves": np.zeros(p, np.int32),
            "expand_action": np.array([0, 1], np.int32),
            "n_insert": np.ones(p, np.int32),
            "insert_base": np.array([1, 2], np.int32),
        }
        new_nodes = np.full((p, cfg.Fp), NULL, np.int32)
        new_nodes[:, 0] = [1, 2]
        eng.expand([(0, st_, sel, new_nodes)])
        assert eng._venv.batch_calls == 1
    finally:
        eng.close()


# --------------------------------------------------------------------------
# hypothesis property tests — only this section needs hypothesis (absent in
# the minimal container, installed in CI); the sweeps above always run
# --------------------------------------------------------------------------

try:
    from hypothesis import given, settings, strategies as st
    _HAVE_HYPOTHESIS = True
except ImportError:
    _HAVE_HYPOTHESIS = False


def _materialize(env, spec):
    """Turn a generated spec into a (states, actions) batch of rows with
    at least one legal action each; None when the spec yields nothing."""
    rng = np.random.RandomState(0)
    states, actions = [], []
    for seed, walk, apick in spec:
        s = _walk(env, rng, seed, walk)
        k = env.num_actions(s)
        if k == 0:
            continue
        states.append(s)
        actions.append(apick % k)
    if not states:
        return None
    return np.stack(states), np.asarray(actions, np.int64)


if _HAVE_HYPOTHESIS:
    _walk_spec = st.lists(
        st.tuples(st.integers(0, 99),       # initial_state seed
                  st.integers(0, 24),       # walk length
                  st.integers(0, 10**6)),   # action pick (mod legal count)
        min_size=1, max_size=24)

    @pytest.mark.parametrize("name", sorted(ENVS))
    @given(spec=_walk_spec)
    @settings(max_examples=40, deadline=None)
    def test_property_step_batch_equivalence(name, spec):
        env = ENVS[name]()
        batch = _materialize(env, spec)
        if batch is None:
            return
        _assert_batch_matches_scalar(env, *batch)
else:
    @pytest.mark.skip(reason="hypothesis not installed (CI runs this)")
    def test_property_step_batch_equivalence():
        pass
