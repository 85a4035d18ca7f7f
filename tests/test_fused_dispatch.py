"""Fused K-superstep device dispatch — unit-level contracts.

The service-level bit-identity legs live in test_executor_matrix (the
fused runs against the sequential numpy oracle).  This file pins the
pieces those legs rest on:

  * the device env/sim twins are BIT-equal to their host twins — the
    splitmix hash emulated on (hi, lo) uint32 pairs, the transition
    function, and the value function whose op sequence is chosen so
    XLA's simplifier cannot rewrite it (no division by a non-power-of-2
    constant, no FMA-contractable multiply-then-subtract);
  * the capability probes gate the fused path exactly;
  * the fused program lowers as ONE compiled XLA program (the pallas
    leg compiled for a TPU v5e lives in test_tpu_compile).
"""

import numpy as np
import pytest

jax = pytest.importorskip("jax")
import jax.numpy as jnp  # noqa: E402

from repro.core import TreeConfig  # noqa: E402
from repro.core.fused import ESCAPE_NAMES, _fused_program  # noqa: E402
from repro.core.tree import init_arena  # noqa: E402
from repro.envs import BanditTreeEnv, BanditValueBackend  # noqa: E402
from repro.envs.bandit_tree import _hash_batch  # noqa: E402
from repro.envs.device import (  # noqa: E402
    has_device_env, has_device_sim, hash24_device, resolvable_device,
)

RNG = np.random.RandomState(7)


# ---------------------------------------------------------------------------
# device twins == host twins, bit for bit
# ---------------------------------------------------------------------------

def test_hash24_device_matches_numpy_hash():
    """The (hi, lo) uint32 emulation of the splitmix mix equals the
    numpy uint64 twin element-for-element over the whole input domain
    the env produces (24-bit hashes, small action codes)."""
    h = RNG.randint(0, 1 << 24, size=4096).astype(np.int64)
    for a in (0, 1, 5, 999, 4242, 7777, 12345):
        want = _hash_batch(h, a)
        got = np.asarray(jax.jit(hash24_device)(h, np.int64(a)))
        np.testing.assert_array_equal(got, want, err_msg=f"a={a}")


@pytest.mark.parametrize("varying", [False, True],
                         ids=["fixed-fanout", "varying-fanout"])
def test_step_device_matches_step_batch(varying):
    """env.step_device is a bit-exact twin of step_batch on every field
    the fused loop consumes (depth, hash, terminal, n_actions) — no
    rewards on device by contract."""
    env = BanditTreeEnv(fanout=4, terminal_depth=6, varying_fanout=varying)
    states = np.stack([env.initial_state(s) for s in range(64)])
    step_dev = jax.jit(env.step_device)
    for _ in range(6):   # walk to (past) terminal depth
        na = env.num_actions_batch(states)
        live = na > 0
        a = np.where(live, RNG.randint(0, np.maximum(na, 1)), 0)
        want_s, _, want_t = env.step_batch(states[live], a[live])
        got_s, got_t = step_dev(jnp.asarray(states), jnp.asarray(a))
        np.testing.assert_array_equal(np.asarray(got_s)[live], want_s)
        np.testing.assert_array_equal(np.asarray(got_t)[live], want_t)
        np.testing.assert_array_equal(
            np.asarray(jax.jit(env.num_actions_device)(got_s))[live],
            env.num_actions_batch(want_s))
        states = np.array(got_s)
        states[~live] = 0   # parked rows: keep the walk total


def test_evaluate_device_matches_host_bitwise():
    """The jitted value twin equals the host evaluate() BITWISE — the op
    sequence survives XLA's div-to-reciprocal rewrite and CPU FMA
    contraction (regression for both, found the hard way)."""
    env = BanditTreeEnv(fanout=4, terminal_depth=8)
    sim = BanditValueBackend()
    states = np.stack([env.initial_state(s) for s in range(2048)])
    want, _ = sim.evaluate(states)
    got = np.asarray(jax.jit(sim.evaluate_device)(jnp.asarray(states)))
    np.testing.assert_array_equal(got, want)


# ---------------------------------------------------------------------------
# capability probes
# ---------------------------------------------------------------------------

def test_capability_probes():
    env, sim = BanditTreeEnv(fanout=4), BanditValueBackend()
    assert has_device_env(env) and has_device_sim(sim)

    class HostOnlyEnv:
        def step(self, s, a): ...

    class HostOnlySim:
        def evaluate(self, s): ...

    assert not has_device_env(HostOnlyEnv())
    assert not has_device_sim(HostOnlySim())
    # no resolvable_device hook -> everything resolvable
    ok = resolvable_device(env, jnp.zeros((3, 8)), jnp.zeros(3, jnp.int32))
    assert np.asarray(ok).all()
    assert set(ESCAPE_NAMES.values()) == {"ran_k", "commit", "expand"}


# ---------------------------------------------------------------------------
# the fused program is ONE compiled XLA program
# ---------------------------------------------------------------------------

def _lower(variant, cfg):
    env = BanditTreeEnv(fanout=4, terminal_depth=10)
    sim = BanditValueBackend()
    Ge, p = 2, 3
    arena = init_arena(cfg, Ge)
    states = jnp.zeros((Ge, cfg.X) + env.state_shape, jnp.float32)
    return _fused_program.lower(
        cfg, variant, p, 4, env, sim, False,
        arena, states, jnp.ones(Ge, bool), jnp.full(Ge, 5, jnp.int32))


def test_fused_program_lowers_single_program_faithful():
    """K supersteps of select/insert/expand/simulate/finalize/backup
    lower (and compile) as one XLA program with a single while loop —
    the dispatch-boundary crossing the tentpole removes."""
    lowered = _lower("faithful", TreeConfig(X=72, F=4, D=6))
    text = lowered.as_text()
    assert "while" in text           # the fused superstep loop
    lowered.compile()                # compiles end-to-end on this host
