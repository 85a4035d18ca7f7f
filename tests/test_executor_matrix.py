"""Cross-executor differential harness.

One randomized service schedule — oversubscribed admissions, staggered
evictions, multi-move requests advancing via reroot — is replayed through
EVERY in-tree executor x {masked, compacted} x {loop, vector, pool}
expansion and compared per slot, bit for bit.

Two claims, split by executor class:

  * bit-compatible executors (reference / faithful / pallas) must
    reproduce the sequential numpy oracle exactly under every combo;
  * relaxed/wavefront change intra-superstep semantics BY DESIGN (they
    diverge from the oracle), but compaction and the expansion engine are
    still required to be pure transforms: every combo must equal that
    executor's own masked/loop run bit for bit.

The executor axis is EXECUTOR_NAMES from core.executor, so a newly
registered executor is enrolled in the whole matrix automatically — a new
name shows up here (and must declare itself in BIT_COMPATIBLE if it
claims oracle equality).

The SearchClient rides the same harness: the schedule submitted through
the handle API (config-carrying requests, persistent compaction
sessions, round-robin policy) must equal each executor's direct
ArenaPool run, drained per handle or through SchedulerCore.run (the
client/core/pool split and session write-back deferral are pure
re-layerings, never semantic changes), and the cross-pool fused evaluate
path (weighted-queue-depth gang ticks batching a >= 3-config mix into ONE
SimulationBackend.evaluate per tick) must equal dedicated single-config
runs per request while its fused batches strictly exceed any single
pool's share.
"""

import numpy as np
import pytest

from repro.core import TreeConfig
from repro.core.executor import EXECUTOR_NAMES
from repro.envs import BanditTreeEnv, BanditValueBackend
from repro.service import ArenaPool, SearchClient, SearchRequest

CFG = TreeConfig(X=160, F=4, D=6)
ENV = BanditTreeEnv(fanout=4, terminal_depth=10)
G, P = 3, 4

# Executors whose per-slot arithmetic is bit-compatible with the
# sequential numpy oracle.  relaxed/wavefront are intentionally absent
# (documented intra-superstep semantics change); everything else MUST be
# listed — a new executor that skips this list still gets the
# self-consistency matrix but not the oracle gate.
BIT_COMPATIBLE = ("reference", "faithful", "pallas")

ORACLE = ("reference", 0.0, "loop")  # the paper's CPU-only master process


def _schedule(seed=42, n=6):
    """Randomized but reproducible request mix: oversubscribed (n > G),
    staggered budgets (uneven eviction), multi-move (reroot path)."""
    rng = np.random.RandomState(seed)
    reqs = [dict(uid=i, seed=int(rng.randint(100)),
                 budget=int(rng.randint(2, 5)),
                 moves=int(rng.randint(1, 3)),
                 keep_tree=True) for i in range(n)]
    # a long tail: the last request outlives the rest, so occupancy
    # decays through 2/G and 1/G and the compacted path really runs
    reqs[-1].update(budget=6, moves=2)
    return reqs


_SCHEDULE = _schedule()
_RESULTS: dict = {}


def _run(executor: str, compact: float, expansion: str):
    key = (executor, compact, expansion)
    if key in _RESULTS:
        return _RESULTS[key]
    svc = ArenaPool(CFG, ENV, BanditValueBackend(), G=G, p=P,
                    executor=executor, compact_threshold=compact,
                    expansion=expansion)
    try:
        for kw in _SCHEDULE:
            svc.submit(SearchRequest(**kw))
        done = {r.uid: r for r in svc.run()}
    finally:
        svc.close()
    assert sorted(done) == [kw["uid"] for kw in _SCHEDULE]
    if compact > 0.0:
        # the combo must actually exercise the compacted path: the tail
        # of the schedule drains occupancy below the threshold
        assert svc.stats.compacted_supersteps > 0
    _RESULTS[key] = (done, svc.stats.supersteps)
    return _RESULTS[key]


def _assert_identical(got, want, label):
    done_a, steps_a = got
    done_b, steps_b = want
    assert steps_a == steps_b, f"{label}: superstep counts diverged"
    for uid in want[0]:
        a, b = done_a[uid], done_b[uid]
        assert a.actions == b.actions, f"{label} uid={uid}"
        assert a.rewards == b.rewards, f"{label} uid={uid}"
        assert a.supersteps == b.supersteps, f"{label} uid={uid}"
        for va, vb in zip(a.visit_counts, b.visit_counts):
            np.testing.assert_array_equal(va, vb,
                                          err_msg=f"{label} uid={uid}")
        for k in b.tree_snapshot:
            np.testing.assert_array_equal(
                a.tree_snapshot[k], b.tree_snapshot[k],
                err_msg=f"{label} uid={uid} field={k}")


@pytest.mark.parametrize("executor", EXECUTOR_NAMES)
@pytest.mark.parametrize("compact", [0.0, 0.7], ids=["masked", "compacted"])
@pytest.mark.parametrize("expansion", ["loop", "vector"])
def test_matrix_self_consistency(executor, compact, expansion):
    """Compaction and the expansion engine are pure transforms for every
    executor: each combo equals the executor's masked/loop run."""
    _assert_identical(
        _run(executor, compact, expansion),
        _run(executor, 0.0, "loop"),
        f"{executor}/{'compacted' if compact else 'masked'}/{expansion}")


@pytest.mark.parametrize("executor", [e for e in EXECUTOR_NAMES
                                      if e in BIT_COMPATIBLE])
@pytest.mark.parametrize("compact", [0.0, 0.7], ids=["masked", "compacted"])
@pytest.mark.parametrize("expansion", ["loop", "vector"])
def test_matrix_matches_sequential_oracle(executor, compact, expansion):
    """Acceptance: every bit-compatible executor x compaction x expansion
    combo reproduces the sequential numpy oracle per slot, bit for bit."""
    _assert_identical(
        _run(executor, compact, expansion),
        _run(*ORACLE),
        f"{executor} vs oracle")


@pytest.mark.parametrize("executor", EXECUTOR_NAMES)
@pytest.mark.parametrize("drain", ["handles", "core.run"])
def test_client_round_trip_matches_direct_service(executor, drain):
    """Acceptance: the SearchClient handle API (round-robin policy) is a
    pure re-surfacing — the matrix schedule submitted through the client
    (requests carrying their TreeConfig, persistent compaction sessions
    on) and drained either with result() on every handle or with
    SchedulerCore.run equals the executor's own direct ArenaPool
    masked/loop run, superstep counts included — and therefore,
    transitively, the sequential oracle for every BIT_COMPATIBLE
    executor."""
    cl = SearchClient(ENV, BanditValueBackend(), G=G, p=P,
                      executor=executor, default_cfg=CFG,
                      compact_threshold=0.7, persistent_compaction=True)
    try:
        handles = [cl.submit(SearchRequest(cfg=CFG, **kw))
                   for kw in _SCHEDULE]
        if drain == "handles":
            done = {h.uid: h.result() for h in handles}
        else:
            done = {r.uid: r for r in cl.core.run()}
        stats = cl.stats
    finally:
        cl.close()
    assert all(h.status() == "done" for h in handles)
    assert len(cl.core.pools) == 1   # one config -> one bucket
    # the drain tail compacts, and sessions persist across supersteps
    # instead of re-gathering each one
    assert stats.compacted_supersteps > 0
    assert stats.session_gathers < stats.compacted_supersteps
    assert stats.session_reuses > 0
    _assert_identical((done, stats.supersteps), _run(executor, 0.0, "loop"),
                      f"client/{drain}/{executor}")


# three shape classes for the cross-pool fusion acceptance: same fanout
# (the env fixes F), different arena/depth classes
XPOOL_CFGS = (CFG, TreeConfig(X=128, F=4, D=5), TreeConfig(X=96, F=4, D=4))


@pytest.mark.parametrize("executor", EXECUTOR_NAMES)
def test_client_xpool_fused_matches_dedicated_services(executor):
    """Acceptance: cross-pool fused evaluate (ONE SimulationBackend
    .evaluate spanning every advancing pool of a 3-config heterogeneous
    mix) is bit-identical per request to dedicated single-config runs,
    and the fused batch strictly exceeds the largest single-pool share."""
    reqs = [dict(uid=i, seed=50 + i, budget=3, moves=1 + i % 2,
                 keep_tree=True) for i in range(6)]
    cl = SearchClient(ENV, BanditValueBackend(), G=2, p=P,
                      executor=executor, policy="weighted-queue-depth")
    try:
        handles = [cl.submit(SearchRequest(cfg=XPOOL_CFGS[i % 3], **kw))
                   for i, kw in enumerate(reqs)]
        done = {h.uid: h.result() for h in handles}
        assert cl.core.xpool_batches > 0
        assert cl.core.xpool_rows_max > cl.core.xpool_pool_rows_max > 0
    finally:
        cl.close()
    for i, kw in enumerate(reqs):
        svc = ArenaPool(XPOOL_CFGS[i % 3], ENV, BanditValueBackend(),
                        G=1, p=P, executor=executor)
        try:
            svc.submit(SearchRequest(**kw))
            (want,) = svc.run()
        finally:
            svc.close()
        got, label = done[kw["uid"]], f"xpool/{executor} uid={kw['uid']}"
        assert got.actions == want.actions, label
        assert got.rewards == want.rewards, label
        assert got.supersteps == want.supersteps, label
        for va, vb in zip(got.visit_counts, want.visit_counts):
            np.testing.assert_array_equal(va, vb, err_msg=label)
        for k in want.tree_snapshot:
            np.testing.assert_array_equal(
                got.tree_snapshot[k], want.tree_snapshot[k],
                err_msg=f"{label} field={k}")


@pytest.mark.parametrize("executor", EXECUTOR_NAMES)
def test_traced_run_bit_identical_to_untraced(executor):
    """Acceptance: tracing + metrics never change WHAT is computed — the
    matrix schedule with a live Tracer/MetricsRegistry (device-fencing
    spans included) equals the executor's own untraced masked/loop run,
    and the recorded trace covers the superstep phases and round-trips
    through json."""
    import json

    from repro.obs import MetricsRegistry, Tracer

    cl = SearchClient(ENV, BanditValueBackend(), G=G, p=P,
                      executor=executor, default_cfg=CFG,
                      compact_threshold=0.7, persistent_compaction=True,
                      trace=Tracer(), metrics=MetricsRegistry())
    try:
        handles = [cl.submit(SearchRequest(cfg=CFG, **kw))
                   for kw in _SCHEDULE]
        done = {h.uid: h.result() for h in handles}
        stats = cl.stats
        trace = cl.trace_export()
        metrics = cl.metrics()
    finally:
        cl.close()
    _assert_identical((done, stats.supersteps), _run(executor, 0.0, "loop"),
                      f"traced/{executor}")
    names = {e["name"] for e in trace["traceEvents"]}
    for phase in ("superstep", "select", "expand", "simulate", "backup",
                  "compact-gather", "compact-scatter"):
        assert phase in names, f"{executor}: phase {phase!r} missing"
    json.loads(json.dumps(trace))        # valid Chrome-trace JSON
    assert "service_supersteps_total" in metrics


def test_pool_expansion_matches_oracle():
    """The process-pool fallback is schedule- and bit-identical too (one
    combo: spawning pools under every executor adds nothing)."""
    _assert_identical(_run("faithful", 0.0, "pool"), _run(*ORACLE),
                      "faithful/pool vs oracle")


def test_expand_all_vector_matches_loop():
    """Gomoku-style expand-all + PUCT priors through the batched engine:
    the flattened (leaf x action) rows must reproduce the loop exactly."""
    jax = pytest.importorskip("jax")
    from repro.envs import GomokuEnv
    from repro.envs.policy_net import NNSimBackend, init_params

    env = GomokuEnv()
    cfg = TreeConfig(X=128, F=36, D=5, beta=5.0, score_fn="puct",
                     leaf_mode="unexpanded", expand_all=True)
    backend = NNSimBackend(env, init_params(jax.random.PRNGKey(0)))

    def go(expansion):
        svc = ArenaPool(cfg, env, backend, G=2, p=4, executor="faithful",
                        alternating_signs=True, expansion=expansion)
        try:
            for i in range(2):
                svc.submit(SearchRequest(uid=i, seed=i, budget=3,
                                         keep_tree=True))
            return {r.uid: r for r in svc.run()}, svc.stats.supersteps
        finally:
            svc.close()

    _assert_identical(go("vector"), go("loop"), "expand-all vector")


# ---------------------------------------------------------------------------
# fused K-superstep device dispatch (repro.core.fused)
# ---------------------------------------------------------------------------

# executors with a fused run_supersteps leg (reference keeps the
# phase-by-phase oracle on purpose)
FUSED_EXECUTORS = ("faithful", "pallas")


@pytest.mark.parametrize("executor", FUSED_EXECUTORS)
@pytest.mark.parametrize("k", [1, 4], ids=["k1", "k4"])
@pytest.mark.parametrize("compact", [0.0, 0.7], ids=["masked", "compacted"])
def test_fused_dispatch_matches_oracle(executor, k, compact):
    """Acceptance: the fused K-superstep device dispatch is grouping-
    independent — the matrix schedule with supersteps_per_dispatch=K
    equals the sequential numpy oracle per slot, bit for bit, on every
    fused-capable executor, masked and compacted.  K=1 keeps the classic
    phase-by-phase path (the degenerate case must not regress); K=4
    must actually run fused dispatches and hit the move-commit escape
    (the schedule's budgets are all < 2K)."""
    svc = ArenaPool(CFG, ENV, BanditValueBackend(), G=G, p=P,
                    executor=executor, compact_threshold=compact,
                    supersteps_per_dispatch=k)
    try:
        for kw in _SCHEDULE:
            svc.submit(SearchRequest(**kw))
        done = {r.uid: r for r in svc.run()}
        stats = svc.stats
    finally:
        svc.close()
    _assert_identical((done, stats.supersteps), _run(*ORACLE),
                      f"fused/{executor}/K={k}")
    if k > 1:
        assert stats.fused_dispatches > 0
        assert stats.fused_supersteps > 0
        assert stats.fused_escape_commit > 0      # commit edge exercised
        if compact > 0.0:
            assert stats.compacted_supersteps > 0  # fused on the sub-arena
    else:
        assert stats.fused_dispatches == 0        # K=1 is the classic path


class _PartialDeviceEnv(BanditTreeEnv):
    """Device twin that refuses transitions from depth >= 2 leaves: every
    deeper expansion forces the fused loop's post-insert escape to the
    host ExpansionEngine path."""

    def resolvable_device(self, states, actions):
        return states[..., 0] < 2


@pytest.mark.parametrize("executor", FUSED_EXECUTORS)
def test_fused_dispatch_expansion_escape_matches_oracle(executor):
    """Acceptance: the escape-at-expansion edge — a superstep whose
    expansion the device env twin cannot resolve exits the loop post-
    insert and completes through the ordinary host expansion path,
    still bit-identical to the oracle on the same env."""
    env = _PartialDeviceEnv(fanout=4, terminal_depth=10)

    def go(executor, k):
        svc = ArenaPool(CFG, env, BanditValueBackend(), G=G, p=P,
                        executor=executor, supersteps_per_dispatch=k)
        try:
            for kw in _SCHEDULE:
                svc.submit(SearchRequest(**kw))
            done = {r.uid: r for r in svc.run()}
            stats = svc.stats
        finally:
            svc.close()
        return (done, stats.supersteps), stats

    got, stats = go(executor, 4)
    want, _ = go("reference", 1)
    assert stats.fused_escape_expand > 0          # the edge really fired
    _assert_identical(got, want, f"fused-escape/{executor}")


def test_new_executors_must_enroll():
    """Guard: the matrix derives from EXECUTOR_NAMES, so this only fires
    if someone renames the constant away — the auto-enrolment contract."""
    assert set(BIT_COMPATIBLE) <= set(EXECUTOR_NAMES)
    assert {"reference", "faithful"} <= set(EXECUTOR_NAMES)


# ---------------------------------------------------------------------------
# D-sharded serving (core/sharded.py): placement is scheduling, never
# semantics.  Per-REQUEST fields are compared — pool-total dispatch
# counters legitimately differ at D > 1 (per-shard sums), but what any
# request computes may not.  On a 1-device host the shard->device map
# wraps (launch.mesh.serving_devices), so the partition logic runs
# everywhere; the CI leg with
# XLA_FLAGS=--xla_force_host_platform_device_count=4 puts each shard on
# its own device.
# ---------------------------------------------------------------------------

SHARD_G = 4                    # divisible by every D leg (G=3 above isn't)
_SHARD_BASE: dict = {}


def _run_sharded(executor, n_shards, k=1, compact=0.0):
    cl = SearchClient(ENV, BanditValueBackend(), G=SHARD_G, p=P,
                      executor=executor, default_cfg=CFG,
                      n_shards=n_shards, supersteps_per_dispatch=k,
                      compact_threshold=compact)
    try:
        handles = [cl.submit(SearchRequest(cfg=CFG, **kw))
                   for kw in _SCHEDULE]
        done = {h.uid: h.result() for h in handles}
        (pool,) = cl.core.pools.values()
        assert pool.n_shards == n_shards
        if n_shards > 1:
            assert getattr(pool.exec, "n_shards", 1) == n_shards
        if k > 1 and executor in FUSED_EXECUTORS:
            assert pool.stats.fused_dispatches > 0
        if compact > 0.0:
            assert pool.stats.compacted_supersteps > 0
    finally:
        cl.close()
    return done


def _shard_base(executor, k=1):
    """D=1 baseline per (executor, K), cached across the leg matrix."""
    key = (executor, k)
    if key not in _SHARD_BASE:
        _SHARD_BASE[key] = _run_sharded(executor, 1, k=k)
    return _SHARD_BASE[key]


def _assert_requests_identical(done_a, done_b, label):
    assert sorted(done_a) == sorted(done_b), label
    for uid in done_b:
        a, b = done_a[uid], done_b[uid]
        assert a.actions == b.actions, f"{label} uid={uid}"
        assert a.rewards == b.rewards, f"{label} uid={uid}"
        assert a.supersteps == b.supersteps, f"{label} uid={uid}"
        for va, vb in zip(a.visit_counts, b.visit_counts):
            np.testing.assert_array_equal(va, vb,
                                          err_msg=f"{label} uid={uid}")
        for k in b.tree_snapshot:
            np.testing.assert_array_equal(
                a.tree_snapshot[k], b.tree_snapshot[k],
                err_msg=f"{label} uid={uid} field={k}")


@pytest.mark.parametrize("executor", EXECUTOR_NAMES)
@pytest.mark.parametrize("n_shards", [2, 4], ids=["d2", "d4"])
def test_sharded_serving_bit_identical(executor, n_shards):
    """Acceptance: the matrix schedule through a D-sharded arena (least-
    loaded placement across per-device shard arenas) returns bit-
    identical per-request results to the same client at n_shards=1, on
    every executor."""
    got = _run_sharded(executor, n_shards)
    _assert_requests_identical(got, _shard_base(executor),
                               f"shard/{executor}/D={n_shards}")


@pytest.mark.parametrize("executor", ["reference", "faithful"])
def test_sharded_compaction_bit_identical(executor):
    """The compaction transform composes with sharding: a D=2 run whose
    drain tail gathers per-shard dense sub-arenas (ShardedExecutor
    .gather_sub, one sub per device behind one session) still equals
    the executor's own D=1 masked run per request."""
    got = _run_sharded(executor, 2, compact=0.7)
    _assert_requests_identical(got, _shard_base(executor),
                               f"shard-compact/{executor}")


@pytest.mark.parametrize("executor", FUSED_EXECUTORS)
@pytest.mark.parametrize("n_shards", [2, 4], ids=["d2", "d4"])
def test_sharded_fused_dispatch_bit_identical(executor, n_shards):
    """Acceptance: per-shard fused K-superstep dispatches — each shard
    runs its own device program to its own escape — stay bit-identical
    per request to the D=1 fused run.  Commit boundaries are slot-
    local, so dispatch grouping (which only decides when the host
    gets control) never leaks into results."""
    got = _run_sharded(executor, n_shards, k=4)
    _assert_requests_identical(got, _shard_base(executor, k=4),
                               f"shard-fused/{executor}/D={n_shards}")


# ---------------------------------------------------------------------------
# overlap mode (service/pool.py GangSchedule): pipelined supersteps.
# Double-buffered gangs reschedule WHEN each slot's superstep runs — one
# gang's host expansion/simulation overlaps the next gang's device
# in-tree phases — but per-slot arithmetic is position-independent and
# gangs partition the slot axis, so every request's trajectory (actions,
# rewards, visit counts, per-request superstep count, final tree) must
# stay bit-identical to the lock-step run on the SAME executor.  The
# gang schedule is a pure function of (G, n_gangs, shard partition) and
# occupancy, so a replay is deterministic by construction.
# ---------------------------------------------------------------------------

def _run_overlap(executor, n_gangs=2, k=1, n_shards=1, overlap=True):
    """The matrix schedule through an overlap-mode client (same G/CFG as
    the sharded legs, so _shard_base supplies the lock-step oracle)."""
    cl = SearchClient(ENV, BanditValueBackend(), G=SHARD_G, p=P,
                      executor=executor, default_cfg=CFG,
                      overlap=overlap, n_gangs=n_gangs,
                      supersteps_per_dispatch=k, n_shards=n_shards)
    try:
        handles = [cl.submit(SearchRequest(cfg=CFG, **kw))
                   for kw in _SCHEDULE]
        done = {h.uid: h.result() for h in handles}
        (pool,) = cl.core.pools.values()
        if overlap:
            assert pool.overlap and pool.gangs.n_gangs == n_gangs
            # a drained pool may not hold a half-finished gang
            assert pool._inflight is None
            assert pool._inflight_fused is None
            if k > 1 and executor in FUSED_EXECUTORS:
                assert pool.stats.fused_dispatches > 0
    finally:
        cl.close()
    return done


@pytest.mark.parametrize("executor", EXECUTOR_NAMES)
def test_overlap_bit_identical_to_lockstep(executor):
    """Acceptance: overlap=True returns bit-identical per-request
    results to the same client at overlap=False, on EVERY executor —
    including relaxed/wavefront, whose intra-superstep semantics differ
    from the oracle but are still per-slot deterministic."""
    got = _run_overlap(executor)
    _assert_requests_identical(got, _shard_base(executor),
                               f"overlap/{executor}")


def test_overlap_gang_count_is_semantics_free():
    """n_gangs only re-phases the pipeline: a 3-gang (and 4-gang, i.e.
    one slot per gang at G=4) run equals the 2-gang and lock-step runs."""
    for n_gangs in (3, 4):
        _assert_requests_identical(
            _run_overlap("faithful", n_gangs=n_gangs),
            _shard_base("faithful"), f"overlap/faithful/gangs={n_gangs}")


@pytest.mark.parametrize("executor", EXECUTOR_NAMES)
def test_overlap_off_is_bit_identical_on_every_executor(executor):
    """Acceptance: the overlap refactor (insert_dev/insert_host split,
    submit/collect expansion, staged fused dispatch) left the default
    overlap=False path bit-identical — pinned explicitly per executor,
    not just via the legacy suites."""
    got = _run_overlap(executor, overlap=False)
    _assert_requests_identical(got, _shard_base(executor),
                               f"overlap-off/{executor}")


def test_overlap_deterministic_replay():
    """Acceptance: the gang schedule is fixed, so an overlap run is
    exactly reproducible — two fresh clients produce identical results
    AND identical per-request superstep counts (same interleaving)."""
    a = _run_overlap("faithful")
    b = _run_overlap("faithful")
    _assert_requests_identical(a, b, "overlap-replay")


@pytest.mark.parametrize("executor", ["reference", "faithful", "pallas"])
@pytest.mark.parametrize("n_shards", [1, 2], ids=["d1", "d2"])
def test_overlap_sharded_bit_identical(executor, n_shards):
    """Acceptance: overlap composes with D-sharding — gang masks
    partition WITHIN shard runs (gang_of interleaves slots round-robin
    inside each shard), so a D=2 overlap run equals the D=1 lock-step
    run per request.  The CI leg with
    XLA_FLAGS=--xla_force_host_platform_device_count=4 places the
    shards on real separate devices."""
    got = _run_overlap(executor, n_shards=n_shards)
    _assert_requests_identical(got, _shard_base(executor),
                               f"overlap-shard/{executor}/D={n_shards}")


@pytest.mark.parametrize("executor", FUSED_EXECUTORS)
def test_overlap_fused_dispatch_bit_identical(executor):
    """Acceptance: overlap composes with the fused K-superstep path —
    one gang's device programs run while the previous gang's collect /
    escape / accounting holds the host — and stays bit-identical to the
    lock-step fused run."""
    got = _run_overlap(executor, k=4)
    _assert_requests_identical(got, _shard_base(executor, k=4),
                               f"overlap-fused/{executor}")


def test_overlap_fused_sharded_composes():
    """All three axes at once: D=2 shards x K=4 fused dispatch x 2-gang
    overlap still equals the plain D=1 K=4 run per request."""
    got = _run_overlap("faithful", k=4, n_shards=2)
    _assert_requests_identical(got, _shard_base("faithful", k=4),
                               "overlap-fused-shard/faithful")


def test_overlap_trace_exposes_gang_tracks():
    """The obs satellite: an overlap run with tracing on emits per-gang
    timeline tracks and spans around the pipeline's two blocking waits
    (env workers, device readback), and tracing still never changes
    WHAT is computed."""
    from repro.obs import MetricsRegistry, Tracer

    cl = SearchClient(ENV, BanditValueBackend(), G=SHARD_G, p=P,
                      executor="faithful", default_cfg=CFG,
                      overlap=True, expansion="vector", trace=Tracer(),
                      metrics=MetricsRegistry())
    try:
        handles = [cl.submit(SearchRequest(cfg=CFG, **kw))
                   for kw in _SCHEDULE]
        done = {h.uid: h.result() for h in handles}
        trace = cl.trace_export()
        metrics = cl.metrics()
    finally:
        cl.close()
    _assert_requests_identical(done, _shard_base("faithful"),
                               "overlap-traced/faithful")
    tracks = {e["args"]["name"] for e in trace["traceEvents"]
              if e.get("name") == "thread_name"}
    gang_tracks = {t for t in tracks if ":gang" in t}
    assert len(gang_tracks) >= 2, tracks   # one per pipelined gang
    names = {e["name"] for e in trace["traceEvents"]}
    # the async split renames the expansion phase into its two halves
    assert {"superstep", "select", "expand-submit", "expand-collect",
            "simulate"} <= names
    assert {"overlap-wait-env", "overlap-wait-device"} <= names
    assert "service_supersteps_total" in metrics


# ---------------------------------------------------------------------------
# NN-backed differential leg (repro.sim): the served DNN simulation path
# — SimServer microbatching + transposition cache — through SearchClient
# on every executor.  SimServer pads every microbatch to a fixed shape,
# so per-row inference is batch-composition independent; therefore
# (a) cache-on must equal cache-off bit for bit on EVERY executor (the
# cache only changes which rows reach the forward), and (b) the
# BIT_COMPATIBLE executors must agree with reference under the NN
# backend exactly as they do under the bandit oracle.
# ---------------------------------------------------------------------------

NN_CFG = TreeConfig(X=128, F=36, D=5, beta=5.0, score_fn="puct",
                    leaf_mode="unexpanded", expand_all=True)
NN_SCHEDULE = [dict(uid=i, seed=i, budget=2, moves=1 + i % 2,
                    keep_tree=True) for i in range(3)]
_NN_RESULTS: dict = {}
_NN_PARAMS: list = []


def _run_nn(executor: str, cache: bool):
    key = (executor, cache)
    if key in _NN_RESULTS:
        return _NN_RESULTS[key]
    jax = pytest.importorskip("jax")
    from repro.envs import GomokuEnv
    from repro.envs.policy_net import NNSimBackend, init_params
    from repro.sim import CachedSimBackend, SimServer

    if not _NN_PARAMS:
        _NN_PARAMS.append(init_params(jax.random.PRNGKey(0)))
    from repro.obs import MetricsRegistry

    env = GomokuEnv()
    reg = MetricsRegistry()
    sim = SimServer(NNSimBackend(env, _NN_PARAMS[0]), max_batch=16)
    if cache:
        sim = CachedSimBackend(sim, capacity=512, metrics=reg)
    cl = SearchClient(env, sim_backend=sim, G=2, p=P, executor=executor,
                      default_cfg=NN_CFG, alternating_signs=True)
    try:
        handles = [cl.submit(SearchRequest(**kw)) for kw in NN_SCHEDULE]
        done = {h.uid: h.result() for h in handles}
    finally:
        cl.close()
    if cache:
        # the leg must actually exercise the cache (re-expansions hit)
        assert reg.get("sim_cache_hits_total").value > 0
    _NN_RESULTS[key] = done
    return done


@pytest.mark.parametrize("executor", EXECUTOR_NAMES)
def test_nn_backend_cache_is_semantics_free(executor):
    """Acceptance: the transposition cache never changes results — the
    NN-backed schedule with CachedSimBackend equals the cache-off run
    bit for bit on every executor (relaxed/wavefront included: whatever
    an executor computes, caching must not perturb it)."""
    _assert_requests_identical(_run_nn(executor, True),
                               _run_nn(executor, False),
                               f"nn-cache/{executor}")


@pytest.mark.parametrize("executor", [e for e in EXECUTOR_NAMES
                                      if e in BIT_COMPATIBLE])
def test_nn_backend_matches_reference(executor):
    """Acceptance: NN-backed runs are bit-identical across the
    bit-compatible executors for a fixed request stream — the serving
    stack (microbatch padding + fixed-shape forward) keeps per-row
    inference results executor-agnostic."""
    _assert_requests_identical(_run_nn(executor, False),
                               _run_nn("reference", False),
                               f"nn-vs-reference/{executor}")
