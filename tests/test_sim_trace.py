"""The simulation layer's spans (repro.sim) in a Gomoku search served
through SearchClient.

  * traced, each `simulate` span holds `sim-cache`, `sim-flush` and
    `sim-finalize` on its own track, and they cover nearly all of it;
  * untraced, the layer holds the no-op tracer and records nothing;
  * tracing changes no result, and both match the plain reference
    search (perfbench/reference/) fed the same network.
"""

import gc
import time

import numpy as np
import pytest

from repro.core import TreeConfig
from repro.envs import GomokuEnv
from repro.obs import NULL_TRACER, Tracer
from repro.service import SearchClient, SearchRequest
from repro.sim import CachedSimBackend, SimServer

CFG = dict(X=2048, F=36, D=5, beta=5.0, vl_mode="wu", score_fn="puct",
           leaf_mode="unexpanded", expand_all=True)
P, BUDGET, MOVES, MAX_BATCH = 16, 3, 3, 64
SEEDS = (5, 17, 30)
SIM_SPANS = {"sim-cache", "sim-flush", "sim-finalize"}


class OpeningGomoku(GomokuEnv):
    """Each request opens with one stone on the cell its seed names."""

    def initial_state(self, seed: int = 0):
        s, _, _ = self.step(super().initial_state(seed), seed % 36)
        return s


@pytest.fixture(scope="module")
def params():
    import jax

    from repro.envs.policy_net import init_params

    return init_params(jax.random.PRNGKey(3), channels=32)


def _server(params) -> SimServer:
    from repro.envs.policy_net import NNSimBackend

    return SimServer(NNSimBackend(OpeningGomoku(), params),
                     max_batch=MAX_BATCH, default_priority="self-play")


def _search(sim, trace=False):
    """Serve one request per seed; returns ({uid: (actions, visit
    counts)}, the exported trace or None)."""
    cl = SearchClient(OpeningGomoku(), G=1, p=P, executor="faithful",
                      default_cfg=TreeConfig(**CFG), alternating_signs=True,
                      trace=trace, sim_backend=sim)
    handles = [cl.submit(SearchRequest(uid=s, seed=s, budget=BUDGET,
                                       moves=MOVES)) for s in SEEDS]
    done = {h.uid: h.result() for h in handles}
    exported = cl.trace_export() if trace else None
    cl.close()
    return ({u: (list(r.actions), [[int(c) for c in v]
                                   for v in r.visit_counts])
             for u, r in done.items()}, exported)


def _inside(c, parent):
    return (c["tid"] == parent["tid"] and parent["ts"] <= c["ts"]
            and c["ts"] + c["dur"] <= parent["ts"] + parent["dur"])


def test_sim_spans_nest_in_simulate_and_cover_it(params):
    """On the process's CPU clock with the garbage collector held off,
    so that the share the children cover is the program's own."""
    sim = CachedSimBackend(_server(params), capacity=256)
    gc.collect()
    gc.disable()
    try:
        _, trace = _search(sim, Tracer(clock_ns=time.process_time_ns))
    finally:
        gc.enable()
    spans = [e for e in trace["traceEvents"] if e.get("ph") == "X"]
    simulate = [e for e in spans if e["name"] == "simulate"]
    inner = [e for e in spans if e["name"] in SIM_SPANS]
    assert simulate and {e["name"] for e in inner} == SIM_SPANS
    # every span of the layer lies in one simulate span, on its track
    assert all(sum(_inside(c, s) for s in simulate) == 1 for c in inner)
    for s in simulate:
        kids = [c for c in spans if c is not s and _inside(c, s)]
        assert {c["name"] for c in kids} <= SIM_SPANS
        # a superstep looks its rows up and stores them: two cache spans
        assert [c["name"] for c in kids].count("sim-cache") == 2
    # the layer's spans do not overlap one another, so their sum is the
    # share of simulate they cover
    covered = sum(c["dur"] for c in inner)
    assert covered >= 0.9 * sum(s["dur"] for s in simulate)
    # one finalize per flushed microbatch, each holding its real rows
    fin = [e for e in inner if e["name"] == "sim-finalize"]
    assert len(fin) == sum(e["name"] == "sim-flush" for e in inner)
    assert all(0 < e["args"]["rows"] <= MAX_BATCH for e in fin)


def test_untraced_sim_layer_records_nothing(params):
    server = _server(params)
    sim = CachedSimBackend(server, capacity=256)
    tracer = Tracer()
    sim.bind_tracer(tracer)
    assert server.trace is tracer
    sim.bind_tracer(None)
    _search(sim)
    assert sim.trace is NULL_TRACER and server.trace is NULL_TRACER
    assert tracer.events() == []


def test_traced_search_equals_untraced_and_reference(params):
    from perfbench.reference import gomoku as ref
    from perfbench.reference import mcts

    traced, _ = _search(CachedSimBackend(_server(params), capacity=256),
                        trace=True)
    plain, _ = _search(CachedSimBackend(_server(params), capacity=256))
    assert traced == plain

    class RefOpening(ref.GomokuEnv):
        def initial_state(self, seed: int = 0):
            s, _ = self.step(super().initial_state(seed), seed % 36)
            return s

    # the same network behind the same fixed-shape microbatches, so each
    # row's outputs are the program's bit for bit
    evaluate = _server(params).evaluate
    for seed in SEEDS:
        actions, counts = mcts.serve(CFG, P, RefOpening(), evaluate, seed,
                                     BUDGET, MOVES, alternating=True)
        assert plain[seed] == (actions, counts), seed
        assert np.sum(counts[0]) > 0
