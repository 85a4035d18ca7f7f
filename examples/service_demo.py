"""Multi-tree search service demo: many users, one scheduler.

Requests go through the SearchClient handle API — the serving surface
the paper's narrow CPU<->accelerator interface maps to.  Each superstep
advances every occupied slot of a pool through one Selection / Insertion
/ Simulation / BackUp round, with all slots' simulation states fused
into one backend batch.  Requests carry THREE different TreeConfig shape
classes and are routed into per-config arena pools by the global
scheduler under --policy:

  round-robin           one pool per tick, rotating (the default)
  weighted-queue-depth  every pool with work advances each tick, deepest
                        backlog first, admission caps proportional to
                        queue-depth share — and the tick's Simulation
                        rows from ALL pools fuse into ONE evaluate()
  deadline-aware        the pool holding the nearest deadline goes first

The demo streams: each handle's moves() generator yields per-move
action/visit-distribution events as the reroots commit (iterating IS
serving — no drain-to-completion), one request carries a deadline it
cannot meet (watch it come back "evicted"), and one is cancelled
mid-flight.  Cold pools retire after --retire-after idle ticks (their
arena is freed; watch the pool summary) and resurrect on demand.

--overlap turns on pipelined supersteps: each pool's slots are split
into --gangs gangs and the superstep is double-buffered — gang A's host
half (expansion + simulation IPC) runs while gang B's device in-tree
phases (select -> insert) are already dispatched through JAX's async
queue.  Results are bit-identical to lock-step; with
--trace-out the summary prints how long the main thread blocked on the
env workers and on the device (the overlap-wait-env /
overlap-wait-device spans).

Observability: --trace-out records every superstep phase
(select / expand / simulate / backup / compact-gather / compact-scatter)
and request lifecycle (submit -> admit -> move commits -> result /
cancel / evict) on per-pool timelines and writes Chrome-trace JSON;
--metrics prints the Prometheus text snapshot (queue depths, smoothed
load, admission waits, fused-batch sizes, evictions, expirations).

To view a trace: open https://ui.perfetto.dev in a browser, click
"Open trace file" and pick trace.json (chrome://tracing also works).
Tracks are one per arena pool plus the scheduler; zoom into any
"superstep" span to see the select/expand/simulate/backup phase split —
the Fig. 8-style breakdown the paper's CPU/FPGA numbers rest on.

  PYTHONPATH=src python examples/service_demo.py
  PYTHONPATH=src python examples/service_demo.py --executor pallas
  PYTHONPATH=src python examples/service_demo.py \
      --policy weighted-queue-depth --trace-out trace.json --metrics
  PYTHONPATH=src python examples/service_demo.py --overlap \
      --expansion pool --gangs 2
"""

import argparse
import time

import numpy as np

from repro.core import TreeConfig
from repro.envs import BanditTreeEnv, BanditValueBackend
from repro.launch.compile_cache import use_compile_cache
from repro.service import POLICY_NAMES, SearchClient, SearchRequest

CFGS = (TreeConfig(X=512, F=6, D=8),    # deep, big arena
        TreeConfig(X=256, F=6, D=6),    # mid
        TreeConfig(X=128, F=6, D=4))    # shallow, latency-lean


def run_client(args):
    """SearchClient handle API: opaque handles, streamed moves, policies,
    deadlines, cancellation and cold-pool retirement."""
    env = BanditTreeEnv(fanout=6, terminal_depth=12)
    # overlap mode double-buffers gangs, which is incompatible with
    # compaction (slot rows must stay put while a gang is in flight)
    compact = 0.0 if args.overlap else 0.5
    client = SearchClient(
        env, BanditValueBackend(), G=4, p=16,
        executor=args.executor, expansion=args.expansion,
        policy=args.policy, retire_after_ticks=args.retire_after,
        compact_threshold=compact,
        compact_exit_threshold=0.75 if compact else None,
        supersteps_per_dispatch=args.supersteps_per_dispatch,
        n_shards=args.shards,
        overlap=args.overlap, n_gangs=args.gangs,
        trace=bool(args.trace_out), metrics=args.metrics,
    )
    t_serve0 = time.perf_counter()
    handles = [client.submit(SearchRequest(
        uid=i, seed=i, budget=6 + 2 * (i % 4), moves=1 if i % 3 else 3,
        cfg=CFGS[i % len(CFGS)]), priority=i % 2)
        for i in range(10)]
    # one request that cannot make its deadline, one we cancel mid-flight
    doomed = client.submit(
        SearchRequest(uid=98, seed=98, budget=40, cfg=CFGS[0]),
        deadline_supersteps=8)
    victim = client.submit(
        SearchRequest(uid=99, seed=99, budget=6, moves=4, cfg=CFGS[1]))

    # stream one long-lived request move by move: iterating moves() polls
    # the scheduler, so every other handle advances underneath it
    streamer = next(h for h in handles if not h.uid % 3)
    print(f"streaming handle uid={streamer.uid} "
          f"({args.policy} policy, everyone else advances underneath):")
    for ev in streamer.moves():
        print(f"  move {ev.move_index}: action={ev.action} "
              f"reward={ev.reward:+.3f} last={ev.last} "
              f"visits={np.asarray(ev.visit_counts).tolist()}")
        if ev.move_index == 1 and not victim.done():
            victim.cancel()
            print(f"  (cancelled uid={victim.uid} mid-flight: "
                  f"status={victim.status()})")

    client.run_until(lambda c: all(h.done() for h in handles)
                     and doomed.done())
    t_serve = time.perf_counter() - t_serve0
    for h in sorted(handles + [doomed, victim], key=lambda h: h.uid):
        r = h.result(wait=False)
        print(f"req {h.uid:2d}: status={h.status():9s} "
              f"actions={r.actions} supersteps={r.supersteps}")

    # drive a few idle ticks against a late request so cold pools retire
    late = client.submit(SearchRequest(uid=100, seed=7, budget=30,
                                       cfg=CFGS[0]))
    late.result()
    print("\npools (cold ones retire after "
          f"{args.retire_after} idle ticks):")
    for ps in client.pool_summaries():
        state = "RETIRED" if ps["retired"] else f"load={ps['active']}"
        print(f"  bucket X={ps['cfg'].X} D={ps['cfg'].D}: "
              f"{ps['completed']} done in {ps['supersteps']} supersteps "
              f"[{state}, idle={ps['idle_ticks']}]")
    s = client.stats
    if args.overlap:
        # the pipeline's two blocking waits, from their trace spans
        waits = ""
        if client.tracer is not None:
            sec = {"overlap-wait-env": 0.0, "overlap-wait-device": 0.0}
            for e in client.trace_export()["traceEvents"]:
                if e.get("ph") == "X" and e["name"] in sec:
                    sec[e["name"]] += e["dur"] * 1e-6
            waits = (f"; blocked on env workers "
                     f"{sec['overlap-wait-env']:.3f}s, on the device "
                     f"{sec['overlap-wait-device']:.3f}s")
        print(f"\noverlap pipeline ({args.gangs} gangs): "
              f"{t_serve:.3f}s serving wall{waits}")
    else:
        print(f"\nserving wall time {t_serve:.3f}s "
              f"(re-run with --overlap to double-buffer gangs)")
    print(f"{s.completed} results ({s.cancelled} cancelled, "
          f"{s.deadline_evictions} deadline-evicted, "
          f"{s.retirements} pool retirements) in {s.ticks} ticks; "
          f"p95 admission wait {s.wait_percentile(95)} ticks; "
          f"cross-pool fused batches: {client.core.xpool_batches} "
          f"(max {client.core.xpool_rows_max} rows vs best single-pool "
          f"{client.core.xpool_pool_rows_max})")
    if args.metrics:
        print("\nPrometheus snapshot:\n" + client.metrics())
    if args.trace_out:
        trace = client.trace_export(args.trace_out)
        print(f"\nwrote {len(trace['traceEvents'])} trace events to "
              f"{args.trace_out} ({client.tracer.dropped} dropped) — open "
              f"it at https://ui.perfetto.dev (Open trace file) or "
              f"chrome://tracing")
    client.close()


def main():
    use_compile_cache()
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--executor", choices=("faithful", "pallas"),
                    default="faithful",
                    help="in-tree executor: vmapped jit arena (faithful) "
                         "or the arena-native [G]-grid Pallas kernels")
    ap.add_argument("--expansion", choices=("loop", "vector", "pool"),
                    default="vector",
                    help="host-expansion engine: per-worker env.step loop, "
                         "one flattened step_batch across all slots "
                         "(vector), or a process pool of scalar workers")
    ap.add_argument("--policy", choices=POLICY_NAMES, default="round-robin",
                    help="global schedule policy: which pools advance "
                         "each tick and how buckets admit; "
                         "weighted-queue-depth gang ticks fuse ONE "
                         "evaluate() batch across every pool")
    ap.add_argument("--supersteps-per-dispatch", type=int, default=1,
                    metavar="K",
                    help="fused K-superstep device dispatch: run up to K "
                         "supersteps per compiled program, escaping only "
                         "at move commits or host-bound expansions.  K>1 "
                         "needs device-evaluable env + sim twins (the "
                         "bandit env here has them; host-only backends "
                         "silently keep the K=1 phase-by-phase path)")
    ap.add_argument("--overlap", action="store_true",
                    help="pipelined supersteps — split each pool's "
                         "slots into --gangs gangs and double-buffer the "
                         "superstep, so one gang's host "
                         "expansion/simulation runs while the next gang's "
                         "device in-tree phases are already dispatched "
                         "(results stay bit-identical; disables "
                         "compaction, which needs slot rows to stay put)")
    ap.add_argument("--gangs", type=int, default=2, metavar="N",
                    help="gangs per pool for --overlap (2 = classic "
                         "double buffering)")
    ap.add_argument("--shards", type=int, default=1, metavar="D",
                    help="partition each bucket's G slots across D "
                         "per-device shard arenas (least-loaded "
                         "placement; results bit-identical to D=1).  Use "
                         "XLA_FLAGS=--xla_force_host_platform_device_count"
                         "=D for real per-shard devices on a CPU host")
    ap.add_argument("--retire-after", type=int, default=12, metavar="TICKS",
                    help="idle ticks before a cold pool releases its "
                         "arena (resurrected on demand)")
    ap.add_argument("--trace-out", metavar="PATH", default=None,
                    help="record phase + request-lifecycle spans and "
                         "write Chrome-trace JSON here (open at "
                         "ui.perfetto.dev)")
    ap.add_argument("--metrics", action="store_true",
                    help="print the Prometheus exposition snapshot of "
                         "the scheduler/pool telemetry")
    run_client(ap.parse_args())


if __name__ == "__main__":
    main()
