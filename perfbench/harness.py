"""One run of one cell: set-up, the measured window, the correctness
check, and the result line.

Set-up builds the system and one `SearchClient` from the cell's files,
serves the cell's own traffic for a few requests (every program the
window runs compiles or loads from the persistent cache there), and
ends when the window starts.  The window drives the client closed loop
(closed_loop.py) for `seconds`.  With `trace` on, the client also keeps its
metrics and phase spans, and the JAX profiler records a stretch in the
middle of the window; the per-layer readers reduce those.  After the
window the device's peak memory is read, the client is closed, and the
reference checks a sample of what the window served.
"""

from __future__ import annotations

import shutil
import statistics
import time
from pathlib import Path

import numpy as np

from perfbench import closed_loop, spec

# share of the window before the traced stretch, and its longest length
TRACE_AT = 0.25
TRACE_SECONDS = 5.0


class CompileCount:
    """Counts JAX's traces and backend compiles from a point on."""

    EVENTS = {"/jax/core/compile/jaxpr_trace_duration": "traces",
              "/jax/core/compile/backend_compile_duration": "compiles"}

    def __init__(self):
        import jax

        self.counts = {"traces": 0, "compiles": 0}
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event: str, duration: float, **_):
        kind = self.EVENTS.get(event)
        if kind is not None:
            self.counts[kind] += 1

    def snapshot(self) -> dict:
        return dict(self.counts)


def requests(seed: int, config: dict, traffic: dict):
    """The request stream of a run: uids in order, request seeds drawn
    from the run's seed, every request the same size."""
    rng = np.random.default_rng(int(seed))
    uid = 0
    while True:
        uid += 1
        yield {"uid": uid, "seed": int(rng.integers(2 ** 31)),
               "budget": config["budget"], "moves": traffic["moves"]}


def clients(config: dict, traffic: dict) -> int:
    if "clients" in traffic:
        return int(traffic["clients"])
    return int(traffic["clients_per_slot"]) * config["service"]["G"]


def work_shapes(config: dict) -> dict:
    """The shapes the in-tree work count (work.py) takes."""
    t = config["tree"]
    return {"p": config["service"]["p"], "D": t["D"], "F": t["F"],
            "puct": t["score_fn"] == "puct"}


def tree_config(config: dict):
    from repro.core.tree import TreeConfig

    return TreeConfig(**config["tree"])


def make_client(config: dict, system, trace: bool):
    """The client, and the perf_counter nanoseconds at which its phase
    spans start their clock (traced runs)."""
    from repro.obs.trace import Tracer
    from repro.service import SearchClient

    s = config["service"]
    base_ns = time.perf_counter_ns()
    client = SearchClient(
        system.env, G=s["G"], p=s["p"], executor=s["executor"],
        default_cfg=tree_config(config),
        supersteps_per_dispatch=s["supersteps_per_dispatch"],
        alternating_signs=s["alternating_signs"],
        trace=Tracer() if trace else False, metrics=trace,
        **system.client_options())
    return client, base_ns


def warm_requests(stream, config: dict, traffic: dict, n_clients: int):
    """Set-up traffic: the cell's own requests, cut to `warm_moves`,
    enough of them to fill the slots the window fills."""
    n = int(traffic.get("warm_requests",
                        min(n_clients, config["service"]["G"])))
    return [dict(next(stream), moves=traffic["warm_moves"])
            for _ in range(n)]


def sample(served: list, config: dict, rng: np.random.Generator) -> list:
    """Finished requests to check, drawn from the seed: the longest
    first, then others until `check_simulations` simulations."""
    if not served:
        return []
    p = config["service"]["p"]
    order = list(rng.permutation(len(served)))
    longest = max(range(len(served)),
                  key=lambda i: len(served[i][1].actions))
    order.remove(longest)
    picked, sims = [], 0
    for i in [longest] + order:
        if picked and sims >= config["check_simulations"]:
            break
        picked.append(served[i])
        sims += len(served[i][1].actions) * served[i][0]["budget"] * p
    return picked


def failed(spec_: dict, res) -> bool:
    return bool(res.cancelled or res.deadline_evicted
                or (len(res.actions) < spec_["moves"] and not res.terminal))


def percentile(values: list, q: float) -> float:
    """The q-th percentile by Python's statistics (exclusive method)."""
    if len(values) < 2:
        return values[0] if values else float("nan")
    return statistics.quantiles(values, n=100)[int(q) - 1]


class Stretch:
    """The traced stretch of a window: profiler on, counters and spans
    read at both ends."""

    def __init__(self, client, out_dir: Path, start: float, seconds: float):
        self.client, self.dir = client, out_dir
        self.start, self.seconds = start, seconds
        self.t0 = self.t1 = None
        self.metrics0 = self.metrics1 = ""

    def tick(self, now: float):
        import jax

        if self.t0 is None and now >= self.start:
            from perfbench.traces import profile_options

            shutil.rmtree(self.dir, ignore_errors=True)
            jax.profiler.start_trace(str(self.dir),
                                     profiler_options=profile_options())
            self.metrics0 = self.client.metrics()
            self.t0 = time.perf_counter()
        elif (self.t0 is not None and self.t1 is None
              and now >= self.t0 + self.seconds):
            self.stop()

    def stop(self):
        import jax

        if self.t0 is not None and self.t1 is None:
            self.t1 = time.perf_counter()
            self.metrics1 = self.client.metrics()
            jax.profiler.stop_trace()


def device_facts(jax, chips: int, require_tpu: bool):
    devs = jax.devices()
    dev = devs[0]
    if require_tpu and dev.platform != "tpu":
        raise SystemExit(f"perfbench: needs a TPU, JAX found "
                         f"{dev.platform!r} ({dev.device_kind})")
    if len(devs) < chips:
        raise SystemExit(f"perfbench: the cell needs {chips} chips, JAX "
                         f"found {len(devs)}")
    return {"platform": dev.platform, "kind": dev.device_kind,
            "count": len(devs)}


def run_cell(workload: str, seed: int, seconds: float, trace: bool, *,
             t_start: float | None = None, require_tpu: bool = True,
             overrides: dict | None = None, log=print,
             keep: bool = False, bench: dict | None = None) -> dict:
    """One run of `workload`; returns the result line's object (with
    `keep`, also the served requests, the system and the checked sample,
    under `_served`, `_system` and `_sample`).  `overrides` replaces
    keys of the configuration's top-level groups (tests run the harness
    at small sizes, without a TPU).  `bench` stands for BENCHMARK.json
    (tests run a cell it does not list)."""
    t_start = time.perf_counter() if t_start is None else t_start
    cell = spec.cell(bench or spec.benchmark(), workload)
    config = dict(cell["config"])
    for k, v in (overrides or {}).items():
        config[k] = dict(config[k], **v) if isinstance(v, dict) else v
    traffic, chips = cell["traffic"], cell["workload"]["chips"]
    out_dir = spec.HERE / "_out" / workload

    import jax

    from repro.launch.compile_cache import use_compile_cache

    device = device_facts(jax, chips, require_tpu)
    say = (lambda m: log(f"# {m}"))
    say(f"device platform={device['platform']} kind={device['kind']} "
        f"count={device['count']} jax={jax.__version__} "
        f"compile_cache={use_compile_cache()}")
    peaks = spec.peaks(device["kind"]) if require_tpu else None
    counter = CompileCount()

    system = spec.load_module(spec.system_path(config["system"]),
                              "system").build(config, seed)
    client, span_base_ns = make_client(config, system, trace)
    stream = requests(seed, config, traffic)
    n_clients = clients(config, traffic)
    warm = warm_requests(stream, config, traffic, n_clients)
    closed_loop.warm(client, warm)
    if hasattr(system, "mark_window"):
        system.mark_window()
    before = counter.snapshot()
    setup_s = time.perf_counter() - t_start
    say(f"setup_s={setup_s!r} (warm-up: {len(warm)} requests); compiles "
        f"so far: {before}")

    stretch = None
    span = closed_loop.no_span
    if trace:
        stretch = Stretch(client, out_dir / "trace",
                          time.perf_counter() + TRACE_AT * seconds,
                          min(TRACE_SECONDS, seconds * (1 - TRACE_AT) / 2))
        span = jax.profiler.TraceAnnotation
    win = closed_loop.run(client, lambda: next(stream), n_clients, seconds,
                     span=span, on_tick=stretch.tick if stretch else None)
    if stretch is not None:
        stretch.stop()
    after = counter.snapshot()
    in_window = {k: after[k] - before[k] for k in after}
    say(f"compiles inside the window: traces={in_window['traces']} "
        f"backend_compiles={in_window['compiles']}")

    stats = jax.devices()[0].memory_stats() or {}
    device["memory_peak_bytes"] = int(stats.get("peak_bytes_in_use", 0))
    spans = client.trace_export() if trace else None
    client.close()

    served = [(s, win.results[s["uid"]]) for s in win.attempted]
    n_failed = sum(failed(s, r) for s, r in served)
    done = [(s, r) for s, r in served if not failed(s, r)]
    rng = np.random.default_rng([int(seed), 7])
    picked = sample(done, config, rng)
    t_check = time.perf_counter()
    numbers = system.check(picked, say, rng)
    say(f"checked {len(picked)} of {len(served)} requests "
        f"({sum(len(r.actions) for _, r in picked)} moves) against the "
        f"reference in {time.perf_counter() - t_check:.1f}s")
    limits = cell["limits"]
    checks = {k: {"value": numbers[k], "limit": limits[k]}
              for k in sorted(numbers)}
    correct = bool(served) and all(
        c["value"] <= c["limit"] for c in checks.values())

    metrics = {}
    if not trace:
        metrics = end_to_end(cell, win, setup_s, say)
    else:
        from perfbench import traces

        ctx = traces.Context(
            stretch=stretch, spans=spans, span_base_ns=span_base_ns,
            config=config, traffic=traffic,
            peaks=peaks, shapes=work_shapes(config),
            active_slots=min(config["service"]["G"], n_clients), log=say)
        device.update(ctx.device_times())
        for m in cell["per_layer"]:
            reader = spec.load_module(spec.reader_path(m["name"]), "reader")
            value = reader.read(ctx)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
        breakdown = ctx.breakdown()
    out = {"correct": correct, "attempted": len(served),
           "failed": n_failed, "metrics": metrics, "device": device}
    if trace:
        out["breakdown"] = breakdown
    out["checks"] = checks
    if keep:
        out["_served"], out["_system"], out["_sample"] = served, system, \
            picked
    return out


def end_to_end(cell: dict, win, setup_s: float, say) -> dict:
    names = {m["name"]: m["unit"] for m in cell["end_to_end"]}
    values = {"setup_s": setup_s,
              "moves_per_s": win.moves / win.seconds}
    lat_ms = [1e3 * s for s in win.latencies_s]
    say(f"window: {win.moves} moves in {win.seconds}s from "
        f"{len(win.attempted)} requests")
    if "move_p95_ms" in names:
        values["move_p95_ms"] = percentile(lat_ms, 95)
        say(f"move latency over {len(lat_ms)} moves: median="
            f"{statistics.median(lat_ms) if lat_ms else float('nan')!r}ms "
            f"p95={values['move_p95_ms']!r}ms")
    return {k: {"value": values[k], "unit": names[k]}
            for k in names if k in values}
