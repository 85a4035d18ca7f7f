"""From a traced stretch to the numbers the per-layer readers need.

Three sources, all read after the stretch:

  device trace   the JAX profiler's `.xplane.pb` of the stretch: each
                 device plane's "XLA Ops" line gives the operations that
                 ran on that chip, with start and duration, and its "XLA
                 Modules" line the jitted programs they belong to; the host
                 plane gives the benchmark's own annotations (`submit`,
                 `poll`, `result`) around its calls into the client;
  counters       the client's Prometheus text (`client.metrics()`) at
                 both ends of the stretch, differenced and summed over
                 label sets;
  spans          the client's phase spans (`client.trace_export()`) that
                 lie inside the stretch.
"""

from __future__ import annotations

import glob
import os
import re
from collections import defaultdict

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
ANNOTATIONS = ("submit", "poll", "result")
BREAKDOWN_ENTRIES = 10


def profile_options():
    """Profiler options of a traced stretch: host annotations kept,
    Python function tracing off (it would trace every call)."""
    import jax

    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 1
    return opts


def op_name(event_name: str) -> str:
    """An op event carries its HLO instruction's text; its name is what
    stands before " = ", without the "%"."""
    return event_name.split(" = ", 1)[0].lstrip("%")


def load(path: str) -> dict:
    """Device op events per chip, program (module) events per chip and
    the host annotations of the trace file `path`, or of the newest
    trace under the directory `path`:
    {"devices": {id: [(op name, start_ns, end_ns)]},
     "modules": {id: [(module name, start_ns, end_ns)]},
     "host": [(name, start_ns, end_ns)]}.  Op events nest (a while loop
    and the ops of its body are all on the line)."""
    import jax

    files = [path] if os.path.isfile(path) else sorted(
        glob.glob(os.path.join(path, "**", "*.xplane.pb"), recursive=True),
        key=os.path.getmtime)
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {path}")
    data = jax.profiler.ProfileData.from_file(files[-1])
    devices, modules, host = defaultdict(list), defaultdict(list), []
    for plane in data.planes:
        m = DEVICE_PLANE.match(plane.name)
        for line in plane.lines:
            if m and line.name == OPS_LINE:
                devices[int(m.group(1))] += [
                    (op_name(ev.name), ev.start_ns,
                     ev.start_ns + ev.duration_ns) for ev in line.events]
            elif m and line.name == MODULES_LINE:
                modules[int(m.group(1))] += [
                    (ev.name, ev.start_ns, ev.start_ns + ev.duration_ns)
                    for ev in line.events]
            elif plane.name.startswith("/host:"):
                host += [(ev.name, ev.start_ns, ev.start_ns + ev.duration_ns)
                         for ev in line.events if ev.name in ANNOTATIONS]
    return {"devices": dict(devices), "modules": dict(modules),
            "host": host}


def union(intervals) -> list:
    """Merge [(start, end)] into disjoint sorted intervals."""
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def busy_ns(events) -> float:
    return float(sum(e - s for s, e in union((s, e) for _, s, e in events)))


def top_ops(events, n: int = BREAKDOWN_ENTRIES) -> list:
    total = defaultdict(float)
    for name, s, e in events:
        total[name] += e - s
    return [[k, v * 1e-9] for k, v in
            sorted(total.items(), key=lambda kv: -kv[1])[:n]]


def idle_gaps(events, host, n: int = BREAKDOWN_ENTRIES) -> list:
    """The longest gaps between device operations, each named by the
    innermost benchmark annotation around its middle."""
    busy = union((s, e) for _, s, e in events)
    gaps = [(busy[i + 1][0] - busy[i][1], busy[i][1], busy[i + 1][0])
            for i in range(len(busy) - 1)]
    gaps.sort(reverse=True)
    out = []
    for length, s, e in gaps[:n]:
        mid = (s + e) / 2
        around = [(he - hs, name) for name, hs, he in host
                  if hs <= mid <= he]
        name = min(around)[1] if around else "between calls"
        out.append([name, length * 1e-9])
    return out


def parse_metrics(text: str) -> dict:
    """Prometheus exposition text -> {series name: value summed over
    label sets} (histograms give name_sum and name_count)."""
    out = defaultdict(float)
    for line in text.splitlines():
        if not line or line.startswith("#"):
            continue
        name_labels, _, value = line.rpartition(" ")
        name = name_labels.split("{", 1)[0]
        try:
            out[name] += float(value)
        except ValueError:
            continue
    return dict(out)


class Context:
    """What a per-layer reader may read (see readers/)."""

    def __init__(self, stretch, spans, span_base_ns, config, traffic,
                 peaks, shapes, active_slots, log):
        self.config, self.traffic, self.peaks = config, traffic, peaks
        self.shapes, self.active_slots, self.log = shapes, active_slots, log
        started = stretch.t0 is not None
        self.window_s = stretch.t1 - stretch.t0 if started else 0.0
        self.trace = (load(str(stretch.dir)) if started
                      else {"devices": {}, "host": []})
        m0, m1 = (parse_metrics(stretch.metrics0),
                  parse_metrics(stretch.metrics1))
        self.counters = {k: m1.get(k, 0.0) - m0.get(k, 0.0) for k in m1}
        lo = (stretch.t0 or 0.0) * 1e9 - span_base_ns
        hi = (stretch.t1 or 0.0) * 1e9 - span_base_ns
        self.spans = [e for e in (spans or {}).get("traceEvents", [])
                      if e.get("ph") == "X"
                      and lo <= e["ts"] * 1e3 <= hi - e.get("dur", 0) * 1e3]
        n_dev = len(self.trace["devices"])
        log(f"trace: {n_dev} device(s), "
            f"{sum(len(v) for v in self.trace['devices'].values())} device "
            f"ops, {len(self.trace['host'])} annotations, "
            f"{len(self.spans)} phase spans in {self.window_s!r}s")

    def counter(self, name: str) -> float:
        return self.counters.get(name, 0.0)

    def supersteps(self) -> float:
        return self.counter("service_supersteps_total")

    def device_events(self):
        for events in self.trace["devices"].values():
            yield from events

    def module_events(self):
        for events in self.trace.get("modules", {}).values():
            yield from events

    def device_times(self) -> dict:
        devs = self.trace["devices"]
        busy = [busy_ns(ev) * 1e-9 for ev in devs.values()]
        return {"busy_s": sum(busy) / len(busy) if busy else 0.0,
                "window_s": self.window_s}

    def breakdown(self) -> dict:
        events = list(self.device_events())
        return {"device_ops": top_ops(events),
                "idle_gaps": idle_gaps(events, self.trace["host"])}

    def span_seconds(self, name: str) -> float:
        return sum(e.get("dur", 0) for e in self.spans
                   if e["name"] == name) * 1e-6
