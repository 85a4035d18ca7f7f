"""Benchmark harness — one module per paper table/figure.

  fig4   (bench_intree)     in-tree op latency vs p, CPU vs accelerated
  fig5   (bench_throughput) system throughput + breakdown
  table1 (bench_resources)  UCT accelerator memory vs VMEM budget
  extras: fixed-point precision (paper §IV-C), selection diversity
          (beyond-paper ablation), roofline summary (reads dry-run),
          multi-tree service scaling vs G x executor x occupancy
          (bench_service, beyond-paper).

Every line printed is ``name,us_per_call,derived`` CSV, and each module's
rows are also written to ``BENCH_<name>.json`` at the repo root so the
perf trajectory is recorded commit to commit.

  python benchmarks/run.py                  # full sweep, all modules
  python benchmarks/run.py --only intree --only service
  python benchmarks/run.py --smoke          # tiny G/p, one repetition (CI)
"""

from __future__ import annotations

import argparse
import inspect
import json
import sys
import time
from pathlib import Path

REPO_ROOT = Path(__file__).resolve().parent.parent


def _write_bench_json(name: str, rows: list, elapsed_s: float,
                      smoke: bool) -> None:
    out = REPO_ROOT / f"BENCH_{name}.json"
    out.write_text(json.dumps({
        "bench": name,
        "smoke": smoke,
        "elapsed_s": round(elapsed_s, 2),
        "unix_time": int(time.time()),
        "rows": rows,
    }, indent=2) + "\n")


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--only", action="append", default=None,
                    metavar="NAME",
                    help="run only these modules (repeatable); names are "
                         "the bench_<NAME> suffixes, e.g. intree, service")
    ap.add_argument("--smoke", action="store_true",
                    help="tiny parameters, one repetition — CI regression "
                         "gate for the bench harness itself")
    args = ap.parse_args(argv)

    from repro.launch.compile_cache import use_compile_cache
    use_compile_cache()
    from benchmarks import (
        bench_diversity, bench_fixedpoint, bench_intree, bench_resources,
        bench_roofline, bench_service, bench_throughput,
    )
    from benchmarks.common import drain_results

    modules = [
        ("resources", bench_resources),
        ("fixedpoint", bench_fixedpoint),
        ("intree", bench_intree),
        ("throughput", bench_throughput),
        ("service", bench_service),
        ("diversity", bench_diversity),
        ("roofline", bench_roofline),
    ]
    if args.only:
        unknown = set(args.only) - {n for n, _ in modules}
        if unknown:
            ap.error(f"unknown bench module(s): {sorted(unknown)}")
        modules = [(n, m) for n, m in modules if n in args.only]

    t0 = time.time()
    print("name,us_per_call,derived")
    for name, mod in modules:
        kwargs = {}
        if args.smoke and "smoke" in inspect.signature(mod.run).parameters:
            kwargs["smoke"] = True
        drain_results()
        tm = time.time()
        mod.run(**kwargs)
        _write_bench_json(name, drain_results(), time.time() - tm,
                          args.smoke)
    print(f"# benchmarks completed in {time.time()-t0:.1f}s",
          file=sys.stderr)


if __name__ == "__main__":
    main()
