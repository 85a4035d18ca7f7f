#!/usr/bin/env python3
"""Run one cell of the benchmark once, on the chips of this machine.

  python3 perfbench/run.py --workload pong.selfplay --seed 7 \\
      --seconds 40 --trace 0

From the root of a checkout.  Earlier lines of standard output ("# ...")
are facts of the run: device, set-up, compiles inside the window, the
correctness sample, trace matches.  The last lines of standard error are
the numbers `correct` compared, each with its limit; the last line of
standard output is one JSON object: correct, attempted, failed, metrics
(the cell's end-to-end metrics, or its per-layer metrics with
--trace 1), device, with --trace 1 breakdown, and checks.  The run
exits non-zero without that line when JAX finds no TPU, or fewer chips
than the cell asks for.
"""

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parent.parent


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    if not (ROOT / "src" / "repro").is_dir():
        print(f"perfbench: the program's sources are not at {ROOT / 'src'}",
              file=sys.stderr)
        return 2
    sys.path[:0] = [str(ROOT), str(ROOT / "src")]
    # the persistent compile cache lives at one fixed path in the checkout
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(ROOT / ".jax_cache")
    # the TPU runtime's logs stay in the checkout too (default: /tmp)
    os.environ.setdefault("TPU_LOG_DIR",
                          str(ROOT / "perfbench" / "_out" / "tpu_logs"))

    from perfbench import harness

    out = harness.run_cell(args.workload, args.seed, args.seconds,
                           bool(args.trace), t_start=T_START)
    sys.stdout.flush()
    for name, c in out["checks"].items():
        print(f"check {name}: {c['value']!r} (limit {c['limit']!r})",
              file=sys.stderr, flush=True)
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
