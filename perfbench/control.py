#!/usr/bin/env python3
"""Readings that set the limits of `correct`: the program's own numbers
and the control's, over several seeds, in one process.

  python3 perfbench/control.py --workload pong.selfplay \\
      --seeds 1,2,3 --seconds 10

For each seed: one run of the cell at its own size with a short window
(as run.py makes it), then the control on the same sample, the
reference put in the program's place one precision step below what the
configuration states (see each system's `control`).  One JSON line per
seed: {"seed", "program": {number: reading}, "control": {...}}.  The
benchmark's own runs never run this.  Needs the chips the cell asks for.
"""

import argparse
import json
import os
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parent.parent


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True,
                    help="comma-separated run seeds")
    ap.add_argument("--seconds", type=float, default=10.0)
    args = ap.parse_args(argv)
    sys.path[:0] = [str(ROOT), str(ROOT / "src")]
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(ROOT / ".jax_cache")
    # the TPU runtime's logs stay in the checkout too (default: /tmp)
    os.environ.setdefault("TPU_LOG_DIR",
                          str(ROOT / "perfbench" / "_out" / "tpu_logs"))

    from perfbench import harness

    for seed in (int(s) for s in args.seeds.split(",")):
        t0 = time.perf_counter()
        out = harness.run_cell(args.workload, seed, args.seconds, False,
                               keep=True, log=lambda m: None)
        control = out["_system"].control(out["_sample"], print)
        print(json.dumps({
            "seed": seed, "correct": out["correct"],
            "requests": len(out["_served"]), "checked": len(out["_sample"]),
            "program": {k: c["value"] for k, c in out["checks"].items()},
            "control": control,
            "moves_per_s": out["metrics"]["moves_per_s"]["value"],
            "seconds": time.perf_counter() - t0}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
