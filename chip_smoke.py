#!/usr/bin/env python3
"""Chip smoke test: the served arena search path on a TPU, at the paper's
tree sizes, through the entry points a user calls.

  python chip_smoke.py              # one chip: the phases below
  python chip_smoke.py --shards 4   # four chips: Pong phase only, the
                                    # slots sharded over 4 devices vs 1

Phases (one chip):

  pong     Pong at the paper's size (configs.pong.TREE: X=56,000, F=6,
           D=9), BanditTreeEnv + BanditValueBackend, SearchClient(G=4,
           p=16).  Eight multi-move requests are served four times —
           executor pallas and faithful, each with 1 and 4 supersteps per
           dispatch — and once more on the sequential numpy oracle
           (executor reference) for two of them.  Every run must give
           every request the same actions and root visit counts.
  gomoku   Gomoku at the paper's size (configs.gomoku_cfg.TREE: X=48,000,
           F=36, D=5, expand-all PUCT), GomokuEnv with a randomly
           initialised policy-value net behind SimServer and the
           transposition cache, SearchClient(G=1, p=16,
           alternating_signs=True); pallas and faithful must agree.
  kernels  The lowered select_arena / backup_arena at the Pong shape
           must contain the compiled kernel (tpu_custom_call).

Each served run is made twice with the same objects: the first compiles,
the second is timed (ending in block_until_ready) and must repeat the
first's results.  Earlier lines report set-up facts (device kind, compile
and served seconds per phase, kernel memory analysis, peak device
memory); they are not benchmark numbers.  The last line is one JSON
object, printed only when every phase passed on a TPU:

  {"ok": true, "device": {"platform": "tpu", "kind": "...", "count": 1}}

The script exits non-zero without that line when JAX finds no TPU or
the repository's sources are missing.  It runs in one process and
starts no other.
"""

from __future__ import annotations

import argparse
import json
import sys
import time
import traceback
from pathlib import Path

ROOT = Path(__file__).resolve().parent
SRC = ROOT / "src"

PONG_REQUESTS = 8        # served per run
PONG_REFERENCE = 2       # of those, also served on the numpy oracle
GOMOKU_REQUESTS = 2
BUDGET = 16              # supersteps per move
MOVES = 2
P = 16                   # workers per tree


def log(msg: str) -> None:
    print(f"# {msg}", flush=True)


class CompileClock:
    """Sums JAX's trace, lowering and backend-compile durations."""

    EVENTS = ("/jax/core/compile/jaxpr_trace_duration",
              "/jax/core/compile/jaxpr_to_mlir_module_duration",
              "/jax/core/compile/backend_compile_duration")

    def __init__(self):
        import jax

        self.seconds = 0.0
        self.events = 0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event: str, duration: float, **_):
        if event in self.EVENTS:
            self.seconds += duration
            self.events += 1


def _key(results) -> dict:
    """What must agree across runs: per request, its actions and the
    root visit counts of every committed move."""
    return {r.uid: (list(r.actions),
                    [[int(v) for v in vc] for vc in r.visit_counts])
            for r in results}


def _block_on_pools(client) -> None:
    import jax

    trees = []
    for pool in client.core.pools.values():
        ex = pool.exec
        for child in ([s[0] for s in ex.shards] if hasattr(ex, "shards")
                      else [ex]):
            if not isinstance(child.trees, list):    # numpy oracle: host
                trees.append(child.trees)
    jax.block_until_ready(trees)


def _shard_devices(client) -> list:
    devs = []
    for pool in client.core.pools.values():
        for child, _, _ in pool.exec.shards:
            devs += list(child.trees.edge_N.devices())
    return devs


def serve(clock, make_client, requests, *, timed: bool = True,
          inspect=None) -> tuple[dict, dict]:
    """Serve `requests` on a fresh client from `make_client`, twice when
    `timed`: the first run compiles, the second is measured and must
    repeat the first's results.  Returns (results by uid, facts)."""
    from repro.service import SearchRequest

    def once():
        client = make_client()
        try:
            t0 = time.perf_counter()
            handles = [client.submit(SearchRequest(**r)) for r in requests]
            results = [h.result() for h in handles]
            _block_on_pools(client)
            short = [r.uid for r, q in zip(results, requests)
                     if r.cancelled or len(r.actions) != q["moves"]]
            if short:
                raise AssertionError(f"requests {short} did not commit "
                                     f"every move")
            wall = time.perf_counter() - t0
            if inspect is not None:
                inspect(client)
        finally:
            client.close()
        return _key(results), wall

    c0, e0 = clock.seconds, clock.events
    first, wall_first = once()
    facts = {"compile_s": clock.seconds - c0, "first_wall_s": wall_first}
    if timed:
        e1 = clock.events
        again, wall = once()
        if again != first:
            raise AssertionError("a repeated run changed its results")
        facts.update(served_wall_s=wall, compile_events_in_window=(
            clock.events - e1))
    facts["compile_events"] = clock.events - e0
    return first, facts


def _agree(runs: dict, what: str) -> None:
    """Every run must match the first on every request both served.
    Logs each differing pair before failing, so one run shows them all."""
    names = list(runs)
    base = runs[names[0]]
    bad = []
    for name in names[1:]:
        diff = [uid for uid, got in runs[name].items() if base[uid] != got]
        if diff:
            bad.append(name)
            uid = diff[0]
            log(f"{what}: {name} differs from {names[0]} on requests "
                f"{diff}; request {uid}: {runs[name][uid]} != {base[uid]}")
    if bad:
        raise AssertionError(f"{what}: {bad} differ from {names[0]}")


def phase_pong(clock, cfg, shards: int = 0) -> None:
    """Pong through SearchClient; with `shards`, only n_shards=shards
    against n_shards=1 on the pallas executor."""
    import jax

    from repro.envs import BanditTreeEnv, BanditValueBackend
    from repro.service import SearchClient

    env = BanditTreeEnv(fanout=cfg.F, terminal_depth=cfg.D + 3)
    sim = BanditValueBackend()   # one env/sim pair: the fused program
    G = 4                        # is cached by their identity
    reqs = [dict(uid=i, seed=i, budget=BUDGET, moves=MOVES)
            for i in range(PONG_REQUESTS)]

    def client(executor, K, n_shards=1):
        return lambda: SearchClient(
            env, sim, G=G, p=P, executor=executor, default_cfg=cfg,
            supersteps_per_dispatch=K, n_shards=n_shards)

    runs = {}
    if shards:
        seen = []

        def on_shards(c):
            seen[:] = _shard_devices(c)

        for K in (1, 4):
            runs[f"pallas/K{K}/D1"], f1 = serve(
                clock, client("pallas", K), reqs)
            runs[f"pallas/K{K}/D{shards}"], fd = serve(
                clock, client("pallas", K, shards), reqs, inspect=on_shards)
            log(f"pong K={K} D=1: {f1}")
            log(f"pong K={K} D={shards}: {fd}")
            ids = {d.id for d in seen}
            log(f"pong K={K} D={shards} shard devices: "
                f"{[str(d) for d in seen]}")
            if len(seen) != shards or len(ids) != shards or any(
                    d.platform != "tpu" for d in seen):
                raise AssertionError(
                    f"{shards} shards must sit on {shards} distinct TPU "
                    f"devices, got {[str(d) for d in seen]}")
        _agree(runs, f"pong n_shards={shards} vs 1")
        return

    for executor in ("pallas", "faithful"):
        for K in (1, 4):
            name = f"{executor}/K{K}"
            runs[name], facts = serve(clock, client(executor, K), reqs)
            log(f"pong {name}: {facts}")
    runs["reference"], facts = serve(clock, client("reference", 1),
                                     reqs[:PONG_REFERENCE], timed=False)
    log(f"pong reference ({PONG_REFERENCE} requests): {facts}")
    log(f"pong peak_bytes_in_use={_peak_bytes(jax)}")
    _agree(runs, "pong")
    log(f"pong: {len(reqs)} requests agree across {sorted(runs)}")


def phase_gomoku(clock, cfg, seed: int = 0) -> None:
    import jax

    from repro.envs import GomokuEnv
    from repro.envs.policy_net import NNSimBackend, init_params
    from repro.service import SearchClient
    from repro.sim import CachedSimBackend, SimServer

    env = GomokuEnv()
    params = init_params(jax.random.PRNGKey(seed))
    reqs = [dict(uid=i, seed=i, budget=BUDGET, moves=MOVES)
            for i in range(GOMOKU_REQUESTS)]

    def client(executor):
        def make():
            sim = CachedSimBackend(
                SimServer(NNSimBackend(env, params), max_batch=64,
                          default_priority="self-play"), capacity=4096)
            return SearchClient(env, sim_backend=sim, G=1, p=P,
                                executor=executor, default_cfg=cfg,
                                alternating_signs=True)
        return make

    runs = {}
    for executor in ("pallas", "faithful"):
        runs[executor], facts = serve(clock, client(executor), reqs)
        log(f"gomoku {executor}: {facts}")
    log(f"gomoku peak_bytes_in_use={_peak_bytes(jax)}")
    _agree(runs, "gomoku")
    log(f"gomoku: {len(reqs)} requests agree across {sorted(runs)}")


def phase_kernels(cfg, G: int = 4) -> None:
    """The UCT kernels at the Pong shape lower to compiled Mosaic kernels
    (not the interpreter) on this device."""
    import jax.numpy as jnp

    from repro.core.tree import init_arena
    from repro.kernels import ops as kops

    arena = init_arena(cfg, G)
    active = jnp.ones((G,), jnp.int32)
    arena2, sel = kops.select_arena(cfg, arena, active, P)
    zeros = jnp.zeros((G, P), jnp.int32)
    lowered = {
        "select_arena": kops.select_arena.lower(cfg, arena, active, P),
        "backup_arena": kops.backup_arena.lower(
            cfg, arena2, active, sel, sel.leaves, zeros, False),
    }
    for name, low in lowered.items():
        if "tpu_custom_call" not in low.as_text():
            raise AssertionError(f"{name}: no tpu_custom_call in the "
                                 f"lowered program (interpreted kernel?)")
        t0 = time.perf_counter()
        compiled = low.compile()
        log(f"kernel {name} G={G} p={P}: tpu_custom_call present; "
            f"compile_s={time.perf_counter() - t0:.3f}; "
            f"memory_analysis={compiled.memory_analysis()}")


def _peak_bytes(jax) -> object:
    stats = jax.devices()[0].memory_stats() or {}
    return stats.get("peak_bytes_in_use", "not reported")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(
        description=__doc__, formatter_class=argparse.RawTextHelpFormatter)
    ap.add_argument("--shards", type=int, default=0, metavar="D",
                    help="run only the Pong phase with the slots sharded "
                         "over D devices, against one device")
    args = ap.parse_args(argv)

    if not (SRC / "repro").is_dir():
        print(f"chip_smoke: the repository's sources are not at {SRC}",
              file=sys.stderr)
        return 2
    sys.path.insert(0, str(SRC))

    import jax

    from repro.configs import gomoku_cfg, pong
    from repro.launch.compile_cache import use_compile_cache

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: needs a TPU, JAX found {dev.platform!r} "
              f"({dev.device_kind})", file=sys.stderr)
        return 1
    if args.shards and len(jax.devices()) < args.shards:
        print(f"chip_smoke: --shards {args.shards} needs {args.shards} "
              f"devices, found {len(jax.devices())}", file=sys.stderr)
        return 1
    log(f"device_kind={dev.device_kind} count={len(jax.devices())} "
        f"jax={jax.__version__} compile_cache={use_compile_cache()}")

    clock = CompileClock()
    phases = ([("pong", lambda: phase_pong(clock, pong.TREE, args.shards))]
              if args.shards else [
                  ("kernels", lambda: phase_kernels(pong.TREE)),
                  ("pong", lambda: phase_pong(clock, pong.TREE)),
                  ("gomoku", lambda: phase_gomoku(clock, gomoku_cfg.TREE)),
              ])
    failed = []
    for name, run in phases:
        t0 = time.perf_counter()
        try:
            run()
        except Exception as e:  # noqa: BLE001 — report, run the rest, fail
            traceback.print_exc()
            print(f"chip_smoke: phase {name} failed: {e!r}", file=sys.stderr)
            failed.append(name)
            continue
        log(f"phase {name} passed in {time.perf_counter() - t0:.1f}s")
    if failed:
        print(f"chip_smoke: failed phases: {failed}", file=sys.stderr)
        return 1

    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
