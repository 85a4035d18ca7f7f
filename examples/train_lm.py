"""End-to-end driver: train a ~100M-parameter llama-family model for a few
hundred steps on the synthetic pipeline, with checkpointing and resume.

This wraps the production launcher (repro.launch.train) with a purpose-
built ~100M config — deliverable (b)'s "train ~100M model for a few
hundred steps" driver.  On this single-CPU container expect ~20+ minutes
for the full 200 steps; pass --steps 20 for a quick look.

  PYTHONPATH=src python examples/train_lm.py --steps 200
"""

import argparse

import jax

from repro.launch.compile_cache import use_compile_cache
from repro.models import lm, steps as msteps
from repro.models.config import LayerSpec, ModelConfig, param_count
from repro.data import Prefetcher, SyntheticTokens
from repro.distributed import CheckpointManager
from repro.optim import make_optimizer
import jax.numpy as jnp

CFG_100M = ModelConfig(
    name="repro-100m",
    d_model=640, n_heads=10, n_kv_heads=5, head_dim=64,
    d_ff=2560, vocab=32000,
    groups=(((LayerSpec(),), 12),),
    tie_embeddings=True, dtype="float32",
)


def main():
    use_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--steps", type=int, default=200)
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--seq", type=int, default=256)
    ap.add_argument("--ckpt", default="/tmp/repro_100m_ckpt")
    args = ap.parse_args()

    cfg = CFG_100M
    print(f"[100m] params: {param_count(cfg):,}")
    params = lm.init_params(cfg, jax.random.PRNGKey(0))
    init, update = make_optimizer("adamw", lr=3e-4, warmup=20,
                                  total=args.steps)
    opt = init(params)
    train = jax.jit(msteps.make_train_step(cfg, update, impl="blockwise"))

    mgr = CheckpointManager(args.ckpt, keep_last=2, async_save=True)
    start = 0
    s, state, _ = mgr.restore_latest({"params": params, "opt": opt})
    if s is not None:
        start, params, opt = s + 1, state["params"], state["opt"]
        print(f"[100m] resumed at {start}")

    src = SyntheticTokens(cfg.vocab, args.batch, args.seq, seed=0)
    pf = Prefetcher(src, start_step=start)
    try:
        for _ in range(start, args.steps):
            i, batch = pf.next()
            batch = {k: jnp.asarray(v) for k, v in batch.items()}
            params, opt, m = train(params, opt, jnp.asarray(i), batch)
            if i % 10 == 0 or i == args.steps - 1:
                print(f"[100m] step {i:4d} loss {float(m['loss']):.4f}",
                      flush=True)
            if i and i % 50 == 0:
                mgr.save(i, {"params": params, "opt": opt})
        mgr.save(args.steps - 1, {"params": params, "opt": opt})
        mgr.wait()
    finally:
        pf.close()
    print("[100m] done")


if __name__ == "__main__":
    main()
