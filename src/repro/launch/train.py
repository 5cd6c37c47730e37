"""Training launcher.

    PYTHONPATH=src python -m repro.launch.train --arch tinyllama-1.1b \
        --smoke --steps 50 --batch 8 --seq 128

``--smoke`` uses the arch's reduced config (CPU-runnable); otherwise the full
config (requires a real fleet; the dry-run path is ``repro.launch.dryrun``).
``--mesh local`` builds the largest mesh the local devices support.
"""

from __future__ import annotations

import argparse
import json

import jax

from repro.configs import get_config, get_smoke_config
from repro.data import SyntheticLM, make_batch, Prefetcher
from repro.optim import AdamW, warmup_cosine
from repro.runtime.compile_cache import use_compile_cache
from repro.train import Trainer, TrainerConfig


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="tinyllama-1.1b")
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--lr", type=float, default=1e-3)
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--mesh", default="none", choices=["none", "local"])
    ap.add_argument("--log-every", type=int, default=10)
    ap.add_argument("--metrics-out", default=None)
    args = ap.parse_args()
    use_compile_cache()

    cfg = get_smoke_config(args.arch) if args.smoke else get_config(args.arch)
    opt = AdamW(lr=warmup_cosine(args.lr, args.steps // 10, args.steps))
    tc = TrainerConfig(steps=args.steps, log_every=args.log_every,
                       ckpt_dir=args.ckpt_dir)

    mesh = None
    if args.mesh == "local":
        from repro.launch.mesh import smoke_mesh
        mesh = smoke_mesh()

    def batches():
        step = 0
        while True:
            yield make_batch(cfg, seq_len=args.seq, batch=args.batch,
                             step=step)
            step += 1

    trainer = Trainer(cfg, tc, optimizer=opt, mesh=mesh)
    ctx = jax.set_mesh(mesh) if mesh is not None else _null()
    with ctx:
        trainer.fit(Prefetcher(batches()), steps=args.steps)
    first = trainer.metrics_log[0]["loss"]
    last = trainer.metrics_log[-1]["loss"]
    print(f"loss {first:.4f} -> {last:.4f} over {args.steps} steps")
    if args.metrics_out:
        with open(args.metrics_out, "w") as f:
            json.dump(trainer.metrics_log, f)


class _null:
    def __enter__(self):
        return None

    def __exit__(self, *a):
        return False


if __name__ == "__main__":
    main()
