"""End-to-end driver: train a ~100M-parameter LM on the synthetic pipeline.

Full substrate path: model -> data -> AdamW(+schedule) -> checkpoints ->
fault-tolerant runner.  Defaults are CPU-sized; pass --steps 300 for the
full few-hundred-step run (the loss visibly converges toward the synthetic
stream's structure).

    PYTHONPATH=src python examples/train_e2e.py --steps 30
"""

import argparse

from repro.data import make_batch, Prefetcher
from repro.models import ModelConfig, count_params
from repro.optim import AdamW, warmup_cosine
from repro.train import Trainer, TrainerConfig

CFG_100M = ModelConfig(
    name="repro-100m", family="dense", n_layers=12, d_model=768,
    n_heads=12, n_kv_heads=4, d_ff=3072, vocab=8192, remat="none")


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--steps", type=int, default=30)
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--ckpt-dir", default="/tmp/repro_e2e_ckpt")
    args = ap.parse_args()

    cfg = CFG_100M
    print(f"model: {count_params(cfg)/1e6:.1f}M params")
    opt = AdamW(lr=warmup_cosine(3e-4, args.steps // 10 + 1, args.steps))
    tc = TrainerConfig(steps=args.steps, log_every=5,
                       ckpt_every=max(10, args.steps // 3),
                       ckpt_dir=args.ckpt_dir)
    trainer = Trainer(cfg, tc, optimizer=opt)

    def batches():
        step = 0
        while True:
            yield make_batch(cfg, seq_len=args.seq, batch=args.batch,
                             step=step)
            step += 1

    trainer.fit(Prefetcher(batches()))
    losses = [m["loss"] for m in trainer.metrics_log]
    print(f"loss: {losses[0]:.4f} -> {losses[-1]:.4f}; "
          f"median step {sorted(m['step_time_s'] for m in trainer.metrics_log)[len(losses)//2]*1e3:.0f} ms")


if __name__ == "__main__":
    main()
