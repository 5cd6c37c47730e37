"""Whole training step's share of the chip's peak: the operations one step
needs (``lib.work.step_ops``, recompute not counted) times the steps in
the traced window, over the window's length times the bf16 peak."""

from lib import work


def read(ctx):
    t = ctx.get("trace")
    if not t or not t["steps"] or t["window_s"] <= 0:
        return None
    ops = work.step_ops(ctx["config"]) * t["steps"]
    return 100.0 * ops / t["window_s"] / ctx["peaks"]["bf16_flops"]
