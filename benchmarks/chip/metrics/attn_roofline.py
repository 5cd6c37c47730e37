"""Attention's share of its roofline: the least time the work under the
``attn`` scope needs (``lib.work.attn``: the larger of its operations at
the bf16 peak and its bytes at HBM bandwidth; compute bounds it at these
shapes) over the device time of the op slices under ``attn``."""

from lib import work


def read(ctx):
    t = ctx.get("trace")
    spent = t and t["scope_s"].get("attn")
    if not spent:
        return None
    ops, nbytes = work.attn(ctx["config"])
    p = ctx["peaks"]
    least = max(ops / p["bf16_flops"], nbytes / p["hbm_bytes_per_s"])
    return 100.0 * least * t["steps"] / spent
