"""The SSD scan's share of its roofline: the least time the work under the
``ssm`` scope needs (``lib.work.ssm``: the larger of its operations at the
bf16 peak and its bytes at HBM bandwidth) over the device time of the op
slices under ``ssm``."""

from lib import work


def read(ctx):
    t = ctx.get("trace")
    spent = t and t["scope_s"].get("ssm")
    if not spent:
        return None
    ops, nbytes = work.ssm(ctx["config"])
    p = ctx["peaks"]
    least = max(ops / p["bf16_flops"], nbytes / p["hbm_bytes_per_s"])
    return 100.0 * least * t["steps"] / spent
