"""The AdamW update's share of its roofline: the least bytes one update
needs over every parameter (``lib.work.update_bytes``) at HBM bandwidth,
over the device time of the op slices under the ``update`` scope."""

from lib import work


def read(ctx):
    t = ctx.get("trace")
    spent = t and t["scope_s"].get("update")
    if not spent:
        return None
    least = work.update_bytes(ctx["config"]) / ctx["peaks"]["hbm_bytes_per_s"]
    return 100.0 * least * t["steps"] / spent
