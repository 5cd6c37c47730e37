"""The least work one training step needs, counted from a configuration
file's shapes: operations (a multiply-add is two) and HBM bytes.

Forward plus backward is three times the forward's matmul work; the
recomputation a remat policy adds does not count, nor does anything an
implementation does beyond the mathematics (a causal mask's upper
triangle, padding, re-reading what it could keep).  A kernel that does the
same mathematics faster can therefore come nearer to these numbers but
never pass them.
"""

from __future__ import annotations

BF16, F32 = 2, 4


def _shape(cfg: dict):
    m, t = cfg["model"], cfg["train"]
    return m, t["batch"], t["seq_len"]


def attn(cfg: dict):
    """(ops, bytes) of everything under the ``attn`` scope in one step: the
    q/k/v/o projections and causal softmax attention over all positions
    (lower triangle with the diagonal), forward and backward."""
    m, B, S = _shape(cfg)
    d, H, K = m["d_model"], m["n_heads"], m["n_kv_heads"]
    hd = m.get("head_dim") or d // H
    L, T = m["n_layers"], B * S
    proj = d * (H + 2 * K) * hd + H * hd * d
    fwd = 2 * T * proj + 2 * 2 * hd * H * B * S * (S + 1) // 2
    ops = 3 * fwd * L
    # weights read forward and backward, their gradients written; x read,
    # q/k/v/o written and read back, the output written, each once a pass
    acts = T * (d + 2 * (H + 2 * K) * hd + 2 * H * hd + d) * BF16
    wbytes = proj * BF16 * 3
    return ops, L * (wbytes + 3 * acts)


def n_params(cfg: dict) -> int:
    """Every parameter the configuration holds."""
    m = cfg["model"]
    d, V, L = m["d_model"], m["vocab"], m["n_layers"]
    H, K, ff = m["n_heads"], m["n_kv_heads"], m["d_ff"]
    hd = m.get("head_dim") or d // H
    layer = d * (H + 2 * K) * hd + H * hd * d + 3 * d * ff + 2 * d
    extra = d * d if m.get("n_patches") else 0
    return L * layer + 2 * V * d + d + extra


def update_bytes(cfg: dict) -> int:
    """Least HBM bytes of one AdamW update over every parameter: bf16
    parameter and gradient, f32 moments; the gradient is read twice (its
    global norm decides the clip before any update), everything else once,
    and parameter and moments written once."""
    per = BF16 * 2 + BF16 * 2 + F32 * 2 + F32 * 2
    return n_params(cfg) * per


def step_ops(cfg: dict) -> int:
    """Operations one training step needs: 6 per matmul parameter per
    position it multiplies, plus causal attention.  The embedding lookup
    multiplies nothing."""
    m, B, S = _shape(cfg)
    d, V, L = m["d_model"], m["vocab"], m["n_layers"]
    attn_ops, _ = attn(cfg)
    ff = m["d_ff"]
    P = m.get("n_patches", 0)
    mlp = 6 * 3 * d * ff * B * S * L
    return attn_ops + mlp + 6 * d * d * B * P + 6 * V * d * B * (S - P)
