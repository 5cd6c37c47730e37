"""The least work one training step needs, counted from a configuration
file's shapes: operations (a multiply-add is two) and HBM bytes.

Forward plus backward is three times the forward's matmul work; the
recomputation a remat policy adds does not count, nor does anything an
implementation does beyond the mathematics (a causal mask's upper
triangle, padding, re-reading what it could keep).  A kernel that does the
same mathematics faster can therefore come nearer to these numbers but
never pass them.

The counts follow ``model["family"]``.  A state-space configuration
(``"ssm"``) is counted as the published Mamba-2 block (``mamba_ssm``
``Mamba2``: one in-projection to z, x, B, C and dt, a depthwise causal
conv over x, B and C with bias, the SSD recurrence, a gated RMSNorm and an
out-projection; one group, head dim 64, conv width 4) with no attention and
no MLP, from the file's shapes and not from the program's code.  Every
other family is counted as attention plus a gated MLP.
"""

from __future__ import annotations

BF16, F32 = 2, 4
SSM_HEAD, SSM_CONV = 64, 4     # the published Mamba-2's head dim, conv width


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


def _ssm_widths(m: dict):
    """(E, N, H): inner width, state size and heads of a Mamba-2 block."""
    E = m.get("ssm_expand", 2) * m["d_model"]
    return E, m["ssm_state"], E // SSM_HEAD


def _ssm_weights(m: dict) -> int:
    """Parameters under the ``ssm`` scope of one layer: in-projection, conv
    weight and bias, ``dt_bias``/``A_log``/``D``, gated norm,
    out-projection."""
    d, (E, N, H) = m["d_model"], _ssm_widths(m)
    return (d * (2 * E + 2 * N + H) + SSM_CONV * (E + 2 * N) + (E + 2 * N)
            + 3 * H + E + E * d)


def ssm(cfg: dict):
    """(ops, bytes) of everything under the ``ssm`` scope in one step.
    Forward: the in-projection, the depthwise conv over x, B and C, the
    state recurrence at its least (B ⊗ dt·x accumulated into the state and
    C read out of it, 2·E·N operations a position each) and the
    out-projection; the chunked form's extra intra-chunk work, the decays
    and the elementwise ops are not counted.  The step is three times the
    forward."""
    m, B, S = _shape(cfg)
    d, L, T = m["d_model"], m["n_layers"], B * S
    E, N, H = _ssm_widths(m)
    fwd = (2 * T * d * (2 * E + 2 * N + H) + 2 * T * SSM_CONV * (E + 2 * N)
           + 2 * 2 * T * E * N + 2 * T * E * d)
    ops = 3 * fwd * L
    # weights read forward and backward, their gradients written; x, z,
    # xBC, dt and y written once and read back once, each once a pass
    acts = T * 2 * (d + E + (E + 2 * N) + H + E) * BF16
    wbytes = _ssm_weights(m) * BF16 * 3
    return ops, L * (wbytes + 3 * acts)


def n_params(cfg: dict) -> int:
    """Every parameter the configuration holds; a tied embedding once."""
    m = cfg["model"]
    d, V, L = m["d_model"], m["vocab"], m["n_layers"]
    if m["family"] == "ssm":
        layer = _ssm_weights(m) + d
    else:
        H, K, ff = m["n_heads"], m["n_kv_heads"], m["d_ff"]
        hd = m.get("head_dim") or d // H
        layer = d * (H + 2 * K) * hd + H * hd * d + 3 * d * ff + 2 * d
    tables = 1 if m.get("tie_embeddings") else 2
    extra = d * d if m.get("n_patches") else 0
    return L * layer + tables * V * d + d + extra


def update_bytes(cfg: dict) -> int:
    """Least HBM bytes of one AdamW update over every parameter: bf16
    parameter and gradient, f32 moments; the gradient is read twice (its
    global norm decides the clip before any update), everything else once,
    and parameter and moments written once."""
    per = BF16 * 2 + BF16 * 2 + F32 * 2 + F32 * 2
    return n_params(cfg) * per


def step_ops(cfg: dict) -> int:
    """Operations one training step needs: 6 per matmul parameter per
    position it multiplies, plus causal attention (or, for ``"ssm"``,
    :func:`ssm`'s count).  The embedding lookup multiplies nothing; the
    unembedding does, tied or not."""
    m, B, S = _shape(cfg)
    d, V, L = m["d_model"], m["vocab"], m["n_layers"]
    if m["family"] == "ssm":
        return ssm(cfg)[0] + 6 * V * d * B * S
    attn_ops, _ = attn(cfg)
    ff = m["d_ff"]
    P = m.get("n_patches", 0)
    mlp = 6 * 3 * d * ff * B * S * L
    return attn_ops + mlp + 6 * d * d * B * P + 6 * V * d * B * (S - P)
