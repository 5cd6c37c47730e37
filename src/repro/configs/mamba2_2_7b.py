"""mamba2-2.7b — Mamba-2 SSD (state-space duality), attention-free.

Source: https://huggingface.co/state-spaces/mamba2-2.7b (``config.json``:
d_model 2560, n_layer 64, d_intermediate 0, vocab_size 50277 padded to a
multiple of 16, tied embeddings; ``ssm_cfg.layer = "Mamba2"`` with the
``mamba_ssm`` ``Mamba2`` defaults: d_state 128, d_conv 4, expand 2,
headdim 64, ngroups 1, chunk_size 256); paper arXiv:2405.21060.
d_inner = 2*d_model = 5120 -> 80 SSD heads of dim 64.  Sub-quadratic: runs
the ``long_500k`` decode cell (O(1)-per-token recurrent state).
"""

from repro.models.model import ModelConfig

CONFIG = ModelConfig(
    name="mamba2-2.7b",
    family="ssm",
    n_layers=64,
    d_model=2560,
    n_heads=80,            # d_inner / 64 (accounting only; SSD derives it)
    n_kv_heads=80,
    d_ff=0,
    vocab=50288,           # 50277 padded to a multiple of 16
    tie_embeddings=True,
    ssm_state=128,
    ssm_expand=2,
    ssm_chunk=256,
    sub_quadratic=True,
    layout="dp",        # §Perf: no-TP DP+FSDP (small/linear arch)
    serve_fsdp=False,   # weights fit replicated-over-data at serve time
)

SMOKE = CONFIG.with_(
    n_layers=2, d_model=64, n_heads=2, n_kv_heads=2, vocab=512, ssm_state=16)
