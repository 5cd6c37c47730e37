"""End-to-end behaviour tests: training converges, checkpoint resume works,
the serving engine generates, and the dry-run path lowers+compiles sharded
cells in a fresh multi-device subprocess."""

import json
import os
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_smoke_config
from repro.data import make_batch
from repro.optim import AdamW
from repro.train import Trainer, TrainerConfig


def _batches(cfg, seq, batch):
    step = 0
    while True:
        yield make_batch(cfg, seq_len=seq, batch=batch, step=step)
        step += 1


def test_training_reduces_loss():
    cfg = get_smoke_config("tinyllama-1.1b")
    tc = TrainerConfig(steps=40, log_every=0)
    tr = Trainer(cfg, tc, optimizer=AdamW(lr=3e-3))
    tr.fit(_batches(cfg, 64, 8))
    losses = [m["loss"] for m in tr.metrics_log]
    assert np.mean(losses[-5:]) < np.mean(losses[:5]) - 0.05
    assert all(np.isfinite(l) for l in losses)


def test_checkpoint_resume_continues(tmp_path):
    cfg = get_smoke_config("llama3.2-1b")
    tc = TrainerConfig(steps=6, log_every=0, ckpt_every=3,
                       ckpt_dir=str(tmp_path), ckpt_async=False)
    tr = Trainer(cfg, tc, optimizer=AdamW(lr=1e-3))
    tr.fit(_batches(cfg, 32, 4), steps=6)
    assert tr.ckpt.latest_step() is not None
    # a "restarted" trainer resumes from the checkpoint step
    tr2 = Trainer(cfg, tc, optimizer=AdamW(lr=1e-3))
    state = tr2.restore_or_init()
    assert int(jax.device_get(state["step"])) == 6


def test_serve_engine_generates():
    from repro.serve import ServeEngine, Request
    cfg = get_smoke_config("tinyllama-1.1b")
    from repro.models import build_model
    params = build_model(cfg).init(jax.random.PRNGKey(0))
    eng = ServeEngine(cfg, params, max_seq=48)
    out = eng.generate([Request(prompt=[3, 5, 7], max_new_tokens=8),
                        Request(prompt=[11, 13], max_new_tokens=8)])
    assert len(out) == 2 and all(len(r.tokens) == 8 for r in out)


_DRYRUN_SNIPPET = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=16"
    import sys, json
    sys.path.insert(0, {src!r})
    import jax
    from repro.configs import get_smoke_config, registry
    from repro.launch.mesh import make_mesh
    from repro.launch.cell import build_cell
    from repro.core.hlo import parse_hlo_module, aggregate_costs

    results = {{}}
    for mesh_shape, axes in [((2, 2), ("data", "model")),
                             ((2, 2, 2), ("pod", "data", "model"))]:
        mesh = make_mesh(mesh_shape, axes)
        for arch, shape_name, seq, gb in {cells!r}:
            cfg = get_smoke_config(arch)
            kind = registry.SHAPES[shape_name].kind
            spec = registry.ShapeSpec(shape_name, seq, gb, kind)
            with jax.set_mesh(mesh):
                cell = build_cell(cfg, spec, mesh)
                compiled = cell.lower().compile()
                agg = aggregate_costs(parse_hlo_module(compiled.as_text()))
            results[f"{{arch}}:{{shape_name}}:{{len(mesh.devices.flatten())}}"] = agg["flops"]
    print(json.dumps(results))
""")


def test_dryrun_cells_lower_and_compile_sharded(tmp_path):
    """The dry-run path (sharded lower+compile, ShapeDtypeStruct inputs) on a
    16-host-device subprocess, covering every step kind and several families.
    """
    cells = [
        ("tinyllama-1.1b", "train_4k", 64, 8),
        ("moonshot-v1-16b-a3b", "train_4k", 64, 8),
        ("deepseek-v2-236b", "decode_32k", 64, 8),
        ("mamba2-2.7b", "long_500k", 128, 8),
        ("recurrentgemma-9b", "decode_32k", 64, 8),
        ("seamless-m4t-large-v2", "prefill_32k", 64, 8),
        ("internvl2-1b", "train_4k", 64, 8),
    ]
    src = os.path.join(os.path.dirname(__file__), "..", "src")
    code = _DRYRUN_SNIPPET.format(src=os.path.abspath(src), cells=cells)
    proc = subprocess.run([sys.executable, "-c", code], capture_output=True,
                          text=True, timeout=1200)
    assert proc.returncode == 0, proc.stderr[-3000:]
    results = json.loads(proc.stdout.strip().splitlines()[-1])
    assert len(results) == 2 * len(cells)
    assert all(v > 0 for v in results.values())
