"""The readings that a training cell's limits are set from, in one process.

    python3 benchmarks/chip/readings.py --config internvl2-1b \\
        --seeds 12 --control-seeds 3 [--small]

For each of ``--seeds`` seeds: the program's first check steps through
``Trainer.fit`` (as a run of the cell makes them) against the plain
reference, which gives the lower reading of each number compared.  For
each of the first ``--control-seeds`` seeds, with the reference in the
program's place: the control (the reference computed with 8-bit float
matmuls) and the fault "half of the batch left out, the mean taken over
the rest"; each gives a reading that the limits must stay below.  A step
that returns its state unchanged reads 1 on the change by construction.

``--small`` shrinks the configuration to the CPU-sized model of its
file's ``small`` block, for a rehearsal without the chip.  One JSON line
per reading, then a summary.
"""

from __future__ import annotations

import argparse
import copy
import json
import os
import sys
import time
import types

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)
sys.path.insert(0, os.path.join(os.path.dirname(os.path.dirname(HERE)),
                                "src"))


def shrink(cfg: dict) -> dict:
    """The configuration at the sizes of its file's ``small`` block: the
    ``model`` and ``train`` keys it names replaced, every other kept."""
    out = copy.deepcopy(cfg)
    out["model"].update(cfg["small"]["model"])
    out["train"].update(cfg["small"]["train"])
    return out


def main(argv=None) -> int:
    from lib import bench, train, weights
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--config", required=True)
    ap.add_argument("--traffic", default="train")
    ap.add_argument("--seeds", type=int, default=12)
    ap.add_argument("--control-seeds", type=int, default=3)
    ap.add_argument("--first-seed", type=int, default=3_000_000_001)
    ap.add_argument("--small", action="store_true")
    args = ap.parse_args(argv)
    cfg, ref = bench.config_files(args.config)
    mix = bench.mix_file(args.traffic)
    if args.small:
        cfg = shrink(cfg)
    else:
        bench.device_info(1)
        bench.use_compile_cache()
    cfg = dict(cfg, _check_steps=mix["check_steps"])
    out = {"program": [], "control": [], "half_batch": []}
    for k in range(args.seeds):
        seed = args.first_seed + 7919 * k
        t0 = time.perf_counter()
        run_args = types.SimpleNamespace(seed=seed, seconds=0.0, trace=0,
                                         spec={"per_layer": []}, peaks={})
        res = train.run({"name": f"{args.config}.{args.traffic}"}, cfg, ref,
                        mix, run_args, t0)
        nums = {k2: v["value"] for k2, v in res["check"].items()}
        nums.update(res["where"].pop("not_compared"))
        out["program"].append(nums)
        print(json.dumps({"kind": "program", "seed": seed, **nums,
                          "where": res["where"],
                          "seconds": time.perf_counter() - t0}), flush=True)
        if k >= args.control_seeds:
            continue
        words = weights.seed_words(seed)
        for kind in ("control", "half_batch"):
            nums, where = placed(cfg, ref, mix, seed, words, kind)
            out[kind].append(nums)
            print(json.dumps({"kind": kind, "seed": seed, **nums,
                              "where": where}), flush=True)
    summary = {kind: {name: max(r[name] for r in rows) if kind == "program"
                      else min(r[name] for r in rows)
                      for name in rows[0]} for kind, rows in out.items()
               if rows}
    print(json.dumps({"summary": summary}), flush=True)
    return 0


def placed(cfg, ref, mix, seed, words, kind):
    """The reference put in the program's place: computed in fp8
    (``control``) or fed only the first half of each step's rows
    (``half_batch``); compared with the reference as a run compares."""
    import functools
    import jax
    import jax.numpy as jnp
    import numpy as np
    from lib import plain, train, weights
    from repro.models.model import ModelConfig, init_params
    from repro.models.paramdecl import SpecLeaf

    spec = init_params(ModelConfig(**cfg["model"]), None)
    like = jax.tree.map(lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype),
                        spec, is_leaf=lambda x: isinstance(x, SpecLeaf))
    params = jax.jit(functools.partial(weights.make_params, like,
                                       rules=cfg["init"]))(words)
    rows = [train.batch_at(cfg, mix, seed, s)
            for s in range(mix["check_steps"])]
    if kind == "half_batch":
        keep = cfg["train"]["batch"] - cfg["train"]["batch"] // 2
        rows = [{k: v[:keep] for k, v in r.items()} for r in rows]
    loss_fn = functools.partial(ref.loss, model=cfg["model"])
    losses, grad, final, _ = plain.train_reference(
        loss_fn, params, rows, cfg["optimizer"],
        plain.Numerics(control=kind == "control"))
    change = np.asarray(jax.jit(lambda a, b: weights.slice_norms(
        jax.tree.map(lambda x, y: x.astype(jnp.float32)
                     - y.astype(jnp.float32), a, b)))(final, params))
    del final, params
    prog = {"loss": losses, "grad": grad, "change": change}
    names = weights.slice_names(like)
    return train.check(cfg, ref, mix, seed, words, like, prog, names)


if __name__ == "__main__":
    sys.exit(main())
