"""What every cell shares: finding a cell's files by name, the device, the
compile cache, the per-layer metric readers, and the result line.

A cell of ``BENCHMARK.json`` names a configuration and a traffic mix.  The
configuration is ``configs/<config>.json`` with its plain reference
``configs/<config>.py`` beside it; the mix is ``mixes/<traffic>.json``,
whose ``kind`` names the generator that reads it (``train`` or
any later one); a per-layer metric is ``metrics/<name>.py`` with a
``read(ctx)`` that returns a number or ``None``; a cell's limits are
``limits/<workload>.json``.  Adding any of them is adding files and
entries.
"""

from __future__ import annotations

import importlib.util
import json
import math
import os
import sys

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(os.path.dirname(HERE))


class NoChip(RuntimeError):
    """JAX found no accelerator, or fewer chips than the cell asks for."""


def load_json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def load_module(path: str, name: str):
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def find_cell(spec: dict, workload: str) -> dict:
    for cell in spec["workloads"]:
        if cell["name"] == workload:
            return cell
    raise KeyError(f"no workload {workload!r} in BENCHMARK.json")


def config_files(config: str):
    """(configuration dict, reference module) of a configuration name."""
    cfg = load_json(os.path.join(HERE, "configs", config + ".json"))
    ref = load_module(os.path.join(HERE, "configs", config + ".py"),
                      "reference_" + config.replace("-", "_").replace(".", "_"))
    return cfg, ref


def mix_file(traffic: str) -> dict:
    return load_json(os.path.join(HERE, "mixes", traffic + ".json"))


def limits_of(workload: str) -> dict:
    """The limit of each number a cell compares, from
    ``limits/<workload>.json`` (which also records the readings each was
    set from); a cell with no file yet has no limits, so it fails."""
    path = os.path.join(HERE, "limits", workload + ".json")
    return load_json(path)["limits"] if os.path.exists(path) else {}


def peaks_for(kind: str) -> dict:
    table = load_json(os.path.join(HERE, "peaks.json"))["devices"]
    if kind not in table:
        raise KeyError(f"no peaks for device kind {kind!r}; known: "
                       f"{sorted(table)}")
    return table[kind]


def use_compile_cache() -> str:
    """JAX's persistent compile cache at a fixed path in the checkout, or
    where ``JAX_COMPILATION_CACHE_DIR`` says."""
    import jax
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") or os.path.join(
        HERE, ".cache", "jax")
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path


def device_info(chips: int) -> dict:
    import jax
    devs = jax.devices()
    if devs[0].platform == "cpu" or len(devs) < chips:
        raise NoChip(f"{chips} accelerator(s) needed, JAX found "
                     f"{len(devs)} {devs[0].platform} device(s)")
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs[:chips])}


def memory_peak(chips: int) -> int:
    import jax
    peaks = []
    for d in jax.devices()[:chips]:
        stats = d.memory_stats() or {}
        peaks.append(int(stats.get("peak_bytes_in_use", 0)))
    return max(peaks) if peaks else 0


def read_metrics(spec: dict, cell: dict, ctx: dict) -> dict:
    """Every per-layer metric of ``cell`` whose reader finds something."""
    out = {}
    for m in spec["per_layer"]:
        if cell["name"] not in m.get("workloads", [cell["name"]]):
            continue
        mod = load_module(os.path.join(HERE, "metrics", m["name"] + ".py"),
                          "metric_" + m["name"].replace(".", "_"))
        value = mod.read(ctx)
        if value is not None:
            out[m["name"]] = {"value": value, "unit": m["unit"]}
    return out


def judge(numbers: dict, limits: dict):
    """(correct, check) where check pairs each number compared with its
    limit.  The numbers compared are those the cell's limits name (every
    number, each failing, where the cell has no limits yet); a number is
    within its limit when it is finite and at most the limit."""
    check, ok = {}, True
    for name in [n for n in numbers if n in limits] if limits else numbers:
        value, limit = numbers[name], limits.get(name)
        good = (limit is not None and isinstance(value, (int, float))
                and math.isfinite(value) and value <= limit)
        ok &= good
        check[name] = {"value": value, "limit": limit}
    return ok, check


def emit(result: dict) -> None:
    """The numbers compared as the last lines of standard error, then the
    result as the last line of standard output, ``check`` last."""
    for name, c in result["check"].items():
        print(f"check {name} = {c['value']!r} (limit {c['limit']!r})",
              file=sys.stderr, flush=True)
    line = {k: result[k] for k in ("correct", "attempted", "failed",
                                   "metrics", "device")}
    if "breakdown" in result:
        line["breakdown"] = result["breakdown"]
    line["check"] = result["check"]
    print(json.dumps(line), flush=True)
