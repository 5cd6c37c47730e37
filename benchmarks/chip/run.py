"""Run one cell of ``BENCHMARK.json`` once, on the chips of this machine.

    python3 benchmarks/chip/run.py --workload <cell> --seed <n> \\
        --seconds <run_seconds> --trace <0|1>

The cell names a configuration (``configs/<config>.json`` and its plain
reference ``configs/<config>.py``) and a traffic mix
(``mixes/<traffic>.json``, whose ``kind`` names the generator
``lib/<kind>.py``); ``limits/<workload>.json`` holds the limits of the
numbers it compares.  With ``--trace 0`` the result carries the cell's
end-to-end metrics; with ``--trace 1`` its per-layer metrics, read by
``metrics/<name>.py``.  The
last line of standard output is the result as one JSON object; the numbers
compared for ``correct`` are the last lines of standard error.  Exits 2,
printing no result, where JAX finds no accelerator or too few chips.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import importlib  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

# libtpu would otherwise log to a fixed path under /tmp
os.environ.setdefault("TPU_LOG_DIR", "disabled")

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
sys.path.insert(0, HERE)
sys.path.insert(0, os.path.join(ROOT, "src"))

from lib import bench  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    spec = bench.load_json(os.path.join(ROOT, "BENCHMARK.json"))
    cell = bench.find_cell(spec, args.workload)
    cfg, ref = bench.config_files(cell["config"])
    mix = bench.mix_file(cell["traffic"])
    if not os.path.isdir(os.path.join(ROOT, "src", "repro")):
        print("the program under test (src/repro) is not in this checkout",
              file=sys.stderr)
        return 2
    try:
        device = bench.device_info(cell["chips"])
    except bench.NoChip as e:
        print(e, file=sys.stderr)
        return 2
    bench.use_compile_cache()
    args.spec, args.peaks = spec, bench.peaks_for(device["kind"])
    generator = importlib.import_module("lib." + mix["kind"])
    result = generator.run(cell, cfg, ref, mix, args, T_START,
                           chips=cell["chips"])
    device["memory_peak_bytes"] = result.pop("memory_peak_bytes")
    if args.trace:
        device["busy_s"] = result["trace"]["busy_s"]
        device["window_s"] = result["trace"]["window_s"]
    result["device"] = device
    print(f"where {result.get('where')}", file=sys.stderr, flush=True)
    bench.emit(result)
    return 0


if __name__ == "__main__":
    sys.exit(main())
