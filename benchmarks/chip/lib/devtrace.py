"""The benchmark's own reduction of a ``jax.profiler`` capture.

It reads the gzipped Chrome-trace JSON that ``jax.profiler.stop_trace``
writes (``<dir>/plugins/profile/<run>/<host>.trace.json.gz``).  Each device
is a process named ``/device:<KIND>:<n>`` with an ``XLA Ops`` line (one
slice per executed HLO op; a ``while`` slice contains its body's ops) and
a ``Steps`` line (one slice per annotated step).  The traced window is the
span of the ``Steps`` line; busy time is the union of the op slices in it;
a scope's time is the summed duration of the leaf op slices whose
``tf_op`` name stack holds that scope, forward, backward and recompute
alike.  The scopes are the program's ``jax.named_scope`` names: ``attn``,
``mlp``, ``loss``, ``update``, ``norm``, ``embed``, ``unembed``,
``frontend``, and the two mechanisms other than attention, ``ssm`` (the
Mamba-2 SSD scan) and ``moe`` (the expert layer).

This is deliberately separate from ``repro.traceio``, which is code under
test.
"""

from __future__ import annotations

import collections
import glob
import gzip
import json
import os
import re

SCOPES = ("attn", "mlp", "loss", "update", "norm", "embed",
          "unembed", "frontend", "ssm", "moe")


def trace_file(trace_dir: str) -> str:
    files = sorted(glob.glob(os.path.join(trace_dir, "plugins", "profile",
                                          "*", "*.trace.json.gz")))
    if not files:
        raise FileNotFoundError(f"no .trace.json.gz under {trace_dir}")
    return files[-1]


def in_scope(tf_op: str, scope: str) -> bool:
    """True when ``scope`` is one whole component of the name stack, also
    inside a transform such as ``transpose(jvp(attn))``."""
    return re.search(r"(^|[/(])" + re.escape(scope) + r"([/)]|$)",
                     tf_op) is not None


def scope_label(tf_op: str) -> str:
    inner = [s for s in SCOPES if in_scope(tf_op, s)]
    # the innermost known scope is the last one in the stack
    inner.sort(key=lambda s: tf_op.rfind(s))
    name = inner[-1] if inner else "other"
    return name + (" bwd" if "transpose(" in tf_op else "")


def union_length(intervals) -> float:
    total, end = 0.0, float("-inf")
    for a, b in sorted(intervals):
        if b <= end:
            continue
        total += b - max(a, end)
        end = b
    return total


def _leaves(ops):
    """Op slices that contain no other op slice (a while's body ops, not
    the while)."""
    ops = sorted(ops, key=lambda e: (e["ts"], -e["dur"]))
    leaf = [True] * len(ops)
    stack = []
    for i, e in enumerate(ops):
        while stack and ops[stack[-1]]["ts"] + ops[stack[-1]]["dur"] <= e["ts"]:
            stack.pop()
        if stack:
            leaf[stack[-1]] = False
        stack.append(i)
    return [e for e, is_leaf in zip(ops, leaf) if is_leaf]


def reduce_trace(path: str, top: int = 10) -> dict:
    """Device busy time, window, per-scope leaf time and breakdown of one
    capture file.  Times in seconds; busy and window averaged over the
    devices in the capture."""
    with gzip.open(path) as f:
        events = json.load(f)["traceEvents"]
    pname, tname = {}, {}
    for e in events:
        if e.get("ph") != "M":
            continue
        if e.get("name") == "process_name":
            pname[e["pid"]] = e["args"]["name"]
        elif e.get("name") == "thread_name":
            tname[(e["pid"], e["tid"])] = e["args"]["name"]
    devices = sorted(p for p, n in pname.items() if n.startswith("/device:"))
    by_line = collections.defaultdict(list)
    for e in events:
        if e.get("ph") == "X":
            by_line[(e["pid"], tname.get((e["pid"], e.get("tid")), ""))
                    ].append(e)
    busy, windows, nsteps, scope_us = [], [], [], collections.Counter()
    labelled = collections.Counter()
    gaps = []
    host = [e for (pid, line), evs in by_line.items()
            if pname.get(pid, "").startswith("/host:")
            and line.startswith("python")
            for e in evs]
    for pid in devices:
        steps = by_line.get((pid, "Steps"), [])
        ops = by_line.get((pid, "XLA Ops"), [])
        if not ops:
            continue
        if steps:
            w0 = min(e["ts"] for e in steps)
            w1 = max(e["ts"] + e["dur"] for e in steps)
        else:
            w0 = min(e["ts"] for e in ops)
            w1 = max(e["ts"] + e["dur"] for e in ops)
        inside = []
        for e in ops:
            a, b = max(e["ts"], w0), min(e["ts"] + e["dur"], w1)
            if b > a:
                inside.append(dict(e, ts=a, dur=b - a))
        busy.append(union_length((e["ts"], e["ts"] + e["dur"])
                                 for e in inside))
        windows.append(w1 - w0)
        nsteps.append(len(steps))
        for e in _leaves(inside):
            tf_op = str(e.get("args", {}).get("tf_op", ""))
            for s in SCOPES:
                if in_scope(tf_op, s):
                    scope_us[s] += e["dur"]
            cat = e.get("args", {}).get("hlo_category", "op")
            labelled[f"{cat} in {scope_label(tf_op)}"] += e["dur"]
        if pid == devices[0]:
            gaps = _idle_gaps(inside, w0, w1, host)
    n = max(len(busy), 1)
    return {
        "devices": len(busy),
        "busy_s": sum(busy) / n * 1e-6,
        "window_s": sum(windows) / n * 1e-6,
        "steps": min(nsteps) if nsteps else 0,
        "scope_s": {k: v / n * 1e-6 for k, v in scope_us.items()},
        "device_ops": [[k, v / n * 1e-6]
                       for k, v in labelled.most_common(top)],
        "idle_gaps": gaps[:top],
    }


def _idle_gaps(ops, w0, w1, host):
    """The longest device idle gaps in the window, each named by the
    innermost span on the host's python thread (``python`` or ``python3``)
    that covers its middle: the step (``train``), the wait for rows, or
    none between them."""
    spans = sorted((e["ts"], e["ts"] + e["dur"]) for e in ops)
    out, end = [], w0
    for a, b in spans + [(w1, w1)]:
        if a > end:
            mid = (a + end) / 2
            frames = [h for h in host if h["ts"] <= mid <= h["ts"] + h["dur"]]
            name = (min(frames, key=lambda h: h["dur"])["name"] if frames
                    else "no host frame")
            out.append([name, (a - end) * 1e-6])
        end = max(end, b)
    out.sort(key=lambda g: -g[1])
    return out
