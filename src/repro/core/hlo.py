"""HLO-text parsing: compiled XLA modules -> Daydream tasks.

This is the TPU-side replacement for CUPTI (DESIGN.md §2).  The compiled HLO of
a jitted step function is the ground-truth "kernel schedule": every instruction
in the entry computation (with ``is_scheduled=true``, text order *is* the
device execution order) becomes one task.  ``while`` bodies (``lax.scan`` over
layers / microbatches) are expanded by their ``known_trip_count`` so FLOP and
byte accounting is exact — XLA's own ``cost_analysis()`` visits loop bodies
once and undercounts them (verified; see tests/test_hlo.py).

Two consumers:
  * :func:`extract_graph`  — full dependency graph for Daydream simulation.
  * :func:`aggregate_costs` — fast trip-count-aware aggregation for roofline.
"""

from __future__ import annotations

import dataclasses
import math
import re
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

import numpy as np

from .costmodel import CostModel
from .graph import DependencyGraph
from .task import Task, TaskKind, DEVICE_STREAM, HOST_THREAD, ici_channel

# ----------------------------------------------------------------- shapes
_DTYPE_BYTES = {
    "pred": 1, "s4": 1, "u4": 1, "s8": 1, "u8": 1,
    "f8e4m3fn": 1, "f8e5m2": 1, "f8e4m3b11fnuz": 1, "f8e4m3": 1, "f8e5m2fnuz": 1,
    "s16": 2, "u16": 2, "f16": 2, "bf16": 2,
    "s32": 4, "u32": 4, "f32": 4,
    "s64": 8, "u64": 8, "f64": 8, "c64": 8, "c128": 16,
    "token": 0, "opaque": 0,
}

_SHAPE_RE = re.compile(r"([a-z][a-z0-9]*)\[([0-9,]*)\]")


def _shape_bytes(type_str: str) -> float:
    """Total bytes of a (possibly tuple) HLO type string."""
    total = 0.0
    for dtype, dims in _SHAPE_RE.findall(type_str):
        if dtype not in _DTYPE_BYTES:
            continue
        n = 1
        if dims:
            for d in dims.split(","):
                n *= int(d)
        total += n * _DTYPE_BYTES[dtype]
    return total


def _shape_elems(type_str: str) -> float:
    total = 0.0
    for _, dims in _SHAPE_RE.findall(type_str):
        n = 1
        if dims:
            for d in dims.split(","):
                n *= int(d)
        total += n
    return total


def _first_dims(type_str: str) -> List[int]:
    m = _SHAPE_RE.search(type_str)
    if not m or not m.group(2):
        return []
    return [int(d) for d in m.group(2).split(",")]


# ------------------------------------------------------------- instructions
COLLECTIVE_OPS = ("all-reduce", "all-gather", "reduce-scatter", "all-to-all",
                  "collective-permute", "ragged-all-to-all")
_SKIP_OPS = {
    "parameter", "constant", "tuple", "get-tuple-element", "bitcast",
    "after-all", "partition-id", "replica-id", "opt-barrier", "domain",
    "add-dependency",
}
# memory-movement opcodes (bytes-bound, zero useful flops)
_MEMORY_OPS = {
    "copy", "copy-start", "copy-done", "transpose", "reshape", "broadcast",
    "slice", "dynamic-slice", "dynamic-update-slice", "concatenate", "pad",
    "gather", "scatter", "reverse", "convert", "iota", "copy-to-host",
    "copy-from-host",
}

_INSTR_HEAD_RE = re.compile(r"^\s*(ROOT\s+)?%([\w\.\-]+)\s*=\s*")
_OPCODE_RE = re.compile(r"\s+([\w\-]+)\(")
_METADATA_RE = re.compile(r'op_name="([^"]*)"')
_CALLS_RE = re.compile(r"(?:calls|to_apply|body)=%([\w\.\-]+)")
_COND_RE = re.compile(r"condition=%([\w\.\-]+)")
_BRANCHES_RE = re.compile(r"branch_computations=\{([^}]*)\}")
_TRIP_RE = re.compile(r'"known_trip_count":\{"n":"(\d+)"\}')
_IOTA_RG_RE = re.compile(r"replica_groups=\[([\d,]+)\]<=\[([\d,]+)\](?:T\(([\d,]+)\))?")
_EXPLICIT_RG_RE = re.compile(r"replica_groups=\{(\{[^=]*?\})\}")
_CONST_RE = re.compile(r"\sconstant\((\d+)\)")
_DIM_LABELS_RE = re.compile(r"dim_labels=\w+_(\w+)->")
_CONTRACT_RE = re.compile(r"lhs_contracting_dims=\{([\d,]*)\}")
_BATCH_RE = re.compile(r"lhs_batch_dims=\{([\d,]*)\}")


@dataclasses.dataclass
class HloInstr:
    name: str
    opcode: str
    type_str: str
    operands: List[str]
    raw: str
    op_name: str = ""
    is_root: bool = False

    @property
    def out_bytes(self) -> float:
        return _shape_bytes(self.type_str)

    @property
    def out_elems(self) -> float:
        return _shape_elems(self.type_str)

    def called(self) -> List[str]:
        return _CALLS_RE.findall(self.raw)

    def cond(self) -> Optional[str]:
        m = _COND_RE.search(self.raw)
        return m.group(1) if m else None

    def branches(self) -> List[str]:
        m = _BRANCHES_RE.search(self.raw)
        if not m:
            return []
        return [b.strip().lstrip("%") for b in m.group(1).split(",")]

    def replica_groups(self) -> Optional[np.ndarray]:
        """Return (num_groups, group_size) array of device ids, or None."""
        m = _IOTA_RG_RE.search(self.raw)
        if m:
            dims = [int(d) for d in m.group(1).split(",")]
            src = [int(d) for d in m.group(2).split(",")]
            n = int(np.prod(src))
            ids = np.arange(n).reshape(src)
            if m.group(3):
                perm = [int(d) for d in m.group(3).split(",")]
                ids = ids.transpose(perm)
            return ids.reshape(dims[0], -1)
        m = _EXPLICIT_RG_RE.search(self.raw)
        if m:
            groups = re.findall(r"\{([\d,\s]*)\}", "{" + m.group(1) + "}")
            parsed = [[int(x) for x in g.split(",") if x.strip()] for g in groups
                      if g.strip()]
            if parsed and all(len(p) == len(parsed[0]) for p in parsed):
                return np.asarray(parsed)
        return None


@dataclasses.dataclass
class HloComputation:
    name: str
    instrs: List[HloInstr]

    def by_name(self) -> Dict[str, HloInstr]:
        return {i.name: i for i in self.instrs}


@dataclasses.dataclass
class HloModule:
    computations: Dict[str, HloComputation]
    entry: str
    num_partitions: int

    @property
    def entry_computation(self) -> HloComputation:
        return self.computations[self.entry]

    def trip_count(self, instr: HloInstr) -> Optional[int]:
        """A ``while`` loop's trip count: ``known_trip_count`` where the
        compiler records it (CPU), else the bound of a condition of the
        form ``induction_var < constant`` (TPU, which drops the record)."""
        m = _TRIP_RE.search(instr.raw)
        if m:
            return int(m.group(1))
        comp = self.computations.get(instr.cond() or "")
        if comp is None:
            return None
        root = next((i for i in comp.instrs if i.is_root), None)
        if root is None or root.opcode != "compare" \
                or "direction=LT" not in root.raw:
            return None
        by_name = comp.by_name()
        for o in root.operands[1:]:
            c = _CONST_RE.search(by_name[o].raw) if o in by_name else None
            if c:
                return int(c.group(1))
        return None


def _type_end(line: str, i: int) -> int:
    """Index just past the HLO type starting at ``line[i]``: a balanced
    tuple ``(...)``, or one array type up to the next top-level space.
    Brackets are balanced because TPU layouts nest parentheses inside
    braces (``bf16[2,2048]{1,0:T(8,128)(2,1)}``)."""
    depth = 0
    for j in range(i, len(line)):
        ch = line[j]
        if ch in "([{":
            depth += 1
        elif ch in ")]}":
            depth -= 1
            if depth == 0 and line[i] == "(":
                return j + 1
        elif ch.isspace() and depth == 0:
            return j
    return len(line)


_COMP_HDR_RE = re.compile(r"^(ENTRY\s+)?%?([\w\.\-]+)\s*\(.*\)\s*->.*\{\s*$")


def parse_hlo_module(text: str) -> HloModule:
    computations: Dict[str, HloComputation] = {}
    entry: Optional[str] = None
    cur: Optional[HloComputation] = None
    num_partitions = 1
    m = re.search(r"num_partitions=(\d+)", text)
    if m:
        num_partitions = int(m.group(1))
    for line in text.splitlines():
        if cur is None:
            hdr = _COMP_HDR_RE.match(line.strip())
            if hdr:
                cur = HloComputation(hdr.group(2), [])
                if hdr.group(1):
                    entry = hdr.group(2)
            continue
        stripped = line.strip()
        if stripped == "}":
            computations[cur.name] = cur
            cur = None
            continue
        head = _INSTR_HEAD_RE.match(line)
        if not head:
            continue
        type_end = _type_end(line, head.end())
        om = _OPCODE_RE.match(line, type_end)
        if not om:
            continue
        is_root, name, type_str, opcode = (
            bool(head.group(1)), head.group(2),
            line[head.end():type_end].strip(), om.group(1))
        # operands: %tokens inside the first balanced paren group after opcode
        rest = line[om.end() - 1:]
        depth = 0
        end = 0
        for i, ch in enumerate(rest):
            if ch == "(":
                depth += 1
            elif ch == ")":
                depth -= 1
                if depth == 0:
                    end = i
                    break
        operand_str = rest[1:end] if end else ""
        operands = re.findall(r"%([\w\.\-]+)", operand_str)
        md = _METADATA_RE.search(line)
        cur.instrs.append(HloInstr(
            name=name, opcode=opcode, type_str=type_str, operands=operands,
            raw=line, op_name=md.group(1) if md else "", is_root=is_root))
    if entry is None:
        # fall back: last computation is the entry in XLA dumps
        entry = list(computations)[-1]
    return HloModule(computations, entry, num_partitions)


# ----------------------------------------------------------------- costing
def _dot_flops(instr: HloInstr, operand_types: Dict[str, str]) -> float:
    """2 * prod(output dims) * prod(contracting dims of lhs)."""
    out = instr.out_elems
    lhs_type = operand_types.get(instr.operands[0], "") if instr.operands else ""
    lhs_dims = _first_dims(lhs_type)
    m = _CONTRACT_RE.search(instr.raw)
    contract = 1
    if m and m.group(1):
        for d in m.group(1).split(","):
            if int(d) < len(lhs_dims):
                contract *= lhs_dims[int(d)]
    return 2.0 * out * contract


def _operand_bytes(instr: HloInstr, operand_types: Dict[str, str]) -> float:
    return sum(_shape_bytes(operand_types.get(o, "")) for o in instr.operands)


def _conv_flops(instr: HloInstr, operand_types: Dict[str, str]) -> float:
    """2 * out_elems * kernel_elems / out_channels.

    The kernel's output feature dim is the ``o`` of ``dim_labels``, else
    its last dim.  TPU lowers every dot to a convolution and folds batch
    dims of a batched dot into window dims that a dilation or reversal
    lines up one-to-one with the output (``lhs_dilate=2x4x1``); such a
    window contracts nothing, so only the input feature dim ``i`` counts.
    """
    out = instr.out_elems
    if len(instr.operands) < 2:
        return 2.0 * out
    kd = _first_dims(operand_types.get(instr.operands[1], ""))
    labels = _DIM_LABELS_RE.search(instr.raw)
    if labels and len(labels.group(1)) == len(kd):
        lab = labels.group(1)
        if "lhs_dilate=" in instr.raw or "rhs_reversal=" in instr.raw:
            return 2.0 * out * kd[lab.index("i")]
        oc = kd[lab.index("o")]
    else:
        oc = kd[-1] if kd else 1
    k = _shape_elems(operand_types.get(instr.operands[1], ""))
    return 2.0 * out * max(k / max(oc, 1), 1.0)


class _CostVisitor:
    """Shared per-instruction flops/bytes/collective classification."""

    def __init__(self, module: HloModule, cost: CostModel,
                 devices_per_pod: Optional[int] = None) -> None:
        self.module = module
        self.cost = cost
        self.devices_per_pod = devices_per_pod
        self._fusion_cache: Dict[str, float] = {}
        self._traffic_cache: Dict[str, float] = {}

    def fusion_traffic(self, comp_name: str, depth: int = 0) -> float:
        """HBM bytes a fusion actually moves.

        Fusion operands are *not* charged wholesale: a body ``dynamic-slice``
        of a parameter (the scan-over-layers stacked-weight pattern) touches
        only the slice; an in-place root ``dynamic-update-slice`` writes only
        the update.  Without this, every layer iteration would be charged the
        full stacked parameter buffer (observed 800x bytes overcount).
        """
        if comp_name in self._traffic_cache:
            return self._traffic_cache[comp_name]
        comp = self.module.computations.get(comp_name)
        if comp is None or depth > 24:
            return 0.0
        types = {i.name: i.type_str for i in comp.instrs}
        by_name = {i.name: i for i in comp.instrs}
        params = {i.name: _shape_bytes(i.type_str) for i in comp.instrs
                  if i.opcode == "parameter"}

        _PASSTHRU = {"convert", "bitcast", "copy", "reshape"}

        def resolve(name: str, lim: int = 8) -> str:
            """Follow convert/bitcast/copy chains back to the origin value."""
            while lim > 0:
                i = by_name.get(name)
                if i is None or i.opcode not in _PASSTHRU or not i.operands:
                    return name
                name = i.operands[0]
                lim -= 1
            return name

        # dynamic-update-slice carries (scan stacking) are in-place on TPU:
        # the carried buffer (even through convert/bitcast wrappers, which
        # XLA:CPU materializes but TPU fuses) is charged the UPDATE size,
        # not the full buffer.
        dus_carry: Dict[str, float] = {}
        dus_names = set()
        for i in comp.instrs:
            if i.opcode == "dynamic-update-slice":
                dus_names.add(i.name)
                ub = _shape_bytes(types.get(i.operands[1], ""))
                src = resolve(i.operands[0])
                if src in params:
                    dus_carry[src] = max(dus_carry.get(src, 0.0), ub)

        touched: Dict[str, float] = {p: 0.0 for p in params}
        extra = 0.0
        root_bytes = 0.0
        for i in comp.instrs:
            if i.opcode == "parameter":
                continue
            for o in i.operands:
                if o in params:
                    if o in dus_carry:
                        touched[o] = max(touched[o], dus_carry[o])
                    elif i.opcode in ("dynamic-slice", "gather", "slice"):
                        touched[o] = max(touched[o], i.out_bytes)
                    elif (i.opcode == "dynamic-update-slice"
                          and o == i.operands[0]):
                        ub = _shape_bytes(types.get(i.operands[1], ""))
                        touched[o] = max(touched[o], ub)
                    else:
                        touched[o] = params[o]
            if i.opcode == "fusion":
                for c in i.called():
                    extra += self.fusion_traffic(c, depth + 1)
            if i.is_root:
                if resolve(i.name) in dus_names \
                        or i.opcode == "dynamic-update-slice":
                    ref = by_name.get(resolve(i.name), i)
                    ops = ref.operands if ref.opcode == "dynamic-update-slice" \
                        else i.operands
                    root_bytes = _shape_bytes(types.get(ops[1], "")) \
                        if len(ops) > 1 else i.out_bytes
                else:
                    root_bytes = i.out_bytes
        total = sum(touched.values()) + extra + root_bytes
        self._traffic_cache[comp_name] = total
        return total

    def fusion_flops(self, comp_name: str) -> float:
        if comp_name in self._fusion_cache:
            return self._fusion_cache[comp_name]
        comp = self.module.computations.get(comp_name)
        total = 0.0
        if comp is not None:
            types = {i.name: i.type_str for i in comp.instrs}
            for i in comp.instrs:
                if i.opcode == "dot":
                    total += _dot_flops(i, types)
                elif i.opcode == "convolution":
                    total += _conv_flops(i, types)
                elif i.opcode in _SKIP_OPS or i.opcode in _MEMORY_OPS:
                    continue
                elif i.opcode == "fusion":
                    for c in i.called():
                        total += self.fusion_flops(c)
                else:
                    total += i.out_elems   # 1 flop/elem for elementwise/reduce
        self._fusion_cache[comp_name] = total
        return total

    def classify(self, instr: HloInstr,
                 operand_types: Dict[str, str]) -> Optional[Dict]:
        """Return task descriptor dict or None for zero-cost bookkeeping ops."""
        op = instr.opcode
        base = op[:-6] if op.endswith("-start") else op
        if base.endswith("-done"):
            return None
        if op in _SKIP_OPS:
            return None
        if base in COLLECTIVE_OPS:
            groups = instr.replica_groups()
            group_size = int(groups.shape[1]) if groups is not None else (
                self.module.num_partitions)
            crosses_pod = False
            if groups is not None and self.devices_per_pod:
                pods = groups // self.devices_per_pod
                crosses_pod = bool((pods != pods[:, :1]).any())
            if base == "all-gather":
                payload = instr.out_bytes       # full gathered output
            else:
                payload = _operand_bytes(instr, operand_types)
            dur = self.cost.collective_time(base, payload, group_size, crosses_pod)
            return dict(kind=TaskKind.COLLECTIVE, flops=0.0,
                        bytes=payload + instr.out_bytes, comm_bytes=payload,
                        duration=dur, group_size=group_size,
                        crosses_pod=crosses_pod, collective=base)
        inb = _operand_bytes(instr, operand_types)
        outb = instr.out_bytes
        if op == "dot":
            f = _dot_flops(instr, operand_types)
            return dict(kind=TaskKind.COMPUTE, flops=f, bytes=inb + outb,
                        duration=self.cost.compute_time(f, inb + outb))
        if op == "convolution":
            f = _conv_flops(instr, operand_types)
            return dict(kind=TaskKind.COMPUTE, flops=f, bytes=inb + outb,
                        duration=self.cost.compute_time(f, inb + outb))
        if op == "fusion":
            f = sum(self.fusion_flops(c) for c in instr.called())
            b = sum(self.fusion_traffic(c) for c in instr.called())
            kind = TaskKind.COMPUTE if f > b else TaskKind.MEMORY
            return dict(kind=kind, flops=f, bytes=b,
                        duration=self.cost.compute_time(f, b))
        if op == "custom-call":
            # opaque kernel (e.g. Pallas): bandwidth-bound estimate unless the
            # caller re-costs it via attrs
            return dict(kind=TaskKind.COMPUTE, flops=0.0, bytes=inb + outb,
                        duration=self.cost.compute_time(0.0, inb + outb))
        if op in ("dynamic-slice", "gather", "slice"):
            b = 2.0 * outb                      # touched slice read + write
            return dict(kind=TaskKind.MEMORY, flops=0.0, bytes=b,
                        duration=self.cost.compute_time(0.0, b))
        if op == "dynamic-update-slice":
            ub = (_shape_bytes(operand_types.get(instr.operands[1], ""))
                  if len(instr.operands) > 1 else outb)
            b = 2.0 * ub                        # in-place update region
            return dict(kind=TaskKind.MEMORY, flops=0.0, bytes=b,
                        duration=self.cost.compute_time(0.0, b))
        if op == "scatter":
            ub = (_shape_bytes(operand_types.get(instr.operands[2], ""))
                  if len(instr.operands) > 2 else outb)
            b = 3.0 * ub                        # read-modify-write of updates
            return dict(kind=TaskKind.MEMORY, flops=0.0, bytes=b,
                        duration=self.cost.compute_time(0.0, b))
        if op in _MEMORY_OPS:
            return dict(kind=TaskKind.MEMORY, flops=0.0, bytes=inb + outb,
                        duration=self.cost.compute_time(0.0, inb + outb))
        # generic elementwise / reduce / compare / select / rng ...
        f = instr.out_elems
        if op in ("reduce", "reduce-window"):
            f = max(f, _shape_elems(operand_types.get(instr.operands[0], ""))
                    if instr.operands else f)
        return dict(kind=TaskKind.COMPUTE, flops=f, bytes=inb + outb,
                    duration=self.cost.compute_time(f, inb + outb))


# ------------------------------------------------------------ aggregation
def aggregate_costs(module: HloModule, cost: Optional[CostModel] = None,
                    devices_per_pod: Optional[int] = None) -> Dict[str, float]:
    """Trip-count-aware totals (per device): flops, bytes, collective payloads.

    Returns the inputs of the §Roofline terms plus per-collective breakdowns.
    """
    cost = cost or CostModel()
    visitor = _CostVisitor(module, cost, devices_per_pod)
    totals = {
        "flops": 0.0, "bytes": 0.0, "collective_bytes": 0.0,
        "collective_s": 0.0, "compute_ops": 0.0, "memory_ops": 0.0,
        "collective_ops": 0.0, "device_time_s": 0.0,
    }
    per_coll: Dict[str, float] = {}

    def walk(comp_name: str, mult: float, depth: int = 0) -> None:
        comp = module.computations.get(comp_name)
        if comp is None or depth > 24:
            return
        types = {i.name: i.type_str for i in comp.instrs}
        for instr in comp.instrs:
            if instr.opcode == "while":
                n = module.trip_count(instr) or 1
                for body in instr.called():
                    walk(body, mult * n, depth + 1)
                continue
            if instr.opcode in ("call", "async-start"):
                for c in instr.called():
                    walk(c, mult, depth + 1)
                continue
            if instr.opcode == "conditional":
                branches = instr.branches() or instr.called()
                if branches:           # cost of the heaviest branch
                    walk(branches[0], mult, depth + 1)
                continue
            desc = visitor.classify(instr, types)
            if desc is None:
                continue
            totals["flops"] += mult * desc["flops"]
            totals["bytes"] += mult * desc["bytes"]
            totals["device_time_s"] += mult * desc["duration"]
            if desc["kind"] == TaskKind.COLLECTIVE:
                totals["collective_bytes"] += mult * desc["comm_bytes"]
                totals["collective_s"] += mult * desc["duration"]
                totals["collective_ops"] += mult
                key = desc["collective"]
                per_coll[key] = per_coll.get(key, 0.0) + mult * desc["comm_bytes"]
            elif desc["kind"] == TaskKind.COMPUTE:
                totals["compute_ops"] += mult
            else:
                totals["memory_ops"] += mult

    walk(module.entry, 1.0)
    for k, v in per_coll.items():
        totals[f"bytes_{k}"] = v
    return totals


# ------------------------------------------------------- graph extraction
def extract_graph(module: HloModule, cost: Optional[CostModel] = None,
                  *, overlap_collectives: bool = False,
                  devices_per_pod: Optional[int] = None,
                  max_tasks: int = 60_000,
                  include_host: bool = True) -> DependencyGraph:
    """Expand the entry computation into a Daydream dependency graph.

    ``overlap_collectives=False`` (default) keeps collectives on the device
    stream — faithful to the synchronous compiled program.  ``True`` moves them
    to per-group ICI channel lanes with data edges, modeling an async-collective
    runtime (a what-if in itself).

    ``while`` bodies are expanded ``known_trip_count`` times until the task
    budget is reached; beyond it, one representative iteration is emitted with
    durations scaled by the remaining trip count (aggregate-exact).
    """
    cost = cost or CostModel()
    visitor = _CostVisitor(module, cost, devices_per_pod)
    g = DependencyGraph()

    if include_host:
        dispatch = Task(name="host:dispatch", kind=TaskKind.HOST,
                        thread=HOST_THREAD, duration=cost.host_dispatch_time())
        g.add_task(dispatch)
    else:
        dispatch = None

    budget = [max_tasks]

    def emit(comp_name: str, env: Dict[str, Task], mult: float,
             depth: int) -> Dict[str, Task]:
        comp = module.computations.get(comp_name)
        if comp is None or depth > 24:
            return env
        types = {i.name: i.type_str for i in comp.instrs}
        local: Dict[str, Task] = dict(env)

        def producer(opname: str) -> Optional[Task]:
            return local.get(opname)

        for instr in comp.instrs:
            if instr.opcode == "while":
                n = module.trip_count(instr) or 1
                bodies = instr.called()
                body = bodies[0] if bodies else None
                if body is None:
                    continue
                body_size = len(module.computations[body].instrs)
                full_iters = n
                scale_tail = 0
                if body_size * n > budget[0]:
                    full_iters = max(1, budget[0] // max(body_size, 1))
                    scale_tail = n - full_iters
                inner = dict(local)
                for it in range(full_iters):
                    m = mult * (1 + scale_tail) if it == full_iters - 1 else mult
                    inner = emit(body, inner, m, depth + 1)
                local.update(inner)
                # while result aliases the body root env; leave names resolved
                continue
            if instr.opcode in ("call", "async-start"):
                for c in instr.called():
                    local = emit(c, local, mult, depth + 1)
                continue
            if instr.opcode == "conditional":
                branches = instr.branches() or instr.called()
                if branches:
                    local = emit(branches[0], local, mult, depth + 1)
                continue
            desc = visitor.classify(instr, types)
            if desc is None:
                # bookkeeping op: alias to its first produced operand task
                for o in instr.operands:
                    if o in local:
                        local[instr.name] = local[o]
                        break
                continue
            if budget[0] <= 0 and desc["kind"] != TaskKind.COLLECTIVE:
                continue
            budget[0] -= 1
            thread = DEVICE_STREAM
            if desc["kind"] == TaskKind.COLLECTIVE and overlap_collectives:
                thread = ici_channel(
                    "dcn" if desc.get("crosses_pod") else "ici")
            layer, phase = split_op_name(instr.op_name)
            t = Task(
                name=f"{instr.opcode}:{instr.name}",
                kind=desc["kind"], thread=thread,
                duration=desc["duration"] * mult,
                flops=desc["flops"] * mult,
                bytes_accessed=desc["bytes"] * mult,
                comm_bytes=desc.get("comm_bytes", 0.0) * mult,
                layer=layer, phase=phase,
                attrs={"opcode": instr.opcode,
                       "group_size": desc.get("group_size"),
                       "collective": desc.get("collective"),
                       "crosses_pod": desc.get("crosses_pod", False)},
            )
            g.add_task(t)
            for o in instr.operands:
                p = producer(o)
                if p is not None and p.uid != t.uid:
                    g.add_edge(p, t)
            if dispatch is not None and not g.parents(t) and thread != HOST_THREAD:
                g.add_edge(dispatch, t)
            local[instr.name] = t
        return local

    env = emit(module.entry, {}, 1.0, 0)

    if include_host:
        done = Task(name="host:sync", kind=TaskKind.SYNC, thread=HOST_THREAD,
                    duration=1e-6)
        g.add_task(done)
        # device completion -> host sync (dependency type 4)
        lane = g.lane_tasks(DEVICE_STREAM)
        if lane:
            g.add_edge(lane[-1], done)
    return g


# --------------------------------------------------------------- layer map
_PHASE_PATTERNS = (
    (re.compile(r"transpose\(jvp"), "bwd"),
    (re.compile(r"jvp\("), "fwd"),
    (re.compile(r"(^|/)update(/|$)"), "update"),
    (re.compile(r"(^|/)bwd(/|$)"), "bwd"),
    (re.compile(r"(^|/)fwd(/|$)"), "fwd"),
)
_NOISE = re.compile(
    r"(jit\([\w\.]*\)/|while/body/|while/cond/|closed_call/|checkpoint/|"
    r"remat\d*/|transpose\(jvp\(|jvp\(|\)+)")


def split_op_name(op_name: str) -> Tuple[Optional[str], Optional[str]]:
    """metadata op_name -> (layer, phase): the synchronization-free mapping."""
    if not op_name:
        return None, None
    phase = None
    for rx, ph in _PHASE_PATTERNS:
        if rx.search(op_name):
            phase = ph
            break
    cleaned = _NOISE.sub("", op_name)
    parts = [p for p in cleaned.split("/") if p]
    layer = "/".join(parts[:-1]) if len(parts) > 1 else None
    return layer or None, phase
