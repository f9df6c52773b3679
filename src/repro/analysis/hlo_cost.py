"""Trip-count-aware cost analysis of compiled (partitioned) HLO text.

Why this exists: XLA's ``compiled.cost_analysis()`` counts each ``while``
body ONCE, but this framework deliberately lowers layer stacks / microbatches
/ KV streams as ``lax.scan`` (HLO size O(1) in depth — the only way 512-device
compiles stay tractable on this container).  A 48-layer model would be
under-counted ~48x.  This module re-derives FLOPs / memory traffic /
collective bytes by walking the computation graph and multiplying while
bodies by their statically-known trip counts (parsed from the loop condition
constants that lax.scan emits).

Traffic model (per chip — the module is the SPMD-partitioned per-device
program):
  * flops: 2 · |result| · |contracted dims| per dot (elementwise ignored:
    <2% for these models); while ×trips; fusion/call/cond recursed.
  * bytes: Σ over scheduled ops of (operand + result bytes); fusions count
    call-site operands/results only (interior is register/VMEM traffic);
    parameter/constant/tuple/get-tuple-element/bitcast are free;
    while recursed ×trips.
  * collectives: per-op result bytes × kind factor:
      all-reduce ×2, all-gather ×1, reduce-scatter ×(group size),
      all-to-all ×1, collective-permute ×1; while ×trips.

Validated against XLA's own numbers for loop-free programs
(tests/test_roofline.py).
"""
from __future__ import annotations

import dataclasses
import math
import re

_DTYPE_BYTES = {
    "f64": 8, "f32": 4, "bf16": 2, "f16": 2, "f8e4m3fn": 1, "f8e5m2": 1,
    "s64": 8, "s32": 4, "s16": 2, "s8": 1, "u64": 8, "u32": 4, "u16": 2,
    "u8": 1, "pred": 1, "c64": 8, "c128": 16, "token": 0, "s4": 1, "u4": 1,
}

_COMP_HDR = re.compile(r"^(?:ENTRY )?%?([\w.\-]+)\s*\(", re.M)
_OP_RE = re.compile(
    r"^\s+(?:ROOT )?%([\w.\-]+)\s*=\s*(\([^=]*?\)|[\w\[\],{}\s/]+?)\s+"
    r"([\w\-]+)\((.*)$")
_SHAPE_RE = re.compile(r"(\w+)\[([\d,]*)\]")
_CALL_ATTR = re.compile(
    r"(?:calls|body|to_apply|true_computation|false_computation)="
    r"%?([\w.\-]+)")
_COND_ATTR = re.compile(r"condition=%?([\w.\-]+)")
_BRANCHES = re.compile(r"branch_computations=\{([^}]*)\}")
_LHS_CONTRACT = re.compile(r"lhs_contracting_dims=\{([\d,]*)\}")
_LHS_BATCH = re.compile(r"lhs_batch_dims=\{([\d,]*)\}")
_CONST_INT = re.compile(r"constant\((\d+)\)")
_GROUPS_PAIR = re.compile(r"replica_groups=\[(\d+),(\d+)\]")
_GROUPS_BRACE = re.compile(r"replica_groups=\{\{([\d,]+)\}")
_OPERAND = re.compile(r"%([\w.\-]+)")

_FREE_OPS = {"parameter", "constant", "tuple", "get-tuple-element",
             "bitcast", "bitcast-convert", "after-all", "iota",
             "partition-id", "replica-id"}

_COLLECTIVES = {"all-gather", "all-reduce", "reduce-scatter", "all-to-all",
                "collective-permute"}


def _parse_shapes(type_str: str) -> list[tuple[str, list[int]]]:
    out = []
    for dt, dims in _SHAPE_RE.findall(type_str):
        if dt in _DTYPE_BYTES:
            out.append((dt, [int(d) for d in dims.split(",") if d]))
    return out


def _nbytes(shapes) -> int:
    return sum(_DTYPE_BYTES[dt] * math.prod(dims) for dt, dims in shapes)


@dataclasses.dataclass
class Op:
    name: str
    shapes: list            # result shapes [(dtype, dims), ...]
    opcode: str
    rest: str               # operand list + attrs (raw tail of the line)
    is_root: bool = False


class Computation:
    def __init__(self, name: str):
        self.name = name
        self.ops: list[Op] = []
        self.symtab: dict[str, list] = {}


def parse_module(text: str) -> dict[str, "Computation"]:
    comps: dict[str, Computation] = {}
    cur: Computation | None = None
    entry_name = None
    for line in text.splitlines():
        if line.startswith(("%", "ENTRY")):
            m = _COMP_HDR.match(line)
            if m:
                cur = Computation(m.group(1))
                comps[cur.name] = cur
                if line.startswith("ENTRY"):
                    entry_name = cur.name
            continue
        if cur is None or not line.startswith(" "):
            continue
        if "/*" in line:  # tuple types embed /*index=N*/ comments
            line = re.sub(r"/\*.*?\*/", "", line)
        m = _OP_RE.match(line)
        if not m:
            continue
        name, type_str, opcode, rest = m.groups()
        shapes = _parse_shapes(type_str)
        op = Op(name, shapes, opcode, rest,
                is_root=line.lstrip().startswith("ROOT "))
        cur.ops.append(op)
        cur.symtab[name] = shapes
    comps["__entry__"] = comps.get(entry_name, Computation("__none__"))
    return comps


def _trip_count(comps, cond_name: str) -> int:
    cond = comps.get(cond_name)
    if cond is None:
        return 1
    best = 1
    for op in cond.ops:
        if op.opcode == "constant":
            # constant op: rest is "N)" (the raw tail after "constant(")
            m = re.match(r"(\d+)\)", op.rest.strip())
            if m:
                best = max(best, int(m.group(1)))
        for c in _CONST_INT.findall(op.rest):
            best = max(best, int(c))
    return best


def _dot_flops(op: Op, comp: Computation) -> float:
    result_elems = sum(math.prod(d) for _, d in op.shapes)
    mc = _LHS_CONTRACT.search(op.rest)
    if not mc:
        return 2.0 * result_elems  # dot with no contraction info
    cdims = [int(x) for x in mc.group(1).split(",") if x]
    operands = _OPERAND.findall(op.rest.split("),")[0] + ")")
    lhs_shape = None
    if operands:
        lhs_shape = comp.symtab.get(operands[0])
    if not lhs_shape or not lhs_shape[0][1]:
        return 2.0 * result_elems
    dims = lhs_shape[0][1]
    csize = math.prod(dims[i] for i in cdims if i < len(dims))
    return 2.0 * result_elems * csize


def _operand_names(op: Op) -> list[str]:
    head = op.rest
    close = head.find(")")
    frag = head[:close if close >= 0 else len(head)]
    return _OPERAND.findall(frag)


def _op_operand_bytes(op: Op, comp: Computation) -> int:
    total = 0
    for nm in _operand_names(op):
        shapes = comp.symtab.get(nm)
        if shapes:
            total += _nbytes(shapes)
    return total


# Ops whose HBM traffic is ~2x their RESULT (they read only the window they
# produce), not their (possibly huge) operand:
_SLICE_LIKE = {"dynamic-slice", "slice", "gather"}


def _op_traffic(op: Op, comp: Computation) -> int:
    """HBM bytes for one non-fusion op under the utilization model."""
    oc = op.opcode
    res = _nbytes(op.shapes)
    if oc in _SLICE_LIKE:
        return 2 * res
    if oc in ("dynamic-update-slice", "scatter"):
        # in-place: read+write the update window only (operand #1)
        ops_ = _operand_names(op)
        upd = _nbytes(comp.symtab.get(ops_[1], [])) if len(ops_) > 1 else res
        return 2 * upd
    return res + _op_operand_bytes(op, comp)


def _fusion_traffic(op: Op, comp: Computation, called: "Computation") -> int:
    """Fusion call-site traffic with operand-utilization awareness.

    Interior ops run in registers; what hits HBM is: each fusion parameter
    (fully, unless only consumed by slice-like interior ops — then just the
    windows), plus the fusion result (unless the root is a
    dynamic-update-slice — in-place window write).
    """
    # parameter(N) gives the call-site operand position — ops-list order is
    # NOT positional in scheduled HLO.
    indexed = []
    for o in called.ops:
        if o.opcode == "parameter":
            m = re.match(r"(\d+)\)", o.rest.strip())
            indexed.append((int(m.group(1)) if m else len(indexed), o.name))
    param_order = [name for _, name in sorted(indexed)]
    param_set = set(param_order)
    sliced_params: set[str] = set()
    full_params: set[str] = set()
    window_bytes = 0
    root_dus_update = None
    # Interior layout ops (bitcast/reshape/copy/transpose) are free inside a
    # kLoop fusion — treat them as transparent aliases of their operand so a
    # bitcast->dynamic-slice chain is credited as a window read, not a full
    # read of the (possibly huge) parameter.
    alias: dict[str, str] = {p: p for p in param_set}
    for iop in called.ops:
        if iop.opcode in ("bitcast", "reshape", "copy", "transpose"):
            src = _operand_names(iop)
            if src and src[0] in alias:
                alias[iop.name] = alias[src[0]]
    for iop in called.ops:
        if iop.opcode == "parameter":
            continue
        onames = [alias.get(n, n) for n in _operand_names(iop)]
        if iop.opcode in ("bitcast", "reshape", "copy", "transpose"):
            if onames and onames[0] in alias:
                continue  # transparent alias, handled at the consumer
        if iop.opcode in _SLICE_LIKE:
            for nm in onames[:1]:   # operand 0 is the sliced buffer
                if nm in param_set:
                    sliced_params.add(nm)
                    window_bytes += 2 * _nbytes(iop.shapes)
            for nm in onames[1:]:
                if nm in param_set:
                    full_params.add(nm)  # indices
            continue
        if iop.opcode == "dynamic-update-slice":
            upd = (_nbytes(called.symtab.get(onames[1], []))
                   if len(onames) > 1 else 0)
            if iop.is_root:
                root_dus_update = upd
            if onames and onames[0] in param_set:
                sliced_params.add(onames[0])  # in-place base
            window_bytes += upd
            for nm in onames[1:]:
                if nm in param_set:
                    full_params.add(nm)
            continue
        for nm in onames:
            if nm in param_set:
                full_params.add(nm)
    total = window_bytes
    # call-site operand shapes: positional match with interior parameters
    call_operands = _operand_names(op)
    for pname, oname in zip(param_order, call_operands):
        if pname in full_params or pname not in sliced_params:
            if pname in full_params:
                shapes = comp.symtab.get(oname)
                if shapes:
                    total += _nbytes(shapes)
    if root_dus_update is not None:
        total += root_dus_update
    else:
        total += _nbytes(op.shapes)
    return total


class Analyzer:
    def __init__(self, text: str):
        self.comps = parse_module(text)
        self._memo: dict[str, tuple] = {}

    def _cost(self, comp_name: str) -> tuple:
        """-> (flops, bytes, coll_dict)"""
        if comp_name in self._memo:
            return self._memo[comp_name]
        comp = self.comps.get(comp_name)
        zero = (0.0, 0.0, {k: 0.0 for k in _COLLECTIVES})
        if comp is None:
            return zero
        self._memo[comp_name] = zero  # cycle guard
        flops, bts = 0.0, 0.0
        coll = {k: 0.0 for k in _COLLECTIVES}

        for op in comp.ops:
            oc = op.opcode
            base = oc[:-6] if oc.endswith("-start") else oc
            if oc in _FREE_OPS or oc.endswith("-done"):
                continue
            if oc == "while":
                body = _CALL_ATTR.search(op.rest)
                cond = _COND_ATTR.search(op.rest)
                trips = _trip_count(self.comps, cond.group(1)) if cond else 1
                if body:
                    f, b, c = self._cost(body.group(1))
                    flops += f * trips
                    bts += b * trips
                    for k in coll:
                        coll[k] += c[k] * trips
                continue
            if oc == "conditional":
                names = []
                mb = _BRANCHES.search(op.rest)
                if mb:
                    names = [n.strip().lstrip("%") for n in
                             mb.group(1).split(",")]
                else:
                    names = [m for m in _CALL_ATTR.findall(op.rest)]
                if names:
                    subs = [self._cost(n) for n in names]
                    flops += max(s[0] for s in subs)
                    bts += max(s[1] for s in subs)
                    for k in coll:
                        coll[k] += max(s[2][k] for s in subs)
                continue
            if oc in ("call", "async-start"):
                cal = _CALL_ATTR.search(op.rest)
                if cal:
                    f, b, c = self._cost(cal.group(1))
                    flops += f
                    bts += b
                    for k in coll:
                        coll[k] += c[k]
                continue
            if base in _COLLECTIVES:
                size = _nbytes(op.shapes)
                factor = 1.0
                if base == "all-reduce":
                    factor = 2.0
                elif base == "reduce-scatter":
                    g = _GROUPS_PAIR.search(op.rest)
                    if g:
                        factor = float(g.group(2))
                    else:
                        gb = _GROUPS_BRACE.search(op.rest)
                        factor = float(len(gb.group(1).split(","))) if gb \
                            else 2.0
                coll[base] += size * factor
                bts += _nbytes(op.shapes) + _op_operand_bytes(op, comp)
                continue
            if oc == "dot":
                flops += _dot_flops(op, comp)
                bts += _nbytes(op.shapes) + _op_operand_bytes(op, comp)
                continue
            if oc == "fusion":
                # count interior dots (XLA occasionally fuses small dots)
                cal = _CALL_ATTR.search(op.rest)
                called = self.comps.get(cal.group(1)) if cal else None
                if called is not None:
                    f, _, c = self._cost(cal.group(1))
                    flops += f
                    for k in coll:
                        coll[k] += c[k]
                    bts += _fusion_traffic(op, comp, called)
                else:
                    bts += _nbytes(op.shapes) + _op_operand_bytes(op, comp)
                continue
            # generic op: utilization-aware memory traffic
            bts += _op_traffic(op, comp)

        out = (flops, bts, coll)
        self._memo[comp_name] = out
        return out

    def totals(self) -> dict:
        f, b, c = self._cost("__entry__")
        return {"flops": f, "bytes": b,
                "collectives": {**c, "total": sum(c.values())}}


def analyze(text: str) -> dict:
    return Analyzer(text).totals()

