"""Static analyzer for post-SPMD optimized HLO text.

``compiled.cost_analysis()`` counts each while-loop (scan) body ONCE, which
undercounts FLOPs/bytes/collectives for scan-over-layers models by ~L×.
This module parses the HLO module text, resolves computation call graphs
(while / fusion / call / conditional), multiplies while bodies by their trip
counts (extracted from the loop-condition constants), and accumulates:

  * flops       — dot (2·M·N·K) and convolution ops,
  * hbm_bytes   — operand+result bytes at fusion boundaries (the XLA
                  bytes-accessed convention),
  * coll        — per-collective-type bytes, result-shape sized
                  (all-reduce ×2 for the reduce+broadcast halves),
  * scatter     — result bytes materialized through generic scatter ops
                  (op, fusion root, or a backend scatter-expander while
                  loop identified by op_name metadata; the TL reassembly
                  assertion).

All values describe the per-device SPMD program.
"""
from __future__ import annotations

import re
from dataclasses import dataclass, field
from typing import Dict, List, Optional, Tuple

_DTYPE_BYTES = {
    "pred": 1, "s8": 1, "u8": 1, "s4": 1, "u4": 1, "s16": 2, "u16": 2,
    "bf16": 2, "f16": 2, "s32": 4, "u32": 4, "f32": 4, "s64": 8, "u64": 8,
    "f64": 8, "c64": 8, "c128": 16, "token": 0, "f8e4m3fn": 1, "f8e5m2": 1,
}

_SHAPE_RE = re.compile(
    r"(pred|s4|u4|s8|u8|s16|u16|bf16|f16|s32|u32|f32|s64|u64|f64|c64|c128|"
    r"f8e4m3fn|f8e5m2|token)\[([0-9,]*)\]")

_INSTR_RE = re.compile(
    r"^\s*(?:ROOT\s+)?%([\w.\-]+)\s*=\s*(\([^)]*\)|\S+)"
    r"\s+([a-z][\w\-]*)\((.*)$")
_COMMENT_RE = re.compile(r"/\*.*?\*/")
_COMP_HDR_RE = re.compile(r"^\s*(?:ENTRY\s+)?%([\w.\-]+)\s*\(")
_ATTR_COMP_RE = re.compile(r"(condition|body|to_apply|calls)=%([\w.\-]+)")
_BRANCHES_RE = re.compile(r"branch_computations=\{([^}]*)\}")


def _shape_elems_bytes(shape_str: str) -> Tuple[int, int]:
    elems = 0
    nbytes = 0
    for m in _SHAPE_RE.finditer(shape_str):
        dt, dims = m.group(1), m.group(2)
        n = 1
        if dims:
            for d in dims.split(","):
                n *= int(d)
        elems += n
        nbytes += n * _DTYPE_BYTES[dt]
    return elems, nbytes


@dataclass
class Instr:
    name: str
    shape: str
    op: str
    rest: str


@dataclass
class Computation:
    name: str
    instrs: List[Instr] = field(default_factory=list)
    shapes: Dict[str, str] = field(default_factory=dict)


def parse_module(text: str) -> Dict[str, Computation]:
    comps: Dict[str, Computation] = {}
    cur: Optional[Computation] = None
    for raw in text.splitlines():
        line = _COMMENT_RE.sub("", raw).rstrip()
        stripped = line.strip()
        if not stripped:
            continue
        # computation header: "%name (params) -> shape {" or "ENTRY %name ... {"
        if stripped.endswith("{") and "=" not in stripped.split("->")[0] \
                and ("->" in stripped or stripped.startswith("ENTRY")):
            m = _COMP_HDR_RE.match(stripped)
            if m:
                cur = Computation(m.group(1))
                comps[cur.name] = cur
                continue
        if stripped == "}":
            cur = None
            continue
        if cur is None:
            continue
        m = _INSTR_RE.match(line)
        if m:
            name, shape, op, rest = m.groups()
            ins = Instr(name, shape, op, rest)
            cur.instrs.append(ins)
            cur.shapes[name] = shape
        else:
            # parameters: "%p = f32[..] parameter(0)" matches; constants with
            # array payloads may not — record shapes anyway
            m2 = re.match(r"^\s*(?:ROOT\s+)?%([\w.\-]+)\s*=\s*"
                          r"(\([^)]*\)|\S+)\s+(\S+)", line)
            if m2:
                cur.shapes[m2.group(1)] = m2.group(2)
    return comps


def _parse_operands(rest: str) -> List[str]:
    depth = 1
    arg = ""
    args: List[str] = []
    for ch in rest:
        if ch == "(":
            depth += 1
            arg += ch
        elif ch == ")":
            depth -= 1
            if depth == 0:
                if arg.strip():
                    args.append(arg)
                break
            arg += ch
        elif ch == "," and depth == 1:
            args.append(arg)
            arg = ""
        else:
            arg += ch
    names = []
    for a in args:
        # operands may be bare ("%copy.10") or typed
        # ("f32[32,64]{1,0} %copy.10") depending on the XLA version
        m = re.search(r"%([\w.\-]+)", a)
        if m:
            names.append(m.group(1))
    return names


def _dot_flops(instr: Instr, comp: Computation) -> float:
    res_elems, _ = _shape_elems_bytes(instr.shape)
    ops = _parse_operands(instr.rest)
    if not ops:
        return 0.0
    lhs_shape = comp.shapes.get(ops[0], "")
    m = re.search(r"lhs_contracting_dims=\{([0-9,]*)\}", instr.rest)
    if not m or not lhs_shape:
        return 2.0 * res_elems
    dims_m = _SHAPE_RE.search(lhs_shape)
    if not dims_m:
        return 2.0 * res_elems
    lhs_dims = [int(d) for d in dims_m.group(2).split(",")] if dims_m.group(2) else []
    k = 1
    for ci in m.group(1).split(","):
        if ci != "" and int(ci) < len(lhs_dims):
            k *= lhs_dims[int(ci)]
    return 2.0 * res_elems * k


def _conv_flops(instr: Instr, comp: Computation) -> float:
    res_elems, _ = _shape_elems_bytes(instr.shape)
    ops = _parse_operands(instr.rest)
    if len(ops) < 2:
        return 2.0 * res_elems
    k_elems, _ = _shape_elems_bytes(comp.shapes.get(ops[1], ""))
    res_dims_m = _SHAPE_RE.search(instr.shape)
    out_feat = 1
    if res_dims_m and res_dims_m.group(2):
        out_feat = int(res_dims_m.group(2).split(",")[-1])
    return 2.0 * res_elems * max(k_elems // max(out_feat, 1), 1)


def _comp_constants_s32(comp: Computation, comps, depth=0) -> List[int]:
    vals: List[int] = []
    if depth > 3 or comp is None:
        return vals
    for ins in comp.instrs:
        if ins.op == "constant" and ins.shape.startswith("s32"):
            m = re.search(r"^(-?\d+)", ins.rest)
            if m:
                vals.append(int(m.group(1)))
        for key, name in _ATTR_COMP_RE.findall(ins.rest):
            vals.extend(_comp_constants_s32(comps.get(name), comps, depth + 1))
    return vals


def _trip_count(cond: Computation, comps) -> int:
    vals = [v for v in _comp_constants_s32(cond, comps) if v > 0]
    return max(vals) if vals else 1


_COLLECTIVES = ("all-reduce", "all-gather", "reduce-scatter", "all-to-all",
                "collective-permute")

def _max_tensor_bytes(shape_str: str) -> int:
    """Largest single tensor in an HLO shape string — for a variadic
    scatter's tuple result, its largest scattered buffer."""
    best = 0
    for m in _SHAPE_RE.finditer(shape_str):
        dt, dims = m.group(1), m.group(2)
        n = 1
        if dims:
            for d in dims.split(","):
                n *= int(d)
        best = max(best, n * _DTYPE_BYTES[dt])
    return best

_HBM_OPS = {"fusion", "dot", "convolution", "custom-call", "scatter",
            "gather", "sort", "reduce", "dynamic-slice",
            "dynamic-update-slice", "copy", "transpose", "broadcast",
            "concatenate", "reshape", "slice", "pad", "iota", "select",
            "add", "multiply", "tanh", "exponential", "rsqrt", "compare"}


@dataclass
class Costs:
    flops: float = 0.0
    hbm_bytes: float = 0.0
    coll: Dict[str, float] = field(default_factory=dict)
    # generic-scatter accounting: how much result data the module
    # materializes through XLA scatter ops (op or fusion root).  The TL
    # reassembly optimization is asserted on exactly this: the Pallas
    # vb_scatter path must drive scatter_bytes on the fused step to zero.
    scatter_bytes: float = 0.0
    n_scatter: float = 0.0

    def scaled(self, k: float) -> "Costs":
        return Costs(self.flops * k, self.hbm_bytes * k,
                     {t: v * k for t, v in self.coll.items()},
                     self.scatter_bytes * k, self.n_scatter * k)

    def add(self, other: "Costs"):
        self.flops += other.flops
        self.hbm_bytes += other.hbm_bytes
        for t, v in other.coll.items():
            self.coll[t] = self.coll.get(t, 0.0) + v
        self.scatter_bytes += other.scatter_bytes
        self.n_scatter += other.n_scatter

    @property
    def coll_total(self) -> float:
        return sum(self.coll.values())


def _fusion_root_op(comp_name: str, comps) -> str:
    comp = comps.get(comp_name)
    if comp and comp.instrs:
        return comp.instrs[-1].op          # ROOT is last
    return ""


def _instr_hbm_bytes(ins: Instr, comp: Computation, comps) -> float:
    """HBM traffic estimate for one instruction.

    Convention: result + operand bytes at fusion boundaries, EXCEPT
    slice-like ops — a dynamic-slice reads only the slice (2× slice bytes),
    a dynamic-update-slice writes only the update region in place (2× update
    bytes).  Without this, scan-carried buffers (KV caches, stacked layer
    params) get charged their full size once per layer per step — orders of
    magnitude above real traffic.
    """
    op = ins.op
    root = op
    attrs = dict(_ATTR_COMP_RE.findall(ins.rest))
    if op == "fusion" and "calls" in attrs:
        root = _fusion_root_op(attrs["calls"], comps)

    _, rb = _shape_elems_bytes(ins.shape)
    operands = _parse_operands(ins.rest)
    ob_list = []
    for name in operands:
        _, b = _shape_elems_bytes(comp.shapes.get(name, ""))
        ob_list.append(b)

    if root == "dynamic-update-slice":
        # in-place: traffic = update region both ways; the big buffer operand
        # and the identically-shaped result alias
        upd = sorted(ob_list)[:-1] if len(ob_list) > 1 else ob_list
        return 2.0 * sum(upd)
    if root in ("dynamic-slice", "slice", "gather"):
        return 2.0 * rb + sum(b for b in ob_list if b <= 8 * rb)
    if root == "scatter":
        big = max(ob_list) if ob_list else 0
        return rb + sum(ob_list) - big + 2.0 * (rb if big > 8 * rb else big)
    return rb + sum(ob_list)


def analyze(text: str) -> Costs:
    comps = parse_module(text)
    memo: Dict[str, Costs] = {}
    called = set()
    for c in comps.values():
        for ins in c.instrs:
            for _, name in _ATTR_COMP_RE.findall(ins.rest):
                called.add(name)
            bm = _BRANCHES_RE.search(ins.rest)
            if bm:
                for n in bm.group(1).split(","):
                    called.add(n.strip().lstrip("%"))

    def cost_of(comp_name: str) -> Costs:
        if comp_name in memo:
            return memo[comp_name]
        comp = comps.get(comp_name)
        out = Costs()
        memo[comp_name] = out
        if comp is None:
            return out
        for ins in comp.instrs:
            op = ins.op
            if op.endswith("-done"):
                continue                     # paired with -start; skip
            attrs = dict(_ATTR_COMP_RE.findall(ins.rest))
            if op == "dot":
                out.flops += _dot_flops(ins, comp)
            elif op == "convolution":
                out.flops += _conv_flops(ins, comp)
            elif op == "while":
                trips = 1
                if "condition" in attrs and attrs["condition"] in comps:
                    trips = _trip_count(comps[attrs["condition"]], comps)
                if "body" in attrs:
                    out.add(cost_of(attrs["body"]).scaled(trips))
            elif op == "conditional":
                bm = _BRANCHES_RE.search(ins.rest)
                if bm:
                    branch_costs = [cost_of(n.strip().lstrip("%"))
                                    for n in bm.group(1).split(",")]
                    if branch_costs:
                        big = max(branch_costs, key=lambda c: c.flops)
                        out.add(big)
            else:
                for key in ("calls", "to_apply"):
                    if key in attrs:
                        out.add(cost_of(attrs[key]))

            # a scatter inside a fusion is counted where it sits: the
            # fused computation is walked through its ``calls`` above
            if op == "scatter":
                out.n_scatter += 1
                out.scatter_bytes += _max_tensor_bytes(ins.shape)

            is_coll = any(op.startswith(c) for c in _COLLECTIVES) \
                and not op.endswith("-done")
            if is_coll:
                _, nb = _shape_elems_bytes(ins.shape)
                ctype = next(c for c in _COLLECTIVES if op.startswith(c))
                if ctype == "all-reduce":
                    nb *= 2
                out.coll[ctype] = out.coll.get(ctype, 0.0) + nb

            if op in _HBM_OPS or is_coll:
                out.hbm_bytes += _instr_hbm_bytes(ins, comp, comps)
        return out

    entries = [n for n in comps if n not in called]
    total = Costs()
    for e in entries:
        total.add(cost_of(e))
    return total
