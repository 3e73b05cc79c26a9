"""Trip-count-aware cost analysis over compiled (post-SPMD) HLO text.

XLA's built-in cost_analysis counts while-loop bodies ONCE — useless for
scan-over-layers programs (a 48-layer model reports ~1/48th of its FLOPs).
This module parses ``compiled.as_text()`` into computations, resolves the
call graph (while/fusion/call/conditional) with loop trip counts recovered
from lax.scan's canonical induction structure, and accumulates:

  * flops        — dot_general (from shapes + dnums) + elementwise
  * bytes        — HBM-traffic model identical in spirit to XLA's: at each
                   computation's top level, operand bytes + output bytes per
                   op; fusion internals are free (one kernel = one read of its
                   params + one write of its outputs); gather/dynamic-slice
                   read only what they produce; scatter/DUS write the update
                   region, not the whole buffer
  * collectives  — per kind: count, output bytes, wire bytes (ring formulas),
                   each weighted by its computation's execution multiplier

Trip counts: a while cond of the form ``compare(gte(param), constant(N)),
direction=LT`` with a 0-initialized induction var (lax.scan canonical) gives
N.  Unrecognized conditions get multiplier 1 and are recorded in .warnings.
"""
from __future__ import annotations

import math
import re
from collections import defaultdict
from dataclasses import dataclass, field

_DTYPE_BYTES = {
    "pred": 1, "s2": 0.25, "u2": 0.25, "s4": 0.5, "u4": 0.5,
    "s8": 1, "u8": 1, "s16": 2, "u16": 2, "bf16": 2, "f16": 2,
    "s32": 4, "u32": 4, "f32": 4, "s64": 8, "u64": 8, "f64": 8,
    "c64": 8, "c128": 16, "f8e4m3fn": 1, "f8e5m2": 1, "f8e4m3b11fnuz": 1,
    "token": 0, "opaque": 0,
}

# ops whose "flops" ~ elements of output (XLA counts transcendentals as >1;
# close enough for roofline purposes)
_ELEMENTWISE = {
    "add", "subtract", "multiply", "divide", "maximum", "minimum", "abs",
    "negate", "exponential", "log", "tanh", "rsqrt", "sqrt", "power",
    "logistic", "cosine", "sine", "floor", "ceil", "round-nearest-afz",
    "compare", "select", "and", "or", "xor", "not", "clamp", "atan2",
    "remainder", "sign", "expm1", "log1p", "cbrt", "erf",
    "shift-left", "shift-right-logical", "shift-right-arithmetic",
    "convert", "reduce", "exponential-minus-one",
}

_GATHERISH = {"gather", "dynamic-slice"}
_SCATTERISH = {"scatter", "dynamic-update-slice"}
_COLLECTIVES = ("all-gather", "all-reduce", "reduce-scatter", "all-to-all",
                "collective-permute")
_FREE = {"parameter", "constant", "get-tuple-element", "tuple", "bitcast",
         "after-all", "rng", "partition-id",
         "replica-id", "custom-call", "reduce-window", "while", "fusion",
         "call", "conditional", "sort", "map", "reduce-precision",
         "optimization-barrier", "copy-start", "copy-done", "domain",
         "send", "recv", "infeed", "outfeed"}

# unfused data-movement ops in a scheduled module are real kernels:
# read input, write output (iota/broadcast write-only)
_MATERIALIZE = {"copy", "transpose", "reshape", "concatenate", "slice",
                "pad", "reverse"}
_WRITE_ONLY = {"iota", "broadcast"}

_SHAPE_TOKEN = re.compile(r"([a-z][a-z0-9]*)\[([\d,]*)\]")
_COMMENT = re.compile(r"/\*.*?\*/")
# name = <type> kind(args...   — type is either a (tuple, ...) or one token;
# a TPU layout in a tuple nests one level of parens: s32[8]{0:T(128)}
_OP_LINE = re.compile(
    r"^\s*(?:ROOT\s+)?%?([\w\.\-]+)\s*=\s*(\((?:[^()]|\([^()]*\))*\)|\S+)"
    r"\s+([\w\-]+)\((.*)$")
_CALLED = re.compile(
    r"(?:condition|body|calls|to_apply|true_computation|"
    r"false_computation|branch_computations)=\{?%?([\w\.\-]+)")
_BRANCHES = re.compile(r"branch_computations=\{([^}]*)\}")
_GROUPS_IOTA = re.compile(r"replica_groups=\[(\d+),(\d+)\]")
_GROUPS_BRACE = re.compile(r"replica_groups=\{\{([^}]*)\}")
_CONTRACT = re.compile(r"lhs_contracting_dims=\{([\d,]*)\}")
_COMP_HEADER = re.compile(r"^(ENTRY\s+)?%?([\w\.\-]+)\s*\(.*\)\s*->.*\{")
_TRIP_COUNT = re.compile(r'"known_trip_count":\s*\{\s*"n":\s*"(\d+)"')


def _parse_shape(s: str):
    """'f32[16,512]{1,0}' or tuple '(f32[2], s32[])' -> list[(dtype, dims)]."""
    out = []
    for m in _SHAPE_TOKEN.finditer(s):
        dt, dims = m.group(1), m.group(2)
        if dt not in _DTYPE_BYTES:
            continue
        shape = tuple(int(d) for d in dims.split(",")) if dims else ()
        out.append((dt, shape))
    return out


def _nbytes(shapes) -> float:
    return sum(_DTYPE_BYTES[dt] * math.prod(sh) for dt, sh in shapes)


def _nelems(shapes) -> float:
    return sum(math.prod(sh) for _, sh in shapes)


@dataclass
class OpInfo:
    name: str
    kind: str
    out_shapes: list
    line: str
    operands: list[str] = field(default_factory=list)
    called: list[str] = field(default_factory=list)


@dataclass
class Computation:
    name: str
    ops: dict = field(default_factory=dict)       # name -> OpInfo
    order: list = field(default_factory=list)


_OPERAND_NAME = re.compile(r"%([\w\.\-]+)")


def _split_computations(text: str) -> tuple[dict[str, Computation], str | None]:
    comps: dict[str, Computation] = {}
    cur: Computation | None = None
    entry = None
    for line in text.splitlines():
        stripped = line.strip()
        if not stripped or stripped.startswith("//"):
            continue
        if not line.startswith(" ") and "->" in line and \
                stripped.endswith("{"):
            m = _COMP_HEADER.match(stripped)
            if m:
                cur = Computation(m.group(2))
                comps[cur.name] = cur
                if m.group(1):
                    entry = cur.name
                continue
        if cur is None:
            continue
        if stripped == "}":
            continue
        m = _OP_LINE.match(_COMMENT.sub("", line))
        if not m:
            continue
        name, shape_s, kind, rest = m.groups()
        info = OpInfo(name=name, kind=kind, out_shapes=_parse_shape(shape_s),
                      line=stripped)
        # operands: up to the closing paren of the op call
        depth = 1
        arg_str = []
        for ch in rest:
            if ch == "(":
                depth += 1
            elif ch == ")":
                depth -= 1
                if depth == 0:
                    break
            arg_str.append(ch)
        info.operands = _OPERAND_NAME.findall("".join(arg_str))
        bm = _BRANCHES.search(stripped)
        if bm:
            info.called = _OPERAND_NAME.findall(bm.group(1))
        else:
            info.called = _CALLED.findall(stripped)
        cur.ops[name] = info
        cur.order.append(name)
    return comps, entry


def _dot_flops(info: OpInfo, comp: Computation) -> float:
    out_elems = _nelems(info.out_shapes)
    m = _CONTRACT.search(info.line)
    contract = 1.0
    if m and info.operands:
        lhs = comp.ops.get(info.operands[0])
        if lhs is not None and lhs.out_shapes:
            dims = lhs.out_shapes[0][1]
            for d in m.group(1).split(","):
                if d.strip():
                    i = int(d)
                    if i < len(dims):
                        contract *= dims[i]
    return 2.0 * out_elems * contract


def _trip_count(cond: Computation) -> float | None:
    """lax.scan canonical: compare(gte, constant(N)), direction=LT."""
    consts = {}
    for name in cond.order:
        op = cond.ops[name]
        if op.kind == "constant":
            cm = re.search(r"constant\((-?\d+)\)", op.line)
            if cm:
                consts[name] = int(cm.group(1))
    for name in reversed(cond.order):
        op = cond.ops[name]
        if op.kind == "compare" and "direction=LT" in op.line:
            for o in op.operands:
                if o in consts:
                    return float(consts[o])
    return None


@dataclass
class HloCost:
    flops: float = 0.0
    bytes: float = 0.0
    coll_counts: dict = field(default_factory=lambda: defaultdict(float))
    coll_bytes: dict = field(default_factory=lambda: defaultdict(float))
    coll_wire_bytes: float = 0.0
    warnings: list = field(default_factory=list)
    bytes_by_kind: dict = field(default_factory=lambda: defaultdict(float))
    coll_ops: dict = field(default_factory=lambda: defaultdict(float))
    fusion_ops: dict = field(default_factory=lambda: defaultdict(float))

    def add_bytes(self, kind: str, b: float):
        self.bytes += b
        self.bytes_by_kind[kind] += b

    def add(self, o: "HloCost", k: float = 1.0):
        self.flops += o.flops * k
        self.bytes += o.bytes * k
        self.coll_wire_bytes += o.coll_wire_bytes * k
        for kk, v in o.coll_counts.items():
            self.coll_counts[kk] += v * k
        for kk, v in o.coll_bytes.items():
            self.coll_bytes[kk] += v * k
        for kk, v in o.bytes_by_kind.items():
            self.bytes_by_kind[kk] += v * k
        for kk, v in o.coll_ops.items():
            self.coll_ops[kk] += v * k
        for kk, v in o.fusion_ops.items():
            self.fusion_ops[kk] += v * k
        self.warnings += o.warnings


def _group_size(line: str) -> int:
    m = _GROUPS_IOTA.search(line)
    if m:
        return max(1, int(m.group(2)))
    m = _GROUPS_BRACE.search(line)
    if m:
        return max(1, len([x for x in m.group(1).split(",") if x.strip()]))
    return 1


def _op_bytes(info: OpInfo, comp: Computation) -> float:
    out_b = _nbytes(info.out_shapes)
    if info.kind in _GATHERISH:
        return 2 * out_b                      # read what you produce + write
    if info.kind in _SCATTERISH:
        upd = 0.0
        if len(info.operands) >= 2:
            u = comp.ops.get(info.operands[-1]) or comp.ops.get(
                info.operands[1])
            if u is not None:
                upd = _nbytes(u.out_shapes)
        return 2 * upd + 0.0                  # read+write the update region
    opb = 0.0
    for o in info.operands:
        src = comp.ops.get(o)
        if src is not None:
            opb += _nbytes(src.out_shapes)
    return opb + out_b


class HloCostModel:
    def __init__(self, text: str):
        self.comps, self.entry = _split_computations(text)
        self._memo: dict[str, HloCost] = {}
        if self.entry is None:                # fall back: main-ish name
            for n in self.comps:
                if "main" in n:
                    self.entry = n
        assert self.entry, "no ENTRY computation found"

    def cost(self) -> HloCost:
        return self._comp_cost(self.entry)

    def _comp_cost(self, name: str) -> HloCost:
        if name in self._memo:
            return self._memo[name]
        comp = self.comps.get(name)
        total = HloCost()
        if comp is None:
            return total
        self._memo[name] = total              # break cycles defensively
        for op_name in comp.order:
            info = comp.ops[op_name]
            k = info.kind
            if k == "while":
                called = [c for c in info.called if c in self.comps]
                cond = called[0] if called else None     # condition=, body=
                body = called[1] if len(called) > 1 else None
                tm = _TRIP_COUNT.search(info.line)       # XLA's annotation
                trips = float(tm.group(1)) if tm else None
                if trips is None and cond:
                    trips = self._trips(cond)
                if trips is None:
                    trips = 1.0
                    total.warnings.append(f"unknown trip count: {op_name}")
                if body:
                    total.add(self._comp_cost(body), trips)
                if cond:
                    total.add(self._comp_cost(cond), trips)
            elif k == "fusion":
                # fusion = one kernel: internal flops/collectives count,
                # internal byte traffic is free (stays in registers/VMEM)
                ccomp = None
                for c in info.called:
                    if c in self.comps:
                        sub = self._comp_cost(c)
                        total.add(sub, 1.0)
                        total.bytes -= sub.bytes          # undo internals
                        for kk, v in sub.bytes_by_kind.items():
                            total.bytes_by_kind[kk] -= v
                        ccomp = ccomp or self.comps[c]
                fb = _fusion_bytes(info, comp, ccomp)
                total.add_bytes("fusion", fb)
                sig = ",".join(f"{dt}[{'x'.join(map(str, sh))}]"
                               for dt, sh in info.out_shapes[:2])
                total.fusion_ops[sig] += fb
            elif k in ("call", "conditional", "map", "sort",
                       "select-and-scatter", "async-start", "custom-call"):
                for c in info.called:
                    if c in self.comps:
                        total.add(self._comp_cost(c), 1.0)
            elif any(k.startswith(c) for c in _COLLECTIVES):
                if k.endswith("-done"):
                    continue
                kind = next(c for c in _COLLECTIVES if k.startswith(c))
                nb = _nbytes(info.out_shapes)
                g = _group_size(info.line)
                total.coll_counts[kind] += 1
                total.coll_bytes[kind] += nb
                total.coll_wire_bytes += _wire_bytes(kind, nb, g)
                total.add_bytes("collective", 2 * nb)
                sig = f"{kind} g{g} " + ",".join(
                    f"{dt}[{'x'.join(map(str, sh))}]"
                    for dt, sh in info.out_shapes[:2])
                total.coll_ops[sig] += _wire_bytes(kind, nb, g)
            elif k == "dot":
                total.flops += _dot_flops(info, comp)
                total.add_bytes("dot", _op_bytes(info, comp))
            elif k == "convolution":
                total.flops += 2 * _nelems(info.out_shapes) * 128  # coarse
                total.add_bytes("conv", _op_bytes(info, comp))
            elif k in ("reduce", "reduce-window"):
                opb = 0.0
                for o in info.operands:
                    src = comp.ops.get(o)
                    if src is not None:
                        opb += _nelems(src.out_shapes)
                total.flops += opb
                total.add_bytes("reduce", _op_bytes(info, comp))
            elif k == "scatter":
                total.add_bytes("scatter", _op_bytes(info, comp))
            elif k in _ELEMENTWISE:
                total.flops += _nelems(info.out_shapes)
                total.add_bytes("elementwise", _op_bytes(info, comp))
            elif k in _GATHERISH:
                total.add_bytes("gather", _op_bytes(info, comp))
            elif k in _MATERIALIZE:
                total.add_bytes("datamove", 2 * _nbytes(info.out_shapes))
            elif k in _WRITE_ONLY:
                total.add_bytes("datamove", _nbytes(info.out_shapes))
            elif k in _FREE:
                continue
            else:
                total.add_bytes("other", _op_bytes(info, comp))
        return total

    def _trips(self, cond_name: str) -> float | None:
        comp = self.comps.get(cond_name)
        return _trip_count(comp) if comp else None


def _wire_bytes(kind: str, nbytes: float, group: int) -> float:
    if group <= 1:
        return 0.0
    g = group
    if kind == "all-reduce":
        return 2 * nbytes * (g - 1) / g
    if kind == "collective-permute":
        return nbytes
    return nbytes * (g - 1) / g


def _op_bytes_fusion(info: OpInfo, comp: Computation) -> float:
    """fusion = one kernel: reads its operands, writes its outputs."""
    opb = 0.0
    for o in info.operands:
        src = comp.ops.get(o)
        if src is not None:
            opb += _nbytes(src.out_shapes)
    return opb + _nbytes(info.out_shapes)


_PARAM_NUM = re.compile(r"parameter\((\d+)\)")


def _fusion_bytes(info: OpInfo, comp: Computation,
                  ccomp: Computation | None) -> float:
    """HBM traffic of one fused kernel, recognizing the two indexed-access
    patterns that dominate scan-over-layers programs:

      * a fusion parameter consumed ONLY by dynamic-slice/gather reads just
        the produced slice, not the whole buffer (remat-stack reads);
      * a fusion containing dynamic-update-slice writes the update region in
        place — the big aliased buffer is neither fully read nor fully
        rewritten (remat-stack writes, KV-cache appends).
    """
    if ccomp is None:
        return _op_bytes_fusion(info, comp)
    out_b = _nbytes(info.out_shapes)
    # param index -> op, consumer map
    params: dict[int, OpInfo] = {}
    consumers: dict[str, list[OpInfo]] = defaultdict(list)
    dus_update_bytes = 0.0
    has_dus = False
    for on in ccomp.order:
        op = ccomp.ops[on]
        if op.kind == "parameter":
            pm = _PARAM_NUM.search(op.line)
            if pm:
                params[int(pm.group(1))] = op
        for o in op.operands:
            consumers[o].append(op)
        if op.kind == "dynamic-update-slice":
            has_dus = True
            if len(op.operands) >= 2:
                upd = ccomp.ops.get(op.operands[1])
                if upd is not None:
                    dus_update_bytes += _nbytes(upd.out_shapes)

    def effective(cons, depth=0):
        """Chase consumers through convert/bitcast/copy: CPU legalization
        wraps bf16 dot/DUS operands in f32 converts that do not exist on the
        TPU target (the MXU consumes bf16 natively) — the *indexed-access*
        structure is what matters for HBM traffic."""
        out = []
        for c in cons:
            if c.kind in ("convert", "bitcast", "copy") and depth < 4:
                nxt = consumers.get(c.name, [])
                out += effective(nxt, depth + 1) if nxt else [c]
            else:
                out.append(c)
        return out

    # elements (not bytes) compare across dtypes (converts change byte size)
    out_elems_each = [math.prod(sh) for _, sh in info.out_shapes]

    total = 0.0
    inplace_bytes = 0.0
    for idx, p_op in params.items():
        p_bytes = _nbytes(p_op.out_shapes)
        p_elems = _nelems(p_op.out_shapes)
        cons = effective(consumers.get(p_op.name, []))
        if cons and all(c.kind in ("dynamic-slice", "gather") for c in cons):
            total += sum(_nbytes(c.out_shapes) for c in cons)
        elif (has_dus and p_elems
              and any(abs(p_elems - oe) < 1e-6 for oe in out_elems_each)
              and any(c.kind == "dynamic-update-slice" for c in cons)):
            # in-place update of an aliased big buffer (possibly one element
            # of a tuple output): write only the update region
            inplace_bytes += p_bytes
        else:
            total += p_bytes
    if inplace_bytes:
        total += 2 * dus_update_bytes           # read+write update regions
        total += max(0.0, out_b - inplace_bytes)  # non-aliased outputs
    else:
        total += out_b
    return total


def analyze_hlo(text: str) -> HloCost:
    return HloCostModel(text).cost()
