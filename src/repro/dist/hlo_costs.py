"""Trip-count-exact cost attribution over optimized HLO text.

XLA's ``compiled.cost_analysis()`` counts a while-loop body **once**, which
under-reports any scanned layer stack by the trip count (an 80-layer model
shows up as one period).  This parser walks the module's call graph —
fusions, calls, conditionals, and while loops — multiplying each
computation's cost by the product of enclosing static trip counts, read
from XLA's ``backend_config={"known_trip_count":{"n":...}}`` annotation
(with a fallback to the loop condition's ``LT`` bound).

Cost model per instruction:

* flops: ``dot`` = 2 * out_elems * contraction_size (from
  ``lhs_contracting_dims`` and the lhs operand shape); ``convolution`` =
  2 * out_elems * kernel_elems / out_features.  Elementwise ops are not
  counted — matmul-class flops are what the roofline compares against peak.
* bytes: operand bytes + output bytes for every materializing instruction.
  Fusion *interiors* are excluded (fused intermediates never touch HBM);
  the fusion's own boundary operands/outputs are what counts.
* collectives: operand bytes plus a ring-model wire estimate per kind
  (all-reduce 2(n-1)/n, all-gather/reduce-scatter/all-to-all (n-1)/n,
  collective-permute 1x), with n = replica-group size.
"""
from __future__ import annotations

import dataclasses
import math
import re
from typing import Dict, List, Optional, Tuple

_DTYPE_BYTES = {
    "pred": 1, "s2": 1, "s4": 1, "s8": 1, "u2": 1, "u4": 1, "u8": 1,
    "f8e4m3fn": 1, "f8e5m2": 1, "f8e4m3b11fnuz": 1, "f8e5m2fnuz": 1,
    "f8e4m3fnuz": 1, "f4e2m1fn": 1,
    "s16": 2, "u16": 2, "f16": 2, "bf16": 2,
    "s32": 4, "u32": 4, "f32": 4,
    "s64": 8, "u64": 8, "f64": 8, "c64": 8,
    "c128": 16,
}

_SHAPE_RE = re.compile(
    r"\b(" + "|".join(sorted(_DTYPE_BYTES, key=len, reverse=True))
    + r")\[([0-9,]*)\]")

_COLLECTIVE_KINDS = (
    "all-reduce", "all-gather", "reduce-scatter", "all-to-all",
    "collective-permute", "collective-broadcast", "ragged-all-to-all",
)

# bytes that cross a link per participating device, ring algorithm, as a
# multiple of the payload (n = replica-group size)
_WIRE_FACTOR = {
    "all-reduce": lambda n: 2.0 * (n - 1) / n,
    "all-gather": lambda n: (n - 1) / n,
    "reduce-scatter": lambda n: (n - 1) / n,
    "all-to-all": lambda n: (n - 1) / n,
    "ragged-all-to-all": lambda n: (n - 1) / n,
    "collective-broadcast": lambda n: (n - 1) / n,
    "collective-permute": lambda n: 1.0,
}

# never touch memory / pure bookkeeping
_FREE_OPS = frozenset({
    "parameter", "constant", "tuple", "get-tuple-element", "bitcast",
    "after-all", "add-dependency", "partition-id", "replica-id", "domain",
})


def _shape_elems(dims: str) -> int:
    elems = 1
    if dims:
        for d in dims.split(","):
            elems *= int(d)
    return elems


def _shapes(text: str) -> List[Tuple[str, str]]:
    """All (dtype, dims) array-shape tokens in ``text``."""
    return _SHAPE_RE.findall(text)


def _nbytes(shapes: List[Tuple[str, str]]) -> int:
    return sum(_shape_elems(dims) * _DTYPE_BYTES[dtype] for dtype, dims in shapes)


def _split_type_and_op(rhs: str) -> Tuple[str, str, int]:
    """``rhs`` is everything after "= ".  Returns (type_str, op, open_idx)
    where open_idx is the index of the op's '(' in rhs."""
    i = 0
    if rhs.startswith("("):           # tuple type: scan to balanced close
        depth = 0
        for i, ch in enumerate(rhs):
            if ch == "(":
                depth += 1
            elif ch == ")":
                depth -= 1
                if depth == 0:
                    break
        i += 1
    else:
        i = rhs.find(" ")
    type_str = rhs[:i]
    rest = rhs[i:].lstrip()
    off = len(rhs) - len(rest)
    paren = rest.find("(")
    if paren < 0:
        return type_str, rest.strip(), -1
    return type_str, rest[:paren].strip(), off + paren


def _balanced(text: str, open_idx: int) -> Tuple[str, str]:
    """(inside-parens, after-close) starting at text[open_idx] == '('."""
    depth = 0
    for j in range(open_idx, len(text)):
        if text[j] == "(":
            depth += 1
        elif text[j] == ")":
            depth -= 1
            if depth == 0:
                return text[open_idx + 1:j], text[j + 1:]
    return text[open_idx + 1:], ""


_OPERAND_NAME_RE = re.compile(r"%([^\s,()]+)")


@dataclasses.dataclass
class _Instr:
    op: str
    name: str = ""
    out_shapes: Tuple[Tuple[str, str], ...] = ()     # (dtype, dims) per array
    out_elems: int = 0
    out_bytes: int = 0
    operand_names: Tuple[str, ...] = ()
    operand_bytes: int = 0
    operand_info: Tuple[Tuple[str, int], ...] = ()   # (name, bytes) per operand
    param_index: Optional[int] = None                # for op == "parameter"
    flops: float = 0.0
    callee: Optional[str] = None
    while_body: Optional[str] = None
    while_cond: Optional[str] = None
    trip: Optional[int] = None
    branches: Tuple[str, ...] = ()
    group_size: Optional[int] = None
    label: str = ""
    attrs: str = ""


def _parse_instr(line: str) -> Optional[_Instr]:
    """One instruction line.  Operands are recorded by name only: the HLO
    text prints an operand's type inline in some XLA versions and not in
    others, so ``_resolve_operands`` reads them from the defining lines."""
    s = line.strip()
    if s.startswith("ROOT "):
        s = s[5:]
    if not s.startswith("%") or " = " not in s:
        return None
    lhs, rhs = s.split(" = ", 1)
    type_str, op, paren = _split_type_and_op(rhs)
    if paren < 0:
        return None
    operands, attrs = _balanced(rhs, paren)
    ins = _Instr(op=op, name=lhs.strip().lstrip("%"), attrs=attrs)
    ins.out_shapes = tuple(_shapes(type_str))
    ins.out_elems = sum(_shape_elems(dims) for _, dims in ins.out_shapes)
    ins.out_bytes = _nbytes(list(ins.out_shapes))
    ins.operand_names = tuple(_OPERAND_NAME_RE.findall(operands))
    if op == "parameter":
        mp = re.match(r"\s*(\d+)", operands)
        if mp:
            ins.param_index = int(mp.group(1))

    m = re.search(r'op_name="([^"]+)"', attrs)
    ins.label = f"{op} {type_str}" + (f"  {m.group(1)}" if m else "")

    if op == "while":
        mb = re.search(r"body=%([^\s,]+)", attrs)
        mc = re.search(r"condition=%([^\s,]+)", attrs)
        ins.while_body = mb.group(1) if mb else None
        ins.while_cond = mc.group(1) if mc else None
        mt = re.search(r'"known_trip_count":\{"n":"(\d+)"\}', attrs)
        if mt:
            ins.trip = int(mt.group(1))
    elif op in ("fusion", "call", "async-start"):
        mcal = re.search(r"calls=%([^\s,)]+)", attrs)
        ins.callee = mcal.group(1) if mcal else None
    elif op == "conditional":
        mbr = re.findall(r"(?:true_computation|false_computation)=%([^\s,]+)",
                         attrs)
        if not mbr:
            mset = re.search(r"branch_computations=\{([^}]*)\}", attrs)
            if mset:
                mbr = re.findall(r"%([^\s,]+)", mset.group(1))
        ins.branches = tuple(mbr)

    kind = op[:-6] if op.endswith("-start") else op
    if kind in _COLLECTIVE_KINDS and not op.endswith("-done"):
        ins.op = kind if op.endswith("-start") else op
        mg = re.search(r"replica_groups=\{\{([0-9,]+)\}", attrs)
        if mg:
            ins.group_size = len(mg.group(1).split(","))
        else:
            mg = re.search(r"replica_groups=\[\d+,(\d+)\]<=\[\d+\]", attrs)
            if mg:
                ins.group_size = int(mg.group(1))
    return ins


def _resolve_operands(ins: _Instr, table: Dict[str, Tuple[Tuple[str, str], ...]]) -> None:
    """Operand bytes and matmul-class flops from the operands' defining
    shapes (``table``: instruction name -> output shapes)."""
    opshapes = [table.get(name, ()) for name in ins.operand_names]
    ins.operand_info = tuple(
        (name, _nbytes(list(shapes)))
        for name, shapes in zip(ins.operand_names, opshapes))
    ins.operand_bytes = sum(b for _, b in ins.operand_info)

    def dims(i: int) -> List[int]:
        if i >= len(opshapes) or not opshapes[i] or not opshapes[i][0][1]:
            return []
        return [int(d) for d in opshapes[i][0][1].split(",")]

    if ins.op == "dot":
        contraction = 1
        mdims = re.search(r"lhs_contracting_dims=\{([0-9,]*)\}", ins.attrs)
        lhs_dims = dims(0)
        for d in (mdims.group(1).split(",") if mdims and mdims.group(1) else []):
            if int(d) < len(lhs_dims):
                contraction *= lhs_dims[int(d)]
        ins.flops = 2.0 * ins.out_elems * contraction
    elif ins.op == "convolution":
        kdims = dims(1)
        kernel_elems = math.prod(kdims)
        out_features = 1
        mlab = re.search(r"dim_labels=[^_]+_([0-9a-z]+)->", ins.attrs)
        if mlab:
            o_pos = mlab.group(1).find("o")
            if 0 <= o_pos < len(kdims):
                out_features = max(kdims[o_pos], 1)
        ins.flops = 2.0 * ins.out_elems * kernel_elems / out_features


@dataclasses.dataclass
class _FusionIO:
    """HBM traffic model for one fused computation's boundary."""
    param_reads: Dict[int, int]       # parameter index -> bytes actually read
    out_bytes_override: Optional[int]  # None = use the fusion's output bytes


@dataclasses.dataclass
class HloModule:
    comps: Dict[str, List[_Instr]]
    raw: Dict[str, List[str]]
    entry: Optional[str]
    num_partitions: int
    _fusion_io: Dict[str, _FusionIO] = dataclasses.field(default_factory=dict)

    def fusion_io(self, comp: str) -> _FusionIO:
        """XLA lowers scan bodies to fusions that *slice* their big operands
        (dynamic-slice) and *update* big outputs in place
        (dynamic-update-slice).  Charging full operand/output bytes per trip
        would overstate HBM traffic by the trip count, so: a parameter
        consumed only by dynamic-slice/gather reads just the slices; a
        parameter consumed only as a dynamic-update-slice target is aliased
        (read ~0); when every output store is an in-place update, the write
        is the update bytes, not the whole buffer."""
        if comp in self._fusion_io:
            return self._fusion_io[comp]
        instrs = self.comps.get(comp, [])
        reads: Dict[int, int] = {}
        for p in instrs:
            if p.op != "parameter" or p.param_index is None:
                continue
            uses = [(ins, pos) for ins in instrs if ins.op != "parameter"
                    for pos, (oname, _) in enumerate(ins.operand_info)
                    if oname == p.name]

            def _reduced(ins, pos):
                if ins.op in ("dynamic-slice", "gather") and pos == 0:
                    return ins.out_bytes          # reads just the slice
                if ins.op == "dynamic-update-slice" and pos == 0:
                    return 0                      # aliased in-place target
                return None

            per_use = [_reduced(ins, pos) for ins, pos in uses]
            if uses and all(r is not None for r in per_use):
                reads[p.param_index] = sum(per_use)
        dus = [ins for ins in instrs if ins.op == "dynamic-update-slice"]
        out_override = None
        if dus and all(len(ins.operand_info) > 1 for ins in dus):
            # read + write of each updated region
            out_override = 2 * sum(ins.operand_info[1][1] for ins in dus)
        io = _FusionIO(reads, out_override)
        self._fusion_io[comp] = io
        return io


def parse_module(hlo_text: str) -> HloModule:
    comps: Dict[str, List[_Instr]] = {}
    raw_lines: Dict[str, List[str]] = {}
    entry = None
    num_partitions = 1
    current: Optional[List[_Instr]] = None
    current_raw: Optional[List[str]] = None
    for raw in hlo_text.splitlines():
        if raw.startswith("HloModule"):
            m = re.search(r"num_partitions=(\d+)", raw)
            if m:
                num_partitions = int(m.group(1))
            continue
        if raw.startswith((" ", "\t")):
            if current is not None:
                current_raw.append(raw)
                ins = _parse_instr(raw)
                if ins is not None:
                    current.append(ins)
            continue
        m = re.match(r"(ENTRY\s+)?%?([^\s(]+)\s*\(.*\{\s*$", raw)
        if m:
            name = m.group(2)
            current = comps.setdefault(name, [])
            current_raw = raw_lines.setdefault(name, [])
            if m.group(1):
                entry = name
        elif raw.startswith("}"):
            current = None
            current_raw = None
    if entry is None and comps:
        entry = next(iter(comps))
    table = {ins.name: ins.out_shapes for instrs in comps.values() for ins in instrs}
    for instrs in comps.values():
        for ins in instrs:
            _resolve_operands(ins, table)
    return HloModule(comps=comps, raw=raw_lines, entry=entry,
                     num_partitions=num_partitions)


def _trip_fallback(module: HloModule, cond_name: Optional[str]) -> int:
    """Read the loop bound from ``compare(.., constant(N)), direction=LT``
    in the condition computation (assumes a 0-based unit-stride counter,
    which is how lax.scan/fori_loop lower).  Used only when XLA's
    known_trip_count annotation is absent."""
    lines = module.raw.get(cond_name or "", [])
    constants = {}
    for ln in lines:
        m = re.match(r"\s*(?:ROOT\s+)?%([^\s]+) = \S+ constant\((\d+)\)", ln)
        if m:
            constants[m.group(1)] = int(m.group(2))
    for ln in lines:
        if "compare(" not in ln or "direction=LT" not in ln:
            continue
        for name in re.findall(r"%([^\s,)]+)", ln.split("compare(", 1)[1]):
            if name in constants:
                return max(constants[name], 1)
    return 1


@dataclasses.dataclass
class HloCostSummary:
    flops: float = 0.0
    bytes_accessed: float = 0.0
    n_whiles: int = 0
    collective_operand_bytes: float = 0.0
    collective_wire_bytes: float = 0.0
    per_kind_operand: Dict[str, float] = dataclasses.field(default_factory=dict)
    per_kind_wire: Dict[str, float] = dataclasses.field(default_factory=dict)


def _walk(module: HloModule, comp: str, mult: float, count_bytes: bool,
          totals: HloCostSummary, rows: List[Tuple[float, float, str, str]],
          stack: Tuple[str, ...]) -> None:
    if comp not in module.comps or comp in stack:
        return
    stack = stack + (comp,)
    for ins in module.comps[comp]:
        if ins.op == "while":
            totals.n_whiles += 1
            trip = ins.trip if ins.trip is not None else _trip_fallback(
                module, ins.while_cond)
            for sub in (ins.while_body, ins.while_cond):
                if sub:
                    _walk(module, sub, mult * trip, count_bytes, totals,
                          rows, stack)
            continue
        if ins.op == "conditional":
            for b in ins.branches:
                _walk(module, b, mult, count_bytes, totals, rows, stack)
            continue
        if ins.op in ("fusion", "async-start") and ins.callee:
            # interior flops/collectives count; interior bytes do not (fused
            # intermediates stay in registers/cache, not HBM)
            _walk(module, ins.callee, mult, False, totals, rows, stack)
            if count_bytes:
                io = module.fusion_io(ins.callee)
                reads = sum(io.param_reads.get(i, nbytes_i)
                            for i, (_, nbytes_i)
                            in enumerate(ins.operand_info))
                writes = (ins.out_bytes if io.out_bytes_override is None
                          else io.out_bytes_override)
                b = reads + writes
                totals.bytes_accessed += mult * b
                rows.append((0.0, mult * b, ins.label, comp))
            continue
        if ins.op == "call" and ins.callee:
            _walk(module, ins.callee, mult, count_bytes, totals, rows, stack)
            continue
        if ins.op in _FREE_OPS or ins.op.endswith("-done"):
            continue

        flops = mult * ins.flops
        if not count_bytes:
            nbytes = 0.0
        elif ins.op in ("dynamic-slice", "gather"):
            nbytes = mult * 2.0 * ins.out_bytes      # read slice + write out
        elif ins.op == "dynamic-update-slice" and len(ins.operand_info) > 1:
            nbytes = mult * 2.0 * ins.operand_info[1][1]  # update region r+w
        else:
            nbytes = mult * (ins.operand_bytes + ins.out_bytes)
        totals.flops += flops
        totals.bytes_accessed += nbytes
        if ins.op in _COLLECTIVE_KINDS:
            n = ins.group_size or module.num_partitions
            payload = (ins.out_bytes if ins.op == "all-gather"
                       else ins.operand_bytes)
            wire = mult * payload * _WIRE_FACTOR[ins.op](max(n, 1)) \
                if n > 1 else 0.0
            operand = mult * ins.operand_bytes
            totals.collective_operand_bytes += operand
            totals.collective_wire_bytes += wire
            totals.per_kind_operand[ins.op] = \
                totals.per_kind_operand.get(ins.op, 0.0) + operand
            totals.per_kind_wire[ins.op] = \
                totals.per_kind_wire.get(ins.op, 0.0) + wire
        if flops or nbytes:
            rows.append((flops, nbytes, ins.label, comp))


def _analyze(hlo_text: str):
    module = parse_module(hlo_text)
    totals = HloCostSummary()
    rows: List[Tuple[float, float, str, str]] = []
    if module.entry:
        _walk(module, module.entry, 1.0, True, totals, rows, ())
    return totals, rows


def analyze_hlo(hlo_text: str) -> HloCostSummary:
    """Whole-module costs with exact while-loop trip-count attribution."""
    return _analyze(hlo_text)[0]


def top_contributors(hlo_text: str, metric: str = "flops",
                     k: int = 10) -> List[Tuple[float, str, str]]:
    """Top-k instructions by ``metric`` ("flops" | "bytes"), each scaled by
    its enclosing trip counts.  Returns (value, label, computation) rows."""
    if metric not in ("flops", "bytes"):
        raise ValueError(f"metric must be 'flops' or 'bytes', got {metric!r}")
    idx = 0 if metric == "flops" else 1
    _, rows = _analyze(hlo_text)
    picked = [(r[idx], r[2], r[3]) for r in rows if r[idx] > 0]
    picked.sort(key=lambda r: r[0], reverse=True)
    return picked[:k]
