"""Roofline-term extraction from a compiled dry-run artifact.

XLA's HloCostAnalysis visits every instruction ONCE — `while` (lax.scan) bodies
are NOT multiplied by their trip count, which undercounts a scanned-layers LM
by ~n_layers×. This module therefore re-derives the three roofline terms from
the compiled HLO text with trip-count multipliers:

  * parse the module into computations; build a symbol table of result shapes;
  * find `while` ops, read the trip count from the loop condition's compare
    constant, and propagate multipliers through called computations;
  * FLOPs: 2·|result|·|contraction| for every dot/convolution (elementwise
    FLOPs are ignored — dots dominate LM workloads; stated in EXPERIMENTS.md);
  * HBM bytes: Σ (operand + result bytes) over *top-level* instructions
    (fusion bodies are not descended into — a fusion reads its operands and
    writes its result once, which is exactly the post-fusion HBM traffic);
  * collective bytes: result-shape bytes × ring factor (all-reduce 2×).

Terms (per chip — the SPMD module is the per-partition program):
  compute    = FLOPs / hw.peak_flops     memory = bytes / hw.hbm_bw
  collective = coll_bytes / hw.ici_bw

The chip numbers live in the peak table of `repro.analysis.hardware`, keyed
by `device_kind` — `Roofline` carries the model it was scored against, and
without one it scores against the chip the process runs on (an unknown kind
raises).
"""
from __future__ import annotations

import dataclasses
import re

from repro.analysis.hardware import (
    TPU_V5E,
    HardwareModel,
    get_default_hardware,
)
from repro.analysis.hlo import DTYPE_BYTES as _DTYPE_BYTES
from repro.analysis.hlo import shape_bytes as _shape_bytes
from repro.analysis.hlo import shape_dims as _shape_dims

# Backwards-compatible aliases for the historical module constants; the
# overridable source of truth is repro.analysis.hardware.
PEAK_FLOPS = TPU_V5E.peak_flops      # bf16 FLOP/s per chip (TPU v5e-class)
HBM_BW = TPU_V5E.hbm_bw              # B/s per chip
ICI_BW = TPU_V5E.ici_bw              # B/s per link

_COLLECTIVE_FACTOR = {
    "all-gather": 1.0, "all-gather-start": 1.0,
    "all-reduce": 2.0, "all-reduce-start": 2.0,
    "reduce-scatter": 1.0,
    "all-to-all": 1.0,
    "collective-permute": 1.0, "collective-permute-start": 1.0,
}

_COMP_HDR = re.compile(r"^(?:ENTRY\s+)?%?([\w.\-]+)\s*(\([^)]*\))\s*->")
_INSTR = re.compile(
    r"^\s*(?:ROOT\s+)?%?([\w.\-]+)\s*=\s*((?:\([^)]*\)|[\w\[\],{}]+))\s+([\w\-]+)\((.*)$"
)
_OPERAND = re.compile(r"%([\w.\-]+)")
_CALLREF_ONE = re.compile(r"(?:body|condition|to_apply|calls)=%?([\w.\-]+)")
_CALLREF_SET = re.compile(r"(?:calls|branch_computations)=\{([^}]*)\}")


def _call_targets(rest: str) -> list[str]:
    out = [m.group(1) for m in _CALLREF_ONE.finditer(rest)]
    for m in _CALLREF_SET.finditer(rest):
        out.extend(re.findall(r"[\w.\-]+", m.group(1)))
    return out
_CONST_INT = re.compile(r"constant\((\d+)\)")


@dataclasses.dataclass
class _Instr:
    name: str
    type_str: str
    op: str
    rest: str
    is_root: bool = False


class HloModule:
    def __init__(self, text: str):
        self.comps: dict[str, list[_Instr]] = {}
        self.defs: dict[str, str] = {}                # global instr name -> type
        self.entry: str | None = None
        cur = None
        for line in text.splitlines():
            s = line.strip()
            if s.endswith("{") and "->" in s and "=" not in s.split("->")[0].split("(")[0]:
                # computation header: "[ENTRY] %name (sig) -> type {"
                head = s.split("(")[0].strip()
                is_entry = head.startswith("ENTRY")
                name = head.replace("ENTRY", "").strip().lstrip("%")
                if name:
                    cur = name
                    self.comps[cur] = []
                    if is_entry:
                        self.entry = cur
                continue
            if cur is None:
                continue
            m = _INSTR.match(line)
            if m:
                name, type_str, op, rest = m.groups()
                self.comps[cur].append(_Instr(
                    name, type_str, op, rest,
                    is_root=line.lstrip().startswith("ROOT"),
                ))
                self.defs[name] = type_str

    # ------------------------------------------------------------------ #
    def _operand_names_types(self, comp: str, rest: str) -> list[tuple[str, str]]:
        """Resolve leading operand %names to (name, type) pairs (defs map —
        every instruction incl. `parameter` defines its type on its own line)."""
        ops = []
        depth = 0
        end = len(rest)
        for i, ch in enumerate(rest):
            if ch == "(":
                depth += 1
            elif ch == ")":
                if depth == 0:
                    end = i
                    break
                depth -= 1
        for m in _OPERAND.finditer(rest[:end]):
            t = self.defs.get(m.group(1))
            if t:
                ops.append((m.group(1), t))
        return ops

    def _operand_types(self, comp: str, rest: str) -> list[str]:
        return [t for _, t in self._operand_names_types(comp, rest)]

    def _trip_count(self, ins: _Instr) -> float:
        """Prefer XLA's known_trip_count backend_config; fall back to the
        largest constant in the condition computation."""
        m = re.search(r"known_trip_count[^0-9]*(\d+)", ins.rest)
        if m:
            return float(m.group(1))
        cond = re.search(r"condition=%?([\w.\-]+)", ins.rest)
        best = 1
        if cond:
            for ci in self.comps.get(cond.group(1), []):
                for mc in _CONST_INT.finditer(ci.op + "(" + ci.rest):
                    best = max(best, int(mc.group(1)))
        return float(best)

    def multipliers(self) -> dict[str, float]:
        """Execution multiplier per computation (entry = 1; while bodies ×trip)."""
        referenced = set()
        refs: dict[str, list[tuple[str, float]]] = {c: [] for c in self.comps}
        for comp, instrs in self.comps.items():
            for ins in instrs:
                factor = self._trip_count(ins) if ins.op == "while" else 1.0
                for target in _call_targets(ins.rest):
                    if target in self.comps:
                        referenced.add(target)
                        # while body AND condition both run ~trip times
                        refs[comp].append((target, factor))
        entries = [c for c in self.comps if c not in referenced]
        if self.entry and self.entry not in entries:
            entries.append(self.entry)
        mult: dict[str, float] = {}
        stack = [(e, 1.0) for e in entries]
        while stack:
            comp, m = stack.pop()
            if comp in mult and mult[comp] >= m:
                continue
            mult[comp] = m
            for tgt, f in refs.get(comp, []):
                stack.append((tgt, m * f))
        return mult

    # ------------------------------------------------------------------ #
    def dot_flops(self, comp: str, ins: _Instr) -> float:
        if ins.op not in ("dot", "convolution"):
            return 0.0
        out_elems = 1
        for _, dims in _shape_dims(ins.type_str):
            for d in dims:
                out_elems *= d
        # contraction size from lhs shape and contracting dims
        ops = self._operand_types(comp, ins.rest)
        contract = 1
        m = re.search(r"lhs_contracting_dims=\{([\d,]*)\}", ins.rest)
        if m and ops:
            lhs_dims = _shape_dims(ops[0])
            if lhs_dims:
                dims = lhs_dims[0][1]
                for i in (int(x) for x in m.group(1).split(",") if x.strip()):
                    if i < len(dims):
                        contract *= dims[i]
        elif ins.op == "convolution" and len(ops) >= 2:
            # windowed contraction ≈ prod(kernel spatial × in features)
            k = _shape_dims(ops[1])
            if k:
                kern = 1
                for d in k[0][1]:
                    kern *= d
                out_last = _shape_dims(ins.type_str)[0][1][-1] if _shape_dims(ins.type_str) else 1
                contract = max(kern // max(out_last, 1), 1)
        return 2.0 * out_elems * contract

    # ------------------------------------------------------------------ #
    def _invariant_names(self) -> dict[str, set[str]]:
        """Per while-body computation: names of loop-INVARIANT carried values
        (get-tuple-element(param, i) returned unchanged at root-tuple slot i).

        These are parameters the loop re-reads every iteration — weights used
        inside a time scan. On TPU, XLA keeps them VMEM-resident across the
        loop when they fit (they are written to HBM once, not per step), so
        the HBM model charges them zero inside the body. Without this, a
        recurrent matrix re-counts per timestep and dominates every RNN-style
        roofline with traffic a real chip never issues."""
        if hasattr(self, "_inv_cache"):
            return self._inv_cache
        bodies = set()
        for instrs in self.comps.values():
            for ins in instrs:
                if ins.op == "while":
                    m = re.search(r"body=%?([\w.\-]+)", ins.rest)
                    if m:
                        bodies.add(m.group(1))
        out: dict[str, set[str]] = {}
        for bname in bodies:
            body = self.comps.get(bname, [])
            gte_idx: dict[str, int] = {}
            root = None
            for bi in body:
                if bi.op == "get-tuple-element":
                    mi = re.search(r"index=(\d+)", bi.rest)
                    if mi:
                        gte_idx[bi.name] = int(mi.group(1))
                if bi.is_root:
                    root = bi
            inv: set[str] = set()
            if root is not None and root.op == "tuple":
                operands = [m.group(1) for m in _OPERAND.finditer(root.rest)]
                for slot, name in enumerate(operands):
                    if gte_idx.get(name) == slot:
                        inv.add(name)
            out[bname] = inv
        self._inv_cache = out
        return out

    def _param_index(self, ins: _Instr) -> int | None:
        m = re.match(r"\s*(\d+)", ins.rest)
        return int(m.group(1)) if m else None

    def _is_pure_convert(self, comp_name: str) -> bool:
        """True if the computation is only parameter/convert/copy/bitcast ops.

        The CPU backend legalizes bf16 dots by materializing explicit f32
        copies of the weights (`wrapped_convert` kLoop fusions). A TPU backend
        consumes bf16 in the MXU directly and fuses dtype converts into the
        consumer — these instructions are measurement artifacts of running the
        dry-run on CPU, not traffic the target chip would issue, so they are
        charged zero. (The f32-sized operand reads at the consumers are still
        counted, which keeps the model conservative.)"""
        body = self.comps.get(comp_name)
        if not body:
            return False
        return all(bi.op in ("parameter", "convert", "copy", "bitcast")
                   for bi in body)

    def _fusion_bytes(self, comp: str, ins: _Instr) -> float:
        """HBM traffic of one fusion call.

        A fusion reads its operands and writes its result once — EXCEPT that a
        parameter consumed only by dynamic-slice/gather ops inside the body
        only reads the slices (XLA keeps the big operand in place; this is how
        scan bodies address their stacked inputs), and a root
        dynamic-update-slice writes only the updated window (in-place carry
        update). Counting full operands here overstates scan-body traffic by
        the trip count × (L/1) — the dominant error for scanned LMs."""
        targets = [t for t in _call_targets(ins.rest) if t in self.comps]
        body = None
        for t in targets:
            if self._is_pure_convert(t):
                return 0.0      # CPU bf16-legalization artifact (see above)
            if t.startswith("fused"):
                body = self.comps[t]
                break
        inv = self._invariant_names().get(comp, set())
        named_ops = self._operand_names_types(comp, ins.rest)
        if body is None:
            b = _shape_bytes(ins.type_str)
            return b + sum(_shape_bytes(t) for nm, t in named_ops if nm not in inv)

        # map parameter index -> instr name; collect per-name uses
        param_name = {}
        uses: dict[str, list[_Instr]] = {}
        for bi in body:
            if bi.op == "parameter":
                idx = self._param_index(bi)
                if idx is not None:
                    param_name[idx] = bi.name
            for m in _OPERAND.finditer(bi.rest):
                uses.setdefault(m.group(1), []).append(bi)

        total = 0.0
        for idx, (nm, t) in enumerate(named_ops):
            if nm in inv:
                continue        # loop-invariant: VMEM-resident across the loop
            name = param_name.get(idx)
            us = uses.get(name, []) if name else []
            if us and all(u.op in ("dynamic-slice", "gather") for u in us):
                total += sum(_shape_bytes(u.type_str) for u in us)
            elif us and all(u.op == "dynamic-update-slice" for u in us):
                # aliased carry being updated in place: reads nothing extra
                continue
            else:
                total += _shape_bytes(t)

        root = body[-1] if body else None
        for bi in body:
            if bi.is_root:
                root = bi
        if root is not None and root.op == "dynamic-update-slice":
            # write = the updated window (operand 1), not the whole buffer
            upd_ops = [m.group(1) for m in _OPERAND.finditer(root.rest)]
            if len(upd_ops) >= 2 and upd_ops[1] in self.defs:
                total += _shape_bytes(self.defs[upd_ops[1]])
            else:
                total += _shape_bytes(root.type_str)
        else:
            total += _shape_bytes(ins.type_str)
        return total

    def analyze(self) -> tuple[float, float, float, dict]:
        mult = self.multipliers()
        flops = 0.0
        hbm = 0.0
        coll = 0.0
        coll_detail: dict = {"bytes": {}, "count": {}}
        for comp, instrs in self.comps.items():
            m = mult.get(comp, 0.0)
            if m == 0.0:
                continue
            for ins in instrs:
                f = self.dot_flops(comp, ins)
                flops += f * m
                if ins.op in _COLLECTIVE_FACTOR and not ins.op.endswith("-done"):
                    b = _shape_bytes(ins.type_str)
                    coll += b * _COLLECTIVE_FACTOR[ins.op] * m
                    coll_detail["bytes"][ins.op] = coll_detail["bytes"].get(ins.op, 0) + b * m
                    coll_detail["count"][ins.op] = coll_detail["count"].get(ins.op, 0) + m
                # HBM: count ops at "executable" level — entry/loop bodies and
                # fusion CALLS (their operands+result), not inside fusion bodies
            if not comp.startswith(("fused_",)):
                for ins in instrs:
                    if ins.op in ("parameter", "constant", "tuple", "get-tuple-element",
                                  "bitcast", "while", "call", "conditional",
                                  "convert"):  # convert: CPU bf16-legalization artifact
                        continue
                    if ins.op in ("dynamic-slice", "gather", "dynamic-update-slice"):
                        # reads/writes only the slice, not the full operand
                        hbm += 2 * _shape_bytes(ins.type_str) * m
                        continue
                    if ins.op == "fusion":
                        hbm += self._fusion_bytes(comp, ins) * m
                        continue
                    inv = self._invariant_names().get(comp, set())
                    b = _shape_bytes(ins.type_str)
                    for nm, t in self._operand_names_types(comp, ins.rest):
                        if nm not in inv:
                            b += _shape_bytes(t)
                    hbm += b * m
        return flops, hbm, coll, coll_detail


@dataclasses.dataclass
class Roofline:
    flops: float                 # per-chip FLOPs (trip-count corrected)
    hbm_bytes: float             # per-chip HBM traffic estimate
    coll_bytes: float            # per-chip weighted collective bytes
    coll_detail: dict
    peak_mem_bytes: float        # per-chip peak allocation (memory_analysis)
    xla_flops: float = 0.0       # raw cost_analysis (uncorrected, for reference)
    xla_bytes: float = 0.0
    hardware: HardwareModel | None = None   # None → the chip in use

    @property
    def hw(self) -> HardwareModel:
        """The chip model this roofline is scored against."""
        if self.hardware is not None:
            return self.hardware
        return get_default_hardware()

    @property
    def t_compute(self) -> float:
        return self.flops / self.hw.peak_flops

    @property
    def t_memory(self) -> float:
        return self.hbm_bytes / self.hw.hbm_bw

    @property
    def t_collective(self) -> float:
        return self.coll_bytes / self.hw.ici_bw

    @property
    def dominant(self) -> str:
        ts = {"compute": self.t_compute, "memory": self.t_memory,
              "collective": self.t_collective}
        return max(ts, key=ts.get)

    @property
    def bound_time(self) -> float:
        return max(self.t_compute, self.t_memory, self.t_collective)

    def compute_fraction(self) -> float:
        return self.t_compute / max(self.bound_time, 1e-30)

    def to_dict(self) -> dict:
        return {
            "flops": self.flops, "hbm_bytes": self.hbm_bytes,
            "coll_bytes": self.coll_bytes, "coll_detail": self.coll_detail,
            "peak_mem_bytes": self.peak_mem_bytes,
            "xla_flops": self.xla_flops, "xla_bytes": self.xla_bytes,
            "t_compute": self.t_compute, "t_memory": self.t_memory,
            "t_collective": self.t_collective, "dominant": self.dominant,
            "roofline_fraction": self.compute_fraction(),
            "hardware": self.hw.name,
        }


def analyze(compiled, hardware: HardwareModel | None = None) -> Roofline:
    cost = compiled.cost_analysis()
    if isinstance(cost, list):
        cost = cost[0]
    mem = compiled.memory_analysis()
    peak = float(
        getattr(mem, "temp_size_in_bytes", 0)
        + getattr(mem, "argument_size_in_bytes", 0)
        + getattr(mem, "output_size_in_bytes", 0)
        - getattr(mem, "alias_size_in_bytes", 0)
    )
    mod = HloModule(compiled.as_text())
    flops, hbm, coll, detail = mod.analyze()
    return Roofline(
        flops=flops, hbm_bytes=hbm, coll_bytes=coll, coll_detail=detail,
        peak_mem_bytes=peak,
        xla_flops=float(cost.get("flops", 0.0)),
        xla_bytes=float(cost.get("bytes accessed", 0.0)),
        hardware=hardware,
    )


def model_flops(n_params_active: int, n_tokens: int, kind: str) -> float:
    """MODEL_FLOPS = 6·N·D (train) / 2·N·D (inference fwd)."""
    return (6.0 if kind == "train" else 2.0) * n_params_active * n_tokens
