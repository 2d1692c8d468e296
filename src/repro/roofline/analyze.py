"""HLO-text cost analyzer with while-loop trip-count correction.

XLA's ``compiled.cost_analysis()`` counts a while-loop body ONCE, so a
scan-over-layers model under-reports FLOPs/bytes/collectives by the trip
count (verified empirically in this repo: scan=16.8MF vs unroll=134MF for
8 matmul layers).  Production roofline numbers therefore need a corrected
walk: this module parses the post-optimization HLO text, builds a
per-computation symbol table, and recursively accumulates

  * dot FLOPs        2 * prod(result_dims) * prod(lhs contracting dims)
  * HBM bytes        operands + results of top-level (fusion-boundary) ops
  * collective wire  ring-model bytes per chip by kind and replica-group

multiplying while bodies by their static trip counts (jax scans lower to
counters compared against a constant).

Roofline terms per (arch, mesh) — hardware constants per assignment:
  compute  = FLOPs_per_chip / 197e12
  memory   = HBM_bytes_per_chip / 819e9
  coll.    = wire_bytes_per_chip / 50e9 (per-link ICI)
"""
from __future__ import annotations

import dataclasses
import re

PEAK_FLOPS = 197e12
HBM_BW = 819e9
ICI_BW = 50e9

_DTYPE_BYTES = {"pred": 1, "s8": 1, "u8": 1, "s4": 1, "u4": 1,
                "s16": 2, "u16": 2, "bf16": 2, "f16": 2,
                "s32": 4, "u32": 4, "f32": 4, "s64": 8, "u64": 8, "f64": 8,
                "c64": 8, "c128": 16}

_SHAPE_RE = re.compile(r"(\w+)\[([\d,]*)\]")
# computation header: "%name (params...) -> type {"  (params may nest parens)
_COMP_HDR = re.compile(r"^(ENTRY\s+)?%?([\w.\-]+)\s*\(.*\)\s*->\s*.+\{\s*$")
# instruction: %name = type op(...)   (tuple types may contain /*index=N*/)
_INSTR = re.compile(
    r"^\s*(?:ROOT\s+)?%?([\w.\-]+)\s*=\s*(\(.*?\)|[^\s]+)\s+"
    r"([\w\-]+)\(", re.M)
_TRIP_CFG = re.compile(r'"known_trip_count":\{"n":"(\d+)"\}')

_ZERO_COST = {"parameter", "constant", "get-tuple-element", "tuple",
              "bitcast", "copy-done", "all-gather-done", "all-reduce-done",
              "after-all", "partition-id", "replica-id", "domain",
              "opt-barrier"}

_COLLECTIVES = {"all-gather", "all-reduce", "reduce-scatter", "all-to-all",
                "collective-permute", "all-gather-start", "all-reduce-start",
                "collective-permute-start", "ragged-all-to-all"}


def _type_bytes(type_str: str) -> int:
    total = 0
    for m in _SHAPE_RE.finditer(type_str):
        dt, dims = m.group(1), m.group(2)
        if dt not in _DTYPE_BYTES:
            continue
        n = 1
        for d in dims.split(","):
            if d:
                n *= int(d)
        total += n * _DTYPE_BYTES[dt]
    return total


def _shape_dims(type_str: str) -> list[int]:
    m = _SHAPE_RE.search(type_str)
    if not m:
        return []
    return [int(d) for d in m.group(2).split(",") if d]


@dataclasses.dataclass
class Instr:
    name: str
    type_str: str
    op: str
    line: str

    def operand_names(self) -> list[str]:
        """Operand instruction names, tolerant of both HLO spellings:
        bare (``dot(%a, %b)``) and inline-typed
        (``dot(f32[64,128]{1,0} %a, ...)``, older jax dumps).  Scans from
        the op's own paren (so tuple-typed results don't shadow the operand
        list) to the matching close paren (types may nest parens and embed
        commas)."""
        idx = self.line.find(self.op + "(")
        if idx < 0:
            return []
        rest = self.line[idx + len(self.op) + 1:]
        depth, end = 0, len(rest)
        for j, ch in enumerate(rest):
            if ch == "(":
                depth += 1
            elif ch == ")":
                if depth == 0:
                    end = j
                    break
                depth -= 1
        seg = rest[:end]
        if "%" in seg:
            return re.findall(r"%([\w.\-]+)", seg)
        return [o.strip().split()[-1] for o in seg.split(",") if o.strip()]


@dataclasses.dataclass
class HloCost:
    flops: float = 0.0
    hbm_bytes: float = 0.0        # raw: operands+results at CPU-fusion
                                  # boundaries (UPPER bound for TPU)
    hbm_fused: float = 0.0        # idealized fusion: 2x result bytes at
                                  # materialization points only (lower bound)
    wire_bytes: float = 0.0
    coll: dict = dataclasses.field(default_factory=dict)

    def add(self, other: "HloCost", mult: float = 1.0):
        self.flops += mult * other.flops
        self.hbm_bytes += mult * other.hbm_bytes
        self.hbm_fused += mult * other.hbm_fused
        self.wire_bytes += mult * other.wire_bytes
        for k, v in other.coll.items():
            d = self.coll.setdefault(k, {"count": 0.0, "wire_bytes": 0.0})
            d["count"] += mult * v["count"]
            d["wire_bytes"] += mult * v["wire_bytes"]


# ops whose result must live in HBM even under perfect fusion
_MATERIALIZE = {"dot", "convolution", "custom-call", "copy", "concatenate",
                "dynamic-slice", "dynamic-update-slice", "gather", "scatter",
                "sort", "rng", "reduce-window", "select-and-scatter",
                "transpose"} | _COLLECTIVES


class HloModule:
    def __init__(self, text: str):
        self.comps: dict[str, list[Instr]] = {}
        self.entry: str | None = None
        self._parse(text)
        self._cost_cache: dict[str, HloCost] = {}
        self._trip_cache: dict[str, int] = {}

    # -- parsing -------------------------------------------------------------

    def _parse(self, text: str):
        cur = None
        for line in text.splitlines():
            hdr = _COMP_HDR.match(line)
            if hdr:
                cur = hdr.group(2)
                self.comps[cur] = []
                if hdr.group(1):
                    self.entry = cur
                continue
            if cur is None:
                continue
            m = _INSTR.match(line)
            if m:
                self.comps[cur].append(
                    Instr(m.group(1), m.group(2), m.group(3), line))
        if self.entry is None and self.comps:
            # fall back: the computation named like the module entry
            self.entry = list(self.comps)[-1]

    def _types_in(self, comp: str) -> dict[str, str]:
        return {i.name: i.type_str for i in self.comps.get(comp, [])}

    # -- per-op costs ----------------------------------------------------------

    def _dot_flops(self, instr: Instr, types: dict[str, str]) -> float:
        out_elems = 1
        for d in _shape_dims(instr.type_str):
            out_elems *= d
        # contraction size from lhs operand shape + contracting dims
        names = instr.operand_names()
        lhs_k = 1
        cd = re.search(r"lhs_contracting_dims=\{([\d,]*)\}", instr.line)
        if names and cd and names[0] in types:
            dims = _shape_dims(types[names[0]])
            for ax in cd.group(1).split(","):
                if ax and int(ax) < len(dims):
                    lhs_k *= dims[int(ax)]
        return 2.0 * out_elems * lhs_k

    def _operand_bytes(self, instr: Instr, types: dict[str, str]) -> int:
        total = 0
        for o in instr.operand_names():
            if o in types:
                total += _type_bytes(types[o])
        return total

    def _collective(self, instr: Instr) -> tuple[str, float]:
        rb = _type_bytes(instr.type_str)
        g = 2
        gm = re.search(r"replica_groups=\{\{([^}]*)\}", instr.line)
        if gm:
            g = max(len(gm.group(1).split(",")), 1)
        else:
            gm2 = re.search(r"replica_groups=\[(\d+),(\d+)\]", instr.line)
            if gm2:
                g = max(int(gm2.group(2)), 1)
        kind = instr.op.replace("-start", "")
        if kind == "all-gather":
            wire = (g - 1) / g * rb
        elif kind == "reduce-scatter":
            wire = (g - 1) * rb
        elif kind == "all-reduce":
            wire = 2 * (g - 1) / g * rb
        elif kind in ("all-to-all", "ragged-all-to-all"):
            wire = (g - 1) / g * rb
        else:  # collective-permute
            wire = rb
        return kind, wire

    def _called_comps(self, instr: Instr) -> list[str]:
        out = []
        for key in ("calls=", "to_apply=", "body=", "condition=",
                    "true_computation=", "false_computation="):
            for m in re.finditer(re.escape(key) + r"%?([\w.\-]+)", instr.line):
                out.append(m.group(1))
        # branch_computations={%a, %b}
        bm = re.search(r"branch_computations=\{([^}]*)\}", instr.line)
        if bm:
            out += [c.strip().lstrip("%") for c in bm.group(1).split(",")]
        return [c for c in out if c in self.comps]

    def trip_count(self, cond_comp: str) -> int:
        """Static trip count from a jax-style while condition."""
        if cond_comp in self._trip_cache:
            return self._trip_cache[cond_comp]
        n = 1
        for i in self.comps.get(cond_comp, []):
            if i.op == "constant":
                m = re.search(r"constant\((\d+)\)", i.line)
                if m:
                    n = max(n, int(m.group(1)))
        self._trip_cache[cond_comp] = n
        return n

    # -- recursive cost --------------------------------------------------------

    def cost(self, comp: str | None = None, _depth=0) -> HloCost:
        comp = comp or self.entry
        if comp in self._cost_cache:
            return self._cost_cache[comp]
        if _depth > 64:
            return HloCost()
        total = HloCost()
        types = self._types_in(comp)
        # consumer counts (for the idealized-fusion byte model)
        uses: dict[str, int] = {}
        instrs = self.comps.get(comp, [])
        root_name = instrs[-1].name if instrs else None
        for instr in instrs:
            for o in instr.operand_names():
                if o in types:
                    uses[o] = uses.get(o, 0) + 1

        def _fused_bytes(instr):
            return 2.0 * _type_bytes(instr.type_str)

        for instr in self.comps.get(comp, []):
            op = instr.op
            if op in _ZERO_COST:
                continue
            if op == "while":
                bm = re.search(r"body=%?([\w.\-]+)", instr.line)
                cm = re.search(r"condition=%?([\w.\-]+)", instr.line)
                body = bm.group(1) if bm else None
                cond = cm.group(1) if cm else None
                tm = _TRIP_CFG.search(instr.line)
                if tm:
                    trips = int(tm.group(1))
                else:
                    trips = self.trip_count(cond) if cond else 1
                if body in self.comps:
                    total.add(self.cost(body, _depth + 1), trips)
                # while carries re-read/written per iteration: already counted
                # inside body instrs; skip the while's own operand bytes.
                continue
            if op == "fusion":
                # HBM traffic at the fusion boundary; dots inside count FLOPs.
                total.hbm_bytes += self._operand_bytes(instr, types) \
                    + _type_bytes(instr.type_str)
                # idealized fusion: only multi-consumer or root fusion
                # outputs materialize
                if uses.get(instr.name, 0) > 1 or instr.name == root_name:
                    total.hbm_fused += _fused_bytes(instr)
                for c in self._called_comps(instr):
                    inner = self.cost(c, _depth + 1)
                    total.flops += inner.flops
                    total.wire_bytes += inner.wire_bytes
                    for k, v in inner.coll.items():
                        d = total.coll.setdefault(
                            k, {"count": 0.0, "wire_bytes": 0.0})
                        d["count"] += v["count"]
                        d["wire_bytes"] += v["wire_bytes"]
                continue
            if op in ("call", "conditional", "custom-call"):
                for c in self._called_comps(instr):
                    total.add(self.cost(c, _depth + 1))
                if op == "custom-call":
                    total.hbm_bytes += self._operand_bytes(instr, types) \
                        + _type_bytes(instr.type_str)
                    total.hbm_fused += _fused_bytes(instr)
                continue
            if op in _COLLECTIVES:
                kind, wire = self._collective(instr)
                d = total.coll.setdefault(kind,
                                          {"count": 0.0, "wire_bytes": 0.0})
                d["count"] += 1
                d["wire_bytes"] += wire
                total.wire_bytes += wire
                total.hbm_bytes += _type_bytes(instr.type_str)
                total.hbm_fused += _fused_bytes(instr)
                continue
            if op == "dot":
                total.flops += self._dot_flops(instr, types)
                total.hbm_bytes += self._operand_bytes(instr, types) \
                    + _type_bytes(instr.type_str)
                total.hbm_fused += self._operand_bytes(instr, types) \
                    + _type_bytes(instr.type_str)
                continue
            if op in ("convolution",):
                # rough: 2 * out_elems * (kh*kw*cin) — parse window
                out_elems = 1
                for d in _shape_dims(instr.type_str):
                    out_elems *= d
                total.flops += 2.0 * out_elems  # lower bound w/o window info
                total.hbm_bytes += self._operand_bytes(instr, types) \
                    + _type_bytes(instr.type_str)
                total.hbm_fused += _fused_bytes(instr)
                continue
            # default: elementwise-ish top-level op — HBM traffic only
            total.hbm_bytes += self._operand_bytes(instr, types) \
                + _type_bytes(instr.type_str)
            if op in _MATERIALIZE:
                total.hbm_fused += _fused_bytes(instr)
        self._cost_cache[comp] = total
        return total


def analyze_hlo(text: str) -> HloCost:
    return HloModule(text).cost()


def roofline_terms(cost: HloCost, *, chips_note: str = "per-chip") -> dict:
    """Three-term roofline (inputs are PER-CHIP quantities: post-SPMD HLO
    describes one device's program).

    memory_s uses the idealized-fusion byte model (TPU XLA fuses elementwise
    chains the CPU backend leaves at fine granularity); memory_s_raw is the
    CPU-fusion-boundary upper bound.  Truth on hardware lies between.
    """
    ct = cost.flops / PEAK_FLOPS
    mt = cost.hbm_fused / HBM_BW
    mt_raw = cost.hbm_bytes / HBM_BW
    lt = cost.wire_bytes / ICI_BW
    dom = max(("compute", ct), ("memory", mt), ("collective", lt),
              key=lambda kv: kv[1])
    return {
        "compute_s": ct, "memory_s": mt, "memory_s_raw": mt_raw,
        "collective_s": lt,
        "dominant": dom[0], "bound_s": dom[1],
        "flops": cost.flops, "hbm_bytes": cost.hbm_fused,
        "hbm_bytes_raw": cost.hbm_bytes,
        "wire_bytes": cost.wire_bytes,
        "collectives": cost.coll,
    }
