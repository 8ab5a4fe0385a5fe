"""HLO async-overlap auditor.

Generalizes the Domino HLO-evidence methodology
(``tests/unit/runtime/test_domino_hlo.py``, ``DOMINO_TPU_r4.log``) into a
reusable library: compile a step, parse the optimized HLO, and measure —
instead of assuming — whether collectives can run off the critical path.

Two evidence tiers, reported side by side and never conflated:

* **native pairs** — literal ``all-gather-start``/``all-gather-done``,
  ``all-reduce-start/done``, ``collective-permute-start/done`` and generic
  ``async-start/done`` instruction pairs found in the compiled module.
  On a scheduled module (TPU) the text order IS the schedule, so each
  pair is scored by the number of dot/fusion ops the compiler placed
  between start and done — the measured overlap. ``DOMINO_TPU_r4.log``
  is the cautionary tale: a backend may compile ZERO such pairs, which
  is exactly what this tier detects.
* **derived pairs** — for backends that keep collectives synchronous
  (the CPU backend at every flag combination we probed; injecting async
  HLO via MHLO ``async_start`` segfaults the CPU compiler), the auditor
  computes the async schedule the dependence structure *legally admits*:
  a sync collective whose def-use graph has >= 1 dot/fusion neither
  ancestor nor descendant of it could be split into a start/done pair
  with that compute inside the window by any latency-hiding scheduler.
  A collective with zero such free ops is **sequential** — every
  downstream op waits on it. This tier is deterministic on CPU, which is
  what lets structural overlap tests run in tier-1.

A program whose gathers are all *derived-overlappable* proves the
prefetch restructuring exists in the compiled program; a program whose
gathers are all *sequential* proves ``overlap_comm=False`` really
serializes. Neither claims wall-clock overlap on hardware — that is the
native tier's job, on a real chip.
"""

import json
import re
from dataclasses import dataclass, field
from typing import Dict, List, Optional

COLLECTIVE_OPS = (
    "all-gather",
    "all-reduce",
    "reduce-scatter",
    "collective-permute",
    "all-to-all",
)

#: opcodes counted as compute inside a NATIVE start/done window (the
#: module is scheduled there — whatever the compiler placed inside the
#: window really runs during the collective)
COMPUTE_OPS = ("dot", "fusion", "convolution", "custom-call")

#: opcodes counted for DERIVED overlap. Deliberately narrower: only
#: concrete FLOP producers. Elementwise fusions (e.g. a sibling
#: gather's dequantize) are legally free next to almost any collective
#: and would make even a fully serialized program audit as
#: "overlappable"; independent *dots* are the evidence that real math
#: can hide the wire time.
DERIVED_COMPUTE_OPS = ("dot", "convolution")

_HEADER_RE = re.compile(r"^(ENTRY\s+)?%?([\w.\-]+)\s*(\(.*\))?\s*"
                        r"(?:->\s*.*?)?\s*{\s*$")
_INSTR_RE = re.compile(r"^(ROOT\s+)?(%?[\w.\-]+)\s+=\s+(.*?)"
                       r"([a-z][a-z0-9\-]*)\((.*)$")
_OPERAND_RE = re.compile(r"%([\w.\-]+)")
_SHAPE_RE = re.compile(r"([a-z][a-z0-9]*)\[([0-9,]*)\]")

#: HLO element-type byte widths (sub-byte types fractional)
_DTYPE_BYTES = {
    "pred": 1, "s4": 0.5, "u4": 0.5, "s8": 1, "u8": 1,
    "s16": 2, "u16": 2, "f16": 2, "bf16": 2,
    "s32": 4, "u32": 4, "f32": 4,
    "s64": 8, "u64": 8, "f64": 8, "c64": 8, "c128": 16,
}

#: element types that count as a QUANTIZED wire (int8/int4/fp8 payloads)
_QUANT_DTYPES = ("s8", "u8", "s4", "u4")


def _type_bytes(type_str: str):
    """(total_bytes, quantized_bytes) of an HLO result type string —
    sums every ``dtype[dims]`` token (tuple types included)."""
    total = quant = 0.0
    for dtype, dims in _SHAPE_RE.findall(type_str):
        width = _DTYPE_BYTES.get(
            dtype, 1 if dtype.startswith("f8") else None)
        if width is None:
            continue
        elems = 1
        if dims:
            for d in dims.split(","):
                elems *= int(d)
        total += elems * width
        if dtype in _QUANT_DTYPES or dtype.startswith("f8"):
            quant += elems * width
    return int(total), int(quant)


@dataclass
class Instr:
    name: str
    opcode: str
    operands: List[str]
    index: int
    is_root: bool
    raw: str
    result_bytes: int = 0        # bytes of the result type (wire buffer)
    quantized_bytes: int = 0     # int8/int4/fp8 portion of the result

    @property
    def is_collective(self) -> bool:
        return self.opcode in COLLECTIVE_OPS

    @property
    def async_kind(self) -> Optional[str]:
        """Collective kind if this is a native async start/done op."""
        for kind in COLLECTIVE_OPS:
            if self.opcode in (kind + "-start", kind + "-done"):
                return kind
        if self.opcode in ("async-start", "async-done", "async-update"):
            return "async"
        return None


@dataclass
class Computation:
    name: str
    is_entry: bool
    instrs: List[Instr] = field(default_factory=list)

    def by_name(self) -> Dict[str, Instr]:
        return {i.name: i for i in self.instrs}


@dataclass
class Pair:
    """One (possibly derived) async collective window."""
    kind: str           # all-gather | reduce-scatter | ...
    computation: str
    start: str          # instruction name (derived: the sync collective)
    done: str           # native: the -done op; derived: == start
    interleaved: int    # dot/fusion ops inside the window / legally free
    provenance: str     # "native" | "derived"

    def to_dict(self):
        return {
            "kind": self.kind, "computation": self.computation,
            "start": self.start, "done": self.done,
            "interleaved": self.interleaved,
            "provenance": self.provenance,
        }


def parse_hlo_computations(text: str) -> List[Computation]:
    """Split optimized-HLO text into computations with ordered
    instruction lists. Robust to attribute noise: anything that does not
    look like ``%name = ... opcode(...`` is skipped."""
    comps: List[Computation] = []
    cur: Optional[Computation] = None
    for line in text.splitlines():
        stripped = line.strip()
        if cur is None:
            m = _HEADER_RE.match(stripped)
            # computation headers sit at column 0; require the paren'd
            # parameter list so `whilecond {` noise can't open a block
            if m and not line[:1].isspace() and "(" in stripped:
                cur = Computation(name=m.group(2),
                                  is_entry=bool(m.group(1)))
            continue
        if stripped == "}" or stripped.startswith("} "):
            comps.append(cur)
            cur = None
            continue
        m = _INSTR_RE.match(stripped)
        if not m:
            continue
        is_root, name, type_str, opcode, rest = m.groups()
        total_b, quant_b = _type_bytes(type_str)
        cur.instrs.append(Instr(
            name=name.lstrip("%"), opcode=opcode,
            operands=[o for o in _OPERAND_RE.findall(rest)],
            index=len(cur.instrs), is_root=bool(is_root), raw=stripped,
            result_bytes=total_b, quantized_bytes=quant_b))
    if cur is not None:  # unterminated tail block
        comps.append(cur)
    return comps


def _graph(comp: Computation):
    """name -> operand names, restricted to defs in this computation."""
    defined = set(i.name for i in comp.instrs)
    return {i.name: [o for o in i.operands if o in defined]
            for i in comp.instrs}


def _ancestors(graph, name):
    seen, stack = set(), list(graph.get(name, ()))
    while stack:
        n = stack.pop()
        if n in seen:
            continue
        seen.add(n)
        stack.extend(graph.get(n, ()))
    return seen


def _reverse(graph):
    rev = {n: [] for n in graph}
    for n, ops in graph.items():
        for o in ops:
            rev.setdefault(o, []).append(n)
    return rev


def _native_pairs(comp: Computation) -> List[Pair]:
    """Literal start/done windows, scored by text (schedule) order."""
    pairs = []
    open_windows = {}  # start instr name -> (kind, index)
    for i in comp.instrs:
        kind = i.async_kind
        if kind is None:
            continue
        if i.opcode.endswith("-start"):
            open_windows[i.name] = (kind, i.index)
        elif i.opcode.endswith("-done"):
            # the done's operand chain points at its start (possibly
            # through async-update ops); take the first open match
            src = next((o for o in i.operands if o in open_windows), None)
            if src is None and open_windows:
                # scheduled text without tuple-forwarding noise: pair
                # with the oldest open window of the same kind
                src = next((n for n, (k, _) in open_windows.items()
                            if k == kind), None)
            if src is None:
                continue
            kind, start_idx = open_windows.pop(src)
            interleaved = sum(
                1 for j in comp.instrs
                if start_idx < j.index < i.index
                and j.opcode in COMPUTE_OPS)
            pairs.append(Pair(kind=kind, computation=comp.name,
                              start=src, done=i.name,
                              interleaved=interleaved,
                              provenance="native"))
    return pairs


def _derived_pairs(comp: Computation):
    """(overlappable, sequential) sync collectives, from def-use
    independence: a dot that is neither ancestor nor descendant of a
    collective is legally schedulable inside its window."""
    graph = _graph(comp)
    rev = _reverse(graph)
    overlappable, sequential = [], []
    for c in comp.instrs:
        if not c.is_collective:
            continue
        anc = _ancestors(graph, c.name)
        desc = _ancestors(rev, c.name)
        free = [i for i in comp.instrs
                if i.opcode in DERIVED_COMPUTE_OPS
                and i.name != c.name
                and i.name not in anc and i.name not in desc]
        pair = Pair(kind=c.opcode, computation=comp.name,
                    start=c.name, done=c.name,
                    interleaved=len(free), provenance="derived")
        (overlappable if free else sequential).append(pair)
    return overlappable, sequential


@dataclass
class AuditReport:
    native_pairs: List[Pair]
    derived_pairs: List[Pair]         # sync collectives with >=1 free op
    sequential_collectives: List[Pair]  # sync collectives with 0 free
    computations: int
    #: per collective opcode: result-buffer bytes in the COMPILED
    #: module ``{kind: {bytes, quantized_bytes, count}}`` — the
    #: HLO-measured wire evidence (an int8 wire shows up as s8/u8
    #: buffers here, independent of the trace-time comms attribution).
    wire_bytes: Dict[str, Dict] = field(default_factory=dict)

    def pairs(self, kind: Optional[str] = None,
              min_interleaved: int = 1) -> List[Pair]:
        """Best-evidence view: native pairs when the backend compiled
        any, else the derived schedule. ``kind`` filters by collective
        opcode prefix (e.g. ``"all-gather"``)."""
        src = self.native_pairs if self.native_pairs else self.derived_pairs
        return [p for p in src
                if (kind is None or p.kind.startswith(kind))
                and p.interleaved >= min_interleaved]

    def _all(self, kind=None):
        every = (self.native_pairs + self.derived_pairs
                 + self.sequential_collectives)
        return [p for p in every
                if kind is None or p.kind.startswith(kind)]

    def overlap_ratio(self, kind: Optional[str] = None) -> float:
        """Fraction of ``kind`` collectives with >= 1 interleaved (native)
        or legally-interleavable (derived) compute op. 1.0 on an empty
        set (nothing is ON the critical path)."""
        every = self._all(kind)
        if not every:
            return 1.0
        return sum(1 for p in every if p.interleaved >= 1) / len(every)

    def counts(self) -> Dict[str, int]:
        out: Dict[str, int] = {}
        for p in self._all():
            out[p.kind] = out.get(p.kind, 0) + 1
        return out

    def to_row(self) -> Dict:
        """JSON-safe summary row."""
        return {
            "native_async_pairs": len(self.native_pairs),
            "derived_async_pairs": len(self.derived_pairs),
            "sequential_collectives": len(self.sequential_collectives),
            "gather_overlap_ratio": round(
                self.overlap_ratio("all-gather"), 4),
            "reduce_overlap_ratio": round(
                self.overlap_ratio("reduce-scatter"), 4),
            "allreduce_overlap_ratio": round(
                self.overlap_ratio("all-reduce"), 4),
            "collective_counts": self.counts(),
            "wire_bytes": self.wire_bytes,
            "pairs": [p.to_dict() for p in
                      (self.native_pairs + self.derived_pairs)],
        }

    def to_json(self) -> str:
        return json.dumps(self.to_row())


def audit_hlo_text(text: str) -> AuditReport:
    """Audit one optimized-HLO module's async-overlap structure."""
    native, derived, sequential = [], [], []
    wire: Dict[str, Dict] = {}
    comps = parse_hlo_computations(text)
    for comp in comps:
        native.extend(_native_pairs(comp))
        over, seq = _derived_pairs(comp)
        derived.extend(over)
        sequential.extend(seq)
        for i in comp.instrs:
            if not (i.is_collective or i.opcode.endswith("-start")):
                continue
            kind = i.opcode[:-6] if i.opcode.endswith("-start") \
                else i.opcode
            rec = wire.setdefault(kind, {"bytes": 0,
                                         "quantized_bytes": 0,
                                         "count": 0})
            rec["bytes"] += i.result_bytes
            rec["quantized_bytes"] += i.quantized_bytes
            rec["count"] += 1
    return AuditReport(native_pairs=native, derived_pairs=derived,
                       sequential_collectives=sequential,
                       computations=len(comps), wire_bytes=wire)


def audit_compiled(compiled) -> AuditReport:
    """Audit a ``jax.stages.Compiled`` (or anything with ``as_text``)."""
    return audit_hlo_text(compiled.as_text())


def audit_jit(fn, *args, **kwargs) -> AuditReport:
    """Compile ``fn`` for ``args`` and audit the optimized module."""
    import jax
    return audit_compiled(jax.jit(fn, **kwargs).lower(*args).compile())
