"""Trip-count-aware cost analysis of post-SPMD HLO text.

``compiled.cost_analysis()`` counts a while-loop body ONCE regardless of
trip count (verified: scan(matmul, 8) reports the flops of one matmul), so
any scanned program — every model here scans over layers/microbatches —
is undercounted by orders of magnitude. This module re-derives per-device
costs from the compiled HLO text with loops multiplied out:

  flops  — exact for dot/convolution (2 * out_elems * contracted size),
           one per output element for elementwise ops;
  bytes  — memory-traffic model: operands + outputs per materialized
           instruction; fusions count only their boundary buffers (XLA's
           own fusion-traffic model); dynamic-(update-)slice / gather /
           scatter count only the touched slice (in-place semantics), so
           KV-cache updates inside scans don't absurdly overcount;
  wire   — collective bytes with ring factors: all-gather/reduce-scatter/
           all-to-all F*(g-1)/g, all-reduce 2*F*(g-1)/g, permute F;
  while  — body+cond costs multiplied by the trip count parsed from the
           loop condition (jax emits compare(iv, constant(N)), LT).

Shapes in post-partitioning HLO are per-device shard shapes, so all
results are per-device; multiply by mesh size for global totals.
"""

from __future__ import annotations

import dataclasses
import re
from typing import Optional

_DTYPE_BYTES = {
    "f64": 8, "f32": 4, "bf16": 2, "f16": 2, "f8e4m3fn": 1, "f8e5m2": 1,
    "f8e4m3": 1, "f8e3m4": 1, "s64": 8, "u64": 8, "s32": 4, "u32": 4,
    "s16": 2, "u16": 2, "s8": 1, "u8": 1, "s4": 1, "u4": 1, "pred": 1,
    "c64": 8, "c128": 16,
}

_SHAPE_RE = re.compile(r"([a-z0-9]+)\[([\d,]*)\]")
_INSTR_RE = re.compile(
    r"^\s*(?:ROOT\s+)?%([\w.\-]+)\s*=\s*(.+?)\s+([\w\-]+)\((.*)$"
)
_COMP_RE = re.compile(r"^(?:ENTRY\s+)?%?([\w.\-]+)\s*\((.*?)\)\s*->")
_OPERAND_RE = re.compile(r"%([\w.\-]+)")
_CALLS_RE = re.compile(r"calls=%?([\w.\-]+)")
_COND_RE = re.compile(r"condition=%?([\w.\-]+)")
_BODY_RE = re.compile(r"body=%?([\w.\-]+)")
_LHS_C_RE = re.compile(r"lhs_contracting_dims=\{([\d,]*)\}")
_CONST_RE = re.compile(r"constant\((\d+)\)")
_GROUPS_RE = re.compile(r"replica_groups=\{\{([\d,]+)\}")
_GROUPS_IOTA_RE = re.compile(r"replica_groups=\[(\d+),(\d+)\]")
_PARAM_RE = re.compile(r"([\w.\-]+):\s*((?:\([^)]*\))|(?:[a-z0-9]+\[[\d,]*\](?:\{[^}]*\})?))")

COLLECTIVES = ("all-reduce", "all-gather", "reduce-scatter", "all-to-all",
               "collective-permute")

# Opcodes that produce no memory traffic of their own.
_FREE = {"parameter", "constant", "tuple", "get-tuple-element", "bitcast",
         "after-all", "partition-id", "replica-id", "iota", "reshape"}
# Sliced-access ops: count touched slices, not whole operands.
_SLICED = {"dynamic-slice", "dynamic-update-slice", "gather", "scatter"}


def _shape_list(text: str) -> list[tuple[str, tuple[int, ...]]]:
    out = []
    for dt, dims in _SHAPE_RE.findall(text):
        if dt in _DTYPE_BYTES:
            out.append((dt, tuple(int(d) for d in dims.split(",") if d)))
    return out


def _bytes_of(shapes) -> int:
    total = 0
    for dt, dims in shapes:
        n = 1
        for d in dims:
            n *= d
        total += n * _DTYPE_BYTES[dt]
    return total


def _elems_of(shapes) -> int:
    total = 0
    for _, dims in shapes:
        n = 1
        for d in dims:
            n *= d
        total += n
    return total


@dataclasses.dataclass
class Instr:
    name: str
    opcode: str
    out_shapes: list
    operands: list  # operand %names
    line: str


@dataclasses.dataclass
class Cost:
    flops: float = 0.0
    bytes: float = 0.0
    wire: float = 0.0
    coll_counts: Optional[dict] = None
    # Trip-count-corrected scatter-instruction count: the state-commit
    # scatters are the only scatters in the fabric programs, so this is
    # how fig11/CI assert the window commit is fused (scatters must not
    # scale with pipeline depth).
    scatters: float = 0.0

    def __iadd__(self, o: "Cost"):
        self.flops += o.flops
        self.bytes += o.bytes
        self.wire += o.wire
        self.scatters += o.scatters
        for k, v in (o.coll_counts or {}).items():
            self.coll_counts = self.coll_counts or {}
            dst = self.coll_counts.setdefault(
                k, {"count": 0, "wire_bytes": 0.0})
            dst["count"] += v["count"]
            dst["wire_bytes"] += v["wire_bytes"]
        return self

    def scaled(self, k: float) -> "Cost":
        return Cost(
            self.flops * k, self.bytes * k, self.wire * k,
            {kk: {"count": v["count"] * k, "wire_bytes": v["wire_bytes"] * k}
             for kk, v in (self.coll_counts or {}).items()} or None,
            self.scatters * k,
        )


class HloModule:
    def __init__(self, text: str):
        self.computations: dict[str, list[Instr]] = {}
        self.shape_of: dict[tuple[str, str], list] = {}  # (comp, name)
        self._parse(text)
        self._memo: dict[str, Cost] = {}

    def _parse(self, text: str) -> None:
        comp = None
        for raw in text.splitlines():
            line = raw.rstrip()
            if not line or line.startswith(("HloModule", "//", "#")):
                continue
            mc = _COMP_RE.match(line.strip())
            if mc and line.rstrip().endswith("{"):
                comp = mc.group(1)
                self.computations[comp] = []
                # Parameter shapes from the signature.
                for pname, ptype in _PARAM_RE.findall(mc.group(2)):
                    self.shape_of[(comp, pname)] = _shape_list(ptype)
                continue
            if comp is None:
                continue
            if line.strip() == "}":
                comp = None
                continue
            mi = _INSTR_RE.match(line)
            if not mi:
                continue
            name, out_type, opcode, rest = mi.groups()
            out_shapes = _shape_list(out_type)
            # Operand names: inside the first paren group only.
            depth, args = 0, ""
            for ch in "(" + rest:
                if ch == "(":
                    depth += 1
                    if depth == 1:
                        continue
                elif ch == ")":
                    depth -= 1
                    if depth == 0:
                        break
                if depth >= 1:
                    args += ch
            operands = _OPERAND_RE.findall(args)
            ins = Instr(name, opcode, out_shapes, operands, line.strip())
            self.computations[comp].append(ins)
            self.shape_of[(comp, name)] = out_shapes

    # ----- trip count of a while loop -----

    def _trip_count(self, cond_comp: str) -> int:
        best = 1
        for ins in self.computations.get(cond_comp, []):
            for m in _CONST_RE.finditer(ins.line):
                # scalar integer constants in the condition; jax loops
                # compare the induction var against the trip count.
                if "s32[]" in ins.line or "u32[]" in ins.line \
                        or "s64[]" in ins.line:
                    best = max(best, int(m.group(1)))
            if ins.opcode == "fusion":
                m = _CALLS_RE.search(ins.line)
                if m:
                    best = max(best, self._trip_count(m.group(1)))
        return best

    # ----- per-instruction costs -----

    def _dot_flops(self, comp: str, ins: Instr) -> float:
        out_elems = _elems_of(ins.out_shapes)
        m = _LHS_C_RE.search(ins.line)
        k = 1
        if m and ins.operands:
            lhs_shapes = self.shape_of.get((comp, ins.operands[0]))
            if lhs_shapes:
                _, dims = lhs_shapes[0]
                for idx in (int(i) for i in m.group(1).split(",") if i):
                    if idx < len(dims):
                        k *= dims[idx]
        return 2.0 * out_elems * k

    def _instr_cost(self, comp: str, ins: Instr) -> Cost:
        op = ins.opcode
        if op in _FREE or op.startswith("constant"):
            return Cost()
        if op == "while":
            body = _BODY_RE.search(ins.line)
            cond = _COND_RE.search(ins.line)
            trips = self._trip_count(cond.group(1)) if cond else 1
            inner = Cost()
            if body:
                inner += self.comp_cost(body.group(1))
            if cond:
                inner += self.comp_cost(cond.group(1))
            return inner.scaled(trips)
        if op in ("call", "async-start"):
            m = _CALLS_RE.search(ins.line) or _COND_RE.search(ins.line)
            return self.comp_cost(m.group(1)) if m else Cost()
        if op == "conditional":
            # max over branch computations (upper bound).
            branches = re.findall(r"branch_computations=\{([^}]*)\}",
                                  ins.line)
            names = []
            if branches:
                names = _OPERAND_RE.findall(branches[0])
            costs = [self.comp_cost(n) for n in names]
            best = Cost()
            for c in costs:
                if c.flops + c.bytes > best.flops + best.bytes:
                    best = c
            return best

        out_bytes = _bytes_of(ins.out_shapes)
        opnd_bytes = sum(
            _bytes_of(self.shape_of.get((comp, o), [])) for o in ins.operands
        )
        c = Cost()
        if op == "fusion":
            m = _CALLS_RE.search(ins.line)
            if m:
                nested = self.comp_cost(m.group(1))
                c.flops += nested.flops  # dots inside fusions still count
                c.wire += nested.wire
                c.scatters += nested.scatters
                if nested.coll_counts:
                    c += Cost(coll_counts=nested.coll_counts)
            c.bytes += out_bytes + opnd_bytes  # boundary traffic only
            return c
        if op == "dot":
            c.flops = self._dot_flops(comp, ins)
            c.bytes = out_bytes + opnd_bytes
            return c
        if op in _SLICED:
            # Touched region ~ the small operand/output, not the big buffer.
            small = min(out_bytes, opnd_bytes) if opnd_bytes else out_bytes
            if op == "dynamic-update-slice" and len(ins.operands) >= 2:
                upd = _bytes_of(
                    self.shape_of.get((comp, ins.operands[1]), []))
                small = 2 * upd
            c.bytes = small + out_bytes if op != "dynamic-update-slice" \
                else small
            if op == "scatter":
                c.scatters = 1.0
            return c
        base = op.split("-start")[0]
        if base in COLLECTIVES:
            full = max(out_bytes, opnd_bytes)
            g = 2
            m = _GROUPS_RE.search(ins.line)
            if m:
                g = len(m.group(1).split(","))
            else:
                m = _GROUPS_IOTA_RE.search(ins.line)
                if m:
                    g = int(m.group(2))
            g = max(g, 2)
            ring = (g - 1) / g
            wire = {"all-reduce": 2 * full * ring,
                    "collective-permute": full}.get(base, full * ring)
            c.wire = wire
            c.bytes = out_bytes + opnd_bytes
            c.coll_counts = {base: {"count": 1, "wire_bytes": wire}}
            return c
        # Generic elementwise / data movement.
        c.bytes = out_bytes + opnd_bytes
        c.flops = float(_elems_of(ins.out_shapes))  # 1 flop per out elem
        if op in ("transpose", "copy", "slice", "concatenate", "pad",
                  "broadcast", "reverse", "convert"):
            c.flops = 0.0
        return c

    def comp_cost(self, comp: str) -> Cost:
        if comp in self._memo:
            return self._memo[comp]
        total = Cost(coll_counts={})
        for ins in self.computations.get(comp, []):
            total += self._instr_cost(comp, ins)
        self._memo[comp] = total
        return total

    def entry_cost(self) -> Cost:
        # The entry computation is conventionally the last one, but find
        # the one that is not referenced by any other computation.
        referenced = set()
        for instrs in self.computations.values():
            for ins in instrs:
                for pat in (_CALLS_RE, _COND_RE, _BODY_RE):
                    m = pat.search(ins.line)
                    if m:
                        referenced.add(m.group(1))
                for b in re.findall(r"branch_computations=\{([^}]*)\}",
                                    ins.line):
                    referenced.update(_OPERAND_RE.findall(b))
        roots = [c for c in self.computations if c not in referenced]
        # Heuristic: the entry has the most instructions among roots.
        entry = max(roots or list(self.computations),
                    key=lambda c: len(self.computations[c]))
        return self.comp_cost(entry)


def analyze(hlo_text: str) -> dict:
    mod = HloModule(hlo_text)
    c = mod.entry_cost()
    return {
        "flops": c.flops,
        "bytes": c.bytes,
        "collective_wire_bytes": c.wire,
        "collectives": c.coll_counts or {},
        "scatter_count": c.scatters,
    }


def parse_collectives(hlo: str) -> dict:
    """Flat per-line collective census of post-SPMD HLO (the dry-run's
    report format): ``{type: {count, wire_bytes, buffer_bytes}}`` plus
    ``total_wire_bytes``. UNLIKE :func:`analyze` this counts each
    instruction once regardless of loop trip counts — it is the
    static-text census dryrun records next to the trip-corrected
    ``hlo_cost`` block. Shapes are per-device shard shapes; ring
    transfer factors as in :func:`analyze` (all-gather/reduce-scatter/
    all-to-all F*(g-1)/g, all-reduce 2*F*(g-1)/g, permute F).

    This is the one shared parser — ``launch/dryrun.py`` re-exports it
    (its private copy had drifted: no f8e4m3/f8e3m4, no s4/u4).
    """
    out = {c: {"count": 0, "wire_bytes": 0.0, "buffer_bytes": 0.0}
           for c in COLLECTIVES}
    for line in hlo.splitlines():
        s = line.strip()
        if not s or s.startswith("//"):
            continue
        op = None
        for c in COLLECTIVES:
            if f" {c}(" in s or f" {c}-start(" in s:
                op = c
                break
        if op is None:
            continue
        full = max((_bytes_of([t]) for t in _shape_list(s)), default=0)
        g = None
        m = _GROUPS_RE.search(s)
        if m:
            g = len(m.group(1).split(","))
        else:
            m = _GROUPS_IOTA_RE.search(s)
            if m:
                g = int(m.group(2))
        if not g or g <= 1:
            g = 2  # conservative
        ring = (g - 1) / g
        if op == "all-reduce":
            wire = 2 * full * ring
        elif op == "collective-permute":
            wire = full
        else:
            wire = full * ring
        out[op]["count"] += 1
        out[op]["wire_bytes"] += wire
        out[op]["buffer_bytes"] += full
    out["total_wire_bytes"] = sum(
        v["wire_bytes"] for v in out.values() if isinstance(v, dict)
    )
    return out
