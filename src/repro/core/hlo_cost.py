"""HLO-text cost analysis that is *loop-aware*.

``compiled.cost_analysis()`` counts a while-loop body ONCE, which silently
undercounts scan-over-layers models by a factor of n_layers (validated in
tests/test_roofline.py).  This module parses the compiled HLO text and walks
the computation graph from ENTRY, multiplying while bodies by their
``known_trip_count`` backend config, so the roofline terms are correct for
scanned programs.  It also attributes collective wire bytes inside loops
(a per-layer all-reduce in a 95-layer scan is 95 all-reduces, not 1).

Cost model:
  flops   dot = 2 * |out| * contracted;  float elementwise = |out|;
          reduce/reduce-window = |in|;  conditional = max(branches)
  bytes   post-fusion HBM model: every top-level op moves its operands +
          output once; fusions count only their boundary; free ops
          (parameter, tuple, gte, bitcast, constant, reshape) move nothing.
  wire    ring-algorithm collective bytes (see core.roofline)
"""
from __future__ import annotations

import re
from dataclasses import dataclass, field
from typing import Dict, List, Optional, Tuple

from .roofline import DTYPE_BYTES, _ring_wire_bytes

_COMP_START = re.compile(r"^(ENTRY\s+)?%?([\w\.\-]+)\s*\((.*)\)\s*->.*\{\s*$")
_SHAPE = re.compile(r"\b([a-z][a-z0-9]*)\[([0-9,]*)\]")
_TRIP = re.compile(r'known_trip_count[":{]+n[":]+(\d+)')
_CALLS = re.compile(r"calls=%?([\w\.\-]+)")
_BODY = re.compile(r"body=%?([\w\.\-]+)")
_COND = re.compile(r"condition=%?([\w\.\-]+)")
_BRANCHES = re.compile(r"branch_computations=\{([^}]*)\}")
_GROUPS = re.compile(r"replica_groups=\{\{([^}]*)\}")
_GROUPS_IOTA = re.compile(r"replica_groups=\[(\d+),(\d+)\]")
_CONTRACT = re.compile(r"lhs_contracting_dims=\{([0-9,]*)\}")

_FLOP1 = {  # 1 flop per output element
    "add", "subtract", "multiply", "divide", "maximum", "minimum", "negate",
    "abs", "exponential", "exponential-minus-one", "log", "log-plus-one",
    "tanh", "rsqrt", "sqrt", "power", "cosine", "sine", "logistic",
    "atan2", "cbrt", "erf", "floor", "ceil", "round-nearest-afz",
    "remainder",
}
_FREE = {
    "parameter", "constant", "get-tuple-element", "tuple", "bitcast",
    "reshape", "after-all", "partition-id", "replica-id", "iota",
    "rng-bit-generator", "rng-get-and-update-state", "opt-barrier",
    "custom-call", "get-dimension-size",
}
_COLLECTIVES = ("all-reduce", "all-gather", "reduce-scatter", "all-to-all",
                "collective-permute")


def _shape_elems_bytes(text: str) -> Tuple[int, int]:
    """Total (elements, bytes) over every shape literal in ``text``."""
    elems = tot = 0
    for dtype, dims in _SHAPE.findall(text):
        n = 1
        for d in dims.split(","):
            if d.strip():
                n *= int(d)
        nb = DTYPE_BYTES.get(dtype, 0)
        if nb:
            elems += n
            tot += n * nb
    return elems, tot


@dataclass
class Cost:
    """``bytes`` is the CPU-granularity upper bound (every top-level op moves
    its operands); ``bytes_fused`` assumes a TPU-grade fusing compiler where
    elementwise/convert/select chains ride along with their consumers —
    the memory roofline term uses ``bytes_fused`` and reports both."""
    flops: float = 0.0
    bytes: float = 0.0
    bytes_fused: float = 0.0
    wire_bytes: float = 0.0
    collective_counts: Dict[str, int] = field(default_factory=dict)
    collective_bytes: Dict[str, float] = field(default_factory=dict)

    def __iadd__(self, o: "Cost"):
        self.flops += o.flops
        self.bytes += o.bytes
        self.bytes_fused += o.bytes_fused
        self.wire_bytes += o.wire_bytes
        for k, v in o.collective_counts.items():
            self.collective_counts[k] = self.collective_counts.get(k, 0) + v
        for k, v in o.collective_bytes.items():
            self.collective_bytes[k] = self.collective_bytes.get(k, 0.0) + v
        return self

    def scaled(self, t: float) -> "Cost":
        return Cost(self.flops * t, self.bytes * t, self.bytes_fused * t,
                    self.wire_bytes * t,
                    {k: int(v * t) for k, v in self.collective_counts.items()},
                    {k: v * t for k, v in self.collective_bytes.items()})


@dataclass
class _Op:
    opcode: str
    line: str
    out_elems: int
    out_bytes: int
    in_elems: int
    in_bytes: int
    lhs_dims: Optional[List[int]] = None    # first-operand dims (for dot)
    arg_bytes: Optional[List[int]] = None   # per-operand bytes
    arg_names: Optional[List[str]] = None   # per-operand value names


_NAME = re.compile(r"%([\w\.\-]+)")


def _split_args(s: str) -> List[str]:
    """Split an HLO operand list on top-level commas."""
    out, depth, cur = [], 0, []
    for ch in s:
        if ch in "([{":
            depth += 1
        elif ch in ")]}":
            depth -= 1
        if ch == "," and depth == 0:
            out.append("".join(cur))
            cur = []
        else:
            cur.append(ch)
    if cur:
        out.append("".join(cur))
    return out


def _parse_computations(hlo: str) -> Tuple[Dict[str, List[_Op]], Optional[str]]:
    comps: Dict[str, List[_Op]] = {}
    entry: Optional[str] = None
    cur: Optional[str] = None
    # symbol table: value name -> (elems, bytes, dims-of-first-shape)
    sym: Dict[str, Tuple[int, int, List[int]]] = {}
    for raw in hlo.splitlines():
        line = raw.rstrip()
        stripped = line.strip()
        m = _COMP_START.match(stripped)
        if m and stripped.endswith("{"):
            cur = m.group(2)
            comps[cur] = []
            sym = {}
            if m.group(1):
                entry = cur
            # computation parameters appear in the signature:  (p: f32[2,3])
            sig = m.group(3)
            for part in _split_args(sig):
                if ":" in part:
                    pname, ptype = part.split(":", 1)
                    e, b = _shape_elems_bytes(ptype)
                    dims = _first_dims(ptype)
                    sym[pname.strip().lstrip("%")] = (e, b, dims)
            continue
        if stripped == "}":
            cur = None
            continue
        if cur is None or " = " not in line:
            continue
        lhs, rhs = line.split(" = ", 1)
        lhs_name = lhs.strip().lstrip("%")
        if lhs_name.startswith("ROOT "):
            lhs_name = lhs_name[5:].lstrip("%")
        if lhs.strip().startswith("ROOT"):
            lhs_name = lhs.strip().split()[-1].lstrip("%")
        rhs2 = rhs.strip()
        # the output type may be a tuple "(s32[], f32[2,3])" — skip it first
        if rhs2.startswith("("):
            depth = 0
            tend = len(rhs2)
            for i, ch in enumerate(rhs2):
                if ch == "(":
                    depth += 1
                elif ch == ")":
                    depth -= 1
                    if depth == 0:
                        tend = i + 1
                        break
            head = rhs2[:tend]
            rest = rhs2[tend:].lstrip()
        else:
            parts = rhs2.split(None, 1)
            head = parts[0]
            rest = parts[1] if len(parts) > 1 else ""
        paren = rest.find("(")
        if paren < 0:
            continue
        opcode = rest[:paren].strip()
        out_e, out_b = _shape_elems_bytes(head)
        sym[lhs_name] = (out_e, out_b, _first_dims(head))
        # strip async wrappers: count "-start", skip "-done"/"-update"
        if opcode.endswith("-done") or opcode.endswith("-update"):
            continue
        base = opcode[:-6] if opcode.endswith("-start") else opcode
        # operand region: top-level parens only
        depth, end = 0, len(rest)
        for i, ch in enumerate(rest[paren:], paren):
            if ch == "(":
                depth += 1
            elif ch == ")":
                depth -= 1
                if depth == 0:
                    end = i
                    break
        args = _split_args(rest[paren + 1:end])
        in_e = in_b = 0
        lhs_dims: Optional[List[int]] = None
        arg_bytes: List[int] = []
        arg_names: List[str] = []
        for i, a in enumerate(args):
            nm = _NAME.search(a)
            if _SHAPE.search(a):
                e, b = _shape_elems_bytes(a)
                dims = _first_dims(a)
            else:
                e, b, dims = sym.get(nm.group(1), (0, 0, [])) if nm \
                    else (0, 0, [])
            in_e += e
            in_b += b
            arg_bytes.append(b)
            arg_names.append(nm.group(1) if nm else "")
            if i == 0:
                lhs_dims = dims
        comps[cur].append(_Op(base, line, out_e, out_b, in_e, in_b,
                              lhs_dims, arg_bytes, arg_names))
    return comps, entry


def _first_dims(text: str) -> List[int]:
    m = _SHAPE.search(text)
    if not m:
        return []
    return [int(d) for d in m.group(2).split(",") if d.strip()]


def _dot_flops(op: _Op) -> float:
    m = _CONTRACT.search(op.line)
    lhs_dims = op.lhs_dims or []
    contracted = 1
    if m and m.group(1).strip() and lhs_dims:
        for i in m.group(1).split(","):
            idx = int(i)
            if idx < len(lhs_dims):
                contracted *= lhs_dims[idx]
    return 2.0 * op.out_elems * contracted


def _group_size(line: str) -> int:
    m = _GROUPS.search(line)
    if m:
        return len([g for g in m.group(1).split(",") if g.strip()])
    m2 = _GROUPS_IOTA.search(line)
    return int(m2.group(2)) if m2 else 1


# loop-invariant operands up to this size are assumed VMEM-resident across
# loop iterations (the TPU would hoist them); larger ones stream per trip
VMEM_RESIDENT_BYTES = 48 * 2 ** 20


def _body_invariants(ops: List[_Op]) -> Dict[str, int]:
    """gte-name -> bytes for loop-carried tuple slots that pass through the
    body unchanged (root tuple operand k is the gte of index k)."""
    gtes: Dict[str, Tuple[int, int]] = {}       # name -> (index, bytes)
    root: Optional[_Op] = None
    for op in ops:
        if op.opcode == "get-tuple-element":
            mi = re.search(r"index=(\d+)", op.line)
            nm = re.search(r"%([\w\.\-]+)\s*=", op.line)
            if mi and nm:
                gtes[nm.group(1)] = (int(mi.group(1)), op.out_bytes)
        if op.opcode == "tuple" and "ROOT" in op.line:
            root = op
    if root is None or not root.arg_names:
        return {}
    inv: Dict[str, int] = {}
    for pos, nm in enumerate(root.arg_names):
        if nm in gtes and gtes[nm][0] == pos:
            inv[nm] = gtes[nm][1]
    return inv


def analyze_hlo(hlo: str) -> Cost:
    comps, entry = _parse_computations(hlo)
    if entry is None:
        # single-computation fallback
        entry = next(iter(comps)) if comps else None
        if entry is None:
            return Cost()
    memo: Dict[Tuple[str, frozenset], Cost] = {}

    producers: Dict[str, Dict[str, _Op]] = {}

    def _producer_map(name: str) -> Dict[str, _Op]:
        if name not in producers:
            m: Dict[str, _Op] = {}
            for op in comps.get(name, []):
                nm = re.search(r"%([\w\.\-]+)\s*=", op.line)
                if nm:
                    m[nm.group(1)] = op
            producers[name] = m
        return producers[name]

    def comp_cost(name: str, exclude: frozenset = frozenset()) -> Cost:
        key = (name, exclude)
        if key in memo:
            return memo[key]
        memo[key] = Cost()           # break cycles defensively
        total = Cost()
        pmap = _producer_map(name)
        for op in comps.get(name, []):
            total += op_cost(op, exclude, pmap)
        memo[key] = total
        return total

    def _excluded_bytes(op: _Op, exclude: frozenset) -> float:
        if not exclude or not op.arg_names:
            return 0.0
        return float(sum(b for n, b in zip(op.arg_names, op.arg_bytes or [])
                         if n in exclude))

    def op_cost(op: _Op, exclude: frozenset = frozenset(),
                pmap: Optional[Dict[str, _Op]] = None) -> Cost:
        c = Cost()
        oc = op.opcode
        if oc == "while":
            body = _BODY.search(op.line)
            cond = _COND.search(op.line)
            trips = 1
            mt = _TRIP.search(op.line)
            if mt:
                trips = int(mt.group(1))
            inner = Cost()
            once = 0.0
            if body:
                bname = body.group(1)
                inv = {n: b for n, b in
                       _body_invariants(comps.get(bname, [])).items()
                       if 0 < b <= VMEM_RESIDENT_BYTES}
                inner += comp_cost(bname, frozenset(inv))
                once = float(sum(set(inv.values())) if False
                             else sum(inv.values()))
            if cond:
                inner += comp_cost(cond.group(1))
            total = inner.scaled(trips)
            # invariant small operands stream to VMEM once, not per trip
            total.bytes += once
            total.bytes_fused += once
            return total
        if oc == "conditional":
            mb = _BRANCHES.search(op.line)
            if mb:
                branches = [b.strip().lstrip("%") for b in
                            mb.group(1).split(",") if b.strip()]
                costs = [comp_cost(b) for b in branches]
                if costs:
                    best = max(costs, key=lambda x: x.flops + x.bytes)
                    c += best
            c.bytes += op.in_bytes + op.out_bytes
            return c
        if oc == "fusion":
            mcall = _CALLS.search(op.line)
            inner_bytes = float(op.in_bytes)
            inner_fused = float(op.in_bytes)
            if mcall:
                inner = comp_cost(mcall.group(1))
                c.flops += inner.flops          # flops inside the fusion
                c.wire_bytes += inner.wire_bytes
                for k, v in inner.collective_counts.items():
                    c.collective_counts[k] = v
                for k, v in inner.collective_bytes.items():
                    c.collective_bytes[k] = v
                inner_bytes = inner.bytes
                inner_fused = inner.bytes_fused
            # boundary traffic, but a fusion that only windows into a big
            # operand/output (dynamic-slice / dynamic-update-slice of the
            # stacked scan buffers) moves the window, not the buffer: take
            # the smaller of boundary and inner-walk models.
            boundary = float(op.in_bytes + op.out_bytes) \
                - _excluded_bytes(op, exclude)
            # a fusion node IS the fused unit: its boundary is what a TPU
            # fusion moves; the inner walk only catches slice/DUS windows
            b = min(boundary, inner_bytes)
            c.bytes += b
            c.bytes_fused += b
            return c
        if oc == "call":
            mcall = _CALLS.search(op.line) or re.search(
                r"to_apply=%?([\w\.\-]+)", op.line)
            if mcall:
                c += comp_cost(mcall.group(1))
            return c
        if oc in _COLLECTIVES:
            n = _group_size(op.line)
            in_b = float(op.in_bytes if op.in_bytes else op.out_bytes)
            # bf16-emulation correction: the CPU backend upcasts bf16 values
            # to f32 around dots, so collectives of "converted" operands are
            # printed at twice the width a TPU program would move.  When the
            # producing op is a pure upcast (input bytes == output/2), charge
            # the collective at the source width.
            if pmap and op.arg_names:
                shrink = True
                for a in op.arg_names:
                    prod = pmap.get(a)
                    if prod is None or prod.opcode not in (
                            "convert", "fusion", "copy"):
                        shrink = False
                        break
                    if not (prod.arg_bytes and any(
                            b2 * 2 == prod.out_bytes      # pure upcast
                            or b2 == 2 * prod.out_bytes   # slice of bf16 full
                            for b2 in prod.arg_bytes if b2)):
                        shrink = False
                        break
                if shrink:
                    in_b *= 0.5
            wire = _ring_wire_bytes(oc, in_b, op.out_bytes, n)
            c.wire_bytes += wire
            c.collective_counts[oc] = 1
            c.collective_bytes[oc] = wire
            c.bytes += op.in_bytes + op.out_bytes
            c.bytes_fused += op.in_bytes + op.out_bytes
            return c
        if oc in _FREE:
            if oc == "custom-call":
                c.bytes += op.in_bytes + op.out_bytes
            return c
        # ordinary op
        skip = _excluded_bytes(op, exclude)
        if oc == "dot":
            c.flops += _dot_flops(op)
        elif oc == "convolution":
            c.flops += 2.0 * op.out_elems  # no convs in these models
        elif oc in _FLOP1 or oc in ("select", "compare", "clamp", "and",
                                    "or", "not", "xor"):
            if oc in _FLOP1:
                c.flops += op.out_elems
        elif oc in ("reduce", "reduce-window", "sort", "scatter"):
            c.flops += op.in_elems
        # HBM traffic: slicing/windowed ops touch only the window, not the
        # whole operand (a scan reading per-layer slices of stacked params
        # would otherwise be charged L x full-stack bytes).
        fusable = oc in _FLOP1 or oc in ("select", "compare", "clamp",
                                         "and", "or", "not", "xor",
                                         "convert", "copy", "transpose",
                                         "broadcast", "reverse", "pad")
        if oc in ("dynamic-slice", "slice", "gather"):
            b = 2.0 * op.out_bytes + (
                sum(op.arg_bytes[1:]) if op.arg_bytes else 0)
            c.bytes += b
            c.bytes_fused += b
        elif oc == "dynamic-update-slice":
            upd = op.arg_bytes[1] if op.arg_bytes and len(op.arg_bytes) > 1 \
                else op.out_bytes
            c.bytes += 2.0 * upd
            c.bytes_fused += 2.0 * upd
        elif oc == "scatter":
            upd = op.arg_bytes[2] if op.arg_bytes and len(op.arg_bytes) > 2 \
                else op.out_bytes
            idx = op.arg_bytes[1] if op.arg_bytes and len(op.arg_bytes) > 1 \
                else 0
            c.bytes += 2.0 * upd + idx
            c.bytes_fused += 2.0 * upd + idx
        elif oc == "broadcast":
            c.bytes += op.out_bytes
        elif fusable:
            # upper bound: materialised; fused model: rides with consumer
            c.bytes += max(op.in_bytes - skip, 0) + op.out_bytes
        else:
            b = max(op.in_bytes - skip, 0) + op.out_bytes
            c.bytes += b
            c.bytes_fused += b
        return c

    return comp_cost(entry)


def cost_with_loops(compiled) -> Cost:
    """Loop-aware cost of a compiled executable (per device, SPMD)."""
    return analyze_hlo(compiled.as_text())


# ---------------------------------------------------------------------------
# Profiling: weighted top ops (the dry-run "profile" — there is no wall-clock
# trace on this host, so §Perf iterations read this instead)
# ---------------------------------------------------------------------------

def top_costs(hlo: str, k: int = 15):
    """Top-k ops by trip-weighted fused bytes and by flops.  Returns
    (by_bytes, by_flops, by_wire) lists of (weighted_value, weight, line)."""
    comps, entry = _parse_computations(hlo)
    weights = {entry: 1.0}
    order = [entry]
    i = 0
    while i < len(order):
        name = order[i]
        i += 1
        w = weights[name]
        for op in comps.get(name, []):
            trips = 1
            if op.opcode == "while":
                mt = _TRIP.search(op.line)
                trips = int(mt.group(1)) if mt else 1
            for regex in (_BODY, _COND, _CALLS):
                m = regex.search(op.line)
                if m:
                    child = m.group(1)
                    if child not in weights:
                        weights[child] = 0.0
                        order.append(child)
                    weights[child] += w * trips

    memo_b: Dict[str, float] = {}

    def comp_bytes(name):
        if name in memo_b:
            return memo_b[name]
        memo_b[name] = 0.0
        t = sum(op_bytes(op)[0] for op in comps.get(name, []))
        memo_b[name] = t
        return t

    FUSABLE = _FLOP1 | {"select", "compare", "clamp", "and", "or", "not",
                        "xor", "convert", "copy", "transpose", "broadcast",
                        "reverse", "pad"}

    def op_bytes(op):
        oc = op.opcode
        if oc == "while":
            return 0.0, True        # charged via child weights
        if oc == "fusion":
            m = _CALLS.search(op.line)
            inner = comp_bytes(m.group(1)) if m else 1e30
            return min(float(op.in_bytes + op.out_bytes), inner), False
        if oc in _FREE or oc in FUSABLE:
            return 0.0, False
        if oc in ("dynamic-slice", "slice", "gather"):
            return 2.0 * op.out_bytes, False
        if oc == "dynamic-update-slice":
            return 2.0 * (op.arg_bytes[1] if op.arg_bytes
                          and len(op.arg_bytes) > 1 else op.out_bytes), False
        return float(op.in_bytes + op.out_bytes), False

    by_bytes, by_flops, by_wire = [], [], []
    for name, ops in comps.items():
        w = weights.get(name, 0.0)
        if not w:
            continue
        for op in ops:
            if op.opcode == "while":
                continue
            b, skip = op_bytes(op)
            if b:
                by_bytes.append((w * b, w, op.line.strip()[:140]))
            if op.opcode == "dot":
                f = _dot_flops(op)
                if f:
                    by_flops.append((w * f, w, op.line.strip()[:140]))
            if op.opcode in _COLLECTIVES:
                n = _group_size(op.line)
                in_b = op.in_bytes or op.out_bytes
                wire = _ring_wire_bytes(op.opcode, in_b, op.out_bytes, n)
                if wire:
                    by_wire.append((w * wire, w, op.line.strip()[:140]))
    for lst in (by_bytes, by_flops, by_wire):
        lst.sort(key=lambda t: -t[0])
    return by_bytes[:k], by_flops[:k], by_wire[:k]
