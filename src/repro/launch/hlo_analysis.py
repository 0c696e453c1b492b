"""Read compiled HLO text: collective traffic and named-scope ownership.

XLA's ``cost_analysis()`` counts a while-loop (scan) body ONCE, not by
trip count, so any layer-scanned program under-reports by ~L x. The
optimized HLO, however, annotates every while op with
``known_trip_count`` — so we parse the module into computations, build
the while/call nesting graph, and multiply each computation's
collective bytes by the product of its enclosing trip counts. This
gives exact per-device collective traffic for §Roofline.

The same parse maps every instruction of a compiled program to the
innermost ``jax.named_scope`` it belongs to (:func:`scope_map`), so
that device time read from a profiler trace, where an operation is
named only by its HLO instruction, can be charged to a scope.
"""

from __future__ import annotations

import re
from collections import defaultdict
from typing import NamedTuple

_DTYPE_BYTES = {
    "f64": 8, "f32": 4, "f16": 2, "bf16": 2, "f8e4m3fn": 1, "f8e5m2": 1,
    "s64": 8, "u64": 8, "s32": 4, "u32": 4, "s16": 2, "u16": 2,
    "s8": 1, "u8": 1, "pred": 1, "c64": 8, "c128": 16,
}
COLLECTIVES = ("all-reduce", "all-gather", "reduce-scatter", "all-to-all", "collective-permute")
#: the prefix of the scope names that :func:`scope_map` follows, and
#: the scope of an instruction that has none of them
SCOPE_PREFIX = "tmsn."
UNSCOPED = "unscoped"
#: instructions that run other computations as a sequence of device
#: operations; the trace shows each as an event spanning its children
CONTROL = ("while", "conditional", "call")

_SHAPE_RE = re.compile(
    r"(f64|f32|f16|bf16|f8e4m3fn|f8e5m2|s64|u64|s32|u32|s16|u16|s8|u8|pred|c64|c128)\[([0-9,]*)\]"
)
_COMP_HDR = re.compile(r"^(?:ENTRY\s+)?%?([\w.\-]+)\s*\(.*\)\s*->.*\{$")
_TRIP_RE = re.compile(r'known_trip_count[^0-9]*?(\d+)')
_REF_RE = re.compile(r"%([\w.\-]+)")
_OP_NAME_RE = re.compile(r'op_name="([^"]*)"')
_CALLED_RE = re.compile(
    r"\b(body|condition|calls|to_apply|true_computation|false_computation)=%([\w.\-]+)"
)
_BRANCHES_RE = re.compile(r"branch_computations=\{([^}]*)\}")
_BRANCH_ATTRS = ("branch", "true_computation", "false_computation")


class Instruction(NamedTuple):
    name: str
    opcode: str
    type_text: str  # the result type, e.g. ``f32[8,128]{1,0}``
    operands: tuple[str, ...]
    called: tuple[tuple[str, str], ...]  # (attribute, computation)
    op_name: str


def _close(text: str, i: int) -> int:
    """Index just past the bracket group that opens at ``text[i]``."""
    depth = 0
    for j in range(i, len(text)):
        c = text[j]
        if c in "([{":
            depth += 1
        elif c in ")]}":
            depth -= 1
            if depth == 0:
                return j + 1
    return len(text)


def parse_instruction(line: str) -> Instruction | None:
    """One instruction line of an HLO computation, or None."""
    s = line.strip()
    if s.startswith("ROOT "):
        s = s[5:]
    if not s.startswith("%") or " = " not in s:
        return None
    name, rhs = s.split(" = ", 1)
    end = _close(rhs, 0) if rhs.startswith("(") else rhs.find(" ")
    type_text, rest = rhs[:end], rhs[end:].lstrip()
    paren = rest.find("(")
    if paren < 0:
        return None
    opcode = rest[:paren].strip()
    args_end = _close(rest, paren)
    attrs = rest[args_end:]
    called = [(m.group(1), m.group(2)) for m in _CALLED_RE.finditer(attrs)]
    bm = _BRANCHES_RE.search(attrs)
    if bm:
        called += [("branch", b) for b in _REF_RE.findall(bm.group(1))]
    om = _OP_NAME_RE.search(attrs)
    return Instruction(
        name=name[1:],
        opcode=opcode,
        type_text=type_text,
        operands=tuple(_REF_RE.findall(rest[paren:args_end])),
        called=tuple(called),
        op_name=om.group(1) if om else "",
    )


def split_computations(hlo_text: str) -> tuple[dict[str, list[str]], str | None]:
    """``({computation: [instruction lines]}, entry name)``."""
    comps: dict[str, list[str]] = {}
    entry = None
    cur = None
    for line in hlo_text.splitlines():
        if not line.startswith(" "):
            m = _COMP_HDR.match(line.strip())
            if m:
                cur = m.group(1)
                comps[cur] = []
                if line.strip().startswith("ENTRY"):
                    entry = cur
            elif line.strip() == "}":
                cur = None
            continue
        if cur is not None:
            comps[cur].append(line.strip())
    return comps, entry


def _shape_bytes(text: str) -> int:
    total = 0
    for m in _SHAPE_RE.finditer(text):
        n = 1
        for d in m.group(2).split(","):
            if d:
                n *= int(d)
        total += n * _DTYPE_BYTES[m.group(1)]
    return total


def parse_collectives(hlo_text: str) -> dict[str, float]:
    """Per-collective-kind bytes, weighted by loop trip counts.

    A while body and its condition count once per trip. Every branch of
    a conditional counts once, as if each were taken: an upper bound
    where more than one branch holds collectives."""
    comps, entry = split_computations(hlo_text)

    # per-computation direct collective bytes + child edges
    direct: dict[str, dict[str, int]] = {c: defaultdict(int) for c in comps}
    children: dict[str, list[tuple[str, int]]] = {c: [] for c in comps}
    for c, lines in comps.items():
        for s in lines:
            ins = parse_instruction(s)
            if ins is None:
                continue
            base = ins.opcode[:-6] if ins.opcode.endswith("-start") else ins.opcode
            if base in COLLECTIVES:
                direct[c][base] += _shape_bytes(ins.type_text)
            for attr, comp in ins.called:
                if comp not in comps:
                    continue
                if attr in ("body", "condition"):
                    tm = _TRIP_RE.search(s)
                    children[c].append((comp, int(tm.group(1)) if tm else 1))
                elif attr in _BRANCH_ATTRS or (
                    attr in ("calls", "to_apply") and ins.opcode in ("call", "fusion")
                ):
                    children[c].append((comp, 1))

    # accumulate multipliers from entry
    mult: dict[str, float] = defaultdict(float)
    if entry is None:
        entry = next(iter(comps), None)
    if entry is None:
        return {k: 0.0 for k in COLLECTIVES}
    stack = [(entry, 1.0)]
    seen_edges = 0
    while stack:
        comp, m = stack.pop()
        mult[comp] += m
        for child, trip in children.get(comp, ()):
            seen_edges += 1
            if seen_edges > 100_000:  # cycle guard
                break
            stack.append((child, m * trip))

    out = {k: 0.0 for k in COLLECTIVES}
    for c, d in direct.items():
        if mult.get(c, 0.0) <= 0.0:
            # unreachable from entry (a reducer's to_apply): not counted
            continue
        for k, v in d.items():
            out[k] += v * mult[c]
    return out


class ScopeEntry(NamedTuple):
    scope: str  # innermost named scope with SCOPE_PREFIX, or UNSCOPED
    leaf: bool  # runs as a device operation of its own


_SCOPE_RE = re.compile(r"(?<![\w.])" + re.escape(SCOPE_PREFIX) + r"\w+")


def _innermost(op_name: str) -> str | None:
    found = _SCOPE_RE.findall(op_name)
    return found[-1] if found else None


def scope_map(hlo_text: str) -> dict[str, ScopeEntry]:
    """``{instruction name: ScopeEntry}`` of a compiled program.

    An instruction takes, in this order: the innermost path component
    of its own ``op_name`` that starts with :data:`SCOPE_PREFIX`; else
    the scope of its first consumer in the same computation that has one (this is
    how the copies XLA inserts, which carry no metadata, are charged);
    else the scope of the instruction that calls its computation (a
    while body or condition, a conditional branch, a fusion); else
    :data:`UNSCOPED`.

    A leaf is an instruction of a computation that runs as a sequence
    of device operations (the entry, while bodies and conditions,
    conditional branches, called computations), other than a while,
    conditional or call: so the device time of the leaves of one call
    of the program adds up to the program's. Instructions inside a
    fusion or a reducer are not leaves.
    """
    comps, entry = split_computations(hlo_text)
    parsed = {c: [i for i in map(parse_instruction, lines) if i is not None] for c, lines in comps.items()}
    caller: dict[str, Instruction] = {}
    sequenced = {entry}
    order = [entry] if entry in parsed else []
    for c in order:  # grows while it is walked: callers before callees
        for ins in parsed[c]:
            for _, comp in ins.called:
                if comp in parsed and comp not in caller:
                    caller[comp] = ins
                    order.append(comp)
                    if ins.opcode in CONTROL:
                        sequenced.add(comp)
    order += [c for c in parsed if c not in caller and c != entry]

    out: dict[str, ScopeEntry] = {}
    for c in order:
        instrs = parsed[c]
        scope = {i.name: _innermost(i.op_name) for i in instrs}
        users: dict[str, list[str]] = defaultdict(list)
        for i in instrs:
            for op in i.operands:
                users[op].append(i.name)
        # text order defines before it uses, so a reverse pass resolves
        # chains of unscoped producers (copy -> copy -> conditional)
        for i in reversed(instrs):
            if scope[i.name] is None:
                scope[i.name] = next((scope[u] for u in users[i.name] if scope.get(u)), None)
        up = caller.get(c)
        inherited = out[up.name].scope if up is not None and up.name in out else UNSCOPED
        for i in instrs:
            out[i.name] = ScopeEntry(scope[i.name] or inherited, c in sequenced and i.opcode not in CONTROL)
    return out
