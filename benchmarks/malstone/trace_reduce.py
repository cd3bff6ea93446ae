"""From a profiler trace (``.xplane.pb``) to the numbers the per-layer
metrics read.

A TPU trace holds one plane per chip (``/device:TPU:<n>``) whose
``XLA Ops`` line has one event per executed HLO instruction, named
``%<instruction> = <shape> <opcode>(...)``. Control-flow instructions
(``while``, ``conditional``, ``call``) contain the events of their bodies,
so an instruction's own time is its duration less that of the events nested
in it. Asynchronous copies and collectives also appear on ``Async XLA Ops``.
On the CPU, XLA's thunks carry an ``hlo_op`` stat on the host plane's
threads; they are read as one device, so the same code runs on a trace
recorded here.

What an instruction does comes from the compiled program's HLO text
(``parse_hlo``): its opcode, the opcodes inside a fusion it calls, and the
JAX op path in its metadata. The benchmark's own host spans are
``jax.profiler.TraceAnnotation`` events named ``bench.*``.
"""

from __future__ import annotations

import collections
import re
from typing import NamedTuple

COLLECTIVES = frozenset({
    "all-to-all", "all-reduce", "all-gather", "reduce-scatter",
    "collective-permute", "all-reduce-start", "all-reduce-done",
    "all-gather-start", "all-gather-done", "collective-permute-start",
    "collective-permute-done", "ragged-all-to-all",
})
CONTAINERS = frozenset({"while", "conditional", "call"})
# JAX scopes left out of an op's label: they name no layer
_GENERIC_SCOPES = frozenset({"while", "body", "cond", "closed_call", "vmap()",
                             "shard_map", "checkpoint", "remat"})
HOST_SPAN_PREFIX = "bench."
JOB_SPAN = "bench.job"      # one whole job; the window is their extent


class OpInfo(NamedTuple):
    opcode: str
    op_name: str            # JAX op path from the metadata, "" if none
    inner: frozenset        # opcodes of the computations it calls


class Op(NamedTuple):
    name: str
    start_ns: float
    end_ns: float


class Trace(NamedTuple):
    ops: dict               # device id -> [[Op] per trace line]
    async_ops: dict         # device id -> [Op]
    host_spans: list        # [Op] named bench.*


# ------------------------------------------------------------------ HLO
_INSTR = re.compile(r"^\s*(?:ROOT\s+)?%([^\s=]+)\s*=\s*(.*)$")
_COMP = re.compile(r"^\s*(?:ENTRY\s+)?%([^\s(]+).*\{\s*$")
_CALLS = re.compile(
    r"\b(?:calls|to_apply|body|condition|branch_computations)="
    r"\{?%([\w.\-]+)")
_OP_NAME = re.compile(r'op_name="([^"]*)"')


def _opcode(rest: str) -> str:
    """The opcode of an instruction, given the text after ``=``."""
    i = 0
    if rest.startswith("("):            # tuple shape: skip to its close
        depth = 0
        for i, ch in enumerate(rest):
            depth += ch == "("
            depth -= ch == ")"
            if depth == 0:
                break
        i += 1
    else:
        i = rest.find(" ")
    m = re.match(r"\s*([a-z][\w\-]*)\(", rest[i:])
    return m.group(1) if m else ""


def parse_hlo(text: str) -> dict:
    """Instruction name -> ``OpInfo`` for every instruction of an HLO
    module's text."""
    comp_ops = collections.defaultdict(set)
    comp_calls = collections.defaultdict(set)
    raw = {}
    comp = None
    for line in text.splitlines():
        m = _INSTR.match(line)
        if m and comp is not None:
            name, rest = m.groups()
            opcode = _opcode(rest)
            called = _CALLS.findall(rest)
            op_name = _OP_NAME.search(rest)
            raw[name] = (opcode, op_name.group(1) if op_name else "", called)
            comp_ops[comp].add(opcode)
            comp_calls[comp].update(called)
            continue
        m = _COMP.match(line)
        if m:
            comp = m.group(1)

    closure = {}

    def inner(c, seen=()):
        if c in closure:
            return closure[c]
        out = set(comp_ops.get(c, ()))
        for d in comp_calls.get(c, ()):
            if d not in seen:
                out |= inner(d, seen + (c,))
        closure[c] = frozenset(out)
        return closure[c]

    return {name: OpInfo(opcode, op_name,
                         frozenset().union(*(inner(c) for c in called)))
            for name, (opcode, op_name, called) in raw.items()}


def is_collective(info: OpInfo) -> bool:
    """A collective, or a fusion or async wrapper around one."""
    return info.opcode not in CONTAINERS and (
        info.opcode in COLLECTIVES or bool(info.inner & COLLECTIVES))


def is_scatter(info: OpInfo) -> bool:
    """A scatter, or a fusion around one."""
    return info.opcode not in CONTAINERS and (
        info.opcode == "scatter" or "scatter" in info.inner)


# ---------------------------------------------------------------- trace
def _stats(event) -> dict:
    return {k: v for k, v in event.stats}


def load(path) -> Trace:
    """Read an ``.xplane.pb`` file into per-device op lists and the
    benchmark's host spans."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(str(path))
    ops, async_ops, host, cpu_lines = {}, {}, [], []
    for plane in data.planes:
        m = re.match(r"^/device:(?:TPU|GPU):(\d+)$", plane.name)
        if m:
            dev = int(m.group(1))
            for line in plane.lines:
                if line.name not in ("XLA Ops", "Async XLA Ops"):
                    continue
                evs = [Op(e.name.split(" = ")[0].lstrip("%"), e.start_ns,
                          e.start_ns + e.duration_ns) for e in line.events]
                if line.name == "XLA Ops":
                    ops.setdefault(dev, []).append(evs)
                else:
                    async_ops.setdefault(dev, []).extend(evs)
        elif plane.name == "/host:CPU":
            for line in plane.lines:
                thunks = []
                for e in line.events:
                    if e.name.startswith(HOST_SPAN_PREFIX):
                        host.append(Op(e.name, e.start_ns,
                                       e.start_ns + e.duration_ns))
                        continue
                    hlo_op = _stats(e).get("hlo_op") if e.duration_ns else None
                    if hlo_op:
                        thunks.append(Op(str(hlo_op), e.start_ns,
                                         e.start_ns + e.duration_ns))
                if thunks:
                    cpu_lines.append(thunks)
    if not ops and cpu_lines:
        ops = {0: cpu_lines}
    for lines in ops.values():
        for lst in lines:
            lst.sort(key=lambda o: (o.start_ns, -o.end_ns))
    host.sort(key=lambda o: o.start_ns)
    return Trace(ops, async_ops, host)


def union_ns(ops, lo: float, hi: float) -> float:
    """Length of the union of the ops' intervals, clipped to [lo, hi)."""
    total, cur_lo, cur_hi = 0.0, None, None
    for o in sorted(ops, key=lambda o: o.start_ns):
        s, e = max(o.start_ns, lo), min(o.end_ns, hi)
        if e <= s:
            continue
        if cur_hi is None or s > cur_hi:
            if cur_hi is not None:
                total += cur_hi - cur_lo
            cur_lo, cur_hi = s, e
        else:
            cur_hi = max(cur_hi, e)
    if cur_hi is not None:
        total += cur_hi - cur_lo
    return total


def self_times(ops) -> list:
    """(name, own ns) of every op of one trace line: its duration less that
    of the ops nested directly inside it. ``ops`` is sorted by
    (start, -end)."""
    out, stack = [], []   # stack of [op, child ns]
    for o in ops:
        while stack and stack[-1][0].end_ns <= o.start_ns:
            done, child = stack.pop()
            out.append((done.name, done.end_ns - done.start_ns - child))
        if stack:
            stack[-1][1] += min(o.end_ns, stack[-1][0].end_ns) - o.start_ns
        stack.append([o, 0.0])
    while stack:
        done, child = stack.pop()
        out.append((done.name, done.end_ns - done.start_ns - child))
    return out


def window(trace: Trace, span: str = JOB_SPAN):
    """(start, end) in the trace's clock of the host spans named ``span``,
    or ``None`` when there are none."""
    spans = [s for s in trace.host_spans if s.name == span]
    if not spans:
        return None
    return min(s.start_ns for s in spans), max(s.end_ns for s in spans)


def idle_gaps(ops, lo: float, hi: float, host_spans, top: int = 10):
    """The ``top`` longest intervals in [lo, hi) with no op running, each
    named by the host span inside a job (dispatch, wait) that overlaps it
    most."""
    gaps, cursor = [], lo
    for o in sorted(ops, key=lambda o: o.start_ns):
        if o.start_ns > cursor:
            gaps.append((cursor, min(o.start_ns, hi)))
        cursor = max(cursor, o.end_ns)
        if cursor >= hi:
            break
    if cursor < hi:
        gaps.append((cursor, hi))
    gaps = sorted((g for g in gaps if g[1] > g[0]),
                  key=lambda g: g[0] - g[1])[:top]
    named = []
    for s, e in gaps:
        best, name = 0.0, "between host spans"
        for h in host_spans:
            overlap = min(e, h.end_ns) - max(s, h.start_ns)
            if h.name != JOB_SPAN and overlap > best:
                best, name = overlap, h.name
        named.append((name, (e - s) / 1e9))
    return named


class Summary(NamedTuple):
    window_s: float
    busy_s: dict            # device -> seconds some op ran
    collective_s: dict      # device -> seconds some collective ran
    scatter_s: dict         # device -> own seconds of scatter ops
    top_ops: list           # [(label, seconds per device)], longest first
    gaps: list              # [(host span, seconds)] on the idlest device


def summarize(trace: Trace, hlo: dict, top: int = 10):
    """Reduce a trace to a ``Summary`` over the ``bench.job`` window, or
    ``None`` when the trace holds no job or no device op."""
    win = window(trace)
    if win is None or not trace.ops:
        return None
    lo, hi = win
    unknown = OpInfo("", "", frozenset())
    busy, coll, scat = {}, {}, {}
    per_op = collections.Counter()
    flat = {dev: [o for line in lines for o in line]
            for dev, lines in trace.ops.items()}
    for dev, lines in trace.ops.items():
        busy[dev] = union_ns(flat[dev], lo, hi) / 1e9
        both = flat[dev] + trace.async_ops.get(dev, [])
        coll[dev] = union_ns([o for o in both
                              if is_collective(hlo.get(o.name, unknown))],
                             lo, hi) / 1e9
        own = [t for line in lines for t in self_times(
            [o for o in line if o.start_ns >= lo and o.end_ns <= hi])]
        scat[dev] = sum(t for n, t in own
                        if is_scatter(hlo.get(n, unknown))) / 1e9
        for n, t in own:
            if hlo.get(n, unknown).opcode not in CONTAINERS:
                per_op[n] += t
    ndev = len(trace.ops)

    def label(name):
        path = [p for p in hlo.get(name, unknown).op_name.split("/")
                if p and p not in _GENERIC_SCOPES]
        return f"{name} {'/'.join(path[-3:])}" if path else name

    top_ops = [(label(n), t / 1e9 / ndev)
               for n, t in per_op.most_common(top)]
    idlest = min(busy, key=busy.get)
    gaps = idle_gaps(flat[idlest], lo, hi, trace.host_spans, top)
    return Summary((hi - lo) / 1e9, busy, coll, scat, top_ops, gaps)
