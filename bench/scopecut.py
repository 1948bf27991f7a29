"""Device self time of a profiler trace, split by the program's named
scopes (``repro.obs.trace.SCOPE_*``).

A device operation's *self* time is the part of its interval in which
no operation nested in it on the same ``XLA Ops`` line runs: a ``while``
counts only the gaps between its body's operations. Each instant of the
window's busy time goes to exactly one operation, the innermost one
running, so the self times add up to ``tracecut.reduce``'s ``busy_s``.

The trace's operation events carry no ``op_name``: the map from HLO
instruction name to ``op_name`` is parsed from the compiled step's
optimised HLO (``compiled.as_text()``), and each ``op_name`` falls in
one class of ``CLASSES``. An operation the map does not hold is
``unscoped``.
"""
from __future__ import annotations

import collections
import re
from typing import Dict, Iterable, List, Optional, Tuple

import tracecut

# the program's scope vocabulary (repro.obs.trace.SCOPE_*); the tests
# hold the two equal
CLASS_OF_SCOPE = {
    "model": "fwd",                 # "bwd" under transpose(
    "model.accumulate": "bwd",
    "demo.encode": "encode",
    "demo.topk": "topk",
    "demo.decode": "decode",
    "demo.apply": "apply",
}
CLASSES = ("fwd", "bwd", "encode", "topk", "decode", "apply", "unscoped")
GAP_PREFIXES = (tracecut.SPAN_PREFIX, "gauntlet.")

# "%name = shape opcode(%operand, ...), calls=%comp, metadata={op_name=..."
_INSTR = re.compile(r'^\s*(?:ROOT\s+)?%([\w.\-]+) = (.*)$')
_COMPUTATION = re.compile(r'^(?:ENTRY\s+)?%([\w.\-]+) \(.*\{\s*$')
_OP_NAME = re.compile(r'\bop_name="([^"]*)"')
_REF = re.compile(r'%([\w.\-]+)')
_ROOTS = ("jit(", "pjit(")
# "transpose(jvp(model))" -> "model"
_UNWRAP = re.compile(r"^(?:[\w\-]+\()*([^()]*)\)*$")


def op_names(hlo_text: str) -> Dict[str, str]:
    """HLO instruction name -> ``op_name`` (the whole name stack,
    ``jit(<fn>)/...``), for every instruction of a module's text that
    has one. An instruction that a compiler pass made without metadata
    (a scatter's fusion, the sort of its indices, a broadcast of a
    constant) takes the ``op_name`` of the nearest instruction that
    consumes its result and has one; failing that, of the nearest one
    among the computations it calls and its operands."""
    comps: Dict[str, List[str]] = {}
    own: Dict[str, Optional[str]] = {}
    refs: Dict[str, List[str]] = {}
    body: List[str] = []
    for line in hlo_text.splitlines():
        m = _COMPUTATION.match(line)
        if m:
            body = comps.setdefault(m.group(1), [])
            continue
        m = _INSTR.match(line)
        if m:
            name, rest = m.groups()
            body.append(name)
            found = _OP_NAME.search(rest)
            # a reducer's or comparator's own instructions carry names
            # relative to their caller ("scatter-add"): not a stage's
            own[name] = (found.group(1) if found and found.group(1)
                         .startswith(_ROOTS) else None)
            refs[name] = _REF.findall(rest.split(", metadata=", 1)[0])
    users: Dict[str, List[str]] = collections.defaultdict(list)
    for name, rs in refs.items():
        for r in rs:
            users[r].append(name)

    def inside(n: str) -> List[str]:
        called = [i for r in refs[n] if r in comps for i in comps[r]]
        return called + [r for r in refs[n] if r not in comps]

    def nearest(name: str, step) -> Optional[str]:
        queue, seen = collections.deque([name]), {name}
        while queue:
            n = queue.popleft()
            if own[n]:
                return own[n]
            for r in step(n):
                if r in own and r not in seen:
                    seen.add(r)
                    queue.append(r)
        return None

    out = {}
    for name in own:
        found = nearest(name, users.__getitem__) or nearest(name, inside)
        if found:
            out[name] = found
    return out


def scope_class(op_name: Optional[str]) -> str:
    """The class of one instruction: the innermost scope of the
    vocabulary on its name stack. The model's operations under
    ``transpose(`` (autodiff's backward, remat's recomputation
    included) are ``bwd``; no scope, or no ``op_name``, is
    ``unscoped``."""
    if not op_name:
        return "unscoped"
    # a fused location joins several names with ";": the first is whole
    parts = op_name.split(";", 1)[0].split("/")
    found, transposed = "unscoped", False
    for part in parts:
        if part.startswith(_ROOTS):
            continue
        transposed = transposed or "transpose(" in part
        m = _UNWRAP.match(part)
        scope = m.group(1) if m else None
        if scope in CLASS_OF_SCOPE:
            found = CLASS_OF_SCOPE[scope]
            if found == "fwd" and transposed:
                found = "bwd"
    return found


def _window(planes) -> Tuple[float, float]:
    windows = [(s, e) for n, s, e in tracecut.host_spans(planes)
               if n == tracecut.WINDOW_SPAN]
    if not windows:
        raise ValueError(f"trace holds no {tracecut.WINDOW_SPAN!r} span")
    return windows[0]


def self_ns(ops: Iterable[Tuple[str, float, float]], lo: float,
            hi: float) -> Dict[str, float]:
    """Self time per instruction name (ns) of one device's operations,
    clipped to [lo, hi]. Where two operations overlap without nesting,
    the shared time goes to the one that started later."""
    evs = sorted(((max(s, lo), min(e, hi), tracecut.op_name(n))
                  for n, s, e in ops if min(e, hi) > max(s, lo)),
                 key=lambda ev: (ev[0], -ev[1]))
    out: Dict[str, float] = collections.defaultdict(float)
    stack: List[Tuple[float, float, str]] = []
    t = lo

    def advance(to: float) -> None:
        nonlocal t
        while stack:
            _, end, name = stack[-1]
            if end <= to:
                if end > t:
                    out[name] += end - t
                    t = end
                stack.pop()
            else:
                if to > t:
                    out[name] += to - t
                t = max(t, to)
                return
        t = max(t, to)

    for ev in evs:
        advance(ev[0])
        stack.append(ev)
    advance(hi)
    return dict(out)


def self_times(planes) -> Dict[str, float]:
    """Self time per instruction name in the ``bench.window`` span,
    seconds, averaged over the devices that ran any operation."""
    planes = list(planes)
    lo, hi = _window(planes)
    per_device = tracecut.device_ops(planes)
    if not per_device:
        raise ValueError("trace holds no device operation")
    total: Dict[str, float] = collections.defaultdict(float)
    for ops in per_device.values():
        for name, ns in self_ns(ops, lo, hi).items():
            total[name] += ns
    n = len(per_device)
    return {name: ns / n / 1e9 for name, ns in total.items()}


def by_class(selfs: Dict[str, float],
             names: Dict[str, str]) -> Dict[str, float]:
    """Self time summed per class of ``CLASSES`` (every class present),
    through ``names`` (``op_names`` of the compiled program)."""
    out = dict.fromkeys(CLASSES, 0.0)
    for instr, secs in selfs.items():
        out[scope_class(names.get(instr))] += secs
    return out


def named_gaps(planes, top: int = 10,
               prefixes: Tuple[str, ...] = GAP_PREFIXES) -> list:
    """The window's ``top`` longest idle gaps as ``tracecut.reduce``
    gives them, each named by the innermost host span of any of
    ``prefixes`` open at its middle (without the ``bench.`` prefix), or
    "none"."""
    planes = list(planes)
    lo, hi = _window(planes)
    spans = [(ev.name, ev.start_ns, ev.start_ns + ev.duration_ns)
             for plane in planes if plane.name.startswith("/host:")
             for line in plane.lines for ev in line.events
             if ev.name.startswith(prefixes)]
    all_gaps = []
    for ops in tracecut.device_ops(planes).values():
        busy = tracecut.merge([(s, e) for _, s, e in ops], lo, hi)
        all_gaps.extend(tracecut.gaps(busy, lo, hi))
    out = []
    for s, e in sorted(all_gaps, key=lambda g: g[0] - g[1])[:top]:
        mid, best = (s + e) / 2, None
        for name, a, b in spans:
            if a <= mid < b and (best is None or (a, -b) > best[:2]):
                best = (a, -b, name)
        name = best[2] if best else "none"
        if name.startswith(tracecut.SPAN_PREFIX):
            name = name[len(tracecut.SPAN_PREFIX):]
        out.append([name, (e - s) / 1e9])
    return out
