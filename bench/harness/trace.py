"""Reduce a profiler trace to device time by layer.

The trace's device planes (``/device:TPU:<n>``) hold an ``XLA Modules``
line (one event per program run, with its ``run_id``) and an ``XLA Ops``
line (one event per HLO instruction run; a ``while`` event encloses its
body's events).  The events carry no scope, so each op is attributed by its
instruction name to the ``op_name`` metadata of the compiled program that
ran it (``HloModule`` text from ``Compiled.as_text()``): an op under the
``local_step`` or ``round_set`` named scope belongs to that layer.  Times
are exclusive: an enclosing op is charged only what its children leave.

Host spans (``bench.select``, ``bench.dispatch``, ``bench.sync`` inside
``bench.window``) are the benchmark's own ``TraceAnnotation``s.  The device
clock is put on the host's by the least shift for which no program starts
before the host enqueued it (``DoEnqueueProgram`` of the same ``run_id``).
"""
from __future__ import annotations

import bisect
import collections
import dataclasses
import glob
import os
import re

from harness.driver import LOCAL_STEP_SCOPE, ROUND_SET_SCOPE

HOST_ACTIVITIES = ("bench.select", "bench.dispatch", "bench.sync")
SCOPES = (LOCAL_STEP_SCOPE, ROUND_SET_SCOPE)
_INSTR = re.compile(r"^\s*(?:ROOT\s+)?%([\w.\-]+) = ")
_OP_NAME = re.compile(r'metadata=\{[^}]*?op_name="([^"]*)"')
_CALLED = re.compile(r"(?:calls|to_apply|body|condition|branch_computations)=\{?%([\w.\-]+)")
_REF = re.compile(r"%([\w.\-]+)")
_COMPUTATION = re.compile(r"^(?:ENTRY\s+)?%([\w.\-]+)\s.*\{\s*$")
_NAME = re.compile(r"^%?([\w.\-]+)")
_SUFFIX = re.compile(r"\.\d+$")


def _scope_of(op_name: str) -> str | None:
    parts = op_name.split("/")
    return next((s for s in SCOPES if s in parts), None)


def hlo_scopes(hlo_text: str) -> tuple[str, dict[str, str]]:
    """``(module name, instruction -> layer)`` from a compiled HLO module's
    text.  An instruction's layer is the named scope in its ``op_name``, or
    else the layer most of the instructions of the computations it calls
    have.  The compiler's own instructions (copies, the slab's packing)
    carry no ``op_name``: each takes the layer of the instructions that use
    its result, or failing that of those that make its operands, or failing
    that, inside a loop body or a called computation, the body's layer.
    What is left maps to ``"other"``."""
    module = re.search(r"HloModule\s+([\w.\-]+)", hlo_text).group(1)
    own, calls, operands, members = {}, {}, {}, collections.defaultdict(list)
    comp = entry = None
    for line in hlo_text.splitlines():
        head = _COMPUTATION.match(line)
        if head:
            comp = head.group(1)
            entry = comp if line.startswith("ENTRY") else entry
            continue
        m = _INSTR.match(line)
        if not m:
            continue
        name, rest = m.group(1), line[m.end():]
        members[comp].append(name)
        meta = _OP_NAME.search(rest)
        own[name] = _scope_of(meta.group(1)) if meta else None
        calls[name] = _CALLED.findall(rest)
        operands[name] = [r for r in _REF.findall(rest.split(", metadata=")[0]) if r not in calls[name]]

    memo: dict[str, str | None] = {}

    def comp_scope(c, seen=()):
        if c not in memo:
            votes = collections.Counter(
                s for n in members.get(c, ()) if (s := scope(n, seen + (c,)))
            )
            memo[c] = votes.most_common(1)[0][0] if votes else None
        return memo[c]

    def scope(n, seen=()):
        if own.get(n):
            return own[n]
        for c in calls.get(n, ()):
            if c not in seen and (s := comp_scope(c, seen)):
                return s
        return None

    layer = {n: scope(n) for n in own}
    users = collections.defaultdict(list)
    for n, ops in operands.items():
        for o in ops:
            users[o].append(n)
    changed = True
    while changed:
        changed = False
        for n in [n for n, s in layer.items() if s is None]:
            for near in (users[n], operands[n]):
                votes = collections.Counter(layer[x] for x in near if layer.get(x))
                if votes:
                    layer[n] = votes.most_common(1)[0][0]
                    changed = True
                    break
    for c, names in members.items():  # the rest of a loop body goes with the body
        votes = collections.Counter(layer[n] for n in names if layer[n])
        if c != entry and votes:
            for n in names:
                layer[n] = layer[n] or votes.most_common(1)[0][0]
    return module, {n: s or "other" for n, s in layer.items()}


@dataclasses.dataclass
class Event:
    start: float  # ns, on the device's clock for device events
    end: float
    name: str
    stats: dict


def _events(line, stats=lambda name: False) -> list[Event]:
    """The line's events; the stats only of those ``stats(name)`` asks for
    (reading them is most of the cost)."""
    out = []
    for e in line.events:
        start, name = float(e.start_ns), e.name
        out.append(Event(start, start + float(e.duration_ns), name, dict(e.stats) if stats(name) else {}))
    return out


def load(trace_dir: str) -> dict:
    """``{"devices": {plane: {"modules": [...], "ops": [...]}}, "host": [...]}``
    from the one ``.xplane.pb`` under ``trace_dir``."""
    from jax.profiler import ProfileData

    paths = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"), recursive=True)
    if len(paths) != 1:
        raise FileNotFoundError(f"expected one .xplane.pb under {trace_dir}, found {paths}")
    data = ProfileData.from_file(paths[0])
    devices, host = {}, []
    for plane in data.planes:
        lines = {line.name: line for line in plane.lines}
        if plane.name.startswith("/device:TPU:") and "XLA Ops" in lines:
            devices[plane.name] = {
                "modules": _events(lines["XLA Modules"], lambda _: True) if "XLA Modules" in lines else [],
                "ops": _events(lines["XLA Ops"]),
            }
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                host.extend(_events(line, lambda name: name == "DoEnqueueProgram"))
    return {"devices": devices, "host": host}


def union(intervals) -> list[tuple[float, float]]:
    out = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            out[-1] = (out[-1][0], max(out[-1][1], b))
        else:
            out.append((a, b))
    return out


def clipped_length(intervals, lo: float, hi: float) -> float:
    return sum(max(0.0, min(b, hi) - max(a, lo)) for a, b in intervals)


def exclusive(ops: list[Event]) -> list[float]:
    """Each op's own time: its duration less its children's."""
    order = sorted(range(len(ops)), key=lambda i: (ops[i].start, -ops[i].end))
    own = [op.end - op.start for op in ops]
    stack: list[int] = []
    for i in order:
        while stack and ops[stack[-1]].end <= ops[i].start:
            stack.pop()
        if stack:
            own[stack[-1]] -= ops[i].end - ops[i].start
        stack.append(i)
    return own


def clock_shift(modules: list[Event], host: list[Event]) -> float:
    """Host time minus device time: the least shift that puts no program's
    start before the host enqueued it."""
    enqueued = {}
    for e in host:
        if e.name == "DoEnqueueProgram" and "run_id" in e.stats:
            enqueued[int(e.stats["run_id"])] = e.start
    shifts = [
        enqueued[int(m.stats["run_id"])] - m.start
        for m in modules
        if "run_id" in m.stats and int(m.stats["run_id"]) in enqueued
    ]
    return max(shifts) if shifts else 0.0


@dataclasses.dataclass
class Reading:
    """What the per-layer metric readers read: seconds of device time per
    layer, averaged over the chips, over ``n_calls`` calls in a window of
    ``window_s``."""

    window_s: float
    busy_s: float
    n_calls: int
    scope_s: dict  # layer -> device seconds
    op_s: dict  # "<module>:<layer>/<instruction>" -> device seconds
    gap_s: dict  # what the host was doing -> idle device seconds
    counts: dict = dataclasses.field(default_factory=dict)
    device_kind: str = ""

    def per_call_ms(self, scope: str) -> float | None:
        s = self.scope_s.get(scope, 0.0)
        return 1e3 * s / self.n_calls if s > 0 and self.n_calls else None

    def breakdown(self) -> dict:
        top = lambda d: [[k, v] for k, v in sorted(d.items(), key=lambda kv: -kv[1])[:10]]
        return {"device_ops": top(self.op_s), "idle_gaps": top(self.gap_s)}


def reduce(trace: dict, hlo_texts: list[str], n_calls: int, window_s: float) -> Reading:
    """Busy and idle time inside the ``bench.window`` span, device time by
    layer and by op, and idle time by what the host was doing."""
    modules = dict(hlo_scopes(t) for t in hlo_texts)
    host = sorted(trace["host"], key=lambda e: e.start)
    windows = [e for e in host if e.name == "bench.window"]
    activities = [e for e in host if e.name in HOST_ACTIVITIES]
    starts = [e.start for e in activities]
    busy, scope_s, op_s, gap_s = 0.0, collections.Counter(), collections.Counter(), collections.Counter()
    n_dev = max(len(trace["devices"]), 1)
    for dev in trace["devices"].values():
        ops, mods = dev["ops"], sorted(dev["modules"], key=lambda e: e.start)
        shift = clock_shift(mods, host)
        if windows:
            lo, hi = windows[0].start - shift, windows[0].end - shift
        else:
            lo, hi = min(o.start for o in ops), max(o.end for o in ops)
        spans = union((o.start, o.end) for o in ops)
        busy += clipped_length(spans, lo, hi)
        mod_starts = [m.start for m in mods]
        for op, own in zip(ops, exclusive(ops)):
            if not (lo <= op.start < hi):
                continue
            j = bisect.bisect_right(mod_starts, op.start) - 1
            module = mods[j].name.split("(")[0] if j >= 0 and op.start < mods[j].end else "?"
            instr = _NAME.match(op.name).group(1)
            scope = modules.get(module, {}).get(instr, "other")
            if module in modules:
                scope_s[scope] += own * 1e-9
            op_s[f"{module}:{scope}/{_SUFFIX.sub('', instr)}"] += own * 1e-9
        gaps, prev = [], lo
        for a, b in spans:
            if b <= lo or a >= hi:
                continue
            if a > prev:
                gaps.append((prev, a))
            prev = max(prev, b)
        if prev < hi:
            gaps.append((prev, hi))
        for a, b in gaps:
            covered = 0.0
            i = max(bisect.bisect_right(starts, a + shift) - 1, 0)
            while i < len(activities) and activities[i].start < b + shift:
                e = activities[i]
                o = max(0.0, min(e.end, b + shift) - max(e.start, a + shift))
                gap_s[e.name] += o * 1e-9
                covered += o
                i += 1
            gap_s["host loop"] += (b - a - covered) * 1e-9
    return Reading(
        window_s=window_s,
        busy_s=busy * 1e-9 / n_dev,
        n_calls=n_calls,
        scope_s={k: v / n_dev for k, v in scope_s.items()},
        op_s={k: v / n_dev for k, v in op_s.items()},
        gap_s={k: v / n_dev for k, v in gap_s.items() if v > 0},
    )


def read(trace_dir: str, hlo_texts: list[str], n_calls: int, window_s: float) -> Reading:
    return reduce(load(trace_dir), hlo_texts, n_calls, window_s)
