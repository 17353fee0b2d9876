"""From a profiler trace to device busy/idle time, launches, top operations
and idle gaps charged to the benchmark's spans.

Two steps, so that the arithmetic can be checked on a small recorded trace
(`testdata/trace_sample.json`) without a chip:

  load_xplane(path)  reads `*.xplane.pb` with `jax.profiler.ProfileData`
                     into plain lists: {"planes": [{"name", "lines":
                     [{"name", "events": [[name, start_ns, dur_ns], ...]}]}]}
                     Of device planes the lines below are kept; of host
                     planes the benchmark's own annotations (`bench:*`) and
                     what the Python threads' lines show (jax's own host
                     events: `PjitFunction(<name>)`, `DevicePut`, ...).
  reduce(trace)      the numbers.

What counts as what (TPU planes as jax 0.9 / libtpu 0.0.34 writes them):
  device plane   name `/device:TPU:<n>`
  launches       events of that plane's line `XLA Modules`: one per execution
                 of a compiled program, whatever the engine calls it
  busy           union of the intervals of the line `XLA Ops` (operations as
                 XLA named them); where a plane has no such line, of
                 `XLA Modules`
  window         the annotation `bench:traced_window` on a host plane
  idle gaps      the window minus busy. Each second of it is charged to the
                 benchmark span open then (`post`, `poll`, ...; these are
                 disjoint and add up to the idle time), and, beside that, to
                 every event name some Python thread was inside then
                 (`host:<name>`, union over threads; these overlap the spans
                 and each other, so they do not add up)
Everything is clipped to the window; seconds are averaged over the device
planes that ran anything.
"""

from __future__ import annotations

import re
from typing import Dict, List, Optional, Tuple

DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
WINDOW_SPAN = "bench:traced_window"
SPAN_PREFIX = "bench:"
PYTHON_LINE = "python"
HOST_PREFIX = "host:"


def load_xplane(path: str) -> dict:
    import jax.profiler

    data = jax.profiler.ProfileData.from_file(path)
    planes = []
    for plane in data.planes:
        device = bool(DEVICE_PLANE.match(plane.name))
        lines = []
        for line in plane.lines:
            if device:
                if line.name not in (OPS_LINE, MODULES_LINE):
                    continue
                events = [[e.name, int(e.start_ns), int(e.duration_ns)]
                          for e in line.events]
            elif line.name.startswith(PYTHON_LINE):
                events = [[e.name[:80], int(e.start_ns), int(e.duration_ns)]
                          for e in line.events]
            else:
                events = [[e.name, int(e.start_ns), int(e.duration_ns)]
                          for e in line.events
                          if e.name.startswith(SPAN_PREFIX)]
            if events:
                lines.append({"name": line.name, "events": events})
        if lines:
            planes.append({"name": plane.name, "lines": lines})
    return {"planes": planes}


_OPCODE = re.compile(r"\s([a-z][\w\-]*)\(")
_TARGET = re.compile(r'custom_call_target="([^"]+)"')


def short_name(hlo: str) -> str:
    """`%while.33 while`, `%custom-call.2 custom-call X64Combine`: the name
    XLA printed, without the shapes that make it a page long."""
    lhs, sep, rest = hlo.partition(" = ")
    if not sep:
        return hlo[:120]
    op = _OPCODE.search(" " + rest)
    target = _TARGET.search(rest)
    return " ".join(x for x in (lhs, op and op.group(1),
                                target and target.group(1)) if x)[:120]


def _clip(events, lo, hi) -> List[Tuple[int, int]]:
    out = []
    for _, start, dur in events:
        a, b = max(start, lo), min(start + dur, hi)
        if b > a:
            out.append((a, b))
    return out


def union(intervals: List[Tuple[int, int]]) -> List[Tuple[int, int]]:
    """Sorted, disjoint cover of `intervals`."""
    out: List[Tuple[int, int]] = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            if b > out[-1][1]:
                out[-1] = (out[-1][0], b)
        else:
            out.append((a, b))
    return out


def _gaps(busy, lo, hi):
    at = lo
    for a, b in busy:
        if a > at:
            yield at, a
        at = max(at, b)
    if hi > at:
        yield at, hi


def _charge(gaps, spans) -> Dict[str, float]:
    """Seconds of `gaps` by the name of the span open then; what no span
    covers goes to `outside_spans`."""
    spans = sorted(spans, key=lambda s: s[1])
    out: Dict[str, float] = {}
    i = 0
    for a, b in gaps:
        covered = 0
        while i < len(spans) and spans[i][1] + spans[i][2] <= a:
            i += 1
        j = i
        while j < len(spans) and spans[j][1] < b:
            name, s, d = spans[j]
            lo, hi = max(a, s), min(b, s + d)
            if hi > lo:
                out[name] = out.get(name, 0.0) + (hi - lo) / 1e9
                covered += hi - lo
            j += 1
        if b - a > covered:
            out["outside_spans"] = out.get("outside_spans", 0.0) \
                + (b - a - covered) / 1e9
    return out


def _overlap(a, b) -> float:
    """Seconds covered by both of two sorted disjoint interval lists."""
    i = j = 0
    total = 0
    while i < len(a) and j < len(b):
        lo, hi = max(a[i][0], b[j][0]), min(a[i][1], b[j][1])
        if hi > lo:
            total += hi - lo
        if a[i][1] < b[j][1]:
            i += 1
        else:
            j += 1
    return total / 1e9


def reduce(trace: dict, top: int = 10) -> Optional[dict]:
    """None where the trace has no window annotation or no device plane that
    ran anything inside it (a CPU rehearsal): nothing to read."""
    window, spans, host = None, [], {}
    devices = []
    for plane in trace["planes"]:
        if DEVICE_PLANE.match(plane["name"]):
            devices.append(plane)
            continue
        for line in plane["lines"]:
            for ev in line["events"]:
                if ev[0] == WINDOW_SPAN:
                    window = (ev[1], ev[1] + ev[2])
                elif ev[0].startswith(SPAN_PREFIX):
                    spans.append([ev[0][len(SPAN_PREFIX):], ev[1], ev[2]])
                elif ev[2] > 0:
                    host.setdefault(ev[0], []).append((ev[1], ev[1] + ev[2]))
    if window is None:
        return None
    lo, hi = window
    busy_s, launches, ops, gap_by_span, used = 0.0, 0, {}, {}, 0
    longest_gap = 0.0
    for plane in devices:
        lines = {ln["name"]: ln["events"] for ln in plane["lines"]}
        op_events = lines.get(OPS_LINE) or lines.get(MODULES_LINE) or []
        busy = union(_clip(op_events, lo, hi))
        if not busy:
            continue
        used += 1
        busy_s += sum(b - a for a, b in busy) / 1e9
        launches += sum(1 for _, s, d in lines.get(MODULES_LINE, [])
                        if s >= lo and s < hi)
        for name, s, d in op_events:
            a, b = max(s, lo), min(s + d, hi)
            if b > a:
                ops[name] = ops.get(name, 0.0) + (b - a) / 1e9
        gaps = list(_gaps(busy, lo, hi))
        longest_gap = max([longest_gap] + [(b - a) / 1e9 for a, b in gaps])
        for name, sec in _charge(gaps, spans).items():
            gap_by_span[name] = gap_by_span.get(name, 0.0) + sec
        for name, intervals in host.items():
            sec = _overlap(gaps, union(intervals))
            if sec:
                key = HOST_PREFIX + name
                gap_by_span[key] = gap_by_span.get(key, 0.0) + sec
    if not used:
        return None
    def rank(d, name=str):
        return [[name(k), v / used] for k, v in
                sorted(d.items(), key=lambda kv: -kv[1])[:top]]

    return {"window_s": (hi - lo) / 1e9, "busy_s": busy_s / used,
            "launches": launches / used, "devices": used,
            "longest_gap_s": longest_gap,
            "device_ops": rank(ops, short_name), "idle_gaps": rank(gap_by_span)}
