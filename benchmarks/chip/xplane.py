"""Reduce a JAX profiler trace (``.xplane.pb``) to the numbers the
benchmark reports: how long each chip was busy in the measured window,
the device time of each named program, the operations that took most
device time, and the longest idle gaps named by the harness's host spans.

The window is the host span the harness opens at the window's start and
closes at its end (``WINDOW_SPAN``); device events are clipped to it.
Busy time is the union of the intervals in which any operation ran on a
chip, so overlapping operations count once. Operations are named
``<program>/<op>`` (the jitted program whose run holds them, and the HLO
instruction). An idle gap is named by the harness span it falls in (the
runner's phase, the train step, a reward call) and the shortest host
event of the runtime that covers at least half of it, as
``<span> | <event>``.

    python -m benchmarks.chip.xplane <trace.xplane.pb> <out.xplane.pb> \
        <seconds>     # keep the window's first seconds, as a small trace
"""
from __future__ import annotations

import bisect
import re
import sys
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

WINDOW_SPAN = "bench.window"
SPAN_PREFIX = "bench."
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
_DEVICE = re.compile(r"^/device:TPU:(\d+)$")

Interval = Tuple[int, int]


def load(path: str):
    from jax.profiler import ProfileData
    return ProfileData.from_file(path)


def program_name(module: str) -> str:
    """``jit__decode_block(123)`` or ``jit__decode_block.4`` ->
    ``jit__decode_block``: the jitted function's name as the trace shows
    it, without the run's numbering."""
    return re.split(r"[(.]", module, maxsplit=1)[0]


def union(intervals: Iterable[Interval]) -> List[Interval]:
    out: List[List[int]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def _clip(s: int, e: int, lo: int, hi: int) -> Optional[Interval]:
    s, e = max(s, lo), min(e, hi)
    return (s, e) if e > s else None


def _events(line) -> List[Tuple[str, int, int]]:
    return [(ev.name, int(ev.start_ns), int(ev.start_ns + ev.duration_ns))
            for ev in line.events]


def op_name(hlo: str) -> str:
    """``%fusion.3 = bf16[...] fusion(...)`` -> ``fusion.3``."""
    return hlo.split(" = ", 1)[0].lstrip("%")


def host_events(pd) -> List[Tuple[str, int, int]]:
    """Every event on every host thread: the harness's spans
    (``bench.*``) and the runtime's own."""
    out = []
    for plane in pd.planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                out += _events(line)
    return out


def device_lines(pd) -> Dict[int, Dict[str, List[Tuple[str, int, int]]]]:
    """``{chip id: {line name: events}}`` for every TPU plane."""
    out = {}
    for plane in pd.planes:
        m = _DEVICE.match(plane.name)
        if m:
            out[int(m.group(1))] = {ln.name: _events(ln)
                                    for ln in plane.lines}
    return out


def _most(gap: Interval, events, keep) -> Optional[str]:
    best, name = 0, None
    for n, s, e in events:
        if keep(n):
            c = _clip(s, e, *gap)
            if c and c[1] - c[0] > best:
                best, name = c[1] - c[0], n
    return name


def _innermost(gap: Interval, events) -> Optional[str]:
    """The shortest runtime event covering at least half of the gap (the
    deepest thing the host was doing through it)."""
    best, name = None, None
    for n, s, e in events:
        if n.startswith(SPAN_PREFIX):
            continue
        c = _clip(s, e, *gap)
        if c and 2 * (c[1] - c[0]) >= gap[1] - gap[0] and (
                best is None or e - s < best):
            best, name = e - s, n
    return name


def _name_gap(gap: Interval, host: Sequence[Tuple[str, int, int]]) -> str:
    span = _most(gap, host, lambda n: n.startswith(SPAN_PREFIX)
                 and n != WINDOW_SPAN) or "no harness span"
    return f"{span} | {_innermost(gap, host) or 'no host event'}"


def _program_at(modules, t: int) -> str:
    """The program whose run on the chip holds time ``t``."""
    i = bisect.bisect_right(modules, (t, float("inf"), "")) - 1
    if i >= 0 and modules[i][0] <= t < modules[i][1]:
        return program_name(modules[i][2])
    return "?"


def reduce(pd, top: int = 10) -> Dict:
    """The window's numbers. Raises ValueError if the trace holds no
    window span or no TPU plane."""
    host = host_events(pd)
    win = [(s, e) for n, s, e in host if n == WINDOW_SPAN]
    if not win:
        raise ValueError(f"trace holds no {WINDOW_SPAN!r} span")
    lo, hi = win[0]
    chips = device_lines(pd)
    if not chips:
        raise ValueError("trace holds no TPU device plane")
    busy, gaps = {}, []
    ops: Dict[str, float] = {}
    programs: Dict[str, Dict[str, float]] = {}
    for chip, lines in sorted(chips.items()):
        modules = sorted((s, e, n) for n, s, e in lines.get(MODULES_LINE, []))
        clipped = []
        for name, s, e in lines.get(OPS_LINE, []):
            c = _clip(s, e, lo, hi)
            if c:
                clipped.append(c)
                key = f"{_program_at(modules, s)}/{op_name(name)}"
                ops[key] = ops.get(key, 0.0) + (c[1] - c[0]) * 1e-9
        merged = union(clipped)
        busy[chip] = sum(e - s for s, e in merged) * 1e-9
        edges = [lo] + [x for iv in merged for x in iv] + [hi]
        gaps += [(edges[i], edges[i + 1])
                 for i in range(0, len(edges), 2)
                 if edges[i + 1] > edges[i]]
        for name, s, e in lines.get(MODULES_LINE, []):
            c = _clip(s, e, lo, hi)
            if c:
                p = programs.setdefault(program_name(name),
                                        {"seconds": 0.0, "calls": 0})
                p["seconds"] += (c[1] - c[0]) * 1e-9
                p["calls"] += 1
    gaps.sort(key=lambda g: g[1] - g[0], reverse=True)
    return {
        "window_s": (hi - lo) * 1e-9,
        "chips": len(chips),
        "busy_s": sum(busy.values()) / len(busy),
        "busy_per_chip": {str(c): b for c, b in busy.items()},
        "programs": programs,
        "device_ops": sorted(([n, s] for n, s in ops.items()),
                             key=lambda x: x[1], reverse=True)[:top],
        "idle_gaps": [[_name_gap(g, host), (g[1] - g[0]) * 1e-9]
                      for g in gaps[:top]],
    }


def _quote(text: str) -> str:
    return '"' + (text.replace("\\", "\\\\").replace('"', '\\"')
                  .replace("\n", "\\n")) + '"'


def trim(pd, seconds: float) -> bytes:
    """The first ``seconds`` of the trace's window as a serialized XSpace:
    the TPU planes' op and module lines and every host line, cut to that
    span, with HLO text shortened to the instruction's name and the
    window span shortened to match. Small enough to keep as test data."""
    lo = next(s for n, s, e in host_events(pd) if n == WINDOW_SPAN)
    hi = lo + int(seconds * 1e9)
    planes = []
    for pid, plane in enumerate(pd.planes):
        device = _DEVICE.match(plane.name)
        if not (device or plane.name.startswith("/host:")):
            continue
        names: Dict[str, int] = {}
        lines = []
        for lid, line in enumerate(plane.lines):
            if device and line.name not in (OPS_LINE, MODULES_LINE):
                continue
            evs = []
            for n, s, e in _events(line):
                if n == WINDOW_SPAN:
                    s, e = lo, hi
                elif not (s < hi and e > lo):
                    continue
                if device and line.name == OPS_LINE:
                    n = f"%{op_name(n)} = ..."
                mid = names.setdefault(n, len(names) + 1)
                evs.append(f"events {{ metadata_id: {mid} offset_ps: "
                           f"{(s - lo) * 1000} duration_ps: {(e - s) * 1000} }}")
            if evs:
                lines.append(f"lines {{ id: {lid} name: {_quote(line.name)} "
                             f"timestamp_ns: {lo} {' '.join(evs)} }}")
        if lines:
            meta = " ".join(
                f"event_metadata {{ key: {i} value {{ id: {i} "
                f"name: {_quote(n)} }} }}" for n, i in names.items())
            planes.append(f"planes {{ id: {pid} name: {_quote(plane.name)} "
                          f"{' '.join(lines)} {meta} }}")
    from jax.profiler import ProfileData
    return ProfileData.text_proto_to_serialized_xspace("\n".join(planes))


if __name__ == "__main__":
    with open(sys.argv[2], "wb") as f:
        f.write(trim(load(sys.argv[1]), float(sys.argv[3])))
