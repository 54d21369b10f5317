"""From a JAX profiler trace (``.xplane.pb``) to the device's busy time,
its top operations and its longest idle gaps.

- busy: the union of the intervals in which an operation ran on a
  device, from the device planes' op line (``XLA Ops`` where the trace
  has one), averaged over the devices;
- top ops: total device time per operation, by the name the trace gives
  it (the HLO instruction's name, without its text);
- idle gaps: the gaps between busy intervals inside the traced window,
  longest first, each named by the host thread and event that covered
  most of the gap, with the share it covered, where a host event other
  than the window's own annotation covers any of it.

Device planes are named ``/device:<KIND>:<n>``; host threads are the
lines of ``/host:CPU``.
"""

from __future__ import annotations

import glob
import os

OPS_LINE = "XLA Ops"
TOP = 10


def _merge(intervals: list[tuple[int, int]]) -> list[tuple[int, int]]:
    out: list[list[int]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def _device_planes(pd):
    return [p for p in pd.planes if p.name.startswith("/device:")
            and not p.name.startswith("/device:CUSTOM")]


def _op_events(plane) -> list:
    lines = list(plane.lines)
    named = [ln for ln in lines if ln.name == OPS_LINE]
    chosen = named or lines[:1]
    return [e for ln in chosen for e in ln.events if e.duration_ns > 0]


def _host_events(pd, exclude: str | None = None) -> list[tuple[int, int, str]]:
    out = []
    for p in pd.planes:
        if p.name != "/host:CPU":
            continue
        for ln in p.lines:
            for e in ln.events:
                if e.duration_ns > 0 and e.name != exclude:
                    out.append((int(e.start_ns), int(e.end_ns), f"{ln.name}: {e.name}"))
    return out


def _name_gap(gap: tuple[int, int], host: list[tuple[int, int, str]]) -> str:
    """The host event that covers most of ``gap``, with the share of the
    gap it covers (the program's own host spans are not in the trace
    yet, so most of a gap is often uncovered)."""
    best, best_cover = None, 0
    for s, e, name in host:
        cover = min(e, gap[1]) - max(s, gap[0])
        if cover > best_cover:
            best, best_cover = name, cover
    if best is None:
        return "no host event"
    return f"{best} ({100.0 * best_cover / (gap[1] - gap[0]):.1f}% of the gap)"


def op_name(event_name: str) -> str:
    """``%fusion.3 = s32[8]{0} fusion(...)`` → ``fusion.3``."""
    return event_name.split(" = ", 1)[0].lstrip("%")


def window_of(pd, name: str) -> tuple[int, int] | None:
    """The span of the host event ``name`` (the harness's annotation of
    its measured window)."""
    for p in pd.planes:
        if p.name != "/host:CPU":
            continue
        for ln in p.lines:
            for e in ln.events:
                if e.name == name:
                    return int(e.start_ns), int(e.end_ns)
    return None


def reduce(pd, window_ns: tuple[int, int] | None = None,
           window_event: str | None = None) -> dict | None:
    """``busy_s``, ``window_s``, ``device_ops`` and ``idle_gaps`` of a
    ``jax.profiler.ProfileData``, or None when it has no device plane (a
    CPU run, whose operations run on host threads). The window defaults
    to the span from the first to the last event of any plane; the host
    event ``window_event`` names no gap."""
    planes = _device_planes(pd)
    if not planes:
        return None
    per_plane = [_op_events(p) for p in planes]
    if window_ns is None:
        starts, ends = [], []
        for p in pd.planes:
            for ln in p.lines:
                for e in ln.events:
                    starts.append(int(e.start_ns))
                    ends.append(int(e.end_ns))
        window_ns = (min(starts), max(ends))
    lo, hi = window_ns
    busy_total = 0
    ops: dict[str, float] = {}
    merged0 = None
    for events in per_plane:
        clipped = [
            (max(lo, int(e.start_ns)), min(hi, int(e.end_ns)))
            for e in events
        ]
        merged = _merge([(s, e) for s, e in clipped if e > s])
        busy_total += sum(e - s for s, e in merged)
        if merged0 is None:
            merged0 = merged
        for e in events:
            name = op_name(e.name)
            ops[name] = ops.get(name, 0.0) + e.duration_ns / 1e9
    gaps = []
    prev = lo
    for s, e in merged0 or []:
        if s > prev:
            gaps.append((prev, s))
        prev = max(prev, e)
    if hi > prev:
        gaps.append((prev, hi))
    gaps.sort(key=lambda g: g[1] - g[0], reverse=True)
    host = _host_events(pd, window_event)
    return {
        "busy_s": busy_total / len(per_plane) / 1e9,
        "window_s": (hi - lo) / 1e9,
        "device_ops": [
            [k, v] for k, v in
            sorted(ops.items(), key=lambda kv: kv[1], reverse=True)[:TOP]
        ],
        "idle_gaps": [
            [_name_gap(g, host), (g[1] - g[0]) / 1e9] for g in gaps[:TOP]
        ],
    }


def describe(pd) -> str:
    """Each plane's lines with their event counts and time span: what a
    reader checks before trusting the reduction."""
    out = []
    for p in pd.planes:
        lines = []
        for ln in p.lines:
            evs = list(ln.events)
            if evs:
                lo = min(int(e.start_ns) for e in evs)
                hi = max(int(e.end_ns) for e in evs)
                lines.append(f"{ln.name}={len(evs)}@[{lo},{hi}]")
        if lines:
            out.append(f"{p.name}: " + " ".join(lines[:8]))
    return "; ".join(out)


def find_xplane(log_dir: str) -> str:
    paths = glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"), recursive=True)
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    return max(paths, key=os.path.getmtime)


def reduce_dir(log_dir: str, window_event: str | None = None
               ) -> tuple[dict | None, str]:
    """The reduction of the newest trace under ``log_dir``, over the span
    of the host event ``window_event`` where the trace has it, and the
    trace's description."""
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(find_xplane(log_dir))
    window = window_of(pd, window_event) if window_event else None
    return reduce(pd, window, window_event), describe(pd)
