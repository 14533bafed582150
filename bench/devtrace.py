"""Reduction of a JAX profiler trace to the numbers the metrics read.

`read_trace` takes the ``.xplane.pb`` the profiler wrote and returns the
device operations (name, start ns, duration ns) of every TPU plane's op
line and the host annotations of the benchmark's own spans. The functions
below are pure arithmetic over those lists, so the benchmark's tests check
them on a small recorded trace:

* `busy_ns`        union of one device's op intervals inside a window;
* `time_by_name`   summed op durations per op name;
* `tpu_kernel_operands`  which ops are Pallas kernels, by operand count;
* `idle_gaps`      the gaps between busy intervals, split by the host span
                   that covered each part.
"""
from __future__ import annotations

import pathlib
import re
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

Event = Tuple[str, int, int]          # (name, start ns, duration ns)

#: the device planes' line that holds one event per executed XLA operation
OPS_LINE = "XLA Ops"
DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")


def find_xplane(log_dir) -> pathlib.Path:
    files = sorted(pathlib.Path(log_dir).rglob("*.xplane.pb"))
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    return files[-1]


def read_trace(path) -> dict:
    """{"devices": {id: [Event]}, "host": [Event]} from one xplane file.

    Host events are those of any host-plane line whose name starts with
    ``bench.`` (the benchmark's `jax.profiler.TraceAnnotation` spans).
    """
    from jax.profiler import ProfileData

    data = ProfileData.from_file(str(path))
    devices: Dict[int, List[Event]] = {}
    host: List[Event] = []
    for plane in data.planes:
        m = DEVICE_PLANE.match(plane.name)
        for line in plane.lines:
            if m and line.name == OPS_LINE:
                devices.setdefault(int(m.group(1)), []).extend(
                    (ev.name, int(ev.start_ns), int(ev.duration_ns))
                    for ev in line.events)
            elif not m:
                host.extend((ev.name, int(ev.start_ns), int(ev.duration_ns))
                            for ev in line.events
                            if ev.name.startswith("bench."))
    return {"devices": devices, "host": host}


def tpu_kernel_operands(name: str) -> Optional[int]:
    """Operand count of a Pallas kernel's device op, None for other ops.

    On the TPU a `pallas_call` runs as an XLA custom call whose event name
    is its HLO text, ``%x = f32[..] custom-call(f32[..] %a, ...),
    custom_call_target="tpu_custom_call", ...``; each operand is one
    ``%``-named value inside the call's parentheses.
    """
    if 'custom_call_target="tpu_custom_call"' not in name:
        return None
    head, sep, rest = name.partition("custom-call(")
    if not sep:
        return None
    args = rest.split(", custom_call_target=", 1)[0]
    return args.count("%")


def merged(events: Iterable[Event], lo: int, hi: int) -> List[Tuple[int, int]]:
    """Sorted, disjoint busy intervals of ``events`` clipped to [lo, hi)."""
    spans = sorted((max(s, lo), min(s + d, hi)) for _, s, d in events
                   if s < hi and s + d > lo)
    out: List[Tuple[int, int]] = []
    for s, e in spans:
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1] = (out[-1][0], e)
        else:
            out.append((s, e))
    return out


def busy_ns(events: Iterable[Event], lo: int, hi: int) -> int:
    """Nanoseconds of [lo, hi) in which at least one op ran."""
    return sum(e - s for s, e in merged(events, lo, hi))


def time_by_name(events: Iterable[Event]) -> Dict[str, int]:
    """Summed durations (ns) per event name."""
    out: Dict[str, int] = {}
    for name, _, dur in events:
        out[name] = out.get(name, 0) + dur
    return out


def idle_gaps(events: Iterable[Event], host: Sequence[Event], lo: int,
              hi: int) -> Dict[str, int]:
    """Idle ns of [lo, hi) summed by what the host was doing: each part of
    a gap goes to the innermost (shortest) host span covering it, or to
    ``idle`` where none does."""
    import numpy as np

    busy = merged(events, lo, hi)
    gaps, cursor = [], lo
    for s, e in busy:
        if s > cursor:
            gaps.append((cursor, s))
        cursor = max(cursor, e)
    if cursor < hi:
        gaps.append((cursor, hi))
    names = [h[0] for h in host]
    starts = np.array([h[1] for h in host], np.int64)
    ends = starts + np.array([h[2] for h in host], np.int64)
    out: Dict[str, int] = {}
    for gs, ge in gaps:
        over = np.flatnonzero((starts < ge) & (ends > gs))
        cuts = sorted({gs, ge, *np.clip(starts[over], gs, ge).tolist(),
                       *np.clip(ends[over], gs, ge).tolist()})
        for a, b in zip(cuts, cuts[1:]):
            inner = [(ends[i] - starts[i], names[i]) for i in over
                     if starts[i] <= a and ends[i] >= b]
            name = min(inner)[1] if inner else "idle"
            out[name] = out.get(name, 0) + (b - a)
    return out
