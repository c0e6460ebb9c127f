"""Reduce a profiler trace (``.xplane.pb``) to what the benchmark reports.

A run with ``--trace 1`` records the measured window under the JAX
profiler, with the harness's host spans (``jax.profiler.TraceAnnotation``)
on the same clock as the device's operations. From it this module takes:

- busy seconds: the union of the intervals in which an operation ran on a
  device, inside the host span ``window``, averaged over the devices used;
- device time per operation name, clipped to the window;
- idle gaps: stretches of the window in which no operation ran, each
  attributed to the harness span that overlaps it most (``none`` if none
  does), summed by span name. A gap of a millisecond or more is named
  ``<span>><event>`` after the shortest other host event (the JAX and
  runtime events that the host tracer records) that covers at least
  half of it, so that a long gap says what the host was doing.
"""
from __future__ import annotations

import bisect
import re
from collections import defaultdict
from pathlib import Path

import numpy as np

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE = "XLA Ops"
HOST_PLANE = "/host:CPU"
WINDOW = "window"
DEEP_GAP_NS = 1_000_000  # gaps this long are named after a host event too


def find_trace(log_dir) -> Path:
    """The one ``.xplane.pb`` that ``jax.profiler.start_trace`` wrote."""
    found = sorted(Path(log_dir).rglob("*.xplane.pb"))
    if len(found) != 1:
        raise FileNotFoundError(f"expected one .xplane.pb under {log_dir}, "
                                f"found {len(found)}")
    return found[0]


def op_name(event_name: str) -> str:
    """An op's name, where the trace gives the op's whole HLO text
    (``%fusion.13 = f32[6041]{...} fusion(...)``)."""
    return event_name.split(" = ", 1)[0].lstrip("%")


def events(profile, span_names) -> tuple:
    """``(device_ops, host_spans, host_events)`` of a
    ``jax.profiler.ProfileData``: device ops as ``(device, name, start_ns,
    end_ns)``, host spans whose name is in ``span_names`` as ``(name,
    start_ns, end_ns)``, and the host plane's other events likewise."""
    ops, spans, other = [], [], []
    for plane in profile.planes:
        m = DEVICE_PLANE.match(plane.name)
        if m:
            dev = int(m.group(1))
            for line in plane.lines:
                if line.name == OPS_LINE:
                    ops.extend((dev, op_name(e.name), e.start_ns, e.end_ns)
                               for e in line.events)
        elif plane.name == HOST_PLANE:
            for line in plane.lines:
                for e in line.events:
                    (spans if e.name in span_names else other).append(
                        (e.name, e.start_ns, e.end_ns))
    return ops, spans, other


def event_name(name: str) -> str:
    """A host event's name without the metadata that follows ``#``."""
    return name.split("#", 1)[0][:60] or "unnamed"


def _inner(other, gaps) -> list:
    """For each gap, the shortest host event covering half of it, or None."""
    if not other:
        return [None] * len(gaps)
    other = sorted(other, key=lambda x: x[1])
    start = np.asarray([a for _, a, _ in other], np.int64)
    end = np.asarray([b for _, _, b in other], np.int64)
    out = []
    for g0, g1 in gaps:
        k = int(np.searchsorted(start, g1))
        cover = np.minimum(end[:k], g1) - np.maximum(start[:k], g0)
        hit = np.flatnonzero(2 * cover >= g1 - g0)
        if hit.size:
            j = hit[np.argmin(end[hit] - start[hit])]
            out.append(event_name(other[j][0]))
        else:
            out.append(None)
    return out


def _merge(intervals):
    out = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return out


def reduce(ops, spans, n_devices: int, other=()) -> dict:
    """Busy and idle time, device time per op and idle gaps by host span
    (and, for long gaps, by the host event in ``other`` that covers them),
    all in seconds, over the first ``window`` span."""
    windows = [(a, b) for name, a, b in spans if name == WINDOW]
    if not windows:
        raise ValueError("the trace has no 'window' span")
    w0, w1 = windows[0]
    per_dev = defaultdict(list)
    by_name = defaultdict(float)
    for dev, name, a, b in ops:
        a, b = max(a, w0), min(b, w1)
        if b > a:
            per_dev[dev].append((a, b))
            by_name[name] += (b - a) * 1e-9
    merged = {d: _merge(iv) for d, iv in per_dev.items()}
    busy_ns = sum(b - a for iv in merged.values() for a, b in iv)
    # idle gaps of the union over devices: time in which no device worked
    union = _merge([tuple(x) for iv in merged.values() for x in iv])
    gaps, t = [], w0
    for a, b in union:
        if a > t:
            gaps.append((t, a))
        t = max(t, b)
    if w1 > t:
        gaps.append((t, w1))
    host = sorted((a, b, n) for n, a, b in spans if n != WINDOW)
    starts = [a for a, _, _ in host]
    longest = max((b - a for a, b, _ in host), default=0)
    idle = defaultdict(float)
    deep = [g for g in gaps if g[1] - g[0] >= DEEP_GAP_NS]
    inner = dict(zip(deep, _inner(list(other), deep)))
    for g0, g1 in gaps:
        best, best_ov = "none", 0
        j = bisect.bisect_left(starts, g1) - 1
        while j >= 0 and starts[j] + longest > g0:
            a, b, name = host[j]
            ov = min(b, g1) - max(a, g0)
            if ov > best_ov:
                best, best_ov = name, ov
            j -= 1
        if inner.get((g0, g1)):
            best = f"{best}>{inner[(g0, g1)]}"
        idle[best] += (g1 - g0) * 1e-9
    return {
        "window_s": (w1 - w0) * 1e-9,
        "busy_s": busy_ns * 1e-9 / max(n_devices, 1),
        "ops": dict(by_name),
        "idle_gaps": dict(idle),
    }


def top(d: dict, n: int = 10) -> list:
    """The ``n`` largest entries of a name -> seconds dict, as pairs."""
    return [[k, v] for k, v in sorted(d.items(), key=lambda kv: -kv[1])[:n]]


def read(log_dir, span_names, n_devices: int) -> dict:
    from jax.profiler import ProfileData
    profile = ProfileData.from_file(str(find_trace(log_dir)))
    ops, spans, other = events(profile, set(span_names) | {WINDOW})
    return reduce(ops, spans, n_devices, other)
