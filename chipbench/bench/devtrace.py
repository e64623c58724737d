"""Reduce a profiler trace to device busy time, idle gaps and op totals.

The harness records a steady sub-window with ``jax.profiler`` and marks it
with a ``window`` annotation; every call it makes into the program sits in a
``jax.profiler.TraceAnnotation`` named after the layer it enters (``SPANS``).
This module reads the ``.xplane.pb`` file back with ``ProfileData`` and
computes, on the profiler's one clock:

* busy: the union of the intervals in which an operation ran on a device,
  clipped to the window, averaged over the devices used;
* idle gaps: the complement of that union inside the window, each named
  after the harness span that overlaps it most (``none`` when the host was
  between spans);
* op totals: device time per op name, for the breakdown;
* per-call readings (counts, mean durations, device time inside calls)
  over the spans that lie wholly inside the window, so a call cut by the
  window's edge is neither counted whole nor averaged clipped.

Which trace lines hold device operations is a parameter: ``tpu_device_ops``
reads the "XLA Ops" line of each ``/device:TPU:<n>`` plane and raises when a
device has none, so a trace laid out otherwise fails the run instead of
reading as an idle device.
"""

from __future__ import annotations

import bisect
import dataclasses
import glob
import os
from typing import Callable, Dict, List, Optional, Tuple

SPANS = ("gen", "submit", "flush", "read", "update")
WINDOW = "window"
NO_SPAN = "none"

Interval = Tuple[float, float]


def tpu_device_ops(profile, chips: int) -> List[List[Tuple[str, float, float]]]:
    """Per device (the first ``chips`` TPU planes): (op name, start, end) ns."""
    planes = []
    for plane in profile.planes:
        name = plane.name
        if name.startswith("/device:TPU:") and name[len("/device:TPU:"):].isdigit():
            planes.append((int(name[len("/device:TPU:"):]), plane))
    planes.sort(key=lambda t: t[0])
    if len(planes) < chips:
        raise ValueError(
            f"trace has {len(planes)} /device:TPU:<n> planes, the cell uses {chips}: "
            f"{[p.name for p in profile.planes]}"
        )
    out = []
    for _, plane in planes[:chips]:
        lines = [line for line in plane.lines if line.name == "XLA Ops"]
        if not lines:
            raise ValueError(
                f"{plane.name} has no 'XLA Ops' line: {[ln.name for ln in plane.lines]}"
            )
        out.append(
            [(ev.name, ev.start_ns, ev.start_ns + ev.duration_ns)
             for line in lines for ev in line.events]
        )
    return out


def cpu_device_ops(profile, chips: int) -> List[List[Tuple[str, float, float]]]:
    """A CPU rehearsal's stand-in: XLA runs each op on the client's host
    threads (``tf_XLA...`` lines of ``/host:CPU``), read as one device."""
    ops = []
    for plane in profile.planes:
        if plane.name != "/host:CPU":
            continue
        for line in plane.lines:
            if not line.name.startswith("tf_XLA"):
                continue
            for ev in line.events:
                if ev.duration_ns > 0 and not ev.name.startswith(
                    ("ThreadpoolListener", "end: ")
                ):
                    ops.append((ev.name, ev.start_ns, ev.start_ns + ev.duration_ns))
    return [ops][:chips]


DEVICE_OPS = {"tpu": tpu_device_ops, "cpu": cpu_device_ops}


def union(intervals) -> List[Interval]:
    """Sorted, merged copy of ``intervals`` (touching ones merge)."""
    merged: List[List[float]] = []
    for s, e in sorted(intervals):
        if e <= s:
            continue
        if merged and s <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], e)
        else:
            merged.append([s, e])
    return [(s, e) for s, e in merged]


def total(intervals) -> float:
    return sum(e - s for s, e in intervals)


def intersect(a: List[Interval], b: List[Interval]) -> List[Interval]:
    """Intersection of two merged, sorted interval lists."""
    out, i, j = [], 0, 0
    while i < len(a) and j < len(b):
        s, e = max(a[i][0], b[j][0]), min(a[i][1], b[j][1])
        if e > s:
            out.append((s, e))
        if a[i][1] < b[j][1]:
            i += 1
        else:
            j += 1
    return out


def complement(busy: List[Interval], lo: float, hi: float) -> List[Interval]:
    """The gaps of a merged list inside [lo, hi]."""
    out, t = [], lo
    for s, e in busy:
        if s > t:
            out.append((t, min(s, hi)))
        t = max(t, e)
    if t < hi:
        out.append((t, hi))
    return [(s, e) for s, e in out if e > s]


@dataclasses.dataclass
class Trace:
    """One traced sub-window, in nanoseconds on the profiler's clock."""

    window: Interval
    busy: List[List[Interval]]  # per device, merged and clipped
    ops: List[Tuple[str, float, float]]  # every device op, clipped
    spans: List[Tuple[str, float, float]]  # harness spans that overlap it

    @property
    def window_s(self) -> float:
        return (self.window[1] - self.window[0]) * 1e-9

    @property
    def busy_s(self) -> float:
        """Busy seconds averaged over the devices used."""
        if not self.busy:
            return 0.0
        return sum(total(b) for b in self.busy) * 1e-9 / len(self.busy)

    def whole_spans(self, name: str) -> List[Interval]:
        """Spans ``name`` that lie wholly inside the window."""
        lo, hi = self.window
        return [(s, e) for n, s, e in self.spans if n == name and lo <= s and e <= hi]

    def span_count(self, name: str) -> int:
        return len(self.whole_spans(name))

    def span_mean_s(self, name: str) -> Optional[float]:
        """Mean host seconds of the whole spans ``name``."""
        spans = self.whole_spans(name)
        return sum(e - s for s, e in spans) / len(spans) * 1e-9 if spans else None

    def gaps(self) -> List[Tuple[str, float]]:
        """Idle gaps of the first device, longest first: (span, seconds)."""
        if not self.busy:
            return [(NO_SPAN, self.window_s)]
        out = []
        # harness spans run one after another on the loop's thread, so
        # sorted by start they are sorted by end too
        spans = sorted((s, e, n) for n, s, e in self.spans)
        starts = [s for s, _, _ in spans]
        ends = [e for _, e, _ in spans]
        for gs, ge in complement(self.busy[0], *self.window):
            best, best_overlap = NO_SPAN, 0.0
            lo = bisect.bisect_right(ends, gs)
            hi = bisect.bisect_left(starts, ge)
            for s, e, n in spans[lo:hi]:
                overlap = min(e, ge) - max(s, gs)
                if overlap > best_overlap:
                    best, best_overlap = n, overlap
            out.append((best, (ge - gs) * 1e-9))
        out.sort(key=lambda t: -t[1])
        return out

    def op_totals(self) -> List[Tuple[str, float]]:
        """Device seconds per op name over all devices, largest first."""
        acc: Dict[str, float] = {}
        for name, s, e in self.ops:
            acc[name] = acc.get(name, 0.0) + (e - s) * 1e-9
        return sorted(acc.items(), key=lambda t: -t[1])

    def breakdown(self, top: int = 10) -> dict:
        return {
            "device_ops": [[n, s] for n, s in self.op_totals()[:top]],
            "idle_gaps": [[n, s] for n, s in self.gaps()[:top]],
        }


def xplane_file(log_dir: str) -> str:
    files = glob.glob(os.path.join(log_dir, "plugins", "profile", "*", "*.xplane.pb"))
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    return max(files, key=os.path.getmtime)


def load(
    log_dir: str,
    chips: int = 1,
    device_ops: Callable = tpu_device_ops,
) -> Optional[Trace]:
    """Reduce the newest trace under ``log_dir``; None when it has no
    ``window`` annotation."""
    from jax.profiler import ProfileData

    profile = ProfileData.from_file(xplane_file(log_dir))
    window = None
    spans = []
    for plane in profile.planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.name == WINDOW:
                    window = (ev.start_ns, ev.start_ns + ev.duration_ns)
                elif ev.name in SPANS:
                    spans.append((ev.name, ev.start_ns, ev.start_ns + ev.duration_ns))
    if window is None:
        return None
    lo, hi = window
    per_device = device_ops(profile, chips)
    busy, ops = [], []
    for dev_ops in per_device:
        clipped = [(n, max(s, lo), min(e, hi)) for n, s, e in dev_ops if e > lo and s < hi]
        ops.extend(clipped)
        busy.append(union((s, e) for _, s, e in clipped))
    spans = [(n, s, e) for n, s, e in spans if e > lo and s < hi]
    return Trace(window=window, busy=busy, ops=ops, spans=spans)
