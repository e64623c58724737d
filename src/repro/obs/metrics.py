"""Process-global metrics: Counter / Gauge / Histogram with a no-op default.

The runtime counterpart of the paper's resource tables (DESIGN.md §15):
dispatch counts per registry axis/backend, span seconds and calls
(``repro.obs.tracing``), device-to-host bytes, sparse-compaction
state-machine counters, window-cache hit rates, batch-size and latency
histograms — the numbers the benchmark's per-layer readers and the serve
path's ``--metrics-out`` report through.

Everything here is host-side Python state (ints, floats, bin lists) behind
one lock; no jax array is ever stored.  Two invariants keep the module
safe to leave compiled into every hot seam:

* **No-op default.**  Metrics are disabled until :func:`enable` is called;
  every record site checks one module flag first, so the disabled path is
  a single attribute load + function call (gated ≤3% median on
  ``SketchBank.update_many`` by ``benchmarks/bench_obs.py``).
* **Trace hygiene.**  No record site runs under an active jax trace:
  :func:`recording` reuses the window cache's gate
  (``repro.compat.trace_state_clean()``,
  the same check ``WindowedBank._concrete`` makes before touching hidden
  host state).  Tracing a jitted caller therefore neither leaks tracers
  into the registry nor double-books work the compiled executable replays
  without running Python again.
"""

from __future__ import annotations

import bisect
import functools
import json
import math
import threading
import time
from typing import Callable, Dict, Optional

import jax
import numpy as np

from repro.compat import trace_state_clean

__all__ = [
    "enable",
    "disable",
    "enabled",
    "recording",
    "inc",
    "gauge",
    "observe",
    "counter_value",
    "to_host",
    "timed",
    "seam",
    "wrap_backend",
    "snapshot",
    "to_json",
    "reset",
]

_LOCK = threading.Lock()
_ENABLED = False

_COUNTERS: Dict[str, float] = {}
_GAUGES: Dict[str, float] = {}
_HISTS: Dict[str, "_Hist"] = {}

# Log-scaled bins shared by every histogram: 4 bins/decade from 1e-7 to
# 1e9, wide enough for sub-µs seam timings and 10^9-item batch sizes on
# the same scale.  ~65 edges -> one small int list per histogram.
_EDGES = tuple(10.0 ** (e / 4.0) for e in range(-28, 37))

class _Hist:
    """Log-binned histogram: count/sum/min/max + percentile estimates."""

    __slots__ = ("count", "total", "vmin", "vmax", "bins")

    def __init__(self):
        self.count = 0
        self.total = 0.0
        self.vmin = math.inf
        self.vmax = -math.inf
        self.bins = [0] * (len(_EDGES) + 1)

    def add(self, value: float) -> None:
        value = float(value)
        self.count += 1
        self.total += value
        if value < self.vmin:
            self.vmin = value
        if value > self.vmax:
            self.vmax = value
        self.bins[bisect.bisect_right(_EDGES, value)] += 1

    def percentile(self, q: float) -> float:
        """Bin-interpolated q-quantile (geometric midpoint within a bin)."""
        if self.count == 0:
            return 0.0
        target = q * self.count
        acc = 0
        for i, n in enumerate(self.bins):
            acc += n
            if acc >= target and n:
                lo = _EDGES[i - 1] if i > 0 else self.vmin
                hi = _EDGES[i] if i < len(_EDGES) else self.vmax
                lo = max(min(lo, self.vmax), self.vmin)
                hi = min(max(hi, self.vmin), self.vmax)
                if lo > 0.0 and hi > 0.0:
                    return math.sqrt(lo * hi)
                return 0.5 * (lo + hi)
        return self.vmax

    def summary(self) -> dict:
        if self.count == 0:
            return {
                "count": 0,
                "sum": 0.0,
                "mean": 0.0,
                "min": 0.0,
                "max": 0.0,
                "p50": 0.0,
                "p90": 0.0,
                "p99": 0.0,
            }
        return {
            "count": self.count,
            "sum": self.total,
            "mean": self.total / self.count,
            "min": self.vmin,
            "max": self.vmax,
            "p50": self.percentile(0.50),
            "p90": self.percentile(0.90),
            "p99": self.percentile(0.99),
        }


# ---------------------------------------------------------------------------
# enable / gate


def enable() -> None:
    """Turn recording on (state is kept; call :func:`reset` to clear)."""
    global _ENABLED
    _ENABLED = True


def disable() -> None:
    global _ENABLED
    _ENABLED = False


def enabled() -> bool:
    return _ENABLED


def recording() -> bool:
    """True when a record site should record.

    Order matters: the module flag short-circuits first so the disabled
    path never pays the jax call; under an active trace the site is
    skipped entirely (trace hygiene, DESIGN.md §15).
    """
    return _ENABLED and trace_state_clean()


def reset() -> None:
    """Clear every counter/gauge/histogram (enabled flag untouched)."""
    with _LOCK:
        _COUNTERS.clear()
        _GAUGES.clear()
        _HISTS.clear()


# ---------------------------------------------------------------------------
# record sites


def inc(name: str, value: float = 1) -> None:
    """Add ``value`` to counter ``name`` (no-op unless :func:`recording`)."""
    if not recording():
        return
    with _LOCK:
        _COUNTERS[name] = _COUNTERS.get(name, 0) + value


def gauge(name: str, value: float) -> None:
    """Set gauge ``name`` to ``value`` (last write wins)."""
    if not recording():
        return
    with _LOCK:
        _GAUGES[name] = float(value)


def observe(name: str, value: float) -> None:
    """Record ``value`` into histogram ``name``."""
    if not recording():
        return
    with _LOCK:
        hist = _HISTS.get(name)
        if hist is None:
            hist = _HISTS[name] = _Hist()
        hist.add(value)


def counter_value(name: str) -> float:
    """Current value of counter ``name`` (0 if never incremented)."""
    with _LOCK:
        return _COUNTERS.get(name, 0)


def to_host(x, dtype=None) -> np.ndarray:
    """``np.asarray(x, dtype)``, counting a device array's bytes.

    A ``jax.Array`` input is a device-to-host copy: its ``nbytes`` go to
    the ``transfer.d2h_bytes`` counter.  Host arrays are not copies and
    count nothing.  A repeated read of the same device array counts each
    time, though jax may serve it from its host-side cache.
    """
    if _ENABLED and isinstance(x, jax.Array):
        inc("transfer.d2h_bytes", x.nbytes)
    return np.asarray(x, dtype)


# ---------------------------------------------------------------------------
# timers


class _NullTimer:
    """Shared do-nothing context manager: the disabled fast path."""

    __slots__ = ()
    elapsed_s = 0.0

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False


_NULL = _NullTimer()


class _Timer:
    __slots__ = ("_hist", "_t0", "elapsed_s")

    def __init__(self, hist):
        self._hist = hist
        self.elapsed_s = 0.0

    def __enter__(self):
        self._t0 = time.perf_counter()
        return self

    def __exit__(self, *exc):
        self.elapsed_s = time.perf_counter() - self._t0
        observe(self._hist, self.elapsed_s)
        return False


def timed(name: str) -> "_Timer":
    """Context manager feeding histogram ``name`` with wall seconds."""
    if not recording():
        return _NULL
    return _Timer(name)


def seam(axis: str, backend: str) -> None:
    """Count one dispatch through a seam: ``dispatch.{axis}.{backend}.calls``.

    Gated like every record site (off unless enabled, skipped under an
    active jax trace).  How long a phase of host work takes is a span's
    job (``repro.obs.tracing``), not the seam's.
    """
    inc(f"dispatch.{axis}.{backend}.calls")


def wrap_backend(axis: str, name: str, fn: Callable) -> Callable:
    """Wrap a registry backend so every real dispatch is counted.

    Applied once at registration (``repro.sketch.plan.register_*``), so
    the per-dispatch cost when disabled is one extra frame + flag check.
    Empty-stream short-circuits never reach the backend, so they are
    never counted — the spy-backend contract (tests/test_obs.py).
    """

    @functools.wraps(fn)
    def dispatch(*args, **kwargs):
        if _ENABLED:
            seam(axis, name)
        return fn(*args, **kwargs)

    dispatch.__sketch_backend__ = fn
    return dispatch


# ---------------------------------------------------------------------------
# export


def snapshot() -> dict:
    """Plain-dict snapshot of every metric (stable schema, json-ready)."""
    with _LOCK:
        return {
            "enabled": _ENABLED,
            "counters": dict(_COUNTERS),
            "gauges": dict(_GAUGES),
            "histograms": {k: h.summary() for k, h in _HISTS.items()},
        }


def to_json(indent: Optional[int] = 2) -> str:
    return json.dumps(snapshot(), indent=indent, sort_keys=True)
