"""Program spans on the profiler's clock, plus the shared Stopwatch helper.

``span("layer.phase")`` marks where the host work of one layer happens.
While the metrics registry is recording (:func:`repro.obs.metrics.recording`:
enabled and no active jax trace) a span

* opens a ``jax.profiler.TraceAnnotation("repro/" + name)``, so a
  ``jax.profiler`` capture shows it on the host plane, on the same clock as
  the device's ops, where it can name a device idle gap;
* adds its wall seconds and one call to the registry counters
  ``<name>.seconds`` and ``<name>.calls``;
* feeds ``metric=`` (a histogram name) with its wall seconds.

When the registry is not recording, ``span`` returns one shared null
context after a single flag check, whose ``elapsed_s`` reads 0.0: code
that reports a wall time whether or not the registry records times it
with :class:`Stopwatch`.  Under an active jax trace nothing is recorded,
so tracing a jitted caller neither leaks tracers nor books work the
compiled executable replays without running Python (DESIGN.md §15).
"""

from __future__ import annotations

import time
from typing import Optional

from jax.profiler import TraceAnnotation

from repro.obs import metrics as _metrics

__all__ = ["span", "Stopwatch", "PREFIX"]

PREFIX = "repro/"  # profiler event names: PREFIX + span name


class _Span:
    __slots__ = ("name", "metric", "elapsed_s", "_note", "_t0")

    def __init__(self, name: str, metric: Optional[str], args: dict):
        self.name = name
        self.metric = metric
        self.elapsed_s = 0.0
        self._note = TraceAnnotation(PREFIX + name, **args)

    def __enter__(self) -> "_Span":
        self._note.__enter__()
        self._t0 = time.perf_counter()
        return self

    def __exit__(self, *exc) -> bool:
        self.elapsed_s = time.perf_counter() - self._t0
        self._note.__exit__(*exc)
        _metrics.inc(self.name + ".seconds", self.elapsed_s)
        _metrics.inc(self.name + ".calls")
        if self.metric is not None:
            _metrics.observe(self.metric, self.elapsed_s)
        return False


def span(name: str, *, metric: Optional[str] = None, **args):
    """Context manager around one phase of host work named ``name``.

    ``with span("sparse.route"): ...``; read ``.elapsed_s`` on the value
    it returns.  Extra keyword arguments are attached to the profiler
    event as metadata.
    """
    if not _metrics.recording():
        return _metrics._NULL  # shared do-nothing context, elapsed_s 0.0
    return _Span(name, metric, args)


class Stopwatch:
    """Explicit ``start()``/``stop()`` timer for split begin/end seams.

    The watchdog-style idiom where begin and end live in different calls
    (so a context manager cannot span them).  ``stop()`` returns elapsed
    seconds and disarms; ``elapsed()`` peeks without disarming.
    """

    __slots__ = ("_t0",)

    def __init__(self):
        self._t0: Optional[float] = None

    def start(self) -> None:
        self._t0 = time.perf_counter()

    @property
    def running(self) -> bool:
        return self._t0 is not None

    def elapsed(self) -> float:
        assert self._t0 is not None, "start() not called"
        return time.perf_counter() - self._t0

    def stop(self) -> float:
        dt = self.elapsed()
        self._t0 = None
        return dt
