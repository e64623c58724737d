"""repro.obs — runtime observability: metrics registry + program spans.

DESIGN.md §15.  ``metrics`` holds the process-global Counter/Gauge/
Histogram registry (a no-op until ``metrics.enable()``); ``tracing``
provides ``span()``, which while the registry records puts a
``repro/<name>`` annotation on the ``jax.profiler`` clock and adds
``<name>.seconds`` / ``<name>.calls`` counters; ``format`` is the shared
report-line vocabulary.
"""

from repro.obs import format, metrics, tracing

__all__ = ["format", "metrics", "tracing"]
