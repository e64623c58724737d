"""Observability subsystem: metrics registry, trace hygiene, program spans.

Pins the DESIGN.md §15 contracts:

* disabled is a TRUE no-op — update/estimate paths leave the registry
  empty and add zero backend dispatches;
* record sites inside jax-traced functions are skipped entirely (no
  tracer leaks, no double-booking when the compiled executable replays);
* ``to_json()`` round-trips the snapshot schema exactly;
* ``span`` puts ``repro/<name>`` on the ``jax.profiler`` clock and adds
  ``<name>.seconds`` / ``<name>.calls`` while the registry records, at
  every layer the hybrid ingest and single-sketch update paths cross,
  without changing a bit of what they compute.
"""

import glob
import json
import os
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.obs import metrics, tracing
from repro.obs.format import (
    fmt_bytes,
    fmt_count,
    fmt_pct,
    fmt_rate,
    fmt_seconds,
    kv_line,
    metrics_report_line,
    truncated_note,
)
from repro.sketch import (
    ExecutionPlan,
    HLLConfig,
    HybridBank,
    HyperLogLog,
    SketchBank,
    estimate_many,
    register_bank_backend,
)
from repro.sketch import sparse as sparse_mod
from repro.sketch import register_backend
from repro.sketch.backends import bank_update_jnp, update_pipelined
from repro.sketch.dispatch import update_registers
from repro.sketch.plan import get_bank_backend

CFG = HLLConfig(p=6, hash_bits=32)

_SPY = {"n": 0}


# delegates to the real jnp paths so backend-sweeping suites stay green
# (plan.validate needs the name on the single-sketch axis too)
@register_backend("obs_spy_jnp")
def _spy_backend(registers, items, cfg, plan):
    _SPY["n"] += 1
    return update_pipelined(registers, items, cfg, plan.pipelines)


@register_bank_backend("obs_spy_jnp")
def _spy_bank_backend(registers, keys, items, cfg, plan):
    _SPY["n"] += 1
    return bank_update_jnp(registers, keys, items, cfg)


@pytest.fixture(autouse=True)
def _clean_obs_state():
    """Every test starts and ends with metrics off and empty."""
    metrics.disable()
    metrics.reset()
    yield
    metrics.disable()
    metrics.reset()


def _ingest(bank, n=32, backend="jnp"):
    keys = jnp.arange(n, dtype=jnp.int32) % 4
    items = jnp.arange(n, dtype=jnp.int32)
    return bank.update_many(keys, items, plan=ExecutionPlan(backend=backend))


def _profiled(log_dir, body):
    """Run ``body()`` under a ``jax.profiler`` capture on the CPU.

    Returns the ``repro/`` host events as (plane, name, start_ns, end_ns)
    and the capture's own interval on the profiler's clock, marked by an
    annotation around the body.
    """
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    with jax.profiler.trace(str(log_dir), profiler_options=opts):
        with jax.profiler.TraceAnnotation("capture"):
            body()
    (path,) = glob.glob(
        os.path.join(str(log_dir), "plugins", "profile", "*", "*.xplane.pb")
    )
    events, interval = [], None
    for plane in jax.profiler.ProfileData.from_file(path).planes:
        for line in plane.lines:
            for ev in line.events:
                end = ev.start_ns + ev.duration_ns
                if ev.name == "capture":
                    interval = (ev.start_ns, end)
                elif ev.name.startswith(tracing.PREFIX):
                    events.append((plane.name, ev.name, ev.start_ns, end))
    assert interval is not None, "the capture's marker is not in the trace"
    return events, interval


# ----------------------------------------------------------------------------
# disabled default: true no-op
# ----------------------------------------------------------------------------


def test_disabled_by_default_registry_stays_empty():
    assert not metrics.enabled()
    bank = _ingest(SketchBank.empty(4, CFG))
    np.asarray(estimate_many(bank.registers, CFG))
    snap = metrics.snapshot()
    assert snap["enabled"] is False
    assert snap["counters"] == {}
    assert snap["gauges"] == {}
    assert snap["histograms"] == {}


def test_disabled_adds_zero_backend_dispatches():
    """The seam wrapper forwards exactly one call per real dispatch."""
    bank = SketchBank.empty(4, CFG)
    _SPY["n"] = 0
    bank = _ingest(bank, backend="obs_spy_jnp")
    assert _SPY["n"] == 1  # wrapped, not doubled
    # empty streams short-circuit BEFORE the wrapper: no dispatch, and
    # nothing counted even with metrics on
    metrics.enable()
    _SPY["n"] = 0
    out = bank.update_many(
        jnp.zeros((0,), jnp.int32),
        jnp.zeros((0,), jnp.int32),
        plan=ExecutionPlan(backend="obs_spy_jnp"),
    )
    assert out is bank and _SPY["n"] == 0
    assert metrics.counter_value("dispatch.bank_update.obs_spy_jnp.calls") == 0
    # the single-sketch path counts its skips so the no-dispatch contract
    # stays observable
    regs = update_registers(
        jnp.zeros((CFG.m,), jnp.uint8),
        jnp.zeros((0,), jnp.int32),
        CFG,
        ExecutionPlan(backend="obs_spy_jnp"),
    )
    assert regs.shape == (CFG.m,) and _SPY["n"] == 0
    assert metrics.counter_value("dispatch.update.skipped_empty") == 1


def test_record_sites_noop_when_disabled():
    metrics.inc("x")
    metrics.gauge("g", 3.0)
    metrics.observe("h", 1.0)
    with metrics.timed("t"):
        pass
    assert metrics.snapshot()["counters"] == {}
    assert metrics.counter_value("x") == 0


# ----------------------------------------------------------------------------
# enabled: dispatch seams count and time
# ----------------------------------------------------------------------------


def test_enabled_counts_dispatches_per_axis_and_backend():
    metrics.enable()
    bank = _ingest(SketchBank.empty(4, CFG))
    np.asarray(estimate_many(bank.registers, CFG, estimator="original"))
    HyperLogLog.empty(CFG).update(jnp.arange(8, dtype=jnp.int32))
    snap = metrics.snapshot()
    assert snap["counters"]["dispatch.bank_update.jnp.calls"] == 1
    # host time is the span's to record, not the dispatch seam's
    assert snap["counters"]["hll.update.calls"] == 1
    assert snap["counters"]["hll.update.seconds"] > 0
    assert not any(k.endswith(".seconds") for k in snap["histograms"])
    assert snap["counters"]["dispatch.estimate.original.calls"] == 1
    assert snap["histograms"]["bank.update_many.batch_items"]["count"] == 1
    assert snap["histograms"]["bank.update_many.batch_items"]["max"] == 32.0


def test_reset_clears_but_keeps_enabled():
    metrics.enable()
    metrics.inc("a")
    metrics.reset()
    snap = metrics.snapshot()
    assert snap["enabled"] is True and snap["counters"] == {}


# ----------------------------------------------------------------------------
# jit safety: no record site runs under an active jax trace
# ----------------------------------------------------------------------------


def test_record_sites_skipped_under_jit():
    metrics.enable()

    @jax.jit
    def f(x):
        metrics.inc("jit.counter")
        metrics.gauge("jit.gauge", 1.0)
        metrics.observe("jit.hist", 2.0)
        with metrics.timed("jit.timed"):
            y = x + 1
        return y

    np.asarray(f(jnp.arange(4)))  # traces + runs
    np.asarray(f(jnp.arange(4)))  # compiled: no python at all
    snap = metrics.snapshot()
    assert snap["counters"] == {} and snap["gauges"] == {}
    assert snap["histograms"] == {}


def test_wrapped_backend_seam_skipped_under_jit():
    """Tracing a jitted caller must not book a dispatch the executable
    replays without running Python again."""
    metrics.enable()
    wrapped = get_bank_backend("jnp")
    plan = ExecutionPlan(backend="jnp")
    regs = SketchBank.empty(4, CFG).registers
    keys = jnp.arange(8, dtype=jnp.int32) % 4
    items = jnp.arange(8, dtype=jnp.int32)

    @jax.jit
    def g(r, k, x):
        return wrapped(r, k, x, CFG, plan)

    inside = np.asarray(g(regs, keys, items))
    np.asarray(g(regs, keys, items))
    assert metrics.counter_value("dispatch.bank_update.jnp.calls") == 0
    # ...while the same wrapped fn called eagerly records exactly once
    outside = np.asarray(wrapped(regs, keys, items, CFG, plan))
    assert metrics.counter_value("dispatch.bank_update.jnp.calls") == 1
    np.testing.assert_array_equal(inside, outside)


def test_span_under_jit_emits_no_event(tmp_path):
    metrics.enable()

    @jax.jit
    def f(x):
        with tracing.span("traced.body"):
            return x * 2

    def body():
        np.asarray(f(jnp.arange(3)))  # traces + runs
        np.asarray(f(jnp.arange(3)))  # compiled: no python at all

    events, _ = _profiled(tmp_path, body)
    assert [e for e in events if e[1] == "repro/traced.body"] == []
    assert metrics.snapshot()["counters"] == {}


# ----------------------------------------------------------------------------
# snapshot schema / to_json round-trip
# ----------------------------------------------------------------------------


def test_to_json_roundtrips_snapshot():
    metrics.enable()
    metrics.inc("c", 3)
    metrics.gauge("g", 2.5)
    for v in (0.001, 0.01, 0.1):
        metrics.observe("h", v)
    snap = metrics.snapshot()
    assert json.loads(metrics.to_json()) == snap
    assert set(snap) == {"enabled", "counters", "gauges", "histograms"}
    hist = snap["histograms"]["h"]
    assert set(hist) == {"count", "sum", "mean", "min", "max", "p50", "p90", "p99"}
    assert hist["count"] == 3
    assert hist["min"] == pytest.approx(0.001)
    assert hist["max"] == pytest.approx(0.1)


def test_histogram_percentiles_sane():
    metrics.enable()
    for v in range(1, 1001):
        metrics.observe("lat", float(v))
    h = metrics.snapshot()["histograms"]["lat"]
    assert h["count"] == 1000
    assert h["mean"] == pytest.approx(500.5)
    # log-binned at 4 bins/decade: estimates land within one bin (~1.78x)
    assert 500 / 1.78 <= h["p50"] <= 500 * 1.78
    assert 900 / 1.78 <= h["p90"] <= 1000.0
    assert h["p99"] <= h["max"] <= 1000.0
    assert h["min"] == 1.0


# ----------------------------------------------------------------------------
# tracing: spans on the profiler's clock, their counters, the seams
# ----------------------------------------------------------------------------


def test_span_on_profiler_clock_nests_inside_capture(tmp_path):
    metrics.enable()
    spans = {}

    def body():
        with tracing.span("outer", phase="test") as spans["outer"]:
            with tracing.span("inner") as spans["inner"]:
                time.sleep(0.002)

    events, (lo, hi) = _profiled(tmp_path, body)
    outer, inner = spans["outer"], spans["inner"]
    assert 0 < inner.elapsed_s <= outer.elapsed_s
    found = {name: (plane, s, e) for plane, name, s, e in events}
    assert set(found) == {"repro/outer", "repro/inner"}
    for plane, s, e in found.values():
        assert plane.startswith("/host:")
        assert lo <= s <= e <= hi  # inside the profiled interval
    # nesting is containment on the one clock: inner inside outer
    _, os_, oe = found["repro/outer"]
    _, is_, ie = found["repro/inner"]
    assert os_ <= is_ <= ie <= oe
    assert (ie - is_) * 1e-9 >= 0.002
    counters = metrics.snapshot()["counters"]
    assert counters["outer.calls"] == counters["inner.calls"] == 1
    assert counters["outer.seconds"] == pytest.approx(outer.elapsed_s)
    assert counters["inner.seconds"] == pytest.approx(inner.elapsed_s)


def test_span_metric_feeds_histogram():
    metrics.enable()
    with tracing.span("req", metric="req.seconds"):
        pass
    assert metrics.snapshot()["histograms"]["req.seconds"]["count"] == 1
    assert metrics.counter_value("req.calls") == 1


def test_dispatch_seam_counts_calls_not_seconds(tmp_path):
    """A wrapped backend counts its dispatches and times nothing; a
    profiler capture alone records nothing in the registry."""
    events, _ = _profiled(tmp_path, lambda: _ingest(SketchBank.empty(4, CFG)))
    assert events == [] and metrics.snapshot()["counters"] == {}
    metrics.enable()
    _ingest(SketchBank.empty(4, CFG))
    snap = metrics.snapshot()
    assert snap["counters"]["dispatch.bank_update.jnp.calls"] == 1
    assert "dispatch.bank_update.jnp.seconds" not in snap["counters"]
    assert "dispatch.bank_update.jnp.seconds" not in snap["histograms"]


def test_span_counters_lifecycle():
    # off: one shared null context, nothing recorded
    with tracing.span("once") as off:
        pass
    assert off is tracing.span("other") and off.elapsed_s == 0.0
    assert metrics.snapshot()["counters"] == {}
    metrics.enable()
    for _ in range(3):
        with tracing.span("once") as live:
            pass
    counters = metrics.snapshot()["counters"]
    assert live is not off and counters["once.calls"] == 3
    assert counters["once.seconds"] >= live.elapsed_s >= 0
    metrics.disable()
    with tracing.span("once"):
        pass
    assert metrics.counter_value("once.calls") == 3
    metrics.reset()
    assert metrics.snapshot()["counters"] == {}


# ----------------------------------------------------------------------------
# spans where the work happens: hybrid ingest, single-sketch update
# ----------------------------------------------------------------------------

ROWS = 16
DENSE_ROWS = np.arange(ROWS) % 4 == 0  # rows forced dense at the start
COMPACT_CHILDREN = ("hash", "pairs", "dedup", "products")


def _hybrid_run(monkeypatch, batches=6, n=48):
    """Ingest ``batches`` update_many calls into a hybrid bank with some
    dense rows and low flush floors, so pressure compactions fire, then
    read every row (a read compaction).  Returns the products compared
    bit for bit and the number of update_many calls."""
    monkeypatch.setattr(sparse_mod, "_FLUSH_MIN_PAIRS", 64)
    monkeypatch.setattr(sparse_mod, "_FLUSH_FACTOR", 2)
    rng = np.random.default_rng(7)
    start = SketchBank.empty(ROWS, CFG).update_many(
        jnp.asarray(rng.integers(0, ROWS, 64, dtype=np.int32)),
        jnp.asarray(rng.integers(0, 2**31, 64, dtype=np.int32)),
    )
    hb = HybridBank.from_dense(start, dense_rows=DENSE_ROWS)
    for _ in range(batches):
        keys = rng.integers(0, ROWS, n, dtype=np.int32)
        items = rng.integers(0, 2**31, n, dtype=np.int32)
        hb = hb.update_many(jnp.asarray(keys), jnp.asarray(items))
    hb = hb.update_many(jnp.asarray([0, 1], jnp.int32), jnp.asarray([5, 6], jnp.int32))
    est = np.asarray(hb.estimate_many())
    sketch = HyperLogLog.empty(CFG)
    for _ in range(3):
        sketch = sketch.update(jnp.asarray(rng.integers(0, 2**31, n, dtype=np.int32)))
    products = (
        np.asarray(hb.to_dense().registers),
        hb.counts,
        est,
        np.asarray(sketch.registers),
        sketch.count,
    )
    return products, batches + 1


def test_hybrid_ingest_records_every_span(monkeypatch, tmp_path):
    metrics.enable()
    calls = {}

    def body():
        calls["n"] = _hybrid_run(monkeypatch)[1]

    events, (lo, hi) = _profiled(tmp_path, body)
    c = metrics.snapshot()["counters"]
    n = calls["n"]
    assert c["sparse.route.calls"] == n
    # every batch has keys on a dense row (rows 0, 4, 8, 12 of 16)
    assert c["sparse.dense.calls"] == n
    pressure = c.get("sparse.flush.pressure", 0)
    assert pressure >= 1 and c["sparse.flush.read"] == 1
    assert c["sparse.compact.pressure.calls"] == pressure
    assert c["sparse.compact.read.calls"] == 1
    for child in COMPACT_CHILDREN:
        assert c[f"sparse.compact.{child}.calls"] == pressure + 1
    assert c["hll.update.calls"] == 3
    for name in ("sparse.route", "sparse.dense", "sparse.compact.read", "hll.update"):
        assert c[name + ".seconds"] > 0
    # every update_many reads the (ROWS,) int32 slot map back at least
    assert c["transfer.d2h_bytes"] >= n * ROWS * 4
    # ...and each span lies on the profiler's clock, inside the capture
    names = {name for _, name, _, _ in events}
    want = {
        "sparse.route",
        "sparse.dense",
        "sparse.compact.pressure",
        "sparse.compact.read",
        "hll.update",
        "hll.update.registers",
        "hll.update.counter",
    }
    want |= {f"sparse.compact.{child}" for child in COMPACT_CHILDREN}
    assert names == {tracing.PREFIX + w for w in want}
    assert all(lo <= s <= e <= hi for _, _, s, e in events)


def test_tracing_on_and_off_bit_identical(monkeypatch, tmp_path):
    off, _ = _hybrid_run(monkeypatch)
    metrics.enable()
    on = {}
    _profiled(tmp_path, lambda: on.update(p=_hybrid_run(monkeypatch)[0]))
    assert metrics.counter_value("sparse.route.calls") > 0
    for a, b in zip(off, on["p"]):
        np.testing.assert_array_equal(a, b)


def test_hll_update_records_span_and_children():
    metrics.enable()
    sketch = HyperLogLog.empty(CFG).update(jnp.arange(64, dtype=jnp.int32))
    sketch = sketch.update(jnp.zeros((0,), jnp.int32))  # identity: no children
    c = metrics.snapshot()["counters"]
    assert c["hll.update.calls"] == 2
    assert c["hll.update.registers.calls"] == 1
    assert c["hll.update.counter.calls"] == 1
    assert c["hll.update.seconds"] >= c["hll.update.registers.seconds"]
    assert sketch.count == 64


def test_stopwatch_semantics():
    w = tracing.Stopwatch()
    assert not w.running
    with pytest.raises(AssertionError):
        w.elapsed()
    w.start()
    assert w.running and w.elapsed() >= 0
    dt = w.stop()
    assert dt >= 0 and not w.running


# ----------------------------------------------------------------------------
# formatting helpers (serve report lines)
# ----------------------------------------------------------------------------


def test_format_helpers():
    assert fmt_count(1234567) == "1,234,567"
    assert fmt_pct(0.6667) == "66.7%"
    assert fmt_seconds(0.0000012) == "1µs"
    assert fmt_seconds(0.0034) == "3.4ms"
    assert fmt_seconds(2.5) == "2.50s"
    assert fmt_rate(1.25e6, "tok") == "1,250,000 tok/s"
    assert fmt_bytes(3 * 1024**2) == "3.0MiB"
    assert kv_line("board", [("rows", 4), ("hit", "66.7%")]) == (
        "  board: rows=4 hit=66.7%"
    )
    note = truncated_note(3, 8, "requests")
    assert "+5 more requests" in note and "8 total" in note


def test_metrics_report_line_reads_snapshot():
    metrics.enable()
    _ingest(SketchBank.empty(4, CFG))
    metrics.observe("serve.request.seconds", 0.002)
    metrics.inc("window.fold_cache.hits", 2)
    metrics.inc("window.fold_cache.misses", 1)
    line = metrics_report_line(metrics.snapshot())
    assert line.startswith("[metrics]")
    assert "p50=" in line and "dispatches=" in line and "hit=66.7%" in line
