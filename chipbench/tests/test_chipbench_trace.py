"""The trace reduction, on a small trace recorded on the CPU in the test.

On the CPU, XLA runs each op on the client's host threads (``tf_XLA...``),
so the test reads those lines as the "device" (``devtrace.cpu_device_ops``,
which a CPU rehearsal reads too); a TPU run reads the ``XLA Ops`` lines.
Everything else is the code the harness runs.
"""

import time

import jax
import jax.numpy as jnp
import pytest
from jax.profiler import TraceAnnotation

from chipbench.bench import devtrace, harness

SLEEP_S = 0.03
ROUNDS = 3


@pytest.fixture(scope="module")
def recorded(tmp_path_factory):
    log_dir = str(tmp_path_factory.mktemp("trace"))
    f = jax.jit(lambda x: jnp.sort(x * 3.0 + 1.0))
    x = jnp.arange(1 << 20, dtype=jnp.float32)[::-1]
    f(x).block_until_ready()
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(log_dir, profiler_options=opts)
    with TraceAnnotation(devtrace.WINDOW):
        for _ in range(ROUNDS):
            with TraceAnnotation("submit"):
                time.sleep(SLEEP_S)  # host only: the device idles
            with TraceAnnotation("update"):
                f(x).block_until_ready()
    jax.profiler.stop_trace()
    return devtrace.load(log_dir, 1, device_ops=devtrace.cpu_device_ops)


def test_window_and_spans(recorded):
    assert recorded.window_s >= ROUNDS * SLEEP_S
    assert recorded.span_count("submit") == ROUNDS
    assert recorded.span_count("update") == ROUNDS
    for name in ("submit", "update"):
        for s, e in recorded.whole_spans(name):
            assert recorded.window[0] <= s <= e <= recorded.window[1]
    assert recorded.span_mean_s("submit") >= SLEEP_S
    assert recorded.span_mean_s("gen") is None


def test_spans_cut_by_the_window_are_not_counted():
    lo, hi = 1_000, 2_000
    t = devtrace.Trace(
        window=(lo, hi), busy=[[(1_100, 1_200), (1_900, 2_000)]], ops=[],
        spans=[("read", 900, 1_250), ("read", 1_300, 1_500), ("read", 1_850, 2_100)],
    )
    assert t.span_count("read") == 1
    assert t.span_mean_s("read") == pytest.approx(200e-9)


def test_busy_is_the_union_inside_the_window(recorded):
    busy = recorded.busy[0]
    assert busy, "no device op found"
    for (s0, e0), (s1, _) in zip(busy, busy[1:]):
        assert e0 < s1  # merged: disjoint and sorted
    assert 0 < recorded.busy_s < recorded.window_s - ROUNDS * SLEEP_S * 0.9
    # the ops ran inside the update spans, none while the host slept
    def within(name):
        spans = devtrace.union(recorded.whole_spans(name))
        return devtrace.total(devtrace.intersect(busy, spans)) * 1e-9

    assert within("update") == pytest.approx(recorded.busy_s, rel=0.05)
    assert within("submit") < 0.05 * recorded.busy_s
    idle = 1.0 - recorded.busy_s / recorded.window_s
    assert 0.0 < idle < 1.0


def test_gaps_are_named_after_the_host_span(recorded):
    gaps = recorded.gaps()
    assert gaps == sorted(gaps, key=lambda g: -g[1])
    top = gaps[:ROUNDS]
    assert [name for name, _ in top] == ["submit"] * ROUNDS
    for _, seconds in top:
        assert seconds >= SLEEP_S * 0.8
    total_gaps = sum(s for _, s in gaps)
    assert total_gaps == pytest.approx(recorded.window_s - recorded.busy_s, rel=1e-6)


def test_breakdown_shape(recorded):
    b = recorded.breakdown()
    assert set(b) == {"device_ops", "idle_gaps"}
    for key in b:
        assert 0 < len(b[key]) <= 10
        for name, seconds in b[key]:
            assert isinstance(name, str) and seconds > 0
    assert sum(s for _, s in b["device_ops"]) >= recorded.busy_s * 0.5


def test_interval_algebra():
    merged = devtrace.union([(5, 7), (0, 2), (1, 3), (3, 4), (9, 9)])
    assert merged == [(0, 4), (5, 7)]
    assert devtrace.complement(merged, -1, 10) == [(-1, 0), (4, 5), (7, 10)]
    assert devtrace.intersect(merged, [(2, 6)]) == [(2, 4), (5, 6)]
    assert devtrace.total(merged) == 6


def test_peaks_table_refuses_unknown_device():
    assert harness.peaks_for("TPU v5 lite")["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError):
        harness.peaks_for("TPU v99 imaginary")
