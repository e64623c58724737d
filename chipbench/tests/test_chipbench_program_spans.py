"""The program's own spans beside the harness's, and the readers of their
counters.

A trace recorded on the CPU holds ``repro/<layer>.<phase>`` host events
(``repro.obs.tracing.span`` with the registry on) inside the harness's
spans.  The trace reduction and every reader it feeds must give the same
numbers whether or not those events are in the trace; the readers of the
program's counters must give the hand-computed values, and ``None`` where
a counter is missing (a program without the spans).
"""

import time
import types

import jax
import jax.numpy as jnp
import pytest
from jax.profiler import TraceAnnotation

from chipbench.bench import devtrace, harness

SLEEP_S = 0.02
ROUNDS = 3
PROGRAM = "repro/"


@pytest.fixture(scope="module")
def log_dir(tmp_path_factory):
    from repro.obs import metrics, tracing

    log_dir = str(tmp_path_factory.mktemp("trace"))
    f = jax.jit(lambda x: jnp.sort(x * 3.0 + 1.0))
    x = jnp.arange(1 << 18, dtype=jnp.float32)[::-1]
    f(x).block_until_ready()
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    metrics.reset()
    metrics.enable()
    try:
        jax.profiler.start_trace(log_dir, profiler_options=opts)
        with TraceAnnotation(devtrace.WINDOW):
            for _ in range(ROUNDS):
                with TraceAnnotation("submit"), tracing.span("sparse.route"):
                    time.sleep(SLEEP_S)
                with TraceAnnotation("update"), tracing.span("hll.update"):
                    f(x).block_until_ready()
        jax.profiler.stop_trace()
    finally:
        metrics.disable()
        metrics.reset()
    return log_dir


class _Filtered:
    """A ``ProfileData`` stand-in without the program's events."""

    def __init__(self, profile):
        self.planes = [
            types.SimpleNamespace(
                name=plane.name,
                lines=[
                    types.SimpleNamespace(
                        name=line.name,
                        events=[
                            types.SimpleNamespace(
                                name=ev.name,
                                start_ns=ev.start_ns,
                                duration_ns=ev.duration_ns,
                            )
                            for ev in line.events
                            if not ev.name.startswith(PROGRAM)
                        ],
                    )
                    for line in plane.lines
                ],
            )
            for plane in profile.planes
        ]


def _load(log_dir, without_program, monkeypatch):
    if without_program:
        real = jax.profiler.ProfileData
        monkeypatch.setattr(
            jax.profiler,
            "ProfileData",
            types.SimpleNamespace(from_file=lambda p: _Filtered(real.from_file(p))),
        )
    try:
        return devtrace.load(log_dir, 1, device_ops=devtrace.cpu_device_ops)
    finally:
        monkeypatch.undo()


def _program_events(log_dir):
    profile = jax.profiler.ProfileData.from_file(devtrace.xplane_file(log_dir))
    return [
        ev.name
        for plane in profile.planes
        if plane.name.startswith("/host:")
        for line in plane.lines
        for ev in line.events
        if ev.name.startswith(PROGRAM)
    ]


def _readings(trace):
    ctx = types.SimpleNamespace(
        trace=trace,
        counters={},
        compiles=0,
        counts={"ticks": ROUNDS, "flush_s": [0.1], "calls": ROUNDS,
                "chunk_items": 1 << 18},
        config={"p": 16},
        traffic={},
        peaks=harness.peaks_for("TPU v5 lite"),
    )
    names = ("device_idle.ingest", "submit_us.ingest", "update_roofline.paper")
    return {name: harness.read_metric(name, ctx) for name in names}


def test_the_trace_holds_program_spans(log_dir):
    names = _program_events(log_dir)
    assert names.count(PROGRAM + "sparse.route") == ROUNDS
    assert names.count(PROGRAM + "hll.update") == ROUNDS


def test_reduction_is_the_same_with_and_without_program_spans(log_dir, monkeypatch):
    with_program = _load(log_dir, False, monkeypatch)
    without = _load(log_dir, True, monkeypatch)
    assert with_program.spans == without.spans
    assert with_program.busy == without.busy
    assert with_program.breakdown() == without.breakdown()
    # gaps stay named after the harness's spans, never the program's
    assert {n for n, _ in with_program.gaps()} <= set(devtrace.SPANS) | {
        devtrace.NO_SPAN
    }
    readings = _readings(with_program)
    assert readings == _readings(without)
    assert all(v is not None for v in readings.values())


def _ctx(counters, **counts):
    return types.SimpleNamespace(counters=counters, counts=counts, trace=None)


NEW = (
    "route_ms.ingest",
    "dense_ms.ingest",
    "compact_ms.ingest",
    "readback_mib.ingest",
    "update_host_us.paper",
)


@pytest.mark.parametrize("name", NEW)
def test_new_readers_give_none_without_their_counters(name):
    assert harness.read_metric(name, _ctx({}, ticks=4, calls=4)) is None
    assert harness.read_metric(name, _ctx({})) is None


def test_new_readers_hand_computed():
    counters = {
        "sparse.route.seconds": 2.0,
        "sparse.dense.seconds": 6.0,
        "sparse.compact.pressure.seconds": 1.5,
        "sparse.compact.read.seconds": 0.5,
        "transfer.d2h_bytes": 8 * 2**20,
        "hll.update.seconds": 0.03,
        "hll.update.calls": 10,
    }
    ctx = _ctx(counters, ticks=4, flush_s=[1.0] * 4)
    got = {name: harness.read_metric(name, ctx) for name in NEW}
    assert got == pytest.approx({
        "route_ms.ingest": 500.0,
        "dense_ms.ingest": 1500.0,
        "compact_ms.ingest": 500.0,
        "readback_mib.ingest": 2.0,
        "update_host_us.paper": 3000.0,
    })
    # a window with pressure compactions only still reads
    only = {"sparse.compact.pressure.seconds": 1.0}
    assert harness.read_metric("compact_ms.ingest", _ctx(only, ticks=2)) == 500.0
    # the tick readers need ticks: a paper cell's counts give none
    assert harness.read_metric("route_ms.ingest", _ctx(counters, calls=4)) is None
