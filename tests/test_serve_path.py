"""Production serve path (DESIGN.md §16): sharding, coalescing, bugfix pins.

Three families:

* differential — the same op grammar as tests/test_differential.py driven
  once under ``make_plans`` (local) and once under ``make_sharded_plans``
  (row-sharded over this process's devices), asserting canonical state
  and estimates bit-identical for every registered backend.  A
  subprocess leg forces 4 host devices so the block-local key re-basing
  and phantom-row padding run against REAL shards, not a 1-device mesh.
* coalescer — N interleaved per-tenant submits drained as one merged
  batch must land bit-for-bit with per-batch ingest (§6 lattice laws),
  plus the queue's edge semantics (empty drain, length validation,
  host-carrier routing, staging-ring rotation, shared window rings).
* serve-loop pins — the three launcher bugs this PR fixes stay fixed:
  zero-elapsed spans format instead of raising, empty decode slices do
  not expire the prompt epoch at W > T, and --report-every 0 means
  "snapshot at exit only".
"""

import os
import subprocess
import sys
import textwrap

import numpy as np
import pytest

import jax

from repro.obs import tracing
from repro.obs.format import fmt_count, fmt_rate, per_second
from repro.serve.coalesce import (
    CoalescingQueue,
    DoubleBuffer,
    SharedWindowRing,
)
from repro.sketch import (
    HLLConfig,
    HybridBank,
    SketchBank,
    WindowedBank,
    available_bank_backends,
    available_window_backends,
)

from tests.reference_model import (
    DenseBankSUT,
    DenseWindowSUT,
    HybridBankSUT,
    gen_ops,
    make_plans,
    make_sharded_plans,
    run_ops,
)

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CFG = HLLConfig(p=8, hash_bits=64)


# ----------------------------------------------------------------------------
# differential: sharded placement is invisible to every read
# ----------------------------------------------------------------------------


@pytest.mark.parametrize("backend", available_bank_backends())
@pytest.mark.parametrize("kind", ["dense", "hybrid", "window"], ids=str)
def test_sharded_placement_bit_identical_to_local(kind, backend):
    """One op sequence, two placements, identical canonical state."""
    if kind == "window" and backend not in available_window_backends():
        pytest.skip(f"{backend!r} has no window fold path")
    sut_cls = {
        "dense": DenseBankSUT,
        "hybrid": HybridBankSUT,
        "window": DenseWindowSUT,
    }[kind]
    local = make_plans([backend])[backend]
    sharded = make_sharded_plans([backend])[backend]
    # 37 rows: does not divide any shard count > 1, so the forced-device
    # subprocess leg exercises the phantom-row padding path too
    rows, window = 37, 3
    ops = gen_ops(
        np.random.default_rng(20260808), rows, 12, windowed=(kind == "window")
    )

    def build(plan):
        if kind == "window":
            return sut_cls(window, rows, CFG, plan=plan)
        return sut_cls(rows, CFG, plan=plan, threshold=4)

    a, b = build(local), build(sharded)
    for op in ops:
        for sut in (a, b):
            run_ops([op], sut, _NullOracle())
        if op[0] == "estimate":
            np.testing.assert_array_equal(
                a.estimates(), b.estimates(), err_msg=f"{kind}/{backend}"
            )
    for got, want in zip(b.canonical(), a.canonical()):
        np.testing.assert_array_equal(got, want, err_msg=f"{kind}/{backend}")


class _NullOracle:
    """run_ops needs an oracle; the differential pair checks itself."""

    def __init__(self, rows=0):
        self.rows = rows

    def update(self, keys, items):
        pass

    def merge(self, other):
        pass

    def advance(self, steps=1):
        pass


@pytest.mark.slow
def test_sharded_routing_on_real_multi_device_mesh():
    """4 forced host devices: cross-block key routing must stay exact.

    Runs in a subprocess because the device count must be pinned before
    jax initializes.  B=37 does not divide 4, so phantom-row padding and
    the §9 drop rule both run against real shards.
    """
    code = """
        import numpy as np
        import jax
        assert jax.device_count() == 4, jax.device_count()
        from repro.launch.mesh import make_auto_mesh
        from repro.sketch import ExecutionPlan, HLLConfig, SketchBank

        cfg = HLLConfig(p=8, hash_bits=64)
        mesh = make_auto_mesh((4,), ("data",))
        rng = np.random.default_rng(0)
        for backend in ("jnp", "pallas"):
            local = ExecutionPlan(backend=backend)
            sharded = local.with_sharding(mesh)
            keys = rng.integers(-2, 40, 512).astype(np.int32)
            items = rng.integers(0, 1 << 20, 512).astype(np.int32)
            ref = SketchBank.empty(37, cfg).update_many(keys, items, local)
            got = SketchBank.empty(37, cfg).update_many(keys, items, sharded)
            np.testing.assert_array_equal(
                np.asarray(ref.registers), np.asarray(got.registers), backend
            )
            np.testing.assert_array_equal(ref.counts, got.counts)
            np.testing.assert_array_equal(
                np.asarray(ref.estimate_many()),
                np.asarray(got.estimate_many(plan=sharded)),
            )
        print("OK")
    """
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    env["PYTHONPATH"] = os.path.join(REPO, "src")
    out = subprocess.run(
        [sys.executable, "-c", textwrap.dedent(code)],
        capture_output=True,
        text=True,
        env=env,
        timeout=600,
    )
    assert out.returncode == 0, out.stderr[-3000:]
    assert "OK" in out.stdout


# ----------------------------------------------------------------------------
# coalescer: merged ticks are pure batching
# ----------------------------------------------------------------------------


def test_coalesced_tick_matches_per_batch_ingest_bit_for_bit():
    """N interleaved tenant submits == one merged update_many."""
    rng = np.random.default_rng(1)
    rows = 16
    batches = [
        (
            rng.integers(0, rows, n).astype(np.int32),
            rng.integers(0, 1 << 20, n).astype(np.int32),
        )
        for n in (5, 1, 33, 17, 8)
    ]
    ref = SketchBank.empty(rows, CFG)
    for keys, items in batches:
        ref = ref.update_many(keys, items)

    queue = CoalescingQueue()
    for keys, items in batches:
        queue.submit(keys, items)
    assert queue.pending_batches() == len(batches)
    assert queue.pending_items() == sum(k.shape[0] for k, _ in batches)
    got = queue.flush_into(SketchBank.empty(rows, CFG))
    assert queue.pending_batches() == 0

    np.testing.assert_array_equal(np.asarray(ref.registers), np.asarray(got.registers))
    np.testing.assert_array_equal(ref.counts, got.counts)


def test_coalescer_host_routes_hybrid_carrier():
    """HybridBank ingests the merged batch on host (append-buffer path)."""
    rng = np.random.default_rng(2)
    rows = 8
    keys = rng.integers(0, rows, 64).astype(np.int32)
    items = rng.integers(0, 50, 64).astype(np.int32)
    ref = HybridBank.empty(rows, CFG, threshold=4).update_many(keys, items)

    queue = CoalescingQueue()
    queue.submit(keys[:40], items[:40])
    queue.submit(keys[40:], items[40:])
    got = queue.flush_into(HybridBank.empty(rows, CFG, threshold=4))

    ref, got = ref.compact(), got.compact()
    np.testing.assert_array_equal(
        np.asarray(ref.to_dense().registers),
        np.asarray(got.to_dense().registers),
    )
    np.testing.assert_array_equal(ref.counts, got.counts)
    np.testing.assert_array_equal(ref.modes, got.modes)


def test_coalescer_edge_semantics():
    queue = CoalescingQueue()
    assert queue.drain() is None  # a tick with no traffic dispatches nothing
    bank = SketchBank.empty(4, CFG)
    assert queue.flush_into(bank) is bank
    with pytest.raises(ValueError, match="same length"):
        queue.submit(np.arange(3), np.arange(4))
    assert queue.submit(np.empty(0, np.int32), np.empty(0, np.int32)) == 0
    assert queue.pending_batches() == 0  # empty submits are not queued
    queue.submit_row(2, np.arange(5))
    keys, items = queue.drain(stage=False)
    np.testing.assert_array_equal(keys, np.full(5, 2, np.int32))
    np.testing.assert_array_equal(items, np.arange(5))


def test_double_buffer_rotates_and_pins_in_flight_slots():
    buf = DoubleBuffer()
    assert buf.depth == 2
    with pytest.raises(ValueError, match="2 slots"):
        DoubleBuffer(depth=1)
    a = buf.stage(np.arange(4))
    b = buf.stage(np.arange(8))
    # both in-flight batches stay pinned by the ring; the third stage
    # overwrites the oldest slot only
    assert buf._slots[0] is a and buf._slots[1] is b
    c = buf.stage(np.arange(2))
    assert buf._slots[0] is c and buf._slots[1] is b
    np.testing.assert_array_equal(np.asarray(c[0]), np.arange(2))
    assert isinstance(c[0], jax.Array)


def test_shared_window_ring_reuses_and_swaps():
    SharedWindowRing.reset()
    try:
        key = ("test", 0, 2, 4, CFG)
        built = []
        factory = lambda: built.append(1) or WindowedBank.empty(2, 4, CFG)
        ring = SharedWindowRing.get_or_create(key, factory)
        again = SharedWindowRing.get_or_create(key, factory)
        assert again is ring and built == [1]  # factory ran exactly once
        advanced = ring.advance()
        assert SharedWindowRing.swap(key, advanced) is advanced
        assert SharedWindowRing.get_or_create(key, factory) is advanced
        assert built == [1]
    finally:
        SharedWindowRing.reset()


# ----------------------------------------------------------------------------
# serve-loop pins: the three launcher bugs stay fixed
# ----------------------------------------------------------------------------


def test_zero_elapsed_span_formats_instead_of_raising(monkeypatch):
    """A time quantized to 0.0s must yield a printable rate, not a crash."""
    monkeypatch.setattr(tracing.time, "perf_counter", lambda: 1234.5)
    watch = tracing.Stopwatch()
    watch.start()
    elapsed_s = watch.stop()
    assert elapsed_s == 0.0
    # the exact serve.py report seam: fmt_rate(per_second(work, elapsed))
    assert fmt_rate(per_second(2048, elapsed_s), "tok") == "inf tok/s"
    assert per_second(0, elapsed_s) == 0.0
    assert per_second(-0.0, 0.0) == 0.0
    assert fmt_count(float("inf")) == "inf"
    assert fmt_count(float("-inf")) == "-inf"
    assert fmt_count(float("nan")) == "nan"


def test_empty_decode_slices_do_not_expire_prompt_epoch():
    """W > T: array_split's token-less tail slices must not advance.

    The serve loop splits T decode steps into W window slices; when
    --gen-len < --window-epochs the tail slices are empty.  Rotating on
    them expired the prompt epoch after fewer than W real slices — the
    rolling distinct count silently dropped the whole prompt.
    """
    W, B, S, T = 6, 3, 40, 2  # W > T: 4 of the 6 slices are empty
    rng = np.random.default_rng(3)
    # disjoint value ranges so prompt-vs-decode attribution is exact
    prompts = rng.integers(1 << 10, 1 << 20, (B, S)).astype(np.int32)
    out = rng.integers(0, 8, (B, T)).astype(np.int32)
    rows = np.broadcast_to(np.arange(B, dtype=np.int32)[:, None], (B, S))

    win = WindowedBank.empty(W, B, CFG).observe(rows, prompts)
    advances = 0
    for chunk in np.array_split(out, W, axis=1):
        if chunk.shape[1] == 0:
            continue  # the serve.py guard under test
        win = win.advance()
        advances += 1
        keys = np.broadcast_to(np.arange(B, dtype=np.int32)[:, None], chunk.shape)
        win = win.observe(keys, chunk)
    assert advances == T  # only REAL decode slices rotate the ring
    # prompt epoch alive: rolling window still counts the prompt tokens
    rolling = np.asarray(win.estimate_window())
    floor = 0.5 * S  # far above anything T<=2 decode tokens can explain
    assert (rolling > floor).all(), rolling
    # regression shape: advancing on every split slice expires the prompt
    bad = WindowedBank.empty(W, B, CFG).observe(rows, prompts)
    for chunk in np.array_split(out, W, axis=1):
        bad = bad.advance()
        if chunk.shape[1]:
            keys = np.broadcast_to(np.arange(B, dtype=np.int32)[:, None], chunk.shape)
            bad = bad.observe(keys, chunk)
    assert (np.asarray(bad.estimate_window()) < floor).all()


@pytest.mark.slow
def test_serve_launcher_end_to_end_sharded_report_every_zero(tmp_path):
    """The full launcher under the new flags: --placement sharded plus
    --report-every 0 must emit no periodic [metrics] lines (previously 0
    was clamped to every-request) while still writing the exit snapshot."""
    metrics_out = tmp_path / "metrics.json"
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(REPO, "src")
    out = subprocess.run(
        [
            sys.executable,
            "-m",
            "repro.launch.serve",
            "--requests",
            "4",
            "--prompt-len",
            "16",
            "--gen-len",
            "2",
            "--window-epochs",
            "4",
            "--placement",
            "sharded",
            "--report-every",
            "0",
            "--metrics-out",
            str(metrics_out),
        ],
        capture_output=True,
        text=True,
        env=env,
        timeout=600,
    )
    assert out.returncode == 0, out.stderr[-3000:]
    assert "[metrics]" not in out.stdout  # report-every 0: exit-only
    assert metrics_out.exists()
    import json

    snap = json.loads(metrics_out.read_text())
    assert snap["counters"]["serve.coalesce.ticks"] >= 1
    assert snap["counters"]["serve.coalesce.submitted"] >= 4
    assert snap["histograms"]["serve.request.seconds"]["count"] == 4
