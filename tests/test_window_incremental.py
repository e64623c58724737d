"""Incremental window maintenance (DESIGN.md §14).

The contract under test: every cached or incrementally-maintained window
read is BIT-IDENTICAL to a cold full fold of the same ring — for random
interleavings of observe/advance/advance_to/estimate_window, for every
registered backend, and for rings resurrected through ``from_bytes``
(which drops the hidden state by construction).  Plus: the
``register_window_merge_backend`` axis (three built-in entries, jnp
fallback for plugins), the one-rebuild-per-W amortization schedule, hidden
state staying out of the pytree and out of jit traces, the shared
``last_k`` validation across all three window carriers, and the
``MultiResWindowedBank`` exponential histogram (dense-ring bit-identity
inside the horizon, slot-merge schedule invariants, RHLW v3).
"""

import dataclasses

import numpy as np
import jax
import jax.numpy as jnp
import pytest

from hypothesis_compat import example, given, st

from repro.sketch import (
    CMConfig,
    ExecutionPlan,
    HLLConfig,
    HybridWindowedBank,
    MultiResWindowedBank,
    SketchBank,
    WindowedBank,
    available_window_backends,
    available_window_merge_backends,
    estimate_many,
    get_window_merge_backend,
)
from repro.kernels.window_fold import window_merge_max
from repro.telemetry.sketchboard import StreamSketch

CFG = HLLConfig(p=6, hash_bits=64)  # small m so the pallas paths run


def _chunk(n, rows, seed):
    rng = np.random.default_rng(seed)
    keys = jnp.asarray(rng.integers(0, rows, n, dtype=np.int32))
    items = jnp.asarray(rng.integers(0, 2**31, n, dtype=np.int32))
    return keys, items


def _cold_fold(win, last_k):
    """The reference read: a fresh numpy fold of the ring, no caches."""
    ring = np.asarray(win.registers)
    mask = np.asarray(win._live_mask(last_k))
    acc = np.zeros(ring.shape[1:], ring.dtype)
    for w in range(ring.shape[0]):
        if mask[w]:
            acc = np.maximum(acc, ring[w])
    return acc, np.asarray(estimate_many(jnp.asarray(acc), CFG))


def _assert_reads_cold(win, plan, last_ks=None):
    """Every (cached, incremental) read equals the cold fold, twice over
    so the second read exercises the cache-hit path."""
    for last_k in last_ks or (win.window, max(1, win.window // 2), 1):
        ref_regs, ref_est = _cold_fold(win, last_k)
        for _ in range(2):
            regs = np.asarray(win._fold_registers(last_k, plan))
            np.testing.assert_array_equal(regs, ref_regs)
            est = np.asarray(win.estimate_window(last_k, plan))
            np.testing.assert_array_equal(est, ref_est)


# ----------------------------------------------------------------------------
# the register_window_merge_backend axis
# ----------------------------------------------------------------------------


def test_merge_backends_registered():
    assert set(available_window_merge_backends()) >= {
        "jnp",
        "pallas",
        "pallas_pipelined",
    }


def test_unknown_merge_backend_falls_back_to_jnp():
    # plugins registered only for flat updates still get full-window
    # reads: the merge axis degrades to the jnp fold instead of raising
    assert get_window_merge_backend("definitely_not_registered") is (
        get_window_merge_backend("jnp")
    )


@pytest.mark.parametrize("k", [1, 2, 3, 5])
def test_window_merge_kernel_matches_jnp(k):
    rng = np.random.default_rng(k)
    parts = jnp.asarray(rng.integers(0, 60, (k, 8, CFG.m), dtype=np.int32))
    got = window_merge_max(parts, m=CFG.m, row_block=4, interpret=True)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(parts).max(0))


@pytest.mark.parametrize("backend", available_window_backends())
def test_merge_backend_equals_stack_max(backend):
    rng = np.random.default_rng(7)
    parts = jnp.asarray(rng.integers(0, 60, (3, 9, CFG.m), dtype=np.int32))
    plan = ExecutionPlan(backend=backend).validate()
    got = get_window_merge_backend(backend)(parts, CFG, plan)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(parts).max(0))


# ----------------------------------------------------------------------------
# cache/state coherence: incremental reads == cold folds, always
# ----------------------------------------------------------------------------


@pytest.mark.parametrize("backend", available_window_backends())
def test_random_walk_reads_bit_identical(backend):
    plan = ExecutionPlan(backend=backend, pipelines=3)
    rng = np.random.default_rng(42)
    win = WindowedBank.empty(6, 9, CFG)
    for step in range(48):
        op = rng.integers(0, 5)
        if op <= 1:
            keys, items = _chunk(int(rng.integers(1, 300)), 9, int(step))
            win = win.observe(keys, items, plan)
        elif op == 2:
            win = win.advance()
        elif op == 3:
            win = win.advance(int(rng.integers(2, 4)))
        else:
            win = win.advance_to(win.epoch + int(rng.integers(1, 9)))
        _assert_reads_cold(win, plan)


@given(ops=st.lists(st.integers(min_value=0, max_value=9), max_size=24))
@example(ops=[0])
def test_random_walk_reads_bit_identical_property(ops):
    plan = ExecutionPlan(backend="jnp")
    win = WindowedBank.empty(4, 5, CFG)
    for i, op in enumerate(ops):
        if op <= 4:
            keys, items = _chunk(40 + op, 5, i)
            win = win.observe(keys, items, plan)
        elif op <= 7:
            win = win.advance()
        else:
            win = win.advance_to(win.epoch + op)
        _assert_reads_cold(win, plan, last_ks=(4, 2, 1))


@pytest.mark.parametrize("backend", available_window_backends())
def test_from_bytes_ring_reads_bit_identical(backend):
    plan = ExecutionPlan(backend=backend)
    win = WindowedBank.empty(5, 7, CFG)
    for e in range(7):
        if e:
            win = win.advance()
        win = win.observe(*_chunk(200, 7, seed=e), plan)
        win.estimate_window(plan=plan)  # prime the hidden state + cache
    back = WindowedBank.from_bytes(win.to_bytes())
    # the resurrected ring starts stateless; both must read identically
    # through further lockstep mutation
    for e in range(7):
        keys, items = _chunk(150, 7, seed=100 + e)
        win = win.advance().observe(keys, items, plan)
        back = back.advance().observe(keys, items, plan)
        _assert_reads_cold(back, plan)
        np.testing.assert_array_equal(
            np.asarray(win.estimate_window(plan=plan)),
            np.asarray(back.estimate_window(plan=plan)),
        )


def test_replayed_estimates_match_original_run():
    # the exact sequence a dashboard runs: interleaved ingest/rotation with
    # a read per epoch; replaying the stream on a fresh ring must reproduce
    # every reading bit-for-bit even though the original run answered from
    # the incremental path and the replay from cold folds
    plan = ExecutionPlan(backend="jnp")
    readings = []
    win = WindowedBank.empty(4, 6, CFG)
    for e in range(12):
        win = win.observe(*_chunk(120, 6, seed=e), plan)
        readings.append(np.asarray(win.estimate_window(plan=plan)))
        win = win.advance()
    replay = WindowedBank.empty(4, 6, CFG)
    for e in range(12):
        replay = replay.observe(*_chunk(120, 6, seed=e), plan)
        ref_regs, ref_est = _cold_fold(replay, 4)
        np.testing.assert_array_equal(readings[e], ref_est)
        replay = replay.advance()


# ----------------------------------------------------------------------------
# the amortization schedule and pytree/jit hygiene
# ----------------------------------------------------------------------------


def test_prefix_rebuilds_once_per_window(monkeypatch):
    calls = []
    orig = WindowedBank._rebuild_suffix

    def counted(self):
        calls.append(1)
        return orig(self)

    monkeypatch.setattr(WindowedBank, "_rebuild_suffix", counted)
    window, epochs = 8, 64
    win = WindowedBank.empty(window, 4, CFG)
    for e in range(epochs):
        win = win.observe(*_chunk(50, 4, seed=e))
        win.estimate_window()  # full-window read every epoch
        win = win.advance()
    # steady state costs ONE O(W) rebuild per W rotations (the O(1)
    # amortized bound); allow the warmup rebuild on top
    assert len(calls) <= epochs // window + 2
    assert len(calls) >= epochs // window


def test_hidden_state_stays_out_of_the_pytree():
    win = WindowedBank.empty(4, 3, CFG)
    win = win.observe(*_chunk(100, 3, seed=0))
    win.estimate_window()
    win = win.advance()
    win.estimate_window()
    assert "_inc" in win.__dict__ and "_fold_cache" in win.__dict__
    assert len(jax.tree_util.tree_leaves(win)) == 4
    # flatten/unflatten (what jit does at the boundary) drops the state
    leaves, treedef = jax.tree_util.tree_flatten(win)
    rebuilt = jax.tree_util.tree_unflatten(treedef, leaves)
    assert "_inc" not in rebuilt.__dict__
    assert "_fold_cache" not in rebuilt.__dict__
    _assert_reads_cold(rebuilt, ExecutionPlan(backend="jnp"))


def test_closure_captured_ring_is_jit_safe():
    # regression: a CONCRETE ring captured in someone else's jit closure
    # sees its ops bound to the active trace, so the state machinery must
    # stand down even though every pytree leaf looks concrete
    win = WindowedBank.empty(4, 3, CFG)
    win = win.observe(*_chunk(80, 3, seed=1))
    win.estimate_window()  # prime hidden state on the captured instance
    win = win.advance()

    out = jax.jit(lambda k, it: win.observe(k, it))(*_chunk(60, 3, seed=2))
    assert "_inc" not in out.__dict__ and "_fold_cache" not in out.__dict__
    _assert_reads_cold(out, ExecutionPlan(backend="jnp"))

    est = jax.jit(lambda _: win.estimate_window())(0)
    np.testing.assert_array_equal(np.asarray(est), _cold_fold(win, 4)[1])
    # and nothing traced leaked into the instance caches
    for cached in win.__dict__.get("_fold_cache", {}).values():
        assert not isinstance(cached, jax.core.Tracer)


def test_trace_context_does_not_poison_multires_cache():
    mr = MultiResWindowedBank.empty(2, 3, CFG, levels=2)
    mr = mr.observe(*_chunk(90, 3, seed=3)).advance()
    mr = mr.observe(*_chunk(90, 3, seed=4))
    eager = np.asarray(mr.estimate_window())
    traced = jax.jit(lambda _: mr.estimate_window())(0)
    np.testing.assert_array_equal(np.asarray(traced), eager)
    for cached in mr.__dict__.get("_fold_cache", {}).values():
        assert not isinstance(cached, jax.core.Tracer)


# ----------------------------------------------------------------------------
# shared last_k validation (one helper, one message, three carriers)
# ----------------------------------------------------------------------------


def test_last_k_validation_identical_across_carriers():
    carriers = [
        WindowedBank.empty(4, 3, CFG),
        HybridWindowedBank.empty(4, 3, CFG),
        MultiResWindowedBank.empty(4, 3, CFG, levels=1),  # horizon == 4
    ]
    for bad in (0, -1, 5, 99):
        messages = set()
        for car in carriers:
            with pytest.raises(ValueError) as exc:
                car.estimate_window(bad)
            messages.add(str(exc.value))
        # the deduplicated helper guarantees ONE message, not three copies
        assert messages == {f"last_k must be in [1, 4], got {bad}"}


def test_window_counts_identical_dense_vs_hybrid():
    dense = WindowedBank.empty(4, 5, CFG)
    hybrid = HybridWindowedBank.empty(4, 5, CFG)
    for e in range(6):
        if e:
            dense, hybrid = dense.advance(), hybrid.advance()
        keys, items = _chunk(100, 5, seed=e)
        dense = dense.observe(keys, items)
        hybrid = hybrid.observe(keys, items)
    for last_k in (1, 2, 4):
        np.testing.assert_array_equal(
            dense.window_counts(last_k), hybrid.window_counts(last_k)
        )


# ----------------------------------------------------------------------------
# MultiResWindowedBank: the exponential-histogram ring
# ----------------------------------------------------------------------------


@pytest.mark.parametrize("backend", available_window_backends())
def test_multires_matches_dense_ring_inside_horizon(backend):
    plan = ExecutionPlan(backend=backend)
    base, levels = 2, 3  # horizon = 2 * (2**3 - 1) = 14
    mr = MultiResWindowedBank.empty(base, 3, CFG, levels=levels)
    dense = WindowedBank.empty(mr.horizon, 3, CFG)
    for e in range(9):  # stays inside the horizon: nothing expires
        if e:
            mr, dense = mr.advance(), dense.advance()
        keys, items = _chunk(130, 3, seed=e)
        mr = mr.observe(keys, items, plan)
        dense = dense.observe(keys, items, plan)
    # a full-horizon query covers every epoch on both carriers, and the
    # EH buckets partition the same registers the dense ring holds
    np.testing.assert_array_equal(
        np.asarray(mr.fold_window(plan=plan).registers),
        np.asarray(dense.fold_window(plan=plan).registers),
    )
    np.testing.assert_array_equal(
        np.asarray(mr.estimate_window(plan=plan)),
        np.asarray(dense.estimate_window(plan=plan)),
    )
    np.testing.assert_array_equal(
        mr.window_counts(), dense.window_counts()
    )


def test_multires_slot_bound_and_schedule_invariants():
    base, levels = 2, 3
    mr = MultiResWindowedBank.empty(base, 2, CFG, levels=levels)
    for e in range(64):
        mr = mr.observe(*_chunk(30, 2, seed=e)).advance()
        assert mr.slots <= 1 + base * levels
        sizes = [b.size for b in mr.closed]  # newest first
        assert all(s & (s - 1) == 0 for s in sizes)
        assert sizes == sorted(sizes)  # non-decreasing toward the old end
        per_level = mr.density()["buckets_per_size"]
        assert all(n <= base for n in per_level.values())
        # labels are strictly older going down the list, never overlapping
        for newer, older in zip(mr.closed, mr.closed[1:]):
            assert newer.start > older.end
        # nothing outlives the horizon
        assert all(b.end > mr.epoch - mr.horizon for b in mr.closed)


def test_multires_empty_epochs_cost_no_slots():
    mr = MultiResWindowedBank.empty(2, 2, CFG, levels=2)
    mr = mr.observe(*_chunk(50, 2, seed=0))
    mr = mr.advance_to(40)  # one occupied epoch, then a long quiet gap
    assert mr.slots <= 2  # current + at most the one occupied bucket
    assert mr.epoch == 40


def test_multires_estimates_cover_rounded_window():
    # after coarsening, a short-suffix query answers over a SUPERSET of
    # the asked window (rounded up to bucket edges): its estimate can
    # only be >= the current-bucket-only reading, and the full-horizon
    # read is exact over everything retained
    mr = MultiResWindowedBank.empty(1, 2, CFG, levels=3)
    for e in range(7):
        mr = mr.observe(*_chunk(80, 2, seed=e)).advance()
    short = np.asarray(mr.estimate_window(1))
    full = np.asarray(mr.estimate_window())
    assert np.all(full >= short)


def test_multires_validates_shape():
    with pytest.raises(ValueError, match="at least one bucket"):
        MultiResWindowedBank.empty(0, 2, CFG)
    with pytest.raises(ValueError, match="levels must be in"):
        MultiResWindowedBank.empty(2, 2, CFG, levels=0)
    with pytest.raises(ValueError, match="levels must be in"):
        MultiResWindowedBank.empty(2, 2, CFG, levels=99)
    with pytest.raises(ValueError, match="overflows int32"):
        MultiResWindowedBank.empty(1 << 20, 2, CFG, levels=12)
    with pytest.raises(ValueError, match="at least one row"):
        MultiResWindowedBank.empty(2, 0, CFG)


def test_rhlw_v3_roundtrip():
    mr = MultiResWindowedBank.empty(2, 3, CFG, levels=3)
    for e in range(11):
        mr = mr.observe(*_chunk(120, 3, seed=e)).advance()
    mr = mr.observe(*_chunk(60, 3, seed=99))
    back = MultiResWindowedBank.from_bytes(mr.to_bytes())
    assert (back.epoch, back.base, back.levels) == (
        mr.epoch,
        mr.base,
        mr.levels,
    )
    assert [(b.start, b.end, b.size) for b in back.closed] == [
        (b.start, b.end, b.size) for b in mr.closed
    ]
    np.testing.assert_array_equal(
        np.asarray(back.fold_window().registers),
        np.asarray(mr.fold_window().registers),
    )
    np.testing.assert_array_equal(back.window_counts(), mr.window_counts())


def test_rhlw_v3_cross_version_rejection():
    mr = MultiResWindowedBank.empty(2, 3, CFG, levels=2)
    mr = mr.observe(*_chunk(60, 3, seed=0))
    blob = mr.to_bytes()
    with pytest.raises(ValueError, match="MultiResWindowedBank.from_bytes"):
        WindowedBank.from_bytes(blob)
    dense = WindowedBank.empty(4, 3, CFG).to_bytes()
    with pytest.raises(ValueError, match="unsupported window version"):
        MultiResWindowedBank.from_bytes(dense)
    with pytest.raises(ValueError, match="bad magic"):
        MultiResWindowedBank.from_bytes(b"XXXX" + blob[4:])


@pytest.mark.parametrize("frac", [0.2, 0.6, 0.95])
def test_rhlw_v3_rejects_truncation(frac):
    mr = MultiResWindowedBank.empty(2, 3, CFG, levels=2)
    for e in range(5):
        mr = mr.observe(*_chunk(80, 3, seed=e)).advance()
    blob = mr.to_bytes()
    with pytest.raises(ValueError):
        MultiResWindowedBank.from_bytes(blob[: int(len(blob) * frac)])


def test_rhlw_v3_rejects_corrupt_labels():
    mr = MultiResWindowedBank.empty(2, 3, CFG, levels=2)
    for e in range(6):
        mr = mr.observe(*_chunk(80, 3, seed=e)).advance()
    mr = mr.observe(*_chunk(40, 3, seed=9))
    # tamper the size field of the oldest bucket's label to a non-power-
    # of-two: the parser must refuse to resurrect a broken schedule
    import struct as _struct

    blob = bytearray(mr.to_bytes())
    header, base_sz = 28, 4
    bucket_sz = 12 + (20 + 3 * 8 + 3 * CFG.m)
    off = header + base_sz + (mr.slots - 1) * bucket_sz
    start, end, _size = _struct.unpack_from("<iiI", blob, off)
    _struct.pack_into("<iiI", blob, off, start, end, 3)
    with pytest.raises(ValueError, match="slot-merge schedule"):
        MultiResWindowedBank.from_bytes(bytes(blob))


# ----------------------------------------------------------------------------
# StreamSketch integration (window_levels)
# ----------------------------------------------------------------------------


def test_board_window_levels_reports_and_roundtrips():
    board = StreamSketch(cfg=CFG, window=2, window_levels=3)
    rng = np.random.default_rng(5)
    for _ in range(10):
        for name in ("api", "cdn"):
            board.observe(
                name, jnp.asarray(rng.integers(0, 2**31, 300, dtype=np.int32))
            )
        board.advance()
    assert isinstance(board._wbank, MultiResWindowedBank)
    assert board._wbank.horizon == 2 * (2**3 - 1)
    rep = board.report()
    assert set(rep) == {"api", "cdn"}
    assert all(v["estimate"] > 0 for v in rep.values())
    back = MultiResWindowedBank.from_bytes(board.window_bytes())
    np.testing.assert_array_equal(
        np.asarray(back.fold_window().registers),
        np.asarray(board._wbank.fold_window().registers),
    )


def test_board_window_levels_guards():
    with pytest.raises(ValueError, match="needs a windowed board"):
        StreamSketch(cfg=CFG, window_levels=2)
    with pytest.raises(ValueError, match="at least one level"):
        StreamSketch(cfg=CFG, window=4, window_levels=0)
    with pytest.raises(ValueError, match="cannot combine with track_topk"):
        StreamSketch(
            cfg=CFG,
            window=4,
            window_levels=2,
            track_topk=CMConfig(depth=2, width=64),
        )
