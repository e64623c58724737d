"""HybridBank: sparse rows, dense promotion, RHLB/RHLW v2, density stats.

Acceptance properties for the sparse subsystem (DESIGN.md §12):

* hybrid ingest under EVERY registered bank backend materializes to
  registers bit-identical to dense ingestion of the same keyed stream
  (promotion included), with the §9 drop/counter rules intact;
* rows promote exactly when their distinct-bucket count crosses the
  threshold, promoted registers are bit-identical to dense-from-scratch,
  and the boundary (threshold-1 / threshold / threshold+1) round-trips
  through RHLB v2 and estimates identically to a dense row — per backend;
* the v2 wire formats reject garbage (truncation anywhere, mode-flag
  flips, unsorted/oversized pair lists, v1<->v2 confusion) instead of
  mis-parsing.
"""

import numpy as np
import jax.numpy as jnp
import pytest

from repro.sketch import (
    ExecutionPlan,
    HLLConfig,
    HybridBank,
    HyperLogLog,
    HybridWindowedBank,
    SketchBank,
    WindowedBank,
    available_bank_backends,
    available_estimators,
    default_threshold,
    hll,
    update_many,
)
from repro.sketch.bank import update_bank_registers
from repro.sketch.sparse import MODE_DENSE, MODE_SPARSE
from tests.reference_model import make_sharded_plans

CFG = HLLConfig(p=8, hash_bits=64)  # m=256: small enough for pallas paths


def _stream(n, rows, seed=0, space=2**31):
    rng = np.random.default_rng(seed)
    keys = jnp.asarray(rng.integers(0, rows, n, dtype=np.int32))
    items = jnp.asarray(rng.integers(0, space, n, dtype=np.int32))
    return keys, items


def _skewed_stream(n, rows, seed=0, hot=3):
    """Most traffic on ``hot`` rows; the rest stay nearly empty."""
    rng = np.random.default_rng(seed)
    keys = np.where(
        rng.random(n) < 0.9,
        rng.integers(0, hot, n),
        rng.integers(hot, rows, n),
    ).astype(np.int32)
    items = rng.integers(0, 2**31, n, dtype=np.int32)
    return jnp.asarray(keys), jnp.asarray(items)


def _items_with_distinct_buckets(k, cfg=CFG, seed=0):
    """Items hashing to exactly ``k`` distinct buckets (greedy pick)."""
    rng = np.random.default_rng(seed)
    chosen, seen = [], set()
    while len(chosen) < k:
        cand = rng.integers(0, 2**31, 4 * cfg.m, dtype=np.int32)
        idx, _ = hll.hash_index_rank(jnp.asarray(cand), cfg)
        for item, b in zip(cand, np.asarray(idx)):
            if int(b) not in seen:
                seen.add(int(b))
                chosen.append(int(item))
                if len(chosen) == k:
                    break
    return np.asarray(chosen, np.int32)


# ----------------------------------------------------------------------------
# ingest equivalence (per backend) + routing rules
# ----------------------------------------------------------------------------


@pytest.mark.parametrize("backend", available_bank_backends())
def test_hybrid_ingest_matches_dense_per_backend(backend):
    rows, n = 19, 3001
    plan = ExecutionPlan(backend=backend)
    keys, items = _skewed_stream(n, rows, seed=5)
    dense = update_many(SketchBank.empty(rows, CFG), keys, items, plan)
    hb = HybridBank.empty(rows, CFG, threshold=16)
    for c in np.array_split(np.arange(n), 4):  # chunked: promotions mid-way
        hb = hb.update_many(keys[jnp.asarray(c)], items[jnp.asarray(c)], plan)
    np.testing.assert_array_equal(
        np.asarray(hb.to_dense().registers), np.asarray(dense.registers)
    )
    np.testing.assert_array_equal(hb.counts, dense.counts)
    assert hb.dense_rows > 0 and hb.dense_rows < rows  # genuinely mixed


@pytest.mark.parametrize("backend", available_bank_backends())
def test_hybrid_out_of_range_keys_dropped_not_leaked(backend):
    rows, n = 11, 2001
    keys, items = _stream(n, rows, seed=7)
    bad = np.asarray(keys).copy()
    bad[::5] = -2
    bad[::7] = rows + 3
    plan = ExecutionPlan(backend=backend)
    dense = update_many(SketchBank.empty(rows, CFG), jnp.asarray(bad), items, plan)
    hb = HybridBank.empty(rows, CFG).update_many(jnp.asarray(bad), items, plan)
    np.testing.assert_array_equal(
        np.asarray(hb.to_dense().registers), np.asarray(dense.registers)
    )
    in_range = bad[(bad >= 0) & (bad < rows)]
    np.testing.assert_array_equal(
        hb.counts, np.bincount(in_range, minlength=rows)
    )


def test_chunked_ingest_is_order_invariant():
    rows, n = 13, 2000
    keys, items = _skewed_stream(n, rows, seed=11)
    one = HybridBank.empty(rows, CFG, threshold=16).update_many(keys, items)
    perm = np.random.default_rng(0).permutation(n)
    shuffled = HybridBank.empty(rows, CFG, threshold=16)
    for c in np.array_split(perm, 7):
        shuffled = shuffled.update_many(keys[jnp.asarray(c)], items[jnp.asarray(c)])
    np.testing.assert_array_equal(
        np.asarray(one.to_dense().registers),
        np.asarray(shuffled.to_dense().registers),
    )
    np.testing.assert_array_equal(one.modes, shuffled.modes)
    np.testing.assert_array_equal(one.counts, shuffled.counts)


def test_estimates_bit_identical_to_dense_all_estimators():
    rows = 31
    keys, items = _skewed_stream(2500, rows, seed=3)
    dense = update_many(SketchBank.empty(rows, CFG), keys, items)
    hb = HybridBank.empty(rows, CFG, threshold=32).update_many(keys, items)
    assert (hb.modes == MODE_SPARSE).any() and (hb.modes == MODE_DENSE).any()
    for est in (None,) + tuple(available_estimators()):
        np.testing.assert_array_equal(
            np.asarray(hb.estimate_many(est)),
            np.asarray(dense.estimate_many(est)),
            err_msg=f"estimator {est}",
        )
    # the LC fast path and the histogram path agree with each other too
    np.testing.assert_array_equal(
        np.asarray(hb.estimate_many("original")),
        np.asarray(hb.estimate_many("original", lc_fast=False)),
    )
    # exact host estimates agree row by row
    for b in (0, rows // 2, rows - 1):
        assert hb.estimate(b) == dense.estimate(b)


# ----------------------------------------------------------------------------
# promotion boundary (threshold-1 / threshold / threshold+1), per backend
# ----------------------------------------------------------------------------


@pytest.mark.parametrize("backend", available_bank_backends())
@pytest.mark.parametrize("delta", [-1, 0, 1])
def test_promotion_boundary_roundtrips_and_matches_dense(backend, delta):
    t = 16
    k = t + delta
    items = jnp.asarray(_items_with_distinct_buckets(k, seed=k))
    keys = jnp.zeros(k, jnp.int32)
    plan = ExecutionPlan(backend=backend)
    hb = HybridBank.empty(3, CFG, threshold=t).update_many(keys, items, plan)
    # crossing means strictly exceeding the threshold
    want_mode = MODE_DENSE if k > t else MODE_SPARSE
    assert hb.modes[0] == want_mode and (hb.modes[1:] == MODE_SPARSE).all()
    dense = update_many(SketchBank.empty(3, CFG), keys, items, plan)
    np.testing.assert_array_equal(
        np.asarray(hb.to_dense().registers), np.asarray(dense.registers)
    )
    if k > t:  # promoted registers are bit-identical to dense-from-scratch
        np.testing.assert_array_equal(
            np.asarray(hb.dense[0]), np.asarray(dense.registers[0])
        )
    back = HybridBank.from_bytes(hb.to_bytes())  # RHLB v2 round-trip
    assert back.threshold == t
    np.testing.assert_array_equal(back.modes, hb.modes)
    np.testing.assert_array_equal(back.counts, hb.counts)
    np.testing.assert_array_equal(
        np.asarray(back.to_dense().registers),
        np.asarray(hb.to_dense().registers),
    )
    for est in available_estimators():
        np.testing.assert_array_equal(
            np.asarray(back.estimate_many(est)),
            np.asarray(dense.estimate_many(est)),
            err_msg=f"estimator {est} at threshold{delta:+d}",
        )
        assert back.estimate(0, est) == dense.estimate(0, est)


def test_promotion_is_sticky_and_merge_keeps_it_infectious():
    t = 8
    hot = jnp.asarray(_items_with_distinct_buckets(t + 1, seed=1))
    a = HybridBank.empty(2, CFG, threshold=t).update_many(
        jnp.zeros(t + 1, jnp.int32), hot
    )
    assert a.modes.tolist() == [MODE_DENSE, MODE_SPARSE]
    # a tiny follow-up batch cannot demote the promoted row
    a = a.update_many(jnp.zeros(1, jnp.int32), jnp.asarray([123], jnp.int32))
    assert a.modes.tolist() == [MODE_DENSE, MODE_SPARSE]
    b = HybridBank.empty(2, CFG, threshold=t).update_many(
        jnp.ones(3, jnp.int32), jnp.arange(3, dtype=jnp.int32)
    )
    merged = a | b
    assert merged.modes.tolist() == [MODE_DENSE, MODE_SPARSE]
    np.testing.assert_array_equal(merged.counts, a.counts + b.counts)
    # sparse + sparse whose union crosses the threshold promotes
    half1 = jnp.asarray(_items_with_distinct_buckets(t, seed=2))
    half2 = jnp.asarray(_items_with_distinct_buckets(t, seed=3))
    u = HybridBank.empty(1, CFG, threshold=t).update_many(
        jnp.zeros(t, jnp.int32), half1
    ).merge(
        HybridBank.empty(1, CFG, threshold=t).update_many(
            jnp.zeros(t, jnp.int32), half2
        )
    )
    assert u.modes[0] == MODE_DENSE  # 16 distinct buckets > t=8


def test_merge_mismatches_raise():
    a = HybridBank.empty(4, CFG, threshold=8)
    with pytest.raises(ValueError, match="different sizes"):
        a.merge(HybridBank.empty(5, CFG, threshold=8))
    with pytest.raises(ValueError, match="different configs"):
        a.merge(HybridBank.empty(4, HLLConfig(p=9, hash_bits=64), threshold=8))
    with pytest.raises(ValueError, match="thresholds"):
        a.merge(HybridBank.empty(4, CFG, threshold=16))


# ----------------------------------------------------------------------------
# capacity adaptation + density introspection
# ----------------------------------------------------------------------------


def test_capacity_adapts_and_density_reports_the_win():
    rows = 64
    hb = HybridBank.empty(rows, CFG)
    assert hb.capacity == 0 and hb.nbytes < rows * CFG.m
    keys, items = _skewed_stream(4000, rows, seed=13)
    hb = hb.update_many(keys, items)
    d = hb.density()
    assert d["rows"] == rows and d["dense_rows"] == hb.dense_rows
    assert 0 < d["occupancy_mean"] < 1
    assert d["nbytes"] == hb.nbytes
    assert d["reduction"] > 1.5  # skewed traffic: hybrid must actually win
    # capacity tracks the largest sparse row, not the hot promoted rows
    assert hb.capacity <= hb.threshold
    assert hb.capacity >= int(np.asarray(hb.sparse_len).max())
    # dense SketchBank exposes the same introspection schema
    dd = update_many(SketchBank.empty(rows, CFG), keys, items).density()
    assert set(dd) == set(d) and dd["reduction"] == 1.0


def test_to_hybrid_and_from_dense_roundtrip():
    rows = 12
    keys, items = _skewed_stream(1500, rows, seed=17)
    dense = update_many(SketchBank.empty(rows, CFG), keys, items)
    hb = dense.to_hybrid(threshold=16)
    np.testing.assert_array_equal(
        np.asarray(hb.to_dense().registers), np.asarray(dense.registers)
    )
    np.testing.assert_array_equal(hb.counts, dense.counts)
    # forced dense rows stay dense even when nearly empty
    forced = dense.to_hybrid(threshold=16, dense_rows=np.ones(rows, bool))
    assert (forced.modes == MODE_DENSE).all()
    with pytest.raises(ValueError, match="mask"):
        dense.to_hybrid(dense_rows=np.ones(rows + 1, bool))


def test_row_and_to_sketches_match_dense():
    rows = 6
    keys, items = _skewed_stream(900, rows, seed=19)
    dense = update_many(SketchBank.empty(rows, CFG), keys, items)
    hb = HybridBank.empty(rows, CFG, threshold=16).update_many(keys, items)
    for i in range(-rows, rows):
        np.testing.assert_array_equal(
            np.asarray(hb.row(i).registers), np.asarray(dense.row(i).registers)
        )
        assert hb.row(i).count == dense.row(i).count
    with pytest.raises(IndexError, match="out of range"):
        hb.row(rows)
    assert len(hb.to_sketches()) == rows


def test_threshold_validation():
    with pytest.raises(ValueError, match="threshold"):
        HybridBank.empty(4, CFG, threshold=0)
    with pytest.raises(ValueError, match="threshold"):
        HybridBank.empty(4, CFG, threshold=CFG.m)  # > m // 2: LC guarantee
    with pytest.raises(ValueError, match="at least one row"):
        HybridBank.empty(0, CFG)
    assert HybridBank.empty(4, CFG).threshold == default_threshold(CFG)
    with pytest.raises(ValueError, match="sparse_threshold"):
        ExecutionPlan(sparse_threshold=0)
    assert ExecutionPlan(sparse_threshold=7).sparse_threshold == 7


# ----------------------------------------------------------------------------
# B=0 and empty-stream short-circuits
# ----------------------------------------------------------------------------


def test_hybrid_empty_stream_and_zero_rows_short_circuit():
    hb = HybridBank.empty(4, CFG)
    empty = jnp.zeros((0,), jnp.int32)
    assert hb.update_many(empty, empty) is hb
    with pytest.raises(ValueError, match="same length"):
        hb.update_many(jnp.zeros((2,), jnp.int32), empty)
    zero = HybridBank(
        jnp.zeros((0, 0), jnp.int32),
        jnp.zeros((0,), jnp.int32),
        jnp.zeros((0, CFG.m), hll.REGISTER_DTYPE),
        jnp.zeros((0,), jnp.int32),
        jnp.zeros((0, 2), jnp.uint32),
        CFG,
        8,
    )
    assert zero.update_many(jnp.zeros(5, jnp.int32), jnp.arange(5)) is zero
    assert zero.estimate_many().shape == (0,)


# ----------------------------------------------------------------------------
# RHLB v2 wire format: round-trip + garbage rejection
# ----------------------------------------------------------------------------


def _mixed_bank(rows=9, n=1200, threshold=16, seed=23):
    keys, items = _skewed_stream(n, rows, seed=seed)
    return HybridBank.empty(rows, CFG, threshold).update_many(keys, items)


def test_v2_roundtrip_mixed_modes():
    hb = _mixed_bank()
    assert (hb.modes == MODE_SPARSE).any() and (hb.modes == MODE_DENSE).any()
    back = HybridBank.from_bytes(hb.to_bytes())
    np.testing.assert_array_equal(back.modes, hb.modes)
    np.testing.assert_array_equal(back.counts, hb.counts)
    np.testing.assert_array_equal(
        np.asarray(back.to_dense().registers),
        np.asarray(hb.to_dense().registers),
    )
    np.testing.assert_array_equal(
        np.asarray(back.sparse_len), np.asarray(hb.sparse_len)
    )


def test_v1_dense_blob_parses_as_all_dense_hybrid():
    rows = 5
    keys, items = _stream(800, rows, seed=29)
    dense = update_many(SketchBank.empty(rows, CFG), keys, items)
    hb = HybridBank.from_bytes(dense.to_bytes())  # version-gated v1 parse
    assert (hb.modes == MODE_DENSE).all()
    np.testing.assert_array_equal(
        np.asarray(hb.to_dense().registers), np.asarray(dense.registers)
    )
    np.testing.assert_array_equal(hb.counts, dense.counts)


def test_sketchbank_rejects_v2_with_pointer():
    blob = _mixed_bank().to_bytes()
    with pytest.raises(ValueError, match="HybridBank.from_bytes"):
        SketchBank.from_bytes(blob)


@pytest.mark.parametrize("frac", [0.0, 0.05, 0.2, 0.45, 0.7, 0.9, 0.999])
def test_v2_rejects_truncation_anywhere(frac):
    """Cuts through the header, counts, mode flags, a dense row, and —
    crucially — inside a sparse pair list must all raise, never mis-parse."""
    blob = _mixed_bank().to_bytes()
    cut = int(len(blob) * frac)
    with pytest.raises(ValueError):
        HybridBank.from_bytes(blob[:cut])
    with pytest.raises(ValueError):
        HybridBank.from_bytes(blob + b"\x00")


def test_v2_rejects_cut_inside_pair_list():
    hb = HybridBank.empty(2, CFG, threshold=16).update_many(
        jnp.zeros(8, jnp.int32),
        jnp.asarray(_items_with_distinct_buckets(8, seed=31)),
    )
    blob = hb.to_bytes()
    header = 20 + 4 + 2 * 8 + 2  # header + threshold + counts + modes
    cut = header + 2 + 4  # inside row 0's pair list (8 pairs x 3 bytes)
    assert cut < len(blob)
    with pytest.raises(ValueError, match="cut short|payload"):
        HybridBank.from_bytes(blob[:cut])


def test_v2_rejects_mode_flag_flips():
    hb = _mixed_bank()
    rows = len(hb)
    blob = bytearray(hb.to_bytes())
    modes_off = 20 + 4 + rows * 8
    flip = int(np.argmax(hb.modes == MODE_SPARSE))
    blob[modes_off + flip] = MODE_DENSE  # sparse row re-labeled dense
    with pytest.raises(ValueError):
        HybridBank.from_bytes(bytes(blob))
    blob[modes_off + flip] = 7  # not a mode at all
    with pytest.raises(ValueError, match="mode flag"):
        HybridBank.from_bytes(bytes(blob))


def test_v2_rejects_corrupt_pair_lists():
    t = 16
    hb = HybridBank.empty(1, CFG, threshold=t).update_many(
        jnp.zeros(4, jnp.int32),
        jnp.asarray(_items_with_distinct_buckets(4, seed=37)),
    )
    blob = bytearray(hb.to_bytes())
    payload = 20 + 4 + 8 + 1  # header + threshold + count + mode
    # npairs beyond the declared threshold
    bad = bytearray(blob)
    bad[payload : payload + 2] = (t + 1).to_bytes(2, "little")
    with pytest.raises(ValueError, match="threshold|cut short"):
        HybridBank.from_bytes(bytes(bad))
    # unsorted buckets (swap the first two pairs)
    bad = bytearray(blob)
    first = bytes(bad[payload + 2 : payload + 5])
    bad[payload + 2 : payload + 5] = bad[payload + 5 : payload + 8]
    bad[payload + 5 : payload + 8] = first
    with pytest.raises(ValueError, match="increasing"):
        HybridBank.from_bytes(bytes(bad))
    # rank 0 is not a value a present bucket can hold
    bad = bytearray(blob)
    bad[payload + 4] = 0
    with pytest.raises(ValueError, match="rank"):
        HybridBank.from_bytes(bytes(bad))
    # rank beyond max_rank
    bad = bytearray(blob)
    bad[payload + 4] = CFG.max_rank + 1
    with pytest.raises(ValueError, match="rank"):
        HybridBank.from_bytes(bytes(bad))


# ----------------------------------------------------------------------------
# hybrid windowed ring: sparse buckets, promotion across advance, RHLW v2
# ----------------------------------------------------------------------------


def test_window_promotion_survives_advance():
    t = 8
    win = HybridWindowedBank.empty(3, 2, CFG, threshold=t)
    hot = jnp.asarray(_items_with_distinct_buckets(t + 1, seed=41))
    win = win.observe(jnp.zeros(t + 1, jnp.int32), hot)
    assert win.buckets[win.cursor].modes[0] == MODE_DENSE
    promoted_regs = np.asarray(win.buckets[win.cursor].dense[0])
    win = win.advance()  # the promoted bucket ages but keeps its mode
    aged = win.buckets[(win.cursor - 1) % win.window]
    assert aged.modes[0] == MODE_DENSE
    np.testing.assert_array_equal(np.asarray(aged.dense[0]), promoted_regs)
    # the NEW current bucket starts sparse again
    assert (win.buckets[win.cursor].modes == MODE_SPARSE).all()
    # ...and the fold still sees the promoted epoch until it expires
    assert win.fold_window().modes[0] == MODE_DENSE
    win = win.advance(win.window)  # slide the promoted epoch out
    assert win.window_counts().sum() == 0
    assert (win.fold_window().modes == MODE_SPARSE).all()


def test_hybrid_window_matches_dense_ring():
    window, rows = 3, 10
    wh = HybridWindowedBank.empty(window, rows, CFG, threshold=16)
    wd = WindowedBank.empty(window, rows, CFG)
    rng = np.random.default_rng(43)
    for e in range(5):
        if e:
            wh, wd = wh.advance(), wd.advance()
        keys = jnp.asarray(rng.integers(0, rows, 400, dtype=np.int32))
        items = jnp.asarray(rng.integers(0, 2**31, 400, dtype=np.int32))
        wh, wd = wh.observe(keys, items), wd.observe(keys, items)
    assert wh.epoch == wd.epoch
    for last_k in (1, 2, 3):
        np.testing.assert_array_equal(
            np.asarray(wh.fold_window(last_k).to_dense().registers),
            np.asarray(wd._fold_registers(last_k, None)),
        )
        np.testing.assert_array_equal(
            wh.window_counts(last_k), wd.window_counts(last_k)
        )
    with pytest.raises(ValueError, match="last_k"):
        wh.estimate_window(0)
    d = wh.density()
    assert d["window"] == window and d["rows"] == rows


def test_rhlw_v2_roundtrip_and_v1_interop():
    window, rows = 3, 4
    win = HybridWindowedBank.empty(window, rows, CFG, threshold=8)
    rng = np.random.default_rng(47)
    for e in range(4):
        if e:
            win = win.advance()
        win = win.observe(
            jnp.asarray(rng.integers(0, rows, 300, dtype=np.int32)),
            jnp.asarray(rng.integers(0, 2**31, 300, dtype=np.int32)),
        )
    blob = win.to_bytes()
    back = HybridWindowedBank.from_bytes(blob)
    assert back.cursor == win.cursor and back.epoch == win.epoch
    np.testing.assert_array_equal(back.epochs, win.epochs)
    np.testing.assert_array_equal(back.window_counts(), win.window_counts())
    np.testing.assert_array_equal(
        np.asarray(back.fold_window().to_dense().registers),
        np.asarray(win.fold_window().to_dense().registers),
    )
    # a v1 dense ring parses into an all-dense hybrid ring, version-gated
    wd = WindowedBank.empty(window, rows, CFG).observe(
        jnp.asarray(rng.integers(0, rows, 200, dtype=np.int32)),
        jnp.asarray(rng.integers(0, 2**31, 200, dtype=np.int32)),
    )
    h1 = HybridWindowedBank.from_bytes(wd.to_bytes())
    np.testing.assert_array_equal(
        np.asarray(h1.fold_window().to_dense().registers),
        np.asarray(wd._fold_registers(window, None)),
    )
    # ...while the dense parser refuses the v2 ring with a pointer
    with pytest.raises(ValueError, match="HybridWindowedBank"):
        WindowedBank.from_bytes(blob)


# ----------------------------------------------------------------------------
# deferred dedup: append buffer, pressure flush, settled reads (DESIGN.md §12)
# ----------------------------------------------------------------------------


def test_appends_defer_until_read_then_settle():
    keys, items = _stream(500, 8, seed=3)
    hb = HybridBank.empty(8, CFG).update_many(keys, items)
    assert hb.pending_pairs == 500  # raw appends, no dedup yet
    assert int(np.asarray(hb.pair_len).sum()) == 0  # settled state untouched
    # counters are eager: exact before any compaction
    np.testing.assert_array_equal(
        hb.counts, np.bincount(np.asarray(keys), minlength=8)
    )
    settled = hb.compact()
    assert settled.pending is None
    assert hb.pending_pairs == 500  # the original instance is immutable
    assert settled is hb.compact()  # idempotent AND cached per instance
    eager = HybridBank.empty(8, CFG).update_many(keys, items).compact()
    np.testing.assert_array_equal(
        np.asarray(settled.pair_buf), np.asarray(eager.pair_buf)
    )
    np.testing.assert_array_equal(
        np.asarray(settled.pair_len), np.asarray(eager.pair_len)
    )


@pytest.mark.parametrize(
    "surface", ["estimate", "serialize", "merge", "to_dense", "density", "row"]
)
def test_pending_settles_at_every_read_surface(surface):
    """Deferred-dedup banks read bit-identical to eager per-batch dedup."""
    rows = 11
    keys, items = _skewed_stream(2000, rows, seed=7)
    deferred = HybridBank.empty(rows, CFG, threshold=16)
    eager = HybridBank.empty(rows, CFG, threshold=16)
    for c in np.array_split(np.arange(2000), 5):
        ci = jnp.asarray(c)
        deferred = deferred.update_many(keys[ci], items[ci])
        eager = eager.update_many(keys[ci], items[ci]).compact()
    assert deferred.pending_pairs > 0 and eager.pending_pairs == 0
    if surface == "estimate":
        for est in available_estimators():
            np.testing.assert_array_equal(
                np.asarray(deferred.estimate_many(est)),
                np.asarray(eager.estimate_many(est)),
            )
    elif surface == "serialize":
        assert deferred.to_bytes() == eager.to_bytes()
    elif surface == "merge":
        ok, oi = _stream(300, rows, seed=9)
        other = HybridBank.empty(rows, CFG, threshold=16).update_many(ok, oi)
        assert other.pending_pairs > 0  # merge settles BOTH sides
        a = deferred.merge(other)
        b = eager.merge(other.compact())
        np.testing.assert_array_equal(
            np.asarray(a.to_dense().registers),
            np.asarray(b.to_dense().registers),
        )
        np.testing.assert_array_equal(a.modes, b.modes)
    elif surface == "to_dense":
        np.testing.assert_array_equal(
            np.asarray(deferred.to_dense().registers),
            np.asarray(eager.to_dense().registers),
        )
    elif surface == "density":
        assert deferred.density() == eager.density()
    elif surface == "row":
        for i in range(rows):
            np.testing.assert_array_equal(
                np.asarray(deferred.row(i).registers),
                np.asarray(eager.row(i).registers),
            )


def test_flush_pressure_fires_exactly_at_the_floor(monkeypatch):
    from repro.sketch import sparse as sparse_mod

    monkeypatch.setattr(sparse_mod, "_FLUSH_MIN_PAIRS", 64)
    monkeypatch.setattr(sparse_mod, "_FLUSH_FACTOR", 2)
    hb = HybridBank.empty(4, CFG)
    k1, i1 = _stream(63, 4, seed=1)
    hb = hb.update_many(k1, i1)
    assert hb.pending is not None and hb.pending_pairs == 63  # under the floor
    k2, i2 = _stream(1, 4, seed=2)
    hb = hb.update_many(k2, i2)  # lands exactly AT the floor: >= fires
    assert hb.pending is None and hb.pending_pairs == 0
    # second window: the floor is now max(MIN, FACTOR * live pairs)
    live = int(np.asarray(hb.pair_len).sum())
    gate = max(64, 2 * live)
    k3, i3 = _stream(gate - 1, 4, seed=3)
    hb = hb.update_many(k3, i3)
    assert hb.pending is not None  # one under the amortized floor
    k4, i4 = _stream(1, 4, seed=4)
    hb = hb.update_many(k4, i4)
    assert hb.pending is None  # crossing it compacts inside update_many


@pytest.mark.parametrize("backend", available_bank_backends())
@pytest.mark.parametrize("delta", [-1, 0, 1])
def test_promotion_decided_at_compaction_from_buffered_pairs(backend, delta):
    t = 16
    k = t + delta
    items = jnp.asarray(_items_with_distinct_buckets(k, seed=100 + k))
    keys = jnp.zeros(k, jnp.int32)
    plan = ExecutionPlan(backend=backend)
    hb = HybridBank.empty(2, CFG, threshold=t)
    for i in range(k):  # one item per batch: every pair rides the buffer
        hb = hb.update_many(keys[i : i + 1], items[i : i + 1], plan)
    assert hb.pending_pairs == k
    assert int(np.asarray(hb.slot_map).max()) == -1  # not promoted yet
    want = MODE_DENSE if k > t else MODE_SPARSE
    assert hb.modes[0] == want  # settles; promotion decided at compaction
    dense = update_many(SketchBank.empty(2, CFG), keys, items, plan)
    np.testing.assert_array_equal(
        np.asarray(hb.to_dense().registers), np.asarray(dense.registers)
    )


def test_dense_destined_items_do_not_buffer():
    t = 8
    hot = jnp.asarray(_items_with_distinct_buckets(t + 1, seed=2))
    hb = HybridBank.empty(2, CFG, threshold=t).update_many(
        jnp.zeros(t + 1, jnp.int32), hot
    )
    hb = hb.compact()
    assert hb.modes[0] == MODE_DENSE and hb.pending is None
    # further traffic to the promoted row goes straight to the registers
    more = jnp.asarray(_items_with_distinct_buckets(5, seed=3))
    hb2 = hb.update_many(jnp.zeros(5, jnp.int32), more)
    assert hb2.pending is None and hb2.pending_pairs == 0


def test_cell_space_guard_shares_one_message():
    """Past 2^31 cells the two layouts that flatten (row, bucket) into one
    int32 id refuse with one shared message, and the bank itself no longer
    refuses: it ingests and merges through the (row, bucket) sort."""
    from repro.sketch.backends import sparse_merge, sparse_merge_cells

    rows = 1 << 23  # 2^23 * 256 = 2^31 cells
    row = jnp.zeros(4, jnp.int32)
    bucket = jnp.arange(4, dtype=jnp.int32)
    rank = jnp.ones(4, jnp.int32)
    msg = r"bank cell space B\*m = 8388608\*256 overflows the int32 cell ids"
    with pytest.raises(ValueError, match=msg) as via_cells:
        sparse_merge_cells(row, bucket, rank, rows=rows, m=CFG.m)
    with pytest.raises(ValueError, match=msg) as via_kernel:
        sparse_merge(row, bucket, rank, rows, CFG)
    # one shared guard: both flattened layouts raise the identical message
    assert str(via_cells.value) == str(via_kernel.value)

    big = HybridBank.empty(rows, CFG)
    keys = jnp.asarray([rows - 1, rows - 1, 3, rows], jnp.int32)
    items = jnp.arange(4, dtype=jnp.int32)
    merged = big.update_many(keys, items).merge(big)
    want = HyperLogLog.empty(CFG).update(items[:2])
    np.testing.assert_array_equal(
        np.asarray(merged.row(rows - 1).registers), np.asarray(want.registers)
    )
    assert merged.counts[rows - 1] == 2 and merged.counts.sum() == 3


def test_sparse_backend_registry_and_fallback():
    from repro.sketch import (
        available_sparse_backends,
        dedup_pairs,
        get_sparse_backend,
    )

    assert {"jnp", "pallas", "pallas_pipelined"} <= set(
        available_sparse_backends()
    )
    with pytest.raises(ValueError, match="no sparse dedup path"):
        get_sparse_backend("nope")
    # a bank-only backend (no sparse entry) falls back to the jnp dedup
    row = jnp.asarray([0, 1, -1, 0], jnp.int32)
    bucket = jnp.asarray([3, 5, 0, 3], jnp.int32)
    rank = jnp.asarray([2, 7, 1, 4], jnp.int32)
    got = dedup_pairs(row, bucket, rank, 2, CFG, ExecutionPlan(backend="jnp"))
    assert int(np.asarray(got.distinct).sum()) == 2


@pytest.mark.parametrize("backend", ["pallas", "pallas_pipelined"])
def test_sparse_scatter_kernel_matches_jnp_dedup(backend):
    """The Pallas dedup (interpret off-TPU) == the jnp reference, exactly."""
    from repro.sketch import dedup_pairs

    rows = 16
    rng = np.random.default_rng(12)
    n = 640
    row = jnp.asarray(
        np.where(
            rng.random(n) < 0.1,
            rng.choice([-2, rows + 1], n),
            rng.integers(0, rows, n),
        ).astype(np.int32)
    )
    bucket = jnp.asarray(rng.integers(0, CFG.m, n, dtype=np.int32))
    rank = jnp.asarray(rng.integers(1, 50, n, dtype=np.int32))
    ref = dedup_pairs(row, bucket, rank, rows, CFG, ExecutionPlan())
    got = dedup_pairs(
        row, bucket, rank, rows, CFG, ExecutionPlan(backend=backend)
    )
    assert got.cells is not None
    np.testing.assert_array_equal(np.asarray(got.distinct), np.asarray(ref.distinct))
    if ref.cells is not None:
        np.testing.assert_array_equal(np.asarray(got.cells), np.asarray(ref.cells))


# ----------------------------------------------------------------------------
# dense dispatch at bucketed shapes (DESIGN.md §12)
# ----------------------------------------------------------------------------


CFG_SMALL = HLLConfig(p=4, hash_bits=64)  # m=16: D=1025 stays cheap in interpret


def _bank_with_dense_rows(d, sparse_rows=2, seed=0):
    """A hybrid bank whose first ``d`` rows are dense and hold some history."""
    rows = d + sparse_rows
    rng = np.random.default_rng(seed)
    hist_keys = rng.integers(0, d, 4 * d, dtype=np.int32)
    hist_items = rng.integers(0, 2**31, 4 * d, dtype=np.int32)
    dense = SketchBank.empty(rows, CFG_SMALL).update_many(
        jnp.asarray(hist_keys), jnp.asarray(hist_items)
    )
    force = np.arange(rows) < d
    return HybridBank.from_dense(dense, dense_rows=jnp.asarray(force)), dense


def _plan_for(kind):
    if kind == "sharded":
        return make_sharded_plans(["jnp"])["jnp"]
    return ExecutionPlan(backend=kind)


@pytest.mark.parametrize("kind", ["jnp", "pallas", "sharded"])
@pytest.mark.parametrize("d", [1, 3, 1024, 1025])
@pytest.mark.parametrize("n", [1, 63, 64, 65, 1023, 1025])
def test_bucketed_dense_dispatch_matches_exact_shapes(kind, d, n):
    """Padding the dense sub-stream and block changes no bit of the state."""
    plan = _plan_for(kind)
    hb, dense = _bank_with_dense_rows(d, seed=n + d)
    rng = np.random.default_rng(1000 + n)
    # n dense-destined pairs plus a few sparse-destined and dropped ones
    keys = np.concatenate(
        [rng.integers(0, d, n), [d, d + 1, -1, d + 2]]
    ).astype(np.int32)
    items = rng.integers(0, 2**31, keys.size, dtype=np.int32)
    got = hb.update_many(jnp.asarray(keys), jnp.asarray(items), plan)

    sel = (keys >= 0) & (keys < d)
    slots = np.asarray(hb.slot_map)[keys[sel]]
    exact = update_bank_registers(
        hb.dense_block,
        jnp.asarray(slots),
        jnp.asarray(items[sel]),
        CFG_SMALL,
        plan,
    )
    assert got.dense_block.shape == (d, CFG_SMALL.m)
    np.testing.assert_array_equal(
        np.asarray(got.dense_block), np.asarray(exact)
    )
    np.testing.assert_array_equal(
        np.asarray(got.slot_map), np.asarray(hb.slot_map)
    )
    want = dense.update_many(jnp.asarray(keys), jnp.asarray(items), plan)
    np.testing.assert_array_equal(
        np.asarray(got.n_items), np.asarray(want.n_items)
    )
    np.testing.assert_array_equal(
        np.asarray(got.to_dense().registers), np.asarray(want.registers)
    )
    np.testing.assert_array_equal(
        np.asarray(got.estimate_many()), np.asarray(want.estimate_many())
    )


def test_dense_dispatch_reuses_one_executable_per_bucket(monkeypatch):
    """Same (D, length) buckets reuse the executable; a D past 2^k adds one."""
    from repro.obs import metrics
    from repro.sketch import sparse as sparse_mod
    from repro.sketch.backends import bank_update_jnp

    monkeypatch.setattr(sparse_mod, "_DENSE_SHAPES_SEEN", set())
    # a seed no other test uses, so this test's cache entries are its own
    cfg = HLLConfig(p=4, hash_bits=64, seed=0x5EED0014)
    rng = np.random.default_rng(14)

    def ingest(d, n):
        rows = d + 1
        force = jnp.asarray(np.arange(rows) < d)
        hb = HybridBank.from_dense(SketchBank.empty(rows, cfg), dense_rows=force)
        keys = rng.integers(0, d, n).astype(np.int32)
        items = rng.integers(0, 2**31, n, dtype=np.int32)
        before = bank_update_jnp._cache_size()
        seen = metrics.counter_value("sparse.dense.new_shapes")
        pad = metrics.counter_value("sparse.dense.pad_pairs")
        hb.update_many(jnp.asarray(keys), jnp.asarray(items))
        return (
            bank_update_jnp._cache_size() - before,
            metrics.counter_value("sparse.dense.new_shapes") - seen,
            metrics.counter_value("sparse.dense.pad_pairs") - pad,
        )

    metrics.reset()
    metrics.enable()
    try:
        assert ingest(5, 70) == (1, 1, 58)  # buckets (8, 128): compiles once
        assert ingest(7, 100) == (0, 0, 28)  # same buckets: reused
        assert ingest(9, 90) == (1, 1, 38)  # D crosses 8: exactly one more
        assert ingest(16, 128) == (0, 0, 0)  # (16, 128) again, no padding
    finally:
        metrics.disable()
        metrics.reset()
