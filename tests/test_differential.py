"""Differential harness: every carrier vs the dict-of-sets oracle.

Three properties, checked over op sequences (update_many / merge / advance
/ serialize->deserialize / estimate) drawn from the shared grammar in
tests/reference_model.py:

1. **Backend bit-identity** — for dense, sparse, and mixed banks alike,
   running the SAME op sequence under every registered bank backend must
   leave BIT-IDENTICAL canonical state (registers, exact counters, and
   for hybrid carriers the per-row mode flags) as the jnp reference plan.
2. **Oracle bands** — every registered estimator's reading of every row
   stays within the 3-sigma band of the oracle's true distinct count
   (plus small-count slack; see reference_model.assert_within_band).
3. **Representation equivalence** — the hybrid carriers materialize to
   exactly the dense carriers' registers at every estimate point, so the
   sparse layout can never drift from the storage it compresses.

The fixed-seed sweeps below always run; with hypothesis installed the
same grammar also runs under generated op sequences (profile-controlled
example counts — see tests/hypothesis_compat.py).
"""

import numpy as np
import pytest

from repro.sketch import (
    CMConfig,
    HLLConfig,
    available_bank_backends,
    available_cm_backends,
    available_cm_window_backends,
    available_estimators,
    available_window_backends,
)
from tests.hypothesis_compat import HAVE_HYPOTHESIS, example, given, st
from tests.reference_model import (
    CounterReferenceModel,
    CountMinSUT,
    DenseBankSUT,
    DenseWindowSUT,
    HybridBankSUT,
    HybridWindowSUT,
    ReferenceModel,
    WindowedCountMinSUT,
    assert_cm_bounds,
    assert_within_band,
    gen_ops,
    gen_stream,
    make_plans,
    run_ops,
)

CFG = HLLConfig(p=8, hash_bits=64)  # m=256: small enough for pallas paths
ROWS = 23

# bank kind -> (SUT class, promotion threshold): "sparse" stays almost
# entirely in the COO layout, "mixed" promotes hot rows almost immediately
BANK_KINDS = {
    "dense": (DenseBankSUT, None),
    "sparse": (HybridBankSUT, CFG.m // 2),
    "mixed": (HybridBankSUT, 8),
}


def _estimate_checker(collected):
    def check(sut, oracle):
        true = oracle.true_cardinalities()
        for estimator in available_estimators():
            assert_within_band(sut.estimates(estimator), true, CFG.m)
        np.testing.assert_array_equal(sut.counts(), oracle.observed())
        collected.append(sut.canonical())

    return check


def _run_differential(kind, seed, windowed=False, window=4):
    sut_cls, threshold = BANK_KINDS[kind]
    if windowed:
        sut_cls = HybridWindowSUT if kind != "dense" else DenseWindowSUT
    backends = (
        available_window_backends() if windowed else available_bank_backends()
    )
    plans = make_plans(backends)
    states = {}
    for name, plan in plans.items():
        rng = np.random.default_rng(seed)  # same ops for every backend
        ops = gen_ops(rng, ROWS, n_ops=10, windowed=windowed)
        oracle = ReferenceModel(ROWS, window=window if windowed else None)
        if windowed:
            sut = sut_cls(window, ROWS, CFG, plan=plan, threshold=threshold)
        else:
            sut = sut_cls(ROWS, CFG, plan=plan, threshold=threshold)
        collected = []
        run_ops(ops, sut, oracle, on_estimate=_estimate_checker(collected))
        states[name] = collected
    ref = states["jnp"]
    for name, collected in states.items():
        assert len(collected) == len(ref)
        for step, (got, want) in enumerate(zip(collected, ref)):
            for g, w in zip(got, want):
                np.testing.assert_array_equal(
                    g, w, err_msg=f"backend {name} diverged at estimate {step}"
                )


@pytest.mark.parametrize("seed", [0, 1, 2])
@pytest.mark.parametrize("kind", sorted(BANK_KINDS))
def test_flat_banks_match_oracle_and_backends(kind, seed):
    _run_differential(kind, seed, windowed=False)


@pytest.mark.parametrize("seed", [0, 1])
@pytest.mark.parametrize("kind", sorted(BANK_KINDS))
def test_windowed_banks_match_oracle_and_backends(kind, seed):
    _run_differential(kind, seed, windowed=True)


@pytest.mark.parametrize("seed", [0, 1, 2, 3])
def test_hybrid_state_tracks_dense_state_bit_for_bit(seed):
    """Same ops -> hybrid materializes to the dense bank exactly."""
    rng = np.random.default_rng(100 + seed)
    ops = gen_ops(rng, ROWS, n_ops=8, windowed=False)
    oracle_a = ReferenceModel(ROWS)
    oracle_b = ReferenceModel(ROWS)
    dense = run_ops(ops, DenseBankSUT(ROWS, CFG), oracle_a)
    hybrid = run_ops(ops, HybridBankSUT(ROWS, CFG, threshold=8), oracle_b)
    np.testing.assert_array_equal(
        np.asarray(hybrid.bank.to_dense().registers),
        np.asarray(dense.bank.registers),
    )
    np.testing.assert_array_equal(hybrid.bank.counts, dense.bank.counts)
    # and the device estimates agree bit-for-bit as well (DESIGN.md §12)
    for estimator in available_estimators():
        np.testing.assert_array_equal(
            hybrid.estimates(estimator), dense.estimates(estimator)
        )


@pytest.mark.parametrize("seed", [0, 1, 2])
def test_deferred_dedup_tracks_eager_compaction_bit_for_bit(seed):
    """Same ops -> the deferred append-buffer path settles to EXACTLY the
    state the pre-change eager-dedup-per-update path produced.

    EagerHybridBankSUT compacts after every update/merge (the old
    behavior); the plain SUT lets the buffer ride until an estimate (or
    an explicit peek op) forces settlement.  Canonical state — registers,
    counters, mode flags — must be bit-identical at every estimate point,
    for every registered bank backend (the deferred-dedup regression
    anchor, DESIGN.md §12)."""
    from tests.reference_model import EagerHybridBankSUT

    plans = make_plans(available_bank_backends())
    for name, plan in plans.items():
        rng_a = np.random.default_rng(200 + seed)
        rng_b = np.random.default_rng(200 + seed)
        ops_a = gen_ops(rng_a, ROWS, n_ops=10, windowed=False)
        ops_b = gen_ops(rng_b, ROWS, n_ops=10, windowed=False)
        deferred_states, eager_states = [], []
        run_ops(
            ops_a,
            HybridBankSUT(ROWS, CFG, plan=plan, threshold=8),
            ReferenceModel(ROWS),
            on_estimate=lambda s, o: deferred_states.append(s.canonical()),
        )
        run_ops(
            ops_b,
            EagerHybridBankSUT(ROWS, CFG, plan=plan, threshold=8),
            ReferenceModel(ROWS),
            on_estimate=lambda s, o: eager_states.append(s.canonical()),
        )
        assert len(deferred_states) == len(eager_states) > 0
        for step, (got, want) in enumerate(zip(deferred_states, eager_states)):
            for g, w in zip(got, want):
                np.testing.assert_array_equal(
                    g,
                    w,
                    err_msg=(
                        f"backend {name}: deferred dedup diverged from "
                        f"eager compaction at estimate {step}"
                    ),
                )


def test_windowed_expiry_tracks_oracle_exactly():
    """Advancing past W expires oracle and carriers in lockstep."""
    window = 3
    for sut_cls, threshold in (
        (DenseWindowSUT, None),
        (HybridWindowSUT, 8),
    ):
        oracle = ReferenceModel(ROWS, window=window)
        sut = sut_cls(window, ROWS, CFG, threshold=threshold)
        rng = np.random.default_rng(9)
        for epoch in range(2 * window):
            keys, items = gen_stream(rng, ROWS, 300)
            sut.update(keys, items)
            oracle.update(keys, items)
            np.testing.assert_array_equal(sut.counts(), oracle.observed())
            assert_within_band(
                sut.estimates(), oracle.true_cardinalities(), CFG.m
            )
            sut.advance(1)
            oracle.advance(1)
        # everything beyond the window is gone on both sides
        sut.advance(window)
        oracle.advance(window)
        assert oracle.true_cardinalities().sum() == 0
        assert sut.counts().sum() == 0
        assert np.asarray(sut.estimates()).sum() == 0


# ----------------------------------------------------------------------------
# count-min family vs the dict-of-Counters oracle (DESIGN.md §13)
# ----------------------------------------------------------------------------

CM_CFG = CMConfig(depth=4, width=128, seed=11)
CM_PROBE = np.arange(50, dtype=np.int32)


def _cm_checker(collected):
    def check(sut, oracle):
        est = sut.query(CM_PROBE)
        assert_cm_bounds(
            est,
            oracle.true_counts(CM_PROBE),
            oracle.observed(),
            CM_CFG.width,
            CM_CFG.depth,
        )
        np.testing.assert_array_equal(sut.counts(), oracle.observed())
        collected.append(sut.canonical())

    return check


def _run_cm_differential(seed, windowed=False, window=4):
    """The count-min twin of _run_differential: same shared op grammar
    (update / merge-or-advance / roundtrip / estimate), every registered
    cm backend held bit-identical to jnp on the full canonical state
    (counters AND Topkapi labels AND exact counters), every estimate
    point held to the exact-oracle sandwich bounds."""
    backends = (
        available_cm_window_backends() if windowed else available_cm_backends()
    )
    plans = make_plans(backends)
    states = {}
    for name, plan in plans.items():
        rng = np.random.default_rng(seed)  # same ops for every backend
        ops = gen_ops(rng, ROWS, n_ops=8, windowed=windowed)
        oracle = CounterReferenceModel(
            ROWS, window=window if windowed else None
        )
        if windowed:
            sut = WindowedCountMinSUT(window, ROWS, CM_CFG, plan=plan)
        else:
            sut = CountMinSUT(ROWS, CM_CFG, plan=plan)
        collected = []
        run_ops(ops, sut, oracle, on_estimate=_cm_checker(collected))
        states[name] = collected
    ref = states["jnp"]
    for name, collected in states.items():
        assert len(collected) == len(ref)
        for step, (got, want) in enumerate(zip(collected, ref)):
            for g, w in zip(got, want):
                np.testing.assert_array_equal(
                    g, w, err_msg=f"cm backend {name} diverged at step {step}"
                )


@pytest.mark.parametrize("seed", [0, 1, 2])
def test_flat_countmin_matches_oracle_and_backends(seed):
    _run_cm_differential(seed, windowed=False)


@pytest.mark.parametrize("seed", [0, 1])
def test_windowed_countmin_matches_oracle_and_backends(seed):
    _run_cm_differential(seed, windowed=True)


def test_windowed_countmin_expiry_tracks_oracle_exactly():
    """Advancing past W expires the oracle and the ring in lockstep."""
    window = 3
    oracle = CounterReferenceModel(ROWS, window=window)
    sut = WindowedCountMinSUT(window, ROWS, CM_CFG)
    rng = np.random.default_rng(9)
    for epoch in range(2 * window):
        keys, items = gen_stream(rng, ROWS, 300, value_space=50)
        sut.update(keys, items)
        oracle.update(keys, items)
        np.testing.assert_array_equal(sut.counts(), oracle.observed())
        assert_cm_bounds(
            sut.query(CM_PROBE),
            oracle.true_counts(CM_PROBE),
            oracle.observed(),
            CM_CFG.width,
            CM_CFG.depth,
        )
        sut.advance(1)
        oracle.advance(1)
    # everything beyond the window is gone on both sides
    sut.advance(window)
    oracle.advance(window)
    assert oracle.observed().sum() == 0
    assert sut.counts().sum() == 0
    assert sut.query(CM_PROBE).sum() == 0


def test_topk_recall_on_zipf_traffic():
    """topk(k) recovers >= 0.9 of the true top-10 under Zipf(1.1) streams
    (the acceptance bar: heavy ids must survive Topkapi label voting and
    count-min ranking at production-ish d=4, w=1024)."""
    rows = 3
    cfg = CMConfig(depth=4, width=1024, seed=7)
    rng = np.random.default_rng(42)
    n = 50_000
    items = np.minimum(rng.zipf(1.1, size=n), 1 << 20).astype(np.int32)
    keys = rng.integers(0, rows, n).astype(np.int32)
    oracle = CounterReferenceModel(rows)
    sut = CountMinSUT(rows, cfg)
    sut.update(keys, items)
    oracle.update(keys, items)
    got_vals, got_counts = sut.topk(10)
    truth = oracle.top_k(10)
    recalls = []
    for r in range(rows):
        true_set = set(truth[r])
        got = set(int(v) for v in got_vals[r])
        recalls.append(len(got & true_set) / max(1, len(true_set)))
    assert float(np.mean(recalls)) >= 0.9, recalls
    # the reported counts are count-min estimates: upper bounds on truth
    live = oracle.live_counters()
    for r in range(rows):
        for v, c in zip(got_vals[r], got_counts[r]):
            if c > 0:
                assert int(c) >= live[r][int(v)]


# ----------------------------------------------------------------------------
# hypothesis-generated op sequences (skipped when hypothesis is absent)
# ----------------------------------------------------------------------------

if HAVE_HYPOTHESIS:
    op_seeds = st.lists(
        st.integers(min_value=0, max_value=2**31 - 1), min_size=1, max_size=6
    )
else:  # pragma: no cover - placeholder consumed by the stubbed @given
    op_seeds = None


# no @settings here: the example budget comes from the loaded profile
# (ci/nightly/dev — tests/hypothesis_compat.py), so the nightly schedule
# actually deepens this sweep
@given(seeds=op_seeds, windowed=st.booleans())
@example(seeds=[0], windowed=True)
def test_hypothesis_ops_hybrid_matches_dense_and_oracle(seeds, windowed):
    """Generated sequences: hybrid == dense bit-for-bit, both in-band."""
    window = 3
    rng = np.random.default_rng(seeds[0])
    ops = []
    for s in seeds:
        op_rng = np.random.default_rng(s)
        ops.extend(gen_ops(op_rng, ROWS, n_ops=3, windowed=windowed))
    if windowed:
        dense = DenseWindowSUT(window, ROWS, CFG)
        hybrid = HybridWindowSUT(window, ROWS, CFG, threshold=8)
    else:
        dense = DenseBankSUT(ROWS, CFG)
        hybrid = HybridBankSUT(ROWS, CFG, threshold=8)
    oracle_a = ReferenceModel(ROWS, window=window if windowed else None)
    oracle_b = ReferenceModel(ROWS, window=window if windowed else None)
    run_ops(ops, dense, oracle_a)
    run_ops(ops, hybrid, oracle_b)
    np.testing.assert_array_equal(dense.counts(), oracle_a.observed())
    np.testing.assert_array_equal(hybrid.counts(), oracle_a.observed())
    d = dense.canonical()
    h = hybrid.canonical()
    np.testing.assert_array_equal(h[0], d[0])  # materialized registers
    np.testing.assert_array_equal(h[1], d[1])  # exact counters
    true = oracle_a.true_cardinalities()
    assert_within_band(dense.estimates(), true, CFG.m)
    assert_within_band(hybrid.estimates(), true, CFG.m)
