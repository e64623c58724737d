"""HybridBank in row blocks under the sharded placement, and past 2^31 cells.

DESIGN.md §12 and §16: a sharded plan over several devices splits a
HybridBank into contiguous tenant-row blocks, one local bank per device,
and every read must stay bit-identical to the same ops under a local plan.
The dedup keys on (row, bucket), so a bank (or a block) whose ``B * m``
passes 2^31 ingests, compacts, promotes and reads with no int32 wrap,
while the layouts that flatten (row, bucket) into one id are never picked
there.

The four-device legs run in one subprocess (the device count is pinned
before jax initializes) and report to several tests through a module
fixture; the wide-cell-space legs run in process on one device.
"""

import json
import os
import subprocess
import sys
import textwrap

import jax.numpy as jnp
import numpy as np
import pytest

from repro.obs import metrics
from repro.sketch import (
    ExecutionPlan,
    HLLConfig,
    HybridBank,
    HyperLogLog,
    dedup_pairs,
)
from tests.reference_model import ReferenceModel, assert_within_band

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

FOUR_DEVICES = r"""
import json
import numpy as np
import jax, jax.numpy as jnp
assert jax.device_count() == 4, jax.device_count()
from repro.launch.mesh import make_auto_mesh
from repro.obs import metrics
from repro.serve.coalesce import CoalescingQueue
from repro.sketch import ExecutionPlan, HLLConfig, HybridBank
from repro.sketch import sparse
from tests.reference_model import (
    HybridBankSUT, ReferenceModel, assert_within_band, gen_ops, gen_stream,
)

# pressure compactions fire inside ingest at these sizes, per block
sparse._FLUSH_MIN_PAIRS = 64
cfg = HLLConfig(p=8, hash_bits=64)
mesh = make_auto_mesh((4,), ("data",))
out = {"oracle": [], "local": [], "layout": None}

# 37 rows: blocks of 10, 10, 10 and 7 rows
rows = 37
for backend in ("jnp", "pallas"):
    local = ExecutionPlan(backend=backend)
    sharded = local.with_sharding(mesh)
    for seed in (0, 1, 2):
        rng = np.random.default_rng(100 * seed + len(backend))
        ops = gen_ops(rng, rows, 24, windowed=False)
        a = HybridBankSUT(rows, cfg, plan=local, threshold=4)
        b = HybridBankSUT(rows, cfg, plan=sharded, threshold=4)
        oracle = ReferenceModel(rows)
        same_est, band_ok, counts_ok = True, True, True
        for op in ops:
            kind = op[0]
            if kind == "update":
                a.update(op[1], op[2]); b.update(op[1], op[2])
                oracle.update(op[1], op[2])
            elif kind == "merge":
                a.merge(op[1], op[2]); b.merge(op[1], op[2])
                side = ReferenceModel(rows); side.update(op[1], op[2])
                oracle.merge(side)
            elif kind == "roundtrip":
                a.roundtrip(); b.roundtrip()
            elif kind == "peek":
                a.peek(); b.peek()
            elif kind == "estimate":
                est = b.estimates()
                same_est &= bool(np.array_equal(a.estimates(), est))
                try:
                    assert_within_band(est, oracle.true_cardinalities(), cfg.m)
                except AssertionError:
                    band_ok = False
                counts_ok &= bool(np.array_equal(b.counts(), oracle.observed()))
        same_state = all(
            bool(np.array_equal(x, y)) for x, y in zip(a.canonical(), b.canonical())
        )
        out["oracle"].append([backend, seed, band_ok, counts_ok])
        out["local"].append([backend, seed, same_est, same_state])

# the layout: a fresh bank splits at its first sharded ingest
metrics.reset(); metrics.enable()
rng = np.random.default_rng(7)
keys, items = gen_stream(rng, rows, 2000)
plan = ExecutionPlan().with_sharding(mesh)
queue = CoalescingQueue()
queue.submit(keys[:1500], items[:1500]); queue.submit(keys[1500:], items[1500:])
bank = queue.flush_into(HybridBank.empty(rows, cfg, 4), plan)
snap = metrics.snapshot()["counters"]
metrics.disable()
valid = int(((keys >= 0) & (keys < rows)).sum())
routed = [snap.get(f"sparse.shard.pairs.{d}", 0) for d in range(4)]
ref = HybridBank.empty(rows, cfg, 4).update_many(keys, items)
modes = bank.modes  # the first read settles every block on its own device
out["layout"] = {
    "placed": all(
        a.devices() == blk.n_items.devices()
        for blk in bank.compact().blocks
        for a in (blk.pair_buf, blk.pair_len, blk.dense_block, blk.slot_map)
    ),
    "same_modes": bool(np.array_equal(modes, ref.modes)),
    "block_rows": [len(blk) for blk in bank.blocks],
    "devices": len({next(iter(blk.n_items.devices())) for blk in bank.blocks}),
    "routed": routed,
    "valid": valid,
    "per_block_true": [
        int(((keys >= 10 * d) & (keys < min(rows, 10 * d + 10))).sum())
        for d in range(4)
    ],
    "split_calls": snap.get("sparse.shard.split.calls", 0),
    "pressure": snap.get("sparse.flush.pressure", 0),
    "same_bytes": bank.to_bytes() == ref.to_bytes(),
    "same_range": bool(np.array_equal(
        bank.row_registers(8, 31), np.asarray(ref.to_dense().registers)[8:31])),
    "same_row": bool(np.array_equal(
        np.asarray(bank.row(23).registers), np.asarray(ref.row(23).registers))),
    "same_to_dense": all(
        bool(np.array_equal(np.asarray(x), np.asarray(y)))
        for x, y in ((bank.to_dense().registers, ref.to_dense().registers),
                     (bank.to_dense().n_items, ref.to_dense().n_items))),
    "same_density": [bank.density()[k] == ref.density()[k]
                     for k in ("rows", "dense_rows", "sparse_rows", "threshold")],
    "pending_after_read": bank.compact().pending_pairs,
    # the blocks append concurrently, each from its own thread: every row
    # is sparse at the first tick, so a lost counter update would show here
    "appended": snap.get("sparse.pending.pairs", 0),
}

# a one-device mesh does not split the bank: every phase runs locally
one = ExecutionPlan().with_sharding(jax.sharding.Mesh(np.asarray(jax.devices()[:1]), ("data",)))
solo = HybridBank.empty(rows, cfg, 4).update_many(keys, items, one)
out["one_device"] = {
    "blocks": len(solo.blocks),
    "same_bytes": solo.to_bytes() == ref.to_bytes(),
    "same_est": bool(np.array_equal(
        np.asarray(solo.estimate_many(plan=one)), np.asarray(ref.estimate_many()))),
    "pending_plan": solo.pending is None or solo.pending.plan.placement,
}

# a sharded bank larger than one device: to_dense refuses, ranges read
big_rows = (1 << 18) + 5
big = HybridBank.empty(big_rows, HLLConfig(p=16, hash_bits=64)).update_many(
    np.array([0, big_rows - 1, big_rows - 1], np.int32),
    np.arange(3, dtype=np.int32), plan)
refused = {}
# a CPU device reports no memory limit: give it a v5e chip's 16 GiB
sparse._bytes_limit = lambda device: 16 << 30
for name, call in (("to_dense", big.to_dense), ("pairs", lambda: big.pairs)):
    try:
        call()
        refused[name] = None
    except ValueError as e:
        refused[name] = str(e)
regs = big.row_registers(big_rows - 4, big_rows)
out["big"] = {
    "refused": refused,
    "blocks": len(big.blocks),
    "tail_nonzero": [int((r > 0).sum()) for r in regs],
    "counts_tail": [int(c) for c in big.counts[-2:]],
}
print("RESULT " + json.dumps(out))
"""


@pytest.fixture(scope="module")
def four_devices():
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    env["PYTHONPATH"] = os.pathsep.join([os.path.join(REPO, "src"), REPO])
    env["JAX_PLATFORMS"] = "cpu"
    out = subprocess.run(
        [sys.executable, "-c", textwrap.dedent(FOUR_DEVICES)],
        capture_output=True,
        text=True,
        env=env,
        timeout=900,
    )
    assert out.returncode == 0, out.stderr[-3000:]
    line = next(ln for ln in out.stdout.splitlines() if ln.startswith("RESULT "))
    return json.loads(line[len("RESULT "):])


def test_sharded_hybrid_matches_oracle_on_four_devices(four_devices):
    """Estimates within the oracle's band and counters exact, per backend."""
    for backend, seed, band_ok, counts_ok in four_devices["oracle"]:
        assert band_ok, f"{backend} seed {seed}: estimate outside the band"
        assert counts_ok, f"{backend} seed {seed}: counters differ"


def test_sharded_hybrid_bit_identical_to_local(four_devices):
    """Registers, counters, modes and every estimate equal the local run."""
    for backend, seed, same_est, same_state in four_devices["local"]:
        assert same_est, f"{backend} seed {seed}: estimates differ"
        assert same_state, f"{backend} seed {seed}: registers/counts/modes differ"


def test_sharded_hybrid_splits_into_row_blocks(four_devices):
    """The coalescer's tick lands in four blocks on four devices; the split
    routes every valid key to its block and each read equals local."""
    lay = four_devices["layout"]
    assert lay["block_rows"] == [10, 10, 10, 7]
    assert lay["placed"] and lay["same_modes"]
    assert lay["devices"] == 4
    assert lay["routed"] == lay["per_block_true"]
    assert sum(lay["routed"]) == lay["valid"] == lay["appended"]
    assert lay["split_calls"] == 1
    assert lay["pressure"] >= 1  # per-block pressure compaction ran
    assert lay["same_bytes"] and lay["same_range"] and lay["same_row"]
    assert lay["same_to_dense"]
    assert all(lay["same_density"])
    assert lay["pending_after_read"] == 0


def test_one_device_mesh_keeps_the_bank_local(four_devices):
    solo = four_devices["one_device"]
    assert solo["blocks"] == 0
    assert solo["same_bytes"] and solo["same_est"]
    assert solo["pending_plan"] in (True, "local")


def test_to_dense_refuses_a_bank_larger_than_one_device(four_devices):
    big = four_devices["big"]
    assert big["blocks"] == 4
    assert "row_registers" in big["refused"]["to_dense"]
    assert "row-blocked" in big["refused"]["pairs"]
    # the range read still answers: one item in row 0 (not in the range),
    # two in the last row, nothing between
    assert big["tail_nonzero"][:3] == [0, 0, 0]
    assert 1 <= big["tail_nonzero"][3] <= 2
    assert big["counts_tail"] == [0, 2]


# ----------------------------------------------------------------------------
# past 2^31 cells on one device
# ----------------------------------------------------------------------------


WIDE = HLLConfig(p=16, hash_bits=64)  # m = 65,536
WIDE_ROWS = (1 << 15) + 3  # 2,147,680,256 cells > 2^31


def test_wide_cell_space_bank_tracks_the_oracle(monkeypatch):
    """Rows near the top of a 2.15e9-cell bank: ingest, pressure
    compaction, promotion and a read, row by row against the oracle."""
    from repro.sketch import sparse

    assert WIDE_ROWS * WIDE.m >= 1 << 31
    monkeypatch.setattr(sparse, "_FLUSH_MIN_PAIRS", 4096)
    rng = np.random.default_rng(15)
    top = WIDE_ROWS - 1
    # the top row promotes (past m/4 = 16,384 distinct buckets); the rows
    # under it stay sparse; a low row and out-of-range keys ride along
    keys = np.concatenate(
        [
            np.full(26000, top),
            rng.integers(WIDE_ROWS - 40, top, 1500),
            np.full(30, 5),
            [-1, WIDE_ROWS, WIDE_ROWS + 9],
        ]
    ).astype(np.int32)
    items = rng.integers(0, 2**31, keys.size, dtype=np.int32)
    order = rng.permutation(keys.size)
    keys, items = keys[order], items[order]

    metrics.reset()
    metrics.enable()
    try:
        bank = HybridBank.empty(WIDE_ROWS, WIDE)
        oracle = ReferenceModel(WIDE_ROWS)
        for s in range(0, keys.size, 5000):
            k, x = keys[s : s + 5000], items[s : s + 5000]
            bank = bank.update_many(jnp.asarray(k), jnp.asarray(x))
            oracle.update(k, x)
        est = np.asarray(bank.estimate_many())
        snap = metrics.snapshot()["counters"]
    finally:
        metrics.disable()
        metrics.reset()

    assert snap.get("sparse.flush.pressure", 0) >= 1
    assert snap.get("sparse.dedup.wide", 0) >= 2
    assert snap.get("sparse.promotions", 0) == 1
    assert bank.modes[top] == 1 and bank.modes[:-1].sum() == 0
    np.testing.assert_array_equal(bank.counts, oracle.observed())

    def oracle_registers(r):
        row_items = np.asarray(sorted(oracle.epoch_sets[-1][r]), np.int32)
        sketch = HyperLogLog.empty(WIDE).update(jnp.asarray(row_items))
        return np.asarray(sketch.registers)

    lo = WIDE_ROWS - 40
    got = bank.row_registers(lo, WIDE_ROWS)
    for r in range(lo, WIDE_ROWS):
        np.testing.assert_array_equal(
            got[r - lo], oracle_registers(r), err_msg=f"row {r}"
        )
    np.testing.assert_array_equal(
        np.asarray(bank.row(5).registers), oracle_registers(5)
    )
    assert_within_band(est, oracle.true_cardinalities(), WIDE.m)


@pytest.mark.parametrize("backend", ["jnp", "pallas", "pallas_pipelined"])
def test_cells_layout_never_chosen_past_int32(monkeypatch, backend):
    """Even when the crossover would pick the dense-cells map, a dedup
    whose rows * m reaches 2^31 takes the (row, bucket) sort, whatever the
    plan's backend; one row under that it takes the cells map."""
    from repro.sketch import backends

    monkeypatch.setattr(backends, "_SPARSE_CELLS_CROSSOVER", 1 << 40)
    cfg = HLLConfig(p=12, hash_bits=64)  # m = 4096: pallas-capable
    wide_rows = (1 << 31) // cfg.m  # exactly 2^31 cells
    row = jnp.asarray([0, wide_rows - 1, wide_rows - 1, 3], jnp.int32)
    bucket = jnp.asarray([7, 9, 9, 0], jnp.int32)
    rank = jnp.asarray([2, 3, 5, 1], jnp.int32)
    plan = ExecutionPlan(backend=backend)
    metrics.reset()
    metrics.enable()
    try:
        wide = dedup_pairs(row, bucket, rank, wide_rows, cfg, plan)
        wide_count = metrics.counter_value("sparse.dedup.wide")
    finally:
        metrics.disable()
        metrics.reset()
    assert wide.cells is None and wide.row_s is not None
    assert wide_count == 1
    survivors = np.asarray(wide.survivor)
    kept = sorted(
        zip(
            np.asarray(wide.row_s)[survivors].tolist(),
            np.asarray(wide.bucket_s)[survivors].tolist(),
            np.asarray(wide.rank_s)[survivors].tolist(),
        )
    )
    assert kept == [(0, 7, 2), (3, 0, 1), (wide_rows - 1, 9, 5)]
    distinct = np.asarray(wide.distinct)
    assert distinct[[0, 3, wide_rows - 1]].tolist() == [1, 1, 1]
    assert int(distinct.sum()) == 3

    narrow_rows = 64
    narrow = dedup_pairs(
        jnp.asarray([0, 63, 63, 3], jnp.int32), bucket, rank, narrow_rows, cfg, plan
    )
    assert narrow.cells is not None
