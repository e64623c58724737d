"""The four-chip fleet cell, ``tenants_1m.ingest``, rehearsed on four forced
CPU devices, and the readers of its row-block routing metrics.

The rehearsals run in subprocesses, because the device count is pinned
before JAX initializes: the run must refuse after a ``correct`` rehearsal,
a traced run must read every per-layer metric the cell lists, and a planted
fault and the 32-bit-hash control must come out not correct.
"""

import json
import os
import subprocess
import sys
import textwrap
import types

import numpy as np
import pytest

from chipbench.bench import harness
from chipbench.systems.hybrid_fleet import fnv1a64_rows

CELL = "tenants_1m.ingest"
RUN = os.path.join(harness.BENCH_DIR, "run.py")
BIG_SEED = "4294967311"  # over 32 bits


def _env():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    env[harness.REHEARSAL_ENV] = "1"
    src = os.path.join(harness.ROOT, "src")
    env["PYTHONPATH"] = os.pathsep.join([harness.ROOT, src])
    return env


def test_fleet_rehearses_on_four_devices_then_refuses():
    out = subprocess.run(
        [sys.executable, RUN, "--workload", CELL, "--seed", BIG_SEED,
         "--seconds", "1", "--trace", "0"],
        capture_output=True, text=True, env=_env(), timeout=600,
    )
    assert out.returncode == 2, out.stderr[-3000:]
    assert "rehearsal finished (correct=True)" in out.stderr, out.stderr[-3000:]
    assert f"[chipbench] {CELL} seed {BIG_SEED}" in out.stdout
    checks = [ln for ln in out.stderr.splitlines() if ln.startswith("check ")]
    assert {ln.split()[1] for ln in checks} == {
        "reg_rows_wrong", "count_rows_wrong", "est_rel_gap", "unfinished"
    }


RUNS = r"""
import json, time
import jax
from chipbench.bench import harness
from chipbench.control import CONTROLS
from repro.sketch import HybridBank

cell = harness.load_cell("tenants_1m.ingest", rehearsal=True)
assert jax.device_count() == 4
out = {}
traced = harness.execute(cell, 11, 1.0, True, time.perf_counter(), say=lambda _: None)
out["traced"] = {"correct": traced["correct"], "metrics": traced["metrics"]}
control = harness.execute(cell, 12, 0.5, False, time.perf_counter(),
                          control=CONTROLS["hash32"], say=lambda _: None)
out["control"] = {"correct": control["correct"], "checks": control["checks"]}
original = HybridBank.update_many
def half(self, keys, items, plan=None):
    n = len(keys) // 2
    return original(self, keys[:n], items[:n], plan)
HybridBank.update_many = half
fault = harness.execute(cell, 13, 0.5, False, time.perf_counter(), say=lambda _: None)
out["fault"] = {"correct": fault["correct"], "checks": fault["checks"]}
print("RESULT " + json.dumps(out))
"""


@pytest.fixture(scope="module")
def runs():
    out = subprocess.run(
        [sys.executable, "-c", textwrap.dedent(RUNS)],
        capture_output=True, text=True, env=_env(), timeout=900, cwd=harness.ROOT,
    )
    assert out.returncode == 0, out.stderr[-3000:]
    line = next(ln for ln in out.stdout.splitlines() if ln.startswith("RESULT "))
    return json.loads(line[len("RESULT "):])


def test_traced_rehearsal_reads_every_metric_of_the_cell(runs):
    cell = harness.load_cell(CELL, rehearsal=True)
    assert runs["traced"]["correct"]
    got = runs["traced"]["metrics"]
    for m in cell.per_layer:
        assert got.get(m["name"], {}).get("value") is not None, m["name"]
    assert got["shard_skew.fleet"]["value"] >= 1.0


def test_control_and_planted_fault_are_not_correct(runs):
    assert not runs["control"]["correct"]
    gap = runs["control"]["checks"]["est_rel_gap"]
    assert gap["value"] > gap["limit"]
    assert not runs["fault"]["correct"]
    assert runs["fault"]["checks"]["count_rows_wrong"]["value"] > 0


def _fnv_reference(rank: int, rows: int) -> int:
    """YCSB ``Utils.fnvhash64`` in Python integers, then ``% rows``."""
    h = 0xCBF29CE484222325
    for _ in range(8):
        h ^= rank & 0xFF
        rank >>= 8
        h = (h * 0x100000001B3) & 0xFFFFFFFFFFFFFFFF
    signed = h - (1 << 64) if h >> 63 else h
    return abs(signed) % rows


def test_scrambled_zipfian_map_is_ycsb_fnv():
    ranks = np.array([0, 1, 2, 255, 256, 65537, 999_999, 123_456_789])
    got = fnv1a64_rows(ranks, 1_000_000)
    assert got.tolist() == [_fnv_reference(int(r), 1_000_000) for r in ranks]
    rows = fnv1a64_rows(np.arange(4099), 4099)
    assert rows.min() >= 0 and rows.max() < 4099


def _ctx(counters, ticks=4):
    return types.SimpleNamespace(counters=counters, counts={"ticks": ticks})


def test_split_reader_hand_computed():
    ctx = _ctx({"sparse.shard.split.seconds": 0.5, "sparse.shard.split.calls": 4})
    assert harness.read_metric("split_ms.fleet", ctx) == pytest.approx(125.0)
    assert harness.read_metric("split_ms.fleet", _ctx({})) is None
    no_ticks = _ctx({"sparse.shard.split.seconds": 1.0}, ticks=0)
    assert harness.read_metric("split_ms.fleet", no_ticks) is None


def test_skew_reader_hand_computed():
    counters = {f"sparse.shard.pairs.{d}": v for d, v in enumerate((10, 30, 20, 20))}
    counters["sparse.route.seconds"] = 9.0  # not a block counter
    assert harness.read_metric("shard_skew.fleet", _ctx(counters)) == pytest.approx(1.5)
    even = {f"sparse.shard.pairs.{d}": 7 for d in range(4)}
    assert harness.read_metric("shard_skew.fleet", _ctx(even)) == pytest.approx(1.0)
    assert harness.read_metric("shard_skew.fleet", _ctx({})) is None
    zero = {f"sparse.shard.pairs.{d}": 0 for d in range(4)}
    assert harness.read_metric("shard_skew.fleet", _ctx(zero)) is None
