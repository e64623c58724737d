#!/usr/bin/env python3
"""On-chip smoke check: the sketch engine's main paths on a TPU.

    python chip_smoke.py [--seed 0]     # one chip: every phase below
    python chip_smoke.py --chips 4      # four chips: the sharded serve path
                                        # and its local twin, nothing else

Each phase drives the engine through the entry points a user calls, on
data made from ``--seed``, and checks what the chip returned against plain
numpy that shares no code with the engine:

  serve    CoalescingQueue.submit / flush_into -> HybridBank and a dense
           SketchBank under the default plan: B=65,536 tenants at p=12 with
           a 64-bit hash (256 MiB of dense registers), YCSB zipfian tenant
           popularity (constant 0.99), 4 ticks of 2^22 (key, 32-bit item)
           pairs, then one estimate_many over every row.  Estimates sit in
           the Bonferroni band around the exact per-tenant distinct counts;
           hybrid registers equal the dense ones bit for bit.
  window   WindowedBank W=8, B=4,096, p=12 (128 MiB): 8 epochs of 2^20
           pairs with an advance() between, then estimate_window() and
           estimate_window(1).  In band against the live epochs; the
           incremental read equals a cold fold of the ring bit for bit.
  heavy    CountMinBank B=4,096, d=4, w=1,024 (64 MiB): 2^22 Zipf items,
           then topk(10).  Mean recall >= 0.9 on the loaded rows.
  paper    one p=16, 64-bit-hash sketch over 2^26 32-bit items through
           update_registers (jnp plan).  Within 3 sigma of the exact count.
  kernels  every pallas / pallas_pipelined backend on the seven registry
           axes, compiled, at p=12, B=1,024, 2^19 items: equal to the jnp
           backend bit for bit.

Times on ``[smoke]`` lines are smoke times of one warmed run each, not
benchmark numbers.  The last line of stdout is one JSON object,
``{"ok": true, "device": {...}}``, printed only when every phase passed on
a TPU, every Pallas call ran compiled (none fell back to the interpreter),
each Pallas registry backend dispatched, and no sparse dedup fell back.
Off TPU the script exits non-zero before any phase.  The persistent
compilation cache is ``repro.compat.enable_compilation_cache``'s.

``CHIP_SMOKE_REHEARSAL=1`` is for tests and rehearsals only: every phase
runs at a tiny size on any backend, and the script then refuses at the
device check.
"""

from __future__ import annotations

import argparse
import collections
import json
import os
import statistics
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)), "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402
from jax import monitoring  # noqa: E402

from repro.compat import enable_compilation_cache  # noqa: E402
from repro.launch.mesh import make_auto_mesh  # noqa: E402
from repro.obs import metrics  # noqa: E402
from repro.serve.coalesce import CoalescingQueue  # noqa: E402
from repro.sketch import (  # noqa: E402
    CMConfig,
    CountMinBank,
    ExecutionPlan,
    HLLConfig,
    HybridBank,
    SketchBank,
    WindowedBank,
    backends,
    estimate_many,
    get_backend,
    get_bank_backend,
    get_cm_backend,
    get_cm_window_backend,
    get_sparse_backend,
    get_window_backend,
    get_window_merge_backend,
    init_registers,
    update_registers,
)

REHEARSAL = os.environ.get("CHIP_SMOKE_REHEARSAL") == "1"

FULL = dict(
    serve_rows=1 << 16, serve_tick=1 << 22, serve_ticks=4, serve_submits=64,
    win_rows=4096, win_epochs=8, win_items=1 << 20,
    hh_rows=4096, hh_items=1 << 22, hh_loaded=1024,
    paper_items=1 << 26,
    k_rows=1024, k_items=1 << 19,  # each one-hot kernel call well under 30 s
)
TINY = dict(
    serve_rows=512, serve_tick=1 << 13, serve_ticks=2, serve_submits=4,
    win_rows=64, win_epochs=4, win_items=1 << 12,
    hh_rows=16, hh_items=1 << 15, hh_loaded=1024,
    paper_items=1 << 16,
    k_rows=4, k_items=1 << 10,
)
SIZES = TINY if REHEARSAL else FULL

CFG = HLLConfig(p=12, hash_bits=64)
PAPER_CFG = HLLConfig(p=16, hash_bits=64)
CM_CFG = CMConfig(depth=4, width=1024)
BAND_ALPHA = 0.01  # family-wise budget of the per-row estimate band
PALLAS = ("pallas", "pallas_pipelined")

_COMPILES = collections.Counter()


def _on_event(event, **_):
    _COMPILES[event] += 1


def _on_duration(event, duration, **_):
    if event == "/jax/core/compile/backend_compile_duration":
        _COMPILES["backend_compiles"] += 1
        _COMPILES["backend_compile_s"] += duration


def say(msg: str) -> None:
    print(f"[smoke] {msg}", flush=True)


def refuse(msg: str) -> None:
    print(f"chip_smoke: {msg}", file=sys.stderr, flush=True)
    sys.exit(2)


# ----------------------------------------------------------------------------
# traffic and references (numpy only)
# ----------------------------------------------------------------------------


def ycsb_zipfian(n: int, size: int, rng, theta: float = 0.99) -> np.ndarray:
    """YCSB's ZipfianGenerator (Gray et al. 1994): ids in [0, n), 0 hottest."""
    zetan = np.sum(1.0 / np.arange(1, n + 1, dtype=np.float64) ** theta)
    zeta2 = 1.0 + 0.5**theta
    alpha = 1.0 / (1.0 - theta)
    eta = (1.0 - (2.0 / n) ** (1.0 - theta)) / (1.0 - zeta2 / zetan)
    u = rng.random(size)
    uz = u * zetan
    ids = (n * (eta * u - eta + 1.0) ** alpha).astype(np.int64)
    ids = np.where(uz < 1.0 + 0.5**theta, 1, ids)
    ids = np.where(uz < 1.0, 0, ids)
    return np.minimum(ids, n - 1).astype(np.int32)


def keyed_traffic(rows: int, n: int, rng):
    keys = ycsb_zipfian(rows, n, rng)
    items = rng.integers(0, 1 << 32, n, dtype=np.uint32)
    return keys, items


def exact_distinct(keys, items, rows: int) -> np.ndarray:
    """(rows,) exact distinct items per key: unique (key << 32) | item."""
    pairs = (keys.astype(np.uint64) << np.uint64(32)) | items.astype(np.uint64)
    uniq = np.unique(pairs)
    return np.bincount((uniq >> np.uint64(32)).astype(np.int64), minlength=rows)


def assert_in_band(est, true, cfg: HLLConfig, what: str) -> None:
    """Bonferroni band over the rows (z = Phi^-1(1 - alpha / 2B)) plus the
    small-count slack ``bench_sparse`` uses."""
    est = np.asarray(est, np.float64)
    true = np.asarray(true, np.float64)
    z = statistics.NormalDist().inv_cdf(1.0 - BAND_ALPHA / (2.0 * true.size))
    tol = z * (1.04 / np.sqrt(cfg.m)) * true + 3.0 * np.sqrt(true + 1.0)
    err = np.abs(est - true)
    worst = int(np.argmax(err - tol))
    assert (err <= tol).all(), (
        f"{what}: row {worst} estimate {est[worst]:.1f} vs exact {true[worst]:.0f} "
        f"leaves the {z:.2f}-sigma band (tol {tol[worst]:.1f})"
    )


def same(a, b, what: str) -> None:
    a, b = np.asarray(a), np.asarray(b)
    assert a.shape == b.shape and np.array_equal(a, b), f"{what}: not bit-identical"


def timed_phase(run, *args):
    """Warm every shape with one untimed run, then time a second one."""
    jax.block_until_ready(run(*args))
    t0 = time.perf_counter()
    out = jax.block_until_ready(run(*args))
    secs = time.perf_counter() - t0
    return out, secs


def report(name: str, secs: float, note: str = "") -> None:
    stats = jax.devices()[0].memory_stats() or {}
    peak = stats.get("peak_bytes_in_use", "not reported")
    say(f"{name}: pass  smoke time {secs:.3f} s  peak_bytes_in_use {peak}{note}")


# ----------------------------------------------------------------------------
# phases
# ----------------------------------------------------------------------------


def serve_traffic(rng):
    n = SIZES["serve_tick"] * SIZES["serve_ticks"]
    return keyed_traffic(SIZES["serve_rows"], n, rng)


def serve_ingest(keys, items, plan):
    """Ticks of coalesced submits into a dense and a hybrid bank, then one
    estimate_many over every row of each."""
    rows = SIZES["serve_rows"]
    dense = SketchBank.empty(rows, CFG)
    hybrid = HybridBank.empty(rows, CFG)
    q_dense, q_hybrid = CoalescingQueue(), CoalescingQueue()
    for tick_keys, tick_items in zip(
        np.array_split(keys, SIZES["serve_ticks"]),
        np.array_split(items, SIZES["serve_ticks"]),
    ):
        for k, x in zip(
            np.array_split(tick_keys, SIZES["serve_submits"]),
            np.array_split(tick_items, SIZES["serve_submits"]),
        ):
            q_dense.submit(k, x)
            q_hybrid.submit(k, x)
        dense = q_dense.flush_into(dense, plan)
        hybrid = q_hybrid.flush_into(hybrid, plan)
    dense_est = dense.estimate_many(plan=plan)
    hybrid_est = hybrid.estimate_many(plan=plan)
    return dense, hybrid, dense_est, hybrid_est


def check_serve(keys, items, dense, hybrid, dense_est, hybrid_est, what):
    rows = SIZES["serve_rows"]
    same(hybrid.to_dense().registers, dense.registers, f"{what}: hybrid registers")
    same(hybrid_est, dense_est, f"{what}: hybrid estimates")
    same(dense.counts, np.bincount(keys, minlength=rows), f"{what}: counters")
    assert_in_band(dense_est, exact_distinct(keys, items, rows), CFG, what)


def phase_serve(rng):
    keys, items = serve_traffic(rng)
    out, secs = timed_phase(serve_ingest, keys, items, ExecutionPlan())
    check_serve(keys, items, *out, what="serve")
    dense_rows = out[1].dense_rows
    report("serve", secs, f"  hybrid dense rows {dense_rows}/{SIZES['serve_rows']}")


def window_ingest(epochs):
    win = WindowedBank.empty(len(epochs), SIZES["win_rows"], CFG)
    for e, (k, x) in enumerate(epochs):
        if e:
            win = win.advance()
        win = win.observe(jnp.asarray(k), jnp.asarray(x))
    full = win.estimate_window()
    newest = win.estimate_window(1)
    return win, full, newest


def phase_window(rng):
    rows, w = SIZES["win_rows"], SIZES["win_epochs"]
    epochs = [keyed_traffic(rows, SIZES["win_items"], rng) for _ in range(w)]
    (win, full, newest), secs = timed_phase(window_ingest, epochs)
    keys = np.concatenate([k for k, _ in epochs])
    items = np.concatenate([x for _, x in epochs])
    cold_full = np.max(np.asarray(win.registers), axis=0)  # every slot is live
    same(win.fold_window().registers, cold_full, "window: incremental vs cold fold")
    same(full, estimate_many(jnp.asarray(cold_full), CFG), "window: full estimates")
    flat = SketchBank.empty(rows, CFG).update_many(keys, items)
    same(cold_full, flat.registers, "window: ring fold vs flat bank")
    last = SketchBank.empty(rows, CFG).update_many(*epochs[-1])
    same(win.fold_window(1).registers, last.registers, "window: newest epoch")
    same(newest, estimate_many(last.registers, CFG), "window: newest estimates")
    assert_in_band(full, exact_distinct(keys, items, rows), CFG, "window full")
    assert_in_band(newest, exact_distinct(*epochs[-1], rows), CFG, "window newest")
    report("window", secs)


def phase_heavy(rng):
    rows = SIZES["hh_rows"]
    n = SIZES["hh_items"]
    keys = ycsb_zipfian(rows, n, rng)
    items = np.minimum(rng.zipf(1.1, size=n), 1 << 20).astype(np.int32)
    keys_d, items_d = jnp.asarray(keys), jnp.asarray(items)

    def run():
        bank = CountMinBank.empty(rows, CM_CFG).update_many(keys_d, items_d)
        return bank, bank.topk(10)

    (bank, (vals, cnts)), secs = timed_phase(run)
    pairs = (keys.astype(np.uint64) << np.uint64(32)) | items.astype(np.uint64)
    uniq, freq = np.unique(pairs, return_counts=True)
    row = (uniq >> np.uint64(32)).astype(np.int64)
    item = (uniq & np.uint64(0xFFFFFFFF)).astype(np.int64)
    order = np.lexsort((-freq, row))
    row, item, freq = row[order], item[order], freq[order]
    starts = np.searchsorted(row, np.arange(rows + 1))
    loaded = np.nonzero(np.bincount(keys, minlength=rows) >= SIZES["hh_loaded"])[0]
    assert loaded.size, "heavy: no loaded rows"
    recalls = []
    for r in loaded:
        f, it = freq[starts[r]:starts[r + 1]], item[starts[r]:starts[r + 1]]
        truth = dict(zip(it.tolist(), f.tolist()))
        tenth = f[min(9, f.size - 1)]
        true_top = set(it[f >= tenth].tolist())
        got = [(int(v), int(c)) for v, c in zip(vals[r], cnts[r]) if c > 0]
        recalls.append(len({v for v, _ in got} & true_top) / min(10, f.size))
        for v, c in got:  # a count-min answer never undercounts
            assert c >= truth.get(v, 0), f"heavy: row {r} item {v} undercounted"
    recall = float(np.mean(recalls))
    assert recall >= 0.9, f"heavy: mean top-10 recall {recall:.3f} < 0.9"
    report("heavy", secs, f"  mean recall {recall:.4f} over {loaded.size} loaded rows")


def phase_paper(rng):
    items = rng.integers(0, 1 << 32, SIZES["paper_items"], dtype=np.uint32)
    items_d = jax.device_put(items)
    plan = ExecutionPlan()

    def run():
        regs = update_registers(init_registers(PAPER_CFG), items_d, PAPER_CFG, plan)
        return estimate_many(regs[None, :], PAPER_CFG)[0]

    est, secs = timed_phase(run)
    true = np.unique(items).size
    sigma = 1.04 / np.sqrt(PAPER_CFG.m)
    err = abs(float(est) - true) / true
    assert err <= 3 * sigma, f"paper: relative error {err:.5f} > 3 sigma"
    report("paper", secs, f"  estimate {float(est):.1f} exact {true}")


AXES = (
    "update", "bank_update", "window_fold", "window_merge",
    "sparse_dedup", "cm_update", "cm_window_fold",
)


def kernel_cases(rng):
    """(axis, call(backend_name) -> comparable arrays) per registry axis."""
    b, n, m, w = SIZES["k_rows"], SIZES["k_items"], CFG.m, 8
    items = jnp.asarray(rng.integers(0, 1 << 32, n, dtype=np.uint32))
    keys = jnp.asarray(rng.integers(-1, b + 1, n).astype(np.int32))
    regs = jnp.asarray(rng.integers(0, 8, m).astype(np.uint8))
    bank = jnp.asarray(rng.integers(0, 8, (b, m)).astype(np.uint8))
    ring = jnp.asarray(rng.integers(0, 54, (w, b, m)).astype(np.uint8))
    mask = jnp.asarray(np.arange(w) % 3 != 1)
    parts = ring[:3]
    row = jnp.asarray(rng.integers(-1, b + 1, n).astype(np.int32))
    bucket = jnp.asarray(rng.integers(0, m, n).astype(np.int32))
    rank = jnp.asarray(rng.integers(1, 54, n).astype(np.int32))
    cms = jnp.asarray(
        rng.integers(0, 1 << 32, (b, CM_CFG.depth, CM_CFG.width), dtype=np.uint32)
    )
    cm_ring = jnp.asarray(
        rng.integers(0, 1 << 32, (w, b, CM_CFG.depth, CM_CFG.width), dtype=np.uint32)
    )

    def plan(name):
        return ExecutionPlan(backend=name)

    def sparse(name):
        out = get_sparse_backend(name)(row, bucket, rank, b, CFG, plan(name))
        return out.cells, out.distinct

    return {
        "update": lambda nm: get_backend(nm)(regs, items, CFG, plan(nm)),
        "bank_update": lambda nm: get_bank_backend(nm)(
            bank, keys, items, CFG, plan(nm)
        ),
        "window_fold": lambda nm: get_window_backend(nm)(ring, mask, CFG, plan(nm)),
        "window_merge": lambda nm: get_window_merge_backend(nm)(parts, CFG, plan(nm)),
        "sparse_dedup": sparse,
        "cm_update": lambda nm: get_cm_backend(nm).ingest(
            cms, keys, items, CM_CFG, plan(nm)
        ),
        "cm_window_fold": lambda nm: get_cm_window_backend(nm)(
            cm_ring, mask, CM_CFG, plan(nm)
        ),
    }


def phase_kernels(rng):
    cases = kernel_cases(rng)
    for axis in AXES:
        want = jax.block_until_ready(cases[axis]("jnp"))
        for name in PALLAS:
            got, secs = timed_phase(cases[axis], name)
            for g, w_ in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
                same(g, w_, f"kernels: {axis}[{name}] vs jnp")
            calls = metrics.counter_value(f"dispatch.{axis}.{name}.calls")
            assert calls > 0, f"kernels: {axis}[{name}] never dispatched"
            report(f"kernels {axis}[{name}]", secs, f"  dispatches {calls:g}")


# ----------------------------------------------------------------------------
# four chips: sharded serve path against local placement
# ----------------------------------------------------------------------------


def phase_sharded(rng, chips: int):
    mesh = make_auto_mesh((chips,), ("data",))
    sharded_plan = ExecutionPlan().with_sharding(mesh)
    keys, items = serve_traffic(rng)
    local, secs_local = timed_phase(serve_ingest, keys, items, ExecutionPlan())
    sharded, secs_sharded = timed_phase(serve_ingest, keys, items, sharded_plan)
    check_serve(keys, items, *local, what="serve local")
    check_serve(keys, items, *sharded, what="serve sharded")
    for name, a, b in (
        ("dense registers", local[0].registers, sharded[0].registers),
        ("hybrid registers", *(r[1].to_dense().registers for r in (local, sharded))),
        ("dense estimates", local[2], sharded[2]),
        ("hybrid estimates", local[3], sharded[3]),
    ):
        same(a, b, f"sharded vs local {name}")
    rows = SIZES["serve_rows"]
    shards = sharded[0].registers.addressable_shards
    devices = {s.device for s in shards}
    assert len(devices) == chips, f"sharded bank on {len(devices)} of {chips} devices"
    for s in shards:
        assert s.data.shape[0] == rows // chips, (
            f"device {s.device} holds {s.data.shape[0]} rows, expected {rows // chips}"
        )
    report("serve local", secs_local)
    report("serve sharded", secs_sharded, f"  {rows // chips} rows on each of {chips}")


# ----------------------------------------------------------------------------


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4 runs only the sharded serve path and its local twin")
    args = ap.parse_args()

    device = jax.devices()[0]
    if device.platform != "tpu" and not REHEARSAL:
        refuse(f"no TPU: JAX reports platform {device.platform!r}")
    if len(jax.devices()) < args.chips:
        refuse(f"--chips {args.chips}: JAX has {len(jax.devices())} devices")
    cache_dir = enable_compilation_cache()
    monitoring.register_event_listener(_on_event)
    monitoring.register_event_duration_secs_listener(_on_duration)
    metrics.enable()
    say(f"device {device.platform} {device.device_kind} x{len(jax.devices())}, "
        f"jax {jax.__version__}, seed {args.seed}, compile cache {cache_dir}")

    rng = np.random.default_rng(args.seed)
    t0 = time.perf_counter()
    if args.chips == 4:
        phase_sharded(rng, args.chips)
    else:
        phase_serve(rng)
        phase_window(rng)
        phase_heavy(rng)
        phase_paper(rng)
        phase_kernels(rng)
    say(f"all phases: {time.perf_counter() - t0:.3f} s smoke time; "
        f"{_COMPILES['backend_compiles']} backend compiles "
        f"({_COMPILES['backend_compile_s']:.3f} s), persistent cache hits "
        f"{_COMPILES['/jax/compilation_cache/cache_hits']} misses "
        f"{_COMPILES['/jax/compilation_cache/cache_misses']}")

    if device.platform != "tpu":
        refuse(f"rehearsal finished; no TPU (platform {device.platform!r}), no result")
    modes = dict(backends.PALLAS_MODES)
    if modes.get("interpret", 0):
        refuse(f"Pallas ran in interpret mode on the chip: {modes}")
    if args.chips == 1 and not modes.get("compiled", 0):
        refuse("no Pallas kernel ran compiled")
    fallbacks = metrics.counter_value("dispatch.sparse_dedup.fallback")
    if fallbacks:
        refuse(f"sparse dedup fell back to jnp {fallbacks:g} times")
    print(json.dumps({"ok": True, "device": {
        "platform": device.platform,
        "kind": device.device_kind,
        "count": len(jax.devices()),
    }}))


if __name__ == "__main__":
    main()
