"""Benchmark harness: one function per paper table/figure.

Prints ``name,us_per_call,derived`` CSV rows.  ``--full`` widens sweeps;
``--smoke`` shrinks every bench to seconds-scale sizes (the CI bench-smoke
job runs this, so benchmark scripts can no longer rot unexecuted).

  fig1  error vs cardinality, (p,H) x estimator sweep    (paper Fig. 1)
  fig4a throughput scaling vs #pipelines                 (paper Fig. 4a)
  fig4b hash-width cost, CPU-analogue baseline           (paper Fig. 4b)
  tab2  memory footprint grid                            (paper Tab. II)
  tab3  per-pipeline resource analogue (HLO + VMEM)      (paper Tab. III)
  tab4  sustained streaming throughput + finalization    (paper Tab. IV)
  estimators  accuracy + finalization latency per estimator, single vs
              batched; also writes BENCH_estimators.json
  bank  batched multi-tenant ingest (update_many vs per-sketch loop);
        also writes BENCH_bank_streaming.json
  window  sliding-window query (fused ring fold vs per-bucket merge loop);
          also writes BENCH_window.json
  sparse  hybrid sparse/dense tenant-row storage (memory + ingest latency
          vs the dense bank under Zipf traffic); writes BENCH_sparse.json
  heavy   heavy-hitter ingest (fused d-hash scatter vs per-row loop);
          writes BENCH_heavy.json
  obs   observability overhead (disabled-mode seam cost vs passthrough,
        gated at 3%); writes BENCH_obs.json
  serve production serve path: coalesced row-sharded ingest vs
        one-request-at-a-time under Zipf traffic, plus read-latency
        p50/p99 (gated at 2x coalesced speedup); writes BENCH_serve.json

JSON-writing benches write in every mode: full runs update the tracked
``BENCH_*.json`` perf trajectory, smoke runs write sibling
``BENCH_*.smoke.json`` files (tagged ``"smoke": true``, gitignored) that
the CI bench-smoke job uploads as artifacts — a smoke run can never
clobber the tracked full-run numbers.  Every payload carries an ``env``
block (jax/jaxlib version, backend platform, CPU count) so trajectory
jumps can be told apart from runner swaps; ``--summary`` renders the
tracked files plus their env stamps as one table without running
anything.

``--metrics-check`` runs the suite with metrics ENABLED and asserts the
final snapshot round-trips through JSON with the §15 schema, live
dispatch counters and live span counters — the CI hook that keeps the
instrumentation from rotting silently.

A failing sub-benchmark no longer aborts the rest of the suite: every bench
runs, every failure is reported, and the process exits non-zero at the end,
so one broken bench can't mask another and the CI smoke job still gates.
"""

from __future__ import annotations

import argparse
import glob
import importlib
import json
import os
import sys
import traceback

from repro.compat import enable_compilation_cache

# bench name -> module under benchmarks/; imported lazily per bench so a
# module that rots at import level fails alone instead of masking the rest
SUITE = {
    "fig1": "bench_fig1_error",
    "fig4a": "bench_fig4a_scaling",
    "fig4b": "bench_fig4b_hash_width",
    "tab2": "bench_tab2_memory",
    "tab3": "bench_tab3_resources",
    "tab4": "bench_tab4_streaming",
    "estimators": "bench_estimators",
    "bank": "bench_bank_streaming",
    "window": "bench_window",
    "sparse": "bench_sparse",
    "heavy": "bench_heavy",
    "obs": "bench_obs",
    "serve": "bench_serve",
}


def summarize() -> None:
    """One table over the tracked BENCH_*.json perf-trajectory files."""
    paths = sorted(
        p for p in glob.glob("BENCH_*.json") if not p.endswith(".smoke.json")
    )
    if not paths:
        print("no tracked BENCH_*.json files found", file=sys.stderr)
        sys.exit(1)
    rows = [("bench", "records", "jax", "backend", "cpus", "smoke")]
    for path in paths:
        try:
            with open(path) as f:
                payload = json.load(f)
        except (OSError, json.JSONDecodeError) as e:
            rows.append((os.path.basename(path), f"UNREADABLE: {e}",
                         "-", "-", "-", "-"))
            continue
        env = payload.get("env", {})
        records = sum(
            len(v) for v in payload.values() if isinstance(v, list)
        ) or len(payload)
        name = os.path.basename(path)[len("BENCH_"):-len(".json")]
        rows.append((
            name,
            str(records),
            str(env.get("jax", "-")),
            str(env.get("backend", "-")),
            str(env.get("cpu_count", "-")),
            str(payload.get("smoke", "-")).lower(),
        ))
    widths = [max(len(r[i]) for r in rows) for i in range(len(rows[0]))]
    for i, row in enumerate(rows):
        print("  ".join(c.ljust(w) for c, w in zip(row, widths)).rstrip())
        if i == 0:
            print("  ".join("-" * w for w in widths))


def check_metrics_snapshot() -> None:
    """Assert the post-suite snapshot has the §15 schema and live data."""
    from repro.obs import metrics

    snap = json.loads(metrics.to_json())  # must round-trip through JSON
    missing = {"enabled", "counters", "gauges", "histograms"} - set(snap)
    assert not missing, f"snapshot missing top-level keys: {sorted(missing)}"
    assert snap["enabled"] is True
    dispatch_calls = [
        k for k in snap["counters"]
        if k.startswith("dispatch.") and k.endswith(".calls")
    ]
    assert dispatch_calls, (
        f"no dispatch.*.calls counters recorded; counters="
        f"{sorted(snap['counters'])}"
    )
    # a span (repro.obs.tracing) adds <name>.calls and <name>.seconds
    spans = [
        k[: -len(".calls")] for k, v in snap["counters"].items()
        if k.endswith(".calls") and not k.startswith("dispatch.") and v > 0
        and snap["counters"].get(k[: -len(".calls")] + ".seconds", 0) > 0
    ]
    assert spans, (
        f"no live span counters (<name>.calls + <name>.seconds) recorded; "
        f"counters={sorted(snap['counters'])}"
    )
    for hist in snap["histograms"].values():
        missing = {"count", "sum", "mean", "min", "max", "p50", "p90",
                   "p99"} - set(hist)
        assert not missing, f"histogram summary missing {sorted(missing)}"
    print(
        f"metrics-check,OK,{len(dispatch_calls)} dispatch counters + "
        f"{len(spans)} spans live"
    )


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--full", action="store_true", help="widen sweeps")
    ap.add_argument("--smoke", action="store_true",
                    help="tiny sizes: just prove every bench still runs")
    ap.add_argument("--only", default=None,
                    help=f"comma list of benchmarks: {','.join(SUITE)}")
    ap.add_argument("--metrics-check", action="store_true",
                    help="run with metrics enabled; assert the snapshot "
                         "parses with the DESIGN.md §15 schema (CI hook)")
    ap.add_argument("--summary", action="store_true",
                    help="print one table over the tracked BENCH_*.json "
                         "files and exit (runs nothing)")
    args = ap.parse_args()
    if args.full and args.smoke:
        ap.error("--full and --smoke are mutually exclusive")
    if args.summary:
        summarize()
        return

    selected = args.only.split(",") if args.only else list(SUITE)
    unknown = [name for name in selected if name not in SUITE]
    if unknown:
        ap.error(f"unknown benchmark(s) {unknown}; "
                 f"available: {', '.join(sorted(SUITE))}")

    if args.metrics_check:
        from repro.obs import metrics

        # bench_obs gates the DISABLED path and manages the flag itself
        selected = [n for n in selected if n != "obs"]
        metrics.reset()
        metrics.enable()

    enable_compilation_cache()
    print("name,us_per_call,derived")
    failures = []
    for name in selected:
        try:
            mod = importlib.import_module(f"benchmarks.{SUITE[name]}")
            mod.run(full=args.full, smoke=args.smoke)
        except Exception:
            failures.append(name)
            print(f"BENCH-FAILED,{name}", file=sys.stderr)
            traceback.print_exc()
    if args.metrics_check and not failures:
        try:
            check_metrics_snapshot()
        except AssertionError:
            failures.append("metrics-check")
            traceback.print_exc()
    if failures:
        print(f"\n{len(failures)} benchmark(s) failed: {failures}",
              file=sys.stderr)
        sys.exit(1)


if __name__ == "__main__":
    main()
