"""Run one benchmark cell once: set up, warm, measure, check, print.

    python3 chipbench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

Everything a cell is made of is found by name: the cell in
``BENCHMARK.json``; its configuration in the file that entry names; its
traffic mix in ``chipbench/traffic/<traffic>.json``; the system the
configuration drives in ``chipbench/systems/<system>.py``; the loop the mix
runs in ``chipbench/loops/<loop>.py``; each per-layer metric in
``chipbench/metrics/<metric>.py``.  A new cell or metric adds files and
entries and edits none.

A run:

1. refuses (exit 2, no result) unless JAX sees a TPU with as many chips as
   the cell asks for and its ``device_kind`` is in ``peaks.json``;
2. set-up: turns on the persistent compile cache at a fixed path in the
   checkout, draws the start state and the traffic from ``--seed``, and
   warms the cell's own shapes with untimed work;
3. window: turns the persistent cache off, so that whatever compiles
   inside the window is paid in full in every run, and runs the loop for
   ``--seconds``; every call into the program sits in a
   ``jax.profiler.TraceAnnotation`` named after the layer it enters;
4. with ``--trace 1``, profiles a steady sub-window, reads the program's
   counters (``repro.obs.metrics``), and reports the per-layer metrics and a
   breakdown instead of the end-to-end ones;
5. compares the state and the answers with the plain reference and prints
   each number compared beside its limit, last on stderr and under
   ``checks`` in the result;
6. prints the result as the last line of stdout.

``CHIPBENCH_REHEARSAL=1`` runs the configuration's and the mix's
``rehearsal`` sizes on any backend for tests; the run then refuses at the
device check, with no result.
"""

from __future__ import annotations

import argparse
import dataclasses
import importlib
import importlib.util
import json
import os
import shutil
import sys
import time
import types
from typing import Optional

import numpy as np
from jax.profiler import TraceAnnotation

from chipbench.bench import devtrace
from chipbench.bench.checks import Check

BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH_DIR)
CACHE_DIR = os.path.join(BENCH_DIR, ".jax_cache")
RUN_DIR = os.path.join(BENCH_DIR, ".runs")
REHEARSAL_ENV = "CHIPBENCH_REHEARSAL"
BACKEND_COMPILE = "/jax/core/compile/backend_compile_duration"
TRACE_SECONDS = 4.0


# a host span on the profiler's clock around one call into a layer
span = TraceAnnotation


@dataclasses.dataclass
class Outcome:
    """What a loop's window produced."""

    e2e: dict  # end-to-end metric name -> value
    attempted: int
    failed: int
    log: object  # what the system's check compares
    counts: dict  # numbers the per-layer readers need
    notes: list  # lines printed before the result


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config: dict
    traffic: dict
    end_to_end: list
    per_layer: list


def _merge(base: dict, over: dict) -> dict:
    out = dict(base)
    for k, v in over.items():
        out[k] = _merge(out[k], v) if isinstance(v, dict) and isinstance(out.get(k), dict) else v
    return out


def load_cell(workload: str, rehearsal: bool = False, root: str = ROOT) -> Cell:
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        bench = json.load(f)
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        raise SystemExit(f"chipbench: unknown workload {workload!r}; known: {sorted(cells)}")
    w = cells[workload]
    entry = next(c for c in bench["configs"] if c["name"] == w["config"])
    with open(os.path.join(root, entry["file"])) as f:
        config = json.load(f)
    with open(os.path.join(root, "chipbench", "traffic", w["traffic"] + ".json")) as f:
        traffic = json.load(f)
    if rehearsal:
        config = _merge(config, config.get("rehearsal", {}))
        traffic = _merge(traffic, traffic.get("rehearsal", {}))
    e2e = [m for m in bench["end_to_end"] if workload in m.get("workloads", [workload])]
    names = {m["name"] for m in e2e}
    per_layer = [
        m for m in bench["per_layer"]
        if (workload in m["workloads"] if "workloads" in m else m["moves"] in names)
    ]
    return Cell(workload, int(w["chips"]), config, traffic, e2e, per_layer)


def peaks_for(kind: str) -> dict:
    """The published peaks of ``kind``; an unknown device is an error."""
    with open(os.path.join(BENCH_DIR, "bench", "peaks.json")) as f:
        table = json.load(f)["devices"]
    if kind not in table:
        raise KeyError(f"device kind {kind!r} is not in peaks.json ({sorted(table)})")
    return table[kind]


def read_metric(name: str, ctx) -> Optional[float]:
    """Run the per-layer reader ``chipbench/metrics/<name>.py``."""
    path = os.path.join(BENCH_DIR, "metrics", name + ".py")
    spec = importlib.util.spec_from_file_location("chipbench_metric_" + name.replace(".", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read(ctx)


class CompileCounter:
    """Backend compiles, stamped when each one ends."""

    def __init__(self):
        import jax.monitoring

        self.stamps = []
        self._monitoring = jax.monitoring
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, duration, **_):
        if event == BACKEND_COMPILE:
            self.stamps.append((time.perf_counter(), duration))

    def between(self, t0: float, t1: float) -> list:
        return [d for t, d in self.stamps if t0 <= t <= t1]

    def close(self) -> None:
        self._monitoring.unregister_event_duration_listener(self._on)


class SubTrace:
    """Profiles a steady sub-window of TRACE_SECONDS (or half the window)
    in its middle, marked by a ``window`` annotation."""

    def __init__(self, enabled: bool, seconds: float, log_dir: str):
        self.enabled = enabled
        length = min(TRACE_SECONDS, seconds / 2)
        self.start_at = (seconds - length) / 2
        self.stop_at = self.start_at + length
        self.log_dir = log_dir
        self.state = "idle"
        self._mark = None

    def poll(self, now: float) -> None:
        if not self.enabled:
            return
        if self.state == "idle" and now >= self.start_at:
            import jax

            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            shutil.rmtree(self.log_dir, ignore_errors=True)
            jax.profiler.start_trace(self.log_dir, profiler_options=opts)
            self._mark = TraceAnnotation(devtrace.WINDOW)
            self._mark.__enter__()
            self.state = "on"
        elif self.state == "on" and now >= self.stop_at:
            self.stop()

    def stop(self) -> None:
        if self.state == "on":
            import jax

            self._mark.__exit__(None, None, None)
            jax.profiler.stop_trace()
            self.state = "done"

    def reduce(self, chips: int, platform: str) -> Optional[devtrace.Trace]:
        if self.state != "done":
            return None
        try:
            return devtrace.load(self.log_dir, chips, devtrace.DEVICE_OPS[platform])
        finally:
            shutil.rmtree(self.log_dir, ignore_errors=True)


class Window:
    def __init__(self, seconds: float, tracer: SubTrace):
        self.seconds = seconds
        self.tracer = tracer
        self.t0 = time.perf_counter()

    def now(self) -> float:
        return time.perf_counter() - self.t0

    def poll(self) -> None:
        self.tracer.poll(self.now())


def _rng(seed: int):
    return np.random.default_rng([abs(seed), int(seed < 0)])


def execute(cell: Cell, seed: int, seconds: float, trace: bool, t_start: float,
            peaks: Optional[dict] = None, control: Optional[dict] = None,
            say=print) -> dict:
    """One run of ``cell`` after the device check; returns the result."""
    import jax
    from jax.experimental.compilation_cache import compilation_cache

    from repro.obs import metrics as obs

    jax.config.update("jax_compilation_cache_dir", CACHE_DIR)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_enable_compilation_cache", True)
    compilation_cache.reset_cache()
    counter = CompileCounter()
    try:
        system_mod = importlib.import_module(f"chipbench.systems.{cell.config['system']}")
        loop_mod = importlib.import_module(f"chipbench.loops.{cell.traffic['loop']}")
        rng = _rng(seed)
        system = system_mod.System(cell.config, rng, control)
        loop = loop_mod.Loop(cell.traffic, system, rng)
        tracer = SubTrace(trace, seconds, os.path.join(RUN_DIR, f"trace-{cell.name}"))
        if trace:
            obs.reset()
            obs.enable()
        # window start: from here every compile is paid in full, as in a
        # long-running process, whichever seeds ran before in this checkout
        jax.config.update("jax_enable_compilation_cache", False)
        compilation_cache.reset_cache()
        window = Window(seconds, tracer)
        setup_s = window.t0 - t_start
        outcome = loop.run(window)
        t_close = time.perf_counter()
        tracer.stop()
        compiles = counter.between(window.t0, t_close)
        counters = obs.snapshot()["counters"] if trace else {}
        obs.disable()
        devices = jax.devices()[: cell.chips]
        peak = max((d.memory_stats() or {}).get("peak_bytes_in_use", 0) for d in devices)
        jax.config.update("jax_enable_compilation_cache", True)
        compilation_cache.reset_cache()
        summary = tracer.reduce(cell.chips, devices[0].platform)
        checks = system.check(outcome.log, cell.config["limits"])
        checks.append(Check("unfinished", outcome.failed, 0))
    finally:
        counter.close()

    say(f"[chipbench] {cell.name} seed {seed}: setup_s {setup_s!r}")
    for line in outcome.notes:
        say(f"[chipbench] {line}")
    say(f"[chipbench] in-window backend compiles {len(compiles)} "
        f"({sum(compiles)!r} s)")
    say(f"[chipbench] peak_bytes_in_use {peak}")

    metrics = {}
    if not trace:
        for m in cell.end_to_end:
            value = setup_s if m["name"] == "setup_s" else outcome.e2e.get(m["name"])
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    else:
        ctx = types.SimpleNamespace(
            trace=summary, counters=counters, compiles=len(compiles),
            counts=outcome.counts, config=cell.config, traffic=cell.traffic,
            peaks=peaks,
        )
        for m in cell.per_layer:
            value = read_metric(m["name"], ctx)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    device0 = jax.devices()[0]
    device = {
        "platform": device0.platform,
        "kind": device0.device_kind,
        "count": len(jax.devices()),
        "memory_peak_bytes": int(peak),
    }
    result = {
        "correct": all(c.ok for c in checks),
        "attempted": int(outcome.attempted),
        "failed": int(outcome.failed),
        "metrics": metrics,
        "device": device,
    }
    if summary is not None:
        device["busy_s"] = summary.busy_s
        device["window_s"] = summary.window_s
        result["breakdown"] = summary.breakdown()
    result["checks"] = {c.name: {"value": c.value, "limit": c.limit} for c in checks}
    return result


def refuse(msg: str) -> int:
    print(f"chipbench: {msg}", file=sys.stderr, flush=True)
    return 2


def device_check(cell: Cell, rehearsal: bool):
    """(peaks, None) when the devices can run ``cell``, else (None, why).
    A rehearsal skips the check here and refuses after its run."""
    import jax

    devices = jax.devices()
    if rehearsal:
        return None, None
    if devices[0].platform != "tpu":
        return None, f"no TPU: JAX reports platform {devices[0].platform!r}"
    if len(devices) < cell.chips:
        return None, f"{cell.name} needs {cell.chips} chips, JAX has {len(devices)}"
    try:
        return peaks_for(devices[0].device_kind), None
    except KeyError as e:
        return None, str(e)


def main(argv, t_start: float) -> int:
    ap = argparse.ArgumentParser(description="Run one benchmark cell once.")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    rehearsal = os.environ.get(REHEARSAL_ENV) == "1"
    cell = load_cell(args.workload, rehearsal)
    peaks, why = device_check(cell, rehearsal)
    if why:
        return refuse(why)

    import jax

    devices = jax.devices()
    result = execute(cell, args.seed, args.seconds, bool(args.trace), t_start, peaks)
    sys.stdout.flush()
    for name, c in result["checks"].items():
        print(f"check {name} {c['value']!r} limit {c['limit']!r}", file=sys.stderr)
    sys.stderr.flush()
    if rehearsal:
        return refuse(
            f"rehearsal finished (correct={result['correct']}); "
            f"platform {devices[0].platform!r}, no result"
        )
    print(json.dumps(result), flush=True)
    return 0
