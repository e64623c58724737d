"""Each cell runs end to end at its rehearsal size on the CPU, then refuses.

``chipbench/run.py`` runs in a subprocess pinned to the CPU.  Without the
rehearsal switch it must stop at the device check before any set-up; with
it, the whole run happens at tiny sizes, the comparison with the reference
passes, and the run still exits non-zero with no result line.
"""

import json
import os
import subprocess
import sys
import time

import jax
import jax.numpy as jnp
from jax.experimental.compilation_cache import compilation_cache

import pytest

from chipbench.bench import harness

RUN = os.path.join(harness.BENCH_DIR, "run.py")
CELLS = ("tenants.ingest", "paper.stream", "paper.small_batch")
BIG_SEED = "4294967311"  # over 32 bits


def _run(workload, rehearsal, *extra):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("XLA_FLAGS", None)
    if rehearsal:
        env[harness.REHEARSAL_ENV] = "1"
    else:
        env.pop(harness.REHEARSAL_ENV, None)
    return subprocess.run(
        [sys.executable, RUN, "--workload", workload, "--seed", BIG_SEED,
         "--seconds", "1", *extra],
        capture_output=True, text=True, env=env, timeout=600,
    )


def _no_result(stdout):
    for line in stdout.splitlines():
        line = line.strip()
        if line.startswith("{"):
            assert "correct" not in json.loads(line)


@pytest.mark.parametrize("workload", CELLS)
def test_cell_rehearses_then_refuses(workload):
    out = _run(workload, True, "--trace", "0")
    assert out.returncode == 2, out.stderr[-3000:]
    assert "rehearsal finished (correct=True)" in out.stderr, out.stderr[-3000:]
    assert f"[chipbench] {workload} seed {BIG_SEED}" in out.stdout
    checks = [ln for ln in out.stderr.splitlines() if ln.startswith("check ")]
    assert checks and all(" limit " in ln for ln in checks)
    _no_result(out.stdout)


def test_traced_rehearsal_refuses_too():
    out = _run("paper.small_batch", True, "--trace", "1")
    assert out.returncode == 2, out.stderr[-3000:]
    assert "rehearsal finished (correct=True)" in out.stderr
    _no_result(out.stdout)


def test_refuses_off_tpu_before_setup():
    out = _run("tenants.ingest", False, "--trace", "0")
    assert out.returncode == 2
    assert "no TPU" in out.stderr
    assert "[chipbench]" not in out.stdout
    _no_result(out.stdout)


def test_in_window_new_shape_is_counted():
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    counter = harness.CompileCounter()
    try:
        f = jax.jit(lambda x: x * 7 + 3)
        f(jnp.ones(11)).block_until_ready()
        t0 = time.perf_counter()
        f(jnp.ones(11)).block_until_ready()  # a warmed shape: no compile
        assert counter.between(t0, time.perf_counter()) == []
        f(jnp.ones(13)).block_until_ready()  # a new shape inside the window
        assert len(counter.between(t0, time.perf_counter())) >= 1
    finally:
        counter.close()
