"""``correct`` comes out false when the timed path is broken underneath.

Each test skips the harness's look for a chip and drives the rest of a run
(``harness.execute``) at the cell's rehearsal size on the CPU, with one
fault planted in the program: a step that returns its state unchanged, half
of each batch left out, or an answer altered where it is produced.  The
control (the 32-bit hash under a 64-bit reference) must fail too, and the
unbroken run must pass.  One chip runs these cells, so no exchange between
chips can be left out.
"""

import time

import jax.numpy as jnp
import pytest

from chipbench.bench import harness
from chipbench.control import CONTROLS
from repro.sketch import HybridBank, HyperLogLog

CELLS = ("tenants.ingest", "paper.stream", "paper.small_batch")


def _halve(fn):
    def half(self, *args, **kwargs):
        if fn.__name__ == "update":
            items = args[0]
            return fn(self, items[: items.size // 2], *args[1:], **kwargs)
        keys, items = args[0], args[1]
        n = len(keys) // 2
        return fn(self, keys[:n], items[:n], *args[2:], **kwargs)

    return half


def _plant(monkeypatch, workload, fault):
    if workload.startswith("tenants."):
        cls, step, read = HybridBank, "update_many", "estimate_many"
    else:
        cls, step, read = HyperLogLog, "update", "estimate"
    original_step, original_read = getattr(cls, step), getattr(cls, read)
    if fault == "state_unchanged":
        monkeypatch.setattr(cls, step, lambda self, *a, **k: self)
    elif fault == "half_batch":
        monkeypatch.setattr(cls, step, _halve(original_step))
    elif fault == "answer_altered":
        if cls is HybridBank:
            monkeypatch.setattr(
                cls, read, lambda self, *a, **k: original_read(self, *a, **k) * jnp.float32(1.01)
            )
        else:
            monkeypatch.setattr(cls, read, lambda self, *a, **k: original_read(self, *a, **k) * 1.01)


def _run(workload, control=None, seed=7):
    cell = harness.load_cell(workload, rehearsal=True)
    return harness.execute(
        cell, seed, 0.5, False, time.perf_counter(), control=control, say=lambda _: None
    )


@pytest.mark.parametrize("workload", CELLS)
def test_sound_run_is_correct(workload):
    result = _run(workload)
    assert result["correct"], result["checks"]


@pytest.mark.parametrize("fault", ["state_unchanged", "half_batch", "answer_altered"])
@pytest.mark.parametrize("workload", CELLS)
def test_fault_is_caught(monkeypatch, workload, fault):
    _plant(monkeypatch, workload, fault)
    result = _run(workload)
    assert not result["correct"], (fault, result["checks"])


@pytest.mark.parametrize("workload", CELLS)
def test_control_fails(workload):
    result = _run(workload, control=CONTROLS["hash32"])
    assert not result["correct"], result["checks"]
