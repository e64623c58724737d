"""The unified repro.sketch API: plan equivalence, carrier, serialization.

Acceptance property for the API redesign: every registered
(backend, placement, pipelines) ExecutionPlan produces registers
bit-identical to the single-pipeline jnp reference on the same stream —
including streams whose length divides nothing (uniform padding, never an
error).  Plus: the overflow-safe item counter, to_bytes/from_bytes, set
algebra on the carrier, and the deprecated shims.
"""

import warnings

import numpy as np
import jax
import jax.numpy as jnp
import pytest
from hypothesis_compat import given, settings, st

from repro.sketch import (
    DEFAULT_PIPELINES,
    ExecutionPlan,
    HLLConfig,
    HyperLogLog,
    available_backends,
    example_plans,
    hll,
    reference_plan,
    update_registers,
)
from repro.sketch.carrier import _counter_add, _limb_add

CFG = HLLConfig(p=10, hash_bits=64)  # p <= 12 so every backend is eligible


def _items(n, seed=0):
    return jnp.asarray(
        np.random.default_rng(seed).integers(0, 2**31, n, dtype=np.int32)
    )


def _mesh():
    return jax.make_mesh((jax.device_count(),), ("data",))


def _plan_id(plan):
    return f"{plan.backend}-{plan.placement}-k{plan.pipelines}"


PLANS = example_plans(mesh=_mesh())


# ----------------------------------------------------------------------------
# plan equivalence (the acceptance property)
# ----------------------------------------------------------------------------


def test_all_backends_registered():
    assert set(available_backends()) >= {"jnp", "pallas", "pallas_pipelined"}


@pytest.mark.parametrize("plan", PLANS, ids=_plan_id)
@pytest.mark.parametrize("n", [1, 4096, 4099])  # 4099 is prime: pads everywhere
def test_every_plan_matches_reference(plan, n):
    items = _items(n, seed=n)
    ref = update_registers(
        hll.init_registers(CFG), items, CFG, reference_plan()
    )
    got = update_registers(hll.init_registers(CFG), items, CFG, plan)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(ref))


def test_non_divisible_stream_pads_instead_of_raising():
    """The old update_pipelined raised on n % k != 0; the new API must not."""
    items = _items(1001, seed=3)
    for k in (2, 4, DEFAULT_PIPELINES, 16):
        got = update_registers(
            hll.init_registers(CFG), items, CFG,
            ExecutionPlan(backend="jnp", pipelines=k),
        )
        ref = hll.update(hll.init_registers(CFG), items, CFG)
        np.testing.assert_array_equal(np.asarray(got), np.asarray(ref))


def test_unknown_backend_rejected():
    with pytest.raises(ValueError, match="unknown backend"):
        update_registers(
            hll.init_registers(CFG), _items(16), CFG,
            ExecutionPlan(backend="vhdl"),
        )
    with pytest.raises(ValueError, match="placement"):
        ExecutionPlan(placement="fpga")
    with pytest.raises(ValueError, match="mesh"):
        ExecutionPlan(placement="mesh")


@settings(deadline=None, max_examples=10)
@given(st.lists(st.integers(0, 2**31 - 1), min_size=1, max_size=300))
def test_plan_equivalence_property(xs):
    items = jnp.asarray(xs, jnp.int32)
    ref = hll.update(hll.init_registers(CFG), items, CFG)
    for plan in PLANS:
        got = update_registers(hll.init_registers(CFG), items, CFG, plan)
        np.testing.assert_array_equal(np.asarray(got), np.asarray(ref))


# ----------------------------------------------------------------------------
# HyperLogLog carrier
# ----------------------------------------------------------------------------


def test_carrier_is_a_pytree_and_jits():
    sk = HyperLogLog.of(_items(1000), CFG)
    leaves = jax.tree_util.tree_leaves(sk)
    assert len(leaves) == 2  # registers + counter limbs; cfg is static

    @jax.jit
    def bump(s, items):
        return s.update(items)

    out = bump(sk, _items(500, seed=9))
    assert isinstance(out, HyperLogLog) and out.cfg == CFG
    assert out.count == 1500


def test_counter_is_overflow_safe_past_int32():
    """int32 overflowed at 2.1e9 items; the limb counter must not."""
    near_wrap = jnp.asarray(np.array([0, 0xFFFFFFFF], np.uint32))
    sk = HyperLogLog(hll.init_registers(CFG), near_wrap, CFG)
    assert sk.count == 2**32 - 1
    sk = sk.update(_items(3))
    assert sk.count == 2**32 + 2  # crossed the 32-bit boundary exactly
    # and limb arithmetic keeps carrying well past any int32/uint32 range
    big = _counter_add(sk.n_items, (200 * 10**9))
    assert (int(big[0]) << 32 | int(big[1])) == 2**32 + 2 + 200 * 10**9


def _limbs(value):
    return jnp.asarray(np.array([value >> 32, value & 0xFFFFFFFF], np.uint32))


def _with_count(value):
    return HyperLogLog(hll.init_registers(CFG), _limbs(value), CFG)


@pytest.mark.parametrize(
    "start,addend,via",
    [
        (2**32 - 1, 3, "update"),  # carry across the 2^32 boundary
        (2**32 - 2, 5, "update_in_jit"),  # nested jit inlines; size static
        (2**32 - 2, 2**32 + 7, "merge"),  # limb addend: carry and hi limb
        (2**33 - 1, 2**32 + 1, "merge_in_jit"),
        (2**64 - 1, 1, "merge"),  # wraps modulo 2^64
        (7, 2**40 + 3, "int"),  # int addend >= 2^32, no int32 weak type
        (2**32 - 1, 2**33 + 1, "int"),
        (2**64 - 2, 5, "int"),
    ],
)
def test_counter_add_is_exact_modulo_2_64(start, addend, via):
    sk = _with_count(start)
    if via == "update":
        got = sk.update(_items(addend)).n_items
    elif via == "update_in_jit":
        got = jax.jit(lambda s, x: s.update(x))(sk, _items(addend)).n_items
    elif via == "merge":
        got = sk.merge(_with_count(addend)).n_items
    elif via == "merge_in_jit":
        got = jax.jit(lambda a, b: a | b)(sk, _with_count(addend)).n_items
    else:
        got = _counter_add(sk.n_items, addend)
    limbs = np.asarray(got)
    assert limbs.dtype == np.uint32 and limbs.shape == (2,)
    assert (int(limbs[0]) << 32 | int(limbs[1])) == (start + addend) % 2**64


def test_counter_add_compiles_once_for_every_batch_size():
    """The addend is traced: batch sizes 1, 3 and 2^14 and a merge share
    one compiled ``_limb_add``, and the counter compiles nothing else."""
    batches = [_items(n, seed=n) for n in (1, 3, 2**14)]
    for x in batches:  # compile the register update and merge at each size
        warm = HyperLogLog.empty(CFG).update(x)
        warm = warm | warm
    _limb_add.clear_cache()
    compiled = []

    def on_event(name, seconds, fun_name=None, **kw):
        if "backend_compile" in name:
            compiled.append(fun_name)

    jax.monitoring.register_event_duration_secs_listener(on_event)
    try:
        sk = HyperLogLog.empty(CFG)
        for x in batches:
            sk = sk.update(x)
        sk = sk | sk
    finally:
        jax.monitoring.unregister_event_duration_listener(on_event)
    assert sk.count == 2 * (1 + 3 + 2**14)
    assert compiled == ["jit(_limb_add)"]


def test_merge_checks_config_and_adds_counters():
    a = HyperLogLog.of(_items(100, 1), CFG)
    b = HyperLogLog.of(_items(200, 2), CFG)
    ab = a | b
    assert ab.count == 300
    with pytest.raises(ValueError, match="configs"):
        a.merge(HyperLogLog.empty(HLLConfig(p=12, hash_bits=64)))
    with pytest.raises(ValueError, match="configs"):
        a.jaccard(HyperLogLog.empty(HLLConfig(p=10, hash_bits=32)))


def test_carrier_set_algebra_matches_module_functions():
    from repro.sketch import setops

    a = HyperLogLog.of(jnp.arange(0, 60_000, dtype=jnp.int32), CFG)
    b = HyperLogLog.of(jnp.arange(30_000, 90_000, dtype=jnp.int32), CFG)
    assert a.union_estimate(b) == setops.union_estimate(
        a.registers, b.registers, CFG
    )
    assert a.intersection_estimate(b) == setops.intersection_estimate(a, b, CFG)
    assert 0.0 <= a.jaccard(b) <= 1.0


# ----------------------------------------------------------------------------
# serialization
# ----------------------------------------------------------------------------


@pytest.mark.parametrize("p,H", [(10, 32), (10, 64), (16, 64)])
def test_bytes_roundtrip(p, H):
    cfg = HLLConfig(p=p, hash_bits=H, seed=7)
    sk = HyperLogLog.of(_items(5000, seed=p * H), cfg)
    blob = sk.to_bytes()
    assert len(blob) == 24 + cfg.m
    back = HyperLogLog.from_bytes(blob)
    assert back.cfg == cfg
    assert back.count == sk.count == 5000
    np.testing.assert_array_equal(
        np.asarray(back.registers), np.asarray(sk.registers)
    )
    assert back.estimate() == sk.estimate()


def test_bytes_rejects_garbage():
    with pytest.raises(ValueError, match="truncated"):
        HyperLogLog.from_bytes(b"xx")
    with pytest.raises(ValueError, match="magic"):
        HyperLogLog.from_bytes(b"NOPE" + bytes(20 + CFG.m))
    blob = HyperLogLog.empty(CFG).to_bytes()
    with pytest.raises(ValueError, match="payload"):
        HyperLogLog.from_bytes(blob[:-1])


def test_serialized_sketches_merge_across_boundaries():
    """The wire format carries everything a remote merge needs."""
    a = HyperLogLog.of(_items(4000, 1), CFG)
    b = HyperLogLog.of(_items(4000, 2), CFG)
    remote = HyperLogLog.from_bytes(a.to_bytes()) | HyperLogLog.from_bytes(
        b.to_bytes()
    )
    local = a | b
    np.testing.assert_array_equal(
        np.asarray(remote.registers), np.asarray(local.registers)
    )
    assert remote.count == local.count == 8000


# ----------------------------------------------------------------------------
# deprecated shims stay importable and equivalent
# ----------------------------------------------------------------------------


def test_raw_kernel_modules_import_standalone():
    """repro.kernels.* must be importable as a process's first import
    (regression: the sketch<->kernels cycle broke this)."""
    import os
    import subprocess
    import sys

    r = subprocess.run(
        [sys.executable, "-c",
         "import repro.kernels.hash_rank, repro.kernels.hll_fused, "
         "repro.kernels.bucket_fold, repro.kernels.ref"],
        capture_output=True, text=True, env=dict(os.environ),
    )
    assert r.returncode == 0, r.stderr


def test_legacy_shims_warn_and_match():
    items = _items(2048, seed=11)
    ref = hll.update(hll.init_registers(CFG), items, CFG)
    with warnings.catch_warnings(record=True) as caught:
        warnings.simplefilter("always")
        import repro.core.hll as legacy_hll
        import repro.core.sketch as legacy_sketch
        from repro.core import setops as legacy_setops  # noqa: F401
        from repro.kernels import ops as legacy_ops
    assert any(issubclass(w.category, DeprecationWarning) for w in caught)

    assert legacy_hll.HLLConfig is HLLConfig
    np.testing.assert_array_equal(
        np.asarray(legacy_hll.update(hll.init_registers(CFG), items, CFG)),
        np.asarray(ref),
    )
    np.testing.assert_array_equal(
        np.asarray(
            legacy_sketch.update_pipelined(
                hll.init_registers(CFG), items, CFG, pipelines=4
            )
        ),
        np.asarray(ref),
    )
    np.testing.assert_array_equal(
        np.asarray(
            legacy_ops.pipelined_update(
                hll.init_registers(CFG), items, CFG, 4, interpret=True
            )
        ),
        np.asarray(ref),
    )
