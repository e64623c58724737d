"""Every Pallas kernel compiles for a TPU v5e, with no chip attached.

Interpret mode (what every other kernel test runs) accepts block shapes,
in-kernel slices and reshapes that the Mosaic compiler refuses.  Each test
here lowers one kernel at serving sizes (p=12, B=1024 sketches, 2^19
items) for one chip of a described ``v5e:2x2`` topology and asserts that
the compiled program holds the Mosaic kernel (``tpu_custom_call``) rather
than a fallback.  The topology is described inside a module fixture, so
only the worker that runs this file loads the TPU compiler.
"""

import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels import (
    bank_scatter,
    bucket_fold,
    cm_scatter,
    hash_rank,
    hll_fused,
    sparse_scatter,
    window_fold,
)
from repro.sketch.hll import HLLConfig

CFG = HLLConfig(p=12, hash_bits=64)
ROWS = 1024  # sketches in the bank
TILES = (1 << 19) // 128  # 2^19 items as (rows, 128) stream tiles
W = 8  # ring depth of the window folds
CM_CELLS = 4 * 1024  # count-min d * w

I32, U32 = jnp.int32, jnp.uint32
STREAM = ((TILES, 128), I32)

# kernel -> (entry point, [(shape, dtype) per argument])
KERNELS = {
    "hash_rank": (lambda x: hash_rank.hash_rank(x, CFG), [((TILES, 128), U32)]),
    "bucket_fold": (bucket_fold.bucket_fold, [((4, CFG.m), I32)]),
    "hll_fused": (
        lambda r, x, n: hll_fused.hll_update_fused(r, x, n, CFG),
        [((1, CFG.m), I32), ((TILES, 128), U32), ((1, 1), I32)],
    ),
    "bank_scatter": (
        lambda r, k, i, rk: bank_scatter.bank_scatter_max(
            r, k, i, rk, m=CFG.m, row_block=1
        ),
        [((ROWS, CFG.m), I32), STREAM, STREAM, STREAM],
    ),
    "window_fold": (
        lambda r, mk: window_fold.window_fold_max(r, mk, m=CFG.m, row_block=1),
        [((W, ROWS, CFG.m), I32), ((W,), I32)],
    ),
    "window_merge": (
        lambda p: window_fold.window_merge_max(p, m=CFG.m, row_block=1),
        [((3, ROWS, CFG.m), I32)],
    ),
    "cm_scatter_add": (
        lambda c, k, col, v: cm_scatter.cm_scatter_add(
            c, k, col, v, cells_per_row=CM_CELLS, row_block=1
        ),
        [((ROWS, CM_CELLS), I32), STREAM, STREAM, STREAM],
    ),
    "cm_window_fold_sum": (
        lambda r, mk: cm_scatter.cm_window_fold_sum(
            r, mk, cells_per_row=CM_CELLS, row_block=1
        ),
        [((W, ROWS, CM_CELLS), I32), ((W,), I32)],
    ),
    "sparse_scatter_coo": (
        lambda k, i, rk: sparse_scatter.sparse_scatter_coo(
            k, i, rk, rows=ROWS, m=CFG.m, row_block=1
        ),
        [STREAM, STREAM, STREAM],
    ),
}


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies

    try:
        topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def no_compile_cache():
    """Compiles for a described chip cannot be read back from the
    persistent cache, so keep them out of it."""
    from jax.experimental.compilation_cache import compilation_cache

    before = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", before)
    compilation_cache.reset_cache()


@pytest.mark.parametrize("name", sorted(KERNELS))
def test_kernel_compiles_for_v5e(name, one_chip, no_compile_cache):
    fn, shapes = KERNELS[name]
    args = [jax.ShapeDtypeStruct(s, d, sharding=one_chip) for s, d in shapes]
    text = jax.jit(fn).lower(*args).compile().as_text()
    assert "tpu_custom_call" in text, f"{name} compiled without its Mosaic kernel"
