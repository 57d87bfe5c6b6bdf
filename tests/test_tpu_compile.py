"""Compile the Pallas kernels for a described TPU v5e chip, at deployment widths.

No chip is needed: the TPU compiler is installed and compiles for a
topology that is described rather than attached, so a kernel Mosaic would
refuse fails here at CPU cost.  Widths are those ``chip_smoke.py`` drives
at TPC-H scale factor 1 (lineitem 6M rows, 1.5M orders, 200k parts, a
600k-row insert stream, m = 0.1).

The topology, and everything built from it, lives in module fixtures:
only the worker that runs this file loads the TPU library.  The
persistent compile cache is off around these compiles — an entry written
for a described chip cannot be read back without one.
"""

from __future__ import annotations

import functools
import os

import jax
import jax.numpy as jnp
import pytest

ROWS_DELTA = 1 << 20          # the 600k-row pending-delta arena (pow2)
GROUPS_PART = 1 << 18         # l_partkey domain, 200k parts (pow2)
ROWS_JOINED = 1 << 21         # joinView clean ∪ stale sample rows (pow2)
ROWS_VIEW = 1_875_200         # joinView group arena, 1.875M padded to 256
ROWS_STALE = 1 << 20          # fleet merge bucket (joinView stale arena)


@pytest.fixture(scope="module")
def no_compile_cache():
    from jax.experimental.compilation_cache import compilation_cache as cc

    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", prev)
    cc.reset_cache()


@pytest.fixture(scope="module")
def topo(no_compile_cache):
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — no TPU compiler here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def spec(topo):
    from jax.sharding import SingleDeviceSharding

    one_chip = SingleDeviceSharding(topo.devices[0])

    def make(shape, dtype=jnp.float32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    return make


def _compiles_to_kernel(fn, *args):
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()
    return compiled


def test_fused_clean_compiles(spec):
    from repro.kernels.fused_clean.kernel import fused_clean_tiles

    _compiles_to_kernel(
        functools.partial(fused_clean_tiles, seed_mix=0x9E3779B9, thresh=0.1,
                          num_groups=GROUPS_PART, interpret=False),
        spec((ROWS_DELTA, 1), jnp.int32), spec((ROWS_DELTA, 1), jnp.int8),
        spec((ROWS_DELTA, 3)),
    )


def test_outlier_member_compiles(spec):
    from repro.kernels.outlier_member.kernel import KEY_ROWS, outlier_member_tiles

    _compiles_to_kernel(
        functools.partial(outlier_member_tiles, seed_eta=1, seed_hi=2,
                          seed_lo=3, thresh=0.1, interpret=False),
        spec((ROWS_VIEW, 1), jnp.int32), spec((KEY_ROWS, 256), jnp.uint32),
    )


@pytest.mark.parametrize("sides", [1, 2])
def test_multi_agg_compiles(spec, sides):
    """The whole moment pass — slab pack, kernel, partial sums — as the
    engine calls it: the joined panel two-sided, the view one-sided, with
    4 columns, 8 queries and 1 predicate term.  Its temporaries stay under
    256 MB: no copy of the panel or of a row vector padded to 128 lanes."""
    from repro.kernels.multi_agg.ops import multi_agg_tiles_one, multi_agg_tiles_two

    C, Q, P = 4, 8, 1
    rows = ROWS_JOINED if sides == 2 else ROWS_VIEW
    side = [spec((rows, C)), spec((rows,), jnp.bool_), spec((rows,)), spec((rows,))]
    tail = [spec(((1 + P) * C, Q)), spec((2 + 4 * P, Q))]
    fn = multi_agg_tiles_two if sides == 2 else multi_agg_tiles_one
    compiled = _compiles_to_kernel(functools.partial(fn, interpret=False),
                                   *(side * sides + tail))
    assert compiled.memory_analysis().temp_size_in_bytes < 256 << 20


def test_fleet_moments_compiles(spec):
    from repro.kernels.fleet_moments.kernel import BLOCK_V, fleet_moments_tiles

    # two views folded into one lane tile: 64 row chunks per view
    panel = spec((ROWS_JOINED // 64, BLOCK_V))
    _compiles_to_kernel(
        functools.partial(fleet_moments_tiles, interpret=False), *[panel] * 8
    )


def test_fleet_score_compiles(spec):
    from repro.kernels.fleet_score.kernel import BLOCK_V, FEAT_ROWS, fleet_score_tiles

    _compiles_to_kernel(
        functools.partial(fleet_score_tiles, interpret=False),
        spec((FEAT_ROWS, BLOCK_V)),
    )


def test_fleet_merge_compiles(spec):
    from repro.kernels.fleet_merge.kernel import (
        BLOCK_R,
        BLOCK_V,
        fleet_merge_tiles,
    )

    tiles = ROWS_STALE // BLOCK_R
    _compiles_to_kernel(
        functools.partial(fleet_merge_tiles, n_slabs=32, interpret=False),
        spec((tiles,), jnp.int32), spec((tiles,), jnp.int32),
        spec((ROWS_STALE, BLOCK_V), jnp.int32), spec((3, ROWS_STALE, BLOCK_V)),
        spec((GROUPS_PART, BLOCK_V)), spec((3, GROUPS_PART, BLOCK_V)),
        spec((GROUPS_PART, BLOCK_V)), spec((3, GROUPS_PART, BLOCK_V)),
    )


def test_corr_diff_compiles(spec):
    from repro.kernels.corr_diff.kernel import LANES, corr_diff_tiles

    rows = ROWS_JOINED // LANES
    _compiles_to_kernel(
        functools.partial(corr_diff_tiles, interpret=False),
        spec((rows, LANES)), spec((rows, LANES)), spec((rows, LANES), jnp.int8),
    )


def test_hash_threshold_compiles(spec):
    from repro.kernels.hash_threshold.kernel import LANES, hash_threshold_tiles

    col = spec((ROWS_VIEW // LANES, LANES), jnp.int32)
    _compiles_to_kernel(
        lambda a, b: hash_threshold_tiles((a, b), 0x9E3779B9, 0.1, 2,
                                          interpret=False),
        col, col,
    )


def test_segment_aggsum_compiles(spec):
    from repro.kernels.segment_aggsum.kernel import segment_sum_tiles

    _compiles_to_kernel(
        functools.partial(segment_sum_tiles, num_groups=GROUPS_PART,
                          interpret=False),
        spec((ROWS_DELTA, 1), jnp.int32), spec((ROWS_DELTA, 2)),
    )
