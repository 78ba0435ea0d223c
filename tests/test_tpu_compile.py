"""The device data plane's kernels compile for a TPU v5e.

Each test lowers and compiles one op of the data plane with
``interpret=False`` against a described (not attached) v5e chip, at
real widths: 2**21 objects (16 K rows × 128 lanes) in one packed call.
The TPU compiler refuses what interpret mode accepts — scalar stores to
VMEM, blocks not aligned to the (8, 128) tiling, more VMEM than a
kernel may use — so these tests guard the chip path without a chip.
The topology is described inside a fixture, never at import: only one
process may load the TPU library at a time.
"""
import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import SingleDeviceSharding

from repro.core import IndexConfig
from repro.kernels import ops

N = 16384 * 128     # objects per packed call: 16 K rows of 128 lanes


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — any failure means "no TPU lib"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture
def no_persistent_cache():
    """A compile for a described chip is written to the persistent cache
    but cannot be read back without one — keep these compiles out."""
    from jax.experimental.compilation_cache import compilation_cache as cc
    old = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", old)
    cc.reset_cache()


def _shape(sharding, shape, dtype=jnp.float32):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _grouped_args(sharding, n_seg, ncol, with_sid=True):
    flat = _shape(sharding, (N,))
    return (flat, flat, flat, flat if with_sid else None,
            _shape(sharding, (n_seg, ncol)), _shape(sharding, (), jnp.int32))


# (n_seg, window, gx, gy, with_sid): the shapes the index, the serving
# tick and the split paths send to ops._pallas_flat
GROUPED = {
    "segment_window_agg": (8, True, 1, 1, True),
    "segment_window_agg_multi": (64, True, 1, 1, True),
    "segment_bin_agg_edges": (8, False, 4, 4, True),
    "segment_bin_agg": (8, False, 2, 2, True),
    "window_agg": (1, True, 1, 1, False),
    "bin_agg": (1, False, 2, 2, False),
}


@pytest.mark.parametrize("op", sorted(GROUPED))
def test_grouped_kernel_compiles_for_v5e(one_chip, no_persistent_cache,
                                         op):
    n_seg, window, gx, gy, with_sid = GROUPED[op]
    ncol = (4 if window else 0) + (gx - 1) + (gy - 1)
    compiled = ops._pallas_flat.lower(
        *_grouped_args(one_chip, n_seg, ncol, with_sid), n_seg=n_seg,
        window=window, gx=gx, gy=gy, interpret=False).compile()
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("n_seg,bins", [(8, (8, 8)), (64, (2, 2)),
                                        (64, (8, 8))])
def test_fused_select_multi_compiles_for_v5e(one_chip, no_persistent_cache,
                                             n_seg, bins):
    """The serving tick's kernel (``segment_window_bin_select_multi``):
    grouped table + per-span suffix epilogue in one program."""
    bx, by = bins
    xs, ys, vals, sids, params, n = _grouped_args(
        one_chip, n_seg, 4 + (bx - 1) + (by - 1))
    vb = _shape(one_chip, (n_seg,))
    qend = _shape(one_chip, (n_seg,), jnp.int32)
    compiled = ops._segment_window_bin_select_multi_pallas.lower(
        xs, ys, vals, sids, params, vb, vb, qend, n, n_seg=n_seg, bx=bx,
        by=by, interpret=False).compile()
    text = compiled.as_text()
    assert "tpu_custom_call" in text
    mem = compiled.memory_analysis()
    if mem is not None:   # the packed planes fit one chip with room
        assert mem.temp_size_in_bytes < 2 ** 30


def test_compile_shapes_cover_the_planner():
    """The compiled shapes exercise both planner regimes: a single
    program holding every segment, and several cell groups."""
    from repro.kernels.gridplan import plan_cell_groups
    assert plan_cell_groups(64, 1)[1] == 1
    assert plan_cell_groups(64, 64)[1] > 1
    assert np.all([plan_cell_groups(n, k)[0] * k <= 512
                   for n in (1, 8, 64) for k in (1, 4, 16, 64)])


def test_init_order_compiles_for_v5e(one_chip, no_persistent_cache):
    """The init pass's device route at its full chunk on the default
    16×16 grid: binning by threshold and the one-key sort fit one
    chip."""
    gx, gy = IndexConfig().grid0
    g = gx * gy
    shift = 32 - g.bit_length()
    c = min(ops.INIT_CHUNK_ROWS, 1 << shift)
    plane = _shape(one_chip, (c,))
    keys = ops._init_keys.lower(
        plane, plane, _shape(one_chip, (gx - 1,)),
        _shape(one_chip, (gy - 1,)), _shape(one_chip, (), jnp.int32),
        shift=shift).compile()
    order = ops._init_order.lower(
        _shape(one_chip, (c,), jnp.uint32), g=g, shift=shift).compile()
    for compiled in (keys, order):
        mem = compiled.memory_analysis()
        if mem is not None:
            assert mem.temp_size_in_bytes < 2 ** 30
