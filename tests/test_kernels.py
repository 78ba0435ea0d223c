"""Pallas kernels vs pure-jnp oracles: shape/dtype sweeps + properties."""
import numpy as np
import pytest

import jax.numpy as jnp

from repro.kernels import ops, ref

RNG = np.random.default_rng(42)


def _points(n, scale=100.0, dtype=np.float32):
    xs = RNG.uniform(0, scale, n).astype(dtype)
    ys = RNG.uniform(0, scale, n).astype(dtype)
    vs = RNG.normal(0, 10, n).astype(dtype)
    return xs, ys, vs


@pytest.mark.parametrize("n", [1, 7, 127, 1000, 32768, 100001])
def test_window_agg_backends_agree(n):
    xs, ys, vs = _points(n)
    win = np.array([20, 20, 70, 70], np.float32)
    out_np = ops.window_agg(xs, ys, vs, win, backend="np")
    out_jnp = ops.window_agg(xs, ys, vs, win, backend="jnp")
    out_pal = ops.window_agg(xs, ys, vs, win, backend="pallas")
    np.testing.assert_allclose(out_np, np.asarray(out_jnp), rtol=1e-4,
                               atol=1e-4)
    np.testing.assert_allclose(np.asarray(out_jnp), np.asarray(out_pal),
                               rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("n", [1, 255, 4096, 20000])
@pytest.mark.parametrize("grid", [(2, 2), (4, 4), (3, 2), (8, 8)])
def test_bin_agg_backends_agree(n, grid):
    gx, gy = grid
    xs, ys, vs = _points(n)
    bbox = np.array([0, 0, 100, 100], np.float32)
    a = np.asarray(ops.bin_agg(xs, ys, vs, bbox, gx=gx, gy=gy,
                               backend="np"))
    b = np.asarray(ops.bin_agg(xs, ys, vs, bbox, gx=gx, gy=gy,
                               backend="jnp"))
    c = np.asarray(ops.bin_agg(xs, ys, vs, bbox, gx=gx, gy=gy,
                               backend="pallas"))
    # sums accumulate in different orders per backend: scale-aware atol
    np.testing.assert_allclose(a, b, rtol=1e-4, atol=2e-3)
    np.testing.assert_allclose(b, c, rtol=1e-5, atol=2e-3)
    np.testing.assert_array_equal(a[:, 0], b[:, 0])  # counts exact
    np.testing.assert_array_equal(b[:, 0], c[:, 0])


def test_window_agg_matches_bruteforce():
    xs, ys, vs = _points(5000)
    win = np.array([10, 30, 60, 90], np.float32)
    m = (xs >= win[0]) & (xs <= win[2]) & (ys >= win[1]) & (ys <= win[3])
    got = np.asarray(ops.window_agg(xs, ys, vs, win, backend="pallas"))
    assert got[0] == m.sum()
    np.testing.assert_allclose(got[1], vs[m].sum(dtype=np.float64),
                               rtol=1e-4)
    np.testing.assert_allclose(got[2], vs[m].min(), rtol=1e-6)
    np.testing.assert_allclose(got[3], vs[m].max(), rtol=1e-6)


def test_window_agg_empty_window():
    xs, ys, vs = _points(1000)
    win = np.array([200, 200, 300, 300], np.float32)  # outside domain
    got = np.asarray(ops.window_agg(xs, ys, vs, win, backend="pallas"))
    assert got[0] == 0 and got[1] == 0
    assert np.isinf(got[2]) and got[2] > 0
    assert np.isinf(got[3]) and got[3] < 0


def test_bin_agg_partitions_objects():
    """Each in-bbox object lands in exactly one cell: counts sum to n."""
    xs, ys, vs = _points(9999)
    bbox = np.array([0, 0, 100, 100], np.float32)
    for grid in [(2, 2), (4, 4), (5, 3)]:
        out = np.asarray(ops.bin_agg(xs, ys, vs, bbox, gx=grid[0],
                                     gy=grid[1], backend="pallas"))
        assert out[:, 0].sum() == len(xs)


def test_bin_agg_cell_consistency_with_window_agg():
    """bin_agg cell == window_agg over that cell's rectangle."""
    xs, ys, vs = _points(4000)
    bbox = np.array([0, 0, 100, 100], np.float32)
    gx = gy = 2
    cells = np.asarray(ops.bin_agg(xs, ys, vs, bbox, gx=gx, gy=gy,
                                   backend="jnp"))
    # cell (0,0) = [0,50)x[0,50): use a window slightly inside the edge
    eps = 1e-4
    win = np.array([0, 0, 50 - eps, 50 - eps], np.float32)
    wagg = np.asarray(ops.window_agg(xs, ys, vs, win, backend="jnp"))
    # boundary objects may differ by the half-open convention; tolerate
    # only exact match when no object sits on the seam
    on_seam = np.isclose(xs, 50).any() or np.isclose(ys, 50).any()
    if not on_seam:
        assert cells[0, 0] == wagg[0]


def test_dtype_sweep_window_agg():
    for dt in (np.float32, np.float64, np.int32):
        xs, ys, _ = _points(512)
        vs = RNG.integers(-100, 100, 512).astype(dt)
        win = np.array([10, 10, 90, 90], np.float32)
        a = np.asarray(ops.window_agg(xs, ys, vs, win, backend="np"))
        b = np.asarray(ops.window_agg(xs, ys, vs, win, backend="pallas"))
        np.testing.assert_allclose(a, b, rtol=1e-5, atol=1e-5)


def _segments(lens, scale=100.0):
    bounds = np.concatenate([[0], np.cumsum(lens)]).astype(np.int64)
    xs, ys, vs = _points(int(bounds[-1]), scale=scale)
    return xs, ys, vs, bounds


@pytest.mark.parametrize("lens", [[1], [0, 37, 500, 128, 3],
                                  [4096, 1, 4096], [256] * 8])
def test_segment_window_agg_backends_agree(lens):
    xs, ys, vs, bounds = _segments(lens)
    win = np.array([20, 20, 70, 70], np.float32)
    a = np.asarray(ops.segment_window_agg(xs, ys, vs, bounds, win,
                                          backend="np"))
    b = np.asarray(ops.segment_window_agg(xs, ys, vs, bounds, win,
                                          backend="jnp"))
    c = np.asarray(ops.segment_window_agg(xs, ys, vs, bounds, win,
                                          backend="pallas"))
    np.testing.assert_allclose(a, b, rtol=1e-4, atol=2e-3)
    np.testing.assert_allclose(b, c, rtol=1e-5, atol=2e-3)
    np.testing.assert_array_equal(a[:, 0], b[:, 0])  # counts exact
    np.testing.assert_array_equal(b[:, 0], c[:, 0])
    # packed call ≡ one window_agg per segment
    for s in range(len(lens)):
        sl = slice(bounds[s], bounds[s + 1])
        if lens[s]:
            want = np.asarray(ops.window_agg(xs[sl], ys[sl], vs[sl], win,
                                             backend="np"), np.float64)
        else:
            want = np.array([0, 0, np.inf, -np.inf], np.float64)
        np.testing.assert_allclose(a[s], want, rtol=1e-4, atol=1e-3)


@pytest.mark.parametrize("lens", [[1, 300], [0, 37, 500, 128, 3],
                                  [700] * 6])
@pytest.mark.parametrize("grid", [(2, 2), (3, 2)])
def test_segment_bin_agg_backends_agree(lens, grid):
    gx, gy = grid
    xs, ys, vs, bounds = _segments(lens)
    rng = np.random.default_rng(9)
    n_seg = len(lens)
    # heterogeneous per-segment bboxes (each tile splits its own extent)
    lo = rng.uniform(0, 40, (n_seg, 2))
    hi = lo + rng.uniform(30, 60, (n_seg, 2))
    bboxes = np.concatenate([lo, hi], axis=1).astype(np.float32)
    a = np.asarray(ops.segment_bin_agg(xs, ys, vs, bounds, bboxes,
                                       gx=gx, gy=gy, backend="np"))
    b = np.asarray(ops.segment_bin_agg(xs, ys, vs, bounds, bboxes,
                                       gx=gx, gy=gy, backend="jnp"))
    c = np.asarray(ops.segment_bin_agg(xs, ys, vs, bounds, bboxes,
                                       gx=gx, gy=gy, backend="pallas"))
    np.testing.assert_allclose(a, b, rtol=1e-4, atol=2e-3)
    np.testing.assert_allclose(b, c, rtol=1e-5, atol=2e-3)
    np.testing.assert_array_equal(a[:, :, 0], b[:, :, 0])
    np.testing.assert_array_equal(b[:, :, 0], c[:, :, 0])
    # packed call ≡ one bin_agg per segment against its own bbox
    for s in range(n_seg):
        sl = slice(bounds[s], bounds[s + 1])
        if not lens[s]:
            continue
        want = np.asarray(ops.bin_agg(xs[sl], ys[sl], vs[sl], bboxes[s],
                                      gx=gx, gy=gy, backend="np"),
                          np.float64)
        np.testing.assert_allclose(a[s], want, rtol=1e-4, atol=2e-3)


@pytest.mark.parametrize("lens", [[1, 300], [0, 37, 500, 128, 3],
                                  [700] * 6])
@pytest.mark.parametrize("grid", [(2, 2), (3, 2)])
def test_segment_bin_agg_edges_backends_agree(lens, grid):
    """Bin-aligned split kernel: per-segment explicit edges across all
    backends; uniform edges reproduce cell totals of the bbox variant."""
    gx, gy = grid
    xs, ys, vs, bounds = _segments(lens)
    rng = np.random.default_rng(11)
    n_seg = len(lens)
    lo = rng.uniform(0, 40, (n_seg, 2))
    hi = lo + rng.uniform(30, 60, (n_seg, 2))
    # non-uniform interior edges (snapped-split shape): random cuts
    # strictly inside each extent, sorted
    xe = np.concatenate(
        [lo[:, :1], np.sort(rng.uniform(lo[:, :1] + 1, hi[:, :1] - 1,
                                        (n_seg, gx - 1)), axis=1),
         hi[:, :1]], axis=1)
    ye = np.concatenate(
        [lo[:, 1:], np.sort(rng.uniform(lo[:, 1:] + 1, hi[:, 1:] - 1,
                                        (n_seg, gy - 1)), axis=1),
         hi[:, 1:]], axis=1)
    a = np.asarray(ops.segment_bin_agg_edges(xs, ys, vs, bounds, xe, ye,
                                             backend="np"))
    b = np.asarray(ops.segment_bin_agg_edges(xs, ys, vs, bounds, xe, ye,
                                             backend="jnp"))
    c = np.asarray(ops.segment_bin_agg_edges(xs, ys, vs, bounds, xe, ye,
                                             backend="pallas"))
    np.testing.assert_allclose(a, b, rtol=1e-4, atol=2e-3)
    np.testing.assert_allclose(b, c, rtol=1e-5, atol=2e-3)
    np.testing.assert_array_equal(b[:, :, 0], c[:, :, 0])
    # cells partition every segment (ownership: each object in exactly
    # one cell, outer overflow clamped in)
    np.testing.assert_array_equal(a[:, :, 0].sum(axis=1),
                                  np.diff(bounds))
    # composition invariance of the np mirror: packed == per-segment
    for s in range(n_seg):
        sl = slice(bounds[s], bounds[s + 1])
        solo = np.asarray(ops.segment_bin_agg_edges(
            xs[sl], ys[sl], vs[sl], [0, lens[s]], xe[s:s + 1],
            ye[s:s + 1], backend="np"))[0]
        np.testing.assert_array_equal(a[s], solo)


@pytest.mark.parametrize("lens", [[1, 300], [0, 37, 500, 128, 3]])
@pytest.mark.parametrize("grid", [(2, 2), (4, 3)])
def test_segment_window_bin_agg_backends_agree(lens, grid):
    bx, by = grid
    xs, ys, vs, bounds = _segments(lens)
    win = np.array([15, 25, 80, 75], np.float32)
    a = np.asarray(ops.segment_window_bin_agg(xs, ys, vs, bounds, win,
                                              bx=bx, by=by, backend="np"))
    b = np.asarray(ops.segment_window_bin_agg(xs, ys, vs, bounds, win,
                                              bx=bx, by=by, backend="jnp"))
    c = np.asarray(ops.segment_window_bin_agg(xs, ys, vs, bounds, win,
                                              bx=bx, by=by,
                                              backend="pallas"))
    np.testing.assert_allclose(a, b, rtol=1e-4, atol=2e-3)
    np.testing.assert_allclose(b, c, rtol=1e-5, atol=2e-3)
    np.testing.assert_array_equal(a[:, :, 0], b[:, :, 0])  # counts exact
    np.testing.assert_array_equal(b[:, :, 0], c[:, :, 0])
    # per-segment bins partition the segment's in-window selection, and
    # summing a segment's bins reproduces its window_agg
    m = (xs >= win[0]) & (xs <= win[2]) & (ys >= win[1]) & (ys <= win[3])
    for s in range(len(lens)):
        sl = slice(bounds[s], bounds[s + 1])
        assert a[s, :, 0].sum() == m[sl].sum()
        want = np.asarray(ops.segment_window_agg(
            xs[sl], ys[sl], vs[sl], [0, lens[s]], win, backend="np"))[0]
        np.testing.assert_allclose(a[s, :, 1].sum(), want[1],
                                   rtol=1e-9, atol=1e-9)
        if m[sl].any():
            assert a[s, :, 2].min() == want[2]
            assert a[s, :, 3].max() == want[3]


@pytest.mark.parametrize("lens", [[1, 300], [0, 37, 500, 128, 3],
                                  [600] * 5])
@pytest.mark.parametrize("grid", [(2, 2), (5, 3)])
def test_grouped_extrema_channels_backend_sweep(lens, grid):
    """The min/max channels of the grouped (per-segment, per-window-bin)
    kernels — the state the min/max heatmap aggregates and the
    distributed pmin/pmax merge consume. Adversarial values (all
    negative, so a zero-initialized reduction would corrupt them),
    empty bins (±inf), and singleton segments, swept across np/jnp/
    pallas; extrema don't round, so the backends must agree EXACTLY."""
    bx, by = grid
    nb = bx * by
    xs, ys, vs, bounds = _segments(lens)
    vs = -np.abs(vs) - 1.0          # strictly negative values
    win = np.array([15, 25, 80, 75], np.float32)
    a = np.asarray(ops.segment_window_bin_agg(xs, ys, vs, bounds, win,
                                              bx=bx, by=by, backend="np"))
    b = np.asarray(ops.segment_window_bin_agg(xs, ys, vs, bounds, win,
                                              bx=bx, by=by, backend="jnp"))
    c = np.asarray(ops.segment_window_bin_agg(xs, ys, vs, bounds, win,
                                              bx=bx, by=by,
                                              backend="pallas"))
    for other in (b, c):
        np.testing.assert_array_equal(a[:, :, 0], other[:, :, 0])
        np.testing.assert_array_equal(a[:, :, 2].astype(np.float32),
                                      other[:, :, 2])   # min channel
        np.testing.assert_array_equal(a[:, :, 3].astype(np.float32),
                                      other[:, :, 3])   # max channel
    # brute-force per-(segment, bin) extrema oracle
    m = (xs >= win[0]) & (xs <= win[2]) & (ys >= win[1]) & (ys <= win[3])
    cw = max((win[2] - win[0]) / bx, 1e-30)
    ch = max((win[3] - win[1]) / by, 1e-30)
    cx = np.clip(np.floor((xs - win[0]) / cw).astype(np.int64), 0, bx - 1)
    cy = np.clip(np.floor((ys - win[1]) / ch).astype(np.int64), 0, by - 1)
    cid = cy * bx + cx
    for s in range(len(lens)):
        sl = slice(bounds[s], bounds[s + 1])
        for cell in range(nb):
            sel = vs[sl][m[sl] & (cid[sl] == cell)]
            if sel.size:
                assert a[s, cell, 2] == sel.min(), (s, cell)
                assert a[s, cell, 3] == sel.max(), (s, cell)
            else:                   # empty bins: ±inf sentinels
                assert np.isinf(a[s, cell, 2]) and a[s, cell, 2] > 0
                assert np.isinf(a[s, cell, 3]) and a[s, cell, 3] < 0


def test_segment_window_bin_agg_batch_composition_invariant():
    """k-segment packed call == concatenation of k single-segment calls
    bit-for-bit (the np mirror's per-cell slice arithmetic is independent
    of batch composition — what makes batched == sequential exact)."""
    lens = [64, 0, 129, 1000]
    xs, ys, vs, bounds = _segments(lens)
    win = np.array([10, 10, 90, 90], np.float32)
    packed = np.asarray(ops.segment_window_bin_agg(
        xs, ys, vs, bounds, win, bx=3, by=3, backend="np"))
    for s in range(len(lens)):
        sl = slice(bounds[s], bounds[s + 1])
        solo = np.asarray(ops.segment_window_bin_agg(
            xs[sl], ys[sl], vs[sl], [0, lens[s]], win, bx=3, by=3,
            backend="np"))[0]
        np.testing.assert_array_equal(packed[s], solo)


def test_segment_window_agg_everywhere_is_full_segment():
    """An all-covering window yields full-segment (enrichment) stats."""
    xs, ys, vs, bounds = _segments([64, 0, 129])
    win = np.array([-np.inf, -np.inf, np.inf, np.inf])
    a = ops.segment_window_agg(xs, ys, vs, bounds, win, backend="np")
    for s, (i, j) in enumerate(zip(bounds[:-1], bounds[1:])):
        if j > i:
            assert a[s, 0] == j - i
            np.testing.assert_allclose(
                a[s, 1], vs[i:j].sum(dtype=np.float64), rtol=0)
            assert a[s, 2] == vs[i:j].min()
            assert a[s, 3] == vs[i:j].max()


@pytest.mark.parametrize("lens", [[1], [0, 37, 500, 128, 3],
                                  [4096, 1, 4096], [256] * 8])
def test_segment_window_agg_multi_backends_agree(lens):
    """Serving-tick kernel: each segment filtered by its OWN window."""
    xs, ys, vs, bounds = _segments(lens)
    rng = np.random.default_rng(17)
    n_seg = len(lens)
    lo = rng.uniform(0, 60, (n_seg, 2))
    wins = np.concatenate(
        [lo, lo + rng.uniform(20, 40, (n_seg, 2))], axis=1
    ).astype(np.float32)
    a = np.asarray(ops.segment_window_agg_multi(xs, ys, vs, bounds, wins,
                                                backend="np"))
    b = np.asarray(ops.segment_window_agg_multi(xs, ys, vs, bounds, wins,
                                                backend="jnp"))
    c = np.asarray(ops.segment_window_agg_multi(xs, ys, vs, bounds, wins,
                                                backend="pallas"))
    np.testing.assert_allclose(a, b, rtol=1e-4, atol=2e-3)
    np.testing.assert_allclose(b, c, rtol=1e-5, atol=2e-3)
    np.testing.assert_array_equal(a[:, 0], b[:, 0])  # counts exact
    np.testing.assert_array_equal(b[:, 0], c[:, 0])
    # packed multi-window call ≡ one single-window call per segment —
    # bit-for-bit on the np mirror (what makes a micro-batched serving
    # tick equal the sequential reference)
    for s in range(n_seg):
        sl = slice(bounds[s], bounds[s + 1])
        solo = np.asarray(ops.segment_window_agg(
            xs[sl], ys[sl], vs[sl], [0, lens[s]], wins[s],
            backend="np"))[0]
        np.testing.assert_array_equal(a[s], solo)


@pytest.mark.parametrize("lens", [[1, 300], [0, 37, 500, 128, 3],
                                  [600] * 5])
@pytest.mark.parametrize("grid", [(2, 2), (4, 3)])
def test_segment_window_bin_agg_multi_backends_agree(lens, grid):
    """Heatmap serving-tick kernel: per-segment own window + bin grid."""
    bx, by = grid
    xs, ys, vs, bounds = _segments(lens)
    rng = np.random.default_rng(19)
    n_seg = len(lens)
    lo = rng.uniform(0, 50, (n_seg, 2))
    wins = np.concatenate(
        [lo, lo + rng.uniform(25, 45, (n_seg, 2))], axis=1
    ).astype(np.float32)
    a = np.asarray(ops.segment_window_bin_agg_multi(
        xs, ys, vs, bounds, wins, bx=bx, by=by, backend="np"))
    b = np.asarray(ops.segment_window_bin_agg_multi(
        xs, ys, vs, bounds, wins, bx=bx, by=by, backend="jnp"))
    c = np.asarray(ops.segment_window_bin_agg_multi(
        xs, ys, vs, bounds, wins, bx=bx, by=by, backend="pallas"))
    np.testing.assert_allclose(a, b, rtol=1e-4, atol=2e-3)
    np.testing.assert_allclose(b, c, rtol=1e-5, atol=2e-3)
    np.testing.assert_array_equal(a[:, :, 0], b[:, :, 0])  # counts exact
    np.testing.assert_array_equal(b[:, :, 0], c[:, :, 0])
    # packed ≡ per-segment single-window bin kernel, bit-for-bit (np)
    for s in range(n_seg):
        sl = slice(bounds[s], bounds[s + 1])
        solo = np.asarray(ops.segment_window_bin_agg(
            xs[sl], ys[sl], vs[sl], [0, lens[s]], wins[s], bx=bx, by=by,
            backend="np"))[0]
        np.testing.assert_array_equal(a[s], solo)


def _seg_bounds_vminmax(lens, rng_seed=23):
    """Segments plus per-segment sound value intervals (fold order)."""
    xs, ys, vs, bounds = _segments(lens)
    rng = np.random.default_rng(rng_seed)
    n_seg = len(lens)
    vmin_s = rng.uniform(-40, -10, n_seg).astype(np.float32)
    vmax_s = vmin_s + rng.uniform(5, 60, n_seg).astype(np.float32)
    return xs, ys, vs, bounds, vmin_s, vmax_s


@pytest.mark.parametrize("lens", [[1, 300], [0, 37, 500, 128, 3],
                                  [1201, 0, 1799, 3001]])
@pytest.mark.parametrize("grid", [(1, 1), (2, 2), (4, 3)])
def test_fused_select_backends_agree(lens, grid):
    """Fused classify→scatter→select megakernel: three-backend parity on
    both outputs. lens includes odd counts (padded-tail rows of the 2-D
    grid), empty segments, and a (1,1) single-bin grid (the scalar-query
    route through nb=1)."""
    bx, by = grid
    xs, ys, vs, bounds, vmin_s, vmax_s = _seg_bounds_vminmax(lens)
    win = np.array([15, 25, 80, 75], np.float32)
    a_agg, a_w = ops.segment_window_bin_select(
        xs, ys, vs, bounds, win, vmin_s, vmax_s, bx=bx, by=by,
        backend="np")
    b_agg, b_w = ops.segment_window_bin_select(
        xs, ys, vs, bounds, win, vmin_s, vmax_s, bx=bx, by=by,
        backend="jnp")
    c_agg, c_w = ops.segment_window_bin_select(
        xs, ys, vs, bounds, win, vmin_s, vmax_s, bx=bx, by=by,
        backend="pallas")
    a_agg, b_agg, c_agg = (np.asarray(o) for o in (a_agg, b_agg, c_agg))
    a_w, b_w, c_w = (np.asarray(o) for o in (a_w, b_w, c_w))
    np.testing.assert_allclose(a_agg, b_agg, rtol=1e-4, atol=2e-3)
    np.testing.assert_allclose(b_agg, c_agg, rtol=1e-5, atol=2e-3)
    np.testing.assert_array_equal(a_agg[:, :, 0], b_agg[:, :, 0])
    np.testing.assert_array_equal(b_agg[:, :, 0], c_agg[:, :, 0])
    # the np fused agg IS the composed np grouped kernel, bit-for-bit —
    # fusion may not move a single ulp of the established mirror
    composed = np.asarray(ops.segment_window_bin_agg(
        xs, ys, vs, bounds, win, bx=bx, by=by, backend="np"))
    np.testing.assert_array_equal(a_agg, composed)
    # suffix widths: shape (S+1, nb), row S exactly zero on EVERY
    # backend (the "all segments folded" row — φ=0 must be reachable)
    n_seg, nb = len(lens), bx * by
    for w in (a_w, b_w, c_w):
        assert w.shape == (n_seg + 1, nb)
        np.testing.assert_array_equal(w[-1], np.zeros(nb, w.dtype))
        assert (np.diff(w[::-1], axis=0) >= 0).all()  # monotone fold
    np.testing.assert_allclose(a_w, b_w, rtol=1e-4, atol=2e-3)
    np.testing.assert_allclose(b_w, c_w, rtol=1e-5, atol=2e-3)
    # f64 oracle for the np suffix widths: reversed cumsum of cnt·Δv
    dv = (vmax_s - vmin_s).astype(np.float64)
    per = composed[:, :, 0] * dv[:, None]
    want = np.concatenate(
        [np.cumsum(per[::-1], axis=0)[::-1], np.zeros((1, nb))])
    np.testing.assert_array_equal(a_w, want)


@pytest.mark.parametrize("lens", [[0, 37, 500, 128, 3], [600] * 5])
def test_fused_select_all_negative_values(lens):
    """All-negative value plane: a zero-initialized accumulator would
    corrupt max; extrema must stay exact across the fused backends."""
    bx = by = 2
    xs, ys, vs, bounds, vmin_s, vmax_s = _seg_bounds_vminmax(lens)
    vs = -np.abs(vs) - 1.0
    win = np.array([15, 25, 80, 75], np.float32)
    outs = [ops.segment_window_bin_select(
        xs, ys, vs, bounds, win, vmin_s, vmax_s, bx=bx, by=by,
        backend=bk) for bk in ("np", "jnp", "pallas")]
    a = np.asarray(outs[0][0])
    for agg, _ in outs[1:]:
        agg = np.asarray(agg)
        np.testing.assert_array_equal(a[:, :, 0], agg[:, :, 0])
        np.testing.assert_array_equal(a[:, :, 2].astype(np.float32),
                                      agg[:, :, 2])
        np.testing.assert_array_equal(a[:, :, 3].astype(np.float32),
                                      agg[:, :, 3])
    assert (a[a[:, :, 0] > 0, 3] < 0).all()  # maxima stay negative


def test_fused_select_empty_window():
    """A window covering no points: zero counts, ±inf extrema, and the
    suffix widths still fold to exactly zero everywhere (cnt=0 ⇒ w=0)."""
    xs, ys, vs, bounds, vmin_s, vmax_s = _seg_bounds_vminmax(
        [64, 0, 129])
    win = np.array([200, 200, 300, 300], np.float32)  # off the domain
    for bk in ("np", "jnp", "pallas"):
        agg, w = ops.segment_window_bin_select(
            xs, ys, vs, bounds, win, vmin_s, vmax_s, bx=2, by=2,
            backend=bk)
        agg, w = np.asarray(agg), np.asarray(w)
        np.testing.assert_array_equal(agg[:, :, 0],
                                      np.zeros_like(agg[:, :, 0]))
        assert (agg[:, :, 2] > 0).all() and np.isinf(agg[:, :, 2]).all()
        assert (agg[:, :, 3] < 0).all() and np.isinf(agg[:, :, 3]).all()
        np.testing.assert_array_equal(w, np.zeros_like(w))


@pytest.mark.parametrize("seg_group", [1, 2, 3])
def test_fused_select_forced_multi_group(seg_group):
    """The 2-D grid's outer (cell-group) axis: forcing group sizes that
    split 5 segments across 2–5 programs must be bit-identical to the
    planner's own choice — accumulation order within a (t, c) cell is
    row-block order either way."""
    lens = [301, 0, 512, 77, 1000]
    xs, ys, vs, bounds, vmin_s, vmax_s = _seg_bounds_vminmax(lens)
    win = np.array([10, 10, 90, 90], np.float32)
    base_agg, base_w = ops.segment_window_bin_select(
        xs, ys, vs, bounds, win, vmin_s, vmax_s, bx=3, by=2,
        backend="pallas")
    agg, w = ops.segment_window_bin_select(
        xs, ys, vs, bounds, win, vmin_s, vmax_s, bx=3, by=2,
        backend="pallas", seg_group=seg_group)
    np.testing.assert_array_equal(np.asarray(base_agg), np.asarray(agg))
    np.testing.assert_array_equal(np.asarray(base_w), np.asarray(w))


def _multi_setup(lens, rng_seed=29, degenerate_at=None):
    """Segments + per-segment own windows + sound value intervals."""
    xs, ys, vs, bounds = _segments(lens)
    rng = np.random.default_rng(rng_seed)
    n_seg = len(lens)
    lo = rng.uniform(0, 50, (n_seg, 2))
    wins = np.concatenate(
        [lo, lo + rng.uniform(25, 45, (n_seg, 2))], axis=1
    ).astype(np.float32)
    if degenerate_at is not None:
        wins[degenerate_at] = (2.0, 2.0, 2.0, 2.0)  # zero-area window
    vmin_s = rng.uniform(-40, -10, n_seg).astype(np.float32)
    vmax_s = vmin_s + rng.uniform(5, 60, n_seg).astype(np.float32)
    return xs, ys, vs, bounds, wins, vmin_s, vmax_s


@pytest.mark.parametrize("lens", [[1, 300], [0, 37, 500, 128, 3],
                                  [1201, 0, 1799, 3001]])
@pytest.mark.parametrize("grid", [(1, 1), (2, 2), (4, 3)])
def test_fused_select_multi_backends_agree(lens, grid):
    """Multi-window fused select: three-backend parity on both outputs,
    with padded 2-D-grid tails (odd counts), empty segments, a
    degenerate zero-area window, and a qbounds layout that includes an
    EMPTY query span. Counts AND extrema are bit-equal across backends
    (the contract-params binning, not the rescaled-float one)."""
    bx, by = grid
    xs, ys, vs, bounds, wins, vmin_s, vmax_s = _multi_setup(
        lens, degenerate_at=len(lens) // 2)
    n_seg, nb = len(lens), bx * by
    # spans: [0, 1), [1, n-1), [n-1, n-1) empty, [n-1, n)
    qb = np.array([0, 1, n_seg - 1, n_seg - 1, n_seg], np.int64)
    outs = [ops.segment_window_bin_select_multi(
        xs, ys, vs, bounds, wins, vmin_s, vmax_s, qbounds=qb,
        bx=bx, by=by, backend=bk) for bk in ("np", "jnp", "pallas")]
    (a_agg, a_w), (b_agg, b_w), (c_agg, c_w) = (
        (np.asarray(agg), np.asarray(w)) for agg, w in outs)
    np.testing.assert_allclose(a_agg, b_agg, rtol=1e-4, atol=2e-3)
    np.testing.assert_allclose(b_agg, c_agg, rtol=1e-5, atol=2e-3)
    for o in (b_agg, c_agg):  # counts and extrema: bit-equal
        np.testing.assert_array_equal(a_agg[:, :, 0], o[:, :, 0])
        np.testing.assert_array_equal(
            a_agg[:, :, 2].astype(np.float32), o[:, :, 2])
        np.testing.assert_array_equal(
            a_agg[:, :, 3].astype(np.float32), o[:, :, 3])
    # the np agg IS the established multi-window host mirror, bitwise
    np.testing.assert_array_equal(a_agg, ref.segment_window_bin_agg_multi_np(
        xs, ys, vs, bounds, wins, bx, by))
    # suffix widths: (S, nb); each span's rows are its own f64 reversed
    # cumsum of cnt·Δv, bit-for-bit on the np mirror
    dv = (vmax_s - vmin_s).astype(np.float64)
    per = a_agg[:, :, 0] * dv[:, None]
    want = np.zeros((n_seg, nb))
    for q in range(len(qb) - 1):
        s, e = int(qb[q]), int(qb[q + 1])
        if e > s:
            want[s:e] = np.cumsum(per[s:e][::-1], axis=0)[::-1]
    assert a_w.shape == (n_seg, nb)
    np.testing.assert_array_equal(a_w, want)
    np.testing.assert_allclose(a_w, b_w, rtol=1e-4, atol=2e-3)
    np.testing.assert_allclose(b_w, c_w, rtol=1e-5, atol=2e-3)


@pytest.mark.parametrize("lens", [[0, 37, 500, 128, 3], [600] * 5])
def test_fused_select_multi_all_negative_values(lens):
    """All-negative value plane through the multi-window kernel: maxima
    must stay negative and bit-equal across the fused backends."""
    bx = by = 2
    xs, ys, vs, bounds, wins, vmin_s, vmax_s = _multi_setup(lens)
    vs = -np.abs(vs) - 1.0
    outs = [ops.segment_window_bin_select_multi(
        xs, ys, vs, bounds, wins, vmin_s, vmax_s, bx=bx, by=by,
        backend=bk) for bk in ("np", "jnp", "pallas")]
    a = np.asarray(outs[0][0])
    for agg, _ in outs[1:]:
        agg = np.asarray(agg)
        np.testing.assert_array_equal(a[:, :, 0], agg[:, :, 0])
        np.testing.assert_array_equal(a[:, :, 2].astype(np.float32),
                                      agg[:, :, 2])
        np.testing.assert_array_equal(a[:, :, 3].astype(np.float32),
                                      agg[:, :, 3])
    assert (a[a[:, :, 0] > 0, 3] < 0).all()


def test_fused_select_multi_empty_windows():
    """Every per-segment window off the data domain: zero counts, ±inf
    extrema, zero suffix widths on every backend (single default span —
    qbounds omitted)."""
    xs, ys, vs, bounds, wins, vmin_s, vmax_s = _multi_setup([64, 0, 129])
    wins = wins + 500.0  # all windows off the [0, 100] domain
    for bk in ("np", "jnp", "pallas"):
        agg, w = ops.segment_window_bin_select_multi(
            xs, ys, vs, bounds, wins, vmin_s, vmax_s, bx=2, by=2,
            backend=bk)
        agg, w = np.asarray(agg), np.asarray(w)
        np.testing.assert_array_equal(agg[:, :, 0],
                                      np.zeros_like(agg[:, :, 0]))
        assert (agg[:, :, 2] > 0).all() and np.isinf(agg[:, :, 2]).all()
        assert (agg[:, :, 3] < 0).all() and np.isinf(agg[:, :, 3]).all()
        np.testing.assert_array_equal(w, np.zeros_like(w))


@pytest.mark.parametrize("seg_group", [1, 2, 3])
def test_fused_select_multi_forced_multi_group(seg_group):
    """Forced cell-group sizes across the 2-D grid's outer axis must be
    bit-identical to the planner's own choice for the multi kernel —
    same row-block accumulation order per (t, c) cell, and the per-group
    param rows must stream in aligned with their segments."""
    lens = [301, 0, 512, 77, 1000]
    xs, ys, vs, bounds, wins, vmin_s, vmax_s = _multi_setup(lens)
    qb = np.array([0, 2, 5], np.int64)
    base = ops.segment_window_bin_select_multi(
        xs, ys, vs, bounds, wins, vmin_s, vmax_s, qbounds=qb,
        bx=3, by=2, backend="pallas")
    got = ops.segment_window_bin_select_multi(
        xs, ys, vs, bounds, wins, vmin_s, vmax_s, qbounds=qb,
        bx=3, by=2, backend="pallas", seg_group=seg_group)
    np.testing.assert_array_equal(np.asarray(base[0]), np.asarray(got[0]))
    np.testing.assert_array_equal(np.asarray(base[1]), np.asarray(got[1]))


def test_window_bin_params_binning_contract():
    """THE binning contract, property-tested: device binning from the
    host-precomputed ``ref.window_bin_params`` rows is bit-identical to
    ``ref.window_bin_ids_np`` on float32 coordinates — random windows,
    points snapped onto closed window edges and interior grid lines,
    plus a degenerate zero-area window — and both match
    ``geometry.bin_cell_ids`` (the ownership rule) whenever coordinates
    and cell sizes are exactly representable."""
    from repro.core import geometry
    from repro.kernels import fused_select
    rng = np.random.default_rng(41)
    bx, by = 5, 3
    windows = [np.array([2.0, 2.0, 2.0, 2.0])]  # degenerate
    for _ in range(12):
        x0, y0 = rng.uniform(0, 50, 2)
        windows.append(np.array([x0, y0, x0 + rng.uniform(0.01, 60),
                                 y0 + rng.uniform(0.01, 60)]))
    for win in windows:
        xs = rng.uniform(-5, 115, 4096).astype(np.float32)
        ys = rng.uniform(-5, 115, 4096).astype(np.float32)
        # snap a slice of points onto the window edges and onto the
        # host rule's own grid lines (the adversarial coordinates)
        w32 = win.astype(np.float32)
        xs[:64] = np.resize(w32[[0, 2]], 64)
        ys[64:128] = np.resize(w32[[1, 3]], 64)
        cw = np.float32(max((win[2] - win[0]) / bx, 1e-30))
        ch = np.float32(max((win[3] - win[1]) / by, 1e-30))
        xs[128:192] = (w32[0] + cw * np.arange(64, dtype=np.float32)
                       % (bx + 1)).astype(np.float32)
        ys[192:256] = (w32[1] + ch * np.arange(64, dtype=np.float32)
                       % (by + 1)).astype(np.float32)
        m_h, cid_h = ref.window_bin_ids_np(xs, ys, win, bx, by)
        params = ref.window_bin_params(win[None, :], bx, by)
        p = jnp.broadcast_to(jnp.asarray(params[0]), (len(xs), 6))
        m_d, cid_d = fused_select.window_bin_ids_params(
            jnp.asarray(xs), jnp.asarray(ys), p, bx, by)
        m_d, cid_d = np.asarray(m_d), np.asarray(cid_d)
        np.testing.assert_array_equal(m_h, m_d)
        np.testing.assert_array_equal(cid_h[m_h], cid_d[m_d])
    # exactly-representable case: host rule ≡ geometry ownership rule
    win = np.array([0.0, 0.0, 80.0, 48.0])  # cw=16, ch=16 exactly
    xs = (rng.integers(-16, 200, 4096) * 0.5).astype(np.float32)
    ys = (rng.integers(-16, 120, 4096) * 0.5).astype(np.float32)
    m_h, cid_h = ref.window_bin_ids_np(xs, ys, win, bx, by)
    cid_g = geometry.bin_cell_ids(xs.astype(np.float64),
                                  ys.astype(np.float64), win, bx, by)
    np.testing.assert_array_equal(cid_h[m_h], cid_g[m_h])


def _near(t):
    """Each threshold and its two float32 neighbours."""
    t = np.asarray(t, np.float32).ravel()
    up = np.float32(np.inf)
    return np.concatenate([t, np.nextafter(t, up), np.nextafter(t, -up)])


@pytest.mark.parametrize("bins", [(1, 1), (5, 3), (8, 8)])
def test_window_bin_thresholds_match_host_rule(bins):
    """The Pallas binning contract: ``Σ_j 1[x ≥ t_j]`` over the
    ``ref.window_bin_thresholds`` row is bit-identical to
    ``ref.window_bin_ids_np`` (mask and bin ids), including points on
    and one ulp either side of every threshold and window edge, and a
    degenerate zero-area window."""
    bx, by = bins
    rng = np.random.default_rng(5)
    windows = [(2.0, 2.0, 2.0, 2.0)]
    for _ in range(8):
        x0, y0 = rng.uniform(-50, 500, 2)
        windows.append((float(x0), float(y0),
                        float(x0 + rng.uniform(1e-3, 400)),
                        float(y0 + rng.uniform(1e-3, 400))))
    for win in windows:
        p = ref.window_bin_thresholds(np.array([win]), bx, by)[0]
        tx, ty = p[4:4 + bx - 1], p[4 + bx - 1:]
        xs = np.concatenate([rng.uniform(win[0] - 5, win[2] + 5, 3000),
                             _near(np.concatenate([tx, p[[0, 2]]]))])
        ys = np.concatenate([rng.uniform(win[1] - 5, win[3] + 5, 3000),
                             _near(np.concatenate([ty, p[[1, 3]]]))])
        n = min(len(xs), len(ys))
        xs = np.concatenate([xs[:n], rng.permutation(xs[:n])])
        ys = np.concatenate([rng.permutation(ys[:n]), ys[:n]])
        xs, ys = xs.astype(np.float32), ys.astype(np.float32)
        with np.errstate(invalid="ignore"):
            m_h, cid_h = ref.window_bin_ids_np(xs, ys, win, bx, by)
        m_d = (xs >= p[0]) & (xs <= p[2]) & (ys >= p[1]) & (ys <= p[3])
        cid_d = ((ys[:, None] >= ty[None]).sum(1) * bx
                 + (xs[:, None] >= tx[None]).sum(1))
        np.testing.assert_array_equal(m_h, m_d)
        np.testing.assert_array_equal(cid_h[m_h], cid_d[m_d])


def test_split_and_edge_thresholds_match_host_rules():
    """``ref.split_thresholds`` reproduces the f64 even-split rule of
    ``segment_bin_agg_np`` and ``ref.edge_thresholds`` the f64 edge rule
    of ``edge_cell_ids_np`` on every float32 coordinate, boundary ulps
    included — the pallas split kernels' cells equal the host's."""
    rng = np.random.default_rng(6)
    gx, gy = 3, 4
    lo = rng.uniform(0, 100, (6, 2))
    bboxes = np.concatenate([lo, lo + rng.uniform(1e-2, 80, (6, 2))], 1)
    t = ref.split_thresholds(bboxes, gx, gy)
    for s in range(len(bboxes)):
        tx, ty = t[s, :gx - 1], t[s, gx - 1:]
        xs = np.concatenate([rng.uniform(lo[s, 0] - 1, lo[s, 0] + 90,
                                         2000), _near(tx)]).astype(
            np.float32)
        cw = max((bboxes[s, 2] - bboxes[s, 0]) / gx, 1e-30)
        want = np.clip(np.floor((xs - bboxes[s, 0]) / cw).astype(np.int64),
                       0, gx - 1)
        np.testing.assert_array_equal(want, (xs[:, None] >= tx).sum(1))
        ys = np.concatenate([rng.uniform(lo[s, 1] - 1, lo[s, 1] + 90,
                                         2000), _near(ty)]).astype(
            np.float32)
        ch = max((bboxes[s, 3] - bboxes[s, 1]) / gy, 1e-30)
        want = np.clip(np.floor((ys - bboxes[s, 1]) / ch).astype(np.int64),
                       0, gy - 1)
        np.testing.assert_array_equal(want, (ys[:, None] >= ty).sum(1))
    # explicit f64 edges, most not representable in float32
    xe = np.sort(rng.uniform(0, 10, (5, gx + 1)), axis=1)
    ye = np.sort(rng.uniform(0, 10, (5, gy + 1)), axis=1)
    t = ref.edge_thresholds(xe, ye)
    for s in range(5):
        pts = np.concatenate([rng.uniform(-1, 11, 2000),
                              _near(t[s])]).astype(np.float32)
        sid = np.full(len(pts), s)
        cid = ref.edge_cell_ids_np(pts, pts, xe, ye, sid)
        cx = (pts[:, None] >= t[s, :gx - 1]).sum(1)
        cy = (pts[:, None] >= t[s, gx - 1:]).sum(1)
        np.testing.assert_array_equal(cid, cy * gx + cx)
