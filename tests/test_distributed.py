"""Distributed AQP engine on 8 fake CPU devices. XLA locks the device
count at first jax init, so these tests run in a subprocess with
XLA_FLAGS set (the main test process keeps 1 device, per the dry-run
isolation rule)."""
import os
import subprocess
import sys
import textwrap

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def run_sub(body: str, timeout=420) -> str:
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    env["PYTHONPATH"] = os.path.join(ROOT, "src")
    code = textwrap.dedent(body)
    out = subprocess.run([sys.executable, "-c", code], env=env,
                         capture_output=True, text=True, timeout=timeout)
    assert out.returncode == 0, out.stderr[-4000:]
    return out.stdout


def test_distributed_query_matches_oracle_and_bound():
    """The SESSION scalar path: the state cracks across the query path
    (refine epochs rewrite the sharded cell ids in place), and every
    answer still contains its oracle with the bound met."""
    print(run_sub("""
        import jax, numpy as np
        import jax.numpy as jnp
        from repro.core.distributed import DistributedAQPEngine, DistConfig
        from repro.data import make_synthetic_dataset
        from repro.data.synthetic import exploration_path
        from repro.kernels.ops import window_mask_np

        mesh = jax.make_mesh((4, 2), ("data", "model"))
        ds = make_synthetic_dataset(n=80_000, seed=3)
        eng = DistributedAQPEngine(ds, mesh, DistConfig(
            grid=(16, 16), capacity=1024, min_split_count=128))
        wins = exploration_path(ds, n_queries=6, target_objects=8000)
        n = len(eng.xs)
        for phi in (0.0, 0.05):
            for w in wins:
                r = eng.query(w, "a0", phi)
                m = window_mask_np(np.asarray(ds.x[:n]),
                                   np.asarray(ds.y[:n]), w)
                vals = ds.read_all_unaccounted("a0")[:n][m]
                truth = vals.sum(dtype=np.float64)
                eps = 1e-5 * abs(truth) + 1e-2  # f32 partial-sum slack
                assert r.lo - eps <= truth <= r.hi + eps, \\
                    (phi, w, r, truth)
                if phi == 0.0:
                    np.testing.assert_allclose(r.value, truth,
                                               rtol=1e-3, atol=1.0)
                else:
                    assert r.bound <= phi + 1e-6 or r.exact
        # the engine records every query into the trace (totals() covers
        # distributed sessions like host ones)
        tot = eng.trace.totals()
        assert tot["queries"] == 12 and tot["scalar_queries"] == 12
        assert tot["total_objects_read"] == sum(
            r.objects_read for r in eng.trace.results)
        assert list(eng.n_active.values())[0] > 16 * 16  # it cracked
        print("DIST-AQP-OK")
    """))


def test_distributed_heatmap_matches_oracle_and_bounds():
    """The SESSION heatmap path: per-bin values + bounds stay oracle-
    correct while the sharded state cracks and the per-(tile, bin)
    exact registry fills across the exploration path."""
    print(run_sub("""
        import jax, numpy as np
        import jax.numpy as jnp
        from repro.core.distributed import DistributedAQPEngine, DistConfig
        from repro.data import make_synthetic_dataset
        from repro.data.synthetic import exploration_path
        from repro.kernels.ref import window_bin_ids_np

        BX, BY = 6, 4
        mesh = jax.make_mesh((4, 2), ("data", "model"))
        ds = make_synthetic_dataset(n=80_000, seed=3)
        eng = DistributedAQPEngine(ds, mesh, DistConfig(
            grid=(16, 16), capacity=1024, min_split_count=128))
        wins = exploration_path(ds, n_queries=4, target_objects=8000)
        n = len(eng.xs)
        xs = np.asarray(ds.x[:n]); ys = np.asarray(ds.y[:n])
        col = ds.read_all_unaccounted("a0")[:n]
        for phi in (0.0, 0.05):
            for w in wins:
                r = eng.heatmap(w, "a0", bins=(BX, BY), phi=phi)
                m, cid = window_bin_ids_np(xs, ys, w, BX, BY)
                truth = np.bincount(cid[m], weights=col[m].astype(
                    np.float64), minlength=BX * BY)
                occ = np.bincount(cid[m], minlength=BX * BY) > 0
                eps = 1e-4 * np.abs(truth) + 0.5   # f32 partial-sum slack
                assert (r.lo[occ] - eps[occ] <= truth[occ]).all(), \\
                    (phi, w)
                assert (truth[occ] <= r.hi[occ] + eps[occ]).all(), \\
                    (phi, w)
                if phi == 0.0:
                    np.testing.assert_allclose(r.values[occ],
                                               truth[occ], rtol=1e-3,
                                               atol=1.0)
                else:
                    assert r.bound <= phi + 1e-6 or r.exact
                # per-bin bound covers each bin's observed deviation
                err = np.abs(r.values[occ] - truth[occ])
                cap = r.bin_bound[occ] * np.maximum(
                    np.abs(r.values[occ]), 1e-9) + eps[occ]
                assert (err <= cap).all(), (phi, w)
        tot = eng.trace.totals()
        assert tot["heatmap_queries"] == 8
        print("DIST-HEATMAP-OK")
    """))


def test_distributed_session_reads_fewer_on_repeat():
    """The acceptance property of the sharded session state: a REPEATED
    window reads strictly fewer objects on query 2+ than on query 1 —
    previously-read tiles answer from the per-(tile, bin) exact
    registry and refine epochs shrink the pending boundary — while the
    stateless one-shot step pays the full price every time."""
    print(run_sub("""
        import jax, numpy as np
        import jax.numpy as jnp
        from repro.core.distributed import (DistributedAQPEngine,
                                            DistConfig, make_heatmap_step)
        from repro.data import make_synthetic_dataset
        from repro.data.synthetic import exploration_path

        mesh = jax.make_mesh((8,), ("data",))
        ds = make_synthetic_dataset(n=80_000, seed=5)
        cfg = DistConfig(grid=(16, 16), capacity=2048,
                         min_split_count=128)
        eng = DistributedAQPEngine(ds, mesh, cfg)
        w = exploration_path(ds, n_queries=1, target_objects=12_000)[0]
        r1 = eng.heatmap(w, "a0", bins=(6, 6), phi=0.02)
        r2 = eng.heatmap(w, "a0", bins=(6, 6), phi=0.02)
        r3 = eng.heatmap(w, "a0", bins=(6, 6), phi=0.02)
        assert r1.objects_read > 0
        assert r2.objects_read < r1.objects_read, (r1.objects_read,
                                                   r2.objects_read)
        assert r3.objects_read <= r2.objects_read
        # the stateless wrapper rebuilds the surrogate per call: the
        # repeat costs exactly what the first call cost
        step = make_heatmap_step(mesh, cfg, (6, 6))
        args = (eng.xs, eng.ys, eng.vals["a0"], eng.domain,
                jnp.asarray(w, jnp.float32), jnp.float32(0.02))
        s1 = float(step(*args)["objects_read"])
        s2 = float(step(*args)["objects_read"])
        assert s1 == s2 and s1 > 0
        assert r2.objects_read < s2, (r2.objects_read, s2)
        # the scalar session amortizes too (no registry, cracking only)
        q1 = eng.query(w, "a0", 0.02)
        q2 = eng.query(w, "a0", 0.02)
        assert q2.objects_read <= q1.objects_read
        print("DIST-SESSION-OK")
    """))


def test_distributed_uniform_policy_parity_and_phi_b_vs_host():
    """φ_b in-SPMD: (a) the UNIFORM policy routes to — and equals
    bit-for-bit — the scalar-φ build (the host ``set_policy`` drop
    rule), and the stateless wrapper equals a fresh session's first
    pass bit-for-bit (the pre-refactor step contract); (b) floored /
    salience φ_b allocations meet every per-bin budget against the
    ground truth, on the device mesh AND on the host engine the same
    policy semantics came from."""
    print(run_sub("""
        import jax, numpy as np
        import jax.numpy as jnp
        from repro.core import AQPEngine, IndexConfig
        from repro.core.bounds import AccuracyPolicy, phi_budgets
        from repro.core.distributed import (
            DistributedAQPEngine, DistConfig, make_heatmap_step,
            make_init_state, make_session_heatmap_step, _empty_cache)
        from repro.data import make_synthetic_dataset
        from repro.data.rawfile import RawDataset
        from repro.data.synthetic import exploration_path
        from repro.kernels.ref import window_bin_ids_np

        BX, BY = 6, 4
        NB = BX * BY
        mesh = jax.make_mesh((8,), ("data",))
        ds = make_synthetic_dataset(n=64_000, seed=7)
        cfg = DistConfig(grid=(16, 16), capacity=1024,
                         min_split_count=128)
        w = exploration_path(ds, n_queries=1, target_objects=10_000)[0]
        win = jnp.asarray(w, jnp.float32)

        # (a) uniform-policy routing parity: bit-for-bit the plain path
        e1 = DistributedAQPEngine(ds, mesh, cfg)
        e2 = DistributedAQPEngine(ds, mesh, cfg)
        r1 = e1.heatmap(w, "a0", bins=(BX, BY), phi=0.05, policy=None)
        r2 = e2.heatmap(w, "a0", bins=(BX, BY), phi=0.05,
                        policy=AccuracyPolicy())
        for f in ("values", "lo", "hi", "bin_bound"):
            np.testing.assert_array_equal(getattr(r1, f), getattr(r2, f))
        assert (r1.objects_read, r1.tiles_processed) == \\
            (r2.objects_read, r2.tiles_processed)

        # stateless wrapper ≡ fresh-session single pass, bit-for-bit
        init = make_init_state(mesh, cfg)
        sess = make_session_heatmap_step(mesh, cfg, (BX, BY), "sum",
                                         with_policy=False)
        st = init(e1.xs, e1.ys, e1.vals["a0"], e1.domain)
        out_s, _ = sess(st, _empty_cache(cfg.capacity, NB), e1.xs,
                        e1.ys, e1.vals["a0"], win, jnp.float32(0.05),
                        jnp.zeros((NB,), jnp.float32), jnp.float32(0.0))
        out_w = make_heatmap_step(mesh, cfg, (BX, BY))(
            e1.xs, e1.ys, e1.vals["a0"], e1.domain, win,
            jnp.float32(0.05))
        for f in ("values", "lo", "hi", "bin_bound", "objects_read",
                  "n_processed"):
            np.testing.assert_array_equal(np.asarray(out_s[f]),
                                          np.asarray(out_w[f]))

        # (b) non-uniform φ_b allocations meet per-bin budgets vs truth,
        # SPMD and host alike, on skewed data (one hot corner)
        rng = np.random.default_rng(11)
        n = 64_000
        x = rng.uniform(0, 1000, n).astype(np.float32)
        y = rng.uniform(0, 1000, n).astype(np.float32)
        hot = (x > 750) & (y > 750)
        v = np.where(hot, rng.normal(100, 10, n),
                     rng.normal(0, 0.02, n)).astype(np.float32)
        sk = RawDataset(x, y, {"a0": v})
        wsk = (500.0, 500.0, 1000.0, 1000.0)
        m, cid = window_bin_ids_np(x, y, wsk, BX, BY)
        truth = np.bincount(cid[m], weights=v[m].astype(np.float64),
                            minlength=NB)
        occ = np.bincount(cid[m], minlength=NB) > 0
        PHI = 0.05
        eps_abs = 0.02 * float(np.abs(truth).max())
        deng = DistributedAQPEngine(sk, mesh, cfg)
        heng = AQPEngine(sk, IndexConfig(grid0=(8, 8),
                                         min_split_count=256,
                                         init_metadata_attrs=("a0",)))
        for pol in (AccuracyPolicy(eps_abs=eps_abs),
                    AccuracyPolicy(eps_abs=eps_abs, salience="center")):
            phi_b = pol.phi_b(PHI, (BX, BY))
            tau = phi_budgets(phi_b, np.maximum(np.abs(truth), 1e-9),
                              pol.eps_abs)
            slack = 1e-3 * np.abs(truth) + 0.5   # f32 partial sums
            rd = deng.heatmap(wsk, "a0", bins=(BX, BY), phi=PHI,
                              policy=pol)
            assert rd.bin_met is not None and rd.bin_met.all(), pol
            err_d = np.abs(rd.values[occ] - truth[occ])
            assert (err_d <= tau[occ] + slack[occ]).all(), pol
            rh = heng.heatmap(wsk, "sum", "a0", bins=(BX, BY), phi=PHI,
                              policy=pol)
            err_h = np.abs(rh.values[occ] - truth[occ])
            assert (err_h <= tau[occ] + slack[occ]).all(), pol
        print("DIST-PHI-B-OK")
    """))


def test_distributed_heatmap_min_max_matches_oracle():
    """min/max heatmap aggregates over the mesh (grouped extrema merged
    with pmin/pmax): every occupied bin's CI contains its single-host
    oracle value, φ=0 equals the truth exactly (extrema don't round),
    empty bins come back ±inf, and under φ>0 the reported per-bin-max
    bound meets φ (or everything was processed)."""
    print(run_sub("""
        import jax, numpy as np
        from repro.core.distributed import DistributedAQPEngine, DistConfig
        from repro.data import make_synthetic_dataset
        from repro.data.synthetic import exploration_path

        BX, BY = 5, 3
        mesh = jax.make_mesh((4, 2), ("data", "model"))
        ds = make_synthetic_dataset(n=80_000, seed=3)
        eng = DistributedAQPEngine(ds, mesh, DistConfig(
            grid=(16, 16), capacity=1024, min_split_count=128))
        wins = exploration_path(ds, n_queries=3, target_objects=8000)
        n = len(eng.xs)
        xs = np.asarray(ds.x[:n]); ys = np.asarray(ds.y[:n])
        col = ds.read_all_unaccounted("a0")[:n]
        nb = BX * BY

        def f32_bin_ids(w):
            # mirror the SPMD step's f32 mask/binning bit-for-bit so the
            # phi=0 extrema comparison is exact, not tolerance-based
            w32 = np.asarray(w, np.float32)
            m = ((xs >= w32[0]) & (xs <= w32[2])
                 & (ys >= w32[1]) & (ys <= w32[3]))
            cw = np.maximum((w32[2] - w32[0]) / np.float32(BX),
                            np.float32(1e-30))
            ch = np.maximum((w32[3] - w32[1]) / np.float32(BY),
                            np.float32(1e-30))
            cx = np.clip(np.floor((xs - w32[0]) / cw).astype(np.int64),
                         0, BX - 1)
            cy = np.clip(np.floor((ys - w32[1]) / ch).astype(np.int64),
                         0, BY - 1)
            return m, cy * BX + cx

        for agg in ("min", "max"):
            fill = np.inf if agg == "min" else -np.inf
            for phi in (0.0, 0.05):
                for w in wins:
                    r = eng.heatmap(w, "a0", bins=(BX, BY), phi=phi,
                                    agg=agg)
                    m, cid = f32_bin_ids(w)
                    occ = np.bincount(cid[m], minlength=nb) > 0
                    truth = np.full(nb, fill)
                    for b in np.flatnonzero(occ):
                        sel = col[m & (cid == b)]
                        truth[b] = sel.min() if agg == "min" else sel.max()
                    assert (r.lo[occ] - 1e-4 <= truth[occ]).all(), \\
                        (agg, phi, w)
                    assert (truth[occ] <= r.hi[occ] + 1e-4).all(), \\
                        (agg, phi, w)
                    # empty bins carry the HeatmapResult sentinel
                    assert (r.values[~occ] == fill).all()
                    if phi == 0.0:
                        # extrema don't round: exact equality at phi=0
                        np.testing.assert_array_equal(
                            r.values[occ].astype(np.float32),
                            truth[occ].astype(np.float32))
                    else:
                        assert r.bound <= phi + 1e-6 or r.exact
                    # per-bin bound covers each bin's observed deviation
                    err = np.abs(r.values[occ] - truth[occ])
                    cap = r.bin_bound[occ] * np.maximum(
                        np.abs(r.values[occ]), 1e-9) + 1e-4
                    assert (err <= cap).all(), (agg, phi, w)
        print("DIST-HEATMAP-MINMAX-OK")
    """))


def test_distributed_refine_epoch_invariants():
    """Sharded refine epoch: splits rewrite the sharded cell ids and
    append psum-merged child metadata that stays SOUND — object
    conservation, counts matching a host recount of the cell plane,
    child extents nested in (bin-aligned snaps of) the parent, and
    value bounds containing every owned object's value."""
    print(run_sub("""
        import jax, numpy as np
        import jax.numpy as jnp
        from repro.core.distributed import DistributedAQPEngine, DistConfig
        from repro.data import make_synthetic_dataset

        mesh = jax.make_mesh((8,), ("data",))
        ds = make_synthetic_dataset(n=40_000, seed=4)
        eng = DistributedAQPEngine(ds, mesh, DistConfig(
            grid=(8, 8), capacity=512, min_split_count=64, epoch_k=8))
        n = len(eng.xs)
        col = ds.read_all_unaccounted("a1")[:n]
        BX, BY = 6, 6
        d = np.asarray(eng.domain)
        w = (d[0], d[1], d[2], d[3])
        info = eng.refine("a1", window=w, bins=(BX, BY))
        assert info["n_split"] == 8, info
        info2 = eng.refine("a1", window=w, bins=(BX, BY))
        st = eng._states["a1"]
        active = np.asarray(st.active)
        count = np.asarray(st.count)
        cell = np.asarray(st.cell)
        bbox = np.asarray(st.bbox)
        vmin = np.asarray(st.vmin); vmax = np.asarray(st.vmax)
        nt = int(np.asarray(st.n_tiles))
        assert nt == 8 * 8 + (info["n_split"] + info2["n_split"]) * 4
        # object conservation + count/cell-plane agreement
        assert count[active].sum() == n
        recount = np.bincount(cell, minlength=len(count))
        np.testing.assert_array_equal(recount[active],
                                      count[active].astype(np.int64))
        assert (recount[~active] == 0).all()
        # soundness: every owned object's value inside the tile bounds,
        # coordinates inside the tile extent (f32 binning tolerance)
        for t in np.flatnonzero(active)[:64]:
            own = cell == t
            if not own.any():
                continue
            assert col[own].min() >= vmin[t] - 1e-4
            assert col[own].max() <= vmax[t] + 1e-4
            xs = np.asarray(eng.xs)[own]; ys = np.asarray(eng.ys)[own]
            tol = 1e-3
            assert (xs >= bbox[t, 0] - tol).all() and \\
                (xs <= bbox[t, 2] + tol).all()
            assert (ys >= bbox[t, 1] - tol).all() and \\
                (ys <= bbox[t, 3] + tol).all()
        # bin-aligned snapping: children come in groups of 4 per split
        # parent (rows appended k at a time); the group's interior split
        # edge must sit ON the bin line crossing the parent when one
        # does, and on the even midpoint otherwise (_snapped_edges'
        # fallback rule)
        # (tolerance-based: XLA may compile the step's /b as a
        # reciprocal multiply, so its f32 line values can sit an ulp
        # away from any host mirror — 1e-3 absorbs that while still
        # failing hard if snapping degrades to even splits)
        w32 = np.asarray(w, np.float32)
        xlines = (w32[0] + (w32[2] - w32[0]) / np.float32(BX)
                  * np.arange(1, BX, dtype=np.float32))
        n_children = nt - 8 * 8
        assert n_children > 0 and n_children % 4 == 0
        checked = 0
        for g in range(n_children // 4):
            rows = 8 * 8 + 4 * g + np.arange(4)
            px0 = bbox[rows, 0].min(); px1 = bbox[rows, 2].max()
            cut = bbox[rows[0], 2]          # child 0's right edge
            near_line = np.abs(xlines - cut).min() <= 1e-3
            inside = xlines[(xlines > px0 + 1e-3)
                            & (xlines < px1 - 1e-3)]
            if inside.size:
                # a line clearly crosses the parent: the cut MUST have
                # snapped onto a bin line, not the even midpoint
                assert near_line, (g, cut, inside)
                checked += 1
            else:
                # no clearly-interior line: even midpoint, or a snap to
                # a line hugging the extent boundary (f32 ulp cases)
                assert near_line or \\
                    abs(cut - 0.5 * (px0 + px1)) <= 1e-3, (g, cut)
        assert checked > 0   # at least one parent actually snapped
        print("DIST-REFINE-OK")
    """))


def test_distributed_chunked_snapshot_matches_oracle():
    """A ChunkedDataset shards into the SPMD session as a device-resident
    snapshot of its live chunks (concatenated in insertion order): scalar
    and heatmap answers stay oracle-correct, and the snapshot semantics
    hold — chunks retired AFTER construction don't reshard, the session
    keeps answering over what it captured."""
    print(run_sub("""
        import jax, numpy as np
        import jax.numpy as jnp
        from repro.core.distributed import DistributedAQPEngine, DistConfig
        from repro.data import ChunkedDataset
        from repro.data.synthetic import make_streaming_chunks
        from repro.kernels.ops import window_mask_np

        mesh = jax.make_mesh((8,), ("data",))
        cds = ChunkedDataset()
        for x, y, cols in make_streaming_chunks(
                n_chunks=4, rows_per_chunk=16_000, n_columns=2,
                domain=1000.0, seed=13):
            cds.ingest(x, y, cols)
        eng = DistributedAQPEngine(cds, mesh, DistConfig(
            grid=(16, 16), capacity=1024, min_split_count=128))
        n = len(eng.xs)
        assert n == (cds.n // 8) * 8
        xs = np.asarray(cds.x[:n]); ys = np.asarray(cds.y[:n])
        col = cds.read_all_unaccounted("a0")[:n]
        wins = [(100.0, 100.0, 420.0, 800.0),     # chunks 0-1 only
                (300.0, 50.0, 900.0, 950.0)]      # straddles 1-3
        for phi in (0.0, 0.05):
            for w in wins:
                r = eng.query(w, "a0", phi)
                m = window_mask_np(xs, ys, w)
                truth = col[m].sum(dtype=np.float64)
                eps = 1e-5 * abs(truth) + 1e-2
                assert r.lo - eps <= truth <= r.hi + eps, (phi, w)
                if phi > 0.0:
                    assert r.bound <= phi + 1e-6 or r.exact
        h = eng.heatmap(wins[1], "a0", bins=(4, 4), phi=0.0)
        from repro.kernels.ref import window_bin_ids_np
        m, cid = window_bin_ids_np(xs, ys, wins[1], 4, 4)
        truth_b = np.bincount(cid[m], weights=col[m].astype(np.float64),
                              minlength=16)
        occ = np.bincount(cid[m], minlength=16) > 0
        np.testing.assert_allclose(h.values[occ], truth_b[occ],
                                   rtol=1e-3, atol=1.0)
        # snapshot semantics: retiring a chunk after construction does
        # not reshard — the session still answers over the captured rows
        cds.retire(0)
        r2 = eng.query(wins[0], "a0", 0.0)
        m0 = window_mask_np(xs, ys, wins[0])
        t0 = col[m0].sum(dtype=np.float64)
        eps = 1e-5 * abs(t0) + 1e-2
        assert r2.lo - eps <= t0 <= r2.hi + eps
        print("DIST-CHUNKED-OK")
    """))


def test_distributed_fused_vs_composed_bit_for_bit():
    """The fused classify→scatter→select megakernel path (fused=True,
    the default) against the historical composed chain (fused=False),
    on the 8-device mesh: every output of the scalar-query AND heatmap
    session steps must be bit-for-bit identical — on the fresh state,
    and again after a refine epoch has cracked the sharded cell ids in
    place. This is the acceptance contract that let the fused path
    replace the chain as the default."""
    print(run_sub("""
        import jax, numpy as np
        import jax.numpy as jnp
        from repro.core.distributed import (
            DistributedAQPEngine, DistConfig, _empty_cache,
            make_init_state, make_refine_epoch, make_session_heatmap_step,
            make_session_query_step)
        from repro.data import make_synthetic_dataset
        from repro.data.synthetic import exploration_path

        BX, BY = 4, 3
        NB = BX * BY
        mesh = jax.make_mesh((8,), ("data",))
        ds = make_synthetic_dataset(n=64_000, seed=5)
        cfg = DistConfig(grid=(16, 16), capacity=1024,
                         min_split_count=128)
        eng = DistributedAQPEngine(ds, mesh, cfg)   # device staging only
        xs, ys, vals = eng.xs, eng.ys, eng.vals["a0"]
        wins = exploration_path(ds, n_queries=2, target_objects=9000)

        def assert_same(a, b, ctx):
            assert sorted(a) == sorted(b), (ctx, sorted(a), sorted(b))
            for k in a:
                np.testing.assert_array_equal(
                    np.asarray(a[k]), np.asarray(b[k]),
                    err_msg=f"{ctx}:{k}")

        q_f = make_session_query_step(mesh, cfg, fused=True)
        q_c = make_session_query_step(mesh, cfg, fused=False)
        h_f = make_session_heatmap_step(mesh, cfg, (BX, BY), "sum",
                                        with_policy=False, fused=True)
        h_c = make_session_heatmap_step(mesh, cfg, (BX, BY), "sum",
                                        with_policy=False, fused=False)
        epoch = make_refine_epoch(mesh, cfg)

        init = make_init_state(mesh, cfg)
        st = init(xs, ys, vals, eng.domain)
        phi = jnp.float32(0.05)
        for i, w in enumerate(wins):
            win = jnp.asarray(w, jnp.float32)
            out_f = q_f(st, xs, ys, vals, win, phi)
            out_c = q_c(st, xs, ys, vals, win, phi)
            assert_same(out_f, out_c, f"query[{i}]")
            # crack the state on the tiles the step just read, then the
            # next loop iteration re-checks parity on the refined state
            st, _ = epoch(st, xs, ys, vals, win, out_f["sel"])

        # heatmap step: fresh state, then the epoch-refined one; the
        # grouped exact-cache (a pytree) must also match leaf-for-leaf
        st2 = init(xs, ys, vals, eng.domain)
        for i, state in enumerate((st2, st)):
            cache = _empty_cache(cfg.capacity, NB)
            args = (xs, ys, vals, jnp.asarray(wins[0], jnp.float32),
                    phi, jnp.zeros((NB,), jnp.float32), jnp.float32(0.0))
            out_f, cache_f = h_f(state, cache, *args)
            out_c, cache_c = h_c(state, cache, *args)
            assert_same(out_f, out_c, f"heatmap[{i}]")
            for lf, lc in zip(jax.tree_util.tree_leaves(cache_f),
                              jax.tree_util.tree_leaves(cache_c)):
                np.testing.assert_array_equal(np.asarray(lf),
                                              np.asarray(lc))
        print("DIST-FUSED-PARITY-OK")
    """))
