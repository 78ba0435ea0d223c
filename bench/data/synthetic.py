"""The paper's synthetic point file, generated on the device from a seed.

A seeded copy of the distributions of ``make_synthetic_dataset`` (the
program's ``data/synthetic.py``), written anew so that the benchmark's
inputs do not depend on the code under test and so that 10^8 rows take
seconds, not the minute and more that host NumPy needs:

- 2-D points in ``[0, domain]^2``: with probability ``cluster_frac`` a
  point belongs to one of ``n_clusters`` Gaussian clusters (chosen
  uniformly), at ``centre + N(0, 1) * scale``; otherwise it is uniform
  over the domain. Coordinates are clipped to the domain. Each row
  draws its own class, so the file order is not spatial order, as the
  program's generator gets by permuting its rows.
- The cluster layout (centres ``U(0.05, 0.95) * domain``, scales
  ``U(0.01, 0.05) * domain``) belongs to the configuration: it is drawn
  from the configuration's ``layout_seed``, so every run explores the
  same landscape of dense and sparse regions, and ``--seed`` draws the
  rows and the traffic.
- ``n_columns`` float32 attributes ``a0..``: by ``j % 4`` normal
  ``N(50 + 10 j, 15)``, lognormal ``exp(N(2, 0.6))``, uniform
  ``U(-100, 100)``, or bimodal ``N(-40, 8)`` / ``N(40, 8)`` with equal
  weight.

Rows are made in fixed-size chunks by one jitted program, each chunk
from ``fold_in(key, chunk)``, and copied to host memory, where the
program reads them; the next chunk is computed while one is copied. The
chunks are small (a few MB on the device), so that the chip's peak of
memory in use is the program's, not the generator's. The same seed
gives byte-identical arrays on one platform.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

CHUNK_ROWS = 1 << 16


def layout(cfg: dict):
    """Cluster centres ``(k, 2)`` and scales ``(k,)`` of a configuration."""
    rng = np.random.default_rng(cfg["layout_seed"])
    d = float(cfg["domain"])
    k = int(cfg["n_clusters"])
    centres = rng.uniform(0.05 * d, 0.95 * d, size=(k, 2))
    scales = rng.uniform(0.01 * d, 0.05 * d, size=k)
    return centres.astype(np.float32), scales.astype(np.float32)


def device_seed(seed: int) -> int:
    """A 32-bit PRNG seed derived from any non-negative whole number."""
    return int(np.random.SeedSequence(int(seed)).generate_state(1)[0])


@functools.partial(jax.jit, static_argnames=("rows", "n_columns"))
def _chunk(key, centres, scales, frac, domain, *, rows, n_columns):
    kc, ka, kn, ku, kv = jax.random.split(key, 5)
    clustered = jax.random.uniform(kc, (rows,)) < frac
    a = jax.random.randint(ka, (rows,), 0, centres.shape[0])
    pts = centres[a] + jax.random.normal(kn, (rows, 2)) * scales[a, None]
    uni = jax.random.uniform(ku, (rows, 2), maxval=domain)
    xy = jnp.clip(jnp.where(clustered[:, None], pts, uni), 0.0, domain)
    cols = []
    for j, kj in enumerate(jax.random.split(kv, n_columns)):
        k1, k2 = jax.random.split(kj)
        kind = j % 4
        if kind == 0:
            v = 50.0 + 10.0 * j + 15.0 * jax.random.normal(k1, (rows,))
        elif kind == 1:
            v = jnp.exp(2.0 + 0.6 * jax.random.normal(k1, (rows,)))
        elif kind == 2:
            v = jax.random.uniform(k1, (rows,), minval=-100.0, maxval=100.0)
        else:
            sign = jnp.where(jax.random.uniform(k2, (rows,)) < 0.5, -1.0, 1.0)
            v = 40.0 * sign + 8.0 * jax.random.normal(k1, (rows,))
        cols.append(v)
    return jnp.stack([xy[:, 0], xy[:, 1], *cols]).astype(jnp.float32)


def generate(cfg: dict, seed: int, chunk_rows: int = CHUNK_ROWS):
    """``(x, y, {"a0": ..., ...})`` host float32 arrays of ``cfg["rows"]``
    rows for ``seed``."""
    n = int(cfg["rows"])
    n_cols = int(cfg["n_columns"])
    centres, scales = layout(cfg)
    key = jax.random.key(device_seed(seed))
    out = np.empty((2 + n_cols, n), np.float32)
    rows = min(chunk_rows, n)
    starts = range(0, n, rows)

    def chunk(c):
        return _chunk(jax.random.fold_in(key, c), centres, scales,
                      np.float32(cfg["cluster_frac"]),
                      np.float32(cfg["domain"]), rows=rows, n_columns=n_cols)

    nxt = chunk(0)
    for c, start in enumerate(starts):
        block, nxt = nxt, (chunk(c + 1) if c + 1 < len(starts) else None)
        take = min(rows, n - start)
        out[:, start:start + take] = np.asarray(block)[:, :take]
        del block
    cols = {f"a{j}": out[2 + j] for j in range(n_cols)}
    return out[0], out[1], cols
