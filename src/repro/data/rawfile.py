"""Simulated in-situ raw data file with byte-level I/O accounting.

The paper's cost model is "objects read from the raw file". This module is
the file abstraction the index reads through: every access to non-axis
attribute values is routed via :meth:`RawDataset.read_values`, which
accounts rows and bytes. The benchmark harness reports both, reproducing
the paper's "evaluation time closely follows the number of objects read"
analysis.

Three access modes:
- ``array`` (default): the "file" is a host numpy array; a read is a
  gather. Cost scales with rows read, at memory speed.
- ``csv``: columns are stored as fixed-width TEXT records and every
  ``read_values`` actually parses the selected rows' bytes to floats —
  the cost structure of true in-situ raw-file access (NoDB/RawVis:
  parsing, not seeking, dominates). The benchmark harness uses this
  mode; it is what reproduces the paper's exact-vs-approximate gap.
- ``mmap``: on-disk binary via ``np.memmap`` (OS page cache in play).

On a TPU deployment the object store lives in HBM sharded over the data
axis and "reads" are HBM→VMEM streams inside the Pallas kernels; the
accounting here is the host-side mirror of those bytes (see DESIGN.md §2).
"""
from __future__ import annotations

import dataclasses
import os
from typing import Dict, Optional, Sequence

import numpy as np

from ..spans import READ, span


@dataclasses.dataclass
class IOStats:
    rows_read: int = 0
    bytes_read: int = 0
    read_calls: int = 0
    init_rows: int = 0
    # chunk skipped wholesale on its axis bounding-box test (chunked
    # storage): the query touched ZERO of the chunk's rows — the pruning
    # win the streaming benchmark (B8) reports
    pruned_calls: int = 0

    def snapshot(self) -> "IOStats":
        return dataclasses.replace(self)

    def delta(self, before: "IOStats") -> "IOStats":
        # field-complete by construction: a counter added to the
        # dataclass can't silently drift out of snapshot/delta
        return IOStats(**{
            f.name: getattr(self, f.name) - getattr(before, f.name)
            for f in dataclasses.fields(self)})

    def merge(self, other: "IOStats") -> "IOStats":
        """Field-wise sum (chunked datasets aggregate per-chunk stats)."""
        return IOStats(**{
            f.name: getattr(self, f.name) + getattr(other, f.name)
            for f in dataclasses.fields(self)})


class RawDataset:
    """A raw data file: 2 axis attributes + M non-axis numeric attributes.

    ``axis`` values are exposed directly (the index ingests them once at
    initialization — that pass is accounted in ``stats.init_rows``); all
    non-axis value access is accounted per row.
    """

    ITEM_BYTES = 4       # float32 column storage (array/mmap modes)
    CSV_WIDTH = 14       # fixed-width text record (csv mode)

    def __init__(self, x: np.ndarray, y: np.ndarray,
                 columns: Dict[str, np.ndarray],
                 mmap_dir: Optional[str] = None,
                 storage: str = "array"):
        self.n = len(x)
        assert all(len(v) == self.n for v in columns.values())
        self.x = np.asarray(x, np.float32)
        self.y = np.asarray(y, np.float32)
        # axis bbox computed once — domain() sits on the per-query
        # classify path and chunk pruning calls it per chunk
        if self.n:
            self._domain = (float(self.x.min()), float(self.y.min()),
                            float(self.x.max()), float(self.y.max()))
        else:
            self._domain = (0.0, 0.0, 0.0, 0.0)
        self.stats = IOStats()
        self._closed = False
        self._mmap_dir = mmap_dir
        self.storage = "mmap" if mmap_dir is not None else storage
        self._cols = {}
        self._text = {}
        if self.storage == "mmap":
            os.makedirs(mmap_dir, exist_ok=True)
            for k, v in columns.items():
                path = os.path.join(mmap_dir, f"{k}.f32")
                np.asarray(v, np.float32).tofile(path)
                self._cols[k] = np.memmap(path, dtype=np.float32, mode="r")
        elif self.storage == "csv":
            w = self.CSV_WIDTH
            for k, v in columns.items():
                vf = np.asarray(v, np.float32)
                # the "raw file": fixed-width text records, parsed on read
                self._text[k] = np.char.ljust(
                    np.char.mod(f"%.6g", vf).astype(f"S{w}"), w).view(
                        f"S{w}")
                # ground truth (oracle only) = what the file contains
                self._cols[k] = self._text[k].astype(np.float32)
        else:
            for k, v in columns.items():
                self._cols[k] = np.asarray(v, np.float32)

    @property
    def attributes(self) -> Sequence[str]:
        return tuple(self._cols.keys())

    def domain(self):
        """(x0, y0, x1, y1) bounding box of the axis attributes."""
        return self._domain

    @property
    def closed(self) -> bool:
        """True once :meth:`close` ran (chunk retired) — readers probe
        this to degrade gracefully instead of tripping the accounted-read
        guard mid-refinement."""
        return self._closed

    def close(self) -> None:
        """Release column storage (chunk retirement). Accounted reads
        after close raise — a retired chunk must never be read."""
        self._closed = True
        self._cols = {}
        self._text = {}
        if self.storage == "mmap" and self._mmap_dir is not None:
            import shutil
            shutil.rmtree(self._mmap_dir, ignore_errors=True)

    def account_init_pass(self):
        """The index-initialization scan over the file (axis attrs)."""
        if self._closed:
            raise RuntimeError("init pass on a retired chunk")
        self.stats.init_rows += self.n

    def read_values(self, attr: str, rows: np.ndarray) -> np.ndarray:
        """Read attribute values for specific rows — THE accounted I/O.

        In ``csv`` mode this PARSES the rows' text records (the real
        in-situ cost); in array/mmap modes it's a gather.
        """
        if self._closed:
            raise RuntimeError("read_values on a retired chunk")
        self.stats.rows_read += int(len(rows))
        self.stats.read_calls += 1
        with span(READ):
            if self.storage == "csv":
                self.stats.bytes_read += int(len(rows)) * self.CSV_WIDTH
                return self._text[attr][rows].astype(np.float32)
            self.stats.bytes_read += int(len(rows)) * self.ITEM_BYTES
            return np.asarray(self._cols[attr][rows], np.float32)

    def read_all_unaccounted(self, attr: str) -> np.ndarray:
        """Test/oracle access — bypasses accounting (ground truth only)."""
        return np.asarray(self._cols[attr][:], np.float32)
