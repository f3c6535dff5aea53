"""The benchmark's own copy of a generated store: a CSR vertex pool."""
from __future__ import annotations

import dataclasses
import os
import shutil

import numpy as np

POLYGON = 0   # closed ring; a single vertex is a point
POLYLINE = 1  # open chain


@dataclasses.dataclass
class Store:
    """Records in CSR layout. Coordinates are float64 values that float32
    represents exactly, so a float64 reference and a float32 device decide
    on the same numbers."""

    pool: np.ndarray      # (P, 2) float64
    offsets: np.ndarray   # (N,) int64
    nverts: np.ndarray    # (N,) int32
    kinds: np.ndarray     # (N,) int8
    mbrs: np.ndarray      # (N, 4) float64: xmin, ymin, xmax, ymax

    def __len__(self) -> int:
        return int(self.nverts.shape[0])

    def save(self, path: str) -> None:
        """Write the store under the directory ``path`` (coordinates as
        float32, which holds them exactly); a half-written directory is
        never seen under that name."""
        tmp = f"{path}.part"
        shutil.rmtree(tmp, ignore_errors=True)
        os.makedirs(tmp)
        for name, a in (("pool", self.pool.astype(np.float32)),
                        ("offsets", self.offsets), ("nverts", self.nverts),
                        ("kinds", self.kinds),
                        ("mbrs", self.mbrs.astype(np.float32))):
            np.save(os.path.join(tmp, f"{name}.npy"), a)
        shutil.rmtree(path, ignore_errors=True)
        os.replace(tmp, path)

    @classmethod
    def load(cls, path: str) -> "Store":
        def get(name):
            return np.load(os.path.join(path, f"{name}.npy"))

        return cls(pool=get("pool").astype(np.float64), offsets=get("offsets"),
                   nverts=get("nverts"), kinds=get("kinds"),
                   mbrs=get("mbrs").astype(np.float64))

    def permuted(self, perm: np.ndarray) -> "Store":
        """The same records in another order: record ``j`` of the result is
        record ``perm[j]`` of this store."""
        nverts = self.nverts[perm]
        offsets = np.zeros(len(perm), np.int64)
        np.cumsum(nverts[:-1], out=offsets[1:])
        src = np.repeat(self.offsets[perm] - offsets, nverts)
        src += np.arange(len(src))
        return Store(pool=self.pool[src], offsets=offsets, nverts=nverts,
                     kinds=self.kinds[perm], mbrs=self.mbrs[perm])
