"""Bidirectional id <-> dense-index maps.

Analog of the reference's ``BiMap``/``EntityMap`` (reference: data/src/main/
scala/io/prediction/data/storage/BiMap.scala:25-164, EntityMap.scala) — the
reindexing step every factorization algorithm needs: string entity ids to
contiguous integer indices that address rows of TPU-resident factor matrices.

TPU-first design note: instead of the reference's RDD-based constructors
(``BiMap.stringInt(rdd)``), construction here is vectorized over numpy arrays
(``BiMap.from_array``) so a million-id vocabulary builds in one
``np.unique`` call and the forward map lives as a hash map on host while the
inverse map is a dense numpy array usable directly for device gathers.
"""

from __future__ import annotations

from typing import Generic, Iterable, Iterator, Mapping, Sequence, TypeVar

import numpy as np

K = TypeVar("K")
V = TypeVar("V")

__all__ = ["BiMap", "string_int_bimap"]


class BiMap(Generic[K, V]):
    """Immutable bidirectional map. ``apply``/``[]`` maps forward;
    ``inverse`` gives the reversed map. Raises ``KeyError`` on misses,
    like the reference's ``BiMap.apply`` (BiMap.scala:38).
    """

    __slots__ = ("_m", "_i")

    def __init__(self, m: Mapping[K, V], _inverse: "BiMap[V, K] | None" = None):
        self._m = dict(m)
        if len(self._m) != len(set(self._m.values())):
            raise ValueError("BiMap values must be unique")
        self._i = _inverse

    @property
    def inverse_built(self) -> bool:
        """Whether ``inverse`` is there already or is built at its next
        use (a dict of as many entries: seconds at millions)."""
        return self._i is not None

    @property
    def inverse(self) -> "BiMap[V, K]":
        if self._i is None:
            self._i = BiMap({v: k for k, v in self._m.items()}, _inverse=self)
        return self._i

    def __getitem__(self, k: K) -> V:
        return self._m[k]

    def get(self, k: K, default: V | None = None) -> V | None:
        return self._m.get(k, default)

    def get_or_else(self, k: K, default: V) -> V:
        return self._m.get(k, default)

    def contains(self, k: K) -> bool:
        return k in self._m

    def __contains__(self, k: object) -> bool:
        return k in self._m

    def __len__(self) -> int:
        return len(self._m)

    def __iter__(self) -> Iterator[K]:
        return iter(self._m)

    def keys(self):
        return self._m.keys()

    def values(self):
        return self._m.values()

    def items(self):
        return self._m.items()

    def to_dict(self) -> dict[K, V]:
        return dict(self._m)

    def take(self, n: int) -> "BiMap[K, V]":
        return BiMap(dict(list(self._m.items())[:n]))

    def __eq__(self, other: object) -> bool:
        return isinstance(other, BiMap) and self._m == other._m

    def __repr__(self) -> str:
        return f"BiMap({len(self._m)} entries)"

    # -- vectorized construction / lookup (the TPU path) ------------------
    @staticmethod
    def from_iterable(keys: Iterable[K]) -> "BiMap[K, int]":
        """Dense 0..n-1 indexing of distinct keys, first-seen order is not
        guaranteed (sorted for determinism, matching ``np.unique``)."""
        uniq = sorted(set(keys))  # type: ignore[type-var]
        return BiMap({k: i for i, k in enumerate(uniq)})

    @staticmethod
    def from_array(keys: np.ndarray) -> tuple["BiMap[object, int]", np.ndarray]:
        """Vectorized: returns (bimap, indices) where ``indices[j]`` is the
        dense index of ``keys[j]``. One ``np.unique`` pass — the analog of
        the reference's ``stringInt(rdd)`` (BiMap.scala:116-126) without a
        shuffle."""
        uniq, inv = np.unique(keys, return_inverse=True)
        bm = BiMap({k.item() if hasattr(k, "item") else k: i for i, k in enumerate(uniq)})
        return bm, inv.astype(np.int32)

    def map_array(self, keys: Sequence[K], default: int = -1) -> np.ndarray:
        """Map a batch of keys to indices; unseen keys -> ``default``."""
        return np.asarray([self._m.get(k, default) for k in keys], dtype=np.int32)

    def inverse_array(self) -> np.ndarray:
        """Dense inverse for int-valued BiMaps: array ``a`` with
        ``a[index] = key position``; only valid when values are 0..n-1."""
        n = len(self._m)
        keys = list(self._m.keys())
        vals = np.asarray(list(self._m.values()))
        if vals.min(initial=0) != 0 or vals.max(initial=-1) != n - 1:
            raise ValueError("inverse_array requires dense 0..n-1 values")
        out = np.empty(n, dtype=object)
        out[vals] = keys
        return out


def string_int_bimap(keys: Iterable[str]) -> BiMap[str, int]:
    """Reference ``BiMap.stringInt`` (BiMap.scala:72-90)."""
    return BiMap.from_iterable(keys)
