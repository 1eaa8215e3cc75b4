"""Plain ``transmogrify()`` of Integral and PickList columns, NumPy float64:
the reference the typed pool cell's design is decided against
(``criteo_bin_pool.search``).

The package's semantics (``ops/numeric.py`` IntegralVectorizer,
``ops/categorical.py`` OneHotVectorizer, ``ops/transmogrify.py``), written
straight from their description with nothing of the package:

- an Integral column becomes two columns: its value with every missing one
  filled by the column's MODE over the fitted rows (the most frequent
  present value, the smallest of those tied; 0 where none is present), then
  a null indicator (1 where the value was missing);
- a PickList column becomes ``K + 2`` one-hot columns: its top-K categories
  (``top_k`` 20) among those present at least ``min_support`` (10) times on
  the fitted rows, by count descending then lexically, then OTHER (a
  present value outside them, seen or not) and NULL (missing);
- the design is every Integral's pair in the order declared, then every
  PickList's block in the order declared (``transmogrify`` groups the
  features by type, the groups by the type's name).

``columns()`` names each design column as (parent feature, indicator value
or None), the provenance the package's vector metadata records.
"""
from __future__ import annotations

from typing import Dict, List, Optional, Sequence, Tuple

import numpy as np

NULL = "NullIndicatorValue"
OTHER = "OTHER"


def mode(values: np.ndarray) -> float:
    """The most frequent present (non-NaN) value, the smallest of those
    tied; 0 where none is present."""
    counts: Dict[float, int] = {}
    for v in values[~np.isnan(values)].tolist():
        counts[v] = counts.get(v, 0) + 1
    if not counts:
        return 0.0
    best = max(counts.values())
    return min(v for v, c in counts.items() if c == best)


def top_categories(values: Sequence, top_k: int, min_support: int
                   ) -> List[str]:
    """The top-K categories of a column: present at least ``min_support``
    times, by count descending then lexically."""
    counts: Dict[str, int] = {}
    for v in values:
        if v is not None:
            counts[v] = counts.get(v, 0) + 1
    ranked = sorted((c for c in counts.items() if c[1] >= min_support),
                    key=lambda c: (-c[1], c[0]))
    return [c for c, _ in ranked[:top_k]]


class PlainTransmogrify:
    """``fit(table)`` on the training rows, then ``transform(table)`` of
    any rows; a table maps a column's name to its values (float64 with NaN,
    or an object array of strings and None)."""

    def __init__(self, integral: Sequence[str], picklist: Sequence[str],
                 top_k: int = 20, min_support: int = 10):
        self.integral, self.picklist = list(integral), list(picklist)
        self.top_k, self.min_support = top_k, min_support

    def fit(self, table: Dict[str, np.ndarray]) -> "PlainTransmogrify":
        self.fills = [mode(np.asarray(table[name], np.float64))
                      for name in self.integral]
        self.categories = [top_categories(table[name], self.top_k,
                                          self.min_support)
                           for name in self.picklist]
        return self

    def columns(self) -> List[Tuple[str, Optional[str]]]:
        out: List[Tuple[str, Optional[str]]] = []
        for name in self.integral:
            out += [(name, None), (name, NULL)]
        for name, cats in zip(self.picklist, self.categories):
            out += [(name, c) for c in cats] + [(name, OTHER), (name, NULL)]
        return out

    def transform(self, table: Dict[str, np.ndarray]) -> np.ndarray:
        blocks = []
        for name, fill in zip(self.integral, self.fills):
            values = np.asarray(table[name], np.float64)
            missing = np.isnan(values)
            blocks += [np.where(missing, fill, values),
                       missing.astype(np.float64)]
        for name, cats in zip(self.picklist, self.categories):
            index = {c: j for j, c in enumerate(cats)}
            values = table[name]
            block = np.zeros((len(values), len(cats) + 2))
            for i, v in enumerate(values):
                block[i, len(cats) + 1 if v is None
                      else index.get(v, len(cats))] = 1.0
            blocks += list(block.T)
        return np.stack(blocks, axis=1)
