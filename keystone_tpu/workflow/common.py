"""Identity and Cacher stages.

Mirror ``workflow/graph/Identity.scala`` and ``workflow/graph/Cacher.scala``.
On TPU, "caching" means the dataset is materialized device-resident (jax
arrays are already eager), so Cacher's real job is (1) marking the node
saveable for the cross-pipeline prefix memo — the analogue of the
reference's ``ExtractSaveablePrefixes`` treating Cacher specially — and
(2) forcing any lazy upstream to materialize once.
"""
from __future__ import annotations

from typing import Any

from ..parallel.dataset import Dataset
from .transformer import Transformer


class Identity(Transformer):
    maps_columns = True

    def apply(self, x: Any) -> Any:
        return x

    def apply_dataset(self, ds: Dataset) -> Dataset:
        return ds


class Cacher(Transformer):
    """Marks its output for materialization + cross-pipeline reuse
    (reference ``nodes/util/Cacher.scala:15-25``)."""

    saveable = True
    maps_columns = True     # the identity

    def __init__(self, name: str = ""):
        self.name = name

    def apply(self, x: Any) -> Any:
        return x

    def apply_dataset(self, ds: Dataset) -> Dataset:
        return ds.cache()

    def label(self) -> str:
        return f"Cache({self.name})" if self.name else "Cache"
