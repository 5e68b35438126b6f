"""Sampling nodes (reference ``stats/Sampling.scala``)."""
from __future__ import annotations

import jax.numpy as jnp
import numpy as np

from ...parallel.dataset import ArrayDataset, Dataset, HostDataset
from ...workflow.transformer import Transformer


def sample_indices(n: int, size: int, seed: int) -> np.ndarray:
    """Sorted indices of a seeded sample of ``min(size, n)`` of ``n``
    items without replacement — the one draw every sampling node shares,
    so a node that samples before materializing its input picks exactly
    the items :class:`Sampler` would have picked after."""
    rng = np.random.RandomState(seed)
    idx = rng.choice(n, size=min(size, n), replace=False)
    idx.sort()
    return idx


class Sampler(Transformer):
    """Random subsample of approximately ``size`` items (reference
    ``Sampler``: RDD takeSample without replacement). Deterministic seed."""

    def __init__(self, size: int, seed: int = 42):
        self.size = size
        self.seed = seed

    def apply(self, x):
        return x

    def apply_dataset(self, ds: Dataset) -> Dataset:
        idx = sample_indices(len(ds), self.size, self.seed)
        take = len(idx)
        if isinstance(ds, ArrayDataset):
            import jax

            # gather ON DEVICE: the input may be huge (e.g. every window
            # of every training image); pulling it to host to select a
            # small sample is a multi-GB transfer for a few-MB result
            idx_dev = jnp.asarray(idx)
            data = jax.tree_util.tree_map(
                lambda x: jnp.take(x, idx_dev, axis=0), ds.data
            )
            return ArrayDataset(data, take, ds.mesh)
        items = ds.collect()
        return HostDataset([items[i] for i in idx])

    def abstract_eval(self, dep_specs):
        from ...analysis.spec import DatasetSpec

        out = super().abstract_eval(dep_specs)
        if isinstance(out, DatasetSpec) and out.n is not None:
            return DatasetSpec(out.element, n=min(self.size, out.n),
                               host=out.host, sparsity=out.sparsity)
        return out


class ColumnSampler(Transformer):
    """Sample ``num_cols`` columns of each per-item (d, cols) matrix
    (reference ``ColumnSampler``, used to subsample SIFT descriptors)."""

    def __init__(self, num_cols: int, seed: int = 42):
        self.num_cols = num_cols
        self.seed = seed

    def apply(self, x):
        # deterministic per-node sample of columns; jax-traceable via fixed
        # host-side indices requires static col count, so sample uniformly
        # with a fixed numpy draw over the static shape
        cols = x.shape[-1]
        rng = np.random.RandomState(self.seed)
        idx = rng.choice(cols, size=min(self.num_cols, cols), replace=False)
        idx.sort()
        return x[..., jnp.asarray(idx)]


def sample_rows(mat: np.ndarray, num_rows: int, seed: int = 0) -> np.ndarray:
    """Random row subset (reference ``MatrixUtils.sampleRows``)."""
    return np.asarray(mat)[sample_indices(mat.shape[0], num_rows, seed)]
