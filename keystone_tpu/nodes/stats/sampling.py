"""Sampling nodes (reference ``stats/Sampling.scala``)."""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from ...parallel.dataset import ArrayDataset, Dataset, HostDataset
from ...parallel.ragged import RaggedDataset
from ...workflow.transformer import Transformer


def sample_indices(n: int, size: int, seed: int) -> np.ndarray:
    """Sorted indices of a seeded sample of ``min(size, n)`` of ``n``
    items without replacement — the one draw every sampling node shares,
    so a node that samples before materializing its input picks exactly
    the items :class:`Sampler` would have picked after.

    The contract: the sorted first ``min(size, n)`` entries of
    ``np.random.RandomState(seed).permutation(n)``, bit for bit, however
    computed: without the shuffle of ``n`` entries where the native
    library loads (``keystone_tpu.native.permutation_head``), by NumPy's
    ``choice`` where it does not; ``featurize.sample_draw.sparse`` /
    ``.dense`` count which."""
    from ...native import permutation_head
    from ...observability.metrics import MetricsRegistry

    size = min(size, n)
    idx = permutation_head(n, size, seed)
    MetricsRegistry.get_or_create().counter(
        "featurize.sample_draw."
        + ("dense" if idx is None else "sparse")).inc()
    if idx is None:
        idx = np.random.RandomState(seed).choice(n, size=size, replace=False)
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
    (reference ``ColumnSampler``, used to subsample SIFT descriptors):
    without replacement, in the columns' own order. Item ``i`` of a
    dataset draws from ``(seed, i)``, whatever the dataset's kind, so
    one seed gives one sample and another seed another."""

    def __init__(self, num_cols: int, seed: int = 42):
        self.num_cols = num_cols
        self.seed = seed

    def columns(self, cols: int, item: int = 0) -> np.ndarray:
        """Which of ``cols`` columns item ``item`` keeps, sorted."""
        rng = np.random.default_rng((self.seed, item))
        idx = rng.choice(cols, size=min(self.num_cols, cols), replace=False)
        idx.sort()
        return idx

    def apply(self, x):
        return x[..., jnp.asarray(self.columns(x.shape[-1]))]

    def apply_dataset(self, ds: Dataset) -> Dataset:
        if isinstance(ds, RaggedDataset):
            try:
                return self._sample_chunks(ds)
            except _TooFewColumns:
                ds = HostDataset(ds.collect())   # samples of different widths
        if isinstance(ds, ArrayDataset):
            cols = int(ds.data.shape[-1])
            idx = np.stack([self.columns(cols, i) for i in range(ds.n)])
            idx = np.concatenate(
                [idx, np.zeros((ds.padded_n - ds.n, idx.shape[1]), idx.dtype)])
            return ArrayDataset(_take_columns(ds.data, jnp.asarray(idx)),
                                ds.n, ds.mesh, _already_sharded=True)
        return HostDataset([
            np.asarray(x)[..., self.columns(np.shape(x)[-1], i)]
            for i, x in enumerate(ds.collect())])

    def _sample_chunks(self, ds: RaggedDataset) -> ArrayDataset:
        """Matrices of different widths in padded chunks: the draw is
        made on the host from each item's true width, mapped to where
        its columns stand in the padded matrix, and gathered on the
        device. Every item has to have ``num_cols`` columns for the
        result to be one array."""
        def sample(chunk):
            idx = np.zeros((len(chunk.ids), self.num_cols), np.int32)
            for slot in np.flatnonzero(chunk.real):
                real = (np.flatnonzero(chunk.mask[slot])
                        if chunk.mask is not None
                        else np.arange(chunk.data.shape[-1]))
                if len(real) < self.num_cols:
                    raise _TooFewColumns(int(chunk.ids[slot]))
                idx[slot] = real[self.columns(len(real), int(chunk.ids[slot]))]
            return _take_columns(chunk.data, jnp.asarray(idx))

        return ds.gather(sample)


class _TooFewColumns(Exception):
    """An item is narrower than the sample asked of it."""


@jax.jit
def _take_columns(x, idx):
    """``x[b, ..., idx[b]]``: each item's own columns."""
    return jnp.take_along_axis(
        x, idx.reshape((idx.shape[0],) + (1,) * (x.ndim - 2) + idx.shape[1:]),
        axis=-1)


def sample_rows(mat: np.ndarray, num_rows: int, seed: int = 0) -> np.ndarray:
    """Random row subset (reference ``MatrixUtils.sampleRows``)."""
    return np.asarray(mat)[sample_indices(mat.shape[0], num_rows, seed)]
