"""Sampling nodes (reference ``stats/Sampling.scala``)."""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from ...parallel.dataset import ArrayDataset, Dataset, HostDataset
from ...parallel.ragged import RaggedDataset
from ...workflow.operators import Operator
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
        return _entry(_draw((self,), ds), 0)


class SharedColumnSampler(ColumnSampler):
    """A ``ColumnSampler`` that also draws for the others that read the
    dataset it reads (``workflow/optimizer/column_samples.py`` puts it
    in the first one's place): every sampler's columns are drawn as it
    alone draws them and gathered from ONE making of each item, so a
    dataset whose items are made when asked for (``RaggedDataset``:
    dense SIFT a chunk) is made once for all of them. The node's value
    is its own sample, ``samplers[0]``'s; the others ride on its
    expression (``drawn``, by the sampler's place) until the node that
    asked (``ColumnSampleAhead``) takes its own away: a place after the
    first has ONE reader, so nothing holds a sample once its reader has
    made of it what it needs (a million raw descriptors are half a
    gigabyte). ``featurize.sample_pass.siblings`` rises by ``serves``,
    the nodes drawn for, once a pass."""

    def __init__(self, samplers, serves: int):
        super().__init__(samplers[0].num_cols, samplers[0].seed)
        self.samplers = tuple(samplers)
        self.serves = serves

    def execute(self, deps):
        from ...observability.metrics import MetricsRegistry
        from ...workflow.expression import DatasetExpression, DatumExpression

        (rows,) = deps
        batch = isinstance(rows, DatasetExpression)

        def own():
            MetricsRegistry.get_or_create().counter(
                "featurize.sample_pass.siblings").inc(self.serves)
            if batch:
                drawn = _draw(self.samplers, rows.get())
            else:
                x = rows.get()
                drawn = tuple(s.apply(x) for s in self.samplers)
            expr.drawn = {i: _entry(drawn, i)
                          for i in range(len(self.samplers))}
            return expr.drawn[0]

        expr = (DatasetExpression if batch else DatumExpression)(own)
        return expr

    def canonical_prefix(self, dep_prefixes):
        from ...workflow.prefix import operator_prefix

        return operator_prefix(self.samplers[0], dep_prefixes)

    def label(self) -> str:
        return f"ColumnSampler (draws for {self.serves})"


class ColumnSampleAhead(Operator):
    """A ``ColumnSampler`` drawn in front of the column-wise ``chain``
    the pipeline wrote it behind (``workflow/optimizer/column_samples.
    py``): the sample is drawn from what the chain stands on
    (``index`` None: dependency 0 is that dataset) or taken from the
    pass a ``SharedColumnSampler`` makes of it anyway (dependency 0 is
    that node, ``index`` this sampler's place among its draws: 0 where
    it draws what that node draws for itself, else a place of its own),
    and the chain is then applied to the sample. ``chain`` lists what the
    sampler moved over, from its old input down: a transformer that
    maps columns (a ``Cacher`` among them holds nothing here: a sample
    is held by whoever asked for it) or None for a fitted transformer,
    whose fits are the dependencies after the first, in that order. To
    the state table the node is what the pipeline wrote."""

    def __init__(self, sampler: ColumnSampler, chain=(), index=None):
        self.sampler = sampler
        self.chain = tuple(chain)
        self.index = index

    def execute(self, deps):
        from ...workflow.common import Cacher
        from ...workflow.expression import DatasetExpression, DatumExpression

        drawn, fits = deps[0], deps[1:]
        batch = isinstance(drawn, DatasetExpression)

        def sample():
            value = drawn.get()     # index 0: the shared node's own sample
            if self.index is None:
                value = (self.sampler.apply_dataset(value) if batch
                         else self.sampler.apply(value))
            elif self.index:
                value = drawn.drawn.pop(self.index)
            fitted = iter(reversed(fits))
            for node in reversed(self.chain):
                if isinstance(node, Cacher):
                    continue
                node = next(fitted).get() if node is None else node
                value = (node.batch_transform([value]) if batch
                         else node.single_transform([value]))
            return value

        return (DatasetExpression if batch else DatumExpression)(sample)

    def canonical_prefix(self, dep_prefixes):
        from ...workflow.operators import DelegatingOperator
        from ...workflow.prefix import operator_prefix

        cur, fits = dep_prefixes[0], list(dep_prefixes[1:])
        if self.index is not None:
            (cur,) = cur[2]       # a sampler's ("prefix", key, (rows',))
        for node in reversed(self.chain):
            cur = (operator_prefix(DelegatingOperator(), (fits.pop(), cur))
                   if node is None else operator_prefix(node, (cur,)))
        return operator_prefix(self.sampler, (cur,))

    def label(self) -> str:
        return "ColumnSampler (drawn ahead)"


def _entry(drawn, index: int):
    """Sample ``index`` of what ``_draw`` gave, or of one item's tuple."""
    if isinstance(drawn, ArrayDataset):
        return ArrayDataset(drawn.data[index], drawn.n, drawn.mesh,
                            _already_sharded=True)
    if isinstance(drawn, Dataset):
        return drawn.map(lambda samples: samples[index])
    return drawn[index]


def _draw(samplers, ds: Dataset) -> Dataset:
    """Every item of ``ds`` as the tuple of the samples ``samplers``
    draw from it, one gather of the concatenated draws an array."""
    if isinstance(ds, RaggedDataset):
        try:
            return _draw_chunks(samplers, ds)
        except _TooFewColumns:
            ds = HostDataset(ds.collect())   # samples of different widths
    if isinstance(ds, ArrayDataset):
        cols = int(ds.data.shape[-1])
        widths = [min(s.num_cols, cols) for s in samplers]
        idx = np.zeros((ds.padded_n, sum(widths)), np.int64)
        for i in range(ds.n):
            idx[i] = np.concatenate([s.columns(cols, i) for s in samplers])
        return ArrayDataset(_take(ds.data, idx, widths),
                            ds.n, ds.mesh, _already_sharded=True)
    return HostDataset([
        tuple(np.asarray(x)[..., s.columns(np.shape(x)[-1], i)]
              for s in samplers)
        for i, x in enumerate(ds.collect())])


def _draw_chunks(samplers, ds: RaggedDataset) -> ArrayDataset:
    """Matrices of different widths in padded chunks: the draws are
    made on the host from each item's true width, mapped to where its
    columns stand in the padded matrix, and gathered on the device.
    Every item has to have the widest sampler's ``num_cols`` columns
    for the result to be arrays."""
    widths = [s.num_cols for s in samplers]

    def sample(chunk):
        idx = np.zeros((len(chunk.ids), sum(widths)), np.int32)
        for slot in np.flatnonzero(chunk.real):
            real = (np.flatnonzero(chunk.mask[slot])
                    if chunk.mask is not None
                    else np.arange(chunk.data.shape[-1]))
            if len(real) < max(widths):
                raise _TooFewColumns(int(chunk.ids[slot]))
            item = int(chunk.ids[slot])
            idx[slot] = np.concatenate(
                [real[s.columns(len(real), item)] for s in samplers])
        return _take(chunk.data, idx, widths)

    return ds.gather(sample)


class _TooFewColumns(Exception):
    """An item is narrower than the sample asked of it."""


def _take(x, idx: np.ndarray, widths) -> tuple:
    """The columns ``idx`` of each item of ``x``, cut into runs of
    ``widths``: a lone sampler's gather is the program it always was."""
    if len(widths) == 1:
        return (_take_columns(x, jnp.asarray(idx)),)
    return _take_columns_split(x, jnp.asarray(idx), tuple(widths))


def _columns_of_each(x, idx):
    return jnp.take_along_axis(
        x, idx.reshape((idx.shape[0],) + (1,) * (x.ndim - 2) + idx.shape[1:]),
        axis=-1)


@jax.jit
def _take_columns(x, idx):
    """``x[b, ..., idx[b]]``: each item's own columns."""
    return _columns_of_each(x, idx)


@functools.partial(jax.jit, static_argnames="widths")
def _take_columns_split(x, idx, widths):
    """``_take_columns`` once, its columns handed back in runs of
    ``widths``."""
    taken = _columns_of_each(x, idx)
    ends = np.cumsum(widths)
    return tuple(taken[..., end - width:end]
                 for width, end in zip(widths, ends))


def sample_rows(mat: np.ndarray, num_rows: int, seed: int = 0) -> np.ndarray:
    """Random row subset (reference ``MatrixUtils.sampleRows``)."""
    return np.asarray(mat)[sample_indices(mat.shape[0], num_rows, seed)]
