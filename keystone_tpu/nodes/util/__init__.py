"""Utility nodes (reference ``nodes/util``, SURVEY.md section 2.8)."""
from __future__ import annotations

from typing import Sequence

import jax
import jax.numpy as jnp
import numpy as np

from ...parallel.dataset import ArrayDataset, Dataset
from ...workflow.transformer import Transformer


class ClassLabelIndicatorsFromIntLabels(Transformer):
    """int label -> +-1 one-hot vector
    (reference ``util/ClassLabelIndicators.scala:15-34``)."""

    def __init__(self, num_classes: int):
        assert num_classes > 1, "numClasses must be > 1"
        self.num_classes = num_classes

    def apply(self, label):
        idx = jnp.arange(self.num_classes)
        return jnp.where(idx == label, 1.0, -1.0).astype(jnp.float32)


class ClassLabelIndicatorsFromIntArrayLabels(Transformer):
    """multi-label int array -> +-1 multi-hot vector
    (reference ``util/ClassLabelIndicators.scala:41-55``). Inputs are
    fixed-width padded label arrays with -1 for missing entries (the TPU
    layout for ragged label sets)."""

    def __init__(self, num_classes: int):
        assert num_classes > 1, "numClasses must be > 1"
        self.num_classes = num_classes

    def apply(self, labels):
        base = jnp.full((self.num_classes,), -1.0, dtype=jnp.float32)
        valid = labels >= 0
        onehot = jax.nn.one_hot(
            jnp.where(valid, labels, 0), self.num_classes, dtype=jnp.float32
        )
        hits = jnp.sum(onehot * valid[:, None].astype(jnp.float32), axis=0)
        return jnp.where(hits > 0, 1.0, base)


class VectorCombiner(Transformer):
    """Concatenate a gathered tuple of vectors into one vector
    (reference ``util/VectorCombiner.scala:12-14``)."""

    #: what ``optimizer/stream_gather.py`` looks for after a gather
    concatenates_gather = True

    def apply(self, xs):
        return jnp.concatenate(list(xs), axis=-1)


class MaxClassifier(Transformer):
    """argmax (reference ``util/MaxClassifier.scala:9-11``)."""

    def apply(self, x):
        return jnp.argmax(x, axis=-1).astype(jnp.int32)


class TopKClassifier(Transformer):
    """Indices of the k largest values, descending
    (reference ``util/TopKClassifier.scala:9-11``)."""

    def __init__(self, k: int):
        self.k = k

    def apply(self, x):
        _, idx = jax.lax.top_k(x, self.k)
        return idx.astype(jnp.int32)


class VectorSplitter(Transformer):
    """Split the feature dimension into blocks of ``block_size``
    (reference ``util/VectorSplitter.scala:11-36``). Returns a tuple of
    sub-vectors per item; block boundaries are static."""

    def __init__(self, block_size: int, num_features: int = None):
        self.block_size = block_size
        self.num_features = num_features

    def _bounds(self, d: int):
        bs = self.block_size
        nb = (d + bs - 1) // bs
        return [(i * bs, min(d, (i + 1) * bs)) for i in range(nb)]

    def apply(self, x):
        d = self.num_features or x.shape[-1]
        return tuple(x[..., lo:hi] for lo, hi in self._bounds(d))


class FloatToDouble(Transformer):
    """Precision promotion (reference ``util/FloatToDouble.scala``). On TPU
    f64 is unsupported; this promotes to the highest available float so
    downstream solvers run at full precision."""

    maps_columns = True     # a cast, entry by entry

    def apply(self, x):
        return x.astype(jnp.float64 if jax.config.jax_enable_x64 else jnp.float32)


class DoubleToFloat(Transformer):
    def apply(self, x):
        return x.astype(jnp.float32)


class MatrixVectorizer(Transformer):
    """Flatten a matrix into a vector, column-major to match Breeze's
    ``toDenseVector`` (reference ``util/MatrixVectorizer.scala``)."""

    def apply(self, x):
        return x.T.reshape(-1)


class Densify(Transformer):
    """Sparse -> dense passthrough (reference ``util/Densify.scala:10-21``).
    ArrayDatasets are already dense; sparse host datasets are stacked."""

    def apply(self, x):
        if hasattr(x, "todense"):
            return jnp.asarray(x.todense())
        return x

    def apply_dataset(self, ds: Dataset) -> Dataset:
        from ...parallel.dataset import HostDataset

        if isinstance(ds, ArrayDataset):
            return ds
        from ...parallel.dataset import is_streaming

        if is_streaming(ds):
            # StreamingDataset: chunks are already dense device arrays;
            # collect() here would silently materialize the stream
            return ds
        items = ds.collect()
        dense = [
            np.asarray(
                it.todense() if hasattr(it, "todense") else it, dtype=np.float32
            ).ravel()
            for it in items
        ]
        return ArrayDataset.from_items(dense)

    def abstract_single(self, elements):
        from ...analysis.spec import SparseSpec, Unknown

        (e,) = elements
        if isinstance(e, SparseSpec):
            if e.size is None:
                return Unknown("sparse element of unknown size")
            return jax.ShapeDtypeStruct((e.size,), np.float32)
        return super().abstract_single(elements)


class Cast(Transformer):
    def __init__(self, dtype: str):
        self.dtype = dtype

    def apply(self, x):
        return x.astype(self.dtype)


from .sparse import (  # noqa: E402
    AllSparseFeatures,
    CommonSparseFeatures,
    SparseFeatureVectorizer,
    SparseVector,
    Sparsify,
    sparse_batch,
)


class LabelAugmenter(Transformer):
    """Repeat each item ``mult`` times, item-major — aligns labels/ids
    with patch-augmented data (reference
    ``RandomPatchCifarAugmented.LabelAugmenter``)."""

    def __init__(self, mult: int):
        self.mult = mult

    def apply(self, x):
        return x

    def abstract_eval(self, dep_specs):
        from ...analysis.spec import DatasetSpec

        out = super().abstract_eval(dep_specs)
        if isinstance(out, DatasetSpec) and out.n is not None:
            return DatasetSpec(out.element, n=out.n * self.mult,
                               host=out.host, sparsity=out.sparsity)
        return out

    def apply_dataset(self, ds: Dataset) -> Dataset:
        if isinstance(ds, ArrayDataset):
            # on the device, where the rows are: real rows stay first
            # and padding last, and a shard's rows stay that shard's
            from ...parallel.mesh import batch_sharding

            rep = jax.tree_util.tree_map(
                lambda x: jax.device_put(
                    jnp.repeat(x, self.mult, axis=0),
                    batch_sharding(ds.mesh)), ds.data)
            return ArrayDataset(rep, ds.n * self.mult, ds.mesh,
                                _already_sharded=True)
        from ...parallel.dataset import HostDataset

        return HostDataset(
            [it for it in ds.collect() for _ in range(self.mult)])
