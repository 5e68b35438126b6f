"""Multi-label image extractors (reference
``nodes/images/LabeledImageExtractors.scala``).

Items are :class:`~keystone_tpu.loaders.image_loader_utils.MultiLabeledImage`
host objects; label sets are ragged, so ``MultiLabelExtractor`` pads them
to a fixed width with -1 (the TPU layout consumed by
``ClassLabelIndicatorsFromIntArrayLabels``).
"""
from __future__ import annotations

import numpy as np

from ...parallel.dataset import ArrayDataset, Dataset
from ...parallel.ragged import RaggedDataset
from ...workflow.transformer import Transformer


class MultiLabelExtractor(Transformer):
    """MultiLabeledImage -> padded int label array
    (reference ``LabeledImageExtractors.scala``)."""

    def apply(self, item):
        return np.asarray(item.labels, dtype=np.int32)

    def apply_dataset(self, ds: Dataset) -> Dataset:
        items = ds.collect()
        width = max((len(it.labels) for it in items), default=1) or 1
        padded = np.full((len(items), width), -1, dtype=np.int32)
        for i, it in enumerate(items):
            padded[i, : len(it.labels)] = np.asarray(it.labels, np.int32)
        return ArrayDataset.from_numpy(padded)


class MultiLabeledImageExtractor(Transformer):
    """MultiLabeledImage -> image array. The images of a collection
    differ in size: they go to the device in padded chunks, bucketed by
    size (``parallel.ragged``)."""

    def apply(self, item):
        return item.image

    def apply_dataset(self, ds: Dataset) -> Dataset:
        return RaggedDataset.from_items([it.image for it in ds.collect()])
