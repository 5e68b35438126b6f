"""VOC 2007 loader (reference ``loaders/VOCLoader.scala``).

Images come from a tar; the labels CSV has a header row and columns where
column 1 is the 1-based class id and column 4 the quoted image filename —
one row per (image, label) pair, so images accumulate multiple labels.
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Dict, List, Optional

import numpy as np

from ..parallel.dataset import HostDataset
from .image_loader_utils import (
    MultiLabeledImage,
    list_archive_paths,
    load_tar_files,
)

NUM_CLASSES = 20  # constant of the VOC 2007 dataset


@dataclass
class VOCDataPath:
    images_dir_name: str
    name_prefix: str = "VOCdevkit"
    num_parts: Optional[int] = None


@dataclass
class VOCLabelPath:
    labels_file_name: str


def parse_voc_labels(labels_path: str) -> Dict[str, List[int]]:
    """filename -> 0-based label list (reference ``VOCLoader.scala:33-48``)."""
    labels_map: Dict[str, List[int]] = {}
    with open(labels_path) as f:
        lines = f.read().splitlines()
    for line in lines[1:]:  # drop header
        if not line.strip():
            continue
        parts = line.split(",")
        fname = parts[4].replace('"', "")
        label = int(parts[1]) - 1
        labels_map.setdefault(fname, []).append(label)
    return labels_map


def voc_loader(data_path: VOCDataPath, labels_path: VOCLabelPath) -> HostDataset:
    """RDD[MultiLabeledImage] analogue (reference ``VOCLoader.scala:29-52``).
    Label lookup keys on the entry's basename, matching the CSV filenames.
    Images stay the decoder's own bytes (``PixelScaler`` makes floats of
    them on the device): VOC 2007's 9,963 images are 5.6 GB as bytes."""
    labels_map = parse_voc_labels(labels_path.labels_file_name)

    def lookup(entry_name: str) -> List[int]:
        base = entry_name.split("/")[-1]
        return labels_map.get(base, [])

    return load_tar_files(
        list_archive_paths(data_path.images_dir_name),
        lookup,
        lambda img, labels, name: MultiLabeledImage(img, labels, name),
        name_prefix=data_path.name_prefix or None,
        decode_dtype=np.uint8,
    )
