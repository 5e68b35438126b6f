"""RaggedDataset: items of one rank whose sizes differ (images of a
collection, their descriptor matrices), on the device.

A ``HostDataset`` of such items is a Python loop of one jitted call an
item a node, and a program a shape. Here the items are sorted into a few
BUCKETS by size (each ragged side rounded up to ``GRANULE``), and a
bucket is cut into CHUNKS of ``ITEMS_A_CHUNK`` items padded with zeros to
the bucket's shape: a node runs one program a bucket and one call a
chunk, whatever sizes the items in it have. A chunk says which part of
each padded item is real (``extent``, ``mask``); a node that can work on
padded items says so (``Transformer.chunk_stage``), and any other node
is handed the items one by one, cut to their own sizes, as before.

The dataset is LAZY: mapping a node over it records the node, and a
chunk is made when something asks for it (a sampler, an encoder whose
output is small, ``cache``), one chunk at a time. That is what lets a
chain whose intermediate values no device holds run at all: dense SIFT
descriptors of a thousand VOC images are 25 GB. ``cache()`` (what a
``Cacher`` node calls) holds the chunks that fit in ``CACHE_SHARE`` of
the device's free memory and leaves the rest to be made again by every
reader: the reference's cache under a memory budget, decided here from
the sizes the chunks turn out to have.
"""
from __future__ import annotations

import collections
import dataclasses
import math
from typing import Any, Callable, Iterator, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..observability.timeline import flight_span
from .dataset import ArrayDataset, Dataset, HostDataset, _h2d, padded_rows
from .mesh import batch_sharding, get_mesh, num_data_shards

#: bucket sides are multiples of this (the matrix unit's tile)
GRANULE = 128
#: items a chunk: a chunk of dense SIFT descriptors at 512 x 512 is 0.5 GB
ITEMS_A_CHUNK = 16
#: the share of the device's free memory ``cache()`` may hold. What it
#: leaves has to take what the readers of the cache need beside it: a
#: few chunks in flight with their intermediates (1.5 GB each for dense
#: SIFT at 512 x 512), samples, an estimator's temporaries (3 GB for EM
#: over a million descriptors). At 0.4 a fit of 1,024 + 1,024 VOC images
#: peaked at 14.2 of 15.75 GiB (my chip runs, PR 33): a third leaves the
#: next seed's mix of sizes room.
CACHE_SHARE = 1.0 / 3.0
#: chunks the host may run ahead of the device (each holds its
#: intermediates from the moment it is dispatched)
CHUNKS_IN_FLIGHT = 2


@dataclasses.dataclass
class Chunk:
    """``ITEMS_A_CHUNK`` items padded to one shape."""

    data: Any                 # [b, ...] on the device, zero outside the real
    ids: np.ndarray           # [b] place in the dataset; -1: an empty slot
    extent: np.ndarray        # [b, r] int32 true sizes of the leading r axes
    #: the item's axes after its ragged ones, which ``data`` holds FOLDED
    #: into the last ragged axis (``unfold``): an image of ``(h, w, 3)``
    #: bytes is held as ``(h, 3 w)``. The TPU lays an array out in tiles
    #: of its two minor axes and pads the minor one to 128 lanes, so a
    #: chunk of ``[16, 384, 512, 3]`` bytes would take 42 times its size
    #: on the device, and in the host's staging buffers on its way there
    #: (1,024 VOC images: 26 GB; my chip runs, PR 33)
    tail: Tuple[int, ...] = ()
    #: [b, L] bool, which entries of the LAST axis are real, where those
    #: are not a leading part of it (descriptors of an image's own grid
    #: inside its bucket's); None: the extent says it all
    mask: Optional[np.ndarray] = None

    @property
    def real(self) -> np.ndarray:
        return self.ids >= 0

    def nbytes(self) -> int:
        return sum(int(x.nbytes) for x in jax.tree_util.tree_leaves(self.data))


Stage = Callable[[Chunk], Chunk]


def unfold(x, tail: Tuple[int, ...]):
    """``[..., last * prod(tail)] -> [..., last, *tail]`` (numpy or jax;
    inside a program the compiler keeps it out of memory)."""
    if not tail:
        return x
    return x.reshape(x.shape[:-1] + (x.shape[-1] // math.prod(tail),)
                     + tuple(tail))


def fold(x, ragged_axes: int):
    """``[b, R1..Rr, *tail] -> [b, R1..R(r-1), Rr * prod(tail)]``."""
    if ragged_axes == 0 or x.ndim == 1 + ragged_axes:
        return x
    return x.reshape(x.shape[:ragged_axes] + (-1,))


class RaggedDataset(Dataset):
    """Chunks, each with the stages still to be applied to it."""

    def __init__(self, parts: List[Tuple[Chunk, Tuple[Stage, ...]]], n: int,
                 tag: Optional[str] = None):
        self.parts = parts
        self.n = int(n)
        self.tag = tag

    # -- construction -----------------------------------------------------
    @staticmethod
    def from_items(items: Sequence[np.ndarray], ragged_axes: int = 2,
                   ) -> "RaggedDataset":
        """Arrays of one rank and dtype whose first ``ragged_axes`` sizes
        differ: bucketed, padded and put on the device (``ingest:h2d``)."""
        items = [np.asarray(x) for x in items]
        tail = items[0].shape[ragged_axes:]
        buckets = collections.defaultdict(list)
        for i, x in enumerate(items):
            assert x.shape[ragged_axes:] == tail, (x.shape, tail)
            sides = tuple(-(-s // GRANULE) * GRANULE
                          for s in x.shape[:ragged_axes])
            buckets[sides + tail].append(i)
        mesh = get_mesh()
        sharding = batch_sharding(mesh)
        # a chunk's items are spread over the mesh's data axis
        size = padded_rows(ITEMS_A_CHUNK, num_data_shards(mesh))
        parts = []
        for shape in sorted(buckets):
            members = buckets[shape]
            for at in range(0, len(members), size):
                ids = np.full(size, -1, np.int64)
                took = members[at:at + size]
                ids[:len(took)] = took
                host = np.zeros((size,) + shape, items[0].dtype)
                extent = np.zeros((size, ragged_axes), np.int32)
                for slot, i in enumerate(took):
                    x = items[i]
                    host[(slot,) + tuple(slice(0, s) for s in x.shape)] = x
                    extent[slot] = x.shape[:ragged_axes]
                parts.append((Chunk(_h2d(fold(host, ragged_axes), size,
                                         sharding), ids, extent, tail=tail),
                              ()))
        return RaggedDataset(parts, len(items))

    # -- the lazy map -----------------------------------------------------
    def with_stage(self, stage: Stage) -> "RaggedDataset":
        return RaggedDataset([(chunk, stages + (stage,))
                              for chunk, stages in self.parts], self.n)

    def chunks(self) -> Iterator[Chunk]:
        """Every chunk with its stages applied, in the dataset's own
        chunk order. The host stays at most ``CHUNKS_IN_FLIGHT`` chunks
        ahead of the device: a dispatched chunk owns its intermediates
        at once, and a thousand of them are more than the device has."""
        flying = collections.deque()
        for chunk, stages in self.parts:
            yield _made(chunk, stages, flying)

    def cache(self) -> "RaggedDataset":
        """Hold what fits: chunks are made in order and kept while they
        are within ``CACHE_SHARE`` of what the device had free when asked;
        the others keep their stages and are made again by each reader."""
        from ..analysis.resources import device_memory_bytes

        budget = CACHE_SHARE * device_memory_bytes(free=True)
        held, parts, flying = 0, [], collections.deque()
        for chunk, stages in self.parts:
            if stages and held <= budget:
                made = _made(chunk, stages, flying)
                held += made.nbytes()
                if held <= budget:
                    parts.append((made, ()))
                    continue
            parts.append((chunk, stages))
        return RaggedDataset(parts, self.n)

    # -- a fixed-shape result a chunk, back in the dataset's order ---------
    def gather(self, per_chunk: Callable[[Chunk], jax.Array]) -> ArrayDataset:
        """``per_chunk(chunk) -> [b, ...]`` of one shape for every chunk
        (a sample of columns, an encoding), or a tuple of such arrays
        (several samples drawn from one making of the chunk): the rows
        of all chunks in the dataset's item order, as an
        ``ArrayDataset``."""
        rows, ids = [], []
        for chunk in self.chunks():
            rows.append(per_chunk(chunk))
            ids.append(chunk.ids)
        ids = np.concatenate(ids)
        slots = np.flatnonzero(ids >= 0)
        order = jnp.asarray(slots[np.argsort(ids[slots], kind="stable")])
        out = jax.tree_util.tree_map(
            lambda *of_chunks: _take_rows(jnp.concatenate(of_chunks), order),
            *rows)
        return ArrayDataset(out, self.n)

    # -- the items themselves, on the host --------------------------------
    def collect(self) -> List[Any]:
        out: List[Any] = [None] * self.n
        for chunk in self.chunks():
            data = unfold(np.asarray(chunk.data), chunk.tail)
            for slot in np.flatnonzero(chunk.real):
                item = data[(slot,) + tuple(
                    slice(0, int(s)) for s in chunk.extent[slot])]
                if chunk.mask is not None:
                    item = item[..., chunk.mask[slot]]
                out[int(chunk.ids[slot])] = item
        return out

    def element(self) -> Optional[jax.ShapeDtypeStruct]:
        """Shape and dtype of the dataset's first item, at its own size,
        from the chunks' metadata alone; None once stages wait to be
        applied."""
        for chunk, stages in self.parts:
            if stages or chunk.mask is not None:
                return None
            for slot in np.flatnonzero(chunk.ids == 0):
                r = chunk.extent.shape[1]
                return jax.ShapeDtypeStruct(
                    tuple(int(s) for s in chunk.extent[slot])
                    + (chunk.tail if r else tuple(chunk.data.shape[1:])),
                    chunk.data.dtype)
        return None

    def map(self, fn: Callable[[Any], Any]) -> HostDataset:
        return HostDataset(self.collect()).map(fn)

    def __len__(self) -> int:
        return self.n


def _made(chunk: Chunk, stages: Tuple[Stage, ...], flying) -> Chunk:
    if not stages:
        return chunk
    if len(flying) >= CHUNKS_IN_FLIGHT:
        with flight_span("chunk", "wait"):
            jax.block_until_ready(flying.popleft())
    for stage in stages:
        chunk = stage(chunk)
    flying.append(chunk.data)
    return chunk


@jax.jit
def _take_rows(x, order):
    return jnp.take(x, order, axis=0)
