"""Tar-archive image loading (reference ``loaders/ImageLoaderUtils.scala``).

Streams tar archives of images, decodes with PIL (the reference uses
ImageIO), and yields labeled image items. Images keep the reference's
convention: float32 (H, W, C) arrays with values in [0, 255].

Ragged image sizes stay host-side (HostDataset); pipelines resize/crop
or extract fixed-size features before moving to device arrays.

Resilience (:mod:`keystone_tpu.resilience`): tar-member reads and image
decodes retry transient failures under a :class:`RetryPolicy`
(``ingest.read`` / ``ingest.decode`` fault-injection sites exercise the
real paths), and undecodable records are routed to a
:class:`Quarantine` — skipped but accounted, with the fit failing
loudly once the bad-record budget is exceeded — instead of being
silently dropped.
"""
from __future__ import annotations

import gzip
import io
import logging
import os
import tarfile
import zlib
from dataclasses import dataclass, field
from typing import Callable, Iterator, List, Optional, Sequence

import numpy as np

from ..parallel.dataset import HostDataset
from ..resilience.faults import inject
from ..resilience.quarantine import Quarantine
from ..resilience.retry import RetryPolicy, default_retry_policy


@dataclass
class LabeledImage:
    """Image + single int label (reference ``utils/images/Image.scala:371-380``)."""

    image: np.ndarray
    label: int
    filename: Optional[str] = None


@dataclass
class MultiLabeledImage:
    """Image + multiple labels (reference ``Image.scala:383-394``)."""

    image: np.ndarray
    labels: List[int] = field(default_factory=list)
    filename: Optional[str] = None


def decode_image(data: bytes,
                 dtype: np.dtype = np.float32) -> Optional[np.ndarray]:
    """JPEG/PNG bytes -> ``dtype`` (H, W, C) in [0, 255]; None if
    undecodable (the reference's loadImage returns Option). The decoder
    works in uint8 underneath, so ``dtype=np.uint8`` is lossless and
    skips the widening copy — the streamed path decodes uint8 and lets
    the device cast (4x fewer host->device wire bytes)."""
    try:
        from PIL import Image as PILImage

        img = PILImage.open(io.BytesIO(data))
        img = img.convert("RGB")
        return np.asarray(img, dtype=dtype)
    except Exception:
        return None


def list_archive_paths(data_path: str, process_shard: bool = True) -> List[str]:
    """All non-directory files under a path (reference
    ``ImageLoaderUtils.getFilePathsRDD`` filters only directories).
    Non-archive files (labels.txt, READMEs) routinely sit alongside the
    archives; :func:`load_tar_files` skips them at open time.

    On a multi-host (SPMD) run each process keeps its
    ``process_index``-strided share of the archives — the analogue of
    HDFS splits landing on different executors (CLUSTER.md "Data").
    ``process_shard=False`` returns the full global listing.
    """
    if os.path.isfile(data_path):
        paths = [data_path]
    else:
        paths = sorted(
            os.path.join(data_path, f)
            for f in os.listdir(data_path)
            if os.path.isfile(os.path.join(data_path, f))
        )
    if process_shard:
        import jax

        pc = jax.process_count()
        if pc > 1:
            # stride over actual archives only — READMEs/labels.txt in
            # the sorted listing must not skew which host gets which
            # share (they'd be skipped at open time anyway)
            archives = [p for p in paths if p.endswith(
                (".tar", ".tar.gz", ".tgz", ".tar.bz2"))]
            mine = archives[jax.process_index()::pc]
            if not mine:
                # an empty share would surface as a collective hang or a
                # shape mismatch far from here — fail at the loader
                raise ValueError(
                    f"host {jax.process_index()}/{pc} has no archives: "
                    f"only {len(archives)} archive(s) under "
                    f"{data_path!r}. Repack the data into >= "
                    "process_count archives, or pass process_shard="
                    "False to load everything on each host."
                )
            paths = mine
    return paths


def _iter_tar_entries(
    tar_path: str, name_prefix: Optional[str] = None,
    retry: Optional[RetryPolicy] = None,
) -> Iterator[tuple]:
    """Yield (entry_name, raw_bytes) for each matching file in a tar —
    the single source of mode selection and entry filtering shared by
    :func:`iter_tar_images` and :func:`load_tar_files`. Per-member
    reads retry transient I/O errors when a ``retry`` policy is given
    (the ``ingest.read`` fault site sits inside the attempt)."""
    mode = "r:gz" if tar_path.endswith(".gz") else "r"
    with tarfile.open(tar_path, mode) as tf:
        for entry in tf:
            if not entry.isfile():
                continue
            if name_prefix and not entry.name.startswith(name_prefix):
                continue

            def read(entry=entry):
                inject("ingest.read",
                       context=f"{tar_path}::{entry.name}")
                fobj = tf.extractfile(entry)
                return None if fobj is None else fobj.read()

            raw = (read() if retry is None
                   else retry.call(read, site="ingest.read"))
            if raw is None:
                continue
            yield entry.name, raw


def _decode_with_retry(raw: bytes, context: str,
                       retry: Optional[RetryPolicy],
                       decode_dtype: np.dtype = np.float32):
    """One record's decode behind the retry policy; the
    ``ingest.decode`` fault site lives inside the attempt so injected
    transient faults exercise the real retry path. Returns None for
    genuinely undecodable bytes (the quarantine case)."""

    def attempt():
        inject("ingest.decode", context=context)
        return decode_image(raw, dtype=decode_dtype)

    if retry is None:
        return attempt()
    return retry.call(attempt, site="ingest.decode")


def iter_tar_images(
    tar_path: str, name_prefix: Optional[str] = None,
    quarantine: Optional[Quarantine] = None,
    retry_policy: Optional[RetryPolicy] = None,
) -> Iterator[tuple]:
    """Yield (entry_name, decoded_image) for each image file in a tar
    (reference ``ImageLoaderUtils.loadFile``) — the serial (unpooled)
    decode path. With a ``quarantine``, undecodable members are
    skipped-but-accounted instead of silently dropped."""
    for name, raw in _iter_tar_entries(tar_path, name_prefix,
                                       retry=retry_policy):
        img = _decode_with_retry(raw, f"{tar_path}::{name}", retry_policy)
        if img is not None:
            if quarantine is not None:
                quarantine.record_ok()
            yield name, img
        elif quarantine is not None:
            quarantine.quarantine(f"{tar_path}::{name}",
                                  "undecodable image bytes")


def _pooled_decoded(
    archive_paths: Sequence[str],
    name_prefix: Optional[str] = None,
    on_archive_end: Optional[Callable[[str, Optional[Exception], int], None]] = None,
    quarantine: Optional[Quarantine] = None,
    retry_policy: Optional[RetryPolicy] = None,
    decode_dtype: np.dtype = np.float32,
) -> Iterator[tuple]:
    """Yield ``(entry_name, decoded_image)`` from every archive, decode
    on a thread pool behind a bounded in-flight window — the ONE home of
    the pool/window/per-archive-recovery machinery shared by
    :func:`iter_decoded_chunks` and :func:`load_tar_files`.

    Order is deterministic (archive order, then entry order). With a
    ``quarantine``, undecodable entries are skipped-but-accounted (and
    the budget enforced); without one they are dropped as before.
    Transient read/decode failures retry under ``retry_policy``. An
    archive that raises mid-stream (non-archive file, truncation) stops
    there but keeps what was read.
    ``on_archive_end(path, error_or_None, n_images_yielded)`` fires per
    archive so callers implement their own skip/warn/raise policy.
    """
    import collections
    from concurrent.futures import ThreadPoolExecutor

    workers = _loader_threads()
    window = 4 * workers
    with ThreadPoolExecutor(workers) as pool:
        pending: collections.deque = collections.deque()

        def drain(n):
            out = []
            while len(pending) > n:
                name, ctx, fut = pending.popleft()
                img = fut.result()  # retry exhaustion re-raises here
                if img is not None:
                    if quarantine is not None:
                        quarantine.record_ok()
                    out.append((name, img))
                elif quarantine is not None:
                    # skipped but accounted — never silently missing
                    # from the counts; raises once the budget is blown
                    quarantine.quarantine(ctx, "undecodable image bytes")
            return out

        for path in archive_paths:
            n_from_archive = 0
            err: Optional[Exception] = None
            try:
                for name, raw in _iter_tar_entries(path, name_prefix,
                                                   retry=retry_policy):
                    ctx = f"{path}::{name}"
                    pending.append((name, ctx, pool.submit(
                        _decode_with_retry, raw, ctx, retry_policy,
                        decode_dtype)))
                    for item in drain(window):
                        n_from_archive += 1
                        yield item
            except (tarfile.ReadError, gzip.BadGzipFile, EOFError,
                    zlib.error) as e:
                err = e
            # archive boundary: drain fully so the per-archive count is
            # exact (a negligible pipeline bubble once per archive)
            for item in drain(0):
                n_from_archive += 1
                yield item
            if on_archive_end is not None:
                on_archive_end(path, err, n_from_archive)


def iter_decoded_chunks(
    archive_paths: Sequence[str],
    chunk_size: int,
    name_prefix: Optional[str] = None,
    quarantine: Optional[Quarantine] = None,
    retry_policy: Optional[RetryPolicy] = None,
    decode_dtype: np.dtype = np.float32,
) -> Iterator[List[tuple]]:
    """Stream archives as chunks of ``chunk_size`` decoded images.

    This is the loader half of the loader/device pipeline: a consumer
    that ``device_put``s + dispatches accelerator work per chunk gets
    decode-compute overlap for free, because JAX dispatch is async and
    the pool keeps decoding the next window while the device runs the
    current chunk (the reference got the same overlap from Spark
    executor threads feeding JNI featurizers,
    ``ImageLoaderUtils.scala:23-94``). Unreadable/truncated archives are
    skipped with a warning, keeping entries read before the error.
    """
    log = logging.getLogger(__name__)

    def on_end(path, err, n):
        if err is not None:
            log.warning(
                "Skipping unreadable/truncated archive %s (%s); kept "
                "%d entries read before the error", path, err, n)

    out: list = []
    for item in _pooled_decoded(archive_paths, name_prefix, on_end,
                                quarantine=quarantine,
                                retry_policy=retry_policy,
                                decode_dtype=decode_dtype):
        out.append(item)
        while len(out) >= chunk_size:
            yield out[:chunk_size]
            del out[:chunk_size]
    while out:
        yield out[:chunk_size]
        del out[:chunk_size]


def _loader_threads() -> int:
    """Decode worker count: the reference got multi-core decode for free
    from Spark executors; here a thread pool does it (PIL releases the
    GIL while decoding). ``KEYSTONE_LOADER_THREADS=1`` forces serial."""
    env = os.environ.get("KEYSTONE_LOADER_THREADS")
    if env:
        return max(1, int(env))
    return min(32, os.cpu_count() or 4)


def stream_tar_images(
    archive_paths: Sequence[str],
    chunk_size: int,
    prepare: Optional[Callable[[List[tuple]], np.ndarray]] = None,
    name_prefix: Optional[str] = None,
    n: Optional[int] = None,
    quarantine: Optional[Quarantine] = None,
    retry_policy: Optional[RetryPolicy] = None,
    decode_dtype: Optional[np.dtype] = None,
    **stream_kw,
):
    """tar archives -> threaded decode pool -> double-buffered device
    stream: the loader half of ``iter_decoded_chunks`` composed with
    ``parallel.streaming.StreamingDataset``, so chunk *i+1*'s decode AND
    upload run behind the prefetch buffer while chunk *i* computes.

    Dtype on the wire: with no ``prepare`` hook, images are decoded
    UINT8 (the decoder's native width — lossless for [0, 255] pixels)
    and shipped uint8 across the host->device link, 1/4 the wire bytes
    of the old f32 staging; consumers still see float32 [0, 255] chunks
    because the stream's ``compute_dtype`` casts on device. A custom
    ``prepare`` keeps the documented float32 decode (its output dtype
    is whatever it returns — return uint8 and the wire stays narrow);
    ``decode_dtype`` overrides the decode width either way, and
    ``wire_dtype=``/``compute_dtype=`` pass through to the stream.

    ``prepare`` maps one decoded chunk (a list of ``(entry_name,
    image)`` pairs) to a stacked fixed-shape host array — the hook for
    resize/crop/grayscale of ragged archive images; the default stacks
    as-is (uniform-size archives). ``n`` is the total image count when
    known (streams from unindexed tars leave it None; a completed pass
    pins it).

    Resilience defaults: reads/decodes retry transients under
    ``retry_policy`` (shared default policy when None) and corrupt
    members land in ``quarantine`` (a fresh default-budget
    :class:`Quarantine` when None) — attached to the returned stream as
    ``.quarantine`` so callers can pass it to ``fit_streaming`` or
    inspect the manifest.
    """
    from ..parallel.streaming import StreamingDataset

    if prepare is None:
        if decode_dtype is None:
            # uint8 on the wire, f32 on device: the default pipeline's
            # consumers keep seeing float32 [0, 255] images while the
            # transfer moves 1/4 the bytes
            decode_dtype = np.uint8
            stream_kw.setdefault("compute_dtype", np.float32)

        def prepare(batch):
            return np.stack([img for _, img in batch])
    elif decode_dtype is None:
        decode_dtype = np.float32  # documented prepare() input contract

    tag = f"tar:{archive_paths[0]}" if archive_paths else "tar"
    if quarantine is None:
        quarantine = Quarantine(label=tag)
    if retry_policy is None:
        retry_policy = default_retry_policy()

    def factory():
        for batch in iter_decoded_chunks(
                archive_paths, chunk_size, name_prefix,
                quarantine=quarantine, retry_policy=retry_policy,
                decode_dtype=decode_dtype):
            yield prepare(batch)

    return StreamingDataset.from_chunks(
        factory, chunk_size, n=n, tag=tag, retry_policy=retry_policy,
        quarantine=quarantine, **stream_kw)


def stream_tar_shards(data_path: str, chunk_size: int,
                      **stream_kw):
    """Per-host SHARD-LOCAL tar streaming: this process's
    ``process_index``-strided share of the archives under ``data_path``
    (:func:`list_archive_paths`) fed through :func:`stream_tar_images`
    on the host-local mesh — the ingest half of the elastic multi-host
    streamed fit (``parallel.distributed``; each host decodes only its
    own shards, carries tree-reduce at finalize).

    The returned stream is tagged ``tarshard:h<process>/<world>`` and
    marked ``process_sharded`` (the static analyzer reports the flag,
    and ``fit_streaming``'s distributed mode is the only fit that
    understands a shard-local ``n``: the stream's row count is THIS
    host's share, not the dataset's). Keyword arguments pass through to
    :func:`stream_tar_images` (``prepare=``, ``wire_dtype=``,
    ``quarantine=``, ...); the mesh defaults to
    :func:`~keystone_tpu.parallel.mesh.local_mesh` so staging never
    targets another host's devices. Single-process this degrades to a
    plain full-listing tar stream.

    An empty share raises at listing time
    (:func:`list_archive_paths`): repack the data into at least
    ``process_count`` archives — silent empty hosts would surface as a
    collective hang far from the cause.
    """
    from ..parallel.distributed import process_count, process_index
    from ..parallel.mesh import local_mesh

    paths = list_archive_paths(data_path, process_shard=True)
    pid, nproc = process_index(), process_count()
    if "mesh" not in stream_kw and nproc > 1:
        stream_kw["mesh"] = local_mesh()
    stream = stream_tar_images(paths, chunk_size, **stream_kw)
    stream.tag = f"tarshard:h{pid}/{nproc}"
    #: consumed by analysis.spec.dataset_spec: the stream's n (when it
    #: pins) is a PER-HOST share, and the non-streamable-fit family
    #: reports the sharded provenance in its diagnostics
    stream.process_sharded = True
    stream.shard_archives = list(paths)
    return stream


def load_tar_files(
    archive_paths: Sequence[str],
    labels_map: Callable[[str], object],
    image_builder: Callable[[np.ndarray, object, str], object],
    name_prefix: Optional[str] = None,
    quarantine: Optional[Quarantine] = None,
    retry_policy: Optional[RetryPolicy] = None,
    decode_dtype: np.dtype = np.float32,
) -> HostDataset:
    """Load every image from every archive, applying the label mapping
    (reference ``ImageLoaderUtils.loadFiles``). ``decode_dtype=np.uint8``
    keeps the decoder's own bytes (lossless, a quarter of the memory).

    Decode machinery (thread pool, bounded window, deterministic order,
    per-archive recovery) is shared with :func:`iter_decoded_chunks` via
    :func:`_pooled_decoded`; this wrapper adds the label mapping plus
    the skip-vs-truncated warning policy and the nothing-opened error."""
    log = logging.getLogger(__name__)
    items: list = []
    opened_any = False

    def on_end(path, err, n):
        nonlocal opened_any
        if err is None:
            opened_any = True  # readable archive, possibly zero images
        elif n == 0:
            # Failed before yielding anything: not a tar (labels.txt,
            # README, checksums) — skip, matching the reference where
            # non-archives simply yield no image records.
            log.warning("Skipping non-archive file %s", path)
        else:
            # Truncated/corrupt mid-stream: keep what was read, but say
            # so — silent partial data is worse than a warning.
            log.warning(
                "Archive %s truncated/corrupt (%s); kept %d items "
                "from it", path, err, n)
            opened_any = True

    for name, img in _pooled_decoded(archive_paths, name_prefix, on_end,
                                     quarantine=quarantine,
                                     retry_policy=retry_policy,
                                     decode_dtype=decode_dtype):
        # only a decoded image proves the path held real data;
        # None-decodes must not suppress the final ReadError
        opened_any = True
        items.append(image_builder(img, labels_map(name), name))
    if archive_paths and not opened_any:
        raise tarfile.ReadError(
            f"None of {len(archive_paths)} file(s) under the data path could be "
            f"opened as tar archives (first: {archive_paths[0]})"
        )
    return HostDataset(items)
