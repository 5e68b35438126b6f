"""Transformer: the per-item pure-function pipeline stage.

Mirrors ``workflow/Transformer.scala`` + ``workflow/graph/Transformer.scala``:
a Transformer is simultaneously an operator (executable node) and a
one-node Pipeline. The user implements per-item ``apply`` with jnp ops;
batch execution is ``jit(vmap(apply))`` over the mesh-sharded batch —
the TPU-native analogue of the reference's default
``in.map(apply)`` / per-partition GEMM batching (Transformer.scala:27,35).
Nodes whose batch form isn't a vmap (e.g. whole-batch GEMM with masking)
override ``apply_dataset``.
"""
from __future__ import annotations

import dataclasses
import math
from typing import Any, Callable, Sequence

import jax
import numpy as np

from ..parallel.dataset import ArrayDataset, Dataset, HostDataset, is_streaming
from ..parallel.ragged import RaggedDataset
from .operators import TransformerOperator
from .pipeline import Chainable, Pipeline
from .graph import Graph


#: (tag, eq_key) -> jitted callable: the per-item vmap program
#: ("batched") plus any bespoke whole-batch programs nodes register via
#: ``_cached_jit``. Entries keep node instances (hence their params)
#: alive, so the memo is a bounded LRU (``utils.lru.LruMemo``):
#: content-keyed entries (fitted weights baked in as constants) get
#: zero reuse across a hyperparameter sweep and would otherwise pin
#: host+HBM memory for the process lifetime (ADVICE r2).
#: ``clear_jit_cache`` is the hard reset for long-lived processes.
from ..utils.lru import LruMemo  # noqa: E402

_JIT_CACHE = LruMemo()


def clear_jit_cache() -> None:
    """Drop all globally memoized jitted programs (long-lived processes;
    see also ``parallel.dataset.clear_vmap_cache``)."""
    _JIT_CACHE.clear()


def _is_host_scalar(leaf):
    # np.generic AND 0-d np.ndarray (np.array(x)): both are 0-d host
    # values a node may legitimately store as config, and both would
    # otherwise ride into the hot shared program as retained ndarrays
    # (ADVICE r3 + r4).
    return isinstance(leaf, np.generic) or (
        isinstance(leaf, np.ndarray) and leaf.ndim == 0)


def config_shim(node: "Transformer") -> "Transformer":
    """Array-free clone for closure capture by struct-keyed cached
    programs: the cached entry is hot (shared by every refit by design),
    so closing over the live node would pin the FIRST refit's fitted
    arrays in host+HBM memory for the process lifetime. The shim keeps
    only config attributes — exactly what ``apply_with_params`` may read
    from self per its contract; an implementation that violates the
    contract now fails loudly (missing attribute) instead of silently
    sharing stale weights."""
    shim = object.__new__(type(node))
    for k, v in node.__dict__.items():
        if k.startswith("_jit_") or k == "_eq_key_val":
            continue
        leaves = jax.tree_util.tree_leaves(v)
        if any(getattr(leaf, "ndim", 0) > 0 or isinstance(leaf, Transformer)
               or (isinstance(leaf, jax.Array) and leaf.ndim == 0)
               for leaf in leaves):
            # Fitted arrays / nested nodes are not config. 0-d device
            # arrays count as fitted too: they come out of jitted
            # computation, and keeping one would bake the first refit's
            # value into the hot shared program — the loud AttributeError
            # is the correct failure for a contract violation.
            continue
        if any(_is_host_scalar(leaf) for leaf in leaves):
            # 0-d HOST numpy scalars ARE config (e.g. np.float32 alpha
            # from a constructor); dropping them breaks apply_with_params
            # at trace time far from the construction site (ADVICE r3).
            # Coerce to Python scalars so the shim stays array-free.
            v = jax.tree_util.tree_map(
                lambda leaf: leaf.item() if _is_host_scalar(leaf) else leaf, v)
        shim.__dict__[k] = v
    return shim


def struct_cached_jit(key: Any, builder: Callable[[], Callable]) -> Callable:
    """Globally memoized ``jax.jit(builder())`` under an explicit key —
    the structure-keyed sibling of ``Transformer._cached_jit`` (which
    keys on content-bearing eq_keys). Used by fusion to share ONE
    compiled program across refits whose fitted params ride as runtime
    arguments. Programs are compile-observatory sites: the memo stores
    the WATCHED wrapper, so every refit shares one site and a refit
    that recompiles shows up as a classified compile record instead of
    silent wall time."""
    from ..observability.compilelog import watch_jit

    fn = _JIT_CACHE.get(key)
    if fn is None:
        name = (key[0] if isinstance(key, tuple) and key
                and isinstance(key[0], str) else "struct_jit")
        fn = watch_jit(jax.jit(builder()), name=name)
        _JIT_CACHE.put(key, fn)
    return fn


class Transformer(TransformerOperator, Chainable):
    #: Set True on subclasses whose ``apply_dataset`` override is merely
    #: an optimized equivalent of the default per-item map (so map-chain
    #: fusion may still fuse through them).
    fusion_safe = False
    #: Set True on subclasses whose ``apply`` leaves zero padding zero
    #: and an item's real part where it was (pointwise maps, a product
    #: from the left): a chunk of padded items of different sizes
    #: (``parallel.ragged``) is then mapped like a batch of whole ones.
    keeps_padding = False
    #: Set True on subclasses whose ``apply`` maps every column (entry of
    #: the last axis) of an item by itself and keeps their number and
    #: order: ``apply(x)[..., j]`` is a function of ``x[..., j]`` alone
    #: (a product from the left, a cast, the identity). Keeping some
    #: columns by index then commutes with the node, and the optimizer
    #: may draw a column sample in front of it
    #: (``optimizer/column_samples.py``).
    maps_columns = False

    def apply(self, x: Any) -> Any:
        """Per-item transform (pure, jax-traceable unless host-only)."""
        raise NotImplementedError

    # -- fitted-param protocol (content-free compiled programs) -----------
    def apply_params(self) -> Any:
        """Pytree of FITTED arrays consumed by ``apply_with_params``, or
        None for stateless/config-only nodes (whose arrays may bake into
        programs as constants — config is stable across refits). When
        not None, jitted programs built over ``apply_with_params`` take
        the params as runtime arguments, so ONE compile serves every
        refit (in-process and via the persistent compilation cache)."""
        return None

    def apply_with_params(self, params: Any, x: Any) -> Any:
        """``apply(x)`` reading fitted arrays from ``params`` (the same
        pytree ``apply_params`` returns). Must not read array attributes
        from ``self`` when ``apply_params`` is not None."""
        return self.apply(x)

    def struct_key(self) -> Any:
        """Content-free structural identity: equal struct_keys MUST
        imply identical ``apply_with_params`` behavior given equal
        params. Default = the content-bearing eq_key, which is always
        sound (equal content implies equal behavior)."""
        return self._cached_eq_key()

    def chunk_stage(self):
        """This node as a stage over padded chunks of items whose sizes
        differ (``parallel.ragged.Chunk -> Chunk``), or None: the items
        are then handed over one by one, each cut to its own size. A
        node that ``keeps_padding`` is mapped over the chunk's items as
        over a batch, in ONE program that unfolds the chunk's trailing
        axes, applies the node and folds them again (see
        ``Chunk.tail``); fitted arrays ride as arguments."""
        if not self.keeps_padding:
            return None
        from ..parallel import ragged

        params = self.apply_params()
        node = self if params is None else config_shim(self)
        try:
            key = ("chunk", self._cached_eq_key() if params is None
                   else self.struct_key())
            hash(key)
        except TypeError:
            key = None
        programs, tails = {}, {}

        def stage(chunk):
            r, tail = chunk.extent.shape[1], chunk.tail

            def builder():
                def raw(p, X):
                    return ragged.fold(jax.vmap(
                        lambda x: node.apply_with_params(p, x))(
                            ragged.unfold(X, tail)), r)

                return raw

            if key is not None:
                fn = struct_cached_jit(key + (tail, r), builder)
            else:   # an unhashable node: one program a stage, not a call
                fn = programs.setdefault((tail, r), jax.jit(builder()))
            at = (tail, tuple(chunk.data.shape[1:]), chunk.data.dtype)
            if at not in tails:     # the output's own trailing axes
                item = jax.eval_shape(
                    lambda p, x: node.apply_with_params(p, x), params,
                    jax.ShapeDtypeStruct(
                        chunk.data.shape[1:-1] + (
                            chunk.data.shape[-1] // math.prod(tail),) + tail,
                        chunk.data.dtype))
                tails[at] = tuple(item.shape[r:]) if r else ()
            return dataclasses.replace(
                chunk, data=fn(params, chunk.data), tail=tails[at])

        return stage

    def apply_dataset(self, ds: Dataset) -> Dataset:
        if isinstance(ds, ArrayDataset):
            return ds.map_batch(self._batched())
        if isinstance(ds, RaggedDataset):
            stage = self.chunk_stage()
            return ds.map(self.apply) if stage is None \
                else ds.with_stage(stage)
        if is_streaming(ds):
            # per-chunk apply: every chunk shares one padded shape, so
            # the chain compiles once (fitted params ride as jit
            # arguments via the usual structure-keyed programs) and
            # chunk i+1's ingest overlaps chunk i's compute
            return ds.map_chunks(self.apply_dataset)
        return ds.map(self.apply)

    def _batched(self) -> Callable:
        """jit(vmap(apply)), cached per instance AND globally by eq_key.

        The global memo gives equal-config node instances built in later
        pipelines the SAME jitted callable, so refitting or rebuilding a
        pipeline reuses the warm XLA executable instead of recompiling
        (eq_key is the CSE equality — same key means same semantics, so
        sharing the compiled program is sound by construction).

        Nodes implementing the fitted-param protocol route through a
        STRUCTURE-keyed program with their params as runtime arguments
        instead: one compile serves every refit, even with new fitted
        content (the content-bearing eq_key path would bake the arrays
        as program constants and recompile per refit)."""
        params = self.apply_params()
        if params is not None:
            try:
                key = ("param_batched", self.struct_key())
                hash(key)
            except TypeError:
                key = None
            if key is not None:
                node = config_shim(self)  # must not pin fitted arrays

                def builder():
                    # contract: apply_with_params reads NO array attrs
                    # from the closed-over shim — only config (which the
                    # struct_key covers), so sharing across equal keys
                    # is sound
                    def raw(p, X):
                        return jax.vmap(
                            lambda x: node.apply_with_params(p, x))(X)

                    return raw

                fn = struct_cached_jit(key, builder)
                return lambda X: fn(params, X)
        return self._cached_jit(
            "batched", lambda: jax.vmap(self.apply))

    def _cached_jit(self, tag: str, builder: Callable[[], Callable]) -> Callable:
        """jit(builder()), cached per instance and globally by
        (tag, eq_key) — the mechanism behind ``_batched``, reusable by
        nodes with bespoke whole-batch programs (e.g. RandomPatcher) so
        their executables also survive pipeline rebuilds."""
        attr = "_jit_" + tag
        fn = self.__dict__.get(attr)
        if fn is None:
            from ..observability.compilelog import watch_jit

            try:
                key = (tag, self._cached_eq_key())
                fn = _JIT_CACHE.get(key)
            except TypeError:  # unhashable eq_key: per-instance only
                key = None
                fn = None
            if fn is None:
                # observed site named by node class + tag: a
                # per-instance-only program (unhashable eq_key) that
                # recompiles per refit is exactly what the runtime
                # recompile detector exists to surface
                fn = watch_jit(jax.jit(builder()),
                               name=f"{type(self).__name__}.{tag}")
                if key is not None:
                    _JIT_CACHE.put(key, fn)
            self.__dict__[attr] = fn
        return fn

    # -- operator plumbing -------------------------------------------------
    def single_transform(self, inputs: Sequence[Any]) -> Any:
        return self.apply(inputs[0])

    def batch_transform(self, inputs: Sequence[Dataset]) -> Dataset:
        return self.apply_dataset(inputs[0])

    def to_pipeline(self) -> Pipeline:
        return Pipeline(*Graph.single(self))

    # jitted callables must not leak into pickles
    def __getstate__(self):
        state = {k: v for k, v in self.__dict__.items()
                 if not k.startswith("_jit_")}
        state.pop("_eq_key_val", None)
        return state


class LambdaTransformer(Transformer):
    """Function lift (reference ``Transformer.apply(f)``,
    Transformer.scala:55-58)."""

    def __init__(self, fn: Callable[[Any], Any], name: str = "Lambda"):
        self.fn = fn
        self.name = name

    def eq_key(self):
        return (LambdaTransformer, self.fn, self.name)

    def apply(self, x: Any) -> Any:
        return self.fn(x)

    def label(self) -> str:
        return self.name


def transformer(fn: Callable[[Any], Any]) -> LambdaTransformer:
    """Decorator/lift: ``transformer(lambda x: x * 2)``."""
    return LambdaTransformer(fn, getattr(fn, "__name__", "Lambda"))


class HostTransformer(Transformer):
    """A transformer whose apply runs host-side Python (tokenizers, IO).

    Batch path maps over items of a HostDataset; ArrayDatasets are
    collected to host first.
    """

    def apply_dataset(self, ds: Dataset) -> Dataset:
        if is_streaming(ds):
            raise TypeError(
                f"host stage {self.label()!r} cannot consume a "
                "StreamingDataset: chunks are device-resident and a host "
                "stage would sync every chunk back. Run host stages "
                "before building the stream, or materialize() it.")
        if isinstance(ds, ArrayDataset):
            ds = HostDataset(ds.collect())
        return ds.map(self.apply)

    def abstract_single(self, elements: Sequence[Any]) -> Any:
        """Host stages run arbitrary Python — not shape-propagatable via
        eval_shape. Subclasses with known output specs (Sparsify,
        Densify-style codecs) override this."""
        from ..analysis.spec import Unknown

        return Unknown(f"host stage {self.label()}")

    def abstract_eval(self, dep_specs: Sequence[Any]) -> Any:
        from ..analysis.spec import DatasetSpec

        out = super().abstract_eval(dep_specs)
        if isinstance(out, DatasetSpec):
            # the batch path collects to host before mapping; streaming
            # is preserved so the host-stage-on-stream lint (and any
            # downstream streaming diagnostics) see the true provenance
            # — at runtime this combination raises in apply_dataset
            return DatasetSpec(out.element, n=out.n, host=True,
                               sparsity=out.sparsity,
                               streaming=out.streaming,
                               sharded=out.sharded)
        return out
