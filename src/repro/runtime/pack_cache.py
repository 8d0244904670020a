"""LRU cache for worker tile packs (the block_sparse backend's host metadata).

``pack_worker_tiles`` is pure in its two inputs, both of which are reused
heavily by the runtime: a training/serving loop packs the same BlockELL
against the same plan on every step, and survivor-mask re-derivations
(``plan.with_survivors``) never change the pack at all -- it depends only on
``plan.cols``/``plan.weights``.  The cache key is identity of both objects
(``id(ell), id(plan)``): BlockELL holds mutable ndarrays, so value-hashing
would be both slow (it defeats the point of caching the pack) and unsound
under in-place mutation.  Keying on identity is safe because the cache entry
pins strong references to the keyed objects -- a live key id can never be
recycled while its entry is resident.

The runtime layer owns this cache (not core): core stays a pure library.
The consumer is ``repro.coded.CodedOp`` -- ``op.pack_for(ell)`` (and
therefore ``op.apply(..., a_sparse=ell)``) consults it keyed on the op's
BASE plan, so survivor rebinds of the same op share one pack.  The cache is
a ``PackCache`` object with hit/miss/eviction counters; the module-level
functions operate on the process-wide default instance (``GLOBAL``), whose
``stats()`` snapshot rides along in ``ExecutionReport.decode_stats`` so
multi-job cache sharing is auditable from any report.  A pack's device copy
(``coded_matmul.STAGED``) lives as long as the pack, so a pack this cache
evicts or clears, and that no op still holds, frees it.  ``clear()`` is the
process's cold start: it also empties the staged programs.
"""

from __future__ import annotations

from collections import OrderedDict

from repro.core.coded_matmul import (
    STAGED,
    CodedMatmulPlan,
    WorkerTilePack,
    pack_worker_tiles,
)
from repro.sparse.blocksparse import BlockELL

_MAX_ENTRIES = 16


class PackCache:
    """Identity-keyed LRU of (BlockELL, plan, compute_dtype) -> WorkerTilePack."""

    def __init__(self, max_entries: int = _MAX_ENTRIES):
        self.max_entries = max_entries
        # key -> (ell, plan, pack): the refs pin the ids the key is built from
        self._cache: OrderedDict[
            tuple[int, int, str],
            tuple[BlockELL, CodedMatmulPlan, WorkerTilePack]]
        self._cache = OrderedDict()
        self.hits = 0
        self.misses = 0
        self.evictions = 0

    def get_pack(self, ell: BlockELL, plan: CodedMatmulPlan,
                 compute_dtype: str = "float32") -> WorkerTilePack:
        """The pack for (ell, plan), computed at most once while both are
        alive.  compute_dtype is part of the key: an f32 pack and an int8
        pack of the same operands are different artifacts."""
        key = (id(ell), id(plan), compute_dtype)
        hit = self._cache.get(key)
        if hit is not None:
            self._cache.move_to_end(key)
            self.hits += 1
            return hit[2]
        pack = pack_worker_tiles(ell, plan, compute_dtype=compute_dtype)
        self._cache[key] = (ell, plan, pack)
        if len(self._cache) > self.max_entries:
            self._cache.popitem(last=False)
            self.evictions += 1
        self.misses += 1
        return pack

    def stats(self) -> dict:
        return {"entries": len(self._cache), "hits": self.hits,
                "misses": self.misses, "evictions": self.evictions}

    def clear(self) -> None:
        self._cache.clear()
        self.hits = 0
        self.misses = 0
        self.evictions = 0


#: the process-wide cache every ``CodedOp`` (and so every job) shares
GLOBAL = PackCache()


def get_pack(ell: BlockELL, plan: CodedMatmulPlan,
             compute_dtype: str = "float32") -> WorkerTilePack:
    return GLOBAL.get_pack(ell, plan, compute_dtype=compute_dtype)


def cache_stats() -> dict:
    return GLOBAL.stats()


def clear() -> None:
    """Empty the process-wide cache and the staged programs, so the next
    product packs, uploads and lowers afresh."""
    GLOBAL.clear()
    STAGED.clear()
