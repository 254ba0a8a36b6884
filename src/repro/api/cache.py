"""KVCacheManager — session memory as a managed surface.

The decode state's KV cache stops being a raw pytree the session threads
around and becomes an object with an owner: a ``KVCacheManager`` builds the
cache, admits rows into it, and retires them out of it. Two implementations
share the surface:

* ``DenseKVCache`` — the historical slot-masked ``(B, max_seq, ...)`` layout,
  preserved bit-for-bit. It is the numerics reference the paged layout is
  property-tested against.
* ``PagedKVCache`` — vLLM-style paged memory adapted to JAX's static shapes:
  every attention entry stores K/V (and int8 scales under ``kv_quant``) in a
  per-layer *page pool* leaf ``(num_pages + 1, page_size, ...)``; one
  ``page_table (B, pages_per_row)`` int32, shared by all layers, maps each
  row's logical pages to physical ids (the ``+1`` page is a write-only trash
  page that unallocated/retired rows alias). A host-side free-page list backs
  admission control (``can_admit``), and per-row compaction
  (``retire_row``) frees a finished row's pages and zeroes its logical
  length, so a long-idle slot stops paying attention span the moment it
  retires instead of dragging its stale context through every tick.

Recurrent/SSD entries (per-row O(1) states, no sequence axis) are never
paged; hybrid and SSM architectures get paged attention entries next to
dense recurrent ones, so the manager works for every arch in the zoo.

Allocation is deliberately reservation-based: a row's full
``pages_per_row`` worth of pages is claimed at admission and returned at
retirement. The jitted step functions never allocate — they only index
through an already-valid table (``repro.core.paged``), which keeps them pure
and keeps paged decode bit-identical to the dense reference.
"""
from __future__ import annotations

from dataclasses import dataclass, replace
from functools import partial
from typing import Any, List, Optional, Union

import jax
import jax.numpy as jnp
import numpy as np

from repro.config import ATTN, LOCAL_ATTN
from repro.core import paged as paged_lib
from repro.runtime import faultinject


@dataclass(frozen=True)
class CacheSpec:
    """How a session's KV memory is laid out.

    kind: "dense" (slot-masked reference) | "paged" (page pool + table).
    page_size: tokens per page (paged only); ``ServeConfig.page_size``
        validates the serving default at config construction.
    num_pages: physical pages per layer pool. None = ``batch *
        pages_per_row`` (capacity parity with the dense layout); smaller
        values oversubscribe the pool and make ``can_admit`` a real gate.
    """
    kind: str = "dense"
    page_size: int = 128
    num_pages: Optional[int] = None

    def __post_init__(self) -> None:
        if self.kind not in ("dense", "paged"):
            raise ValueError(
                f"CacheSpec.kind must be 'dense' or 'paged', got {self.kind!r}")
        if self.page_size <= 0:
            raise ValueError(
                f"CacheSpec.page_size must be > 0, got {self.page_size}")

    @staticmethod
    def resolve(spec: Union[None, str, "CacheSpec"],
                serve_cfg=None) -> "CacheSpec":
        """None -> dense (back-compat); "dense"/"paged" -> spec with the
        run's ``ServeConfig.page_size``; a CacheSpec passes through."""
        if isinstance(spec, CacheSpec):
            return spec
        if spec is None:
            spec = "dense"
        page = serve_cfg.page_size if serve_cfg is not None else 128
        return CacheSpec(kind=spec, page_size=page)


@partial(jax.jit, static_argnames=("batch",), donate_argnums=(0,))
def insert_row_pytree(big, small, row, batch: int):
    """Insert batch-1 pytree ``small`` as row ``row`` of batched ``big``.

    The historical ``DecodeSession`` row-insert: the batch axis of each leaf
    is found by matching ``batch`` vs 1 dims; batch-independent leaves (PRNG
    key) pass through. Lives here so both the session (non-cache state) and
    ``DenseKVCache.insert_row`` share one definition. ``big`` is donated and
    updated in place (an eager update would copy the whole batched cache);
    callers must drop their reference to it.
    """
    def one(b, s):
        axis = None
        for i, (db, ds) in enumerate(zip(b.shape, s.shape)):
            if db == batch and ds == 1:
                axis = i
                break
        if axis is None and b.shape == s.shape:
            return b  # batch-independent leaf (e.g. PRNG key): keep
        assert axis is not None, f"no batch axis: {b.shape} vs {s.shape}"
        idx = [slice(None)] * b.ndim
        idx[axis] = row
        src = jnp.squeeze(s, axis=axis)
        return b.at[tuple(idx)].set(src.astype(b.dtype))
    return jax.tree_util.tree_map(one, big, small)


class KVCacheManager:
    """Owner of one session's KV memory: layout, admission, compaction."""

    kind = "base"

    def __init__(self, model, batch: int, seq_len: int, spec: CacheSpec):
        self.model = model
        self.batch = batch
        self.seq_len = seq_len          # requested logical capacity per row
        self.spec = spec

    # ----- layout -----
    def empty_cache(self) -> Any:
        raise NotImplementedError

    def from_prefill(self, dense_cache: Any) -> Any:
        """Adopt a whole-batch dense prefill cache (``model.prefill``'s
        output) into this manager's layout."""
        raise NotImplementedError

    # ----- admission / retirement -----
    def insert_row(self, cache: Any, row: int, row_cache: Any) -> Any:
        """Admit a batch-1 dense cache (one prefilled request) into ``row``."""
        raise NotImplementedError

    def retire_row(self, cache: Any, row: int) -> Any:
        """Per-row compaction: drop the row's logical length (and, when
        paged, return its pages to the free list) so the idle slot's
        attention span collapses to zero."""
        raise NotImplementedError

    def can_admit(self, prompt_len: int = 0) -> bool:
        """Admission gate. The ``pool_exhausted`` fault-injection site lives
        here so a seeded schedule can simulate a dry pool on any layout —
        driving the serving engine's victim-eviction path deterministically
        (repro.runtime.faultinject)."""
        if faultinject.fire("pool_exhausted"):
            return False
        return self._can_admit(prompt_len)

    def _can_admit(self, prompt_len: int = 0) -> bool:
        return True

    # ----- checkpoint / restore (DESIGN.md §7) -----
    def export_state(self) -> dict:
        """Host-side allocator state for a session snapshot (the device
        arrays — pools, page table — travel in the DecodeState pytree)."""
        return {"kind": self.kind}

    def import_state(self, st: dict) -> None:
        """Adopt a snapshot's allocator state. The manager must have been
        built with the same layout the snapshot was taken under."""
        if st.get("kind") != self.kind:
            raise ValueError(
                f"cache snapshot is {st.get('kind')!r}, manager is "
                f"{self.kind!r} — restore needs the same cache layout")

    # ----- mesh layout (DESIGN.md §9) -----
    def partition_specs(self, cache: Any, mesh, policy: str = "tp_dp") -> Any:
        """PartitionSpec tree describing how this manager's cache pytree
        lays out on ``mesh`` (tensor-parallel serving): KV sharded on the
        head dim over 'model', bookkeeping replicated. The default delegates
        to the Megatron-role cache rules (``sharding/policies.cache_specs``
        with the sequence split off — decode scatters positions
        dynamically)."""
        from repro.sharding import policies as pol
        return pol.cache_specs(self.model, mesh, policy, cache,
                               kv_seq_shard=False)

    # ----- introspection (tests / benchmarks) -----
    def row_span(self, cache: Any, row: int) -> int:
        """Attention span the row currently pays (valid cache positions)."""
        return int(np.asarray(cache["len"])[row])

    def row_pages(self, row: int) -> int:
        return 0

    @property
    def free_pages(self) -> int:
        return 0

    @property
    def capacity(self) -> int:
        return self.seq_len

    def _attention_units(self):
        for seg, (unit, _reps) in enumerate(self.model.segments):
            for i, kind in enumerate(unit):
                yield seg, f"u{i}", kind in (ATTN, LOCAL_ATTN)


class DenseKVCache(KVCacheManager):
    """Bit-identical reference: the historical slot-masked dense layout."""

    kind = "dense"

    def empty_cache(self) -> Any:
        return self.model.empty_cache(self.batch, self.seq_len)

    def from_prefill(self, dense_cache: Any) -> Any:
        return dense_cache

    def insert_row(self, cache: Any, row: int, row_cache: Any) -> Any:
        return insert_row_pytree(cache, row_cache, row, self.batch)

    def retire_row(self, cache: Any, row: int) -> Any:
        return dict(cache, len=cache["len"].at[row].set(0))


class PagedKVCache(KVCacheManager):
    """Paged layout: per-layer page pools + one shared page table."""

    kind = "paged"

    def __init__(self, model, batch: int, seq_len: int, spec: CacheSpec):
        super().__init__(model, batch, seq_len, spec)
        ps = spec.page_size
        self.page_size = ps
        self.pages_per_row = -(-seq_len // ps)
        self.num_pages = (spec.num_pages if spec.num_pages is not None
                          else batch * self.pages_per_row)
        if self.num_pages < self.pages_per_row:
            raise ValueError(
                f"paged cache pool of {self.num_pages} pages cannot hold even "
                f"one row ({self.pages_per_row} pages/row)")
        self.trash_page = self.num_pages        # extra write-only page
        self._free: List[int] = list(range(self.num_pages - 1, -1, -1))
        self._row_pages: List[List[int]] = [[] for _ in range(batch)]

    @property
    def capacity(self) -> int:
        """Logical per-row capacity (rounded up to whole pages)."""
        return self.pages_per_row * self.page_size

    @property
    def free_pages(self) -> int:
        return len(self._free)

    def row_pages(self, row: int) -> int:
        return len(self._row_pages[row])

    def _can_admit(self, prompt_len: int = 0) -> bool:
        return len(self._free) >= self.pages_per_row

    def export_state(self) -> dict:
        return {"kind": self.kind, "page_size": self.page_size,
                "num_pages": self.num_pages,
                "free": [int(p) for p in self._free],
                "row_pages": [[int(p) for p in r] for r in self._row_pages]}

    def import_state(self, st: dict) -> None:
        super().import_state(st)
        if (st["page_size"] != self.page_size
                or st["num_pages"] != self.num_pages):
            raise ValueError(
                f"paged snapshot geometry (page_size={st['page_size']}, "
                f"num_pages={st['num_pages']}) does not match manager "
                f"(page_size={self.page_size}, num_pages={self.num_pages})")
        self._free = [int(p) for p in st["free"]]
        self._row_pages = [[int(p) for p in r] for r in st["row_pages"]]

    # ----- layout -----
    def empty_cache(self) -> Any:
        from repro.models.model import _empty_cache_entry
        m, cfg = self.model, self.model.cfg
        from repro.models import common
        dtype = common.dtype_of(cfg.dtype)
        segs = []
        for unit, reps in m.segments:
            entry = {}
            for i, kind in enumerate(unit):
                if kind in (ATTN, LOCAL_ATTN):
                    # pool leaves: (num_pages + 1, page_size, ...) — the last
                    # page is the trash page unallocated rows alias
                    one = _empty_cache_entry(cfg, kind, self.num_pages + 1,
                                             self.page_size, dtype,
                                             m.flags.kv_quant)
                else:
                    one = _empty_cache_entry(cfg, kind, self.batch,
                                             self.page_size, dtype,
                                             m.flags.kv_quant)
                entry[f"u{i}"] = jax.tree_util.tree_map(
                    lambda x: jnp.broadcast_to(x[None], (reps,) + x.shape),
                    one)
            segs.append(entry)
        table = jnp.full((self.batch, self.pages_per_row), self.trash_page,
                         jnp.int32)
        return {"segments": segs,
                "len": jnp.zeros((self.batch,), jnp.int32),
                "page_table": table}

    def partition_specs(self, cache: Any, mesh, policy: str = "tp_dp") -> Any:
        """Head-sharded paged layout: attention pool leaves shard their
        KV-head dim over 'model' (``core.paged.pool_partition_dims`` — page
        ids index the leading dims, so pages/page_size stay whole); the page
        table, lengths, and non-attention entries are replicated — every
        shard resolves the same page indirection."""
        from jax.sharding import PartitionSpec as P
        from repro.core import paged as paged_lib
        M = int(dict(mesh.shape).get("model", 1))
        rep = lambda x: P(*([None] * np.ndim(x)))  # noqa: E731
        attn = {(seg, key): is_attn
                for seg, key, is_attn in self._attention_units()}
        segs = []
        for seg, entry in enumerate(cache["segments"]):
            out = {}
            for key, sub in entry.items():
                if attn.get((seg, key)):
                    out[key] = jax.tree_util.tree_map(
                        lambda x: P(*paged_lib.pool_partition_dims(
                            np.shape(x), M)), sub)
                else:
                    out[key] = jax.tree_util.tree_map(rep, sub)
            segs.append(out)
        return {"segments": segs, "len": P(), "page_table": P()}

    def _alloc_row(self, row: int) -> np.ndarray:
        if not self._row_pages[row]:
            if len(self._free) < self.pages_per_row:
                raise RuntimeError(
                    f"paged KV pool exhausted: row {row} needs "
                    f"{self.pages_per_row} pages, {len(self._free)} free "
                    "(gate admission with can_admit())")
            self._row_pages[row] = [self._free.pop()
                                    for _ in range(self.pages_per_row)]
        return np.asarray(self._row_pages[row], np.int32)

    @staticmethod
    @partial(jax.jit, donate_argnums=(0,))
    def _scatter_entry(pool_entry, dense_entry, slots):
        """Write a dense cache entry's first ``len(slots)`` logical slots
        into the pool. pool leaves: (reps, NP, ps, ...); dense leaves:
        (reps, B?, S, ...) pre-indexed to match ``slots``'s batch shape.
        The pool is donated and written in place — an eager scatter would
        copy the whole pool, which a full-size model's memory cannot hold
        next to the live one."""
        def one(pool, src):
            flat = pool.reshape((pool.shape[0], pool.shape[1] * pool.shape[2])
                                + pool.shape[3:])
            flat = flat.at[:, slots].set(src.astype(pool.dtype))
            return flat.reshape(pool.shape)
        return jax.tree_util.tree_map(one, pool_entry, dense_entry)

    def from_prefill(self, dense_cache: Any) -> Any:
        B = self.batch
        table_np = np.stack([self._alloc_row(r) for r in range(B)])
        table = jnp.asarray(table_np)
        cache = self.empty_cache()
        segs = [dict(e) for e in cache["segments"]]
        for seg, key, is_attn in self._attention_units():
            dense_entry = dense_cache["segments"][seg][key]
            if not is_attn:
                segs[seg][key] = dense_entry      # per-row state: unchanged
                continue
            S = jax.tree_util.tree_leaves(dense_entry)[0].shape[2]
            slots = paged_lib.view_slots(table, self.page_size)[:, :S]  # (B,S)
            segs[seg][key] = self._scatter_entry(
                cache["segments"][seg][key], dense_entry, slots)
        return {"segments": segs, "len": dense_cache["len"],
                "page_table": table}

    def insert_row(self, cache: Any, row: int, row_cache: Any) -> Any:
        pages = self._alloc_row(row)
        table = cache["page_table"].at[row].set(jnp.asarray(pages))
        row_slots = (pages[:, None] * self.page_size
                     + np.arange(self.page_size)[None, :]).reshape(-1)
        segs = [dict(e) for e in cache["segments"]]
        for seg, key, is_attn in self._attention_units():
            src = row_cache["segments"][seg][key]
            if not is_attn:
                segs[seg][key] = insert_row_pytree(
                    cache["segments"][seg][key], src, row, self.batch)
                continue
            S = jax.tree_util.tree_leaves(src)[0].shape[2]
            slots = jnp.asarray(row_slots[:S])                   # (S,)
            src_rows = jax.tree_util.tree_map(lambda x: x[:, 0], src)
            segs[seg][key] = self._scatter_entry(
                cache["segments"][seg][key], src_rows, slots)
        length = cache["len"].at[row].set(row_cache["len"][0])
        return {"segments": segs, "len": length, "page_table": table}

    def retire_row(self, cache: Any, row: int) -> Any:
        self._free.extend(self._row_pages[row])
        self._row_pages[row] = []
        table = cache["page_table"].at[row].set(self.trash_page)
        return dict(cache, len=cache["len"].at[row].set(0),
                    page_table=table)


def make_cache_manager(model, batch: int, seq_len: int,
                       spec: Union[None, str, CacheSpec]) -> KVCacheManager:
    spec = CacheSpec.resolve(spec, model.run.serve)
    cls = PagedKVCache if spec.kind == "paged" else DenseKVCache
    return cls(model, batch, seq_len, spec)
