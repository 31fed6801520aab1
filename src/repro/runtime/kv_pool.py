"""KV-cache pools for the continuous-batching runtime.

Two layouts share the slot-indexed front:

  * :class:`KVCachePool` — the dense layout: one cache pytree laid out
    exactly as ``model.make_cache(n_slots, max_len)`` (batch axis == slot
    axis), every slot reserving a worst-case ``max_len`` row.  This remains
    the path for families whose decode state is CONSTANT-size per slot
    (SSM / xLSTM / hybrid recurrent state): paging buys them nothing.

  * :class:`PagedKVCachePool` — the block-paged layout for attention
    families (dense / moe / MLA): one shared arena of fixed-size KV pages
    (``model.make_paged_cache``) plus a per-slot page table.  A request
    only occupies the pages its tokens fill (prompt pages at admission,
    one more page each time decode crosses a page boundary), so the same
    HBM budget admits several times more mixed-length invocations than
    dense slots — TIDAL's resident-state footprint, attacked at the KV
    level.

Allocation policy (paged): admission RESERVES the request's worst-case
block count (``ceil((prompt + max_new) / page_size)``) against the free
pool but maps pages lazily.  Reservation keeps admission deadlock-free —
an admitted request can always grow to its declared maximum, so decode
never stalls waiting for a page — while the arena is still sized for the
sum of actual request lengths rather than ``n_slots * max_len``.
Exhaustion raises :class:`PoolExhausted` instead of hanging admission.

Chunked prefill relaxes that to an INCREMENTAL reservation: ``alloc(...,
budget_tokens=n)`` reserves only the pages covering the first prefill
chunk, and ``extend_budget`` grows the reservation chunk by chunk as the
cursor advances (to the full worst case before the final chunk, so the
decode phase keeps the deadlock-free invariant above).  A long prompt
therefore no longer locks its whole page span at admission time — short
requests admit alongside it out of the same arena.

Multi-tenancy (slot partitions): the paged arena is shared by SEVERAL
engines at once.  Each engine registers an OWNER token
(:meth:`PagedKVCachePool.register_owner`) and allocates its slots under
that token; every mutating slot operation is owner-checked, so a
misbehaving engine writing outside its partition raises loudly instead of
corrupting a co-tenant's KV.  ``device_page_table(owner)`` returns a
per-owner MASKED view of the page table — rows of slots held by other
owners read as all-NULL — which is what makes the batched decode step
slot-masked: foreign slots behave exactly like free slots (null-page
dummies), compiled shapes never change, and co-resident engines interleave
at quantum granularity instead of borrowing the arena exclusively.

Prefix sharing (copy-on-write): every page carries a REFCOUNT.  A
:class:`PrefixHandle` pins a span of already-filled prompt-prefix pages
(TIDAL's template-baked warm state, at the KV level); ``alloc(...,
shared_prefix=handle, reuse_len=r)`` maps the prefix's full pages straight
into the new slot's page table — refcount++, zero copies — and makes ONE
device copy of the trailing partial page when ``r`` ends mid-page, so the
slot can keep appending without ever mutating the donor's page.  Shared
pages return to the free list only when their refcount reaches 0
(``release`` decrements uniformly: exclusively-owned pages sit at 1).

Quantized mode (``kv_dtype='int8'``): the arena's value leaves are int8
with a per-row float32 scale arena (``<leaf>_scale``) in the same cache
pytree, page-indexed exactly like its value leaf.  The pool quantizes on
write (``write_prompt`` / ``write_suffix`` / ``bake_prefix`` — decode-step
appends quantize inside the model layer) and dequantizes on read
(``read_slot`` / ``read_slot_full`` hand back fp dense caches, so suffix
prefill and parity readers are layout-blind).  Because scale leaves live
in ``self.cache``, copy-on-write page copies, refcounts, byte accounting
and sharding specs cover them with no extra bookkeeping: scales are
refcounted WITH their pages by construction.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.distributed.sharding import ShardingPlan
from repro.models import quant
from repro.models.registry import Model
from repro.runtime.errors import PartitionViolation, PoolExhausted

__all__ = ["PoolExhausted", "PartitionViolation", "PrefixHandle",
           "KVCachePool", "PagedKVCachePool"]


@dataclasses.dataclass
class PrefixHandle:
    """A pinned, refcounted span of prompt-prefix KV pages.

    ``pages`` are physical arena pages in logical order; ``n_tokens`` may
    end mid-page (the trailing partial page is the copy-on-write unit).
    The handle itself holds one reference on every page — template prefix
    pages stay resident across serve/evict cycles until ``release_prefix``
    drops the pin.  ``tokens`` keeps the prefix token ids for exact-match
    verification (the index's page hashes only nominate candidates).
    """

    pool: "PagedKVCachePool"
    pages: tuple
    n_tokens: int
    tokens: np.ndarray
    pinned: bool = True

    @property
    def page_size(self) -> int:
        """Tokens per page of the owning pool."""
        return self.pool.page_size

    @property
    def n_full_pages(self) -> int:
        """Pages the prefix fills completely (aliasable without a copy)."""
        return self.n_tokens // self.page_size


class KVCachePool:
    """Slot-indexed KV/state cache shared by one decode batch.

    With a ``plan`` the pool's arena is allocated directly as
    NamedSharding-placed buffers on the plan's mesh (heads / feature dims
    over 'model'), so every engine decode runs tensor-parallel without a
    placement copy.
    """

    def __init__(self, model: Model, n_slots: int, max_len: int,
                 plan: Optional[ShardingPlan] = None):
        if n_slots < 1:
            raise ValueError("n_slots must be >= 1")
        self.model = model
        self.n_slots = n_slots
        self.max_len = max_len
        self.plan = plan
        self.cache = model.make_cache(n_slots, max_len)
        if plan is not None:
            self.cache = jax.device_put(
                self.cache, plan.cache_shardings(model, self.cache))
        self._free = list(range(n_slots - 1, -1, -1))
        self._free_set = set(self._free)

    # ---- slot bookkeeping -------------------------------------------------
    @property
    def n_free(self) -> int:
        """Slots currently unallocated."""
        return len(self._free)

    def alloc(self) -> int:
        """Claim a free slot; raises :class:`PoolExhausted` when none."""
        if not self._free:
            raise PoolExhausted("KVCachePool exhausted: no free slots")
        slot = self._free.pop()
        self._free_set.discard(slot)
        return slot

    def release(self, slot: int) -> None:
        """Return ``slot`` to the free list (double-release raises)."""
        if slot in self._free_set or not (0 <= slot < self.n_slots):
            raise ValueError(f"bad slot release: {slot}")
        self._free.append(slot)
        self._free_set.add(slot)

    # ---- cache movement ---------------------------------------------------
    def write_slot(self, slot: int, sub_cache: Any) -> None:
        """Scatter a batch-1 cache (same ``max_len`` layout) into ``slot``."""
        self.cache = self.model.scatter_cache_slots(self.cache, [slot],
                                                    sub_cache)

    def read_slot(self, slot: int) -> Any:
        """Gather ``slot`` back out as a batch-1 cache."""
        return self.model.gather_cache_slots(self.cache, [slot])

    def nbytes(self) -> int:
        """Total bytes of the pool's cache arena."""
        return sum(int(l.nbytes) for l in jax.tree.leaves(self.cache))


class PagedKVCachePool:
    """Block-paged KV arena + per-slot page tables.

    Page 0 is the NULL page: free slots (which still ride in the shared
    decode batch at position 0) and unallocated logical blocks point at it,
    so their cache writes scribble on a page no request owns and their
    reads are masked out by the per-slot length.  Allocatable pages are
    ``1 .. n_pages-1``.

    Refcount invariants (prefix sharing):

      * a freshly mapped page has refcount 1, held by its slot;
      * ``bake_prefix`` pages hold refcount 1 via their handle, surviving
        every serve/evict cycle until ``release_prefix``;
      * ``alloc(shared_prefix=...)`` increments the refcount of every
        aliased full page; writes to any page with refcount > 1 raise
        (copy-on-write: the trailing partial page is copied, never shared
        mutably);
      * ``release`` decrements uniformly; a page returns to the free list
        only at refcount 0.

    With ``kv_dtype='int8'`` the arena is quantized: int8 value leaves and
    per-row float32 ``<leaf>_scale`` leaves share the cache pytree and the
    page axis, so every page-granular operation above covers scales too.
    """

    NULL_PAGE = 0

    def __init__(self, model: Model, n_slots: int, max_len: int,
                 page_size: int = 8, n_pages: int | None = None,
                 plan: Optional[ShardingPlan] = None,
                 kv_dtype: Optional[str] = None):
        if n_slots < 1:
            raise ValueError("n_slots must be >= 1")
        if page_size < 1:
            raise ValueError("page_size must be >= 1")
        if not model.supports_paged_kv:
            raise ValueError(
                f"{model.cfg.name}: family {model.cfg.family!r} has no "
                "paged KV layout (use the dense KVCachePool)")
        self.model = model
        self.n_slots = n_slots
        self.max_len = max_len
        self.page_size = page_size
        self.kv_dtype = kv_dtype
        self.blocks_per_slot = -(-max_len // page_size)
        # logical span of a full slot (page-multiple; == max_len when the
        # page size divides it, which is also the bit-parity condition
        # against the dense layout's reduction shapes)
        self.padded_len = self.blocks_per_slot * page_size
        if n_pages is None:
            # default: capacity-equal to the dense pool (every slot can
            # grow to max_len) — benchmarks/servers size it tighter
            n_pages = 1 + n_slots * self.blocks_per_slot
        if n_pages < 2:
            raise ValueError("n_pages must be >= 2 (null page + 1)")
        self.n_pages = n_pages
        self.plan = plan
        self.cache = model.make_paged_cache(n_pages, page_size,
                                            kv_dtype=kv_dtype)
        # the fp dtype prefill produces and read_slot* hands back (the
        # quantized arena dequantizes reads to this)
        self._fp_dtype = jax.tree.leaves(
            model.make_cache(1, page_size, abstract=True))[0].dtype
        if plan is not None:
            # page + in-page axes replicated (any device serves any page),
            # heads / latent dims over 'model'
            self.cache = jax.device_put(
                self.cache, plan.paged_cache_shardings(model, self.cache))
        self.page_table = np.zeros((n_slots, self.blocks_per_slot), np.int32)
        self._free_slots = list(range(n_slots - 1, -1, -1))
        self._free_slot_set = set(self._free_slots)
        self._free_pages = list(range(n_pages - 1, 0, -1))
        self._reserved = 0                 # reserved-but-unmapped blocks
        self._mapped: dict[int, int] = {}  # slot -> mapped block count
        self._budget: dict[int, int] = {}  # slot -> reserved block total
        # prefix sharing: per-page refcount (0 = free / never allocated;
        # exclusively-owned pages sit at 1, shared prefix pages higher)
        self._page_refs = np.zeros(n_pages, np.int32)
        # multi-tenancy: owner tokens partition the slot space.  A slot
        # allocated under an owner is invisible (all-NULL page-table row)
        # to every other owner's device view, and mutating it under the
        # wrong owner raises.
        self._next_owner = 0
        self._owners: dict[int, Optional[str]] = {}
        self._slot_owner: dict[int, int] = {}
        self._owner_pts: dict[int, Any] = {}
        self._owner_dirty: dict[int, set] = {}
        # cumulative mapping counters — the benchmark/test surface for
        # "a prefix hit maps strictly fewer fresh pages per request"
        self.stats = {"fresh_pages_mapped": 0, "shared_pages_mapped": 0,
                      "cow_page_copies": 0}
        self.peak_used_pages = 0           # high-water resident footprint
        # device-resident page table, synced by dirty row (decode-step
        # upload micro-opt: admit/grow/retire touch a handful of rows, the
        # full (n_slots, blocks_per_slot) table re-uploads only once)
        self._device_pt = None
        self._dirty_rows: set = set()

    # ---- accounting -------------------------------------------------------
    def blocks_for(self, n_tokens: int) -> int:
        """Pages needed to back ``n_tokens`` positions (minimum 1)."""
        return max(1, -(-n_tokens // self.page_size))

    @property
    def n_free_slots(self) -> int:
        """Slots currently unallocated."""
        return len(self._free_slots)

    @property
    def n_free_pages(self) -> int:
        """Pages on the free list (some may be promised to reservations)."""
        return len(self._free_pages)

    @property
    def n_available_pages(self) -> int:
        """Pages neither mapped nor promised to an admitted request."""
        return len(self._free_pages) - self._reserved

    def can_admit(self, n_tokens_total: int, reuse_len: int = 0) -> bool:
        """True when a request of this total length is admissible now.

        ``reuse_len`` tokens covered by a shared prefix need no fresh
        pages for their full pages (the COW partial page, if any, is
        already counted in ``blocks_for(total) - reuse // page_size``).
        """
        fresh = self.blocks_for(n_tokens_total) - reuse_len // self.page_size
        return bool(self._free_slots) and fresh <= self.n_available_pages

    # ---- slot partitions (multi-tenancy) ----------------------------------
    def register_owner(self, name: Optional[str] = None) -> int:
        """Mint an owner token partitioning the slot space.

        Engines sharing this arena each hold a token; slots allocate
        under it, and :meth:`device_page_table` with the token masks out
        every other owner's rows so a batched decode only sees (and
        therefore only reads/writes) the caller's own partition.
        """
        self._next_owner += 1
        token = self._next_owner
        self._owners[token] = name
        self._owner_dirty[token] = set()
        return token

    def release_owner(self, owner: int) -> None:
        """Drop an owner token, releasing any slots it still holds.

        Co-tenants' slots, page refcounts and device views are untouched
        — evicting one tenant returns exactly its own pages.
        """
        if owner not in self._owners:
            raise ValueError(f"unknown owner token {owner}")
        for slot in [s for s, o in self._slot_owner.items() if o == owner]:
            self.release(slot, owner=owner)
        del self._owners[owner]
        self._owner_pts.pop(owner, None)
        self._owner_dirty.pop(owner, None)

    def slot_owner(self, slot: int) -> Optional[int]:
        """Owner token holding ``slot`` (None: free or unowned legacy)."""
        return self._slot_owner.get(slot)

    def owner_slots(self, owner: int) -> list:
        """Slots currently allocated under ``owner`` (sorted)."""
        return sorted(s for s, o in self._slot_owner.items() if o == owner)

    def n_foreign_slots(self, owner: Optional[int]) -> int:
        """Allocated slots NOT held by ``owner`` (co-tenant occupancy)."""
        n_held = self.n_slots - len(self._free_slots)
        if owner is None:
            return n_held - sum(
                1 for s in range(self.n_slots)
                if s not in self._free_slot_set
                and self._slot_owner.get(s) is None)
        return n_held - len(self.owner_slots(owner))

    def partition_stats(self, owner: int) -> dict:
        """Resident footprint of one owner's slot partition."""
        if owner not in self._owners:
            raise ValueError(f"unknown owner token {owner}")
        slots = self.owner_slots(owner)
        mapped = sum(self._mapped[s] for s in slots)
        budget = sum(self._budget[s] for s in slots)
        return {"owner": owner, "name": self._owners[owner],
                "n_slots": len(slots), "mapped_pages": mapped,
                "reserved_pages": budget - mapped}

    def _check_owner(self, slot: int, owner: Optional[int],
                     verb: str) -> None:
        """Raise when ``owner`` tries to touch a slot it does not hold."""
        if owner is None:
            return
        held_by = self._slot_owner.get(slot)
        if held_by != owner:
            whose = (f"partition {held_by} "
                     f"({self._owners.get(held_by)!r})"
                     if held_by is not None else "no partition")
            raise PartitionViolation(
                f"slot {slot}: owner {owner} "
                f"({self._owners.get(owner)!r}) may not {verb} a slot "
                f"held by {whose}")

    # ---- alloc / grow / release ------------------------------------------
    def alloc(self, prompt_len: int, max_new_tokens: int,
              shared_prefix: Optional[PrefixHandle] = None,
              reuse_len: int = 0,
              budget_tokens: Optional[int] = None,
              owner: Optional[int] = None) -> int:
        """Claim a slot and reserve the request's worst-case block count.

        With ``shared_prefix``, the first ``reuse_len`` tokens of the
        prompt are served from the handle's already-filled pages: full
        pages alias into the slot's page table (refcount++, no copy); a
        trailing partial page — ``reuse_len`` ending mid-page — is copied
        once into a fresh page the slot owns exclusively, so later writes
        never touch the donor (copy-on-write).  In quantized mode the
        copy spans value AND scale leaves (same page axis), so a
        borrower's re-quantized appends can never perturb donor scales.

        ``budget_tokens`` caps the INITIAL reservation at the pages
        covering that many tokens instead of the worst case (chunked
        prefill: the engine grows the budget via :meth:`extend_budget` as
        chunks land).  The worst case is still validated against the
        arena/slot capacity so an admission can never be unservable.

        ``owner`` files the slot under a partition token from
        :meth:`register_owner`; later mutations must present the same
        token, and other owners' device page tables mask this slot out.
        """
        if owner is not None and owner not in self._owners:
            raise ValueError(f"unknown owner token {owner}")
        total = self.blocks_for(prompt_len + max_new_tokens)
        if total > self.blocks_per_slot:
            raise ValueError(
                f"request needs {total} pages but a slot's page table "
                f"holds {self.blocks_per_slot} (max_len={self.max_len})")
        if total > self.n_pages - 1:
            raise ValueError(
                f"request needs {total} pages but the arena only has "
                f"{self.n_pages - 1} allocatable pages")
        n_full = 0
        if shared_prefix is not None and reuse_len > 0:
            if shared_prefix.pool is not self:
                raise ValueError("shared_prefix belongs to another pool")
            if not shared_prefix.pinned:
                raise ValueError("shared_prefix has been released")
            if reuse_len > shared_prefix.n_tokens:
                raise ValueError(
                    f"reuse_len={reuse_len} exceeds the prefix's "
                    f"{shared_prefix.n_tokens} cached tokens")
            if reuse_len >= prompt_len:
                raise ValueError(
                    "reuse_len must leave at least one prompt token to "
                    "prefill (the suffix produces the first logits)")
            n_full = reuse_len // self.page_size
        partial = (shared_prefix is not None and reuse_len > 0
                   and reuse_len % self.page_size != 0)
        budget = total
        if budget_tokens is not None:
            if budget_tokens <= reuse_len:
                raise ValueError(
                    f"budget_tokens={budget_tokens} must cover the reused "
                    f"prefix ({reuse_len} tokens) plus at least one more")
            budget = min(total, self.blocks_for(budget_tokens))
        fresh = budget - n_full             # incl. the COW partial page
        if not self._free_slots:
            raise PoolExhausted("PagedKVCachePool exhausted: no free slots")
        if fresh > self.n_available_pages:
            raise PoolExhausted(
                f"PagedKVCachePool exhausted: need {fresh} fresh pages, "
                f"{self.n_available_pages} available")
        slot = self._free_slots.pop()
        self._free_slot_set.discard(slot)
        if owner is not None:
            self._slot_owner[slot] = owner
        mapped = 0
        if n_full:
            # zero-copy aliasing of the page-aligned span
            share = [int(p) for p in shared_prefix.pages[:n_full]]
            self.page_table[slot, :n_full] = share
            self._page_refs[share] += 1
            mapped = n_full
            self.stats["shared_pages_mapped"] += n_full
        if partial:
            # one page copy for the trailing partial page: the slot keeps
            # appending tokens into ITS copy, the donor page never mutates
            # (value and scale leaves alike — same page axis)
            page = self._claim_free_page()
            donor = int(shared_prefix.pages[n_full])
            self.cache = jax.tree.map(
                lambda arena: arena.at[:, page].set(arena[:, donor]),
                self.cache)
            self.page_table[slot, mapped] = page
            mapped += 1
            self.stats["cow_page_copies"] += 1
        self._reserved += budget - mapped
        self._budget[slot] = budget
        self._mapped[slot] = mapped
        if mapped:
            self._touch(slot)
        return slot

    def extend_budget(self, slot: int, n_tokens: int,
                      owner: Optional[int] = None) -> bool:
        """Grow ``slot``'s reserved block budget to cover ``n_tokens``.

        Chunked prefill calls this before each chunk, and with the full
        ``prompt + max_new`` before the final one so decode keeps the
        reservation invariant.  Returns False — no state change — when
        the free pool cannot back the extra reservation right now; the
        caller retries after retirements free pages.  (The reservation is
        page-count bookkeeping only: the pages — and, in quantized mode,
        their scale rows — materialize at :meth:`ensure_len` time.)
        """
        if slot not in self._budget:
            raise ValueError(f"slot {slot} is not allocated")
        self._check_owner(slot, owner, "grow the budget of")
        need = self.blocks_for(n_tokens)
        if need > self.blocks_per_slot:
            raise ValueError(
                f"slot {slot}: {n_tokens} tokens needs {need} pages but a "
                f"slot's page table holds {self.blocks_per_slot}")
        extra = need - self._budget[slot]
        if extra <= 0:
            return True
        if extra > self.n_available_pages:
            return False
        self._budget[slot] = need
        self._reserved += extra
        return True

    def slot_budget(self, slot: int) -> int:
        """Currently reserved block budget of an allocated slot."""
        return self._budget[slot]

    def ensure_len(self, slot: int, n_tokens: int,
                   owner: Optional[int] = None) -> None:
        """Map pages so positions ``0 .. n_tokens-1`` are backed."""
        if slot not in self._budget:
            raise ValueError(f"slot {slot} is not allocated")
        self._check_owner(slot, owner, "map pages into")
        need = self.blocks_for(n_tokens)
        if need > self._budget[slot]:
            raise ValueError(
                f"slot {slot}: {n_tokens} tokens exceeds the reserved "
                f"budget of {self._budget[slot]} pages")
        while self._mapped[slot] < need:
            if not self._free_pages:        # unreachable within budget
                raise PoolExhausted("PagedKVCachePool: free list empty")
            page = self._claim_free_page()
            self.page_table[slot, self._mapped[slot]] = page
            self._mapped[slot] += 1
            self._reserved -= 1
            self._touch(slot)

    def _claim_free_page(self) -> int:
        """Pop a free page at refcount 1, tracking counters + peak."""
        page = self._free_pages.pop()
        self._page_refs[page] = 1
        self.stats["fresh_pages_mapped"] += 1
        self.peak_used_pages = max(self.peak_used_pages, self.n_used_pages)
        return page

    def _unref_page(self, page: int) -> None:
        self._page_refs[page] -= 1
        if self._page_refs[page] == 0:
            self._free_pages.append(page)
        elif self._page_refs[page] < 0:
            raise AssertionError(f"page {page} refcount went negative")

    def release(self, slot: int, owner: Optional[int] = None) -> None:
        """Retire ``slot``: unref its mapped pages and free the slot.

        Aliased prefix pages merely drop one reference; pages return to
        the free list only at refcount 0, so a donor prefix (or another
        borrower) is never freed out from under its remaining users.
        With ``owner``, releasing a co-tenant's slot raises.
        """
        if slot in self._free_slot_set or not (0 <= slot < self.n_slots):
            raise ValueError(f"bad slot release: {slot}")
        self._check_owner(slot, owner, "release")
        self._slot_owner.pop(slot, None)
        mapped = self._mapped.pop(slot)
        budget = self._budget.pop(slot)
        for p in self.page_table[slot, :mapped]:
            self._unref_page(int(p))
        self._reserved -= budget - mapped
        self.page_table[slot, :] = self.NULL_PAGE
        self._free_slots.append(slot)
        self._free_slot_set.add(slot)
        self._touch(slot)

    # ---- prefix sharing ---------------------------------------------------
    def bake_prefix(self, sub_cache: Any, tokens) -> PrefixHandle:
        """Materialize a prompt prefix as pinned shared pages.

        ``sub_cache`` is a batch-1 prefilled dense cache covering
        ``tokens`` (leaves ``[L, 1, T, ...]``, ``T`` a page multiple ≥
        ``len(tokens)``).  Pages come straight from the free list — no
        slot involved — with refcount 1 held by the returned handle, so
        they survive every serve/evict cycle until ``release_prefix``.
        In quantized mode the baked pages are quantized once here and
        served int8 to every borrower.
        """
        tokens = np.asarray(tokens, np.int32).reshape(-1)
        n_tokens = len(tokens)
        if n_tokens < 1:
            raise ValueError("a prefix needs at least one token")
        nb = self.blocks_for(n_tokens)
        if nb > self.n_available_pages:
            raise PoolExhausted(
                f"PagedKVCachePool exhausted: prefix needs {nb} pages, "
                f"{self.n_available_pages} available")
        pages = [self._claim_free_page() for _ in range(nb)]
        self._write_blocks(np.asarray(pages, np.int32), sub_cache,
                           first_block=0)
        return PrefixHandle(pool=self, pages=tuple(pages),
                            n_tokens=n_tokens, tokens=tokens)

    def release_prefix(self, handle: PrefixHandle) -> None:
        """Drop the handle's pin.

        Pages free as their refcount hits 0; live slots still aliasing
        them keep them alive.
        """
        if not handle.pinned or handle.pool is not self:
            raise ValueError("handle is not pinned on this pool")
        handle.pinned = False
        for p in handle.pages:
            self._unref_page(int(p))

    def prefix_page_refs(self, handle: PrefixHandle):
        """Current refcounts of the handle's pages (test/debug surface)."""
        return [int(self._page_refs[p]) for p in handle.pages]

    # ---- cache movement ---------------------------------------------------
    def _write_blocks(self, pages, sub_cache: Any, first_block: int) -> None:
        """Scatter logical blocks of a batch-1 dense fp cache into pages.

        Blocks ``first_block ..`` land in the given physical ``pages``
        (one per block).  In quantized mode each block's rows are
        quantized here — int8 values into the value leaf, per-row scales
        into its ``_scale`` leaf — so callers always hand over plain fp
        caches.
        """
        ps = self.page_size
        nb = len(pages)

        def span(sub):
            L, _, T = sub.shape[:3]
            blocks = sub[:, 0].reshape((L, T // ps, ps) + sub.shape[3:])
            return blocks[:, first_block:first_block + nb]

        if self.kv_dtype is None:
            self.cache = jax.tree.map(
                lambda arena, sub: arena.at[:, pages].set(
                    span(sub).astype(arena.dtype)),
                self.cache, sub_cache)
            return
        new = dict(self.cache)
        for key, sub in sub_cache.items():
            q, s = quant.quantize_rows(span(sub))
            new[key] = self.cache[key].at[:, pages].set(q)
            skey = key + quant.SCALE_SUFFIX
            new[skey] = self.cache[skey].at[:, pages].set(s)
        self.cache = new

    def write_prompt(self, slot: int, sub_cache: Any, n_tokens: int,
                     owner: Optional[int] = None) -> None:
        """Write a prefilled prompt into ``slot``'s pages (allocating them).

        ``sub_cache`` is a batch-1 dense fp cache whose leaves are
        ``[L, 1, T, ...]`` with ``T`` a page multiple covering
        ``n_tokens`` — only the occupied pages are written (and quantized,
        in int8 mode).
        """
        self.write_suffix(slot, sub_cache, 0, n_tokens, owner=owner)

    def write_suffix(self, slot: int, sub_cache: Any, start_token: int,
                     n_tokens: int, owner: Optional[int] = None,
                     req_id: int = -1) -> None:
        """Write positions ``start_token .. n_tokens-1`` into ``slot``.

        Maps any still-missing pages, then writes whole blocks from
        ``start_token // page_size`` on — the block containing
        ``start_token`` is the slot's COW copy when a shared prefix ends
        mid-page, never an aliased donor page (shared-page writes raise).
        Quantized mode re-quantizes the rewritten first block from its
        dequantized values, which is bit-exact (see ``repro.models.quant``).
        The write is a ``tidal.arena.write`` profiler span carrying the
        request's ``req_id`` and the pages written.
        """
        self._check_owner(slot, owner, "write KV into")
        self.ensure_len(slot, n_tokens, owner=owner)
        first = start_token // self.page_size
        nb = self.blocks_for(n_tokens)
        if first >= nb:
            return
        pages = self.page_table[slot, first:nb]
        shared = [int(p) for p in pages if self._page_refs[int(p)] > 1]
        if shared:
            raise ValueError(
                f"slot {slot}: refusing to write shared pages {shared} "
                "(aliased prefix pages are copy-on-write)")
        with jax.profiler.TraceAnnotation("tidal.arena.write", req_id=req_id,
                                          pages=len(pages)):
            self._write_blocks(pages, sub_cache, first_block=first)

    def _gather_pages(self, pages, length: int) -> Any:
        """Gather ``pages`` into a batch-1 dense fp cache of ``length``."""
        def gather(arena):
            blocks = arena[:, pages]                   # [L, nb, ps, ...]
            L = blocks.shape[0]
            return blocks.reshape((L, 1, length) + blocks.shape[3:])

        if self.kv_dtype is None:
            return jax.tree.map(gather, self.cache)
        return {
            key: quant.dequantize_rows(
                gather(self.cache[key]),
                gather(self.cache[key + quant.SCALE_SUFFIX]),
                self._fp_dtype)
            for key in quant.value_keys(self.cache)
        }

    def read_slot(self, slot: int, n_tokens: int) -> Any:
        """Gather ``slot``'s first ``n_tokens`` positions as a dense cache.

        Returns a batch-1 fp cache of page-multiple length (dequantized
        from the int8 arena in quantized mode).
        """
        nb = self.blocks_for(n_tokens)
        pages = self.page_table[slot, :nb]
        return self._gather_pages(pages, nb * self.page_size)

    def read_slot_full(self, slot: int) -> Any:
        """Gather the slot's WHOLE page-table row as a dense fp cache.

        The result spans ``padded_len`` positions — the suffix-prefill
        working cache: mapped prefix blocks carry their KV, unmapped
        blocks read the null page (masked out by position before any
        unwritten slot is attended).
        """
        return self._gather_pages(self.page_table[slot], self.padded_len)

    # ---- device page table (dirty-row sync) -------------------------------
    def _touch(self, slot: int) -> None:
        self._dirty_rows.add(slot)
        for dirty in self._owner_dirty.values():
            dirty.add(slot)

    def _masked_rows(self, owner: int, rows) -> np.ndarray:
        """Host page-table rows with co-tenants' slots forced to NULL.

        A foreign slot's masked row is indistinguishable from a free
        slot's, so the owner's batched decode treats it as a null-page
        dummy — its writes scribble on the null page, its reads are
        position-masked, and the co-tenant's pages are unreachable.
        """
        out = np.zeros((len(rows), self.blocks_per_slot), np.int32)
        for i, slot in enumerate(rows):
            if self._slot_owner.get(slot) == owner:
                out[i] = self.page_table[slot]
        return out

    def device_page_table(self, owner: Optional[int] = None):
        """Return the page table as a device-resident array.

        Only rows that changed since the last call re-upload
        (admit/grow/retire touch a few rows; steady-state decode uploads
        nothing).  With ``owner``, the returned table is that partition's
        MASKED view: rows of slots held by any other owner are all-NULL,
        so a batched decode under this table cannot read or write a
        co-tenant's pages.  Shapes are identical across owners (and to
        the unmasked view), keeping compiled executables shared.
        """
        if owner is None:
            if self._device_pt is None:
                self._device_pt = self._upload_full(self.page_table)
                self._dirty_rows.clear()
            elif self._dirty_rows:
                rows = sorted(self._dirty_rows)
                self._device_pt = self._upload_rows(
                    self._device_pt, rows, self.page_table[rows])
                self._dirty_rows.clear()
            return self._device_pt
        if owner not in self._owners:
            raise ValueError(f"unknown owner token {owner}")
        dirty = self._owner_dirty[owner]
        if owner not in self._owner_pts:
            self._owner_pts[owner] = self._upload_full(
                self._masked_rows(owner, range(self.n_slots)))
            dirty.clear()
        elif dirty:
            rows = sorted(dirty)
            self._owner_pts[owner] = self._upload_rows(
                self._owner_pts[owner], rows,
                self._masked_rows(owner, rows))
            dirty.clear()
        return self._owner_pts[owner]

    def _upload_full(self, table: np.ndarray):
        if self.plan is not None:
            return jax.device_put(table, self.plan.replicated)
        return jnp.asarray(table)

    def _upload_rows(self, device_pt, rows, host_rows):
        idx = jnp.asarray(rows, jnp.int32)
        return device_pt.at[idx].set(jnp.asarray(host_rows))

    # ---- footprint --------------------------------------------------------
    @property
    def n_used_pages(self) -> int:
        """Pages currently holding KV (mapped by slots or pinned by prefixes).

        The arena's RESIDENT footprint, as opposed to its allocated
        capacity.
        """
        return (self.n_pages - 1) - len(self._free_pages)

    def page_nbytes(self) -> int:
        """Bytes per page (scale rows included in quantized mode)."""
        return self.nbytes() // self.n_pages

    def resident_nbytes(self) -> int:
        """Bytes of the pages currently holding KV."""
        return self.n_used_pages * self.page_nbytes()

    def nbytes(self) -> int:
        """Total bytes of the arena (value + scale leaves)."""
        return sum(int(l.nbytes) for l in jax.tree.leaves(self.cache))
