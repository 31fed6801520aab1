"""Continuous-batching serving engine over the KV-cache slot pool.

Where :class:`repro.runtime.engine.Engine` runs one fixed-shape batch to
completion, this engine keeps an admission queue and a step loop:

  * **prefill-on-arrival** — a queued request is admitted the moment a pool
    slot frees up: its prompt prefills as a batch-1 call (optionally the
    layer-streamed path when params are a :class:`ForkSession` whose weights
    are still in flight) and the filled cache scatters into the slot;
  * **batched decode** — every iteration issues ONE ``decode_step`` over the
    whole pool with a per-slot position vector, so requests of different
    prompt lengths and ages share the batch;
  * **retirement** — finished requests release their slot, which unblocks
    the next queued admission on the same step.

With ``chunk_tokens`` set (paged pools only), prefill is CHUNKED into the
step loop instead of running to completion at admission: every ``step``
becomes one MIXED batched step — up to ``chunk_tokens`` prompt tokens
advance the prefill cursors of mid-prefill slots (each chunk a
page-multiple ``prefill_from`` call at the cursor's offset), then one
batched decode runs over the slots that already finished their prompt.  A
burst of long cold prompts therefore no longer head-of-line-blocks the
decode tokens of everything admitted behind it — the p95-TTFT tail TIDAL
targets.  Admission under chunking reserves only the first chunk's pages
(see ``PagedKVCachePool.extend_budget``); the budget grows to the full
worst case before the final chunk so decode keeps the deadlock-free
reservation invariant.  Mid-prefill slots ride the shared decode batch as
dummies writing at the last padded position, whose block is never mapped
while the cursor is short of the prompt — the write lands on the null
page and the logits row is discarded, exactly like a free slot's.

Attention families (dense / moe / MLA) store KV state in a block-paged
:class:`~repro.runtime.kv_pool.PagedKVCachePool`: admission writes only the
prompt's pages, decode maps one more page per boundary crossing, and
retirement frees pages — so arena capacity tracks the tokens that exist,
not ``n_slots * max_len`` worst cases.  Recurrent-state families (SSM /
xLSTM / hybrid) keep the dense slot pool; their state is constant-size.

Engines sharing one paged arena CO-RESIDE (dense multi-tenancy): each
registers an owner token with the pool and decodes under its own MASKED
device page table, so a co-tenant's slots ride this engine's batched
decode as null-page dummies — indistinguishable from free slots, shapes
unchanged — and the gateway interleaves co-resident engines at quantum
granularity instead of enforcing arena exclusivity.  On top of that, an
``adapter_bank`` makes one engine serve MANY functions: each request
carries an ``adapter_id`` and the decode step gathers its low-rank LoRA
delta per slot (id 0 = null adapter for free/foreign slots), so
thousands of dynamic functions co-batch on one resident base model.
Dense (recurrent-state) pools still require exclusivity — their decode
advances every slot's state and cannot be null-masked.

Greedy decoding is bit-identical to the sequential ``Engine.generate``
per request (tested): the per-slot position vector reproduces exactly the
positions, cache writes and attention masks of an isolated batch-1 run.

Request ids are unique in the process (``new_req_id``), so a gateway can
name a request before it forks the engine that will serve it.  Each step
is a ``tidal.step`` profiler span (decoding slots ``live`` of ``rows``,
``admitted``, ``chunked`` prompt tokens) holding ``tidal.prefill``,
``tidal.sample_first``, ``tidal.decode.dispatch``, ``tidal.decode.sync``
and ``tidal.emit`` spans; see PERF.md for what reads each.
"""

from __future__ import annotations

import collections
import contextlib
import dataclasses
import itertools
import time
from typing import Any, Callable, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.streaming import (ForkSession, streamed_prefill,
                                  supports_streamed_prefill)
from repro.distributed.sharding import ShardingPlan, use_kernel_mesh
from repro.models.registry import Model
from repro.runtime.engine import sample_greedy, sample_token
from repro.runtime.faults import fault_point
from repro.runtime.kv_pool import (KVCachePool, PagedKVCachePool,
                                   PoolExhausted)


def _serve_bodies(model: Model, paged: bool, page_size: int,
                  with_adapters: bool = False) -> tuple:
    """The three serve entry points as plain functions, named so their
    device programs read ``jit_serve_prefill``, ``jit_serve_prefill_from``
    and ``jit_serve_decode`` (``serve_prefill_from`` is None without a
    paged arena).  ``with_adapters`` appends ``(adapter_bank,
    adapter_ids)`` arguments to each."""
    if with_adapters:
        if not paged:
            raise ValueError("adapter banks serve over the paged arena only")

        def serve_prefill(p, inputs, cache, bank, aids):
            return model.prefill(p, inputs, cache, adapter_bank=bank,
                                 adapter_ids=aids)

        def serve_prefill_from(p, toks, cache, off, bank, aids):
            return model.prefill_from(p, {"tokens": toks}, cache, off,
                                      adapter_bank=bank, adapter_ids=aids)

        def serve_decode(p, cache, toks, pos, pt, bank, aids):
            return model.decode_step_paged(
                p, cache, {"tokens": toks}, pos, pt, page_size,
                adapter_bank=bank, adapter_ids=aids)
        return serve_prefill, serve_prefill_from, serve_decode

    def serve_prefill(p, inputs, cache):
        return model.prefill(p, inputs, cache)

    if not paged:
        def serve_decode(p, cache, toks, pos):
            return model.decode_step(p, cache, {"tokens": toks}, pos)
        return serve_prefill, None, serve_decode

    def serve_prefill_from(p, toks, cache, off):
        return model.prefill_from(p, {"tokens": toks}, cache, off)

    def serve_decode(p, cache, toks, pos, pt):
        return model.decode_step_paged(p, cache, {"tokens": toks}, pos, pt,
                                       page_size)
    return serve_prefill, serve_prefill_from, serve_decode


def serve_fns(model: Model, paged: bool, page_size: int,
              donate_cache: bool = True, with_adapters: bool = False):
    """jit'd ``(prefill_fn, prefill_from_fn, decode_fn)`` serve entry
    points on one device (see ``_serve_bodies``); the decode step donates
    the KV arena unless ``donate_cache`` is False."""
    prefill, prefill_from, decode = _serve_bodies(model, paged, page_size,
                                                  with_adapters)
    return (jax.jit(prefill),
            jax.jit(prefill_from) if prefill_from is not None else None,
            jax.jit(decode, donate_argnums=(1,) if donate_cache else ()))


def sharded_serve_fns(model: Model, pool, plan: ShardingPlan,
                      donate_cache: bool = True,
                      with_adapters: bool = False):
    """jit'd ``(prefill_fn, prefill_from_fn, decode_fn)`` serve entry
    points whose in/out shardings carry ``plan`` end to end: params arrive
    in their tensor-parallel layout, the pool arena keeps its placement
    across donated decode steps, and GSPMD partitions the dense/paged
    attention paths.  Tokens / positions / page tables / logits are
    replicated (host-driven control state).  ``prefill_from_fn`` is the
    suffix-only entry point for prefix KV reuse (None without a paged
    pool).  Every entry point is called (and therefore traced) under
    ``use_kernel_mesh(plan.mesh)`` so ``attn_impl='pallas'`` shard_maps
    the attention kernels over the 'model' axis instead of silently
    falling back to the XLA reference inside the partitioned jit.

    ``with_adapters`` appends ``(adapter_bank, adapter_ids)`` arguments
    (replicated — banks are low-rank and small) to every entry point for
    batched multi-adapter serving."""
    rep = plan.replicated
    pshard = plan.param_shardings(model)
    paged = isinstance(pool, PagedKVCachePool)
    prefill, prefill_from, decode = _serve_bodies(
        model, paged, pool.page_size if paged else 0, with_adapters)
    extra = (rep, rep) if with_adapters else ()
    prefill_len = pool.padded_len if paged else pool.max_len
    pc_shard = plan.cache_shardings(
        model, model.make_cache(1, prefill_len, abstract=True))

    def _km(fn):
        def wrapped(*args):
            with use_kernel_mesh(plan.mesh):
                return fn(*args)

        def lower(*args):
            # the served program as the engine runs it, for inspection
            with use_kernel_mesh(plan.mesh):
                return fn.lower(*args)
        wrapped.lower = lower
        return wrapped

    prefill_fn = _km(jax.jit(prefill,
                             in_shardings=(pshard, rep, pc_shard) + extra,
                             out_shardings=(rep, pc_shard)))
    prefill_from_fn = None
    if prefill_from is not None:
        prefill_from_fn = _km(jax.jit(
            prefill_from, in_shardings=(pshard, rep, pc_shard, rep) + extra,
            out_shardings=(rep, pc_shard)))
    if paged:
        dshard = plan.paged_cache_shardings(model, pool.cache)
        control = (rep, rep, rep)            # tokens, positions, page table
    else:
        dshard = plan.cache_shardings(model, pool.cache)
        control = (rep, rep)
    decode_fn = _km(jax.jit(
        decode, in_shardings=(pshard, dshard) + control + extra,
        out_shardings=(rep, dshard),
        donate_argnums=(1,) if donate_cache else ()))
    return prefill_fn, prefill_from_fn, decode_fn


_UNMATCHED = object()                # prefix match not yet attempted
_req_ids = itertools.count()


def new_req_id() -> int:
    """A request id no other request of this process carries."""
    return next(_req_ids)


@dataclasses.dataclass
class Request:
    req_id: int
    prompt: np.ndarray               # [S] int32
    max_new_tokens: int
    submit_s: float
    temperature: float = 0.0         # 0 = greedy (bit-parity reference)
    top_p: float = 1.0
    seed: int = 0                    # per-request sampling seed
    deadline_s: Optional[float] = None  # shed if still QUEUED past this
    priority: int = 0                # higher admits first (FIFO within)
    token_cb: Optional[Callable] = None  # (req_id, token, index) per emit
    adapter_id: int = 0              # bank row (0 = null adapter / base)
    # prefix-reuse match, resolved lazily at first admission check and
    # cached ((handle, reuse_len) or None); _UNMATCHED = not yet looked up
    prefix_hit: Any = _UNMATCHED


@dataclasses.dataclass
class RequestOutput:
    req_id: int
    tokens: np.ndarray               # [n_generated] int32
    prompt_len: int
    n_generated: int
    ttft_s: float                    # submit -> first token (incl. queueing)
    e2e_s: float                     # submit -> retirement
    streamed_prefill: bool = False   # admitted while weights were in flight
    reused_prefix_len: int = 0       # prompt tokens served from shared pages
    status: str = "done"             # 'done' | 'cancelled' | 'shed' | 'failed'
    error: Optional[str] = None      # set for 'failed' (unservable) requests


@dataclasses.dataclass
class _Active:
    req: Request
    slot: int
    tokens: list
    streamed: bool
    ttft_s: float
    reused_prefix_len: int = 0
    cursor: int = 0                  # prompt tokens prefilled so far
    prefilling: bool = False         # True until the cursor reaches the prompt


class ContinuousBatchingEngine:
    """Multi-request generation for one model instance.

    ``params`` is either a concrete pytree (warm instance) or a
    :class:`ForkSession` (freshly forked instance): with a session,
    admissions that happen before the stream completes prefill layer-by-layer
    against the weights already on device, and the first batched decode
    blocks only on the remaining transfers.
    """

    def __init__(self, model: Model, params: Any, n_slots: int = 4,
                 max_len: int = 128,
                 prefill_fn: Optional[Callable] = None,
                 decode_fn: Optional[Callable] = None,
                 prefill_from_fn: Optional[Callable] = None,
                 donate_cache: bool = True,
                 paged: Optional[bool] = None, page_size: int = 8,
                 n_pages: Optional[int] = None,
                 plan: Optional[ShardingPlan] = None,
                 pool: Optional[Any] = None,
                 prefix_index: Optional[Any] = None,
                 bucket_suffix: bool = False,
                 chunk_tokens: Optional[int] = None,
                 kv_dtype: Optional[str] = None,
                 adapter_bank: Optional[dict] = None,
                 owner_name: Optional[str] = None):
        if model.is_encdec:
            raise NotImplementedError(
                "continuous batching needs per-slot decode positions; the "
                "enc-dec family still serves through the sequential Engine")
        self.model = model
        self.plan = plan
        self.session = params if isinstance(params, ForkSession) else None
        self._params = None if self.session is not None else params
        if pool is not None:
            # injected shared pool (FaaSRuntime: one arena per mesh slice,
            # engines borrow slots and return them at retirement/eviction)
            self.pool = pool
            self.paged = isinstance(pool, PagedKVCachePool)
            n_slots = pool.n_slots
            if plan is None:
                self.plan = plan = pool.plan
        else:
            # block-paged KV for attention families (their cache grows with
            # the sequence), dense slots for constant-size recurrent state
            if paged is None:
                paged = model.supports_paged_kv
            self.paged = paged
            if paged:
                self.pool = PagedKVCachePool(model, n_slots, max_len,
                                             page_size=page_size,
                                             n_pages=n_pages, plan=plan,
                                             kv_dtype=kv_dtype)
            else:
                if kv_dtype is not None:
                    raise ValueError(
                        "kv_dtype quantization needs the paged arena")
                self.pool = KVCachePool(model, n_slots, max_len, plan=plan)
        if adapter_bank is not None and not self.paged:
            raise ValueError("adapter banks serve over the paged arena only")
        self.adapter_bank = adapter_bank
        # partition lease: paged pools are multi-tenant — this engine's
        # slots file under its owner token and its decode steps run under
        # the pool's masked page-table view, so co-resident engines on the
        # same arena can interleave.  Dense pools have no mask (decode
        # advances every slot's recurrent state) and stay exclusive.
        self._owner = (self.pool.register_owner(owner_name)
                       if self.paged else None)
        self.owner_name = owner_name     # fault-plane / failure-log label
        self.queue: collections.deque = collections.deque()
        self.active: dict = {}                       # slot -> _Active
        self.results: dict = {}                      # req_id -> RequestOutput
        self.n_steps = 0                             # step() calls so far
        if plan is not None:
            self._param_shardings = plan.param_shardings(model)
            prefill_len = (self.pool.padded_len if self.paged
                           else self.pool.max_len)
            self._prefill_cache_shardings = plan.cache_shardings(
                model, model.make_cache(1, prefill_len, abstract=True))
            if self._params is not None:
                # warm params place once; forked sessions place on resolve
                self._params = jax.device_put(self._params,
                                              self._param_shardings)
        if prefill_fn is None or decode_fn is None or (
                prefill_from_fn is None and self.paged):
            if plan is not None:
                default_p, default_pf, default_d = sharded_serve_fns(
                    model, self.pool, plan, donate_cache=donate_cache,
                    with_adapters=adapter_bank is not None)
            else:
                default_p, default_pf, default_d = serve_fns(
                    model, self.paged,
                    self.pool.page_size if self.paged else 0,
                    donate_cache=donate_cache,
                    with_adapters=adapter_bank is not None)
            prefill_fn = prefill_fn or default_p
            prefill_from_fn = prefill_from_fn or default_pf
            decode_fn = decode_fn or default_d
        self.prefill_fn = prefill_fn
        self.prefill_from_fn = prefill_from_fn
        self.decode_fn = decode_fn
        # per-function prefix index: admission matches each prompt against
        # the baked/cached prefixes and serves the hit from shared pages
        self.prefix_index = prefix_index
        # round suffix-prefill lengths up to the next page multiple (by
        # shrinking the reuse) so every hit lands on a pre-compilable
        # bucket instead of a per-length lazy jit trace
        self.bucket_suffix = bucket_suffix
        # chunked prefill: prompts longer than this many tokens past their
        # reused prefix prefill chunk-by-chunk inside the step loop (page
        # multiple so every chunk hits the prewarmed prefill_from buckets);
        # None — or a non-paged pool, whose recurrent state has no
        # position-addressable suffix prefill — keeps whole-prompt prefill
        self.chunk_tokens = None
        if chunk_tokens is not None and self.paged:
            ps = self.pool.page_size
            self.chunk_tokens = max(ps, ps * -(-int(chunk_tokens) // ps))
        # per-slot feedback state (free slots decode position 0 / token 0;
        # their logits are computed and discarded)
        self._tok = np.zeros((n_slots, 1), np.int32)
        self._pos = np.zeros((n_slots,), np.int32)
        # per-slot adapter ids (0 = null adapter: free/foreign slots and
        # base-model requests gather a zero delta)
        self._aid = np.zeros((n_slots,), np.int32)
        self._step_tokens = 0            # work done by the last step()
        self._step_counts = (0, 0, 0)    # its (live, admitted, chunked)

    # ------------------------------------------------------------------
    def params(self):
        """Full params (a session blocks on its outstanding transfers)."""
        if self._params is None:
            self._params = self.session.params()
            if self.plan is not None:
                # leaves streamed whole already carry their NamedSharding;
                # stacked per-layer slices get their final placement here
                self._params = jax.device_put(self._params,
                                              self._param_shardings)
        return self._params

    @property
    def n_pending(self) -> int:
        return len(self.queue) + len(self.active)

    def set_adapter(self, idx: int, adapter, alpha: float = 1.0) -> None:
        """Load a LoRA checkpoint into bank row ``idx`` (functional
        update: in-flight steps keep the bank they were called with)."""
        from repro.models.adapters import load_adapter
        if self.adapter_bank is None:
            raise ValueError("engine was built without an adapter bank")
        fault_point("adapter_load", f"row={idx}")
        self.adapter_bank = load_adapter(self.adapter_bank, idx, adapter,
                                         self.model, alpha=alpha)

    # ------------------------------------------------------------------
    def submit(self, prompt, max_new_tokens: int = 8,
               submit_s: Optional[float] = None,
               temperature: float = 0.0, top_p: float = 1.0,
               seed: int = 0, deadline_s: Optional[float] = None,
               priority: int = 0,
               token_cb: Optional[Callable] = None,
               adapter_id: int = 0, req_id: Optional[int] = None) -> int:
        """Enqueue one request; returns its id (``req_id``, or a fresh one
        from ``new_req_id``).  ``submit_s`` backdates the arrival stamp so
        work done on the request's behalf before enqueueing (forking this
        engine's session, say) counts toward its TTFT.  ``temperature=0``
        decodes greedily (the bit-parity reference); otherwise tokens are
        drawn temperature/top-p with a per-request ``seed`` (deterministic
        across runs and engines).

        ``deadline_s`` is a queueing budget relative to ``submit_s``: a
        request still queued when it expires is SHED (status ``'shed'``,
        no prefill consumed) instead of admitted late.  ``priority`` ranks
        admission (higher first, FIFO within a rank).  ``token_cb`` is
        called as ``token_cb(req_id, token, index)`` the moment each token
        is sampled — the gateway's streaming bridge.  ``adapter_id``
        selects the request's row of the engine's adapter bank (0 = the
        base model / null adapter)."""
        prompt = np.asarray(prompt, np.int32).reshape(-1)
        if max_new_tokens < 1:
            raise ValueError("max_new_tokens must be >= 1")
        if adapter_id:
            from repro.models.adapters import bank_n_adapters
            if self.adapter_bank is None:
                raise ValueError(
                    "adapter_id set but the engine has no adapter bank")
            if not (0 <= adapter_id < bank_n_adapters(self.adapter_bank)):
                raise ValueError(f"adapter_id {adapter_id} out of range")
        if temperature < 0 or not (0 < top_p <= 1):
            raise ValueError("need temperature >= 0 and 0 < top_p <= 1")
        if len(prompt) + max_new_tokens > self.pool.max_len:
            raise ValueError(
                f"prompt({len(prompt)}) + max_new({max_new_tokens}) exceeds "
                f"pool max_len={self.pool.max_len}")
        if self.paged:
            # reject what could NEVER be admitted (undersized arena) so the
            # step loop can't hang waiting for pages that don't exist
            need = self.pool.blocks_for(len(prompt) + max_new_tokens)
            if need > self.pool.n_pages - 1:
                raise ValueError(
                    f"request needs {need} KV pages but the arena has only "
                    f"{self.pool.n_pages - 1} allocatable pages")
        rid = new_req_id() if req_id is None else req_id
        self.queue.append(Request(rid, prompt, max_new_tokens,
                                  submit_s or time.perf_counter(),
                                  temperature=temperature, top_p=top_p,
                                  seed=seed, deadline_s=deadline_s,
                                  priority=priority, token_cb=token_cb,
                                  adapter_id=adapter_id))
        return rid

    def cancel(self, req_id: int) -> bool:
        """Cancel one request wherever it is in its lifecycle.

        Queued: removed without ever prefilling.  Active: the slot retires
        mid-flight — its pages (including aliased shared-prefix pages)
        return to the pool refcount-safely via the normal release path —
        and the tokens emitted so far are kept in the ``'cancelled'``
        result.  Returns False when the request already finished (or was
        never submitted here): too late to cancel."""
        for req in self.queue:
            if req.req_id == req_id:
                self.queue.remove(req)
                self._record_dropped(req, "cancelled")
                return True
        for slot, st in list(self.active.items()):
            if st.req.req_id == req_id:
                self._retire(slot, status="cancelled")
                return True
        return False

    # ------------------------------------------------------------------
    def _prefix_hit(self, req: Request):
        """Resolve (and cache) the request's longest usable cached prefix.

        Re-validated at admission: a handle released after matching falls
        back to full prefill instead of failing the admission."""
        if req.prefix_hit is _UNMATCHED:
            req.prefix_hit = None
            if self.paged and self.prefix_index is not None:
                req.prefix_hit = self.prefix_index.match(req.prompt)
            if req.prefix_hit is not None and (
                self.bucket_suffix or self.chunk_tokens is not None):
                # shrink the reuse so the suffix length lands on a page
                # multiple: the handful of re-prefilled cached tokens is
                # far cheaper than a per-length lazy compile of
                # ``prefill_from`` (the deploy prewarm covers exactly the
                # page-multiple buckets)
                handle, reuse = req.prefix_hit
                ps = self.pool.page_size
                pad = (reuse - len(req.prompt)) % ps
                if pad:
                    reuse -= pad
                    req.prefix_hit = (handle, reuse) if reuse >= 1 else None
        if req.prefix_hit is not None and not req.prefix_hit[0].pinned:
            req.prefix_hit = None            # stale handle: full prefill
        return req.prefix_hit

    def _chunked(self, req: Request, reuse: int) -> bool:
        """True when the request's uncached suffix prefills chunk-by-chunk
        instead of in one shot at admission."""
        return (self.chunk_tokens is not None
                and len(req.prompt) - reuse > self.chunk_tokens)

    def _can_admit(self, req: Request) -> bool:
        if self.paged:
            hit = self._prefix_hit(req)
            reuse = hit[1] if hit else 0
            total = len(req.prompt) + req.max_new_tokens
            if self._chunked(req, reuse):
                # chunked admission reserves only the FIRST chunk's pages;
                # the budget grows chunk-by-chunk (full worst case before
                # the final chunk) so a long cold prompt no longer starves
                # short requests of pages at admission time
                total = reuse + self.chunk_tokens
            return self.pool.can_admit(total, reuse_len=reuse)
        return bool(self.pool.n_free)

    def _record_dropped(self, req: Request, status: str,
                        error: Optional[str] = None) -> None:
        """Result for a request that never reached (or left) a slot."""
        elapsed = time.perf_counter() - req.submit_s
        self.results[req.req_id] = RequestOutput(
            req_id=req.req_id, tokens=np.zeros(0, np.int32),
            prompt_len=len(req.prompt), n_generated=0,
            ttft_s=elapsed, e2e_s=elapsed, status=status, error=error)

    def _shed_expired(self, now: float) -> None:
        """Deadline-expired QUEUED requests are shed — a typed terminal
        status instead of a late prefill nobody is waiting for (in-flight
        requests are never shed: their prefill is already spent)."""
        for req in [r for r in self.queue if r.deadline_s is not None
                    and now - r.submit_s > r.deadline_s]:
            self.queue.remove(req)
            self._record_dropped(req, "shed")

    def _queue_head(self) -> Request:
        """Admission order: highest priority first, FIFO within a rank."""
        return max(self.queue, key=lambda r: (r.priority, -r.req_id))

    def _next_admission(self) -> Optional[Request]:
        """The queue head if it fits now.  The head BLOCKS lower ranks
        while it does not fit (no bypass: a stream of small requests must
        not starve a large one)."""
        if not self.queue:
            return None
        head = self._queue_head()
        return head if self._can_admit(head) else None

    def _call_prefill(self, inputs, cache, adapter_id: int):
        """Whole-prompt prefill, threading the adapter bank when present."""
        if self.adapter_bank is None:
            return self.prefill_fn(self.params(), inputs, cache)
        aids = jnp.asarray([adapter_id], jnp.int32)
        return self.prefill_fn(self.params(), inputs, cache,
                               self.adapter_bank, aids)

    def _call_prefill_from(self, toks, cache, offset: int, adapter_id: int):
        """Suffix-only prefill, threading the adapter bank when present."""
        if self.adapter_bank is None:
            return self.prefill_from_fn(self.params(), toks, cache,
                                        jnp.int32(offset))
        aids = jnp.asarray([adapter_id], jnp.int32)
        return self.prefill_from_fn(self.params(), toks, cache,
                                    jnp.int32(offset),
                                    self.adapter_bank, aids)

    def _kmesh(self):
        """Kernel-mesh scope for streamed (per-block-jitted) prefills, so
        in-model sharding constraints see the plan's mesh exactly like the
        monolithic serve fns do."""
        return (use_kernel_mesh(self.plan.mesh) if self.plan is not None
                else contextlib.nullcontext())

    def _sample_first(self, req: Request, logits) -> int:
        with jax.profiler.TraceAnnotation("tidal.sample_first",
                                          req_id=req.req_id):
            if req.temperature <= 0:
                tok = sample_greedy(logits)
                tok.block_until_ready()
                return int(tok[0])
            return sample_token(np.asarray(logits[0]), req.temperature,
                                req.top_p, req.seed, 0)

    def _admit(self, req: Request) -> None:
        # injection point BEFORE any allocation: a crash here leaves no
        # slot or page behind for teardown to account for
        fault_point("prefill_chunk",
                    f"admit:req={req.req_id}:len={len(req.prompt)}")
        hit = self._prefix_hit(req) if self.paged else None
        reuse = hit[1] if hit else 0
        if self.paged and self._chunked(req, reuse):
            # chunked admission: reserve only the first chunk's pages and
            # park the slot mid-prefill — the step loop advances its
            # cursor chunk-by-chunk alongside everyone else's decode.
            # Until then the slot rides the shared decode batch as a
            # dummy: token 0 written at the LAST padded position, whose
            # page stays unmapped while the cursor is short of the prompt,
            # so the write lands on the null page and the logits row is
            # discarded exactly like a free slot's.
            slot = self.pool.alloc(len(req.prompt), req.max_new_tokens,
                                   shared_prefix=hit[0] if hit else None,
                                   reuse_len=reuse,
                                   budget_tokens=reuse + self.chunk_tokens,
                                   owner=self._owner)
            self._tok[slot, 0] = 0
            self._pos[slot] = self.pool.padded_len - 1
            self._aid[slot] = req.adapter_id
            self.active[slot] = _Active(req=req, slot=slot, tokens=[],
                                        streamed=False, ttft_s=0.0,
                                        reused_prefix_len=reuse,
                                        cursor=reuse, prefilling=True)
            return
        if self.paged:
            slot = self.pool.alloc(len(req.prompt), req.max_new_tokens,
                                   shared_prefix=hit[0] if hit else None,
                                   reuse_len=reuse, owner=self._owner)
        else:
            slot = self.pool.alloc()
        try:
            self._prefill_into(req, slot, reuse)
        except BaseException:
            # crash between alloc and active-registration: hand the slot
            # (and its pages, prefix refcounts included) straight back so
            # engine teardown has nothing unaccounted to leak
            if self.paged:
                self.pool.release(slot, owner=self._owner)
            else:
                self.pool.release(slot)
            raise

    def _prefill(self, req: Request, slot: int, reuse: int,
                 streamed: bool) -> tuple:
        """(last-token logits, filled batch-1 dense cache) of the prompt
        past its ``reuse`` reused tokens, which ``slot`` holds."""
        if reuse:
            # suffix-only prefill: gather the slot's pages (aliased prefix
            # + its COW partial copy) as the working dense cache, then run
            # only the uncached tokens at offset positions
            cache = self.pool.read_slot_full(slot)
            suffix = jnp.asarray(req.prompt[None, reuse:])
            if streamed:
                with self._kmesh():
                    return streamed_prefill(self.session, {"tokens": suffix},
                                            cache, offset=reuse)
            return self._call_prefill_from(suffix, cache, reuse,
                                           req.adapter_id)
        inputs = {"tokens": jnp.asarray(req.prompt[None, :])}
        # prefill runs on a transient batch-1 dense cache either way (same
        # executable as the dense path); paged pools then keep only the
        # prompt's pages
        cache = self.model.make_cache(
            1, self.pool.padded_len if self.paged else self.pool.max_len)
        if self.plan is not None:
            cache = jax.device_put(cache, self._prefill_cache_shardings)
        if not streamed:
            return self._call_prefill(inputs, cache, req.adapter_id)
        with self._kmesh():
            logits, cache = streamed_prefill(self.session, inputs, cache)
        if self.plan is not None:
            # per-block jits leave GSPMD-propagated shardings on the filled
            # cache; re-pin to the pool's layout so the decode
            # executable's in_shardings match
            cache = jax.device_put(cache, self._prefill_cache_shardings)
        return logits, cache

    def _prefill_into(self, req: Request, slot: int, reuse: int) -> None:
        """Whole-prompt (or suffix-only) prefill into an allocated slot."""
        streamed = (self.session is not None and self._params is None
                    and self.adapter_bank is None
                    and supports_streamed_prefill(self.model))
        with jax.profiler.TraceAnnotation(
                "tidal.prefill", req_id=req.req_id,
                tokens=len(req.prompt) - reuse, streamed=int(streamed)):
            logits, cache = self._prefill(req, slot, reuse, streamed)
        first = self._sample_first(req, logits)
        ttft = time.perf_counter() - req.submit_s
        if self.paged:
            self.pool.write_suffix(slot, cache, reuse, len(req.prompt),
                                   owner=self._owner, req_id=req.req_id)
            self._aid[slot] = req.adapter_id
        else:
            self.pool.write_slot(slot, cache)
        self._tok[slot, 0] = first
        # next decode writes the first generated token at position len(prompt)
        self._pos[slot] = len(req.prompt)
        st = _Active(req=req, slot=slot, tokens=[first],
                     streamed=streamed, ttft_s=ttft,
                     reused_prefix_len=reuse)
        self.active[slot] = st
        if req.token_cb is not None:
            req.token_cb(req.req_id, first, 0)
        if len(st.tokens) >= req.max_new_tokens:
            self._retire(slot)

    def _run_chunk(self, slot: int) -> int:
        """Advance one mid-prefill slot by up to ``chunk_tokens`` prompt
        tokens: gather the slot's pages as the working dense cache, run
        ``prefill_from`` at the cursor's offset, scatter the chunk's pages
        back.  Returns the tokens processed — 0 when the pool cannot
        extend the slot's page budget yet (retried next step)."""
        st = self.active[slot]
        req = st.req
        # injection point with the slot parked mid-prefill: first-chunk
        # pages (and any extend_budget reservations) are held, so a crash
        # here exercises the full partition-teardown accounting
        fault_point("prefill_chunk",
                    f"chunk:req={req.req_id}:cursor={st.cursor}")
        P = len(req.prompt)
        ps = self.pool.page_size
        rem = P - st.cursor
        final = rem <= self.chunk_tokens
        if final:
            # decode invariant: the FULL worst-case budget must be
            # reserved before the first generated token exists, so
            # ensure_len during decode can never fail
            if not self.pool.extend_budget(slot, P + req.max_new_tokens,
                                           owner=self._owner):
                return 0
            # re-run back to the last page boundary so the chunk length
            # stays a page multiple (the prewarmed bucket shapes);
            # re-prefilled tokens rewrite their own pages with identical
            # values — greedy output is bit-identical
            start = max(st.reused_prefix_len, P - ps * -(-rem // ps))
            end = P
        else:
            start = st.cursor
            end = st.cursor + self.chunk_tokens
            if not self.pool.extend_budget(slot, end, owner=self._owner):
                return 0
        cache = self.pool.read_slot_full(slot)
        toks = jnp.asarray(req.prompt[None, start:end])
        streamed = (self.session is not None and self._params is None
                    and self.adapter_bank is None
                    and supports_streamed_prefill(self.model))
        with jax.profiler.TraceAnnotation(
                "tidal.prefill", req_id=req.req_id, tokens=end - start,
                streamed=int(streamed)):
            if streamed:
                with self._kmesh():
                    logits, cache = streamed_prefill(
                        self.session, {"tokens": toks}, cache, offset=start)
            else:
                logits, cache = self._call_prefill_from(
                    toks, cache, start, req.adapter_id)
        self.pool.write_suffix(slot, cache, start, end, owner=self._owner,
                               req_id=req.req_id)
        st.streamed = st.streamed or streamed
        st.cursor = end
        if final:
            first = self._sample_first(req, logits)
            st.ttft_s = time.perf_counter() - req.submit_s
            st.prefilling = False
            st.tokens.append(first)
            self._tok[slot, 0] = first
            # next decode writes the first generated token at len(prompt)
            self._pos[slot] = P
            if req.token_cb is not None:
                req.token_cb(req.req_id, first, 0)
            if len(st.tokens) >= req.max_new_tokens:
                self._retire(slot)
        return end - start

    def _retire(self, slot: int, status: str = "done",
                error: Optional[str] = None) -> None:
        st = self.active.pop(slot)
        if self.paged:
            self.pool.release(slot, owner=self._owner)
        else:
            self.pool.release(slot)
        self._tok[slot, 0] = 0
        self._pos[slot] = 0
        self._aid[slot] = 0
        e2e = time.perf_counter() - st.req.submit_s
        self.results[st.req.req_id] = RequestOutput(
            req_id=st.req.req_id,
            tokens=np.asarray(st.tokens, np.int32),
            prompt_len=len(st.req.prompt),
            n_generated=len(st.tokens),
            # a slot cancelled/failed mid-prefill never emitted a token
            ttft_s=st.ttft_s if st.tokens else e2e,
            e2e_s=e2e,
            streamed_prefill=st.streamed,
            reused_prefix_len=st.reused_prefix_len,
            status=status, error=error)

    # ------------------------------------------------------------------
    def _foreign_slots(self) -> int:
        """Slots of the pool allocated by a DIFFERENT engine (shared-pool
        runtimes lend one arena to several engines)."""
        if self.paged:
            return self.pool.n_foreign_slots(self._owner)
        return (self.pool.n_slots - self.pool.n_free) - len(self.active)

    def step(self) -> bool:
        """One MIXED batched step: admit what fits, advance mid-prefill
        cursors by up to ``chunk_tokens`` prompt tokens, run one batched
        decode over the slots past their prompt, retire the finished.

        Returns False once the engine is fully drained."""
        self.n_steps += 1
        self._step_counts = (0, 0, 0)
        with jax.profiler.TraceAnnotation("tidal.step",
                                          rows=len(self._pos)) as span:
            alive = self._step()
            live, admitted, chunked = self._step_counts
            span.set_metadata(live=live, admitted=admitted, chunked=chunked)
        return alive

    def _step(self) -> bool:
        if self.queue or self.active:
            # injection point before any work or allocation this step
            fault_point("engine_step",
                        f"{self.owner_name or 'engine'}:"
                        f"pending={self.n_pending}")
        if (self.queue or self.active) and not self.paged:
            # a DENSE pool's batched decode advances EVERY slot's
            # recurrent state — there is no masked view that protects a
            # co-tenant's slot — so dense-pool engines still borrow the
            # arena exclusively.  (Paged engines decode under their
            # owner-masked page table: foreign slots are null-page
            # dummies, and co-residency is the normal state.)
            foreign = self._foreign_slots()
            if foreign > 0:
                raise RuntimeError(
                    f"shared KV pool: {foreign} slot(s) held by another "
                    "engine; drain or evict it before decoding here "
                    "(dense-pool engines borrow the arena exclusively)")
        self._shed_expired(time.perf_counter())
        self._step_tokens = 0
        admitted = 0
        while True:
            head = self._next_admission()
            if head is None:
                break
            self.queue.remove(head)
            self._admit(head)
            admitted += 1
        chunked = 0
        if self.chunk_tokens is not None:
            # chunk phase: spend up to chunk_tokens prompt tokens across
            # the mid-prefill slots, oldest request first (one admission's
            # worth of prefill work per step, whoever it belongs to)
            budget = self.chunk_tokens
            for slot in sorted(
                    (s for s in self.active if self.active[s].prefilling),
                    key=lambda s: self.active[s].req.req_id):
                if budget <= 0:
                    break
                n = self._run_chunk(slot)
                budget -= n
                chunked += n
        decoding = [s for s in self.active if not self.active[s].prefilling]
        self._step_counts = (len(decoding), admitted, chunked)
        if decoding:
            # injection point at the decode-quantum boundary: active slots
            # hold their full reserved budgets, results are partial
            fault_point("decode_quantum",
                        f"{self.owner_name or 'engine'}:n={len(decoding)}")
        if not decoding:
            if not self.active:
                if self.queue:
                    if self.paged and self._foreign_slots() > 0:
                        # co-tenants hold arena pages: their retirements
                        # can still free capacity for this queue, so this
                        # is back-pressure, not a livelock — yield the
                        # quantum and retry after they run.
                        self._step_tokens = chunked
                        return True
                    # the pool is completely idle (no active slots here, no
                    # foreign slots) yet the head request still does not
                    # fit: nothing can ever retire to unblock it — only
                    # pinned prefix pages occupy the arena — so looping
                    # would livelock.  Drop the doomed request (the queue
                    # behind it stays servable) and surface the error.
                    head = self._queue_head()
                    self.queue.remove(head)
                    msg = (
                        f"request {head.req_id} needs more KV pages than "
                        "the idle arena can ever free (pinned prefix pages "
                        "shrink attainable capacity); use a larger arena "
                        "or release template prefixes")
                    # a 'failed' result terminates any gateway handle
                    # waiting on the dropped request; the raise surfaces
                    # the error to whoever is driving the step loop
                    self._record_dropped(head, "failed", error=msg)
                    raise PoolExhausted(msg)
                return False
            if not admitted and not chunked:
                if self.paged and self._foreign_slots() > 0:
                    # a co-tenant's decode can still retire and free pages
                    # for the wedged chunk budgets — defer the unwedge
                    # verdict until this engine alone holds the arena.
                    self._step_tokens = 0
                    return True
                # every slot is mid-prefill and none could extend its page
                # budget this step (nor could anything be admitted): the
                # chunked budgets have wedged against each other and no
                # decode can ever retire to free pages.  Fail the YOUNGEST
                # mid-prefill request — the elders keep their progress and
                # its pages unwedge them next step.
                slot = max((s for s in self.active
                            if self.active[s].prefilling),
                           key=lambda s: self.active[s].req.req_id)
                msg = (
                    f"request {self.active[slot].req.req_id} cannot grow "
                    "its chunked-prefill page budget and no decode can "
                    "free pages (all slots mid-prefill); failed to unwedge "
                    "the arena — use a larger arena or smaller chunks")
                self._retire(slot, status="failed", error=msg)
                raise PoolExhausted(msg)
            self._step_tokens = chunked
            return True
        if self.paged:
            # crossing a page boundary this step maps one more page
            # (reserved at admission, so this can never exhaust the pool);
            # mid-prefill slots skip this — their dummy position's page is
            # deliberately unmapped (null-page write)
            for slot in decoding:
                self.pool.ensure_len(slot, int(self._pos[slot]) + 1,
                                     owner=self._owner)
        with jax.profiler.TraceAnnotation("tidal.decode.dispatch"):
            logits = self._dispatch_decode()
        with jax.profiler.TraceAnnotation("tidal.decode.sync"):
            nxt = np.asarray(sample_greedy(logits))      # [n_slots]
            sampled = [s for s in decoding
                       if self.active[s].req.temperature > 0]
            if sampled:
                nxt = nxt.copy()             # jax-backed views are read-only
                rows = np.asarray(logits)
                for slot in sampled:
                    st = self.active[slot]
                    nxt[slot] = sample_token(rows[slot], st.req.temperature,
                                             st.req.top_p, st.req.seed,
                                             len(st.tokens))
        with jax.profiler.TraceAnnotation("tidal.emit"):
            for slot in decoding:
                st = self.active[slot]
                tok = int(nxt[slot])
                st.tokens.append(tok)
                self._tok[slot, 0] = tok
                self._pos[slot] += 1
                if st.req.token_cb is not None:
                    st.req.token_cb(st.req.req_id, tok, len(st.tokens) - 1)
                if len(st.tokens) >= st.req.max_new_tokens:
                    self._retire(slot)
        self._step_tokens = chunked + len(decoding)
        return bool(self.queue or self.active)

    def _dispatch_decode(self):
        """Dispatch one batched decode over every slot; returns its logits
        while the device runs on."""
        if not self.paged:
            logits, self.pool.cache = self.decode_fn(
                self.params(), self.pool.cache, jnp.asarray(self._tok),
                jnp.asarray(self._pos))
            return logits
        # the page table rides device-resident; only rows dirtied by
        # admit/grow/retire re-upload (steady-state decode sends none).
        # The OWNER-masked view nulls co-tenants' rows, so their slots
        # decode as free-slot dummies — the step never reads or writes a
        # foreign slot's pages even though it spans every slot index.
        args = (self.params(), self.pool.cache, jnp.asarray(self._tok),
                jnp.asarray(self._pos),
                self.pool.device_page_table(self._owner))
        if self.adapter_bank is not None:
            args += (self.adapter_bank, jnp.asarray(self._aid))
        logits, self.pool.cache = self.decode_fn(*args)
        return logits

    def step_n(self, n: int) -> bool:
        """Up to ``n`` steps — the gateway's scheduling quantum.  Between
        calls the engine yields control holding everything it has (slots,
        pages, queue): a quantum boundary is a scheduling point, not a
        release point.  Returns False once fully drained."""
        for _ in range(max(1, n)):
            if not self.step():
                return False
        return True

    def step_tokens(self, budget: int) -> bool:
        """Steps until at least ``budget`` tokens of work have run — the
        gateway's TOKEN quantum under chunked prefill, where a step's cost
        is its chunked prompt tokens plus its decode batch, not a request
        count.  Returns False once fully drained."""
        spent = 0
        while spent < max(1, budget):
            alive = self.step()
            spent += max(1, self._step_tokens)
            if not alive:
                return False
        return True

    def run(self) -> dict:
        """Drain queue + active set; returns {req_id: RequestOutput}."""
        while self.step():
            pass
        return self.results

    def release_all(self) -> int:
        """Abandon in-flight work: release every active slot (returning its
        pages to a paged pool) and drop queued requests.  The keep-alive
        eviction path — an engine sharing a runtime-owned pool must hand
        its slots back before it is dropped, or the arena leaks.  Returns
        the number of abandoned requests; completed results are kept and
        abandoned ones record a ``'cancelled'`` result (so a gateway
        handle waiting on them terminates instead of polling forever)."""
        n = len(self.active) + len(self.queue)
        for slot in list(self.active):
            self._retire(slot, status="cancelled")
        for req in list(self.queue):
            self._record_dropped(req, "cancelled")
        self.queue.clear()
        return n

    def close(self) -> int:
        """Tear the engine off its pool: release all in-flight work, then
        retire the engine's slot-partition lease (dropping its masked
        device page table and ownership bookkeeping).  A closed engine
        must not step again.  Returns the number of abandoned requests."""
        n = self.release_all()
        if self.paged and self._owner is not None:
            self.pool.release_owner(self._owner)
            self._owner = None
        return n
