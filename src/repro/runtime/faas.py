"""FaaS front-end over the full TIDAL stack.

The front door is the async gateway: ``submit(InvocationRequest)``
returns an :class:`~repro.runtime.gateway.InvocationHandle` ticket
(stream ``tokens()``, block ``result()``, abort ``cancel()``); the
legacy ``submit(fn_name, event, prompt)`` / ``submit_many(tuples)``
forms are thin compat shims over the same gateway with bit-identical
greedy results.

Composes the pieces the launch scripts used to glue together by hand:

  * :class:`TemplateServer` — register/fork (static reuse, dynamic replay,
    access-order streaming);
  * :class:`ExecutableCache` / :class:`ProcessPool` — §5.1 proactive code
    loading (AOT-compiled serve entry points in pre-warmed workers);
  * :class:`ContinuousBatchingEngine` — the execution layer; one warm engine
    per (function, dynamic-config) is kept alive so subsequent invocations
    skip forking entirely.

Invocation kinds mirror the cluster scheduler's service classes:

  * ``warm`` — a live engine existed: service = prefill + decode only;
  * ``fork`` — template existed, new engine forked (streamed prefill
    overlaps the weight transfers);
  * ``cold`` — first invocation of the function since deploy (pays any
    lazy compilation not covered by pre-warming, then forks).

Multi-device serving (TIDAL §6 on one host): with ``mesh=`` the runtime
splits the device mesh into one serving INSTANCE per 'data' slice, each
tensor-parallel over its slice's 'model' axis.  Every instance owns a
sharded KV arena per model (allocated once, engines borrow slots from it)
and its own jit'd serve entry points; new forks are placed by the same
locality policy :class:`~repro.core.scheduler.ClusterSim` simulates —
prefer the instance already warm for the function unless its load exceeds
the least-loaded instance by more than ``locality_max_extra_load``.

:func:`measure_service_times` turns those wall-clock measurements into a
:class:`MeasuredServiceTimes` oracle the cluster scheduler can consume via
``SchedulerConfig.measured`` — closing the sim-vs-real loop.
"""

from __future__ import annotations

import dataclasses
import time
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh

from repro.core import api as tidal
from repro.core.api import LLMFunction
from repro.core.prewarm import (ExecutableCache, ProcessPool,
                                compile_counts, listen_for_compiles)
from repro.core.template_server import TemplateServer
from repro.distributed.sharding import ShardingPlan, serving_plan
from repro.models.adapters import check_bank_config, make_adapter_bank
from repro.models.registry import get_smoke_model
from repro.runtime.continuous import (ContinuousBatchingEngine, serve_fns,
                                      sharded_serve_fns)
from repro.runtime.gateway import (InvocationGateway, InvocationHandle,
                                   InvocationRequest, SubmitResult)
from repro.runtime.kv_pool import KVCachePool, PagedKVCachePool
from repro.runtime.prefix import PrefixIndex

KINDS = ("warm", "fork", "cold")


def _engine_key(fn_name: str, event: dict) -> tuple:
    return (fn_name, tuple(sorted((event or {}).items())))


def _zero_params(model, plan: Optional[ShardingPlan]):
    """Zero-filled params for pre-warm compiles, each leaf created in its
    final placement (a model sharded over several chips is never
    materialized whole on one)."""
    abstract = model.init_params(abstract=True)
    if plan is None:
        return jax.tree.map(lambda s: jnp.zeros(s.shape, s.dtype), abstract)
    return jax.tree.map(lambda s, sh: jnp.zeros(s.shape, s.dtype, device=sh),
                        abstract, plan.param_shardings(model))


@dataclasses.dataclass
class _WarmEngine:
    engine: ContinuousBatchingEngine
    last_used_s: float
    instance: int = 0
    # shared-adapter engines: fn_name -> bank row already loaded, and the
    # next free row (0 is the null adapter, never assigned)
    adapter_ids: dict = dataclasses.field(default_factory=dict)
    next_adapter_id: int = 1


@dataclasses.dataclass
class _Instance:
    """One serving instance: a mesh slice (or the single default device)."""
    idx: int
    plan: Optional[ShardingPlan]


class FaaSRuntime:
    """Serving runtime for deployed LLM functions."""

    def __init__(self, server: Optional[TemplateServer] = None,
                 n_slots: int = 4, max_len: int = 64,
                 keep_alive_s: float = 60.0, max_warm_engines: int = 8,
                 prewarm: bool = True, pool_workers: int = 2,
                 trace_seq: int = 32, page_size: int = 8,
                 mesh: Optional[Mesh] = None,
                 locality_max_extra_load: int = 2,
                 gateway_quantum: int = 2,
                 chunk_tokens: Optional[int] = None,
                 kv_dtype: Optional[str] = None,
                 max_retries: int = 2, retry_backoff_s: float = 0.0,
                 max_live: Optional[int] = None,
                 brownout_threshold: float = 0.75,
                 brownout_max_new: Optional[int] = None):
        self.mesh = mesh
        self.locality_max_extra_load = locality_max_extra_load
        self.instances = self._make_instances(mesh)
        self.server = server or TemplateServer(trace_batch=1,
                                               trace_seq=trace_seq,
                                               plan=self.instances[0].plan)
        self.n_slots = n_slots
        self.max_len = max_len
        self.page_size = page_size
        # chunked prefill: engines split every prompt suffix longer than
        # this into page-multiple prefill_from chunks interleaved with
        # decode (None = legacy whole-prompt prefill at admission); the
        # gateway's quantum switches to the same TOKEN budget so a chunk
        # and a decode batch cost one comparable unit of schedule
        self.chunk_tokens = chunk_tokens
        # int8-quantized paged arenas (None = fp): halves resident KV
        # bytes per token; recurrent-state models keep dense fp pools
        self.kv_dtype = kv_dtype
        self.keep_alive_s = keep_alive_s
        self.max_warm_engines = max_warm_engines
        self.prewarm = prewarm
        listen_for_compiles()            # process-wide: stats()["compile"]
        self.exe_cache = ExecutableCache()
        self.workers = ProcessPool(pool_workers, self.exe_cache)
        self.functions: dict[str, LLMFunction] = {}
        self._engines: dict[tuple, _WarmEngine] = {}
        self._fn_keys: dict[str, list] = {}
        self._invoked: set = set()
        # jit'd serve entry points shared across every engine of a model on
        # one instance: a fresh fork reuses the executables earlier engines
        # compiled (the §5.1 dedup story at the engine level)
        self._serve_fns: dict[tuple, tuple] = {}
        # one KV arena per (instance, model): allocated once — sharded on
        # the instance's mesh slice — and lent to engines slot by slot;
        # eviction returns every borrowed slot/page (see ``evict``)
        self._pools: dict[tuple, object] = {}
        # template-baked prompt-prefix KV: one pinned PrefixHandle + one
        # PrefixIndex per (function, instance, event-key) — static
        # functions share one bake per instance (event-key ()); dynamic
        # functions bake lazily per event on first fork, since the baked
        # KV depends on the event's dynamic weights.  Bakes are shared by
        # every fork on that instance and survive engine eviction
        self._prefix_handles: dict[tuple, object] = {}
        self._prefix_indexes: dict[tuple, PrefixIndex] = {}
        self._baked_events: dict[str, dict] = {}
        # RUNTIME-LEARNED prefixes (control plane): hot observed prompt
        # prefixes baked after deploy, tracked separately from template
        # bakes so re-deploys and budget eviction release exactly them
        self._runtime_prefix_handles: dict[tuple, list] = {}
        # per-function service-class counters (cold/fork/warm/reuse-hit/
        # shed/...): the observation stream the control plane consumes,
        # surfaced through ``stats()``
        self.fn_stats: dict[str, dict] = {}
        # predictive prewarm control plane (attach_control_plane / the
        # ControlPlane(runtime) constructor); None = pure keep-alive decay
        self.control_plane = None
        # multi-tenant adapter serving: base functions deployed through
        # ``deploy_shared_base`` keep ONE resident engine per instance
        # whose adapter bank serves every function attached to them
        self._shared_bases: dict[str, dict] = {}
        self._adapter_fns: dict[str, tuple] = {}
        # the async front door: submit() tickets route through this loop;
        # the legacy tuple APIs are thin compat shims over it.  The
        # gateway also supervises engine crashes (max_retries bounded
        # retry with backoff) and degrades gracefully under pressure
        # (max_live bounded admission, brown-out budget clamps)
        self.gateway = InvocationGateway(
            self, quantum=gateway_quantum, quantum_tokens=chunk_tokens,
            max_retries=max_retries, retry_backoff_s=retry_backoff_s,
            max_live=max_live, brownout_threshold=brownout_threshold,
            brownout_max_new=brownout_max_new)

    @staticmethod
    def _make_instances(mesh: Optional[Mesh]) -> list:
        if mesh is None:
            return [_Instance(0, None)]
        if tuple(mesh.axis_names) != ("data", "model"):
            raise ValueError(
                "serving mesh must have axes ('data', 'model'): one "
                "instance per data slice, tensor-parallel over model "
                f"(got {mesh.axis_names})")
        out = []
        for i in range(mesh.shape["data"]):
            sub = Mesh(mesh.devices[i:i + 1], mesh.axis_names)
            out.append(_Instance(i, serving_plan(sub)))
        return out

    # ------------------------------------------------------------------
    def _pool_for(self, inst: _Instance, model) -> object:
        key = (inst.idx, id(model))
        if key not in self._pools:
            if model.supports_paged_kv:
                self._pools[key] = PagedKVCachePool(
                    model, self.n_slots, self.max_len,
                    page_size=self.page_size, plan=inst.plan,
                    kv_dtype=self.kv_dtype)
            else:
                self._pools[key] = KVCachePool(model, self.n_slots,
                                               self.max_len, plan=inst.plan)
        return self._pools[key]

    def kv_pool_stats(self) -> dict:
        """{(instance, model-key): free-slot/page counts} — the invariant
        surface for eviction tests: after every engine drains or is
        evicted, all counts are back at their initial values."""
        out = {}
        for key, pool in self._pools.items():
            if isinstance(pool, PagedKVCachePool):
                out[key] = {"n_free_slots": pool.n_free_slots,
                            "n_free_pages": pool.n_free_pages,
                            "n_available_pages": pool.n_available_pages}
            else:
                out[key] = {"n_free_slots": pool.n_free}
        return out

    def _serve_fns_for(self, fn_name: str,
                       inst: Optional[_Instance] = None) -> tuple:
        """(prefill_fn, prefill_from_fn, decode_fn) shared by every engine
        of one model on one instance (``prefill_from_fn`` — suffix-only
        prefill for prefix reuse — is None for non-paged families)."""
        inst = inst or self.instances[0]
        model = self.functions[fn_name].model
        key = (id(model), inst.idx)
        if key not in self._serve_fns:
            if inst.plan is not None:
                pool = self._pool_for(inst, model)
                self._serve_fns[key] = sharded_serve_fns(model, pool,
                                                         inst.plan)
            else:
                # attention families decode against the paged arena
                self._serve_fns[key] = serve_fns(
                    model, model.supports_paged_kv, self.page_size)
        return self._serve_fns[key]

    # ------------------------------------------------------------------
    def deploy(self, fn: LLMFunction, example_event: Optional[dict] = None,
               prewarm_seq: int = 32,
               template_prompt: Optional[object] = None) -> None:
        """Register the function's template and pre-warm its executables.

        Pre-warming compiles the ENGINE's actual serve entry points (the
        shared jit'd prefill at ``prewarm_seq`` and the pool-shaped decode)
        so the first invocation pays forking, not lazy compilation — the
        §5.1 policy.  Prompts of other lengths still compile lazily.

        ``template_prompt`` (int32 tokens) is the function's shared prompt
        prefix (system prompt / few-shot header): its KV is baked ONCE
        into pinned pages of the instance's paged arena — the template
        carries warm state, not just weights — and every invocation whose
        prompt starts with it prefills only the suffix."""
        if template_prompt is not None:
            if not fn.model.supports_paged_kv:
                raise ValueError(
                    f"{fn.name}: template prompts need a paged attention "
                    f"family (got {fn.model.cfg.family!r})")
            n_tpl = len(np.asarray(template_prompt).reshape(-1))
            if n_tpl > self.max_len - 1:
                raise ValueError(
                    f"{fn.name}: template prompt must leave room for a "
                    f"suffix within max_len={self.max_len}")
            if n_tpl < self.page_size:
                raise ValueError(
                    f"{fn.name}: template prompt of {n_tpl} tokens is "
                    f"shorter than one page ({self.page_size}) — it could "
                    "never be matched, only pin dead pages")
        # a re-deploy REPLACES the function: evict its warm engines (they
        # serve the old params, and their prefix index is shared — a new
        # bake must never mix into an old engine's serving) and drop any
        # previously baked prefix (its KV was computed under the old
        # params, in the old model's pool)
        if fn.name in self.functions:
            self.evict(fn.name)
        self.release_template_prefix(fn.name)
        self._drop_runtime_prefixes(fn.name)
        self.functions[fn.name] = fn
        self.server.register(fn, example_event or {},
                             template_prompt=template_prompt)
        if template_prompt is not None:
            self._baked_events[fn.name] = dict(example_event or {})
            # prewarm bake on the default instance; other mesh slices bake
            # lazily the first time the function forks onto them
            self._bake_template_prefix(fn.name, self.instances[0])
        if self.prewarm and not fn.model.is_encdec:
            self._fn_keys[fn.name] = self._prewarm_engine_fns(fn,
                                                              prewarm_seq)
            if template_prompt is not None or (
                    self.chunk_tokens is not None
                    and fn.model.supports_paged_kv):
                # chunked prefill runs every chunk through prefill_from at
                # a page-multiple length — the same bucket shapes the
                # suffix-reuse prewarm compiles — so chunking never pays a
                # lazy per-length jit either
                self._fn_keys[fn.name] += self._prewarm_suffix_fns(fn)
            self.workers.prewarm_for_functions(self._fn_keys)

    # ------------------------------------------------------------------
    def _prefix_key(self, fn_name: str, inst: _Instance,
                    event: Optional[dict]) -> tuple:
        """Bake identity: static functions share one bake per instance
        (their params never depend on the event); dynamic functions bake
        per event, because the event's dynamic weights change the
        template's KV."""
        fn = self.functions[fn_name]
        ekey = () if fn.static else tuple(sorted(dict(event or {}).items()))
        return (fn_name, inst.idx, ekey)

    def _bake_template_prefix(self, fn_name: str, inst: _Instance,
                              params_fn=None,
                              event: Optional[dict] = None) -> None:
        """Prefill the function's template prompt once and pin its KV
        pages in the instance's shared arena (refcount 1 held by the
        handle), registering the prefix for admission-time matching.

        ``params_fn`` lazily supplies already-forked params (the engine
        being built on the serve path) so a lazy per-(function, event)
        bake does not stream the whole model a second time; without it —
        the deploy-time prewarm — the bake forks its own session."""
        if fn_name not in self._baked_events:
            return
        if event is None:
            event = self._baked_events[fn_name]
        key = self._prefix_key(fn_name, inst, event)
        if key in self._prefix_handles:
            return
        prompt = self.server.template_prompts.get(fn_name)
        if prompt is None:
            return
        model = self.functions[fn_name].model
        pool = self._pool_for(inst, model)
        if params_fn is not None:
            params = params_fn()
        else:
            session, _ = self.server.fork(fn_name, dict(event),
                                          plan=inst.plan)
            params = session.params()
            if inst.plan is not None:
                params = jax.device_put(params,
                                        inst.plan.param_shardings(model))
        prefill_fn = self._serve_fns_for(fn_name, inst)[0]
        cache = model.make_cache(1, pool.padded_len)
        if inst.plan is not None:
            cache = jax.device_put(
                cache, inst.plan.cache_shardings(model, cache))
        _, cache = prefill_fn(params, {"tokens": jnp.asarray(prompt[None, :])},
                              cache)
        handle = pool.bake_prefix(cache, prompt)
        index = self._prefix_indexes.setdefault(key,
                                                PrefixIndex(self.page_size))
        index.register(handle)
        self._prefix_handles[key] = handle

    def _prefix_index_for(self, fn_name: str, event: Optional[dict],
                          inst: _Instance,
                          params_fn=None) -> Optional[PrefixIndex]:
        """The prefix index an engine of (function, event) may consult.

        Baked KV is params-specific: engines of a *static* function all
        share one bake; a DYNAMIC function bakes lazily per (event,
        instance) on the first fork of that event — reusing the fork's
        own params via ``params_fn`` — so every engine serves its
        template suffix-only, not just the deploy-time example event.

        Runtime-LEARNED prefixes live in the same per-key index, so a
        function without any template still gets an index once the
        control plane bakes an observed hot prefix for it — and a fresh
        fork picks the learned bakes up immediately."""
        if fn_name in self._baked_events:
            self._bake_template_prefix(fn_name, inst, params_fn=params_fn,
                                       event=event)
        return self._prefix_indexes.get(
            self._prefix_key(fn_name, inst, event))

    def release_template_prefix(self, fn_name: str) -> int:
        """Unpin the function's baked prefix pages on every instance (they
        free once no live slot aliases them) and STOP baking: later
        invocations take the full-prefill path until a re-deploy with a
        template prompt opts back in.  Returns handles dropped."""
        self._baked_events.pop(fn_name, None)
        keys = [k for k in self._prefix_handles if k[0] == fn_name]
        for k in keys:
            handle = self._prefix_handles.pop(k)
            index = self._prefix_indexes.get(k)
            if index is not None:
                index.unregister(handle)
            handle.pool.release_prefix(handle)
        return len(keys)

    # ------------------------------------------------------------------
    # runtime-learned prefixes + predictive prewarm (control-plane hooks)
    # ------------------------------------------------------------------
    def attach_control_plane(self, control_plane) -> None:
        """Bind a ControlPlane: the gateway starts feeding it arrivals/
        completions and ticking its actuators, and ``_prune`` consults
        its predictive per-function keep-alive."""
        control_plane.bind(self)

    def runtime_prefix_nbytes(self, fn_name: str, n_tokens: int) -> int:
        """Pinned bytes a runtime bake of ``n_tokens`` would cost on the
        function's preferred instance (the control plane budgets BEFORE
        baking, so the pinned-bytes cap is never overshot)."""
        model = self.functions[fn_name].model
        pool = self._pool_for(self._pick_instance(fn_name), model)
        return pool.blocks_for(n_tokens) * pool.page_nbytes()

    def _params_for_bake(self, fn_name: str, inst: _Instance, ekey: tuple,
                         event: dict):
        """Params to prefill a runtime bake under: a live warm engine's
        (free — static functions accept any event's engine) or a fresh
        fork's (streams the weights once)."""
        fn = self.functions[fn_name]
        for k, w in self._engines.items():
            if k[0] != fn_name or w.instance != inst.idx:
                continue
            if fn.static or k[1] == ekey:
                return w.engine.params()
        session, _ = self.server.fork(fn_name, dict(event), plan=inst.plan)
        params = session.params()
        if inst.plan is not None:
            params = jax.device_put(params,
                                    inst.plan.param_shardings(fn.model))
        return params

    def bake_runtime_prefix(self, fn_name: str, tokens,
                            event: Optional[dict] = None):
        """Bake an OBSERVED hot prompt prefix into pinned arena pages.

        The learned-prefix analogue of ``_bake_template_prefix``: prefill
        ``tokens`` (page-aligned, >= one page, leaving suffix room within
        ``max_len``) once, pin the pages (refcount 1 on the handle) and
        register them in the function's per-(instance, event-key)
        PrefixIndex — live warm engines of the same bake identity start
        matching immediately; later forks pick the index up through
        ``_prefix_index_for``.  Returns the PrefixHandle, or None when an
        existing bake (template or learned) already covers ``tokens``."""
        if fn_name not in self.functions:
            raise KeyError(f"function {fn_name!r} is not deployed")
        if fn_name in self._adapter_fns:
            raise ValueError(
                f"{fn_name}: adapter functions share a mixed-adapter "
                "engine; their baked KV would be adapter-specific")
        fn = self.functions[fn_name]
        if not fn.model.supports_paged_kv:
            raise ValueError(
                f"{fn_name}: runtime prefixes need a paged attention "
                f"family (got {fn.model.cfg.family!r})")
        tokens = np.asarray(tokens, np.int32).reshape(-1)
        n = len(tokens)
        if n < self.page_size or n % self.page_size:
            raise ValueError(
                f"{fn_name}: runtime prefix length {n} must be a "
                f"non-zero multiple of the page size ({self.page_size})")
        if n > self.max_len - 1:
            raise ValueError(
                f"{fn_name}: runtime prefix of {n} tokens leaves no "
                f"suffix room within max_len={self.max_len}")
        event = dict(event or {})
        inst = self._pick_instance(fn_name)
        key = self._prefix_key(fn_name, inst, event)
        index = self._prefix_indexes.get(key)
        if index is not None:
            # probe with one sentinel token appended: a full-length match
            # (reuse == n) means some existing bake already covers every
            # token of this prefix — re-baking would only pin dead pages
            probe = np.concatenate([tokens, np.asarray([-1], np.int32)])
            hit = index.match(probe)
            if hit is not None and hit[1] >= n:
                return None
        model = fn.model
        pool = self._pool_for(inst, model)
        params = self._params_for_bake(fn_name, inst, key[2], event)
        prefill_fn = self._serve_fns_for(fn_name, inst)[0]
        cache = model.make_cache(1, pool.padded_len)
        if inst.plan is not None:
            cache = jax.device_put(
                cache, inst.plan.cache_shardings(model, cache))
        _, cache = prefill_fn(params, {"tokens": jnp.asarray(tokens[None, :])},
                              cache)
        handle = pool.bake_prefix(cache, tokens)
        index = self._prefix_indexes.setdefault(key,
                                                PrefixIndex(self.page_size))
        index.register(handle)
        self._runtime_prefix_handles.setdefault(key, []).append(handle)
        for k, w in self._engines.items():
            if k[0] != fn_name or w.instance != inst.idx:
                continue
            if (() if fn.static else k[1]) == key[2]:
                w.engine.prefix_index = index
        return handle

    def release_runtime_prefix(self, handle) -> None:
        """Evict one learned prefix: unregister it from matching and drop
        its pin.  Pages a live slot still borrows survive until that last
        borrower releases (refcounts defer the reclaim); fresh requests
        stop matching it immediately."""
        for key in list(self._runtime_prefix_handles):
            handles = self._runtime_prefix_handles[key]
            if not any(h is handle for h in handles):
                continue
            handles[:] = [h for h in handles if h is not handle]
            if not handles:
                del self._runtime_prefix_handles[key]
            index = self._prefix_indexes.get(key)
            if index is not None:
                index.unregister(handle)
            break
        if handle.pinned:
            handle.pool.release_prefix(handle)

    def _drop_runtime_prefixes(self, fn_name: Optional[str] = None) -> int:
        """Release every learned prefix of ``fn_name`` (or all): their KV
        was computed under params a re-deploy is about to replace."""
        keys = [k for k in self._runtime_prefix_handles
                if fn_name is None or k[0] == fn_name]
        n = 0
        for key in keys:
            for handle in self._runtime_prefix_handles.pop(key):
                index = self._prefix_indexes.get(key)
                if index is not None:
                    index.unregister(handle)
                if handle.pinned:
                    handle.pool.release_prefix(handle)
                n += 1
        return n

    def prewarm_function(self, fn_name: str, event: Optional[dict] = None,
                         now: Optional[float] = None) -> bool:
        """Pre-fork an engine AHEAD of a forecast arrival so the next
        invocation lands warm.  Returns True when a new engine was
        actually created (False = one was already resident)."""
        now = time.perf_counter() if now is None else now
        if fn_name not in self.functions:
            raise KeyError(f"function {fn_name!r} is not deployed")
        n_before = len(self._engines)
        self._engine_for(fn_name, event, now)
        return len(self._engines) > n_before

    def _count(self, fn_name: str, field: str, n: int = 1) -> None:
        """Bump one per-function service-class counter."""
        d = self.fn_stats.setdefault(fn_name, {})
        d[field] = d.get(field, 0) + n

    def stats(self) -> dict:
        """Observability snapshot: per-function service-class counters
        (cold/fork/warm admission kinds, terminal done/reuse_hits/shed/
        failed/cancelled/rejected) with derived rates, the gateway's
        supervision stats, the process's compile events (``"compile"``:
        ``{(event, fun_name): (count, seconds)}``, see
        ``core.prewarm.compile_counts``) and — when attached — the control
        plane's."""
        fns = {}
        for fn_name, c in self.fn_stats.items():
            d = dict(c)
            admitted = sum(c.get(k, 0) for k in KINDS)
            d["admitted"] = admitted
            if admitted:
                d["warm_rate"] = c.get("warm", 0) / admitted
                d["cold_start_rate"] = (c.get("fork", 0)
                                        + c.get("cold", 0)) / admitted
            if c.get("done"):
                d["reuse_hit_rate"] = c.get("reuse_hits", 0) / c["done"]
            fns[fn_name] = d
        out = {"functions": fns, "gateway": dict(self.gateway.stats),
               "compile": compile_counts()}
        if self.control_plane is not None:
            out["control_plane"] = dict(self.control_plane.stats)
        return out

    def _prewarm_engine_fns(self, fn: LLMFunction, seq: int) -> list:
        """Populate the jit caches of this model's shared serve fns by
        running them once on zero-filled inputs, accounting the compiles
        in the ExecutableCache (dedup'd across functions of one model,
        per serving instance — each mesh slice has its own executables)."""
        model = fn.model
        paged = model.supports_paged_kv
        # shape bookkeeping mirrors PagedKVCachePool's defaults so the
        # pre-warmed executables are exactly the ones engines will call
        bps = -(-self.max_len // self.page_size)
        prefill_len = bps * self.page_size if paged else self.max_len
        keys = []
        for inst in self.instances:
            prefill_fn, _, decode_fn = self._serve_fns_for(fn.name, inst)
            kp = (id(model), "prefill", inst.idx, 1, seq, self.max_len)
            kd = (id(model), "decode-pool", inst.idx, self.n_slots,
                  self.max_len)

            def warm_prefill(inst=inst, prefill_fn=prefill_fn):
                params = _zero_params(model, inst.plan)
                inputs = {"tokens": jnp.zeros((1, seq), jnp.int32)}
                cache = model.make_cache(1, prefill_len)
                if inst.plan is not None:
                    cache = jax.device_put(
                        cache, inst.plan.cache_shardings(model, cache))
                jax.block_until_ready(prefill_fn(params, inputs, cache))
                return prefill_fn

            def warm_decode(inst=inst, decode_fn=decode_fn):
                params = _zero_params(model, inst.plan)
                toks = jnp.zeros((self.n_slots, 1), jnp.int32)
                pos = jnp.zeros((self.n_slots,), jnp.int32)
                if paged:
                    cache = model.make_paged_cache(1 + self.n_slots * bps,
                                                   self.page_size,
                                                   kv_dtype=self.kv_dtype)
                    if inst.plan is not None:
                        cache = jax.device_put(
                            cache,
                            inst.plan.paged_cache_shardings(model, cache))
                    pt = jnp.zeros((self.n_slots, bps), jnp.int32)
                    jax.block_until_ready(
                        decode_fn(params, cache, toks, pos, pt))
                else:
                    cache = model.make_cache(self.n_slots, self.max_len)
                    if inst.plan is not None:
                        cache = jax.device_put(
                            cache, inst.plan.cache_shardings(model, cache))
                    jax.block_until_ready(decode_fn(params, cache, toks, pos))
                return decode_fn

            self.exe_cache.get_or_compile(kp, warm_prefill)
            self.exe_cache.get_or_compile(kd, warm_decode)
            keys += [kp, kd]
        return keys

    def _prewarm_suffix_fns(self, fn: LLMFunction) -> list:
        """Pre-compile the suffix-only prefill at every PAGE-MULTIPLE
        suffix length.  The engine buckets each reuse hit onto exactly
        these shapes (``bucket_suffix``: the reuse shrinks by up to a
        page so the suffix rounds up to a page multiple), so a
        reused-prefix invocation's first hit pays forking, never a lazy
        per-length compile.  ``offset`` is traced — one executable per
        bucket covers every reuse length."""
        model = fn.model
        if not model.supports_paged_kv:
            return []
        ps = self.page_size
        bps = -(-self.max_len // ps)
        padded = bps * ps
        keys = []
        for inst in self.instances:
            prefill_from = self._serve_fns_for(fn.name, inst)[1]
            if prefill_from is None:
                continue
            for k in range(1, bps + 1):
                slen = k * ps
                key = (id(model), "prefill-from", inst.idx, slen,
                       self.max_len)

                def warm(inst=inst, prefill_from=prefill_from, slen=slen):
                    params = _zero_params(model, inst.plan)
                    cache = model.make_cache(1, padded)
                    if inst.plan is not None:
                        cache = jax.device_put(
                            cache, inst.plan.cache_shardings(model, cache))
                    toks = jnp.zeros((1, slen), jnp.int32)
                    jax.block_until_ready(
                        prefill_from(params, toks, cache, jnp.int32(0)))
                    return prefill_from

                self.exe_cache.get_or_compile(key, warm)
                keys.append(key)
        return keys

    # ------------------------------------------------------------------
    # multi-tenant adapter serving: many functions, one resident engine
    # ------------------------------------------------------------------
    def deploy_shared_base(self, fn: LLMFunction, n_adapters: int = 8,
                           rank: int = 4,
                           target_paths: tuple = ("blocks.attn.wq",),
                           example_event: Optional[dict] = None,
                           prewarm_seq: int = 32) -> None:
        """Deploy ``fn`` as a SHARED BASE: one resident engine per
        instance carries an adapter bank of ``n_adapters - 1`` loadable
        rows (row 0 is the null adapter), and every function attached via
        :meth:`attach_adapter` serves from that engine's decode batch —
        thousands of dynamic functions, one copy of the base weights.
        The bank targets the attention projections in ``target_paths``."""
        check_bank_config(fn.model, target_paths, n_adapters)
        if not fn.model.supports_paged_kv:
            raise ValueError(
                f"{fn.name}: shared-base serving needs the paged arena")
        self.deploy(fn, example_event, prewarm_seq=prewarm_seq)
        self._shared_bases[fn.name] = {
            "n_adapters": int(n_adapters), "rank": int(rank),
            "targets": tuple(target_paths)}

    def attach_adapter(self, fn_name: str, base_name: str, adapter,
                       alpha: float = 1.0) -> None:
        """Register ``fn_name`` as an adapter function over ``base_name``.

        ``adapter`` is a ``lora_checkpoint``-layout Checkpoint; its
        factors load lazily into the shared engine's bank on the
        function's first invocation (per instance).  The function shows
        up in ``functions`` like any other deployment, but invoking it
        routes to the base's co-resident engine with its bank row as the
        per-slot adapter id."""
        if base_name not in self._shared_bases:
            raise KeyError(
                f"{base_name!r} is not a shared base (deploy_shared_base)")
        if fn_name in self._shared_bases:
            raise ValueError(f"{fn_name!r} already names a shared base")
        base = self.functions[base_name]
        self.functions[fn_name] = dataclasses.replace(base, name=fn_name)
        self._adapter_fns[fn_name] = (base_name, adapter, float(alpha))

    def _shared_engine_for(self, fn_name: str, now: float,
                           req_id: int = -1) -> tuple:
        """Resolve an adapter function to its base's resident engine,
        creating the engine (bank and all) on first use and loading the
        function's factors into a free bank row on its first invocation."""
        base_name, adapter, alpha = self._adapter_fns[fn_name]
        cfg = self._shared_bases[base_name]
        inst = self._pick_instance(base_name)
        key = ("__adapters__", base_name, inst.idx)
        warm = self._engines.get(key)
        stats = None
        if warm is None:
            kind = "fork" if base_name in self._invoked else "cold"
            model = self.functions[base_name].model
            session, stats = self.server.fork(base_name, {}, plan=inst.plan,
                                              req_id=req_id)
            bank = make_adapter_bank(model, cfg["targets"],
                                     cfg["n_adapters"], cfg["rank"])
            engine = ContinuousBatchingEngine(
                model, session, max_len=self.max_len,
                page_size=self.page_size, plan=inst.plan,
                pool=self._pool_for(inst, model),
                bucket_suffix=True, chunk_tokens=self.chunk_tokens,
                adapter_bank=bank,
                owner_name=f"adapters:{base_name}@{inst.idx}")
            # no prefix index: baked template KV is adapter-specific, and
            # this engine's batch mixes adapters
            warm = _WarmEngine(engine, now, inst.idx)
            self._engines[key] = warm
            self._invoked.add(base_name)
        else:
            kind = "warm"
        aid = warm.adapter_ids.get(fn_name)
        if aid is None:
            n = cfg["n_adapters"]
            if warm.next_adapter_id >= n:
                raise RuntimeError(
                    f"{base_name}: adapter bank is full "
                    f"({n - 1} rows, row 0 reserved for the null adapter)")
            aid = warm.next_adapter_id
            warm.next_adapter_id += 1
            warm.engine.set_adapter(aid, adapter, alpha=alpha)
            warm.adapter_ids[fn_name] = aid
            if kind == "warm":
                kind = "fork"        # first hit pays the factor load
        self._invoked.add(fn_name)
        return key, warm.engine, kind, stats

    def _adapter_id_for(self, fn_name: str, engine_key: tuple) -> int:
        """The bank row a request of ``fn_name`` decodes under (0 — the
        null adapter — for every non-adapter function)."""
        if fn_name not in self._adapter_fns:
            return 0
        return self._engines[engine_key].adapter_ids[fn_name]

    # ------------------------------------------------------------------
    def warm_engines(self) -> list:
        return sorted(self._engines)

    def _drop_engine(self, key: tuple) -> None:
        """Remove one warm engine, returning every slot/page it still holds
        to the instance's shared KV pool (the arena outlives the engine —
        dropping without releasing would leak it) and retiring its
        slot-partition lease on the paged arena, so a co-tenant's pool
        drops the evicted engine's masked page table too."""
        w = self._engines.pop(key)
        w.engine.close()

    def evict(self, fn_name: Optional[str] = None) -> int:
        """Drop warm engines (all of ``fn_name``'s, or every one), returning
        their KV slots/pages to the shared pools.  The next invocation takes
        the fork path again — i.e. keep-alive expiry."""
        keys = [k for k in self._engines
                if fn_name is None or k[0] == fn_name
                or (k[0] == "__adapters__" and k[1] == fn_name)]
        for k in keys:
            self._drop_engine(k)
        return len(keys)

    def _keep_alive_for(self, key: tuple, now: float) -> float:
        """Keep-alive window for one engine key: the static default, or —
        with a control plane attached — its predictive per-function value
        (extended for functions forecast to recur, shortened for ones
        forecast idle)."""
        if self.control_plane is None:
            return self.keep_alive_s
        return self.control_plane.keep_alive_s_for(key[0], self.keep_alive_s,
                                                   now=now)

    def _prune(self, now: float) -> None:
        """Keep-alive expiry + LRU cap — IDLE engines only: an engine with
        queued/active gateway requests is serving someone's ticket, and
        dropping it would spuriously cancel them (``evict()`` remains the
        explicit force-drop)."""
        idle = [k for k, w in self._engines.items()
                if not w.engine.n_pending]
        for k in [k for k in idle
                  if now - self._engines[k].last_used_s
                  > self._keep_alive_for(k, now)]:
            idle.remove(k)
            self._drop_engine(k)
        while len(self._engines) > self.max_warm_engines and idle:
            oldest = min(idle, key=lambda k: self._engines[k].last_used_s)
            idle.remove(oldest)
            self._drop_engine(oldest)

    # ------------------------------------------------------------------
    def _pick_instance(self, fn_name: str) -> _Instance:
        """Locality routing across mesh slices — the live analogue of
        ``ClusterSim._pick_gpu``: prefer an instance already warm for this
        function (its template executables and pool are hot) unless it is
        more than ``locality_max_extra_load`` engines busier than the
        least-loaded instance."""
        if len(self.instances) == 1:
            return self.instances[0]

        def load(inst):
            return sum(1 for w in self._engines.values()
                       if w.instance == inst.idx)

        best_any = min(self.instances, key=lambda i: (load(i), i.idx))
        warm_idx = {w.instance for k, w in self._engines.items()
                    if k[0] == fn_name}
        if warm_idx:
            cands = [i for i in self.instances if i.idx in warm_idx]
            best_warm = min(cands, key=lambda i: (load(i), i.idx))
            if load(best_warm) - load(best_any) <= self.locality_max_extra_load:
                return best_warm
        return best_any

    def _engine_for(self, fn_name: str, event: Optional[dict],
                    now: float, req_id: int = -1) -> tuple:
        """Resolve (key, engine, kind, fork_stats) for one invocation,
        forking a new engine when no warm one exists.  ``req_id`` names
        the invocation in the fork's profiler spans
        (``tidal.engine_for`` and below)."""
        if fn_name not in self.functions:
            raise KeyError(f"function {fn_name!r} is not deployed")
        if fn_name in self._adapter_fns:
            return self._shared_engine_for(fn_name, now, req_id)
        key = _engine_key(fn_name, event or {})
        warm = self._engines.get(key)
        if warm is not None:
            self._invoked.add(fn_name)
            return key, warm.engine, "warm", None
        kind = "fork" if fn_name in self._invoked else "cold"
        with jax.profiler.TraceAnnotation("tidal.engine_for", req_id=req_id,
                                          kind=kind):
            return self._fork_engine(fn_name, event, now, req_id, key, kind)

    def _fork_engine(self, fn_name: str, event: Optional[dict], now: float,
                     req_id: int, key: tuple, kind: str) -> tuple:
        inst = self._pick_instance(fn_name)
        model = self.functions[fn_name].model
        session, stats = self.server.fork(fn_name, event or {},
                                          plan=inst.plan, req_id=req_id)
        prefill_fn, prefill_from_fn, decode_fn = self._serve_fns_for(fn_name,
                                                                     inst)
        engine = ContinuousBatchingEngine(
            model, session, max_len=self.max_len,
            prefill_fn=prefill_fn, decode_fn=decode_fn,
            prefill_from_fn=prefill_from_fn,
            page_size=self.page_size, plan=inst.plan,
            pool=self._pool_for(inst, model),
            bucket_suffix=True, chunk_tokens=self.chunk_tokens,
            owner_name=f"{fn_name}@{inst.idx}")
        # a lazy per-instance bake reuses THIS fork's params rather than
        # streaming the model a second time (params_fn only resolves —
        # blocking on the stream — when a bake actually happens here)
        engine.prefix_index = self._prefix_index_for(fn_name, event, inst,
                                                     params_fn=engine.params)
        self._engines[key] = _WarmEngine(engine, now, inst.idx)
        self._invoked.add(fn_name)
        return key, engine, kind, stats

    def observe_ttft(self, fn_name: str, ttft_s: float) -> None:
        """Route Eq. 1 TTFT feedback to the template server.  Adapter
        functions credit their BASE's template — the resident artifact
        whose keep-warm decision the feedback drives."""
        name = self._adapter_fns.get(fn_name, (fn_name,))[0]
        self.server.observe_ttft(name, ttft_s)

    def _validate(self, fn_name: str, prompt, max_new_tokens: int) -> None:
        """Reject what could never serve before it touches any engine."""
        if fn_name not in self.functions:
            raise KeyError(f"function {fn_name!r} is not deployed")
        plen = len(np.asarray(prompt).reshape(-1))
        if max_new_tokens < 1 or plen + max_new_tokens > self.max_len:
            raise ValueError(
                f"{fn_name}: prompt({plen}) + max_new({max_new_tokens}) "
                f"exceeds runtime max_len={self.max_len}")

    def submit(self, request, event: Optional[dict] = None, prompt=None,
               max_new_tokens: int = 8, *, temperature: float = 0.0,
               top_p: float = 1.0, seed: int = 0):
        """Invoke a deployed function.

        The async form takes an :class:`InvocationRequest` and returns an
        :class:`InvocationHandle` ticket immediately — stream with
        ``handle.tokens()``, block with ``handle.result()``, abort with
        ``handle.cancel()``.

        The legacy positional form ``submit(fn_name, event, prompt,
        max_new_tokens, temperature=..., top_p=..., seed=...)`` stays: it
        is a compat shim that submits through the same gateway and drains
        it, returning the :class:`SubmitResult` (bit-identical tokens)."""
        if isinstance(request, InvocationRequest):
            return self.gateway.submit(request)
        return self.submit_many([(request, event, prompt, max_new_tokens,
                                  temperature, top_p, seed)])[0]

    def submit_async(self, request: InvocationRequest) -> InvocationHandle:
        """Explicitly-named alias of the async ``submit`` form."""
        return self.gateway.submit(request)

    def submit_many(self, requests: list) -> list:
        """Batch compat shim over the gateway: ``requests`` is a list of
        ``(fn_name, event, prompt, max_new_tokens[, temperature[, top_p[,
        seed]]])`` tuples.  All requests are ticketed BEFORE any engine
        steps, so requests resolving to the same engine genuinely share
        decode batches, and the gateway interleaves engines in quanta; at
        temperature 0 the tokens are bit-identical to the old
        drain-to-completion order (decode is per-slot independent)."""
        parsed = []
        for req in requests:
            fn_name, event, prompt, max_new_tokens = req[:4]
            extra = tuple(req[4:])
            temperature = extra[0] if len(extra) > 0 else 0.0
            top_p = extra[1] if len(extra) > 1 else 1.0
            seed = extra[2] if len(extra) > 2 else 0
            parsed.append(InvocationRequest(
                fn_name=fn_name, prompt=prompt, event=event,
                max_new_tokens=max_new_tokens, temperature=temperature,
                top_p=top_p, seed=seed))
        # validate the whole batch BEFORE touching any engine: a bad member
        # must not orphan earlier enqueues or misclassify first invocations
        for r in parsed:
            self._validate(r.fn_name, r.prompt, r.max_new_tokens)

        worker = self.workers.acquire()                      # §5.1 pool
        try:
            handles = [self.gateway.submit(r) for r in parsed]
            self.gateway.drain()
            return [h.result() for h in handles]
        finally:
            if worker is not None:
                self.workers.release(worker)


# ---------------------------------------------------------------------------
# measured service times -> cluster-scheduler oracle
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class MeasuredServiceTimes:
    """Wall-clock warm/fork/cold service times per function, LENGTH-
    BUCKETED: each kind maps to measurements at one or more prompt lengths
    and ``service_s`` linearly interpolates between buckets (clamping
    outside the measured range), so the scheduler's per-request
    ``input_len`` actually changes the oracle's answer.

    Satisfies the duck-typed ``SchedulerConfig.measured`` hook: the sim
    calls ``service_s(fn_name, kind, input_len)`` and falls back to the
    analytic cost model whenever this returns None.  ``"*"`` is a wildcard
    function entry.  ``times`` values may be plain floats (one bucket) or
    ``[(input_len, seconds), ...]`` lists."""
    times: dict                  # fn_name -> {kind: float | [(len, s), ...]}
    measured_prompt_len: Optional[int] = None

    def _buckets(self, fn_name: str, kind: str):
        d = self.times.get(fn_name) or self.times.get("*")
        if d is None or kind not in d:
            return None
        v = d[kind]
        if isinstance(v, (int, float)):
            return [(self.measured_prompt_len or 0, float(v))]
        return sorted((int(length), float(s)) for length, s in v)

    def service_s(self, fn_name: str, kind: str,
                  input_len: Optional[int] = None) -> Optional[float]:
        pts = self._buckets(fn_name, kind)
        if pts is None:
            return None
        if input_len is None or len(pts) == 1:
            return pts[0][1]
        xs = np.asarray([p[0] for p in pts], np.float64)
        ys = np.asarray([p[1] for p in pts], np.float64)
        return float(np.interp(float(input_len), xs, ys))

    def summary(self) -> str:
        rows = []
        for fn, d in sorted(self.times.items()):
            parts = []
            for k in KINDS:
                pts = self._buckets(fn, k)
                if pts is None:
                    continue
                parts.append(k + "=" + "/".join(
                    f"{s*1e3:.1f}ms@{length}" for length, s in pts))
            rows.append(fn + ": " + " ".join(parts))
        return "\n".join(rows)


def measure_service_times(runtime: FaaSRuntime, fn_events: dict,
                          prompt_len: int = 16, max_new_tokens: int = 4,
                          warm_reps: int = 2, seed: int = 0,
                          prompt_lens: Optional[list] = None
                          ) -> MeasuredServiceTimes:
    """Exercise each function's cold, fork and warm paths on the REAL
    runtime and record wall-clock service times.

    ``fn_events``: {fn_name: event dict}.  Functions already invoked on this
    runtime report their first measurement under the kind the runtime
    actually took (fork), not cold.  The warm figure is the best of
    ``warm_reps`` repeats: the first warm hit on a fresh engine may still
    pay one-off lazy compilation, which is a compile artifact, not the
    steady-state warm service time the scheduler models.

    ``prompt_lens`` turns on LENGTH BUCKETING: the fork/warm dance repeats
    at every bucket length and the oracle interpolates between them (cold
    can only ever happen once per function, so it stays a single point)."""
    rng = np.random.default_rng(seed)
    lens = sorted(set(prompt_lens or [prompt_len]))
    times: dict = {}
    for fn_name, event in fn_events.items():
        vocab = runtime.functions[fn_name].model.cfg.vocab_size
        per: dict = {}

        def record(kind: str, length: int, seconds: float):
            pts = per.setdefault(kind, [])
            for i, (L, s) in enumerate(pts):
                if L == length:
                    pts[i] = (L, min(s, seconds))
                    return
            pts.append((length, seconds))

        for j, L in enumerate(lens):
            prompt = rng.integers(0, vocab, L).astype(np.int32)
            first = runtime.submit(fn_name, event, prompt, max_new_tokens)
            record(first.kind, L, first.ttft_s)         # cold at 1st bucket
            runtime.evict(fn_name)                      # expire keep-alive
            forked = runtime.submit(fn_name, event, prompt, max_new_tokens)
            if forked.kind not in per or j > 0:
                record(forked.kind, L, forked.ttft_s)   # fork per bucket
            for _ in range(max(1, warm_reps)):
                warm = runtime.submit(fn_name, event, prompt, max_new_tokens)
                record(warm.kind, L, warm.ttft_s)
        times[fn_name] = per
    return MeasuredServiceTimes(times, measured_prompt_len=lens[0])


def measure_smoke_service_times(functions: dict, arch: str = "smollm-135m",
                                n_layers: int = 2, n_slots: int = 2,
                                max_len: int = 32, trace_seq: int = 16,
                                prompt_len: int = 16, max_new_tokens: int = 4,
                                seed: int = 0,
                                mesh: Optional[Mesh] = None
                                ) -> MeasuredServiceTimes:
    """One-stop live measurement rig shared by the ``--measured`` demos
    (``benchmarks/fig13_ttft.py``, ``examples/faas_cluster.py``,
    ``benchmarks/fig18_distributed.py`` with a ``mesh``): build a
    smoke-scale runtime on CPU, deploy one variant per ``functions`` entry
    ({name: 'static' | 'lora'}), and measure cold/fork/warm wall-clock
    service times for each."""
    model = get_smoke_model(arch, n_layers=n_layers)
    rt = FaaSRuntime(n_slots=n_slots, max_len=max_len, trace_seq=trace_seq,
                     mesh=mesh)
    params = model.init_params(jax.random.PRNGKey(seed))
    events: dict = {}
    for name, kind in functions.items():
        if kind == "lora":
            rt.deploy(tidal.lora_function(name, model, params,
                                          ["blocks.attn.wq"], n_adapters=2),
                      {"adapter": "adapter-0"}, prewarm_seq=prompt_len)
            events[name] = {"adapter": "adapter-1"}
        elif kind == "static":
            rt.deploy(tidal.static_function(name, model, params), {},
                      prewarm_seq=prompt_len)
            events[name] = {}
        else:
            raise ValueError(f"{name}: unknown function kind {kind!r}")
    return measure_service_times(rt, events, prompt_len=prompt_len,
                                 max_new_tokens=max_new_tokens)
