"""Template server: host pinned pool + device-resident templates + adaptive
state forking (TIDAL §5.2, Figure 12 left).

Per registered function the server keeps:

  * the :class:`FunctionTemplate` (order / kernels / fingerprints / Eq. 1
    residency / merge plan),
  * host-pool copies of every *static* weight (pinned numpy),
  * device buffers for the access-order resident prefix.

``fork`` implements adaptive state forking for a new invocation:

  * the initializer re-runs under strict tracing (cheap: TracedArrays are
    lazy, nothing static materializes);
  * fingerprints are diffed against the template -> newly dynamic weights are
    excluded incrementally;
  * static weights: resident ones are *shared* device buffers (copy-on-write
    is native — JAX arrays are immutable and the server never donates them),
    the rest stream asynchronously in access order;
  * dynamic weights: replayed from the traced DFG (materialize + upload),
    the only per-request work — <1% of the model for LoRA functions.
"""

from __future__ import annotations

import dataclasses
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from repro.core.api import LLMFunction
from repro.core.fingerprint import TracedArray
from repro.core.streaming import ForkSession, StreamEntry, WeightStreamer
from repro.core.template import FunctionTemplate, generate_template
from repro.distributed.sharding import ShardingPlan
from repro.core.tracing import trace_weight_access, weight_sizes
from repro.hw import HardwareProfile, TPU_V5E
from repro.utils import path_str


@dataclasses.dataclass
class ForkStats:
    reused_bytes: int = 0        # shared device buffers (resident prefix)
    streamed_bytes: int = 0      # async host->device in access order
    dynamic_bytes: int = 0       # replayed request-specific weights
    new_dynamic: tuple = ()


class TemplateServer:
    def __init__(self, hw: HardwareProfile = TPU_V5E,
                 device_budget_bytes: int = 1 << 62,
                 trace_batch: int = 1, trace_seq: int = 64,
                 plan: Optional[ShardingPlan] = None):
        self.hw = hw
        self.device_budget = device_budget_bytes
        self.trace_batch = trace_batch
        self.trace_seq = trace_seq
        # default placement plan for resident buffers and forks; fork(...)
        # can override per call (multi-instance runtimes fork one function
        # onto different mesh slices)
        self.plan = plan
        self.templates: dict[str, FunctionTemplate] = {}
        # fn -> int32 tokens of the function's shared prompt prefix: the
        # template's WARM STATE beyond weights — serving runtimes bake its
        # KV once into pinned paged-arena pages and share it across forks
        self.template_prompts: dict[str, np.ndarray] = {}
        self.host_pool: dict[str, dict] = {}          # fn -> path -> np array
        self.device_cache: dict[str, dict] = {}       # fn -> path -> jax.Array
        self._leaf_order: dict[str, list] = {}        # fn -> [path,...]
        self._leaf_kinds: dict[str, dict] = {}        # fn -> path -> kind
        self._leaf_specs: dict[tuple, dict] = {}      # (fn, mesh) -> path -> P
        self._placed_resident: dict[tuple, dict] = {}  # (fn, mesh) -> buffers
        self._functions: dict[str, LLMFunction] = {}

    def _specs_for(self, fn_name: str, plan: Optional[ShardingPlan]):
        """{path -> PartitionSpec} of the function's params under ``plan``
        (pure shape arithmetic, cached per (function, mesh) — Mesh is
        hashable, so a recreated mesh of the same devices/axes hits)."""
        if plan is None:
            return None
        key = (fn_name, plan.mesh)
        if key not in self._leaf_specs:
            model = self._functions[fn_name].model
            self._leaf_specs[key] = plan.leaf_param_specs(model)
        return self._leaf_specs[key]

    def _resident_for(self, fn_name: str, plan: Optional[ShardingPlan],
                      specs: Optional[dict]) -> dict:
        """The resident prefix as shared device buffers for ``plan``.

        Placement onto a non-default mesh slice happens ONCE per
        (function, mesh) and is cached — every later fork onto that slice
        reuses the same sharded buffers (invalidated whenever residency
        changes)."""
        base = self.device_cache.get(fn_name, {})
        if specs is None:
            return dict(base)
        key = (fn_name, plan.mesh)
        if key not in self._placed_resident:
            self._placed_resident[key] = {
                path: jax.device_put(a, plan.named(specs[path]))
                for path, a in base.items()}
        return dict(self._placed_resident[key])

    def _invalidate_placements(self, fn_name: str) -> None:
        for key in [k for k in self._placed_resident if k[0] == fn_name]:
            del self._placed_resident[key]

    # ------------------------------------------------------------------
    def device_bytes_used(self) -> int:
        return sum(int(a.nbytes) for d in self.device_cache.values()
                   for a in d.values())

    def register(self, fn: LLMFunction, example_event: dict,
                 resident_bytes: int = 0,
                 template_prompt=None) -> FunctionTemplate:
        """Build the function's template (offline or first-invocation).

        ``template_prompt`` records the function's shared prompt prefix
        (system prompt) as part of the template: runtimes bake its KV at
        prewarm and serve later invocations suffix-only."""
        model = fn.model
        # a re-register without a template opts OUT: never leave a stale
        # prompt behind; the new entry lands only after the initializer
        # has run (a failing registration must not record warm state)
        self.template_prompts.pop(fn.name, None)
        traced, fps = fn.run_initializer(example_event)

        specs = model.init_params(abstract=True)
        B, S = self.trace_batch, self.trace_seq
        inputs = model.input_specs("prefill", B, S, dtype=jnp.float32)
        cache = model.make_cache(B, S, abstract=True)
        trace = trace_weight_access(
            lambda p, i, c: model.prefill(p, i, c), specs, inputs, cache)
        sizes = weight_sizes(specs, trace.order)

        template = generate_template(fn.name, trace, sizes, fps,
                                     resident_bytes=resident_bytes)
        self.templates[fn.name] = template
        self._functions[fn.name] = fn

        # leaf bookkeeping: access order of leaves + whole/sliced kinds
        leaf_order, kinds = [], {}
        flat = {path_str(p): l
                for p, l in jax.tree_util.tree_leaves_with_path(specs)}
        for path, idx in trace.order:
            if path not in kinds:
                leaf_order.append(path)
                if idx == ():
                    kinds[path] = ("whole",)
                else:
                    kinds[path] = ("sliced", int(flat[path].shape[0]))
        self._leaf_order[fn.name] = leaf_order
        self._leaf_kinds[fn.name] = kinds

        # host pool: materialize static weights once (the pinned pool)
        pool = {}
        for p, leaf in jax.tree_util.tree_leaves_with_path(
                traced, is_leaf=lambda x: isinstance(x, TracedArray)):
            path = path_str(p)
            if path not in template.dynamic:
                pool[path] = np.asarray(leaf.materialize())
        self.host_pool[fn.name] = pool
        self._refresh_residency(fn.name)
        if template_prompt is not None:
            self.template_prompts[fn.name] = np.asarray(
                template_prompt, np.int32).reshape(-1)
        return template

    # ------------------------------------------------------------------
    def _resident_leaves(self, fn_name: str) -> list:
        """Access-order prefix of static leaves within the Eq.1 budget."""
        t = self.templates[fn_name]
        pool = self.host_pool[fn_name]
        budget = min(t.resident_bytes, self.device_budget)
        out = []
        for path in self._leaf_order[fn_name]:
            if path in t.dynamic or path not in pool:
                continue
            n = pool[path].nbytes
            if n <= budget:
                out.append(path)
                budget -= n
            else:
                break
        return out

    def _refresh_residency(self, fn_name: str) -> None:
        pool = self.host_pool[fn_name]
        want = self._resident_leaves(fn_name)
        cache = self.device_cache.setdefault(fn_name, {})
        specs = self._specs_for(fn_name, self.plan)
        changed = False
        for path in list(cache):
            if path not in want:
                del cache[path]
                changed = True
        for path in want:
            if path not in cache:
                if specs is not None:
                    cache[path] = jax.device_put(
                        pool[path], self.plan.named(specs[path]))
                else:
                    cache[path] = jnp.asarray(pool[path])
                changed = True
        if changed:
            self._invalidate_placements(fn_name)

    def set_resident_bytes(self, fn_name: str, nbytes: int) -> None:
        self.templates[fn_name].resident_bytes = int(nbytes)
        self._refresh_residency(fn_name)

    # ------------------------------------------------------------------
    def fork(self, fn_name: str, event: dict,
             plan: Optional[ShardingPlan] = None, req_id: int = -1
             ) -> tuple[ForkSession, ForkStats]:
        """Adaptive state forking for one invocation.

        With a ``plan`` (per call, or the server default) every weight is
        placed tensor-parallel on the plan's mesh: resident buffers are
        shared (re-placed once if the fork targets a different mesh slice),
        dynamic replays upload sharded, and the access-order stream lands
        each slice directly in its NamedSharding device buffers.

        ``req_id`` names the invocation the fork serves in the profiler
        spans of the fork and of its weight stream (-1: none)."""
        with jax.profiler.TraceAnnotation("tidal.fork",
                                          req_id=req_id) as span:
            session, stats = self._fork(fn_name, event, plan, req_id)
            span.set_metadata(streamed_bytes=stats.streamed_bytes)
        return session, stats

    def _fork(self, fn_name: str, event: dict,
              plan: Optional[ShardingPlan], req_id: int
              ) -> tuple[ForkSession, ForkStats]:
        plan = plan or self.plan
        fn = self._functions[fn_name]
        template = self.templates[fn_name]
        pool = self.host_pool[fn_name]
        kinds = self._leaf_kinds[fn_name]
        specs = self._specs_for(fn_name, plan)

        traced, fps = fn.run_initializer(event)
        new_dyn = template.observe_init(fps)
        if new_dyn:
            # evict newly dynamic weights from pool + device cache
            for path in new_dyn:
                pool.pop(path, None)
                self.device_cache.get(fn_name, {}).pop(path, None)
            self._invalidate_placements(fn_name)

        traced_by_path = {path_str(p): l
                          for p, l in jax.tree_util.tree_leaves_with_path(
                              traced, is_leaf=lambda x: isinstance(x, TracedArray))}

        stats = ForkStats(new_dynamic=tuple(sorted(new_dyn)))
        # shared sharded buffers, placed once per (function, mesh slice)
        # and reused by every later fork there.  nbytes stays the GLOBAL
        # size, so the byte accounting matches a single-device fork.
        resident = self._resident_for(fn_name, plan, specs)
        stats.reused_bytes = sum(int(a.nbytes) for a in resident.values())

        # dynamic weights: replay the DFG now (request-specific work)
        dynamic: dict = {}
        for path in sorted(template.dynamic):
            arr = traced_by_path[path].materialize()
            if specs is not None:
                dynamic[path] = jax.device_put(arr, plan.named(specs[path]))
            else:
                dynamic[path] = jnp.asarray(arr)
            stats.dynamic_bytes += arr.nbytes

        def _shard_for(path: str, sliced: bool):
            if specs is None:
                return None
            spec = specs[path]
            # a layer slice of a stacked leaf drops the (never-sharded)
            # leading scan-axis entry of the spec
            return plan.named(P(*spec[1:]) if sliced else spec)

        # remaining static weights: stream in traced access order
        entries = []
        for key in template.static_order:
            path, idx = key
            if path in resident or path in dynamic:
                continue
            kind = kinds[path]
            if kind[0] == "whole":
                if idx != ():
                    continue
                src = pool[path]
                entries.append(StreamEntry(key=key, fetch=lambda s=src: s,
                                           sharding=_shard_for(path, False)))
                stats.streamed_bytes += src.nbytes
            else:
                layer = idx[0]
                src = pool[path]
                entries.append(StreamEntry(
                    key=key, fetch=lambda s=src, l=layer: s[l],
                    sharding=_shard_for(path, True)))
                stats.streamed_bytes += src[layer].nbytes

        streamer = WeightStreamer(entries, resident, dynamic,
                                  req_id=req_id).start()
        return ForkSession(fn.model, streamer, kinds), stats

    # ------------------------------------------------------------------
    def observe_ttft(self, fn_name: str, ttft_s: float) -> None:
        """Feed a measured TTFT back into Eq. 1 and refresh residency."""
        self.templates[fn_name].observe_ttft(ttft_s, self.hw)
        self._refresh_residency(fn_name)
