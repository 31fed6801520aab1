"""Overlapped weight streaming + layer-granular execution (TIDAL §5.2,
Figure 12 right).

``WeightStreamer`` is the template server's async loader: a background
thread issues ``device_put`` per (weight, layer) slice in the *traced access
order*.  Consumers wait on per-key events — the JAX analogue of TIDAL's
injected synchronization events between async copies and kernels.

``streamed_prefill`` executes the first inference layer-by-layer while later
layers' weights are still in flight: layer k's compute waits only for layer
k's weights.  On TPU ``device_put`` is an async DMA, so this is true
transfer/compute overlap; on CPU it still validates the schedule and the
sync correctness (results must equal the monolithic prefill bit-for-bit —
tested).  The per-layer block function is jitted ONCE and reused for every
layer: the executable-sharing analogue of TIDAL's kernel dedup.

Profiler spans (host side, carrying the forked request's ``req_id``):
``tidal.stream.upload`` per slice on the streamer thread,
``tidal.stream.wait`` where a consumer blocks on a slice the streamer
thread has not handed over yet, ``tidal.fork.assemble`` where the first
full params are stacked, and ``tidal.prefill.layer`` per layer of the
streamed prefill.  An upload span ends when its transfer is enqueued, not
when it lands: the device's wait for a slice in flight shows as device
idle time under the consumer's spans, not as a wait span.
"""

from __future__ import annotations

import dataclasses
import threading
import time
from typing import Callable, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.models import ssm, transformer
from repro.models.layers import (attention_block, embed_tokens, lm_head,
                                 mlp_block, rmsnorm)
from repro.models.registry import Model
from repro.utils import path_str

_fault_point = None


def _visit_fault_point(point: str, detail: str) -> None:
    # lazy import: repro.core must stay importable before repro.runtime
    # finishes initializing (runtime.continuous imports this module)
    global _fault_point
    if _fault_point is None:
        from repro.runtime.faults import fault_point
        _fault_point = fault_point
    _fault_point(point, detail)


@dataclasses.dataclass
class StreamEntry:
    key: tuple                        # (path, idx)
    fetch: Callable[[], np.ndarray]   # host-pool slice provider
    sharding: Optional[object] = None  # NamedSharding target (None = default)


class WeightStreamer:
    """Background device uploader following the traced access order."""

    def __init__(self, entries: list, resident: dict, dynamic: dict,
                 record_order: bool = True, fetch_retries: int = 2,
                 retry_backoff_s: float = 0.005,
                 max_backoff_s: float = 0.25, req_id: int = -1):
        """resident/dynamic: {path: device array} available immediately.

        A slice fetch that raises is retried up to ``fetch_retries`` times
        with capped exponential backoff (``retry_backoff_s`` doubling up
        to ``max_backoff_s``) before the failure propagates — transient
        source hiccups (a flaky host pool read, an injected fault) cost
        latency, not the fork.  Slices that completed before a terminal
        failure stay servable either way."""
        self.entries = entries
        self.resident = dict(resident)
        self.dynamic = dict(dynamic)
        self.fetch_retries = int(fetch_retries)
        self.retry_backoff_s = float(retry_backoff_s)
        self.max_backoff_s = float(max_backoff_s)
        self.retries_used = 0
        self.req_id = req_id             # the request whose fork streams
        self._arrays: dict = {}
        self._events: dict = {e.key: threading.Event() for e in entries}
        self.completed_order: list = [] if record_order else None
        self._thread: Optional[threading.Thread] = None
        self._error: Optional[BaseException] = None

    def start(self) -> "WeightStreamer":
        self._thread = threading.Thread(target=self._run, daemon=True)
        self._thread.start()
        return self

    def _fetch_one(self, e: StreamEntry):
        """Fetch + upload one slice, retrying transient failures."""
        with jax.profiler.TraceAnnotation(
                "tidal.stream.upload", req_id=self.req_id,
                layer=_layer(e.key)) as span:
            arr = self._upload(e)
            span.set_metadata(nbytes=int(arr.nbytes))
        return arr

    def _upload(self, e: StreamEntry):
        delay = self.retry_backoff_s
        attempt = 0
        while True:
            try:
                _visit_fault_point("weight_fetch",
                                   f"{e.key[0]}:{e.key[1]}")
                # with a sharding the upload IS the placement: each slice
                # lands directly in its NamedSharding device buffers (the
                # tensor-parallel fork never materializes a replica)
                if e.sharding is not None:
                    return jax.device_put(e.fetch(), e.sharding)
                return jnp.asarray(e.fetch())
            except Exception:
                attempt += 1
                if attempt > self.fetch_retries:
                    raise
                self.retries_used += 1
                time.sleep(delay)
                delay = min(delay * 2.0, self.max_backoff_s)

    def _run(self):
        try:
            for e in self.entries:
                arr = self._fetch_one(e)
                self._arrays[e.key] = arr
                if self.completed_order is not None:
                    self.completed_order.append(e.key)
                self._events[e.key].set()
        except BaseException as ex:  # surfaced on next get()
            self._error = ex
            for ev in self._events.values():
                ev.set()

    # ---- consumer side -----------------------------------------------------
    def get(self, key: tuple):
        path, idx = key
        for store in (self.resident, self.dynamic):
            if path in store:
                arr = store[path]
                return arr if idx == () else arr[idx[0]]
        ev = self._events.get(key)
        if ev is None:
            raise KeyError(f"{key} neither resident, dynamic nor streamed")
        if not ev.is_set():
            with jax.profiler.TraceAnnotation(
                    "tidal.stream.wait", req_id=self.req_id,
                    layer=_layer(key)):
                ev.wait()
        # a fetch failure sets every event so no consumer hangs: slices that
        # landed before the failure stay servable, the rest raise
        if key in self._arrays:
            return self._arrays[key]
        if self._error is not None:
            raise self._error
        return self._arrays[key]

    def wait_all(self) -> None:
        if self._thread is not None:
            self._thread.join()
        if self._error is not None:
            raise self._error

    def release(self, keys) -> None:
        """Drop landed slices that a consumer now holds elsewhere (stacked
        into a full leaf); their device buffers free with the last use."""
        for key in keys:
            self._arrays.pop(key, None)


def _layer(key: tuple) -> int:
    """The layer of a stream key's slice; -1 for a whole leaf."""
    return int(key[1][0]) if key[1] else -1


class ForkSession:
    """The materialized state of one forked invocation."""

    def __init__(self, model: Model, streamer: WeightStreamer,
                 leaf_index: dict):
        self.model = model
        self.streamer = streamer
        # path -> either ("whole",) or ("sliced", n_layers)
        self.leaf_index = leaf_index
        self._params = None
        self._leaves: dict = {}          # path -> leaf of ``_params``

    def leaf(self, path: str):
        if path in self._leaves:
            return self._leaves[path]
        if path in self.streamer.resident:
            return self.streamer.resident[path]
        if path in self.streamer.dynamic:
            return self.streamer.dynamic[path]
        kind = self.leaf_index[path]
        if kind[0] == "whole":
            return self.streamer.get((path, ()))
        n = kind[1]
        slices = [self.streamer.get((path, (l,))) for l in range(n)]
        return jnp.stack(slices)

    def block_slice(self, path: str, layer: int):
        if path in self._leaves:
            return self._leaves[path][layer]
        kind = self.leaf_index[path]
        if kind[0] == "whole":
            return self.streamer.get((path, ()))[layer]
        return self.streamer.get((path, (layer,)))

    def params(self):
        """Full params pytree (waits for every outstanding transfer).

        Stacking copies a leaf's streamed layer slices into it, so they are
        released leaf by leaf: a forked model then occupies its size once
        on the device, not twice."""
        if self._params is None:
            with jax.profiler.TraceAnnotation(
                    "tidal.fork.assemble", req_id=self.streamer.req_id):
                self._assemble()
        return self._params

    def _assemble(self) -> None:
        specs = self.model.init_params(abstract=True)
        flat, treedef = jax.tree_util.tree_flatten_with_path(specs)
        paths = [path_str(p) for p, _ in flat]
        leaves = []
        for path in paths:
            leaves.append(self.leaf(path))
            kind = self.leaf_index.get(path, ("whole",))
            layers = [()] if kind[0] == "whole" else [
                (l,) for l in range(kind[1])]
            self.streamer.release([(path, idx) for idx in layers])
        self._params = jax.tree_util.tree_unflatten(treedef, leaves)
        self._leaves = dict(zip(paths, leaves))


# ---------------------------------------------------------------------------
# layer-streamed prefill (dense / moe / mla + xlstm / zamba hybrids)
# ---------------------------------------------------------------------------

def supports_streamed_prefill(model: Model) -> bool:
    return (model.cfg.family in ("dense", "moe", "xlstm", "zamba")
            and not model.is_encdec)


def _subtree_paths(model: Model, group: str) -> tuple:
    """Leaf paths (and treedef) of one top-level param group."""
    specs = model.init_params(abstract=True)[group]
    flat, treedef = jax.tree_util.tree_flatten_with_path(specs)
    return [f"{group}." + path_str(p) for p, _ in flat], treedef


def _subtree_at(session: ForkSession, paths: list, treedef, layer: int):
    """One layer's param subtree, waiting only on that layer's slices."""
    return jax.tree_util.tree_unflatten(
        treedef, [session.block_slice(p, layer) for p in paths])


def _subtree_whole(session: ForkSession, paths: list, treedef):
    return jax.tree_util.tree_unflatten(
        treedef, [session.leaf(p) for p in paths])


def _streamed_head(session: ForkSession, cfg, x):
    """Shared tail: final norm + LM head over the last position."""
    x = rmsnorm(x[:, -1:, :], session.leaf("final_norm"), cfg.norm_eps)
    head_params = {"embed": session.leaf("embed")}
    if not cfg.tied_embeddings:
        head_params["lm_head"] = session.leaf("lm_head")
    return lm_head(x, head_params, cfg.tied_embeddings)[:, 0]


def streamed_prefill(session: ForkSession, inputs: dict, cache, offset: int = 0):
    """Layer-by-layer prefill consuming weights as they arrive.

    Returns (last-token logits, filled cache) — must equal
    ``model.prefill`` exactly (tested).  With ``offset`` the tokens are a
    prompt SUFFIX at positions ``offset..`` over a cache whose first
    ``offset`` positions hold a reused prefix (prefix KV sharing from a
    still-streaming fork): positions, RoPE and the mask carry the offset,
    matching ``model.prefill_from``.  The hybrid families (xlstm, zamba)
    stream block-by-block in the same execution order their scans run —
    their recurrent state is not position-addressable, so they support
    only ``offset=0``.
    """
    model = session.model
    cfg = model.cfg
    assert supports_streamed_prefill(model)
    if cfg.family in ("xlstm", "zamba"):
        if offset:
            raise ValueError(
                f"{cfg.name}: {cfg.family!r} recurrent state has no "
                "suffix-only streamed prefill")
        if cfg.family == "xlstm":
            return _streamed_prefill_xlstm(session, inputs["tokens"], cache)
        return _streamed_prefill_zamba(session, inputs["tokens"], cache)

    tokens = inputs["tokens"]
    B, S = tokens.shape

    blocks_specs = model.init_params(abstract=True)["blocks"]
    flat_specs, blocks_treedef = jax.tree_util.tree_flatten_with_path(blocks_specs)
    block_paths = ["blocks." + path_str(p) for p, _ in flat_specs]

    off = jnp.asarray(offset, jnp.int32)
    positions = jnp.broadcast_to(off + jnp.arange(S)[None, :], (B, S))

    # a fresh jit per call: every fork traces and lowers it again
    @jax.jit
    def serve_prefill_layer(bp, x, layer_cache):
        return transformer._dense_block(bp, x, cfg, positions, layer_cache,
                                        off)

    x = embed_tokens(session.leaf("embed"), tokens,
                     scale_by_dim=cfg.scale_embed)
    new_layer_caches = []
    for l in range(cfg.n_layers):
        with jax.profiler.TraceAnnotation("tidal.prefill.layer", layer=l):
            leaves = [session.block_slice(p, l) for p in block_paths]
            bp = jax.tree_util.tree_unflatten(blocks_treedef, leaves)
            layer_cache = jax.tree.map(lambda t: t[l], cache)
            x, new_c, _ = serve_prefill_layer(bp, x, layer_cache)
        new_layer_caches.append(new_c)

    x = rmsnorm(x[:, -1:, :], session.leaf("final_norm"), cfg.norm_eps)
    head_params = {"embed": session.leaf("embed")}
    if not cfg.tied_embeddings:
        head_params["lm_head"] = session.leaf("lm_head")
    logits = lm_head(x, head_params, cfg.tied_embeddings)

    new_cache = jax.tree.map(lambda *ls: jnp.stack(ls), *new_layer_caches)
    return logits[:, 0], new_cache


def _streamed_prefill_xlstm(session: ForkSession, tokens, cache):
    """xLSTM streamed prefill: mLSTM blocks (and one sLSTM per unit when
    ``slstm_every`` is set) run as their weights land, in the exact order
    ``transformer._xlstm_stack`` scans them.  One jitted executable per
    block kind, reused across every layer."""
    model = session.model
    cfg = model.cfg
    m_paths, m_tree = _subtree_paths(model, "mlstm")

    @jax.jit
    def m_fn(bp, bc, h):
        y, ns = ssm.mlstm_mixer(bp["mixer"],
                                rmsnorm(h, bp["norm"], cfg.norm_eps), cfg, bc)
        return h + y, ns

    x = embed_tokens(session.leaf("embed"), tokens,
                     scale_by_dim=cfg.scale_embed)
    every = cfg.slstm_every
    new_m: list = []
    if not every:
        for l in range(cfg.n_layers):
            bp = _subtree_at(session, m_paths, m_tree, l)
            bc = jax.tree.map(lambda t: t[l], cache["mlstm"])
            x, ns = m_fn(bp, bc, x)
            new_m.append(ns)
        return (_streamed_head(session, cfg, x),
                {"mlstm": jax.tree.map(lambda *ls: jnp.stack(ls), *new_m)})

    s_paths, s_tree = _subtree_paths(model, "slstm")

    @jax.jit
    def s_fn(sp_, sc, h):
        y, new_sc = ssm.slstm_mixer(sp_["mixer"],
                                    rmsnorm(h, sp_["norm"], cfg.norm_eps),
                                    cfg, sc)
        h = h + y
        hn = rmsnorm(h, sp_["mlp_norm"], cfg.norm_eps)
        h = h + mlp_block(sp_["mixer"]["mlp"], hn, cfg.act)
        return h, new_sc

    n_units = cfg.n_layers // every
    m_per = every - 1
    new_s: list = []
    for u in range(n_units):
        for j in range(m_per):
            l = u * m_per + j
            bp = _subtree_at(session, m_paths, m_tree, l)
            bc = jax.tree.map(lambda t: t[l], cache["mlstm"])
            x, ns = m_fn(bp, bc, x)
            new_m.append(ns)
        sp_ = _subtree_at(session, s_paths, s_tree, u)
        sc = jax.tree.map(lambda t: t[u], cache["slstm"])
        x, new_sc = s_fn(sp_, sc, x)
        new_s.append(new_sc)
    return (_streamed_head(session, cfg, x),
            {"mlstm": jax.tree.map(lambda *ls: jnp.stack(ls), *new_m),
             "slstm": jax.tree.map(lambda *ls: jnp.stack(ls), *new_s)})


def _streamed_prefill_zamba(session: ForkSession, tokens, cache):
    """Zamba2 streamed prefill: ``attn_every`` mamba blocks then the
    SHARED attention+mlp per unit, matching ``transformer._zamba_stack``.
    The shared block's weights are fetched once (they are the densest
    single transfer) and its executable is reused by every unit."""
    model = session.model
    cfg = model.cfg
    B, S = tokens.shape
    positions = jnp.broadcast_to(jnp.arange(S)[None, :], (B, S))
    m_paths, m_tree = _subtree_paths(model, "mamba")
    a_paths, a_tree = _subtree_paths(model, "shared_attn")

    @jax.jit
    def m_fn(bp, bc, h):
        y, ns = ssm.mamba2_mixer(bp["mixer"],
                                 rmsnorm(h, bp["norm"], cfg.norm_eps),
                                 cfg, bc)
        return h + y, ns

    @jax.jit
    def a_fn(shared, kv, h):
        hn = rmsnorm(h, shared["attn_norm"], cfg.norm_eps)
        a, new_kv = attention_block(shared["attn"], hn, cfg, positions,
                                    kv, jnp.int32(0))
        h = h + a
        hn = rmsnorm(h, shared["mlp_norm"], cfg.norm_eps)
        h = h + mlp_block(shared["mlp"], hn, cfg.act)
        return h, new_kv

    x = embed_tokens(session.leaf("embed"), tokens,
                     scale_by_dim=cfg.scale_embed)
    every = cfg.attn_every
    n_units = cfg.n_layers // every
    shared = None
    new_m: list = []
    new_kv: list = []
    for u in range(n_units):
        for j in range(every):
            l = u * every + j
            bp = _subtree_at(session, m_paths, m_tree, l)
            bc = jax.tree.map(lambda t: t[l], cache["mamba"])
            x, ns = m_fn(bp, bc, x)
            new_m.append(ns)
        if shared is None:
            shared = _subtree_whole(session, a_paths, a_tree)
        kv = jax.tree.map(lambda t: t[u], cache["attn_kv"])
        x, nk = a_fn(shared, kv, x)
        new_kv.append(nk)
    return (_streamed_head(session, cfg, x),
            {"mamba": jax.tree.map(lambda *ls: jnp.stack(ls), *new_m),
             "attn_kv": jax.tree.map(lambda *ls: jnp.stack(ls), *new_kv)})
