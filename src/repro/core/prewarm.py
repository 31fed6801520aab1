"""Proactive code-segment loading (TIDAL §5.1), JAX edition.

The CUDA mechanism (lazy ``cuModuleLoad`` on first kernel call, ~180 ms)
maps to XLA executables: the first ``jit`` call pays trace+compile+load.
TIDAL's fix — pre-warm exactly the kernels the traced template names —
becomes: AOT-compile the function's entry points (prefill / decode / the
shared block body) for its traced shape signatures *before* any invocation,
inside pooled workers.

The dedup story carries over: identical transformer blocks share one
executable because the model scans over stacked layers, so the cache key
space is tiny (one prefill + one decode signature per function/shape), vs
eagerly compiling everything (the strawman's 1.12 GB / 3 s problem).

The loading *policy* (§5.1) also carries over: a worker pre-warms the
executables of exactly the functions currently cached in its host pool.

Compile accounting lives here too: ``CacheStats`` counts this module's own
AOT compiles, and ``compile_counts`` every trace, lowering and backend
compile JAX reports in the process (a fork's per-call ``jit`` included).
"""

from __future__ import annotations

import dataclasses
import threading
import time
from typing import Any, Callable, Optional

import jax


@dataclasses.dataclass
class CacheStats:
    hits: int = 0
    misses: int = 0
    compile_s: float = 0.0


# JAX's duration events for the three stages of turning a call into an
# executable; each carries the traced function's name as ``fun_name``
COMPILE_EVENTS = ("/jax/core/compile/jaxpr_trace_duration",
                  "/jax/core/compile/jaxpr_to_mlir_module_duration",
                  "/jax/core/compile/backend_compile_duration")
_compiles: dict = {}                  # (event, fun_name) -> [count, seconds]
_compiles_lock = threading.Lock()
_compiles_listening = False


def _on_compile_event(event: str, duration: float, **kwargs) -> None:
    if event not in COMPILE_EVENTS:
        return
    key = (event, str(kwargs.get("fun_name", "")))
    with _compiles_lock:
        c = _compiles.setdefault(key, [0, 0.0])
        c[0] += 1
        c[1] += float(duration)


def listen_for_compiles() -> None:
    """Register the process-wide compile listener (once)."""
    global _compiles_listening
    with _compiles_lock:
        if _compiles_listening:
            return
        _compiles_listening = True
    jax.monitoring.register_event_duration_secs_listener(_on_compile_event)


def compile_counts() -> dict:
    """``{(event, fun_name): (count, seconds)}`` of every compile event
    since ``listen_for_compiles`` was first called."""
    with _compiles_lock:
        return {k: (c[0], c[1]) for k, c in _compiles.items()}


class ExecutableCache:
    """AOT-compiled executable store, keyed by (fn, arch, shape-sig, mesh)."""

    def __init__(self):
        self._cache: dict = {}
        self.stats = CacheStats()

    def __contains__(self, key) -> bool:
        return key in self._cache

    def keys(self):
        return list(self._cache)

    def get_or_compile(self, key, build: Callable[[], Any]):
        """build() must return the compiled executable (lower().compile())."""
        if key in self._cache:
            self.stats.hits += 1
            return self._cache[key]
        t0 = time.perf_counter()
        exe = build()
        self.stats.compile_s += time.perf_counter() - t0
        self.stats.misses += 1
        self._cache[key] = exe
        return exe

    def compile_jit(self, key, fn: Callable, *specs,
                    in_shardings=None, out_shardings=None,
                    donate_argnums=()):
        def build():
            jitted = jax.jit(fn, donate_argnums=donate_argnums,
                             **({"in_shardings": in_shardings}
                                if in_shardings is not None else {}),
                             **({"out_shardings": out_shardings}
                                if out_shardings is not None else {}))
            return jitted.lower(*specs).compile()
        return self.get_or_compile(key, build)


@dataclasses.dataclass
class Worker:
    """A pre-warmed process: context created, selected executables loaded."""
    worker_id: int
    ctx_ready: bool = False
    loaded: set = dataclasses.field(default_factory=set)

    def prewarm_ctx(self) -> None:
        # TPU analogue of CUDA-context creation: touch the runtime once.
        jax.devices()
        self.ctx_ready = True

    def load_executables(self, keys) -> None:
        self.loaded |= set(keys)


class ProcessPool:
    """Pool of pre-warmed workers following the §5.1 loading policy:
    each worker pre-warms the executables of the functions whose weights are
    cached in this host's pool."""

    def __init__(self, size: int, cache: ExecutableCache):
        self.cache = cache
        self.workers = [Worker(i) for i in range(size)]
        for w in self.workers:
            w.prewarm_ctx()
        self._free = list(self.workers)

    def prewarm_for_functions(self, fn_keys: dict) -> None:
        """fn_keys: function name -> list of executable cache keys (already
        compiled into the shared cache)."""
        keys = [k for ks in fn_keys.values() for k in ks]
        for w in self.workers:
            w.load_executables(keys)

    def acquire(self) -> Optional[Worker]:
        return self._free.pop() if self._free else None

    def release(self, w: Worker) -> None:
        self._free.append(w)

    def is_prewarmed(self, w: Worker, keys) -> bool:
        return w.ctx_ready and set(keys) <= w.loaded


def prewarm_function(cache: ExecutableCache, model, fn_name: str,
                     batch: int, seq: int, max_len: Optional[int] = None):
    """Compile a function's serve entry points ahead of invocation.

    Returns the cache keys (what the pool loads into workers)."""
    import jax.numpy as jnp
    max_len = max_len or seq * 2
    inputs = model.input_specs("prefill", batch, seq, dtype=jnp.float32)
    cache_spec = model.make_cache(batch, max_len, abstract=True)
    kp = (fn_name, "prefill", batch, seq, max_len)
    cache.compile_jit(kp, lambda p, i, c: model.prefill(p, i, c),
                      model.init_params(abstract=True), inputs, cache_spec)
    dec_inputs = model.input_specs("decode", batch, seq, dtype=jnp.float32)
    kd = (fn_name, "decode", batch, max_len)
    cache.compile_jit(
        kd, lambda p, c, i, pos: model.decode_step(p, c, i, pos),
        model.init_params(abstract=True), cache_spec, dec_inputs,
        jax.ShapeDtypeStruct((), jnp.int32))
    return [kp, kd]
