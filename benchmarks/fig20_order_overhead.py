"""Fig. 20a: weight loading order ablation (traced vs default-init vs
reverse) — paper: traced order is ~1.55x / 1.54x faster, because e.g. the
tied embedding is initialized LAST but accessed FIRST.

Fig. 20b: runtime tracing overhead on decode — paper: <1.2% vs native
PyTorch.  Our jaxpr tracing is ahead-of-time, so the steady-state overhead
is structurally zero; we MEASURE it live on CPU with smollm."""

import time

import jax
import jax.numpy as jnp

from benchmarks.common import PAPER_HW, emit
from repro.core import costmodel as cm
from repro.core.plans import plan_for
from repro.core.tracing import trace_weight_access
from repro.data.pipeline import make_prompts
from repro.models.registry import get_smoke_model


def main():
    rows = []
    # ---- Fig 20a: loading order (gemma-2b has the tied embedding) --------
    for arch in ("gemma-2b", "llama3-8b"):
        plan = plan_for(arch, 1, 2048)
        tr = cm.ttft_tidal(plan, PAPER_HW, order="traced").total
        de = cm.ttft_tidal(plan, PAPER_HW, order="default").total
        rv = cm.ttft_tidal(plan, PAPER_HW, order="reverse").total
        rows += [(f"{arch}/order_traced", round(tr * 1e3, 1), ""),
                 (f"{arch}/order_default", round(de * 1e3, 1),
                  f"traced_speedup={de/tr:.2f}x (paper~1.54x)"),
                 (f"{arch}/order_reverse", round(rv * 1e3, 1),
                  f"traced_speedup={rv/tr:.2f}x (paper~1.55x)")]

    # ---- Fig 20b: tracing overhead, measured live on CPU -----------------
    m = get_smoke_model("smollm-135m", n_layers=4)
    params = m.init_params(jax.random.PRNGKey(0))
    toks = jnp.asarray(make_prompts(m.cfg.vocab_size, 1, 32))
    cache = m.make_cache(1, 64)
    prefill = jax.jit(lambda p, i, c: m.prefill(p, i, c))
    decode = jax.jit(lambda p, c, i, t: m.decode_step(p, c, i, t))
    lg, cache = prefill(params, {"tokens": toks}, cache)
    tok = jnp.argmax(lg, -1)[:, None].astype(jnp.int32)
    lg, cache = decode(params, cache, {"tokens": tok}, jnp.int32(32))
    jax.block_until_ready(lg)

    def measure_decode(n=30):
        nonlocal cache
        t0 = time.perf_counter()
        for i in range(n):
            lg2, cache = decode(params, cache, {"tokens": tok},
                                jnp.int32(33 + i))
        jax.block_until_ready(lg2)
        return (time.perf_counter() - t0) / n

    base = measure_decode()
    # "tracing active": TIDAL's tracer ran ahead-of-time; re-run the jaxpr
    # trace to price even a full re-trace, then measure decode again.
    t0 = time.perf_counter()
    trace_weight_access(
        lambda p, c, i: m.decode_step(p, c, i, jnp.int32(5)),
        m.init_params(abstract=True), m.make_cache(1, 64, abstract=True),
        {"tokens": jax.ShapeDtypeStruct((1, 1), jnp.int32)})
    trace_cost = time.perf_counter() - t0
    traced = measure_decode()
    over = (traced - base) / base * 100
    rows += [
        ("decode_native_ms", round(base * 1e3, 3), "live CPU, smollm"),
        ("decode_with_tidal_runtime_ms", round(traced * 1e3, 3),
         f"overhead={over:+.1f}% (paper<1.2%; ours is AOT)"),
        ("one_time_trace_cost_ms", round(trace_cost * 1e3, 1),
         "amortized once per function"),
    ]
    return emit(rows)


if __name__ == "__main__":
    from repro.utils import enable_compile_cache
    enable_compile_cache()
    main()
