"""Compile the main-path Pallas kernels for a described TPU v5e.

The TPU compiler is installed with JAX even where no chip is attached, so
these tests lower each kernel at real serving widths for a ``v5e:2x2``
topology and compile it: the compiler refuses block shapes the chip's
tiling cannot take, which interpret mode never checks.  Nothing runs.
Every compile must contain the kernel as a ``tpu_custom_call``.

The topology is described inside a module-scoped fixture, never while a
module is imported: only one process may hold the TPU library at a time.
"""

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import NamedSharding, PartitionSpec as P
from jax.sharding import SingleDeviceSharding

from repro.kernels import ops
from repro.kernels.decode_attention import decode_attention
from repro.kernels.paged_decode_attention import paged_decode_attention

# smollm-135m decode at its published widths, 8 slots of 1024 tokens
SMOLLM = dict(B=8, H=9, KV=3, d=64, max_len=1024)
SERVE_PAGE_SIZE = 64


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    # a compile for a described chip is written to the persistent cache but
    # cannot be read back without one: keep the cache out of these tests
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        yield topologies.get_topology_desc(platform="tpu",
                                           topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    finally:
        jax.config.update("jax_enable_compilation_cache", was)


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _spec(sharding, shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _assert_kernel(compiled):
    assert "tpu_custom_call" in compiled.as_text()


def _paged_specs(sharding, B, H, KV, d, ps, NB, kv_dtype):
    """q, the K and V arenas in the model's storage layout
    [n_pages, ps, KV, d], the page table and the lengths."""
    n_pages = 1 + B * NB
    return [_spec(sharding, (B, H, d), jnp.bfloat16),
            _spec(sharding, (n_pages, ps, KV, d), kv_dtype),
            _spec(sharding, (n_pages, ps, KV, d), kv_dtype),
            _spec(sharding, (B, NB), jnp.int32),
            _spec(sharding, (B,), jnp.int32)]


def _compile_paged(sharding, B, H, KV, d, ps, NB, kv_dtype):
    """Compile the paged kernel for one chip; int8 arenas bring their
    [n_pages, ps, KV] float32 scales."""
    args = _paged_specs(sharding, B, H, KV, d, ps, NB, kv_dtype)
    if kv_dtype != jnp.int8:
        return jax.jit(
            lambda q, k, v, pt, ln: paged_decode_attention(
                q, k, v, pt, ln, interpret=False)).lower(*args).compile()
    scales = _spec(sharding, (1 + B * NB, ps, KV), jnp.float32)
    return jax.jit(
        lambda q, k, v, pt, ln, ks, vs: paged_decode_attention(
            q, k, v, pt, ln, k_scales=ks, v_scales=vs, interpret=False)
    ).lower(*args, scales, scales).compile()


@pytest.mark.parametrize("ps", [8, SERVE_PAGE_SIZE])
def test_paged_decode_bf16_compiles(one_chip, ps):
    s = SMOLLM
    args = _paged_specs(one_chip, s["B"], s["H"], s["KV"], s["d"], ps,
                        s["max_len"] // ps, jnp.bfloat16)
    compiled = jax.jit(
        lambda q, k, v, pt, ln: paged_decode_attention(
            q, k, v, pt, ln, interpret=False)).lower(*args).compile()
    _assert_kernel(compiled)


@pytest.mark.parametrize("ps", [8, SERVE_PAGE_SIZE])
def test_paged_decode_int8_compiles(one_chip, ps):
    s = SMOLLM
    compiled = _compile_paged(one_chip, s["B"], s["H"], s["KV"], s["d"], ps,
                              s["max_len"] // ps, jnp.int8)
    _assert_kernel(compiled)


@pytest.mark.parametrize("kv_dtype", [jnp.bfloat16, jnp.int8],
                         ids=["bf16", "int8"])
def test_paged_decode_qwen3_cell_compiles(one_chip, kv_dtype):
    """qwen3-14b-l10's decode as the benchmark serves it: 4 slots of 2560
    tokens in pages of 8 (1281 pages), 40 heads over 8 KV heads of 128."""
    _assert_kernel(_compile_paged(one_chip, 4, 40, 8, 128, 8, 2560 // 8,
                                  kv_dtype))


def test_paged_decode_mqa_head_dim_256_compiles(one_chip):
    """One KV head of width 256 (MQA with wide heads), pages of 8."""
    _assert_kernel(_compile_paged(one_chip, 4, 8, 1, 256, 8, 2048 // 8,
                                  jnp.bfloat16))


def test_dense_decode_compiles(one_chip):
    s = SMOLLM
    B, H, KV, d, T = s["B"], s["H"], s["KV"], s["d"], s["max_len"]
    compiled = jax.jit(
        lambda q, k, v, ln: decode_attention(q, k, v, ln, interpret=False)
    ).lower(_spec(one_chip, (B, H, d), jnp.bfloat16),
            _spec(one_chip, (B, KV, T, d), jnp.bfloat16),
            _spec(one_chip, (B, KV, T, d), jnp.bfloat16),
            _spec(one_chip, (B,), jnp.int32)).compile()
    _assert_kernel(compiled)


def test_paged_decode_llama2_13b_shard_compiles(one_chip):
    """The shard-local kernel of llama2-13b under 4-way tensor parallelism:
    40 heads and 40 KV heads split into 10 per chip, head_dim 128."""
    ps, NB = SERVE_PAGE_SIZE, 256 // SERVE_PAGE_SIZE
    args = _paged_specs(one_chip, 4, 10, 10, 128, ps, NB, jnp.bfloat16)
    compiled = jax.jit(
        lambda q, k, v, pt, ln: paged_decode_attention(
            q, k, v, pt, ln, interpret=False)).lower(*args).compile()
    _assert_kernel(compiled)


def test_paged_decode_under_shard_map_compiles(topo, monkeypatch):
    """ops.paged_decode_attention over a (1, 4) mesh wraps the kernel in
    shard_map: the compiled program must still hold the kernel."""
    import numpy as np
    from jax.sharding import Mesh
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    mesh = Mesh(np.asarray(topo.devices).reshape(1, 4), ("data", "model"))
    B, H, KV, d, ps = 4, 40, 40, 128, SERVE_PAGE_SIZE
    NB = 256 // ps
    n_pages = 1 + B * NB

    def named(*spec):
        return NamedSharding(mesh, P(*spec))

    arena = _spec(named(None, None, "model", None), (n_pages, ps, KV, d),
                  jnp.bfloat16)
    compiled = jax.jit(
        lambda q, k, v, pt, ln: ops.paged_decode_attention(
            q, k, v, pt, ln, mesh=mesh)
    ).lower(_spec(named(None, "model", None), (B, H, d), jnp.bfloat16),
            arena, arena, _spec(named(), (B, NB), jnp.int32),
            _spec(named(), (B,), jnp.int32)).compile()
    _assert_kernel(compiled)


def test_flash_attention_unaligned_length_compiles(one_chip, monkeypatch):
    """S=130 has no tile that is a multiple of 8: ops must pick a block the
    chip accepts (the whole axis), not halve down to 2 rows."""
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    S, H, KV, d = 130, SMOLLM["H"], SMOLLM["KV"], SMOLLM["d"]
    compiled = jax.jit(lambda q, k, v: ops.flash_attention(q, k, v)).lower(
        _spec(one_chip, (1, H, S, d), jnp.bfloat16),
        _spec(one_chip, (1, KV, S, d), jnp.bfloat16),
        _spec(one_chip, (1, KV, S, d), jnp.bfloat16)).compile()
    _assert_kernel(compiled)


def test_paged_decode_carries_its_name_and_the_metric_signature(
        one_chip, monkeypatch):
    """The compiled kernel is named ``paged_decode_attention``, and the
    benchmark's signature for it (``paged_decode_roofline.cold``) still
    matches its HLO text as the device trace prints it: qwen3-14b-l10's
    decode, 4 slots of 2560 tokens in pages of 8."""
    import importlib.util
    import pathlib
    import re

    from jax._src.lib import xla_client

    bench = pathlib.Path(__file__).resolve().parents[1] / "bench"
    monkeypatch.syspath_prepend(str(bench))        # the reader's benchlib
    spec = importlib.util.spec_from_file_location(
        "paged_roofline", bench / "metrics" / "paged_decode_roofline.cold.py")
    metric = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(metric)
    B, ps = 4, 8
    args = _paged_specs(one_chip, B, 40, 8, 128, ps, 2560 // ps,
                        jnp.bfloat16)
    compiled = jax.jit(
        lambda q, k, v, pt, ln: paged_decode_attention(
            q, k, v, pt, ln, interpret=False)).lower(*args).compile()
    opts = xla_client._xla.HloPrintOptions.short_parsable()
    opts.print_operand_shape = True
    text = compiled.runtime_executable().hlo_modules()[0].to_string(opts)
    calls = [line.strip() for line in text.splitlines()
             if "tpu_custom_call" in line]
    assert len(calls) == 1
    assert calls[0].startswith("paged_decode_attention")
    assert re.search(metric.KERNEL.format(rows=B), calls[0])
    assert not re.search(metric.KERNEL.format(rows=B + 1), calls[0])
