"""Core transformer layers, pure-functional JAX.

Every layer is a function ``f(params, x, ...) -> y`` over a params dict.
Param construction goes through :class:`ParamFactory` so the same structure
code yields real arrays (smoke tests / live serving) or
``jax.ShapeDtypeStruct`` stand-ins (dry-run lowering, no allocation).
"""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.models.config import ModelConfig, use_pallas


class ParamFactory:
    """Creates either concrete arrays or abstract ShapeDtypeStructs."""

    def __init__(self, rng: Optional[jax.Array], dtype, abstract: bool = False):
        self.rng = rng
        self.dtype = dtype
        self.abstract = abstract
        self._counter = 0

    def _next_key(self):
        self._counter += 1
        return jax.random.fold_in(self.rng, self._counter)

    def __call__(self, shape, init: str = "normal", scale: Optional[float] = None):
        shape = tuple(int(s) for s in shape)
        if self.abstract:
            return jax.ShapeDtypeStruct(shape, self.dtype)
        if init == "zeros":
            return jnp.zeros(shape, self.dtype)
        if init == "ones":
            return jnp.ones(shape, self.dtype)
        if scale is None:
            # fan-in scaled normal
            fan_in = shape[0] if len(shape) > 1 else max(shape[0], 1)
            scale = 1.0 / np.sqrt(fan_in)
        w = jax.random.normal(self._next_key(), shape, jnp.float32) * scale
        return w.astype(self.dtype)


# ---------------------------------------------------------------------------
# normalization
# ---------------------------------------------------------------------------

def rmsnorm(x: jax.Array, scale: jax.Array, eps: float = 1e-6) -> jax.Array:
    dt = x.dtype
    x = x.astype(jnp.float32)
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    x = x * jax.lax.rsqrt(var + eps)
    return (x * scale).astype(dt)


def layernorm(x: jax.Array, scale: jax.Array, bias: jax.Array, eps: float = 1e-6) -> jax.Array:
    dt = x.dtype
    x = x.astype(jnp.float32)
    mu = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.var(x, axis=-1, keepdims=True)
    x = (x - mu) * jax.lax.rsqrt(var + eps)
    return (x * scale + bias).astype(dt)


# ---------------------------------------------------------------------------
# rotary position embedding
# ---------------------------------------------------------------------------

def rope(x: jax.Array, positions: jax.Array, theta: float = 10000.0) -> jax.Array:
    """Apply RoPE. x: [..., S, H, hd]; positions: [..., S] (broadcastable)."""
    hd = x.shape[-1]
    half = hd // 2
    freqs = jnp.exp(-jnp.arange(0, half, dtype=jnp.float32) * (np.log(theta) / half))
    angles = positions[..., :, None].astype(jnp.float32) * freqs  # [..., S, half]
    cos = jnp.cos(angles)[..., None, :]  # [..., S, 1, half]
    sin = jnp.sin(angles)[..., None, :]
    x1, x2 = x[..., :half], x[..., half:]
    out = jnp.concatenate(
        [x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.astype(x.dtype)


# ---------------------------------------------------------------------------
# attention
# ---------------------------------------------------------------------------

def _sdpa(q, k, v, mask, softcap: float = 0.0, seq_shard: bool = False):
    """Grouped scaled-dot-product attention.

    q: [B, S, KV, G, hd]   (G = query groups per kv head)
    k: [B, T, KV, hd]
    v: [B, T, KV, hd]
    mask: broadcastable to [B, S, 1, 1, T] (True = attend)

    K/V stay in their storage dtype (the einsum accumulates in f32 via
    preferred_element_type) — casting a 32k-long cache to f32 materializes
    2x the bytes and, under SPMD, forced a full resharding copy (hillclimb
    #1 iter 2).  ``seq_shard`` adds sharding constraints keeping the score
    axis partitioned over 'model' (flash-decoding style: only softmax stats
    and [B,H,hd] partials cross shards).
    """
    hd = q.shape[-1]
    scale = 1.0 / np.sqrt(hd)
    scores = jnp.einsum("bskgd,btkd->bskgt", q, k,
                        preferred_element_type=jnp.float32) * scale
    if softcap > 0:
        scores = jnp.tanh(scores / softcap) * softcap
    if seq_shard:
        from jax.sharding import PartitionSpec as P
        scores = jax.lax.with_sharding_constraint(
            scores, P("data", None, None, None, "model"))
    scores = jnp.where(mask, scores, jnp.finfo(jnp.float32).min)
    probs = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum("bskgt,btkd->bskgd", probs.astype(v.dtype), v,
                     preferred_element_type=jnp.float32)
    return out.astype(v.dtype)


def make_attn_params(pf: ParamFactory, cfg: ModelConfig) -> dict:
    D, H, KV, hd = cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    if cfg.fused_qkv:
        p = {"wqkv": pf((D, (H + 2 * KV) * hd)), "wo": pf((H * hd, D))}
    else:
        p = {
            "wq": pf((D, H * hd)),
            "wk": pf((D, KV * hd)),
            "wv": pf((D, KV * hd)),
            "wo": pf((H * hd, D)),
        }
    if cfg.qkv_bias:
        p["bq"] = pf((H * hd,), init="zeros")
        p["bk"] = pf((KV * hd,), init="zeros")
        p["bv"] = pf((KV * hd,), init="zeros")
    if cfg.qk_norm:
        p["q_norm"] = pf((hd,), init="ones")
        p["k_norm"] = pf((hd,), init="ones")
    return p


def attention_block(
    p: dict,
    x: jax.Array,                       # [B, S, D]
    cfg: ModelConfig,
    positions: jax.Array,               # [B, S]
    kv_cache: Optional[dict] = None,    # {'k','v': [B, T, KV, hd]} or None
    cache_pos: Optional[jax.Array] = None,  # scalar or [B]: write offset(s)
    causal: bool = True,
    page_table: Optional[jax.Array] = None,  # [B, NB]: block-paged decode
    page_size: int = 0,
    adapters: Optional[dict] = None,    # per-layer bank slices {name: slab}
    adapter_ids: Optional[jax.Array] = None,  # [B] int32, 0 = null adapter
):
    """GQA/MQA attention with optional KV cache.

    Returns (y, new_kv_cache).  With a cache, K/V for the current x are
    written at ``cache_pos`` and attention spans the whole cache up to
    ``cache_pos + S``.  A vector ``cache_pos`` of shape [B] writes each
    sequence's K/V at its own offset (continuous batching: slots in one
    decode batch sit at different positions); vector offsets are
    decode-only (S == 1).

    With ``page_table``, the cache leaves are one shared block-paged arena
    ``[P, page_size, KV, hd]`` instead of per-sequence rows: logical block
    ``j`` of sequence ``b`` lives in physical page ``page_table[b, j]``
    (page 0 is the runtime's null page).  Paged mode is decode-only.

    With ``adapters`` (batched multi-adapter LoRA), each targeted
    projection adds its per-sequence low-rank delta — adapter row
    ``adapter_ids[b]`` gathered from the bank slice — before biases,
    norms and RoPE, matching the merged-weight ``W + A @ B`` oracle.
    """
    B, S, D = x.shape
    H, KV, hd = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    G = H // KV

    if adapters is not None and cfg.fused_qkv:
        raise NotImplementedError(
            "adapter gather targets the unfused wq/wk/wv/wo projections")
    if cfg.fused_qkv:
        qkv = jnp.einsum("bsd,de->bse", x, p["wqkv"])
        nq = H * hd
        q = qkv[..., :nq]
        k = qkv[..., nq:nq + KV * hd]
        v = qkv[..., nq + KV * hd:]
    else:
        q = jnp.einsum("bsd,de->bse", x, p["wq"])
        k = jnp.einsum("bsd,de->bse", x, p["wk"])
        v = jnp.einsum("bsd,de->bse", x, p["wv"])
    if adapters is not None:
        from repro.models.adapters import lora_delta
        if "wq" in adapters:
            q = q + lora_delta(x, adapters["wq"], adapter_ids)
        if "wk" in adapters:
            k = k + lora_delta(x, adapters["wk"], adapter_ids)
        if "wv" in adapters:
            v = v + lora_delta(x, adapters["wv"], adapter_ids)
    if cfg.qkv_bias:
        q, k, v = q + p["bq"], k + p["bk"], v + p["bv"]
    q = q.reshape(B, S, H, hd)
    k = k.reshape(B, S, KV, hd)
    v = v.reshape(B, S, KV, hd)
    if cfg.qk_norm:
        q = rmsnorm(q, p["q_norm"], cfg.norm_eps)
        k = rmsnorm(k, p["k_norm"], cfg.norm_eps)
    q = rope(q, positions, cfg.rope_theta)
    k = rope(k, positions, cfg.rope_theta)

    if kv_cache is not None and page_table is not None:
        # block-paged decode: write this token's K/V into its page, then
        # attend over the pages the table maps for each sequence
        assert S == 1, "paged attention is decode-only"
        ck, cv = kv_cache["k"], kv_cache["v"]
        ps = page_size
        b = jnp.arange(B)
        pages = page_table[b, cache_pos // ps]                   # [B]
        off = cache_pos % ps
        quantized = "k_scale" in kv_cache
        if quantized:
            # int8 arena: quantize this token's rows on append — values
            # into the value leaf, per-row scales into its _scale leaf
            from repro.models import quant
            qk, sk = quant.quantize_rows(k[:, 0])     # [B,KV,hd], [B,KV]
            qv, sv = quant.quantize_rows(v[:, 0])
            ck = ck.at[pages, off].set(qk)
            cv = cv.at[pages, off].set(qv)
            cks = kv_cache["k_scale"].at[pages, off].set(sk)
            cvs = kv_cache["v_scale"].at[pages, off].set(sv)
            new_cache = {"k": ck, "k_scale": cks, "v": cv, "v_scale": cvs}
        else:
            cks = cvs = None
            ck = ck.at[pages, off].set(k[:, 0].astype(ck.dtype))
            cv = cv.at[pages, off].set(v[:, 0].astype(cv.dtype))
            new_cache = {"k": ck, "v": cv}
        if use_pallas(cfg):
            from repro.distributed.sharding import current_kernel_mesh
            from repro.kernels import ops as kops
            out = kops.paged_decode_attention(q[:, 0], ck, cv, page_table,
                                              cache_pos + 1,
                                              k_scales=cks, v_scales=cvs,
                                              mesh=current_kernel_mesh())
            out = out[:, None]                                   # [B,1,H,hd]
        else:
            T = page_table.shape[1] * ps
            kg = jnp.take(ck, page_table, axis=0).reshape(B, T, KV, hd)
            vg = jnp.take(cv, page_table, axis=0).reshape(B, T, KV, hd)
            if quantized:
                from repro.models import quant
                ksg = jnp.take(cks, page_table, axis=0).reshape(B, T, KV)
                vsg = jnp.take(cvs, page_table, axis=0).reshape(B, T, KV)
                kg = quant.dequantize_rows(kg, ksg, x.dtype)
                vg = quant.dequantize_rows(vg, vsg, x.dtype)
            kv_pos = jnp.arange(T)[None, None, None, None, :]
            mask = kv_pos <= positions[:, :, None, None, None]
            qg = q.reshape(B, S, KV, G, hd)
            out = _sdpa(qg, kg, vg, mask, cfg.attn_logit_softcap,
                        seq_shard=cfg.attn_seq_shard_constraint)
    elif kv_cache is not None:
        ck, cv = kv_cache["k"], kv_cache["v"]
        if jnp.ndim(cache_pos) == 0:
            ck = jax.lax.dynamic_update_slice(ck, k.astype(ck.dtype), (0, cache_pos, 0, 0))
            cv = jax.lax.dynamic_update_slice(cv, v.astype(cv.dtype), (0, cache_pos, 0, 0))
        else:
            assert S == 1, "per-sequence cache_pos is decode-only"
            b = jnp.arange(B)
            ck = ck.at[b, cache_pos].set(k[:, 0].astype(ck.dtype))
            cv = cv.at[b, cache_pos].set(v[:, 0].astype(cv.dtype))
        T = ck.shape[1]
        new_cache = {"k": ck, "v": cv}
        if use_pallas(cfg) and S == 1:
            # decode: flash-decoding kernel over the cache (scalar or
            # per-sequence [B] positions; under a ShardingPlan the wrapper
            # shard_maps the kernel over the mesh's 'model' axis)
            from repro.distributed.sharding import current_kernel_mesh
            from repro.kernels import ops as kops
            out = kops.decode_attention(
                q[:, 0], ck.transpose(0, 2, 1, 3), cv.transpose(0, 2, 1, 3),
                length=cache_pos + 1, mesh=current_kernel_mesh())
            out = out[:, None]                                       # [B,1,H,hd]
        else:
            kg, vg = ck, cv
            if cfg.attn_sp_prefill and S > 1:
                from jax.sharding import PartitionSpec as P
                # prefill sequence parallelism: q seq-sharded over 'model',
                # K/V gathered -> the [B, S/16, ., ., T] scores stay local
                q = jax.lax.with_sharding_constraint(
                    q, P("data", "model", None, None))
                kg = jax.lax.with_sharding_constraint(
                    ck, P("data", None, None, None))
                vg = jax.lax.with_sharding_constraint(
                    cv, P("data", None, None, None))
            kv_pos = jnp.arange(T)[None, None, None, None, :]       # [1,1,1,1,T]
            q_pos = (positions[:, :, None, None, None])              # [B,S,1,1,1]
            mask = kv_pos <= q_pos
            qg = q.reshape(B, S, KV, G, hd)
            out = _sdpa(qg, kg, vg, mask, cfg.attn_logit_softcap,
                        seq_shard=cfg.attn_seq_shard_constraint and S == 1)
    else:
        T = S
        new_cache = None
        if cfg.attn_sp_prefill and S > 1:
            from jax.sharding import PartitionSpec as P
            # sequence parallelism: q sharded on S over 'model', k/v
            # gathered — scores [B, S/16, ., ., T] stay shard-local
            q = jax.lax.with_sharding_constraint(
                q, P("data", "model", None, None))
            k = jax.lax.with_sharding_constraint(
                k, P("data", None, None, None))
            v = jax.lax.with_sharding_constraint(
                v, P("data", None, None, None))
        if use_pallas(cfg) and causal and S > 1:
            from repro.distributed.sharding import current_kernel_mesh
            from repro.kernels import ops as kops
            out = kops.flash_attention(
                q.transpose(0, 2, 1, 3), k.transpose(0, 2, 1, 3),
                v.transpose(0, 2, 1, 3), causal=True,
                softcap=cfg.attn_logit_softcap,
                mesh=current_kernel_mesh())
            out = out.transpose(0, 2, 1, 3)                          # [B,S,H,hd]
        else:
            if causal:
                mask = (jnp.arange(T)[None, None, None, None, :]
                        <= positions[:, :, None, None, None])
            else:
                mask = jnp.ones((1, 1, 1, 1, T), dtype=bool)
            qg = q.reshape(B, S, KV, G, hd)
            out = _sdpa(qg, k, v, mask, cfg.attn_logit_softcap)

    out = out.reshape(B, S, H * hd)
    y = jnp.einsum("bse,ed->bsd", out, p["wo"])
    if adapters is not None and "wo" in adapters:
        from repro.models.adapters import lora_delta
        y = y + lora_delta(out, adapters["wo"], adapter_ids)
    return y, new_cache


def make_cross_attn_params(pf: ParamFactory, cfg: ModelConfig) -> dict:
    D, H, hd = cfg.d_model, cfg.n_heads, cfg.head_dim
    return {
        "wq": pf((D, H * hd)),
        "wk": pf((D, H * hd)),
        "wv": pf((D, H * hd)),
        "wo": pf((H * hd, D)),
    }


def cross_attention(p: dict, x: jax.Array, enc: jax.Array, cfg: ModelConfig):
    """Full-head cross attention (whisper decoder -> encoder states)."""
    B, S, D = x.shape
    T = enc.shape[1]
    H, hd = cfg.n_heads, cfg.head_dim
    q = jnp.einsum("bsd,de->bse", x, p["wq"]).reshape(B, S, H, hd)
    k = jnp.einsum("btd,de->bte", enc, p["wk"]).reshape(B, T, H, hd)
    v = jnp.einsum("btd,de->bte", enc, p["wv"]).reshape(B, T, H, hd)
    mask = jnp.ones((1, 1, 1, 1, T), dtype=bool)
    out = _sdpa(q.reshape(B, S, H, 1, hd), k, v, mask)
    out = out.reshape(B, S, H * hd)
    return jnp.einsum("bse,ed->bsd", out, p["wo"])


# ---------------------------------------------------------------------------
# gated MLP (SwiGLU / GeGLU)
# ---------------------------------------------------------------------------

def make_mlp_params(pf: ParamFactory, d_model: int, d_ff: int,
                    fused: bool = False) -> dict:
    if fused:
        return {"w_gu": pf((d_model, 2 * d_ff)), "w_down": pf((d_ff, d_model))}
    return {
        "w_gate": pf((d_model, d_ff)),
        "w_up": pf((d_model, d_ff)),
        "w_down": pf((d_ff, d_model)),
    }


def mlp_block(p: dict, x: jax.Array, act: str = "silu") -> jax.Array:
    if "w_gu" in p:
        gu = jnp.einsum("bsd,df->bsf", x, p["w_gu"])
        F = gu.shape[-1] // 2
        g, u = gu[..., :F], gu[..., F:]
    else:
        g = jnp.einsum("bsd,df->bsf", x, p["w_gate"])
        u = jnp.einsum("bsd,df->bsf", x, p["w_up"])
    a = jax.nn.silu(g) if act == "silu" else jax.nn.gelu(g, approximate=True)
    return jnp.einsum("bsf,fd->bsd", a * u, p["w_down"])


# ---------------------------------------------------------------------------
# embeddings / lm head
# ---------------------------------------------------------------------------

def embed_tokens(embed: jax.Array, tokens: jax.Array, scale_by_dim: bool = False):
    x = jnp.take(embed, tokens, axis=0)
    if scale_by_dim:
        x = x * np.sqrt(embed.shape[1])
    return x


def lm_head(x: jax.Array, params: dict, tied: bool) -> jax.Array:
    if tied:
        return jnp.einsum("bsd,vd->bsv", x, params["embed"])
    return jnp.einsum("bsd,dv->bsv", x, params["lm_head"])
