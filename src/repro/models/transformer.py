"""Decoder language models for all assigned families (dense / moe / xlstm /
zamba hybrid), built as ``jax.lax.scan`` over stacked homogeneous blocks.

The scan structure matters for three reasons:
  1. compact HLO -> fast multi-pod dry-run compiles;
  2. the per-layer block executable is literally shared across layers — the
     JAX analogue of TIDAL's kernel dedup across identical transformer blocks;
  3. weight streaming operates on the stacked leading axis (layer index =
     traced access order position).

Entry points (uniform across families, dispatched by ``cfg.family``):
  forward(params, cfg, tokens)                      -> logits        (training)
  prefill(params, cfg, tokens, cache)               -> (logits, cache)
  decode_step(params, cfg, cache, tokens, pos)      -> (logits, cache)
  init_params(cfg, rng|None, abstract)              -> params pytree
  make_cache(cfg, batch, max_len, abstract)         -> cache pytree
"""

from __future__ import annotations

from typing import Any, Optional

import jax
import jax.numpy as jnp

from repro.models.config import ModelConfig
from repro.models import quant, ssm
from repro.models.layers import (
    ParamFactory, attention_block, embed_tokens, lm_head, make_attn_params,
    make_mlp_params, mlp_block, rmsnorm)
from repro.models.mla import make_mla_params, mla_attention_block
from repro.models.moe import make_moe_params, moe_aux_loss, moe_block

Params = Any
Cache = Any


def _dtype(cfg: ModelConfig, override=None):
    return jnp.dtype(override or cfg.dtype)


# ---------------------------------------------------------------------------
# parameter construction
# ---------------------------------------------------------------------------

def _stack(pf_factory, n: int, make_one):
    """Build n copies of a param subtree and stack leaves on a leading axis.

    Concrete params come from one program vmapped over the layer index —
    the same values as building each layer alone, but one layer's worth
    of operations to trace and compile, not ``n``."""
    if pf_factory(0).abstract:
        trees = [make_one(pf_factory(i)) for i in range(n)]
        if trees[0] is None:
            return None
        return jax.tree.map(lambda *ls: _stack_leaves(ls), *trees)
    return jax.vmap(lambda i: make_one(pf_factory(i)))(jnp.arange(n))


def _stack_leaves(leaves):
    if isinstance(leaves[0], jax.ShapeDtypeStruct):
        return jax.ShapeDtypeStruct((len(leaves),) + leaves[0].shape, leaves[0].dtype)
    return jnp.stack(leaves)


def _block_params(pf: ParamFactory, cfg: ModelConfig) -> dict:
    """One decoder block (dense or moe or mla)."""
    D = cfg.d_model
    p: dict = {"attn_norm": pf((D,), init="ones"), "mlp_norm": pf((D,), init="ones")}
    if cfg.use_mla:
        p["attn"] = make_mla_params(pf, cfg)
    else:
        p["attn"] = make_attn_params(pf, cfg)
    if cfg.n_experts:
        p["moe"] = make_moe_params(pf, cfg)
    else:
        p["mlp"] = make_mlp_params(pf, D, cfg.d_ff, fused=cfg.fused_glu)
    return p


def init_params(cfg: ModelConfig, rng: Optional[jax.Array] = None,
                abstract: bool = False, dtype=None) -> Params:
    dt = _dtype(cfg, dtype)
    if rng is None:
        rng = jax.random.PRNGKey(0)

    def pf_for(i):
        return ParamFactory(jax.random.fold_in(rng, 1000 + i), dt, abstract)

    top_pf = ParamFactory(jax.random.fold_in(rng, 7), dt, abstract)
    V, D = cfg.vocab_size, cfg.d_model
    params: dict = {"embed": top_pf((V, D), scale=0.02)}

    if cfg.family == "xlstm":
        if cfg.slstm_every:
            n_units = cfg.n_layers // cfg.slstm_every
            m_per_unit = cfg.slstm_every - 1
        else:
            n_units, m_per_unit = 1, cfg.n_layers
        params["mlstm"] = _stack(
            lambda i: pf_for(i), n_units * m_per_unit,
            lambda pf: {"norm": pf((D,), init="ones"),
                        **{"mixer": ssm.make_mlstm_params(pf, cfg)}})
        if cfg.slstm_every:
            params["slstm"] = _stack(
                lambda i: pf_for(10_000 + i), n_units,
                lambda pf: {"norm": pf((D,), init="ones"),
                            "mlp_norm": pf((D,), init="ones"),
                            **{"mixer": ssm.make_slstm_params(pf, cfg)}})
    elif cfg.family == "zamba":
        n_units = cfg.n_layers // cfg.attn_every
        params["mamba"] = _stack(
            lambda i: pf_for(i), cfg.n_layers,
            lambda pf: {"norm": pf((D,), init="ones"),
                        **{"mixer": ssm.make_mamba2_params(pf, cfg)}})
        sp = ParamFactory(jax.random.fold_in(rng, 99), dt, abstract)
        params["shared_attn"] = {
            "attn_norm": sp((D,), init="ones"),
            "attn": make_attn_params(sp, cfg),
            "mlp_norm": sp((D,), init="ones"),
            "mlp": make_mlp_params(sp, D, cfg.d_ff),
        }
    else:  # dense / moe / vlm backbone
        params["blocks"] = _stack(lambda i: pf_for(i), cfg.n_layers,
                                  lambda pf: _block_params(pf, cfg))

    params["final_norm"] = top_pf((D,), init="ones")
    if not cfg.tied_embeddings:
        params["lm_head"] = top_pf((D, V), scale=0.02)
    return params


# ---------------------------------------------------------------------------
# caches / recurrent state
# ---------------------------------------------------------------------------

def _mk(abstract: bool, shape, dtype):
    shape = tuple(int(s) for s in shape)
    if abstract:
        return jax.ShapeDtypeStruct(shape, dtype)
    return jnp.zeros(shape, dtype)


def make_cache(cfg: ModelConfig, batch: int, max_len: int,
               abstract: bool = False, dtype=None) -> Cache:
    dt = _dtype(cfg, dtype)
    f32 = jnp.float32

    if cfg.family == "xlstm":
        every = cfg.slstm_every or 0
        n_m = cfg.n_layers - (cfg.n_layers // every if every else 0)
        cache: dict = {"mlstm": {
            k: _mk(abstract, (n_m,) + s, f32 if k != "conv" else dt)
            for k, s in ssm.mlstm_state_shape(cfg, batch).items()}}
        if not abstract:
            cache["mlstm"]["m"] = cache["mlstm"]["m"] + ssm.EMPTY_M
        if every:
            n_s = cfg.n_layers // every
            cache["slstm"] = {
                k: _mk(abstract, (n_s,) + s, f32)
                for k, s in ssm.slstm_state_shape(cfg, batch).items()}
        return cache

    if cfg.family == "zamba":
        n_units = cfg.n_layers // cfg.attn_every
        cache = {"mamba": {
            k: _mk(abstract, (cfg.n_layers,) + s, f32 if k == "h" else dt)
            for k, s in ssm.mamba2_state_shape(cfg, batch).items()}}
        cache["attn_kv"] = {
            "k": _mk(abstract, (n_units, batch, max_len, cfg.n_kv_heads, cfg.head_dim), dt),
            "v": _mk(abstract, (n_units, batch, max_len, cfg.n_kv_heads, cfg.head_dim), dt),
        }
        return cache

    L = cfg.n_layers
    if cfg.use_mla:
        return {
            "c_kv": _mk(abstract, (L, batch, max_len, cfg.kv_lora_rank), dt),
            "k_rope": _mk(abstract, (L, batch, max_len, cfg.qk_rope_dim), dt),
        }
    return {
        "k": _mk(abstract, (L, batch, max_len, cfg.n_kv_heads, cfg.head_dim), dt),
        "v": _mk(abstract, (L, batch, max_len, cfg.n_kv_heads, cfg.head_dim), dt),
    }


def supports_paged_kv(cfg: ModelConfig) -> bool:
    """Block-paged KV applies to attention caches that grow with sequence
    length: the dense/moe (incl. MLA) families.  SSM/xLSTM/hybrid state is
    constant-size per slot, so those keep the dense slot pool."""
    return cfg.family in ("dense", "moe")


def make_paged_cache(cfg: ModelConfig, n_pages: int, page_size: int,
                     abstract: bool = False, dtype=None,
                     kv_dtype: str | None = None) -> Cache:
    """One shared KV page arena: the (batch, max_len) axes of ``make_cache``
    become (n_pages, page_size).  Logical position ``t`` of a request lives
    at ``[layer, page_table[slot, t // page_size], t % page_size]``.

    ``kv_dtype='int8'`` makes the value leaves int8 and adds a per-row
    float32 ``<leaf>_scale`` arena next to each (shape = value leaf minus
    its last axis; one scale per (page, position, kv_head) head_dim row,
    or per (page, position) latent row for MLA) — quantize-on-write,
    dequantized inside the paged-decode kernel at read time."""
    if not supports_paged_kv(cfg):
        raise ValueError(
            f"{cfg.name}: {cfg.family!r} family has no paged KV layout")
    if kv_dtype not in (None, "int8"):
        raise ValueError(f"kv_dtype must be None or 'int8', got {kv_dtype!r}")
    dt = _dtype(cfg, dtype)
    L = cfg.n_layers
    if cfg.use_mla:
        shapes = {
            "c_kv": (L, n_pages, page_size, cfg.kv_lora_rank),
            "k_rope": (L, n_pages, page_size, cfg.qk_rope_dim),
        }
    else:
        shapes = {
            "k": (L, n_pages, page_size, cfg.n_kv_heads, cfg.head_dim),
            "v": (L, n_pages, page_size, cfg.n_kv_heads, cfg.head_dim),
        }
    if kv_dtype is None:
        return {k: _mk(abstract, s, dt) for k, s in shapes.items()}
    cache = {}
    for k, s in shapes.items():
        cache[k] = _mk(abstract, s, jnp.int8)
        cache[k + quant.SCALE_SUFFIX] = _mk(abstract, s[:-1], jnp.float32)
    return cache


# ---------------------------------------------------------------------------
# block bodies
# ---------------------------------------------------------------------------

def _dense_block(bp, x, cfg, positions, kv_cache, cache_pos,
                 page_table=None, page_size=0, adapters=None,
                 adapter_ids=None):
    h = rmsnorm(x, bp["attn_norm"], cfg.norm_eps)
    if cfg.use_mla:
        if adapters is not None:
            raise NotImplementedError(
                "adapter gather targets the GQA projections, not MLA")
        a, new_cache = mla_attention_block(bp["attn"], h, cfg, positions,
                                           kv_cache, cache_pos,
                                           page_table=page_table,
                                           page_size=page_size)
    else:
        a, new_cache = attention_block(bp["attn"], h, cfg, positions,
                                       kv_cache, cache_pos,
                                       page_table=page_table,
                                       page_size=page_size,
                                       adapters=adapters,
                                       adapter_ids=adapter_ids)
    x = x + a
    h = rmsnorm(x, bp["mlp_norm"], cfg.norm_eps)
    aux = jnp.zeros((), jnp.float32)
    if cfg.n_experts:
        m = moe_block(bp["moe"], h, cfg)
        aux = moe_aux_loss(bp["moe"], h, cfg)
    else:
        m = mlp_block(bp["mlp"], h, cfg.act)
    return x + m, new_cache, aux


def _scan_decoder_blocks(params, cfg, x, positions, cache, cache_pos,
                         training, page_table=None, page_size=0,
                         adapter_bank=None, adapter_ids=None):
    """Scan over stacked dense/moe blocks.  cache may be None (training).
    ``page_table`` (shared across layers, not scanned) switches the
    per-layer cache slices to the block-paged arena layout.  An
    ``adapter_bank`` (leading layer axis) joins the scan's xs so each
    block gathers its own per-layer adapter slices."""

    def body(carry, xs):
        h = carry
        if adapter_bank is None:
            bp, bc = xs
            ab = None
        else:
            bp, bc, ab = xs
        h, new_c, aux = _dense_block(bp, h, cfg, positions, bc, cache_pos,
                                     page_table=page_table,
                                     page_size=page_size,
                                     adapters=ab, adapter_ids=adapter_ids)
        return h, (new_c, aux)

    body_fn = body
    if training and cfg.remat:
        body_fn = jax.checkpoint(body, policy=jax.checkpoint_policies.nothing_saveable)

    xs = ((params["blocks"], cache) if adapter_bank is None
          else (params["blocks"], cache, adapter_bank))
    x, (new_cache, auxs) = jax.lax.scan(body_fn, x, xs)
    return x, new_cache, jnp.sum(auxs)


def _xlstm_stack(params, cfg, x, cache, training):
    """xLSTM: stacked mLSTM blocks with an sLSTM block every ``slstm_every``.

    mLSTM params are stacked [n_m, ...]; sLSTM [n_units, ...].  We scan over
    units; each unit runs (every-1) mLSTM blocks (inner scan) + 1 sLSTM.
    """
    every = cfg.slstm_every

    def mlstm_block(bp, bc, h):
        y, new_state = ssm.mlstm_mixer(bp["mixer"],
                                       rmsnorm(h, bp["norm"], cfg.norm_eps),
                                       cfg, bc)
        return h + y, new_state

    def m_body(h, xs):
        bp, bc = xs
        h, ns = mlstm_block(bp, bc, h)
        return h, ns

    m_body_fn = jax.checkpoint(m_body) if (training and cfg.remat) else m_body

    m_cache = cache["mlstm"] if cache is not None else None
    if not every:
        xs = (params["mlstm"], m_cache)
        x, new_m = jax.lax.scan(m_body_fn, x, xs)
        return x, ({"mlstm": new_m} if cache is not None else None)

    n_units = cfg.n_layers // every
    m_per = every - 1

    def reshape_unit(t):
        return t.reshape((n_units, m_per) + t.shape[1:])

    m_params_u = jax.tree.map(reshape_unit, params["mlstm"])
    m_cache_u = jax.tree.map(reshape_unit, m_cache) if cache is not None else None

    def unit_body(h, xs):
        mp, sp_, mc, sc = xs
        h, new_mc = jax.lax.scan(m_body_fn, h, (mp, mc))
        y, new_sc = ssm.slstm_mixer(sp_["mixer"],
                                    rmsnorm(h, sp_["norm"], cfg.norm_eps), cfg, sc)
        h = h + y
        hn = rmsnorm(h, sp_["mlp_norm"], cfg.norm_eps)
        h = h + mlp_block(sp_["mixer"]["mlp"], hn, cfg.act)
        return h, (new_mc, new_sc)

    s_cache = cache["slstm"] if cache is not None else None
    if cache is None:
        # supply fresh per-unit zero states (training runs from zero state)
        B = x.shape[0]
        zero_m = {k: jnp.zeros((n_units, m_per) + s,
                               x.dtype if k == "conv" else jnp.float32)
                  for k, s in ssm.mlstm_state_shape(cfg, B).items()}
        zero_m["m"] = zero_m["m"] + ssm.EMPTY_M
        zero_s = {k: jnp.zeros((n_units,) + s, jnp.float32)
                  for k, s in ssm.slstm_state_shape(cfg, B).items()}
        m_cache_u, s_cache = zero_m, zero_s

    xs = (m_params_u, params["slstm"], m_cache_u, s_cache)
    x, (new_m_u, new_s) = jax.lax.scan(unit_body, x, xs)
    if cache is None:
        return x, None
    new_m = jax.tree.map(
        lambda t: t.reshape((n_units * m_per,) + t.shape[2:]), new_m_u)
    return x, {"mlstm": new_m, "slstm": new_s}


def _shape_tree(d):
    return {k: v for k, v in d.items()}


def _zamba_stack(params, cfg, x, positions, cache, cache_pos, training):
    """Zamba2: units of ``attn_every`` mamba blocks + one SHARED attn+mlp."""
    every = cfg.attn_every
    n_units = cfg.n_layers // every
    shared = params["shared_attn"]

    def mamba_block(bp, bc, h):
        y, ns = ssm.mamba2_mixer(bp["mixer"],
                                 rmsnorm(h, bp["norm"], cfg.norm_eps), cfg, bc)
        return h + y, ns

    def m_body(h, xs):
        bp, bc = xs
        return mamba_block(bp, bc, h)

    m_body_fn = jax.checkpoint(m_body) if (training and cfg.remat) else m_body

    B = x.shape[0]
    if cache is None:
        m_cache_u = {
            k: jnp.zeros((n_units, every) + s,
                         x.dtype if k == "conv" else jnp.float32)
            for k, s in ssm.mamba2_state_shape(cfg, B).items()}
        kv_u = None
    else:
        m_cache_u = jax.tree.map(
            lambda t: t.reshape((n_units, every) + t.shape[1:]), cache["mamba"])
        kv_u = cache["attn_kv"]

    def unit_body(h, xs):
        if cache is None:
            mp, mc = xs
            kv = None
        else:
            mp, mc, kv = xs
        h, new_mc = jax.lax.scan(m_body_fn, h, (mp, mc))
        hn = rmsnorm(h, shared["attn_norm"], cfg.norm_eps)
        a, new_kv = attention_block(shared["attn"], hn, cfg, positions,
                                    kv, cache_pos)
        h = h + a
        hn = rmsnorm(h, shared["mlp_norm"], cfg.norm_eps)
        h = h + mlp_block(shared["mlp"], hn, cfg.act)
        out = (new_mc,) if cache is None else (new_mc, new_kv)
        return h, out

    m_params_u = jax.tree.map(
        lambda t: t.reshape((n_units, every) + t.shape[1:]), params["mamba"])

    if cache is None:
        x, _ = jax.lax.scan(unit_body, x, (m_params_u, m_cache_u))
        return x, None
    x, (new_m_u, new_kv) = jax.lax.scan(unit_body, x,
                                        (m_params_u, m_cache_u, kv_u))
    new_m = jax.tree.map(
        lambda t: t.reshape((cfg.n_layers,) + t.shape[2:]), new_m_u)
    return x, {"mamba": new_m, "attn_kv": new_kv}


# ---------------------------------------------------------------------------
# entry points
# ---------------------------------------------------------------------------

def _backbone(params, cfg, x, positions, cache, cache_pos, training,
              adapter_bank=None, adapter_ids=None):
    if cfg.family in ("xlstm", "zamba"):
        if adapter_bank is not None:
            raise NotImplementedError(
                f"{cfg.family!r}: adapter gather needs the stacked "
                "dense/moe block layout")
        if cfg.family == "xlstm":
            x, new_cache = _xlstm_stack(params, cfg, x, cache, training)
        else:
            x, new_cache = _zamba_stack(params, cfg, x, positions, cache,
                                        cache_pos, training)
        aux = jnp.zeros((), jnp.float32)
    else:
        x, new_cache, aux = _scan_decoder_blocks(params, cfg, x, positions,
                                                 cache, cache_pos, training,
                                                 adapter_bank=adapter_bank,
                                                 adapter_ids=adapter_ids)
    return x, new_cache, aux


def forward(params: Params, cfg: ModelConfig, tokens: jax.Array,
            training: bool = True):
    """Full-sequence causal forward -> logits [B, S, V]."""
    B, S = tokens.shape
    x = embed_tokens(params["embed"], tokens,
                     scale_by_dim=cfg.scale_embed)
    positions = jnp.broadcast_to(jnp.arange(S)[None, :], (B, S))
    x, _, aux = _backbone(params, cfg, x, positions, None, None, training)
    x = rmsnorm(x, params["final_norm"], cfg.norm_eps)
    logits = lm_head(x, params, cfg.tied_embeddings)
    return logits, aux


def prefill(params: Params, cfg: ModelConfig, tokens: jax.Array, cache: Cache,
            adapter_bank: Optional[dict] = None,
            adapter_ids: Optional[jax.Array] = None):
    """Process the prompt, fill the cache; returns (last-token logits, cache)."""
    B, S = tokens.shape
    x = embed_tokens(params["embed"], tokens, scale_by_dim=cfg.scale_embed)
    positions = jnp.broadcast_to(jnp.arange(S)[None, :], (B, S))
    x, new_cache, _ = _backbone(params, cfg, x, positions, cache,
                                jnp.int32(0), training=False,
                                adapter_bank=adapter_bank,
                                adapter_ids=adapter_ids)
    x = rmsnorm(x[:, -1:, :], params["final_norm"], cfg.norm_eps)
    logits = lm_head(x, params, cfg.tied_embeddings)
    return logits[:, 0], new_cache


def prefill_from(params: Params, cfg: ModelConfig, tokens: jax.Array,
                 cache: Cache, offset: jax.Array,
                 adapter_bank: Optional[dict] = None,
                 adapter_ids: Optional[jax.Array] = None):
    """Suffix-only prefill: process ``tokens`` as positions ``offset ..
    offset+S-1`` against a cache whose first ``offset`` positions are
    ALREADY filled (a reused prompt prefix).

    Positions, RoPE angles and the causal mask all carry the offset, and
    the new K/V land at ``cache_pos=offset`` — so a prefix-reusing request
    reproduces exactly the states a full prefill of prefix+suffix would
    compute (token parity is enforced in tests).  ``offset`` is traced:
    one executable serves every reuse length of a given suffix shape.
    Dense / moe / MLA only (recurrent state has no positional cache).
    """
    if cfg.family not in ("dense", "moe"):
        raise ValueError(
            f"{cfg.name}: {cfg.family!r} family has no suffix-only "
            "prefill (recurrent state is not position-addressable)")
    B, S = tokens.shape
    offset = jnp.asarray(offset, jnp.int32)
    x = embed_tokens(params["embed"], tokens, scale_by_dim=cfg.scale_embed)
    positions = jnp.broadcast_to(offset + jnp.arange(S)[None, :], (B, S))
    x, new_cache, _ = _scan_decoder_blocks(params, cfg, x, positions, cache,
                                           offset, training=False,
                                           adapter_bank=adapter_bank,
                                           adapter_ids=adapter_ids)
    x = rmsnorm(x[:, -1:, :], params["final_norm"], cfg.norm_eps)
    logits = lm_head(x, params, cfg.tied_embeddings)
    return logits[:, 0], new_cache


def decode_step(params: Params, cfg: ModelConfig, cache: Cache,
                tokens: jax.Array, pos: jax.Array):
    """One decode step.  tokens: [B, 1]; pos: scalar int32 (next position)
    or an int32 vector [B] of per-sequence positions (continuous batching:
    every slot decodes at its own offset in one call)."""
    B = tokens.shape[0]
    x = embed_tokens(params["embed"], tokens, scale_by_dim=cfg.scale_embed)
    if jnp.ndim(pos) == 0:
        positions = jnp.broadcast_to(pos[None, None], (B, 1)).astype(jnp.int32)
    else:
        positions = pos[:, None].astype(jnp.int32)
    x, new_cache, _ = _backbone(params, cfg, x, positions, cache, pos,
                                training=False)
    x = rmsnorm(x, params["final_norm"], cfg.norm_eps)
    logits = lm_head(x, params, cfg.tied_embeddings)
    return logits[:, 0], new_cache


def decode_step_paged(params: Params, cfg: ModelConfig, cache: Cache,
                      tokens: jax.Array, pos: jax.Array,
                      page_table: jax.Array, page_size: int,
                      adapter_bank: Optional[dict] = None,
                      adapter_ids: Optional[jax.Array] = None):
    """One decode step over a block-paged KV arena.  tokens: [B, 1];
    pos: int32 vector [B] of per-sequence positions; page_table: [B, NB]
    int32 physical page per logical block (the slot axis of the serving
    pool).  ``cache`` comes from :func:`make_paged_cache`.  With an
    ``adapter_bank``, ``adapter_ids`` [B] selects each slot's LoRA delta
    (0 = null adapter for free/foreign slots)."""
    if not supports_paged_kv(cfg):
        raise ValueError(
            f"{cfg.name}: {cfg.family!r} family has no paged decode path")
    x = embed_tokens(params["embed"], tokens, scale_by_dim=cfg.scale_embed)
    pos = jnp.asarray(pos, jnp.int32)
    positions = pos[:, None]
    x, new_cache, _ = _scan_decoder_blocks(params, cfg, x, positions, cache,
                                           pos, training=False,
                                           page_table=page_table,
                                           page_size=page_size,
                                           adapter_bank=adapter_bank,
                                           adapter_ids=adapter_ids)
    x = rmsnorm(x, params["final_norm"], cfg.norm_eps)
    logits = lm_head(x, params, cfg.tied_embeddings)
    return logits[:, 0], new_cache


def loss_fn(params: Params, cfg: ModelConfig, tokens: jax.Array,
            labels: jax.Array, aux_weight: float = 0.01):
    logits, aux = forward(params, cfg, tokens, training=True)
    logits = logits.astype(jnp.float32)
    logz = jax.nn.logsumexp(logits, axis=-1)
    gold = jnp.take_along_axis(logits, labels[..., None], axis=-1)[..., 0]
    ce = jnp.mean(logz - gold)
    return ce + aux_weight * aux
