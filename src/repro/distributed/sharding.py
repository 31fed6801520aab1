"""Sharding plans: params / optimizer / caches / batches onto the
production mesh (single-pod 16x16 = (data, model); multi-pod 2x16x16 =
(pod, data, model)).

The solver is divisibility-aware and heuristic with per-family overrides:

  * params: tensor-parallel over 'model' on the largest divisible
    non-leading axis (ties -> last axis = column-parallel), expert axes
    ALWAYS over 'model' (EP), optional FSDP (ZeRO-3) over 'data' (+'pod')
    on a second axis for large models; the scan-stacked layer axis is never
    sharded (the scan slices it every iteration);
  * batches: global batch over ('pod','data') when divisible;
  * KV caches / recurrent state: batch over data when divisible, else the
    SEQUENCE axis (long_500k with batch 1 shards the 500k-token cache over
    the data axis — attention then reduces partial softmax stats across
    shards, which GSPMD derives from the jnp ops); heads (or head_dim)
    over 'model'.

Every decision is pure shape arithmetic -> property-testable, and every
leaf falls back to replication rather than failing.
"""

from __future__ import annotations

import contextlib
import contextvars
import dataclasses

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.models.config import ModelConfig
from repro.utils import path_str

# The mesh whose 'model' axis the Pallas attention wrappers shard_map
# over.  Pallas calls cannot live inside GSPMD-partitioned jit code —
# the kernel would silently fall back to the XLA reference — so the
# sharded serve entry points enter this context around tracing, and the
# attention layer threads it down to ``repro.kernels.ops`` where the
# kernel is shard_map'd per 'model' shard (heads split; each device runs
# the un-partitioned kernel on its head slice).
_KERNEL_MESH: contextvars.ContextVar = contextvars.ContextVar(
    "kernel_mesh", default=None)


@contextlib.contextmanager
def use_kernel_mesh(mesh):
    """Scope under which Pallas attention wrappers shard_map over
    ``mesh``'s 'model' axis (None = single-device, no wrapping)."""
    token = _KERNEL_MESH.set(mesh)
    try:
        yield
    finally:
        _KERNEL_MESH.reset(token)


def current_kernel_mesh():
    """The mesh installed by :func:`use_kernel_mesh` (or None)."""
    return _KERNEL_MESH.get()


def dp_axes(mesh: Mesh) -> tuple:
    return ("pod", "data") if "pod" in mesh.axis_names else ("data",)


def _axis_size(mesh: Mesh, names) -> int:
    if isinstance(names, str):
        names = (names,)
    n = 1
    for a in names:
        n *= mesh.shape[a]
    return n


@dataclasses.dataclass
class ShardingPlan:
    mesh: Mesh
    fsdp: bool

    def named(self, spec: P) -> NamedSharding:
        return NamedSharding(self.mesh, spec)

    # ---- serving-runtime API ---------------------------------------------
    # The serving stack (TemplateServer -> WeightStreamer -> KV pools ->
    # ContinuousBatchingEngine / FaaSRuntime) threads one plan end to end:
    # params stream into NamedSharding-placed buffers, cache arenas are
    # allocated sharded, and the jit'd serve entry points carry these
    # shardings in/out so GSPMD partitions prefill and decode.

    @property
    def replicated(self) -> NamedSharding:
        return NamedSharding(self.mesh, P())

    def param_shardings(self, model):
        """NamedSharding pytree matching ``model.init_params``."""
        return to_named(param_specs(model, self.mesh, fsdp=self.fsdp),
                        self.mesh)

    def leaf_param_specs(self, model) -> dict:
        """{path -> PartitionSpec} for every param leaf.  The template
        server uses this to place resident / streamed / dynamic weights;
        a per-layer slice of a stacked leaf drops the leading spec entry
        (the scan axis is never sharded)."""
        specs = param_specs(model, self.mesh, fsdp=self.fsdp)
        return {path_str(p): s for p, s in
                jax.tree_util.tree_leaves_with_path(
                    specs, is_leaf=lambda x: isinstance(x, P))}

    def cache_shardings(self, model, cache_tree):
        """Dense slot-pool / transient prefill caches ([L, B, T, ...])."""
        b = next(iter(jax.tree.leaves(cache_tree))).shape[1]
        return to_named(cache_specs(model, cache_tree, self.mesh, batch=b),
                        self.mesh)

    def paged_cache_shardings(self, model, cache_tree):
        """Block-paged KV arenas ([L, n_pages, page_size, ...])."""
        del model
        return to_named(paged_cache_specs(cache_tree, self.mesh), self.mesh)

    def init_params(self, model, rng):
        """Seeded params created directly in their tensor-parallel layout:
        a model that needs several chips is never built whole on one."""
        return jax.jit(model.init_params,
                       out_shardings=self.param_shardings(model))(rng)


def serving_plan(mesh: Mesh) -> ShardingPlan:
    """Tensor-parallel serving plan: TP over 'model', no FSDP (serving
    replicas hold full shards; ZeRO-style gathers would serialize decode)."""
    return ShardingPlan(mesh=mesh, fsdp=False)


def _choose_param_spec(path: str, shape: tuple, mesh: Mesh, cfg: ModelConfig,
                       fsdp: bool, stacked: bool) -> P:
    model_n = mesh.shape["model"]
    dp = dp_axes(mesh)
    dp_n = _axis_size(mesh, dp)
    ndim = len(shape)
    start = 1 if stacked else 0          # never shard the scan axis
    dims = list(range(start, ndim))
    assign: dict[int, object] = {}

    # MLA low-rank bottlenecks (wq_a/wkv_a + their norms): sharding ANY of
    # the rank dims (q_lora 1536 / kv_lora 512 / rope 64) — whether by TP or
    # by the FSDP second axis — makes downstream score einsums contract over
    # a sharded/partial axis, and GSPMD defers the psum into [B,S,H,T]-sized
    # score tensors (measured 38 TiB/step on deepseek train_4k, §Perf #3).
    # They are small (~2 GiB/chip total): keep them fully REPLICATED; TP
    # happens on wq_b/wkv_b head-flattened output dims.
    if cfg.use_mla and ("q_a" in path or "kv_a" in path):
        return P(*[None] * ndim)
    # ...and the b-side projections [rank, H*dims] take TP on the output
    # dim but NO FSDP: their only other dim is the rank (contraction) dim,
    # and data-sharding it re-creates the same deferred-psum blowup
    # (§Perf #3 regression caught when the global fsdp order was reverted).
    mla_b = cfg.use_mla and ("wq_b" in path or "wkv_b" in path)

    # expert-parallel override: the axis equal to n_experts goes to 'model'
    if "experts" in path and cfg.n_experts:
        for d in dims:
            if shape[d] == cfg.n_experts and cfg.n_experts % model_n == 0:
                assign[d] = "model"
                break

    if not assign:
        # tensor parallel: largest divisible axis, ties -> last axis
        best, best_size = None, 0
        for d in dims:
            if shape[d] % model_n == 0 and shape[d] >= best_size:
                best, best_size = d, shape[d]
        if best is not None and best_size >= model_n:
            assign[best] = "model"

    if fsdp and not mla_b:
        # ZeRO-3: one more axis over the data axes.  Preference order:
        # output dim first, contraction dim (ndim-2) LAST RESORT only —
        # contraction-dim sharding makes GSPMD defer the psum into the
        # consumer, which is acceptable for [B,S,F]-sized matmul outputs
        # (classic ZeRO-as-reduce) but catastrophic when the consumer is an
        # attention score tensor (§Perf #3: 38 TiB/step on deepseek-v3).
        candidates = ([ndim - 1]
                      + [d for d in dims if d not in (ndim - 1, ndim - 2)]
                      + ([ndim - 2] if ndim - 2 >= start else []))
        for d in candidates:
            if d in assign or d < start:
                continue
            if shape[d] % dp_n == 0 and shape[d] >= dp_n:
                assign[d] = dp if len(dp) > 1 else dp[0]
                break

    return P(*[assign.get(d) for d in range(ndim)])


_STACKED_PREFIXES = ("blocks.", "mlstm.", "slstm.", "mamba.", "enc_blocks.",
                     "dec_blocks.")


def param_specs(model, mesh: Mesh, fsdp: bool = False, mode: str = "tp"):
    """PartitionSpec pytree matching the model's params.

    mode='tp'     : tensor-parallel over 'model' (+ optional FSDP on 'data');
    mode='fsdp2d' : NO tensor parallelism — params stored sharded over the
        combined (data x model) device grid and all-gathered per layer.
        Pairs with seq-parallel activations: turns per-layer [B,S,D]
        activation psums into per-layer weight gathers, which are ~25x
        smaller at long-sequence prefill (hillclimb #2)."""
    cfg = model.cfg
    specs = model.init_params(abstract=True)
    model_n = mesh.shape["model"]
    dp = dp_axes(mesh)
    all_axes = dp + ("model",)
    all_n = _axis_size(mesh, all_axes)

    def choose(p, leaf):
        path = path_str(p)
        stacked = path.startswith(_STACKED_PREFIXES)
        shape = tuple(leaf.shape)
        if mode == "fsdp2d":
            start = 1 if stacked else 0
            best, best_size = None, 0
            for d in range(start, len(shape)):
                if shape[d] % all_n == 0 and shape[d] >= best_size:
                    best, best_size = d, shape[d]
            assign = {best: all_axes} if best is not None else {}
            if best is None:
                # fall back to the model axis only (small leaves)
                for d in range(start, len(shape)):
                    if shape[d] % model_n == 0 and shape[d] >= model_n:
                        assign = {d: "model"}
                        break
            return P(*[assign.get(d) for d in range(len(shape))])
        return _choose_param_spec(path, shape, mesh, cfg, fsdp, stacked)

    return jax.tree_util.tree_map_with_path(choose, specs)


def opt_state_specs(p_specs, mesh: Mesh, factored: bool = False,
                    opt_state=None):
    """Optimizer-state specs mirror the param specs; factored second-moment
    leaves (reduced rank) get a recomputed spec from their own shape."""
    if opt_state is None:
        return {"m": p_specs, "v": p_specs, "step": P()}

    def mirror(spec_tree, state_tree):
        def pick(p, leaf):
            # match by path into the param spec tree; fall back to replicate
            try:
                node = spec_tree
                for part in p:
                    key = getattr(part, "key", getattr(part, "idx", None))
                    node = node[key]
                if hasattr(node, "__len__") and len(node) == len(leaf.shape):
                    return node
            except Exception:
                pass
            return P()
        return jax.tree_util.tree_map_with_path(pick, state_tree)

    return {"m": mirror(p_specs, opt_state["m"]),
            "v": mirror(p_specs, opt_state["v"]),
            "step": P()}


def batch_specs(batch_tree, mesh: Mesh, seq_parallel: bool = False):
    """Shard global batch over (pod, data) when divisible.

    ``seq_parallel``: additionally shard the sequence axis (dim 1) over
    'model' — activations then enter the network seq-sharded, turning TP
    activation psums into per-layer K/V all-gathers (hillclimb #2)."""
    dp = dp_axes(mesh)
    dp_n = _axis_size(mesh, dp)
    dp_name = dp if len(dp) > 1 else dp[0]
    model_n = mesh.shape["model"]

    def choose(leaf):
        shape = tuple(leaf.shape)
        assign = [None] * len(shape)
        if shape and shape[0] % dp_n == 0 and shape[0] >= dp_n:
            assign[0] = dp_name
        if (seq_parallel and len(shape) >= 2
                and shape[1] % model_n == 0 and shape[1] >= model_n):
            assign[1] = "model"
        return P(*assign)

    return jax.tree.map(choose, batch_tree)


# Recurrent-state cache groups (stacked [L, B, ...] leaves with NO
# sequence axis).  Explicit per-leaf TP dims — shapes from
# ``ssm.{mamba2,mlstm,slstm}_state_shape``:
#   mamba.h    [L,B,H,dh,ds]   heads -> 'model'
#   mamba.conv [L,B,W-1,ch]    conv channels (last) -> 'model'
#   mlstm.C    [L,B,H,dh,dh]   heads -> 'model'
#   mlstm.n/m  [L,B,H(,dh)]    heads -> 'model'
#   mlstm.conv [L,B,W-1,d_in]  conv channels (last) -> 'model'
#   slstm.*    [L,B,H,dh]      heads -> 'model'
_SSM_CACHE_PREFIXES = ("mamba.", "mlstm.", "slstm.")


def _ssm_model_dims(path: str, ndim: int) -> list:
    """Candidate 'model' dims for one recurrent-state leaf, best first."""
    if path.endswith(".conv"):
        return [ndim - 1]                 # channels; NEVER the window dim
    if path.startswith("mamba."):
        # mamba2 h [L,B,H,dh,ds]: heads or REPLICATED.  Pinning dh or ds
        # fights the SSD chunk einsums (their B/C operands propagate
        # ds-factored shardings from the in_proj TP split) and the SPMD
        # partitioner answers with involuntary full rematerializations
        # of the [B,K,H,dh,ds] chunk states every step.
        return [2]
    # mLSTM/sLSTM states tolerate per-head-dim sharding (their update is
    # a per-head outer product): heads first, then dh
    return [2] + list(range(3, ndim))


def cache_specs(model, cache_tree, mesh: Mesh, batch: int,
                prefer_seq: bool = False, replicate_model: bool = False):
    """KV caches / recurrent state.  Leaves are stacked [L, B, ...].

    ``prefer_seq``: put the 'model' axis on the SEQUENCE dim of attention
    caches instead of heads/head_dim.  For decode this is the flash-decoding
    sharding — QK^T and PV run shard-local over the seq partition and only
    softmax stats + the [B,H,hd] partial outputs cross shards, instead of
    psum'ing [B,H,T]-sized score tensors (hillclimb #1 in EXPERIMENTS.md
    §Perf; kept off for prefill where scores are seq-local anyway)."""
    model_n = mesh.shape["model"]
    dp = dp_axes(mesh)
    dp_n = _axis_size(mesh, dp)
    dp_name = dp if len(dp) > 1 else dp[0]

    def choose(p, leaf):
        path = path_str(p)
        shape = tuple(leaf.shape)
        ndim = len(shape)
        assign: dict[int, object] = {}
        # axis 0 = layer stack (never sharded); axis 1 = batch
        if ndim >= 2 and shape[1] % dp_n == 0 and shape[1] >= dp_n:
            assign[1] = dp_name
        elif ndim >= 3 and shape[2] % dp_n == 0 and shape[2] >= dp_n:
            assign[2] = dp_name          # long-context: shard the seq axis
        if replicate_model:
            # SP prefill: K/V consumed fully by every seq shard — a
            # model-replicated cache makes writes and reads local
            return P(*[assign.get(d) for d in range(ndim)])
        # recurrent SSM states (mamba/mLSTM/sLSTM) have NO seq dim — axis 2
        # is heads (or the conv window).  Classify by the cache group, not
        # by substring: "mamba.conv" / "mlstm.conv" contain 'v' and a
        # name-based match would seq-shard a 3-wide conv window.
        if path.startswith(_SSM_CACHE_PREFIXES):
            assign.pop(2, None)          # never data-shard a head/window dim
            for d in _ssm_model_dims(path, ndim):
                if d not in assign and shape[d] % model_n == 0 \
                        and shape[d] >= model_n:
                    assign[d] = "model"
                    break
            return P(*[assign.get(d) for d in range(ndim)])
        # attention caches (k/v, MLA c_kv/k_rope, hybrid attn_kv, cross)
        # have their seq dim at axis 2
        if prefer_seq and ndim >= 3 and 2 not in assign \
                and shape[2] % model_n == 0 and shape[2] >= model_n:
            assign[2] = "model"
        elif path.startswith("attn_kv."):
            # hybrid shared-attention KV ([U,B,S,kv,hd]): kv heads over
            # 'model', or REPLICATED.  Neither the seq axis nor head_dim
            # is a TP fallback here: the shared block's projections leave
            # k/v head-major-sharded, so pinning any other dim makes every
            # decode-step cache update an involuntary full
            # rematerialization in the SPMD partitioner (S=24 and hd=16
            # divided the smoke mesh when the 4 heads did not).
            if ndim > 3 and 3 not in assign and shape[3] % model_n == 0 \
                    and shape[3] >= model_n:
                assign[3] = "model"
        else:
            candidates = [d for d in list(range(3, ndim)) + [2] if ndim > d]
            for d in candidates:
                if d in assign:
                    continue
                if shape[d] % model_n == 0 and shape[d] >= model_n:
                    assign[d] = "model"
                    break
        return P(*[assign.get(d) for d in range(ndim)])

    return jax.tree_util.tree_map_with_path(choose, cache_tree)


def paged_cache_specs(cache_tree, mesh: Mesh):
    """Block-paged KV arenas.  Leaves are ``[L, n_pages, page_size, ...]``:
    the layer stack, page and in-page axes stay REPLICATED (any device must
    be able to read any sequence's pages — the page table is host state,
    not a sharded array), and the head/feature dims go to 'model' — heads
    first, falling back to head_dim / latent rank when the head count does
    not divide the axis."""
    model_n = mesh.shape["model"]

    def choose(leaf):
        shape = tuple(leaf.shape)
        assign: dict[int, object] = {}
        for d in range(3, len(shape)):
            if shape[d] % model_n == 0 and shape[d] >= model_n:
                assign[d] = "model"
                break
        return P(*[assign.get(d) for d in range(len(shape))])

    return jax.tree.map(choose, cache_tree)


def to_named(tree, mesh: Mesh):
    return jax.tree.map(
        lambda s: NamedSharding(mesh, s),
        tree, is_leaf=lambda x: isinstance(x, P))


def validate_specs(spec_tree, shape_tree, mesh: Mesh) -> list:
    """Check divisibility of every sharded dim; returns violations."""
    bad = []
    flat_s = jax.tree_util.tree_leaves_with_path(
        spec_tree, is_leaf=lambda x: isinstance(x, P))
    flat_t = jax.tree_util.tree_leaves_with_path(shape_tree)
    for (ps, spec), (pt, leaf) in zip(flat_s, flat_t):
        for d, names in enumerate(spec):
            if names is None:
                continue
            n = _axis_size(mesh, names)
            if leaf.shape[d] % n:
                bad.append((path_str(ps), d, leaf.shape[d], n))
    return bad
