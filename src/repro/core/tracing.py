"""Lax tracing of inference: weight access order + kernel set (TIDAL §4.1,
Figure 10 right).

TIDAL hooks PyTorch's dispatcher to observe, at runtime, (a) the order in
which weight tensors are consumed by GPU kernels and (b) which kernels are
launched.  In JAX the data-flow graph is *already* explicit — the jaxpr —
so the tracer is a jaxpr walk:

  * each params leaf labels one jaxpr invar;
  * equations are visited in topological (execution) order;
  * the first equation touching a labelled var records an access;
  * ``scan`` bodies are walked once and expanded ``length`` times, giving
    per-layer granularity for stacked weights (this is what makes the order
    finer than "initialization order" — e.g. a tied embedding is initialized
    once but accessed FIRST by the embedding lookup, the paper's Fig. 20
    case);
  * labels flow through pure layout ops (reshape/squeeze/expand_dims) without
    recording an access — those are metadata ops, the bytes are needed only
    at the first *compute* consumer.  This is what gives hierarchical models
    (xlstm units, zamba shared-attn interleave) per-layer granularity even
    though their stacked params are reshaped to [units, per_unit, ...] before
    the scan;
  * every equation's (primitive, shape-signature) goes into the kernel set;
    the deduplicated set is what proactive code loading pre-warms (§5.1) —
    identical transformer blocks contribute one body's worth of signatures,
    mirroring TIDAL's kernel dedup across identical blocks.

Because tracing happens on abstract values (ShapeDtypeStruct), it costs no
device time at all — the JAX substrate improves on the paper's <1.2%
runtime tracing overhead by construction (measured in fig20_overhead).
"""

from __future__ import annotations

import dataclasses
from typing import Callable, Sequence

import jax
import numpy as np

from jax.extend.core import Literal as _Literal

from repro.utils import path_str

# A weight key: (param path, layer index or None).  Weights of a stacked
# leaf carry the flat index into the original leading axis; unstacked
# weights carry ().
WeightKey = tuple


@dataclasses.dataclass
class AccessTrace:
    order: list                    # list[WeightKey] in first-use order
    kernels: set                   # deduped (primitive, shape-sig)
    kernel_launches: int           # total eqn executions (scan-expanded)
    n_params_seen: int

    def key_set(self) -> set:
        return set(self.order)


@dataclasses.dataclass(frozen=True)
class _Label:
    path: str


def _sig(eqn) -> tuple:
    return (eqn.primitive.name,
            tuple((tuple(v.aval.shape), str(v.aval.dtype))
                  for v in eqn.invars if hasattr(v, "aval")))


_SUBJAXPR_KEYS = ("jaxpr", "call_jaxpr", "body_jaxpr", "cond_jaxpr",
                  "fun_jaxpr")

# layout-only primitives: the label flows to the output, no access recorded
_TRANSPARENT = {"reshape", "squeeze", "expand_dims"}


def _closed(j):
    if hasattr(j, "jaxpr"):  # ClosedJaxpr
        return j.jaxpr
    return j


def _get(labels: dict, v):
    if isinstance(v, _Literal):
        return None
    return labels.get(v)


class _Walker:
    def __init__(self):
        self.kernels: set = set()

    def walk(self, jaxpr, labels: dict) -> tuple[list, int]:
        """Returns (accesses, eqn count).

        Each access is (label, idx, dims): ``idx`` are the per-scan-level
        indices accumulated inside this jaxpr (innermost last) and ``dims``
        the corresponding scan lengths, used to flatten to the original
        stacked axis.
        """
        labels = dict(labels)
        order: list = []
        seen: set = set()
        count = 0

        def record(lab, idx, dims):
            key = (lab.path, idx)
            if key not in seen:
                seen.add(key)
                order.append((lab, idx, dims))

        for eqn in jaxpr.eqns:
            count += 1
            self.kernels.add(_sig(eqn))
            name = eqn.primitive.name

            if name in _TRANSPARENT and len(eqn.outvars) == 1:
                data_labels = [_get(labels, v) for v in eqn.invars]
                data_labels = [l for l in data_labels if l is not None]
                if len(data_labels) == 1:
                    labels[eqn.outvars[0]] = data_labels[0]
                    continue

            if name == "scan":
                body = _closed(eqn.params["jaxpr"])
                length = int(eqn.params["length"])
                n_consts = eqn.params["num_consts"]
                n_carry = eqn.params["num_carry"]
                sub_labels = {}
                stacked: set = set()
                for i, (bv, ov) in enumerate(zip(body.invars, eqn.invars)):
                    lab = _get(labels, ov)
                    if lab is not None:
                        sub_labels[bv] = lab
                        if i >= n_consts + n_carry:       # an xs input: peeled
                            stacked.add(lab.path)
                body_order, body_count = self.walk(body, sub_labels)
                count += body_count * length
                for layer in range(length):
                    for lab, idx, dims in body_order:
                        if lab.path in stacked:
                            record(lab, (layer,) + idx, (length,) + dims)
                        else:
                            record(lab, idx, dims)
                continue

            sub = None
            for k in _SUBJAXPR_KEYS:
                if k in eqn.params:
                    sub = eqn.params[k]
                    break
            if sub is not None and not isinstance(sub, (tuple, list)):
                body = _closed(sub)
                if len(body.invars) == len(eqn.invars):
                    sub_labels = {
                        bv: _get(labels, ov)
                        for bv, ov in zip(body.invars, eqn.invars)
                        if _get(labels, ov) is not None}
                    body_order, body_count = self.walk(body, sub_labels)
                    count += body_count
                    for lab, idx, dims in body_order:
                        record(lab, idx, dims)
                    continue

            # plain equation: record first use of any labelled invar
            for v in eqn.invars:
                lab = _get(labels, v)
                if lab is not None:
                    record(lab, (), ())
        return order, count


def _flatten_idx(idx: tuple, dims: tuple):
    """Multi-level scan indices -> flat index into the original leading axis.

    The per-unit reshape [L, ...] -> [U, E, ...] is row-major, so
    flat = ravel_multi_index(idx, dims)."""
    if not idx:
        return ()
    flat = 0
    for i, d in zip(idx, dims):
        flat = flat * d + i
    return (flat,)


def trace_weight_access(fn: Callable, params, *rest) -> AccessTrace:
    """Trace ``fn(params, *rest)`` and extract the weight access order.

    params leaves may be concrete arrays or ShapeDtypeStructs (preferred —
    zero device work).  ``rest`` inputs are traced but not labelled.
    """
    closed = jax.make_jaxpr(fn)(params, *rest)
    jaxpr = closed.jaxpr

    flat_params, _ = jax.tree_util.tree_flatten(params)
    paths = [path_str(p) for p, _ in jax.tree_util.tree_leaves_with_path(params)]
    labels = {}
    for var, path in zip(jaxpr.invars[:len(flat_params)], paths):
        labels[var] = _Label(path)

    w = _Walker()
    order_raw, count = w.walk(jaxpr, labels)
    order, seen = [], set()
    for lab, idx, dims in order_raw:
        key = (lab.path, _flatten_idx(idx, dims))
        if key not in seen:
            seen.add(key)
            order.append(key)
    return AccessTrace(order=order, kernels=w.kernels,
                       kernel_launches=count,
                       n_params_seen=len({p for p, _ in order}))


# ---------------------------------------------------------------------------
# weight size accounting (per WeightKey, for streaming schedules)
# ---------------------------------------------------------------------------

def weight_sizes(params, order: Sequence[WeightKey]) -> dict:
    """Bytes per WeightKey.  A key with a layer index refers to one slice of
    the stacked leaf along its leading axis."""
    by_path = {path_str(p): leaf
               for p, leaf in jax.tree_util.tree_leaves_with_path(params)}
    sizes = {}
    for path, idx in order:
        leaf = by_path[path]
        shape = leaf.shape[len(idx):]
        sizes[(path, idx)] = int(np.prod(shape)) * np.dtype(leaf.dtype).itemsize
    return sizes


def coverage(params, trace: AccessTrace) -> tuple[set, set]:
    """(accessed paths, missed paths) — sanity check that the trace touched
    every parameter (missed weights would never be streamed)."""
    all_paths = {path_str(p)
                 for p, _ in jax.tree_util.tree_leaves_with_path(params)}
    got = {p for p, _ in trace.order}
    return got, all_paths - got


def total_order_bytes(params, trace: AccessTrace) -> int:
    return sum(weight_sizes(params, trace.order).values())
