"""Operations and bytes the algorithms need, computed from shapes and live
lengths, for roofline shares and model FLOP utilisation."""

from __future__ import annotations

import numpy as np

from benchlib import weights


def paged_decode(c: dict, positions, calls: int, n_slots: int,
                 page_size: int, kv_itemsize: int = 2,
                 act_itemsize: int = 2) -> dict:
    """FLOPs and HBM bytes of the paged decode-attention kernel, summed
    over ``calls`` batched decode steps of every layer.

    ``positions`` lists, for every live row of those steps, the position
    it decoded (it attends over ``position + 1`` keys).  The other rows of
    each ``n_slots``-row batch are dummies over one key.  A row reads the
    K and V pages its keys touch, its query and writes its output."""
    d = weights.dims(c)
    H, KV, hd, L = d["H"], d["KV"], d["hd"], d["L"]
    keys = np.asarray(positions, np.int64) + 1
    dummies = max(0, calls * n_slots - len(keys))
    pages = int(np.sum(-(-keys // page_size))) + dummies
    flops = 4 * H * hd * (int(keys.sum()) + dummies)
    kv_bytes = pages * page_size * KV * hd * kv_itemsize * 2
    qo_bytes = calls * n_slots * H * hd * act_itemsize * 2
    return {"flops": L * flops, "bytes": L * kv_bytes + L * qo_bytes}


def roofline_share(work: dict, seconds: float, peak: dict) -> tuple:
    """(percent of the roofline bound reached, which bound applies)."""
    t_flops = work["flops"] / peak["bf16_flops"]
    t_bytes = work["bytes"] / peak["hbm_bytes_per_s"]
    bound = "hbm" if t_bytes >= t_flops else "compute"
    return 100.0 * max(t_flops, t_bytes) / seconds, bound


def decode_token_flops(c: dict, positions) -> float:
    """Model FLOPs of decoding tokens at ``positions``: two per active
    weight (every layer's share that a token reaches, and the output
    head), plus attention over the keys."""
    fam = weights.family(c)
    keys = np.asarray(positions, np.int64) + 1
    return float(2 * fam.n_params(c)["active"] * len(keys)
                 + fam.attention_flops_per_key(c) * int(keys.sum()))
