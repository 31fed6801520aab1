"""The dense decoder family (Llama, Qwen3, SmolLM): one stack of identical
layers of grouped-query attention, optional per-head query/key RMSNorm, and
a SwiGLU MLP, with a tied or untied output head.

A family plug-in is found by a configuration file's ``family`` key
(``bench/families/<family>.py``) and gives the harness everything that
depends on the architecture:

- ``model_config(c)``: the program's ``ModelConfig`` keyword arguments;
- ``groups(c)``: the served layout of the layers, a list of ``(prefix,
  layers, leaves)``: each stacked group's path prefix, the global indices
  of its layers (its leading axis), and one layer's leaves ``{path:
  (shape, kind)}``; ``top_leaves(c)``: the leaves outside the layers;
- ``n_params(c)``: ``total`` parameters, and ``active``: those a decoded
  token multiplies by (every layer's share that a token reaches, and the
  output head; the embedding is a lookup);
- ``attention_flops_per_key(c)``: model FLOPs a decoded token spends on
  each key it attends over, summed over the layers;
- ``kv_bytes_per_token(c)``: arena bytes one cached token takes in bf16;
- ``dims(c)``: the sizes the family's reference and kernel costs read;
- ``rehearsal(c)``: the tiny sizes of the same family for a CPU run.
"""

from __future__ import annotations

import numpy as np


def dims(c: dict) -> dict:
    D, H = c["hidden_size"], c["num_attention_heads"]
    return {"D": D, "H": H, "KV": c["num_key_value_heads"],
            "hd": c.get("head_dim") or D // H, "F": c["intermediate_size"],
            "V": c["vocab_size"], "L": c["num_hidden_layers"],
            "tied": bool(c["tie_word_embeddings"]),
            "qk_norm": bool(c.get("qk_norm", False))}


def model_config(c: dict) -> dict:
    d = dims(c)
    return dict(name=c["name"], family="dense", n_layers=d["L"],
                d_model=d["D"], n_heads=d["H"], n_kv_heads=d["KV"],
                head_dim=d["hd"], d_ff=d["F"], vocab_size=d["V"],
                qk_norm=d["qk_norm"], tied_embeddings=d["tied"],
                rope_theta=float(c["rope_theta"]),
                norm_eps=float(c["rms_norm_eps"]), dtype=c["dtype"])


def layer_leaves(c: dict) -> dict:
    """One decoder layer's leaves: path -> (shape, kind)."""
    d = dims(c)
    D, H, KV, hd, F = d["D"], d["H"], d["KV"], d["hd"], d["F"]
    out = {"attn_norm": ((D,), "norm"), "mlp_norm": ((D,), "norm"),
           "attn.wq": ((D, H * hd), "mat"), "attn.wk": ((D, KV * hd), "mat"),
           "attn.wv": ((D, KV * hd), "mat"), "attn.wo": ((H * hd, D), "mat"),
           "mlp.w_gate": ((D, F), "mat"), "mlp.w_up": ((D, F), "mat"),
           "mlp.w_down": ((F, D), "mat")}
    if d["qk_norm"]:
        out["attn.q_norm"] = ((hd,), "norm")
        out["attn.k_norm"] = ((hd,), "norm")
    return out


def groups(c: dict) -> list:
    return [("blocks", list(range(dims(c)["L"])), layer_leaves(c))]


def top_leaves(c: dict) -> dict:
    d = dims(c)
    out = {"embed": ((d["V"], d["D"]), "embed"),
           "final_norm": ((d["D"],), "norm")}
    if not d["tied"]:
        out["lm_head"] = ((d["D"], d["V"]), "mat")
    return out


def n_params(c: dict) -> dict:
    d = dims(c)
    per_layer = sum(int(np.prod(s)) for s, _ in layer_leaves(c).values())
    top = sum(int(np.prod(s)) for s, _ in top_leaves(c).values())
    layers = per_layer * d["L"]
    return {"total": layers + top, "active": layers + d["V"] * d["D"]}


def attention_flops_per_key(c: dict) -> int:
    d = dims(c)
    return 4 * d["L"] * d["H"] * d["hd"]


def kv_bytes_per_token(c: dict) -> int:
    d = dims(c)
    return 2 * d["KV"] * d["hd"] * 2 * d["L"]


def rehearsal(c: dict) -> dict:
    return dict(c, hidden_size=64, num_attention_heads=4,
                num_key_value_heads=2, head_dim=16, intermediate_size=128,
                vocab_size=512, num_hidden_layers=2)
