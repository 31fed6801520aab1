"""A toy mixture-of-experts family, a plug-in that the tests add as a new
file: the program's ``moe`` family (grouped-query attention; a softmax
router over ``n_routed_experts`` that picks ``num_experts_per_tok`` with
renormalised weights; SwiGLU experts beside ``n_shared_experts`` shared
ones) at the capacity factor that drops no token."""

import numpy as np


def dims(c: dict) -> dict:
    return {"D": c["hidden_size"], "H": c["num_attention_heads"],
            "KV": c["num_key_value_heads"], "hd": c["head_dim"],
            "E": c["n_routed_experts"], "K": c["num_experts_per_tok"],
            "F": c["moe_intermediate_size"], "S": c["n_shared_experts"],
            "V": c["vocab_size"], "L": c["num_hidden_layers"],
            "qk_norm": False}


def model_config(c: dict) -> dict:
    d = dims(c)
    return dict(name=c["name"], family="moe", n_layers=d["L"],
                d_model=d["D"], n_heads=d["H"], n_kv_heads=d["KV"],
                head_dim=d["hd"], d_ff=d["F"], vocab_size=d["V"],
                n_experts=d["E"], top_k=d["K"], moe_d_ff=d["F"],
                n_shared_experts=d["S"], capacity_factor=d["E"] / d["K"],
                tied_embeddings=bool(c["tie_word_embeddings"]),
                rope_theta=float(c["rope_theta"]),
                norm_eps=float(c["rms_norm_eps"]), dtype=c["dtype"])


def _attention(d: dict) -> dict:
    D, H, KV, hd = d["D"], d["H"], d["KV"], d["hd"]
    return {"attn_norm": ((D,), "norm"), "mlp_norm": ((D,), "norm"),
            "attn.wq": ((D, H * hd), "mat"), "attn.wk": ((D, KV * hd), "mat"),
            "attn.wv": ((D, KV * hd), "mat"), "attn.wo": ((H * hd, D), "mat")}


def _experts(d: dict) -> dict:
    D, E, F, S = d["D"], d["E"], d["F"], d["S"]
    return {"moe.router": ((D, E), "mat"),
            "moe.experts.w_gate": ((E, D, F), "mat"),
            "moe.experts.w_up": ((E, D, F), "mat"),
            "moe.experts.w_down": ((E, F, D), "mat"),
            "moe.shared.w_gate": ((D, F * S), "mat"),
            "moe.shared.w_up": ((D, F * S), "mat"),
            "moe.shared.w_down": ((F * S, D), "mat")}


def groups(c: dict) -> list:
    d = dims(c)
    return [("blocks", list(range(d["L"])), {**_attention(d),
                                             **_experts(d)})]


def top_leaves(c: dict) -> dict:
    d = dims(c)
    return {"embed": ((d["V"], d["D"]), "embed"),
            "final_norm": ((d["D"],), "norm"),
            "lm_head": ((d["D"], d["V"]), "mat")}


def _size(leaves: dict) -> int:
    return sum(int(np.prod(s)) for s, _ in leaves.values())


def n_params(c: dict) -> dict:
    d = dims(c)
    layer = _size(_attention(d)) + _size(_experts(d))
    routed = 3 * d["D"] * d["F"]                 # one expert
    active = layer - (d["E"] - d["K"]) * routed
    return {"total": d["L"] * layer + _size(top_leaves(c)),
            "active": d["L"] * active + d["V"] * d["D"]}


def attention_flops_per_key(c: dict) -> int:
    d = dims(c)
    return 4 * d["L"] * d["H"] * d["hd"]


def kv_bytes_per_token(c: dict) -> int:
    d = dims(c)
    return 2 * d["KV"] * d["hd"] * 2 * d["L"]


def rehearsal(c: dict) -> dict:
    return dict(c)                               # already tiny
