"""The llama family (Llama, Yi, Mistral): dense decoder layers of grouped
query attention and a SwiGLU MLP, RMSNorm, rotary positions.  The only
place the benchmark reads this architecture's sizes from a configuration
file (see bench/configs).

Besides the program's ``ArchConfig``, this module gives the weight-draw
rule and the work a token requires: the operations and bytes count what
the algorithm needs, not what a kernel happens to issue, so a later
change to page size or kernel layout moves a roofline share, never these
counts.
"""
from __future__ import annotations

#: the fan-in of each weight, by leaf name, from its stacked shape; None
#: draws ones (a norm's scale)
FAN_IN = {
    "wq": lambda s: s[-3], "wk": lambda s: s[-3], "wv": lambda s: s[-3],
    "wo": lambda s: s[-3] * s[-2],
    "w_gate": lambda s: s[-2], "w_up": lambda s: s[-2],
    "w_down": lambda s: s[-2],
    "head": lambda s: s[-1],
    "table": lambda s: 1,
    "scale": None,
}


def shapes(conf: dict) -> dict:
    """Sizes of a llama-family configuration file."""
    d = int(conf["hidden_size"])
    H = int(conf["num_attention_heads"])
    Kv = int(conf["num_key_value_heads"])
    hd = int(conf.get("head_dim") or d // H)
    ff = int(conf["intermediate_size"])
    V = int(conf["vocab_size"])
    L = int(conf["num_hidden_layers"])
    layer = d * H * hd * 2 + d * Kv * hd * 2 + 3 * d * ff + 2 * d
    itemsize = {"bfloat16": 2, "float16": 2, "float32": 4}[
        conf["torch_dtype"]]
    return dict(L=L, d=d, H=H, Kv=Kv, hd=hd, ff=ff, V=V,
                layer_params=layer, head_params=V * d,
                embed_params=V * d, itemsize=itemsize,
                tied=bool(conf.get("tie_word_embeddings")))


def arch_config(conf: dict):
    """The program's ``ArchConfig`` for a llama-family configuration."""
    from repro.configs.base import ArchConfig

    if conf.get("hidden_act", "silu") != "silu":
        raise ValueError(f"{conf['name']}: only SwiGLU (silu) MLPs run")
    s = shapes(conf)
    return ArchConfig(
        name=conf["name"], family="dense", num_layers=s["L"],
        d_model=s["d"], num_heads=s["H"], num_kv_heads=s["Kv"],
        d_ff=s["ff"], vocab_size=s["V"], head_dim=s["hd"],
        rope_theta=float(conf["rope_theta"]),
        norm_eps=float(conf["rms_norm_eps"]),
        tie_embeddings=s["tied"], param_dtype=conf["torch_dtype"],
        compute_dtype=conf["torch_dtype"])


def param_count(s: dict) -> int:
    """Every parameter held: layers, final norm, embedding and head."""
    return s["L"] * s["layer_params"] + s["d"] + s["embed_params"] + \
        (0 if s["tied"] else s["head_params"])


def kv_bytes_per_token(s: dict) -> int:
    return s["L"] * 2 * s["Kv"] * s["hd"] * s["itemsize"]


def attn_flops(s: dict, ctx: int) -> int:
    """Attention of one query token over ``ctx`` positions, all layers:
    q.k and p.v, 2 operations per multiply-add each."""
    return 4 * ctx * s["H"] * s["hd"] * s["L"]


def token_flops(s: dict, ctx: int) -> int:
    """One token through the whole model: the layers' and the head's
    matrix products (2 operations per weight) plus attention over
    ``ctx`` positions.  The embedding is a lookup and costs none."""
    return 2 * (s["L"] * s["layer_params"] + s["head_params"]) + \
        attn_flops(s, ctx)


def decode_attn_bytes(s: dict, ctx: int) -> int:
    """Least bytes one decode token's attention moves, all layers: its
    whole context's K and V once, its query in and its output out."""
    return kv_bytes_per_token(s) * ctx + \
        2 * s["L"] * s["H"] * s["hd"] * s["itemsize"]
