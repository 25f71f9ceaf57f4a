"""Operations and bytes that a dense GQA decoder's work needs, from shapes.

Copied from the idea of ``benchmarks/flops.py`` (``cell_model``), with one
change: a decode step's cache bytes and attention operations are counted
at each row's own length, not at the cache's capacity for every row.

Every function takes the configuration as its JSON file states it
(Hugging Face key names) and counts what the mathematics needs: the real
vocabulary, causal attention over the positions each row attends to, the
weights read once per step. What an implementation spends beyond that
(padded vocabulary, padded prefill rows, a cache copied per step) is its
own waste, and shows as a share below 100% of the roofline.
"""
from __future__ import annotations


def sizes(cfg: dict) -> dict:
    d = cfg["hidden_size"]
    h = cfg["num_attention_heads"]
    hd = cfg.get("head_dim") or d // h
    return {"d": d, "L": cfg["num_hidden_layers"], "H": h,
            "KVH": cfg["num_key_value_heads"], "hd": hd,
            "ff": cfg["intermediate_size"], "V": cfg["vocab_size"]}


def bytes_per_value(cfg: dict) -> int:
    return {"bfloat16": 2, "float16": 2, "float32": 4}[cfg["torch_dtype"]]


def matmul_params(cfg: dict) -> int:
    """Weights that each token multiplies: every layer's projections and
    SwiGLU MLP, and the output head; not the embedding, which is a gather."""
    s = sizes(cfg)
    d, hd = s["d"], s["hd"]
    per_layer = (d * (s["H"] + 2 * s["KVH"]) * hd + s["H"] * hd * d
                 + 3 * d * s["ff"])
    return s["L"] * per_layer + d * s["V"]


def weight_bytes(cfg: dict) -> int:
    """Bytes of the weights a forward pass reads: the matmul weights and
    the norms (the embedding rows gathered are negligible)."""
    s = sizes(cfg)
    norms = (2 * s["L"] + 1) * s["d"]
    return (matmul_params(cfg) + norms) * bytes_per_value(cfg)


def attention_flops(cfg: dict, attended: int) -> int:
    """Score and value products for one token that attends to ``attended``
    positions, over all layers."""
    s = sizes(cfg)
    return 4 * s["L"] * s["H"] * s["hd"] * attended


def kv_bytes(cfg: dict, positions: int) -> int:
    """Bytes of keys and values for ``positions`` positions, all layers."""
    s = sizes(cfg)
    return 2 * s["L"] * s["KVH"] * s["hd"] * positions * bytes_per_value(cfg)


def decode_step(cfg: dict, rows: int, attended: int) -> tuple[int, int]:
    """(operations, bytes) of one decode step of ``rows`` active rows that
    attend to ``attended`` positions in all (the sum over rows of each
    row's cache length plus its new token). Bytes: the weights once, each
    row's keys and values at its own length read, and the new ones
    written."""
    flops = 2 * matmul_params(cfg) * rows + attention_flops(cfg, attended)
    nbytes = weight_bytes(cfg) + kv_bytes(cfg, attended) + kv_bytes(cfg, rows)
    return flops, nbytes

