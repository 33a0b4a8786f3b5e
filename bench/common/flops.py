"""Operations and bytes of the work a cell does, from shapes alone.

Everything here is a lower bound on what any implementation must do:
matrix products count 2 operations per multiply-add, elementwise work is
left out, and bytes are what has to cross HBM at least once.
"""
from __future__ import annotations

# ---------------------------------------------------------------------
# dense decoder (phi3-mini): keys as in the model's published config.json
# ---------------------------------------------------------------------


def decoder_dims(conf: dict) -> dict:
    d = conf["hidden_size"]
    h = conf["num_attention_heads"]
    kv = conf["num_key_value_heads"]
    hd = conf.get("head_dim") or d // h
    return {"d": d, "h": h, "kv": kv, "hd": hd, "f": conf["intermediate_size"],
            "layers": conf["num_hidden_layers"], "vocab": conf["vocab_size"]}


def decoder_matmul_params(conf: dict) -> int:
    """Weights that every token multiplies: the projections of each layer
    and the output head (the embedding is a lookup, not a product)."""
    m = decoder_dims(conf)
    attn = m["d"] * m["h"] * m["hd"] * 2 + m["d"] * m["kv"] * m["hd"] * 2
    ffn = 3 * m["d"] * m["f"]
    return m["layers"] * (attn + ffn) + m["d"] * m["vocab"]


def decoder_weight_bytes(conf: dict, bytes_per_param: int = 2) -> int:
    """Bytes a decode step must read at least: every matrix and norm
    scale once, and one row of the embedding."""
    m = decoder_dims(conf)
    norms = (2 * m["layers"] + 1) * m["d"]
    return (decoder_matmul_params(conf) + norms + m["d"]) * bytes_per_param


def kv_bytes_per_token(conf: dict, bytes_per_value: int = 2) -> int:
    m = decoder_dims(conf)
    return 2 * m["kv"] * m["hd"] * bytes_per_value * m["layers"]


def attention_flops(conf: dict, keys: int) -> int:
    """Scores and weighted values of one query over ``keys`` keys, all
    layers."""
    m = decoder_dims(conf)
    return 4 * m["layers"] * m["h"] * m["hd"] * keys


def token_flops(conf: dict, position: int) -> int:
    """One token at 0-based ``position``: every projection plus attention
    over itself and the positions before it."""
    return 2 * decoder_matmul_params(conf) + attention_flops(conf, position + 1)


def prefill_flops(conf: dict, prompt: int) -> int:
    """A causal prefill of ``prompt`` tokens (the sum of token_flops)."""
    keys = prompt * (prompt + 1) // 2
    return 2 * decoder_matmul_params(conf) * prompt + attention_flops(conf, keys)


def decode_bytes(conf: dict, position: int) -> int:
    """KV bytes one decode token needs: read the ``position`` cached
    tokens and write its own (weights are counted once per call)."""
    return kv_bytes_per_token(conf) * (position + 1)
