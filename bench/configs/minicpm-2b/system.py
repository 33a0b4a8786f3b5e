"""How the program is built from this configuration: its model config,
with MiniCPM's three muP multipliers from the published keys, and the
weights (made by the benchmark, see ``reference.make_weights``) laid out
as the program takes them.  The head is the embedding (tied), so there
is no ``lm_head``.  The same arrays are re-nested, not copied."""
from __future__ import annotations

import math


def program_config(conf: dict):
    from repro.configs.base import LayerGroup, ModelConfig

    s = conf["serving"]
    d, h = conf["hidden_size"], conf["num_attention_heads"]
    return ModelConfig(
        arch_id=conf["name"], family="dense",
        d_model=d, n_heads=h, n_kv_heads=conf["num_key_value_heads"],
        d_ff=conf["intermediate_size"], vocab_size=conf["vocab_size"],
        head_dim=d // h, rope_theta=conf["rope_theta"],
        norm_eps=conf["rms_norm_eps"],
        tie_embeddings=conf["tie_word_embeddings"],
        groups=(LayerGroup(pattern=("attn",), count=conf["num_hidden_layers"],
                           ffn="dense"),),
        embed_scale=float(conf["scale_emb"]),
        residual_scale=conf["scale_depth"] / math.sqrt(conf["num_hidden_layers"]),
        logit_scale=conf["dim_model_base"] / d,
        param_dtype=s["param_dtype"], compute_dtype=s["compute_dtype"])


def program_params(conf: dict, w: dict) -> dict:
    L = w["layers"]
    return {
        "embed": w["embed"], "final_norm": w["final_norm"],
        "groups": [{"sub0": {
            "norm1": L["attn_norm"], "norm2": L["ffn_norm"],
            "mixer": {k: L[k] for k in ("wq", "wk", "wv", "wo")},
            "ffn": {k: L[k] for k in ("w_gate", "w_up", "w_down")},
        }}],
    }
