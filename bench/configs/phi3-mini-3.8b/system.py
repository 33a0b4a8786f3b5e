"""How the program is built from this configuration: its model config and
the weights (made by the benchmark, see ``reference.make_weights``) laid
out as the program takes them.  The same arrays are re-nested, not
copied."""
from __future__ import annotations


def program_config(conf: dict):
    from repro.configs.base import LayerGroup, ModelConfig

    s = conf["serving"]
    return ModelConfig(
        arch_id=conf["name"], family="dense",
        d_model=conf["hidden_size"], n_heads=conf["num_attention_heads"],
        n_kv_heads=conf["num_key_value_heads"],
        d_ff=conf["intermediate_size"], vocab_size=conf["vocab_size"],
        head_dim=conf["head_dim"], rope_theta=conf["rope_theta"],
        norm_eps=conf["rms_norm_eps"],
        tie_embeddings=conf["tie_word_embeddings"],
        groups=(LayerGroup(pattern=("attn",), count=conf["num_hidden_layers"],
                           ffn="dense"),),
        param_dtype=s["param_dtype"], compute_dtype=s["compute_dtype"])


def program_params(conf: dict, w: dict) -> dict:
    L = w["layers"]
    return {
        "embed": w["embed"], "final_norm": w["final_norm"],
        "lm_head": w["lm_head"],
        "groups": [{"sub0": {
            "norm1": L["attn_norm"], "norm2": L["ffn_norm"],
            "mixer": {k: L[k] for k in ("wq", "wk", "wv", "wo")},
            "ffn": {k: L[k] for k in ("w_gate", "w_up", "w_down")},
        }}],
    }
