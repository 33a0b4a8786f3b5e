"""minicpm-2b [dense] — openbmb/MiniCPM-2B-sft-bf16 (config.json,
https://huggingface.co/openbmb/MiniCPM-2B-sft-bf16); arXiv:2404.06395.

40L d_model=2304 36H (kv=36 = MHA, head_dim 64) d_ff=5760 vocab=122753,
tied embeddings, RoPE θ 10,000, RMSNorm eps 1e-5.  Its muP multipliers,
as ``modeling_minicpm.py`` applies them, from three published keys:

* ``scale_emb`` 12: the token embeddings times 12 (``embed_scale``);
* ``scale_depth`` 1.4: each residual branch times 1.4 / sqrt(40), with
  40 the published ``num_hidden_layers`` (``residual_scale``, kept as a
  number, so a cut in depth keeps it);
* ``dim_model_base`` 256: the final normed hidden state divided by
  ``hidden_size`` / 256 = 9 before the tied head (``logit_scale``).

WSD (warmup-stable-decay) schedule lives in training/optimizer.py.
"""
import math

from .base import LayerGroup, ModelConfig

CONFIG = ModelConfig(
    arch_id="minicpm-2b",
    family="dense",
    d_model=2304,
    n_heads=36,
    n_kv_heads=36,
    d_ff=5760,
    vocab_size=122753,
    head_dim=64,
    rope_theta=1e4,
    tie_embeddings=True,
    groups=(LayerGroup(pattern=("attn",), count=40, ffn="dense"),),
    embed_scale=12.0,
    residual_scale=1.4 / math.sqrt(40),
    logit_scale=256 / 2304,
    notes="WSD schedule (training/optimizer.py); tied embeddings; muP "
          "multipliers scale_emb 12, scale_depth 1.4, dim_model_base 256.",
)
