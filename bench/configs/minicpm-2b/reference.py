"""Plain float32 reference of MiniCPM-2B (MiniCPMForCausalLM): pre-norm
decoder layers of RMSNorm, multi-head attention with rotary positions
(the rotate-half form) under a causal mask, and a SwiGLU feed-forward,
then RMSNorm and the output head, which is the embedding (tied).
Written from the published description (``modeling_minicpm.py``); it
imports nothing of the program.

MiniCPM's muP multipliers, from the published keys:

* the token embeddings times ``scale_emb`` (12);
* each residual branch, attention and feed-forward, times
  ``scale_depth / sqrt(num_hidden_layers)`` (1.4 / sqrt(40)) before it
  is added;
* the final normed hidden state divided by ``hidden_size /
  dim_model_base`` (2304 / 256 = 9) before the head.

Norm scales are stored as offsets from 1 (the layer multiplies by
``1 + scale``), which is the published RMSNorm weight minus one.

It runs layer by layer, each layer's bfloat16 weights upcast to float32
inside one compiled layer program, every product at the highest
precision.  The head's logits (122,753 a position) are computed only at
the positions whose next token was served, in blocks of ``HEAD_BLOCK``
positions.  ``control=True`` is the control: the same forward with every
projection's weights and inputs, the head's included, rounded to float8
(e4m3, one scale per output column and per token), one precision below
bfloat16.  The helpers this shares with phi3-mini's reference (the
float8 rounding, products, norm, rotary positions, the blocks of
sequences and the gaps) are that file's.
"""
from __future__ import annotations

from pathlib import Path

import numpy as np

from bench.common.harness import load_module

_base = load_module(Path(__file__).resolve().parent.parent
                    / "phi3-mini-3.8b" / "reference.py")
seed32 = _base.seed32

#: positions whose logits one call of the head computes
HEAD_BLOCK = 256
#: the std of the two projections that write into the residual stream,
#: attention's (wo) and the feed-forward's (w_down), over 1/sqrt(fan_in);
#: see ``make_weights``
WO_GAIN = 2.0
W_DOWN_GAIN = 8.0
#: the std of the tied embedding, see ``make_weights``
EMBED_STD = 0.01


def _dims(conf: dict) -> tuple:
    d, h = conf["hidden_size"], conf["num_attention_heads"]
    return (d, h, conf["num_key_value_heads"], d // h,
            conf["intermediate_size"], conf["num_hidden_layers"],
            conf["vocab_size"])


def scalings(conf: dict) -> tuple[float, float, float]:
    """The embedding's, each residual branch's and the head input's
    multipliers, from the published keys."""
    return (float(conf["scale_emb"]),
            conf["scale_depth"] / conf["num_hidden_layers"] ** 0.5,
            conf["dim_model_base"] / conf["hidden_size"])


def make_weights(conf: dict, seed: int) -> dict:
    """Every weight, drawn from the seed on the device in bfloat16, in one
    compiled call, uniform: the tied embedding at std ``EMBED_STD``, the
    other projections at the variance of 1/fan_in, wo ``WO_GAIN`` and
    w_down ``W_DOWN_GAIN`` times that std.

    With the head tied to the embedding, phi3's rule (embedding at
    variance 1) gives every position its own token back: the embedding,
    12 times over, outweighs what 40 branches at 0.22 add, so served
    tokens would not depend on the cache.  A small embedding under wide
    residual writes buries the token, but wide attention writes collapse
    every position onto one shared direction (random attention averages
    its context), and greedy decoding then repeats one token: wo at 4x
    served the token before at 97-98% of positions (TPU v5e, full
    width).  So the feed-forward writes widest and attention a little
    wider than 1/fan_in (PERF.md)."""
    import jax
    import jax.numpy as jnp

    d, h, kv, hd, f, n_layers, vocab = _dims(conf)
    shapes = {
        "embed": ((vocab, d), EMBED_STD),
        "final_norm": ((d,), None),
        "layers": {
            "attn_norm": ((n_layers, d), None),
            "wq": ((n_layers, d, h * hd), d ** -0.5),
            "wk": ((n_layers, d, kv * hd), d ** -0.5),
            "wv": ((n_layers, d, kv * hd), d ** -0.5),
            "wo": ((n_layers, h * hd, d), WO_GAIN * (h * hd) ** -0.5),
            "ffn_norm": ((n_layers, d), None),
            "w_gate": ((n_layers, d, f), d ** -0.5),
            "w_up": ((n_layers, d, f), d ** -0.5),
            "w_down": ((n_layers, f, d), W_DOWN_GAIN * f ** -0.5),
        },
    }
    leaves, tree = jax.tree.flatten(shapes, is_leaf=lambda x: isinstance(x, tuple))

    def make(key):
        out = []
        for i, (shape, std) in enumerate(leaves):
            k = jax.random.fold_in(key, i)
            # uniform with variance std**2; norm scales: offsets in ±0.1
            a = 0.1 if std is None else std * 3 ** 0.5
            out.append(jax.random.uniform(k, shape, jnp.bfloat16, -a, a))
        return jax.tree.unflatten(tree, out)

    return jax.jit(make)(jax.random.PRNGKey(seed32(seed)))


# ---------------------------------------------------------------------
# forward
# ---------------------------------------------------------------------


def _layer(conf: dict, control: bool):
    import jax
    import jax.numpy as jnp
    from jax import lax

    _mm, _rms, _rope = _base._mm, _base._rms, _base._rope
    d, h, kv, hd, f, n_layers, vocab = _dims(conf)
    eps, theta = conf["rms_norm_eps"], conf["rope_theta"]
    s = scalings(conf)[1]
    hi = lax.Precision.HIGHEST

    def layer(stack, i, x):
        p = {k: lax.dynamic_index_in_dim(v, i, keepdims=False).astype(jnp.float32)
             for k, v in stack.items()}
        b, n, _ = x.shape
        a = _rms(x, p["attn_norm"], eps)
        q = _rope(_mm(a, p["wq"], control).reshape(b, n, h, hd), theta)
        k = _rope(_mm(a, p["wk"], control).reshape(b, n, kv, hd), theta)
        v = _mm(a, p["wv"], control).reshape(b, n, kv, hd)
        k, v = jnp.repeat(k, h // kv, 2), jnp.repeat(v, h // kv, 2)
        sc = jnp.einsum("bqhd,bkhd->bhqk", q, k, precision=hi) / hd ** 0.5
        causal = jnp.tril(jnp.ones((n, n), bool))
        sc = jnp.where(causal[None, None], sc, -jnp.inf)
        o = jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(sc, -1), v,
                       precision=hi).reshape(b, n, h * hd)
        x = x + s * _mm(o, p["wo"], control)
        a = _rms(x, p["ffn_norm"], eps)
        g = jax.nn.silu(_mm(a, p["w_gate"], control)) * _mm(a, p["w_up"], control)
        return x + s * _mm(g, p["w_down"], control)

    return jax.jit(layer)


def _final(conf: dict):
    """The final norm and the head's input multiplier."""
    import jax
    import jax.numpy as jnp

    eps, scale = conf["rms_norm_eps"], scalings(conf)[2]

    def final(final_norm, x):
        return _base._rms(x, final_norm.astype(jnp.float32), eps) * scale

    return jax.jit(final)


def _head(control: bool):
    """Logits of head inputs ``x`` (..., d) over the tied embedding."""
    import jax
    import jax.numpy as jnp

    def head(embed, x):
        return _base._mm(x, embed.astype(jnp.float32).T, control)

    return jax.jit(head)


def _forward(conf: dict, control: bool):
    """The programs of one forward: ``hidden(w, tokens)``, the head's
    input at every position of ``tokens`` (batch, length): the embedding
    times ``scale_emb``, every layer, the final norm, the division by
    ``hidden_size / dim_model_base``; and ``head(embed, x)``."""
    import jax.numpy as jnp

    layer, final = _layer(conf, control), _final(conf)
    emb_scale = scalings(conf)[0]

    def hidden(w, tokens):
        x = jnp.take(w["embed"], jnp.asarray(tokens), axis=0).astype(jnp.float32)
        x = x * emb_scale
        for i in range(conf["num_hidden_layers"]):
            x = layer(w["layers"], i, x)
        return final(w["final_norm"], x)

    return hidden, _head(control)


def logits(conf: dict, w: dict, tokens, control: bool = False):
    """Logits at every position of ``tokens``; for sizes at which a
    (batch, length, vocab) float32 array fits."""
    hidden, head = _forward(conf, control)
    return head(w["embed"], hidden(w, tokens))


def served_gaps(conf: dict, w: dict, seqs, *, control: bool = False,
                margins: bool = False) -> list:
    """For each (prompt, served tokens): per served token, how far the
    reference's logit of that token lies below the reference's best.
    With ``control``, the served tokens are replaced by the control's own
    greedy choice at each of those positions (same prompts and served
    tokens as inputs), and their gaps are read the same way.  With
    ``margins``, each entry is (gaps, margins): the margins are how far
    the reference's second best lies below its best there."""
    import jax
    import jax.numpy as jnp

    gap = jax.jit(_base._gaps)
    fns = {c: _forward(conf, c) for c in {False, control}}
    out = [None] * len(seqs)
    for idx, block, tokens, targets in _base._batches(conf, seqs):
        rows, cols = np.nonzero(targets >= 0)
        n = len(rows)
        pad = -(-n // HEAD_BLOCK) * HEAD_BLOCK - n
        rows, cols = np.pad(rows, (0, pad)), np.pad(cols, (0, pad))
        want = np.pad(targets[targets >= 0], (0, pad), constant_values=-1)
        xs = {c: hidden(w, tokens)[rows, cols] for c, (hidden, _) in fns.items()}
        g, m = np.zeros(len(want), np.float32), np.zeros(len(want), np.float32)
        for lo in range(0, len(want), HEAD_BLOCK):
            sl = slice(lo, lo + HEAD_BLOCK)
            ref = fns[False][1](w["embed"], xs[False][sl])
            t = jnp.asarray(want[sl])
            if control:
                choice = jnp.argmax(fns[True][1](w["embed"], xs[True][sl]), -1)
                t = jnp.where(t >= 0, choice, -1)
            g[sl], m[sl] = (np.asarray(a) for a in gap(ref, t))
        at = 0
        for i, (prompt, served) in zip(idx, block):
            # the served positions of each sequence, in order: row-major
            # nonzero lists them sequence by sequence
            k = len(served)
            out[i] = (g[at:at + k], m[at:at + k]) if margins else g[at:at + k]
            at += k
    return out
