"""Plain float32 reference of phi3-mini (Phi3ForCausalLM): pre-norm
decoder layers of RMSNorm, multi-head attention with rotary positions
(the rotate-half form) under a causal mask, and a SwiGLU feed-forward,
then RMSNorm and the output head.  Written from the published
description; it imports nothing of the program.

Norm scales are stored as offsets from 1 (the layer multiplies by
``1 + scale``), which is the published RMSNorm weight minus one.  The
source's sliding window (2047) is never reached by a served sequence
here (at most ``max_seq`` = 1280 tokens), so attention is fully causal.

It runs layer by layer, each layer's bfloat16 weights upcast to float32
inside one compiled layer program, every product at the highest
precision.  ``control=True`` is the control: the same forward with every
projection's weights and inputs rounded to float8 (e4m3, one scale per
output column and per token), one precision below bfloat16.
"""
from __future__ import annotations

import numpy as np

_FP8_MAX = 448.0


def _dims(conf: dict) -> tuple:
    return (conf["hidden_size"], conf["num_attention_heads"],
            conf["num_key_value_heads"], conf["head_dim"],
            conf["intermediate_size"], conf["num_hidden_layers"],
            conf["vocab_size"])


def seed32(seed: int) -> int:
    return int(np.random.SeedSequence(seed).generate_state(1)[0] & 0x7FFFFFFF)


def make_weights(conf: dict, seed: int) -> dict:
    """Every weight, drawn from the seed on the device in bfloat16, in one
    compiled call: uniform, with the variance of 1/fan_in, and the two
    projections that write into the residual stream scaled down by
    sqrt(2 x layers) (GPT-2's rule), so that 32 random layers do not
    collapse every position onto one token."""
    import jax
    import jax.numpy as jnp

    d, h, kv, hd, f, n_layers, vocab = _dims(conf)
    shapes = {
        "embed": ((vocab, d), 1.0),
        "lm_head": ((d, vocab), d ** -0.5),
        "final_norm": ((d,), None),
        "layers": {
            "attn_norm": ((n_layers, d), None),
            "wq": ((n_layers, d, h * hd), d ** -0.5),
            "wk": ((n_layers, d, kv * hd), d ** -0.5),
            "wv": ((n_layers, d, kv * hd), d ** -0.5),
            "wo": ((n_layers, h * hd, d), (2 * n_layers * h * hd) ** -0.5),
            "ffn_norm": ((n_layers, d), None),
            "w_gate": ((n_layers, d, f), d ** -0.5),
            "w_up": ((n_layers, d, f), d ** -0.5),
            "w_down": ((n_layers, f, d), (2 * n_layers * f) ** -0.5),
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


def _q8(x, axis):
    """Round to float8 e4m3 with one scale per slice along ``axis``."""
    import jax.numpy as jnp

    s = jnp.max(jnp.abs(x), axis=axis, keepdims=True) / _FP8_MAX
    s = jnp.where(s > 0, s, 1.0)
    return (x / s).astype(jnp.float8_e4m3fn).astype(jnp.float32) * s


def _mm(x, w, control):
    import jax.numpy as jnp
    from jax import lax

    if control:
        x, w = _q8(x, -1), _q8(w, 0)
    return jnp.matmul(x, w, precision=lax.Precision.HIGHEST)


def _rms(x, scale, eps):
    import jax.numpy as jnp

    return x * (1.0 + scale) / jnp.sqrt(jnp.mean(x * x, -1, keepdims=True) + eps)


def _rope(x, theta):
    import jax.numpy as jnp

    s, hd = x.shape[1], x.shape[-1]
    inv = 1.0 / theta ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd)
    ang = jnp.arange(s, dtype=jnp.float32)[:, None] * inv[None, :]
    cos = jnp.concatenate([jnp.cos(ang)] * 2, -1)[None, :, None, :]
    sin = jnp.concatenate([jnp.sin(ang)] * 2, -1)[None, :, None, :]
    half = hd // 2
    rot = jnp.concatenate([-x[..., half:], x[..., :half]], -1)
    return x * cos + rot * sin


def _layer(conf: dict, control: bool):
    import jax
    import jax.numpy as jnp
    from jax import lax

    d, h, kv, hd, f, n_layers, vocab = _dims(conf)
    eps, theta = conf["rms_norm_eps"], conf["rope_theta"]
    hi = lax.Precision.HIGHEST

    def layer(stack, i, x):
        p = {k: lax.dynamic_index_in_dim(v, i, keepdims=False).astype(jnp.float32)
             for k, v in stack.items()}
        b, s, _ = x.shape
        a = _rms(x, p["attn_norm"], eps)
        q = _rope(_mm(a, p["wq"], control).reshape(b, s, h, hd), theta)
        k = _rope(_mm(a, p["wk"], control).reshape(b, s, kv, hd), theta)
        v = _mm(a, p["wv"], control).reshape(b, s, kv, hd)
        k, v = jnp.repeat(k, h // kv, 2), jnp.repeat(v, h // kv, 2)
        sc = jnp.einsum("bqhd,bkhd->bhqk", q, k, precision=hi) / hd ** 0.5
        causal = jnp.tril(jnp.ones((s, s), bool))
        sc = jnp.where(causal[None, None], sc, -jnp.inf)
        o = jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(sc, -1), v,
                       precision=hi).reshape(b, s, h * hd)
        x = x + _mm(o, p["wo"], control)
        a = _rms(x, p["ffn_norm"], eps)
        g = jax.nn.silu(_mm(a, p["w_gate"], control)) * _mm(a, p["w_up"], control)
        return x + _mm(g, p["w_down"], control)

    return jax.jit(layer)


def _head(conf: dict, control: bool):
    import jax
    import jax.numpy as jnp

    eps = conf["rms_norm_eps"]

    def head(final_norm, lm_head, x):
        x = _rms(x, final_norm.astype(jnp.float32), eps)
        return _mm(x, lm_head.astype(jnp.float32), control)

    return jax.jit(head)


def _gaps(logits, targets):
    """Per position: how far the target's logit lies below the best, and
    how far the second best lies below the best."""
    import jax
    import jax.numpy as jnp

    t = jnp.maximum(targets, 0)
    got = jnp.take_along_axis(logits, t[..., None], -1)[..., 0]
    top2 = jax.lax.top_k(logits, 2)[0]
    return (jnp.where(targets >= 0, top2[..., 0] - got, 0.0),
            top2[..., 0] - top2[..., 1])


def _batches(conf: dict, seqs):
    """Blocks of ``reference_batch`` sequences of similar length (in order
    of length), each padded to a multiple of 256 tokens, at most
    ``max_seq``: tokens (prompt and every served token but the last) and,
    at each position whose next token was served, that token.  Yields
    the indices of the block's sequences in ``seqs`` too."""
    s_max = conf["serving"]["max_seq"]
    nb = conf["serving"]["reference_batch"]
    order = sorted(range(len(seqs)), key=lambda i: len(seqs[i][0]) + len(seqs[i][1]))
    for lo in range(0, len(order), nb):
        idx = order[lo: lo + nb]
        block = [seqs[i] for i in idx]
        longest = max(len(p) + len(t) - 1 for p, t in block)
        width = min(s_max, -(-longest // 256) * 256)
        tokens = np.zeros((nb, width), np.int32)
        targets = np.full((nb, width), -1, np.int32)
        for r, (prompt, served) in enumerate(block):
            seq = np.concatenate([prompt, served[:-1]])
            tokens[r, : len(seq)] = seq
            p = len(prompt)
            targets[r, p - 1: p - 1 + len(served)] = served
        yield idx, block, tokens, targets


def _logits(conf, w, tokens, fns):
    import jax.numpy as jnp

    layer, head = fns
    x = jnp.take(w["embed"], jnp.asarray(tokens), axis=0).astype(jnp.float32)
    for i in range(conf["num_hidden_layers"]):
        x = layer(w["layers"], i, x)
    return head(w["final_norm"], w["lm_head"], x)


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

    gap = jax.jit(_gaps)
    fns = {c: (_layer(conf, c), _head(conf, c)) for c in {False, control}}
    out = [None] * len(seqs)
    for idx, block, tokens, targets in _batches(conf, seqs):
        ref = _logits(conf, w, tokens, fns[False])
        if control:
            choice = jnp.argmax(_logits(conf, w, tokens, fns[True]), -1)
            targets = np.where(targets >= 0, np.asarray(choice), -1)
        g, m = (np.asarray(a) for a in gap(ref, jnp.asarray(targets)))
        for r, (i, (prompt, served)) in enumerate(zip(idx, block)):
            sl = slice(len(prompt) - 1, len(prompt) - 1 + len(served))
            out[i] = (g[r, sl], m[r, sl]) if margins else g[r, sl]
    return out
