"""Plain reference of the Qwen2 / Qwen3 dense decoder, in float32.

Follows the published architecture (Qwen2: QKV bias; Qwen3: RMSNorm on
each query and key head): pre-norm blocks of grouped-query attention with
split-half rotary embeddings, a SwiGLU MLP, a final RMSNorm and the
output head.  One sequence at a time, one layer at a time, with no cache,
no kernel and no batching; attention in blocks of queries so that long
sequences fit.  Every matrix product runs at ``Precision.HIGHEST``.

It imports nothing of the program.  ``shapes`` states the parameter
layout, which ``bench/weights.py`` draws from the seed and holds to the
program's; RMSNorm weights there are stored as ``scale`` with weight
``1 + scale``.

``precision="fp8"`` is the control: the same computation with both
operands of every matrix product rounded to float8 e4m3 (one scale per
tensor), the precision below the configuration's bfloat16.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

HI = jax.lax.Precision.HIGHEST
EPS = 1e-6
Q_BLOCK = 512
LOGIT_BLOCK = 1024
SHAPE_BLOCK = 2048      # sequences pad to a multiple: few shapes, rarely a new one
F8_MAX = 448.0          # largest finite float8_e4m3fn


def shapes(c: dict) -> dict:
    """Leaf path -> (shape, kind, fan-in) of the parameter tree for config
    ``c``, in the program's layout: the embedding, an untied head when the
    configuration says so, and one scanned group whose leaves carry the
    layer index first.  Kinds as ``bench/weights.py`` draws them: "w" a
    weight (fan-in given), "norm", "bias"."""
    L, D, F, V = (c["num_hidden_layers"], c["hidden_size"],
                  c["intermediate_size"], c["vocab_size"])
    H, K = c["num_attention_heads"], c["num_key_value_heads"]
    dh = c.get("head_dim") or D // H
    leaves = {
        "embed": ((V, D), "w", D),
        "final_norm/scale": ((D,), "norm", 0),
        "groups/sub0/ln1/scale": ((L, D), "norm", 0),
        "groups/sub0/ln2/scale": ((L, D), "norm", 0),
        "groups/sub0/attn/wq": ((L, D, H, dh), "w", D),
        "groups/sub0/attn/wk": ((L, D, K, dh), "w", D),
        "groups/sub0/attn/wv": ((L, D, K, dh), "w", D),
        "groups/sub0/attn/wo": ((L, H, dh, D), "w", H * dh),
        "groups/sub0/mlp/wi": ((L, D, F), "w", D),
        "groups/sub0/mlp/wg": ((L, D, F), "w", D),
        "groups/sub0/mlp/wo": ((L, F, D), "w", F),
    }
    if not c["tie_word_embeddings"]:
        leaves["lm_head"] = ((D, V), "w", D)
    if c.get("qkv_bias"):
        leaves["groups/sub0/attn/bq"] = ((L, H, dh), "bias", 0)
        leaves["groups/sub0/attn/bk"] = ((L, K, dh), "bias", 0)
        leaves["groups/sub0/attn/bv"] = ((L, K, dh), "bias", 0)
    if c.get("qk_norm"):
        leaves["groups/sub0/attn/q_norm"] = ((L, dh), "norm", 0)
        leaves["groups/sub0/attn/k_norm"] = ((L, dh), "norm", 0)
    return leaves


def _fp8(x):
    """Round to float8 e4m3 with one scale for the tensor, back to f32."""
    amax = jnp.max(jnp.abs(x))
    s = jnp.where(amax > 0, amax / F8_MAX, 1.0)
    return (x / s).astype(jnp.float8_e4m3fn).astype(jnp.float32) * s


def _mm(eq, a, b, lowp: bool):
    if lowp:
        a, b = _fp8(a), _fp8(b)
    return jnp.einsum(eq, a, b, precision=HI)


def _rms(x, scale):
    x = x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + EPS)
    return x * (1.0 + scale)


def _rope(x, pos, theta):
    """x [T, n, dh]; split-half rotation by absolute position."""
    dh = x.shape[-1]
    inv = 1.0 / (theta ** (np.arange(0, dh, 2, dtype=np.float64) / dh))
    ang = pos[:, None].astype(jnp.float32) * jnp.asarray(inv, jnp.float32)
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., : dh // 2], x[..., dh // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


@functools.partial(jax.jit, static_argnames=("theta", "lowp"))
def _layer(x, w, n_valid, *, theta: float, lowp: bool):
    """One decoder block over x [T, D] (positions 0..T-1), of which the
    first ``n_valid`` (a traced scalar, so one program serves every length
    in the padded shape) are the sequence."""
    f32 = lambda a: a.astype(jnp.float32)
    T = x.shape[0]
    pos = jnp.arange(T)
    h = _rms(x, f32(w["ln1"]))
    q = _mm("td,dhx->thx", h, f32(w["wq"]), lowp)
    k = _mm("td,dkx->tkx", h, f32(w["wk"]), lowp)
    v = _mm("td,dkx->tkx", h, f32(w["wv"]), lowp)
    if "bq" in w:
        q, k, v = q + f32(w["bq"]), k + f32(w["bk"]), v + f32(w["bv"])
    if "q_norm" in w:
        q, k = _rms(q, f32(w["q_norm"])), _rms(k, f32(w["k_norm"]))
    q, k = _rope(q, pos, theta), _rope(k, pos, theta)
    H, K, dh = q.shape[1], k.shape[1], q.shape[2]
    G = H // K
    q = q.reshape(T, K, G, dh) * dh ** -0.5

    def block(i):
        qb = jax.lax.dynamic_slice_in_dim(q, i * Q_BLOCK, Q_BLOCK, 0)
        s = _mm("qkgd,tkd->kgqt", qb, k, lowp)
        qpos = i * Q_BLOCK + jnp.arange(Q_BLOCK)
        ok = (pos[None, :] <= qpos[:, None]) & (pos[None, :] < n_valid)
        s = jnp.where(ok[None, None], s, -jnp.inf)
        p = jax.nn.softmax(s, axis=-1)
        return _mm("kgqt,tkd->qkgd", p, v, lowp)

    o = jax.lax.map(block, jnp.arange(T // Q_BLOCK)).reshape(T, H, dh)
    x = x + _mm("thx,hxd->td", o, f32(w["wo"]), lowp)
    h = _rms(x, f32(w["ln2"]))
    g = _mm("td,df->tf", h, f32(w["wg"]), lowp)
    u = _mm("td,df->tf", h, f32(w["wi"]), lowp)
    return x + _mm("tf,fd->td", jax.nn.silu(g) * u, f32(w["wo2"]), lowp)


@functools.partial(jax.jit, static_argnames=("lowp",))
def _head(x, final, head, tokens, *, lowp: bool):
    """log p(tokens[i]) and the best logit's lead over it, for rows x."""
    h = _rms(x, final.astype(jnp.float32))
    logits = _mm("td,dv->tv", h, head.astype(jnp.float32), lowp)
    lse = jax.nn.logsumexp(logits, axis=-1)
    tok = jnp.take_along_axis(logits, tokens[:, None], axis=-1)[:, 0]
    return tok - lse, jnp.max(logits, axis=-1) - tok


def score(weights: dict, c: dict, tokens, *, precision: str = "f32"):
    """For a sequence ``tokens`` [T], the log-probability of each token
    given those before it (entry t scores tokens[t + 1]) and the lead of
    the best logit over it.  Returns two float64 arrays of length T - 1."""
    lowp = {"f32": False, "fp8": True}[precision]
    tokens = np.asarray(tokens, np.int32)
    T = len(tokens)
    Tp = -(-T // SHAPE_BLOCK) * SHAPE_BLOCK
    ids = np.zeros((Tp,), np.int32)
    ids[:T] = tokens
    x = jnp.take(weights["embed"], jnp.asarray(ids), axis=0).astype(jnp.float32)
    g = weights["groups"]["sub0"]
    for i in range(c["num_hidden_layers"]):
        w = {"ln1": g["ln1"]["scale"][i], "ln2": g["ln2"]["scale"][i],
             "wo2": g["mlp"]["wo"][i], "wi": g["mlp"]["wi"][i],
             "wg": g["mlp"]["wg"][i]}
        w.update({k: v[i] for k, v in g["attn"].items()})
        x = _layer(x, w, jnp.int32(T), theta=float(c["rope_theta"]), lowp=lowp)
    head = (weights["lm_head"] if "lm_head" in weights
            else weights["embed"].T)
    nxt = np.zeros((Tp,), np.int32)
    nxt[:T - 1] = tokens[1:]
    lps, leads = [], []
    for s in range(0, Tp, LOGIT_BLOCK):
        lp, lead = _head(x[s:s + LOGIT_BLOCK], weights["final_norm"]["scale"],
                         head, jnp.asarray(nxt[s:s + LOGIT_BLOCK]), lowp=lowp)
        lps.append(np.asarray(lp, np.float64))
        leads.append(np.asarray(lead, np.float64))
    return np.concatenate(lps)[:T - 1], np.concatenate(leads)[:T - 1]
