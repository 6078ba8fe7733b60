"""Plain reference for llama-family decoders (Llama, Yi, Mistral): the
published forward pass in float32 ``jax.numpy`` at ``highest`` matmul
precision, one sequence at a time, with no cache, batching or kernels.

    x = embed[tokens]
    per layer:  h = rmsnorm(x) ; q, k, v = h Wq, h Wk, h Wv
                q, k = rope(q), rope(k)        (rotate-half, theta^(-2i/hd))
                x += softmax(q k^T / sqrt(hd), causal) v  Wo   (GQA: each
                     KV head serves H / Kv query heads)
                h = rmsnorm(x) ; x += (silu(h Wgate) * (h Wup)) Wdown
    logits = rmsnorm(x) Whead^T

Weights come as the tree of arrays the benchmark drew (stacked over
layers); each is read in its stored type and widened to float32.

``lower=True`` also runs the same pass with every matrix product's
operands rounded to float8 (e4m3, one scale per row of activations and
per output column of weights), the step below bfloat16: the control that
the comparison has to reject.
"""
from __future__ import annotations

import functools
import math

import numpy as np

#: sequence lengths are padded up to one of these, so few programs compile
BUCKETS = (256, 384, 512, 768, 1024, 1536, 2048, 3072, 4096)
#: vocabulary rows per block of the head, to bound float32 temporaries
HEAD_BLOCK = 16384


def _bucket(n: int) -> int:
    for b in BUCKETS:
        if n <= b:
            return b
    return -(-n // 1024) * 1024


def _fp8(x, axis):
    """Round ``x`` to float8 e4m3 under one absmax scale per slice along
    ``axis`` (the contraction axis), and widen it back."""
    import jax.numpy as jnp
    amax = jnp.max(jnp.abs(x), axis=axis, keepdims=True)
    scale = jnp.where(amax > 0, amax / 448.0, 1.0)
    return (x / scale).astype(jnp.float8_e4m3fn).astype(jnp.float32) * scale


def _mm(a, w, lower):
    """a (T, k) @ w (k, n) in float32, or from float8 operands."""
    import jax
    import jax.numpy as jnp
    if lower:
        a, w = _fp8(a, -1), _fp8(w, 0)
    return jnp.dot(a, w, precision=jax.lax.Precision.HIGHEST,
                   preferred_element_type=jnp.float32)


def _rmsnorm(x, scale, eps):
    import jax.numpy as jnp
    return x / jnp.sqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * \
        scale.astype(jnp.float32)


def _rope(x, theta):
    """x: (T, heads, hd), position t at row t."""
    import jax.numpy as jnp
    T, _, hd = x.shape
    half = hd // 2
    inv = theta ** (-jnp.arange(half, dtype=jnp.float32) * 2.0 / hd)
    ang = jnp.arange(T, dtype=jnp.float32)[:, None] * inv[None, :]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


@functools.lru_cache(maxsize=None)
def _layer_fn(eps, theta, lower):
    import jax
    import jax.numpy as jnp

    @jax.jit
    def layer(x, i, w):
        f32 = jnp.float32
        T, d = x.shape
        wq, wk, wv, wo = (w["attn"][n][i].astype(f32)
                          for n in ("wq", "wk", "wv", "wo"))
        H, hd, Kv = wq.shape[1], wq.shape[2], wk.shape[1]
        h = _rmsnorm(x, w["ln1"]["scale"][i], eps)
        q = _rope(_mm(h, wq.reshape(d, H * hd), lower).reshape(T, H, hd),
                  theta)
        k = _rope(_mm(h, wk.reshape(d, Kv * hd), lower).reshape(T, Kv, hd),
                  theta)
        v = _mm(h, wv.reshape(d, Kv * hd), lower).reshape(T, Kv, hd)
        k = jnp.repeat(k, H // Kv, axis=1)
        v = jnp.repeat(v, H // Kv, axis=1)
        s = jnp.einsum("thd,shd->hts", q, k,
                       precision=jax.lax.Precision.HIGHEST) / math.sqrt(hd)
        causal = jnp.arange(T)[None, :, None] >= jnp.arange(T)[None, None, :]
        p = jax.nn.softmax(jnp.where(causal, s, -jnp.inf), axis=-1)
        o = jnp.einsum("hts,shd->thd", p, v,
                       precision=jax.lax.Precision.HIGHEST)
        x = x + _mm(o.reshape(T, H * hd), wo.reshape(H * hd, d), lower)
        h = _rmsnorm(x, w["ln2"]["scale"][i], eps)
        m = w["mlp"]
        g = _mm(h, m["w_gate"][i].astype(f32), lower)
        u = _mm(h, m["w_up"][i].astype(f32), lower)
        return x + _mm(jax.nn.silu(g) * u, m["w_down"][i].astype(f32),
                       lower)

    return layer


@functools.lru_cache(maxsize=None)
def _head_fn(eps, lower):
    """Per row: the largest float32 logit, the logit of a given token, and
    with ``lower`` the logit of the token the float8 pass puts first."""
    import jax
    import jax.numpy as jnp

    @jax.jit
    def head(x, x_low, targets, norm, w):
        f32 = jnp.float32
        h = _rmsnorm(x, norm, eps)
        h_low = _rmsnorm(x_low, norm, eps)
        T, V = x.shape[0], w.shape[0]
        best = jnp.full((T,), -jnp.inf, f32)
        served = jnp.zeros((T,), f32)
        low_best = jnp.full((T,), -jnp.inf, f32)
        low_ref = jnp.zeros((T,), f32)
        for v0 in range(0, V, HEAD_BLOCK):
            blk = w[v0:v0 + HEAD_BLOCK].astype(f32)
            lg = jnp.dot(h, blk.T, precision=jax.lax.Precision.HIGHEST)
            best = jnp.maximum(best, lg.max(-1))
            ids = jnp.arange(v0, v0 + blk.shape[0])
            served = served + jnp.sum(
                jnp.where(ids[None, :] == targets[:, None], lg, 0.0), -1)
            if not lower:
                continue
            lw = _mm(h_low, blk.T, True)
            j = jnp.argmax(lw, -1)
            top = lw.max(-1)
            take = top > low_best
            low_ref = jnp.where(take, jnp.take_along_axis(
                lg, j[:, None], 1)[:, 0], low_ref)
            low_best = jnp.where(take, top, low_best)
        return best, served, low_ref

    return head


def gaps(weights, conf: dict, prompt, served, *, lower: bool = False):
    """Run the reference over ``prompt`` followed by the ``served``
    tokens.  Returns, per served token, how far its float32 logit lies
    below the largest one at its position, and with ``lower`` the same
    gap for the token the float8 pass puts first there (else None)."""
    import jax.numpy as jnp

    seq = list(prompt) + list(served[:-1])
    n, P = len(seq), len(prompt)
    T = _bucket(n)
    tokens = np.zeros(T, np.int32)
    tokens[:n] = seq
    targets = np.zeros(T, np.int32)
    targets[P - 1:n] = served
    eps, theta = float(conf["rms_norm_eps"]), float(conf["rope_theta"])
    x = weights["embed"]["table"][jnp.asarray(tokens)].astype(jnp.float32)
    x_low = x
    layers = weights["layers"]
    f32_layer = _layer_fn(eps, theta, False)
    low_layer = _layer_fn(eps, theta, True)
    for i in range(int(conf["num_hidden_layers"])):
        x = f32_layer(x, i, layers)
        if lower:
            x_low = low_layer(x_low, i, layers)
    head = weights["embed"]["table"] if conf.get("tie_word_embeddings") \
        else weights["embed"]["head"]
    best, got, low_ref = (np.asarray(a) for a in _head_fn(eps, lower)(
        x, x_low if lower else x, jnp.asarray(targets),
        weights["final_norm"]["scale"], head))
    rows = slice(P - 1, n)
    return best[rows] - got[rows], \
        (best[rows] - low_ref[rows]) if lower else None
