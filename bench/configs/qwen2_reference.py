"""Plain Qwen2 decoder forward pass, float32, for the benchmark's check.

Follows the published architecture (Qwen2 technical report,
arXiv:2407.10671, and the Hugging Face ``Qwen2ForCausalLM``): token
embedding; per layer pre-RMSNorm, grouped-query attention with q/k/v
biases and rotary position embedding (rotate-half, base ``rope_theta``),
causal softmax, output projection, residual; pre-RMSNorm SwiGLU MLP,
residual; final RMSNorm; logits against the tied embedding or the separate
``lm_head``. It imports nothing of the program under test and runs one
sequence, one layer at a time, with attention in blocks of queries, so
that it fits next to the served weights.

Every matrix product goes through ``matmul``: ``f32`` at
``Precision.HIGHEST``, or ``fp8`` (both operands rounded to float8 e4m3's
four significant bits, then multiplied in float32) for the control that
must fail the check.
Weights are read by the names of the served parameter tree.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

HIGHEST = jax.lax.Precision.HIGHEST
Q_BLOCK = 512          # queries per attention block
V_BLOCK = 256          # positions per block of the logits
COL_BLOCK = 16384      # vocabulary entries per block of the head


def _fp8(x):
    """Round to float8 e4m3's 4 significant bits (1 implicit, 3 stored),
    in float32; the exponent is left unbounded."""
    m, e = jnp.frexp(x)
    return jnp.ldexp(jnp.round(m * 16.0) / 16.0, e)


def matmul(x, w, quant: str):
    """x [..., k] @ w [k, n] in float32."""
    x = x.astype(jnp.float32)
    w = w.astype(jnp.float32)
    if quant == "fp8":
        x, w = _fp8(x), _fp8(w)
    elif quant != "f32":
        raise ValueError(f"unknown precision {quant!r}")
    return jnp.matmul(x, w, precision=HIGHEST)


def rms_norm(x, scale, eps):
    x = x.astype(jnp.float32)
    var = jnp.mean(x * x, axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * scale.astype(jnp.float32)


def rope(x, positions, theta):
    """x [T, heads, hd]; rotate-half rotary embedding."""
    hd = x.shape[-1]
    inv = 1.0 / (theta ** (np.arange(0, hd, 2, dtype=np.float64) / hd))
    ang = positions.astype(jnp.float32)[:, None] * jnp.asarray(
        inv, jnp.float32)[None]
    cos, sin = jnp.cos(ang)[:, None], jnp.sin(ang)[:, None]
    x1, x2 = x[..., :hd // 2], x[..., hd // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


@functools.partial(jax.jit, static_argnames=("m", "quant"))
def layer(x, p, m: dict, quant: str):
    """One decoder layer over the whole sequence x [T, d]."""
    m = dict(m)
    t = x.shape[0]
    heads, kv, hd = m["num_heads"], m["num_kv_heads"], m["head_dim"]
    pos = jnp.arange(t)
    h = rms_norm(x, p["norm1"]["scale"], m["norm_eps"])
    a = p["attn"]
    q = matmul(h, a["wq"], quant) + a["bq"].astype(jnp.float32)
    k = matmul(h, a["wk"], quant) + a["bk"].astype(jnp.float32)
    v = matmul(h, a["wv"], quant) + a["bv"].astype(jnp.float32)
    q = rope(q.reshape(t, heads, hd), pos, m["rope_theta"])
    k = rope(k.reshape(t, kv, hd), pos, m["rope_theta"])
    v = v.reshape(t, kv, hd)
    group = heads // kv
    k = jnp.repeat(k, group, axis=1)
    v = jnp.repeat(v, group, axis=1)
    blocks = []
    for s in range(0, t, Q_BLOCK):
        qb = q[s:s + Q_BLOCK]
        sc = jnp.einsum("qhd,khd->hqk", qb, k, precision=HIGHEST)
        sc = sc / np.sqrt(hd)
        causal = (s + jnp.arange(qb.shape[0]))[:, None] >= pos[None, :]
        sc = jnp.where(causal[None], sc, -jnp.inf)
        w = jax.nn.softmax(sc, axis=-1)
        blocks.append(jnp.einsum("hqk,khd->qhd", w, v, precision=HIGHEST))
    att = jnp.concatenate(blocks, 0).reshape(t, heads * hd)
    x = x + matmul(att, a["wo"], quant)
    h = rms_norm(x, p["norm2"]["scale"], m["norm_eps"])
    f = p["mlp"]
    g = matmul(h, f["w_gate"], quant)
    u = matmul(h, f["w_up"], quant)
    return x + matmul(jax.nn.silu(g) * u, f["w_down"], quant)


def _static(model: dict) -> tuple:
    keys = ("num_heads", "num_kv_heads", "head_dim", "norm_eps",
            "rope_theta")
    return tuple((k, model[k]) for k in keys)


def hidden(model: dict, params, tokens, quant: str = "f32"):
    """Final-normed hidden states [T, d] of ``tokens`` (float32)."""
    x = jnp.take(params["embed"]["embedding"], jnp.asarray(tokens), axis=0)
    x = x.astype(jnp.float32)
    m = _static(model)
    for i in range(model["num_layers"]):
        p = jax.tree.map(lambda a: a[i], params["layers"])
        x = layer(x, p, m, quant)
    return rms_norm(x, params["final_norm"]["scale"], model["norm_eps"])


@functools.partial(jax.jit, static_argnames=("quant",))
def _logits(h, head, quant: str):
    return matmul(h, head, quant)


def head_matrix(model: dict, params):
    """The [d, V] matrix that turns a hidden state into logits."""
    if model["tie_embeddings"]:
        return params["embed"]["embedding"].T
    return params["embed"]["lm_head"]


def logit_blocks(model: dict, params, h, quant: str = "f32"):
    """Yield (start, logits [block, V] float32) over the rows of h; the
    head is taken in column blocks so that no float32 copy of it is
    made whole."""
    head = head_matrix(model, params)
    cols = [head[:, c:c + COL_BLOCK]
            for c in range(0, head.shape[1], COL_BLOCK)]
    for s in range(0, h.shape[0], V_BLOCK):
        rows = h[s:s + V_BLOCK]
        yield s, jnp.concatenate([_logits(rows, w, quant) for w in cols],
                                 axis=-1)


def reward(prm: dict, h):
    """The reward head (two layers, sigmoid) on hidden states [..., d]."""
    h = jnp.tanh(jnp.matmul(h, prm["w1"], precision=HIGHEST) + prm["b1"])
    return jax.nn.sigmoid(jnp.matmul(h, prm["w2"], precision=HIGHEST)
                          + prm["b2"])
