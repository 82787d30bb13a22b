"""Plain reference of a dense decoder block stack as this system runs it.

Float32, no kernels or caches: pre-norm attention with rotary position
embedding and a causal softmax over all earlier positions, then a gated
SiLU feed-forward, each with its residual. Queries are taken a chunk at a
time only to bound memory; each query still attends to every earlier key.

Where the system departs from StableLM-3B-4E1T
(huggingface.co/stabilityai/stablelm-3b-4e1t), this reference follows the
system, so that it compares like with like:
  * RMSNorm without bias where the model has LayerNorm (eps 1e-5);
  * rotary embedding over the whole head (half-split form, theta from the
    configuration) where the model rotates the first 25% of each head.
Neither changes a width or the set of tensors that are checkpointed.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from chipbench.refops import rmsnorm

Q_CHUNK = 512


def rope(x, theta: float):
    """x (B, S, H, D), positions 0..S-1."""
    S, D = x.shape[1], x.shape[-1]
    half = D // 2
    freqs = 1.0 / (theta ** (jnp.arange(half, dtype=jnp.float32) / half))
    ang = jnp.arange(S, dtype=jnp.float32)[:, None] * freqs
    cos, sin = jnp.cos(ang)[None, :, None, :], jnp.sin(ang)[None, :, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def attention(p, x, m, prec):
    eps = m["norm_eps"]
    B, S, d = x.shape
    H, KV = m["num_heads"], m["num_kv_heads"]
    D = m.get("head_dim") or d // H
    h = rmsnorm(x, p["norm1"]["scale"], eps)
    q = rope(prec.mm(h, p["wq"]).reshape(B, S, H, D), m["rope_theta"])
    k = rope(prec.mm(h, p["wk"]).reshape(B, S, KV, D), m["rope_theta"])
    v = prec.mm(h, p["wv"]).reshape(B, S, KV, D)
    if KV != H:
        k = jnp.repeat(k, H // KV, axis=2)
        v = jnp.repeat(v, H // KV, axis=2)
    c = min(Q_CHUNK, S)
    qs = q.reshape(B, S // c, c, H, D).transpose(1, 0, 2, 3, 4)

    @jax.checkpoint
    def chunk(_, xs):
        qc, off = xs
        logits = prec.einsum("bqhd,bshd->bhqs", qc, k) / np.sqrt(D)
        qpos = off + jnp.arange(c)[:, None]
        mask = jnp.arange(S)[None, :] <= qpos
        logits = jnp.where(mask[None, None], logits, -jnp.inf)
        w = jax.nn.softmax(logits, axis=-1)
        return None, prec.einsum("bhqs,bshd->bqhd", w, v)

    _, outs = jax.lax.scan(chunk, None, (qs, jnp.arange(S // c) * c))
    out = outs.transpose(1, 0, 2, 3, 4).reshape(B, S, H * D)
    x = x + prec.mm(out, p["wo"])
    h2 = rmsnorm(x, p["norm2"]["scale"], eps)
    f = p["mlp"]
    y = jax.nn.silu(prec.mm(h2, f["wg"])) * prec.mm(h2, f["wu"])
    return x + prec.mm(y, f["wd"])


def hidden(params, tokens, m, prec):
    """Final-normed hidden states (B, S, d) of ``tokens`` (B, S)."""
    x = prec.act(params["embed"][tokens])

    @jax.checkpoint
    def layer(x, lp):
        return prec.act(attention(lp["b0_attn"], x, m, prec)), None

    x, _ = jax.lax.scan(layer, x, params["blocks"])
    return rmsnorm(x, params["final_norm"]["scale"], m["norm_eps"])


def out_weight(params, m):
    return params["embed"].T if m["tie_embeddings"] else params["head"]
