"""Operations shared by the plain references under ``configs/``.

Everything is float32. Matrix products go through a ``Precision`` policy:
``f32`` multiplies at ``jax.lax.Precision.HIGHEST`` (a TPU otherwise rounds
float32 operands to bfloat16), and ``fp8`` is the control, the reference
one precision step below the bfloat16 that the configurations state: the
usual float8 recipe, with both operands of every product rounded to e4m3
and the gradients that flow back through them to e5m2, each tensor under
its own scale.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

HIGHEST = jax.lax.Precision.HIGHEST


def _round(x, dtype, top: float):
    """Round ``x`` to ``dtype`` under a per-tensor scale that maps its
    largest magnitude to ``top``, and back to float32."""
    s = jnp.max(jnp.abs(x)) / top
    s = jnp.where(s > 0, s, 1.0)
    # a TPU's float32 division is not correctly rounded: keep x / s in
    # range, since e4m3 has no infinity and rounds past it to NaN
    y = jnp.clip(x / s, -top, top)
    return y.astype(dtype).astype(jnp.float32) * s


@jax.custom_vjp
def fp8(x):
    return _round(x, jnp.float8_e4m3fn, 448.0)


def _fp8_fwd(x):
    return fp8(x), None


def _fp8_bwd(_, g):
    return (_round(g, jnp.float8_e5m2, 57344.0),)


fp8.defvjp(_fp8_fwd, _fp8_bwd)


class Precision:
    def __init__(self, kind: str = "f32"):
        if kind not in ("f32", "fp8"):
            raise ValueError(f"unknown reference precision {kind!r}")
        self.kind = kind

    def q(self, x):
        return x if self.kind == "f32" else fp8(x)

    act = q   # a tensor the system keeps in its compute dtype

    def einsum(self, spec: str, a, b):
        return self.q(jnp.einsum(spec, self.q(a), self.q(b),
                                 precision=HIGHEST,
                                 preferred_element_type=jnp.float32))

    def mm(self, a, b):
        return self.einsum("...i,ij->...j", a, b)


def rmsnorm(x, scale, eps: float):
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * scale


def ce_sum(h, w_out, labels, prec: Precision, chunk: int = 512):
    """Sum over positions of -log softmax(h @ w_out)[label], a sequence
    chunk at a time. h (B, S, d), w_out (d, V), labels (B, S)."""
    B, S, d = h.shape
    c = min(chunk, S)
    if S % c:
        raise ValueError(f"sequence {S} is not a multiple of {c}")
    hs = h.reshape(B, S // c, c, d).transpose(1, 0, 2, 3)
    ls = labels.reshape(B, S // c, c).transpose(1, 0, 2)

    @jax.checkpoint
    def body(acc, xs):
        hc, lc = xs
        logits = prec.mm(hc, w_out)
        lse = jax.scipy.special.logsumexp(logits, axis=-1)
        gold = jnp.take_along_axis(logits, lc[..., None], axis=-1)[..., 0]
        return acc + jnp.sum(lse - gold), None

    total, _ = jax.lax.scan(body, jnp.zeros((), jnp.float32), (hs, ls))
    return total
