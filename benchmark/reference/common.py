"""Arithmetic shared by the plain references. Nothing of the program is
imported here or in any file of this directory: only jax.numpy.

`prec` names the precision of every matrix product's operands:
"f32" is float32 at `highest` (the reference), "bf16" and "fp8" round the
operands first (the controls: the nearest precision below what a
configuration states). Accumulation is float32 in all of them.
"""
import jax
import jax.numpy as jnp

HIGHEST = jax.lax.Precision.HIGHEST


def _round_to(x, prec):
    """x with its values rounded to `prec`, gradient passed straight
    through. fp8 (e4m3) is scaled by the tensor's largest magnitude, as an
    fp8 recipe would."""
    if prec == "f32":
        return x
    if prec == "bf16":
        r = x.astype(jnp.bfloat16).astype(jnp.float32)
    elif prec == "fp8":
        amax = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30)
        scale = 448.0 / amax
        r = (x * scale).astype(jnp.float8_e4m3fn).astype(jnp.float32) / scale
    else:
        raise ValueError(f"unknown precision {prec!r}")
    return x + jax.lax.stop_gradient(r - x)


def mm(spec, a, b, prec):
    """einsum in float32 with the operands rounded to `prec`."""
    return jnp.einsum(spec, _round_to(a, prec), _round_to(b, prec),
                      precision=HIGHEST,
                      preferred_element_type=jnp.float32)


def layer_norm(x, w, b, eps):
    mu = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mu), axis=-1, keepdims=True)
    return (x - mu) * jax.lax.rsqrt(var + eps) * w + b


def f32(tree):
    return jax.tree_util.tree_map(lambda v: v.astype(jnp.float32), tree)


def attention(q, k, v, bias, prec):
    """q, k, v: [B, L, H, D] float32; bias broadcastable to [B, H, L, L]
    (0 where a key may be seen, a large negative number where not)."""
    d = q.shape[-1]
    s = mm("blhd,bmhd->bhlm", q, k, prec) / jnp.sqrt(jnp.float32(d))
    p = jax.nn.softmax(s + bias, axis=-1)
    return mm("bhlm,bmhd->blhd", p, v, prec)


def token_nll(logits, labels):
    """Per-position negative log-likelihood and validity (label >= 0)."""
    valid = labels >= 0
    lab = jnp.maximum(labels, 0)
    lse = jax.nn.logsumexp(logits, axis=-1)
    picked = jnp.take_along_axis(logits, lab[..., None], axis=-1)[..., 0]
    return jnp.where(valid, lse - picked, 0.0), valid


def scalars(cfg):
    """A configuration's plain values as a hashable key."""
    return tuple(sorted((k, v) for k, v in cfg.items()
                        if isinstance(v, (int, float, str, bool))))
