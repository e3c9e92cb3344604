"""Fused elementwise/reduction Pallas kernels.

TPU-native equivalents of the reference's fused CUDA ops:
- fused_softmax_cross_entropy ≈ phi softmax_with_cross_entropy kernel
  (paddle/phi/kernels/gpu/cross_entropy_kernel.cu): one pass over the vocab
  axis produces the loss; the backward is the classic (softmax - onehot) * g
  without materializing probabilities in fp32 HBM twice.
- fused_adamw ≈ fused_adam_op (paddle/fluid/operators/fused/fused_adam_op.cc):
  p/m/v updated in a single kernel launch per tensor.
- fused_dropout_residual_layer_norm ≈ fused_dropout_add_ln
  (paddle/fluid/operators/fused/fused_layernorm_residual_dropout_bias.h).

Each has a jnp reference; the Pallas path engages on TPU-friendly shapes and
falls back otherwise (same dispatch pattern as ops/attention.py).
"""
import functools

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P
import numpy as np

from ._fallback import kernel_fallback
from ._per_device import BATCH_AXES, dim_axes, kernel_mesh

__all__ = ["fused_softmax_cross_entropy", "fused_adamw",
           "fused_dropout_residual_layer_norm"]


def _interpret_default():
    return jax.default_backend() == "cpu"


def can_fuse_xent(n, v):
    """True when the streaming CE kernel will engage: TPU backend, row blocks
    tile, and the vocab has a 128-multiple block divisor."""
    if jax.default_backend() == "cpu":
        return False
    if n <= 0 or n % 256 != 0:
        return False
    try:
        _pick_block_v(v)
        return True
    except ValueError:
        return False


def _pick_block_v(v):
    """Largest vocab block (multiple of 128, VMEM-friendly) dividing v."""
    for cand in (1024, 768, 512, 384, 256, 128):
        if v % cand == 0:
            return cand
    raise ValueError(f"vocab {v} has no 128-multiple block divisor")


# --------------------------------------------------------------------------
# fused softmax cross entropy
# --------------------------------------------------------------------------

def _xent_ref(logits, labels):
    lse = jax.scipy.special.logsumexp(logits.astype(jnp.float32), axis=-1)
    picked = jnp.take_along_axis(
        logits.astype(jnp.float32), labels[:, None].astype(jnp.int32), axis=-1)[:, 0]
    return lse - picked


def _xent_fwd_kernel(x_ref, lab_ref, loss_ref, lse_ref, m_s, s_s, p_s, *,
                     block_v, n_vb):
    """Streaming online-softmax CE: the vocab axis is the innermost grid dim
    (TPU grid iterations run sequentially), carry lives in VMEM scratch —
    only one (block_n, block_v) logits tile is resident at a time."""
    from jax.experimental import pallas as pl

    rows = x_ref.shape[0]
    j = pl.program_id(1)
    lab = lab_ref[...]                         # (rows, 1)

    @pl.when(j == 0)
    def _init():
        m_s[...] = jnp.full((rows, 1), -1e30, jnp.float32)
        s_s[...] = jnp.zeros((rows, 1), jnp.float32)
        p_s[...] = jnp.zeros((rows, 1), jnp.float32)

    x = x_ref[...].astype(jnp.float32)
    m = m_s[...]
    m_new = jnp.maximum(m, jnp.max(x, axis=-1, keepdims=True))
    s_s[...] = s_s[...] * jnp.exp(m - m_new) + jnp.sum(
        jnp.exp(x - m_new), axis=-1, keepdims=True)
    m_s[...] = m_new
    cols = j * block_v + jax.lax.broadcasted_iota(jnp.int32, (rows, block_v), 1)
    hit = cols == lab
    p_s[...] = p_s[...] + jnp.sum(jnp.where(hit, x, 0.0), axis=-1, keepdims=True)

    @pl.when(j == n_vb - 1)
    def _fin():
        lse = m_s[...] + jnp.log(jnp.maximum(s_s[...], 1e-30))
        loss_ref[...] = lse - p_s[...]
        lse_ref[...] = lse


def _xent_bwd_kernel(x_ref, lab_ref, lse_ref, g_ref, dx_ref, *, block_v):
    from jax.experimental import pallas as pl

    rows = x_ref.shape[0]
    j = pl.program_id(1)
    x = x_ref[...].astype(jnp.float32)
    lab = lab_ref[...]                          # (rows, 1)
    lse = lse_ref[...]                          # (rows, 1)
    g = g_ref[...]                              # (rows, 1)
    p = jnp.exp(x - lse)
    cols = j * block_v + jax.lax.broadcasted_iota(jnp.int32, (rows, block_v), 1)
    onehot = (cols == lab).astype(jnp.float32)
    dx_ref[...] = ((p - onehot) * g).astype(dx_ref.dtype)


@functools.partial(jax.custom_vjp, nondiff_argnums=())
def fused_softmax_cross_entropy(logits, labels):
    """loss[i] = logsumexp(logits[i]) - logits[i, labels[i]] — (N, V) x (N,)."""
    loss, _ = _xent_fwd(logits, labels)
    return loss


def _xent_rows(mesh, n):
    """Rows are independent: split them over the data axes in whole
    256-row blocks (the vocab stays together; the compiler gathers it)."""
    return P(dim_axes(mesh, n // 256, BATCH_AXES) if n % 256 == 0 else None)


def _xent_fwd_impl(logits, labels, interpret=None):
    from jax.experimental import pallas as pl

    mesh = kernel_mesh()
    if mesh is not None:
        rows = _xent_rows(mesh, logits.shape[0])
        return jax.shard_map(
            lambda lg, lab: _xent_fwd_impl(lg, lab, interpret), mesh=mesh,
            in_specs=(rows, rows), out_specs=(rows, rows),
            check_vma=False)(logits, labels)
    if interpret is None:
        interpret = _interpret_default()
    from jax.experimental.pallas import tpu as pltpu

    n, v = logits.shape
    block_n = 256 if n % 256 == 0 else n
    block_v = _pick_block_v(v)
    n_vb = v // block_v
    loss, lse = pl.pallas_call(
        functools.partial(_xent_fwd_kernel, block_v=block_v, n_vb=n_vb),
        grid=(n // block_n, n_vb),
        in_specs=[
            pl.BlockSpec((block_n, block_v), lambda i, j: (i, j)),
            pl.BlockSpec((block_n, 1), lambda i, j: (i, 0)),
        ],
        out_specs=[
            pl.BlockSpec((block_n, 1), lambda i, j: (i, 0)),
            pl.BlockSpec((block_n, 1), lambda i, j: (i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((n, 1), jnp.float32),
            jax.ShapeDtypeStruct((n, 1), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_n, 1), jnp.float32),
            pltpu.VMEM((block_n, 1), jnp.float32),
            pltpu.VMEM((block_n, 1), jnp.float32),
        ],
        interpret=interpret,
        name="xent_fwd",
    )(logits, labels.astype(jnp.int32).reshape(n, 1))
    return loss[:, 0], lse


def _xent_fwd(logits, labels):
    try:
        loss, lse = _xent_fwd_impl(logits, labels)
    except Exception as e:
        kernel_fallback("fused_softmax_xent_fwd", e)
        loss = _xent_ref(logits, labels)
        lse = None
    return loss, (logits, labels, lse)


def _xent_bwd_impl(logits, labels, lse, g, interpret=None):
    from jax.experimental import pallas as pl

    mesh = kernel_mesh()
    if mesh is not None:
        rows = _xent_rows(mesh, logits.shape[0])
        return jax.shard_map(
            lambda lg, lab, lse, g: _xent_bwd_impl(lg, lab, lse, g,
                                                   interpret),
            mesh=mesh, in_specs=(rows, rows, rows, rows), out_specs=rows,
            check_vma=False)(logits, labels, lse, g)
    if interpret is None:
        interpret = _interpret_default()
    n, v = logits.shape
    block_n = 256 if n % 256 == 0 else n
    block_v = _pick_block_v(v)
    return pl.pallas_call(
        functools.partial(_xent_bwd_kernel, block_v=block_v),
        grid=(n // block_n, v // block_v),
        in_specs=[
            pl.BlockSpec((block_n, block_v), lambda i, j: (i, j)),
            pl.BlockSpec((block_n, 1), lambda i, j: (i, 0)),
            pl.BlockSpec((block_n, 1), lambda i, j: (i, 0)),
            pl.BlockSpec((block_n, 1), lambda i, j: (i, 0)),
        ],
        out_specs=pl.BlockSpec((block_n, block_v), lambda i, j: (i, j)),
        out_shape=jax.ShapeDtypeStruct((n, v), logits.dtype),
        interpret=interpret,
        name="xent_bwd",
    )(logits, labels.astype(jnp.int32).reshape(n, 1), lse.reshape(n, 1),
      g.reshape(n, 1))


def _xent_vjp_fwd(logits, labels):
    loss, res = _xent_fwd(logits, labels)
    return loss, res


def _xent_vjp_bwd(res, g):
    logits, labels, lse = res
    if lse is not None:
        try:
            return _xent_bwd_impl(logits, labels, lse, g), None
        except Exception as e:
            kernel_fallback("fused_softmax_xent_bwd", e)
    p = jax.nn.softmax(logits.astype(jnp.float32), axis=-1)
    onehot = jax.nn.one_hot(labels, logits.shape[-1], dtype=jnp.float32)
    return ((p - onehot) * g[:, None]).astype(logits.dtype), None


fused_softmax_cross_entropy.defvjp(_xent_vjp_fwd, _xent_vjp_bwd)


# --------------------------------------------------------------------------
# fused AdamW update
# --------------------------------------------------------------------------

def _adamw_kernel(p_ref, g_ref, m_ref, v_ref, po_ref, mo_ref, vo_ref, *,
                  lr, beta1, beta2, eps, weight_decay, bc1, bc2):
    p = p_ref[...].astype(jnp.float32)
    g = g_ref[...].astype(jnp.float32)
    m = m_ref[...].astype(jnp.float32)
    v = v_ref[...].astype(jnp.float32)
    m_new = beta1 * m + (1 - beta1) * g
    v_new = beta2 * v + (1 - beta2) * g * g
    mhat = m_new / bc1
    vhat = v_new / bc2
    p_new = p - lr * (mhat / (jnp.sqrt(vhat) + eps) + weight_decay * p)
    po_ref[...] = p_new.astype(po_ref.dtype)
    mo_ref[...] = m_new.astype(mo_ref.dtype)
    vo_ref[...] = v_new.astype(vo_ref.dtype)


def fused_adamw(p, g, m, v, step, lr, beta1=0.9, beta2=0.999, eps=1e-8,
                weight_decay=0.01, interpret=None):
    """One fused AdamW update; returns (p_new, m_new, v_new). `step` is the
    1-based step count used for bias correction (a python/static int)."""
    from jax.experimental import pallas as pl

    if interpret is None:
        interpret = _interpret_default()
    bc1 = 1.0 - beta1 ** step
    bc2 = 1.0 - beta2 ** step
    shape = p.shape
    flat = int(np.prod(shape)) if shape else 1
    args = [t.reshape(flat) for t in (p, g, m, v)]
    block = 65536 if flat % 65536 == 0 else flat
    try:
        po, mo, vo = pl.pallas_call(
            functools.partial(_adamw_kernel, lr=lr, beta1=beta1, beta2=beta2,
                              eps=eps, weight_decay=weight_decay, bc1=bc1, bc2=bc2),
            grid=(flat // block,),
            in_specs=[pl.BlockSpec((block,), lambda i: (i,))] * 4,
            out_specs=[pl.BlockSpec((block,), lambda i: (i,))] * 3,
            out_shape=[jax.ShapeDtypeStruct((flat,), p.dtype),
                       jax.ShapeDtypeStruct((flat,), m.dtype),
                       jax.ShapeDtypeStruct((flat,), v.dtype)],
            interpret=interpret,
            name="fused_adamw",
        )(*args)
    except Exception as e:
        kernel_fallback("fused_adamw", e)
        pf, gf, mf, vf = (t.astype(jnp.float32) for t in args)
        mo = beta1 * mf + (1 - beta1) * gf
        vo = beta2 * vf + (1 - beta2) * gf * gf
        po = pf - lr * ((mo / bc1) / (jnp.sqrt(vo / bc2) + eps)
                        + weight_decay * pf)
        po, mo, vo = po.astype(p.dtype), mo.astype(m.dtype), vo.astype(v.dtype)
    return po.reshape(shape), mo.reshape(shape), vo.reshape(shape)


# --------------------------------------------------------------------------
# fused dropout + residual + layer norm
# --------------------------------------------------------------------------

def _dropout_res_ln_ref(x, residual, weight, bias, key, p, eps, training):
    if training and p > 0:
        keep = jax.random.bernoulli(key, 1.0 - p, x.shape)
        x = jnp.where(keep, x / (1.0 - p), 0.0)
    h = x + residual
    h32 = h.astype(jnp.float32)
    mean = h32.mean(axis=-1, keepdims=True)
    var = h32.var(axis=-1, keepdims=True)
    out = (h32 - mean) * jax.lax.rsqrt(var + eps)
    if weight is not None:
        out = out * weight.astype(jnp.float32)
    if bias is not None:
        out = out + bias.astype(jnp.float32)
    return out.astype(x.dtype), h


def _dropout_res_ln_kernel(x_ref, r_ref, w_ref, b_ref, rng_ref, o_ref, h_ref,
                           *, p, eps, host_bits):
    """rng_ref is the per-call seed (TPU: in-kernel hardware PRNG draws the
    mask, nothing rides through HBM) or a precomputed uint32 bits block
    (host_bits=True: CPU/interpret, where the prng primitives have no
    lowering).  Everything downstream of `bits` is the same code either
    way, so interpret-mode tests assert the real threshold/scale/LN
    arithmetic."""
    from jax.experimental import pallas as pl
    x = x_ref[...].astype(jnp.float32)
    r = r_ref[...].astype(jnp.float32)
    if p > 0:
        if host_bits:
            bits = rng_ref[...]
        else:
            from jax.experimental.pallas import tpu as pltpu
            pltpu.prng_seed(rng_ref[0] + pl.program_id(0))
            bits = pltpu.prng_random_bits(x_ref.shape)
        thresh = jnp.asarray(int((1.0 - p) * (2 ** 32 - 1)), jnp.uint32)
        keep = bits.astype(jnp.uint32) <= thresh
        x = jnp.where(keep, x / (1.0 - p), 0.0)
    h = x + r
    mean = h.mean(axis=-1, keepdims=True)
    var = ((h - mean) ** 2).mean(axis=-1, keepdims=True)
    out = (h - mean) * jax.lax.rsqrt(var + eps)
    out = out * w_ref[...].astype(jnp.float32) + b_ref[...].astype(jnp.float32)
    o_ref[...] = out.astype(o_ref.dtype)
    h_ref[...] = h.astype(h_ref.dtype)


def fused_dropout_residual_layer_norm(x, residual, weight, bias, p=0.1,
                                      eps=1e-5, seed=0, training=True,
                                      interpret=None):
    """out = LN(dropout(x) + residual); also returns the pre-LN sum (the
    residual stream the next block consumes). 2-D (rows, hidden) input."""
    from jax.experimental import pallas as pl

    if interpret is None:
        interpret = _interpret_default()
    n, h = x.shape
    w = weight if weight is not None else jnp.ones((h,), x.dtype)
    b = bias if bias is not None else jnp.zeros((h,), x.dtype)
    block_n = 256 if n % 256 == 0 else n
    if h % 128 == 0:
        # interpret mode has no lowering for the TPU prng primitives:
        # draw the mask bits on the host there so the kernel's dropout
        # arithmetic still runs (and is asserted) on CPU
        host_bits = bool(interpret) and training and p > 0
        if host_bits:
            bits = jax.random.bits(jax.random.PRNGKey(seed), (n, h),
                                   jnp.uint32)
            rng_arg = bits
            rng_spec = pl.BlockSpec((block_n, h), lambda i: (i, 0))
        else:
            rng_arg = jnp.asarray([seed], jnp.int32)
            rng_spec = pl.BlockSpec((1,), lambda i: (0,))
        try:
            return tuple(pl.pallas_call(
                functools.partial(_dropout_res_ln_kernel,
                                  p=p if training else 0.0, eps=eps,
                                  host_bits=host_bits),
                grid=(n // block_n,),
                in_specs=[
                    pl.BlockSpec((block_n, h), lambda i: (i, 0)),
                    pl.BlockSpec((block_n, h), lambda i: (i, 0)),
                    pl.BlockSpec((h,), lambda i: (0,)),
                    pl.BlockSpec((h,), lambda i: (0,)),
                    rng_spec,
                ],
                out_specs=[
                    pl.BlockSpec((block_n, h), lambda i: (i, 0)),
                    pl.BlockSpec((block_n, h), lambda i: (i, 0)),
                ],
                out_shape=[jax.ShapeDtypeStruct((n, h), x.dtype),
                           jax.ShapeDtypeStruct((n, h), x.dtype)],
                interpret=interpret,
                name="dropout_residual_ln",
            )(x, residual, w, b, rng_arg))
        except Exception as e:
            kernel_fallback("fused_dropout_residual_ln", e)
    key = jax.random.PRNGKey(seed)
    return _dropout_res_ln_ref(x, residual, w, b, key, p, eps, training)
