"""Plain reference of the LFM2-MoE decoder as one pipeline stage holds it:
float32 `jax.numpy` at `highest`, a full causal forward with no cache, no
kernels, no batching tricks. Imports nothing of the program.

Layer l, as the published modeling code (`transformers`, `models/lfm2_moe`)
computes it, for input x, RMSNorm N (eps `norm_eps`, `x rsqrt(mean(x^2) +
eps) g`), all Linears without bias (`conv_bias` false):

    h   = x + Op_l(N_op(x))        # ShortConv ("conv") or Attention ("full_attention")
    out = h + F_l(N_ffn(h))        # dense SwiGLU below num_dense_layers, else MoE

then a final RMSNorm and an untied head.

ShortConv(y), y [L, h]: `[B | C | v] = y W_in` (W_in [h, 3h]; B the first h
columns, C the second, v the third); `z_t = B_t v_t`; the depthwise causal
convolution of `conv_L_cache` = 3 taps `c_t = k0 z_{t-2} + k1 z_{t-1} + k2
z_t` (z at a position below 0 is 0); `out_t = (C_t c_t) W_out`.

Attention(y): `q = y Wq` [heads, D], `k = y Wk`, `v = y Wv` [kv heads, D],
D = h / heads; q and k RMS-normed over each head's D values (gains [D]),
then rotated (`rope_theta`, rotate-half: dimension i turns with i + D/2,
positions absolute); query head h attends causally to key/value head h //
(heads / kv heads); scale D^-0.5; `out = concat(heads) Wo`.

MoE(y): `s = sigmoid(y W_r)` in float32 (W_r [h, num_experts]); the
`num_experts_per_tok` largest of `s + expert_bias` are selected (the bias
in the selection alone); a selected expert i weighs `s_i / (sum of the
selected s + 1e-6) x routed_scaling_factor` (`norm_topk_prob`); `MoE(y) =
sum_i w_i E_i(y)`, `E_i(y) = W2_i(silu(W1_i y) * W3_i y)` of
`moe_intermediate_size`. No shared expert, no groups, no capacity, no
dropped pair.

The stage: the configuration's `num_hidden_layers` layers, of the kinds
`stage_layer_types` lists (the published `layer_types` is the whole
model's, kept as published); `num_dense_layers` counts the stage's leading
dense layers. `n_routed_experts` of the configuration is
the number of experts HELD here, from `expert_offset` (all of them in the
configuration this file was written for).

Departures from the published code, all of rounding, layout or naming and
none of mathematics: (1) everything float32 (the published model computes
in bfloat16, its router in float32); (2) a Linear's weight is [in, out];
the conv kernel is [h, taps] (the published Conv1d weight [h, 1, taps]
without its channel axis); a layer's held experts are one leaf [held, in,
out] for each of w1 (gate), w3 (up) and w2 (down); (3) the final norm is
`norm.weight` (published `embedding_norm`); (4) `expert_bias` is a float32
leaf (a buffer there), drawn from the seed at `expert_bias_std` (a trained
model's values are not published; zeros would leave the selection's bias
path unguarded); (5) the weights are drawn by JAX's `rbg` generator (the
seed's draw is this file's to define).

Parameters are one flat dict, named as the published modules are
(`layers.<i>.conv.in_proj.weight`, `layers.<i>.self_attn.q_layernorm.weight`,
`layers.<i>.feed_forward.w1.weight`, `layers.<i>.feed_forward.gate.weight`,
...).

At the published widths an expert layer's 64 experts are 2.4 GB in
float32, so the serving comparison runs a layer in two programs (the
operator, the feed-forward part), the experts converted one at a time.
"""
import functools

import jax
import jax.numpy as jnp
import numpy as np

from .common import f32, mm, scalars
from .deepseek_v2 import _jit, _row_block, rms_norm, swiglu

BIAS = "feed_forward.expert_bias"
_CONV = ("conv.in_proj.weight", "conv.conv.weight", "conv.out_proj.weight")
_ATTN = ("self_attn.q_proj.weight", "self_attn.k_proj.weight",
         "self_attn.v_proj.weight", "self_attn.out_proj.weight",
         "self_attn.q_layernorm.weight", "self_attn.k_layernorm.weight")
_DENSE = ("feed_forward.w1.weight", "feed_forward.w3.weight",
          "feed_forward.w2.weight")
_MOE = ("feed_forward.gate.weight", BIAS, "feed_forward.experts.w1",
        "feed_forward.experts.w3", "feed_forward.experts.w2")


def layer_types(cfg):
    """The kinds of the stage's layers: "conv" or "full_attention"."""
    return list(cfg["stage_layer_types"])


def is_dense(cfg, layer):
    return layer < cfg["num_dense_layers"]


def is_attention(cfg, layer):
    return layer_types(cfg)[layer] == "full_attention"


def head_dim(cfg):
    return cfg["hidden_size"] // cfg["num_attention_heads"]


def layer_leaves(cfg, layer):
    return (("operator_norm.weight",)
            + (_ATTN if is_attention(cfg, layer) else _CONV)
            + ("ffn_norm.weight",)
            + (_DENSE if is_dense(cfg, layer) else _MOE))


def leaf_shapes(cfg):
    h, heads, kv = (cfg["hidden_size"], cfg["num_attention_heads"],
                    cfg["num_key_value_heads"])
    d, f, fe = head_dim(cfg), cfg["intermediate_size"], \
        cfg["moe_intermediate_size"]
    held = cfg["n_routed_experts"]
    block = {
        "operator_norm.weight": (h,), "ffn_norm.weight": (h,),
        "conv.in_proj.weight": (h, 3 * h),
        "conv.conv.weight": (h, cfg["conv_L_cache"]),
        "conv.out_proj.weight": (h, h),
        "self_attn.q_proj.weight": (h, heads * d),
        "self_attn.k_proj.weight": (h, kv * d),
        "self_attn.v_proj.weight": (h, kv * d),
        "self_attn.out_proj.weight": (heads * d, h),
        "self_attn.q_layernorm.weight": (d,),
        "self_attn.k_layernorm.weight": (d,),
        "feed_forward.w1.weight": (h, f), "feed_forward.w3.weight": (h, f),
        "feed_forward.w2.weight": (f, h),
        "feed_forward.gate.weight": (h, cfg["num_experts"]),
        BIAS: (cfg["num_experts"],),
        "feed_forward.experts.w1": (held, h, fe),
        "feed_forward.experts.w3": (held, h, fe),
        "feed_forward.experts.w2": (held, fe, h)}
    shapes = {"embed_tokens.weight": (cfg["vocab_size"], h)}
    for i in range(cfg["num_hidden_layers"]):
        for k in layer_leaves(cfg, i):
            shapes[f"layers.{i}.{k}"] = block[k]
    shapes["norm.weight"] = (h,)
    shapes["lm_head.weight"] = (h, cfg["vocab_size"])
    return shapes


def _key(cfg):
    """A configuration's scalars, its stage's layer kinds and its rotary
    base: what a program of this file depends on."""
    return scalars(cfg) + (tuple(layer_types(cfg)),
                           cfg["rope_parameters"]["rope_theta"])


@functools.lru_cache(maxsize=4)
def _make_init(cfg_key):
    cfg = dict(cfg_key[:-2], stage_layer_types=list(cfg_key[-2]))
    dt = jnp.dtype(cfg["dtype"])
    shapes = leaf_shapes(cfg)
    std = cfg["initializer_range"]

    def make(key):
        keys = jax.random.split(key, len(shapes))
        p = {}
        for k, (name, shape) in zip(keys, shapes.items()):
            if name.endswith(BIAS):
                p[name] = jax.random.normal(k, shape, jnp.float32) \
                    * cfg["expert_bias_std"]
            elif len(shape) == 1:
                p[name] = jnp.ones(shape, dt)               # a norm's gain
            else:
                # every matrix, the conv kernel and the router among them,
                # at `initializer_range`
                p[name] = (jax.random.normal(k, shape, jnp.float32)
                           * std).astype(dt)
        return p

    return jax.jit(make)


def init_params(cfg, seed):
    """The weights of a run, from its seed, in one jitted call on the
    device, in the type the configuration stores them in."""
    return _make_init(_key(cfg))(
        jax.random.key(seed % (2 ** 31), impl="rbg"))


# ------------------------------------------------------------ the layers
def rope(x, pos, cfg):
    """x [L, heads, D] at positions pos [L], rotate-half pairing."""
    d = x.shape[-1]
    inv = jnp.asarray(cfg["rope_parameters"]["rope_theta"]
                      ** (-np.arange(0, d, 2, dtype=np.float64) / d),
                      jnp.float32)
    ang = pos.astype(jnp.float32)[:, None, None] * inv[None, None, :]
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    a, b = x[..., :d // 2], x[..., d // 2:]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], axis=-1)


def short_conv(p, y, prec):
    """The gated short convolution of the module's docstring over normed y
    [L, h]. `p`: the layer's `conv.*` leaves, float32."""
    h = y.shape[-1]
    bcv = mm("lh,hk->lk", y, p["conv.in_proj.weight"], prec)
    b, c, v = bcv[:, :h], bcv[:, h:2 * h], bcv[:, 2 * h:]
    z = b * v
    k = p["conv.conv.weight"]
    taps = k.shape[-1]
    conv = sum(k[:, j] * jnp.pad(z, ((taps - 1 - j, 0), (0, 0)))[:z.shape[0]]
               for j in range(taps))
    return mm("lh,hk->lk", c * conv, p["conv.out_proj.weight"], prec)


def attention(p, y, cfg, prec):
    """Grouped-query attention of the module's docstring over normed y
    [L, h], causal, the queries in blocks of rows. `p`: the layer's
    `self_attn.*` leaves, float32."""
    L, heads, kv = (y.shape[0], cfg["num_attention_heads"],
                    cfg["num_key_value_heads"])
    d, eps = head_dim(cfg), cfg["norm_eps"]
    pos = jnp.arange(L)
    a = "self_attn."
    q = rms_norm(mm("lh,hk->lk", y, p[a + "q_proj.weight"], prec
                    ).reshape(L, heads, d), p[a + "q_layernorm.weight"], eps)
    k = rms_norm(mm("lh,hk->lk", y, p[a + "k_proj.weight"], prec
                    ).reshape(L, kv, d), p[a + "k_layernorm.weight"], eps)
    v = mm("lh,hk->lk", y, p[a + "v_proj.weight"], prec).reshape(L, kv, d)
    q, k = rope(q, pos, cfg), rope(k, pos, cfg)
    # key/value head of query head h: h // (heads / kv)
    k = jnp.repeat(k, heads // kv, axis=1)
    v = jnp.repeat(v, heads // kv, axis=1)
    rb = _row_block(L)

    def rows(lo):
        qb = jax.lax.dynamic_slice_in_dim(q, lo, rb, axis=0)
        s = mm("lhd,mhd->hlm", qb, k, prec) * d ** -0.5
        seen = pos[None, :] <= (lo + jnp.arange(rb))[:, None]
        prob = jax.nn.softmax(jnp.where(seen[None], s, -1e30), axis=-1)
        return mm("hlm,mhd->lhd", prob, v, prec)

    o = jax.lax.map(rows, jnp.arange(0, L, rb)).reshape(L, heads * d)
    return mm("lk,kh->lh", o, p[a + "out_proj.weight"], prec)


def router_scores(p, y, prec):
    """sigmoid over the router's columns, [L, num_experts]."""
    return jax.nn.sigmoid(mm("lh,he->le", y, p["feed_forward.gate.weight"]
                             .astype(jnp.float32), prec))


def selected(scores, bias, cfg):
    """bool [L, num_experts]: the `num_experts_per_tok` experts with the
    largest scores + bias."""
    _, ei = jax.lax.top_k(scores + bias, cfg["num_experts_per_tok"])
    return jax.nn.one_hot(ei, scores.shape[-1], dtype=jnp.int32).sum(-2) > 0


def route(scores, bias, cfg):
    """Combine weights [L, num_experts]: a selected expert at its score
    over the selected scores' sum + 1e-6 (`norm_topk_prob`) times
    `routed_scaling_factor`, every other expert 0."""
    w = jnp.where(selected(scores, bias, cfg), scores, 0.0)
    if cfg["norm_topk_prob"]:
        w = w / (jnp.sum(w, -1, keepdims=True) + 1e-6)
    return w * cfg["routed_scaling_factor"]


def moe(p, y, cfg, prec):
    """The held experts' part of the expert layer over normed y [L, h].
    `p`: the layer's `feed_forward.*` leaves; the experts' leaves may be
    any precision (each expert is converted as it is used)."""
    off, held = cfg["expert_offset"], cfg["n_routed_experts"]
    w = route(router_scores(p, y, prec), p[BIAS].astype(jnp.float32), cfg)

    def one(acc, e):
        gate, up, down, we = f32(e)
        return acc + we[:, None] * swiglu(y, gate, up, down, prec), None

    f = "feed_forward.experts."
    out, _ = jax.lax.scan(one, jnp.zeros_like(y), (
        p[f + "w1"], p[f + "w3"], p[f + "w2"], w[:, off:off + held].T))
    return out


def op_part(p, x, cfg, prec, attn):
    """x + Op(N_op(x)); `p`: the layer's operator leaves and its norm."""
    p = f32(p)
    y = rms_norm(x, p["operator_norm.weight"], cfg["norm_eps"])
    return x + (attention(p, y, cfg, prec) if attn
                else short_conv(p, y, prec))


def ffn_part(p, x, cfg, prec, dense):
    """x + F(N_ffn(x)); `p`: the layer's feed-forward leaves and its
    norm."""
    y = rms_norm(x, p["ffn_norm.weight"].astype(jnp.float32),
                 cfg["norm_eps"])
    if dense:
        m = f32({k: p[k] for k in _DENSE})
        return x + swiglu(y, m["feed_forward.w1.weight"],
                          m["feed_forward.w3.weight"],
                          m["feed_forward.w2.weight"], prec)
    return x + moe(p, y, cfg, prec)


def block(p, x, cfg, prec, i):
    """Layer i of the stage over x [L, h] in one piece."""
    return ffn_part(p, op_part(p, x, cfg, prec, is_attention(cfg, i)), cfg,
                    prec, is_dense(cfg, i))


def final_logits(p, x, cfg, prec):
    p = f32(p)
    return mm("lh,hv->lv", rms_norm(x, p["norm.weight"], cfg["norm_eps"]),
              p["lm_head.weight"], prec)


# -------------------------------------------------------------- serving
def _op_params(cfg, params, i):
    return {k: params[f"layers.{i}.{k}"] for k in layer_leaves(cfg, i)
            if not k.startswith(("feed_forward.", "ffn_norm."))}


def _ffn_params(cfg, params, i):
    return {k: params[f"layers.{i}.{k}"] for k in layer_leaves(cfg, i)
            if k.startswith(("feed_forward.", "ffn_norm."))}


def hidden_states(cfg, params, ids, prec="f32", router=None):
    """The stream [L, h] after the last layer, of the plain forward over
    `ids`: a layer at a time, a layer in two programs (its operator, its
    feed-forward part) that all layers of a kind share. `router(p, y)` is
    called on each expert layer's normed input where given."""
    key = _key(cfg)
    x = params["embed_tokens.weight"][jnp.asarray(ids, jnp.int32)
                                      ].astype(jnp.float32)
    for i in range(cfg["num_hidden_layers"]):
        attn, dense = is_attention(cfg, i), is_dense(cfg, i)
        x = _jit(("op", key, prec, attn), lambda p, x_, a=attn: op_part(
            p, x_, cfg, prec, a))(_op_params(cfg, params, i), x)
        pf = _ffn_params(cfg, params, i)
        if router is not None and not dense:
            router(pf, x)
        x = _jit(("ffn", key, prec, dense), lambda p, x_, d=dense: ffn_part(
            p, x_, cfg, prec, d))(pf, x)
    return x


def served_rows_logits(cfg, params, ids, first_row, rows, prec="f32"):
    """Logits [rows, vocab] of positions first_row .. first_row+rows-1 of
    the plain forward pass over `ids` ([T] token ids, padded at the end to
    any length: the operators are causal and every other part acts on one
    position alone, so what follows a position cannot reach it)."""
    x = hidden_states(cfg, params, ids, prec)
    head = _jit(("head", _key(cfg), prec, rows), lambda p, x_, lo:
                final_logits(p, jax.lax.dynamic_slice_in_dim(x_, lo, rows, 0),
                             cfg, prec))
    return head({k: params[k] for k in ("norm.weight", "lm_head.weight")},
                x, first_row)


def served_gaps(cfg, params, prompt, served, pad_to, control=None):
    """How far each served token's logit lies below the reference's best at
    its position: array [len(served)]. With `control` (a lower precision),
    the token judged at each position is the one that precision puts first
    over the same prompt and tokens, not the served one."""
    n, g = len(prompt), len(served)
    ids = list(prompt) + list(served)
    ids = ids + [0] * (pad_to - len(ids))
    ref = served_rows_logits(cfg, params, ids, n - 1, g)
    if control is None:
        judged = jnp.asarray(served, jnp.int32)
    else:
        judged = jnp.argmax(served_rows_logits(
            cfg, params, ids, n - 1, g, prec=control), axis=-1)
    best = jnp.max(ref, axis=-1)
    return best - jnp.take_along_axis(ref, judged[:, None], axis=-1)[:, 0]


def _selections(cfg, params, ids, prec, biased=True):
    """bool [expert layers, L, num_experts]: each expert layer's selection
    over the forward's own stream, with or without the bias."""
    key, out = _key(cfg), []

    def pick(p, x):
        bias = p[BIAS].astype(jnp.float32)
        y = rms_norm(x, p["ffn_norm.weight"].astype(jnp.float32),
                     cfg["norm_eps"])
        return selected(router_scores(p, y, prec),
                        bias if biased else jnp.zeros_like(bias), cfg)

    def look(p, x):
        out.append(_jit(("selected", key, prec, biased), pick)(p, x))

    hidden_states(cfg, params, ids, prec, router=look)
    return jnp.stack(out)


def selection_differs(cfg, params, ids, prec):
    """Of the (position, expert layer) pairs of the forward over `ids`, the
    share whose selected set under `prec` differs from float32's (each on
    its own stream): what rounding does to the routing."""
    return float(jnp.mean(jnp.any(
        _selections(cfg, params, ids, "f32")
        != _selections(cfg, params, ids, prec), axis=-1)))


def bias_moves_selection(cfg, params, ids):
    """Of the (position, expert layer) pairs of the float32 forward over
    `ids`, the share whose selected set differs from the unbiased top-k of
    the same scores: how much of the routing the bias decides."""
    return float(jnp.mean(jnp.any(
        _selections(cfg, params, ids, "f32")
        != _selections(cfg, params, ids, "f32", biased=False), axis=-1)))
