"""Plain reference of the DeepSeek-V2 decoder as one chip of a deployment
holds it: multi-head latent attention (MLA) in its materialised form only,
YaRN rotary positions, RMSNorm, gated SiLU MLPs without biases, a leading
dense layer and then expert layers that score by softmax over the router's
whole width, select group-limited (the best groups, then the best experts
inside them), drop no token and add two shared experts; untied head.
float32 `jax.numpy` at `highest`, a full causal forward with no cache, no
kernels, no batching tricks. Imports nothing of the program.

The chip's share: `n_routed_experts` of the configuration is the number of
experts HELD here (`expert_offset` .. `expert_offset + n_routed_experts`
of the `router_width` the router scores); the layer routes over all of the
router's width and adds only what held experts give, with the shared
experts whole. That partial result goes on to the next layer. The
vocabulary is the slice the configuration states.

Parameters are one flat dict, named as the published checkpoint names its
modules (`layers.<i>.self_attn.q_a_proj.weight` ...; a Linear's weight is
[in, out]); the held experts of a layer are one leaf [held, in, out] for
each of gate, up and down. `q_b_proj`'s columns are laid out [heads,
nope + rope], `kv_b_proj`'s [heads, nope + v], `kv_a_proj_with_mqa`'s
[latent | rope], as published.

The rotary pairing: dimensions (2i, 2i+1) of the 64 rotary ones are pair
i, turned by `pos * inv_freq[i]`; the result is written with the pairs'
first members in the first half and their second members in the second
(the published code de-interleaves before its `rotate_half`). Queries and
keys are laid out alike, so their products do not depend on it.
"""
import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

from .common import f32, mm, scalars

_ATTN = ("input_layernorm.weight", "self_attn.q_a_proj.weight",
         "self_attn.q_a_layernorm.weight", "self_attn.q_b_proj.weight",
         "self_attn.kv_a_proj_with_mqa.weight",
         "self_attn.kv_a_layernorm.weight", "self_attn.kv_b_proj.weight",
         "self_attn.o_proj.weight", "post_attention_layernorm.weight")
_DENSE = ("mlp.gate_proj.weight", "mlp.up_proj.weight",
          "mlp.down_proj.weight")
_MOE = ("mlp.gate.weight", "mlp.experts.gate_proj", "mlp.experts.up_proj",
        "mlp.experts.down_proj", "mlp.shared_experts.gate_proj.weight",
        "mlp.shared_experts.up_proj.weight",
        "mlp.shared_experts.down_proj.weight")


def is_dense(cfg, layer):
    return layer < cfg["first_k_dense_replace"] \
        or layer % cfg["moe_layer_freq"] != 0


def layer_leaves(cfg, layer):
    return _ATTN + (_DENSE if is_dense(cfg, layer) else _MOE)


def leaf_shapes(cfg):
    h, heads = cfg["hidden_size"], cfg["num_attention_heads"]
    dn, dr, dv = (cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"],
                  cfg["v_head_dim"])
    rq, rkv = cfg["q_lora_rank"], cfg["kv_lora_rank"]
    f, fm = cfg["intermediate_size"], cfg["moe_intermediate_size"]
    held, fs = cfg["n_routed_experts"], fm * cfg["n_shared_experts"]
    block = {
        "input_layernorm.weight": (h,),
        "self_attn.q_a_proj.weight": (h, rq),
        "self_attn.q_a_layernorm.weight": (rq,),
        "self_attn.q_b_proj.weight": (rq, heads * (dn + dr)),
        "self_attn.kv_a_proj_with_mqa.weight": (h, rkv + dr),
        "self_attn.kv_a_layernorm.weight": (rkv,),
        "self_attn.kv_b_proj.weight": (rkv, heads * (dn + dv)),
        "self_attn.o_proj.weight": (heads * dv, h),
        "post_attention_layernorm.weight": (h,),
        "mlp.gate_proj.weight": (h, f), "mlp.up_proj.weight": (h, f),
        "mlp.down_proj.weight": (f, h),
        "mlp.gate.weight": (h, cfg["router_width"]),
        "mlp.experts.gate_proj": (held, h, fm),
        "mlp.experts.up_proj": (held, h, fm),
        "mlp.experts.down_proj": (held, fm, h),
        "mlp.shared_experts.gate_proj.weight": (h, fs),
        "mlp.shared_experts.up_proj.weight": (h, fs),
        "mlp.shared_experts.down_proj.weight": (fs, h),
    }
    shapes = {"embed_tokens.weight": (cfg["vocab_size"], h)}
    for i in range(cfg["num_hidden_layers"]):
        for k in layer_leaves(cfg, i):
            shapes[f"layers.{i}.{k}"] = block[k]
    shapes["norm.weight"] = (h,)
    shapes["lm_head.weight"] = (h, cfg["vocab_size"])
    return shapes


@functools.lru_cache(maxsize=4)
def _make_init(cfg_items):
    cfg = dict(cfg_items)
    dt = jnp.dtype(cfg["dtype"])
    shapes = leaf_shapes(cfg)
    std, router_std = cfg["initializer_range"], cfg["router_init_std"]

    def make(key):
        keys = jax.random.split(key, len(shapes))
        p = {}
        for k, (name, shape) in zip(keys, shapes.items()):
            if len(shape) == 1:
                p[name] = jnp.ones(shape, dt)               # a norm's gain
            elif name.endswith("mlp.gate.weight"):
                # the published MoEGate draws its weight kaiming-uniform
                # (a = sqrt(5)): U(-b, b) with b = 1/sqrt(fan_in), of
                # standard deviation b/sqrt(3); here b = sqrt(3) x the
                # configuration's `router_init_std`
                b = math.sqrt(3.0) * router_std
                p[name] = jax.random.uniform(k, shape, jnp.float32, -b, b
                                             ).astype(dt)
            else:
                p[name] = (jax.random.normal(k, shape, jnp.float32)
                           * std).astype(dt)
        return p

    return jax.jit(make)


def init_params(cfg, seed):
    """The weights of a run, from its seed, in one jitted call on the
    device, in the type the configuration stores them in."""
    return _make_init(scalars(cfg))(jax.random.PRNGKey(seed % (2 ** 31)))


# ------------------------------------------------------------- positions
def yarn_mscale(factor, mscale):
    return 1.0 if factor <= 1 else 0.1 * mscale * math.log(factor) + 1.0


def yarn_inv_freq(cfg):
    """The YaRN frequencies of the rotary dimensions, float64 numpy [d/2]:
    the dimensions that turn fewer than `beta_slow` times over the original
    context are interpolated (divided by `factor`), those that turn more
    than `beta_fast` times are kept, with a linear ramp between."""
    rs, d, base = cfg["rope_scaling"], cfg["qk_rope_head_dim"], \
        cfg["rope_theta"]
    f = base ** (-np.arange(0, d, 2, dtype=np.float64) / d)
    if not rs:
        return f

    def correction_dim(rotations):
        return d * math.log(rs["original_max_position_embeddings"]
                            / (rotations * 2 * math.pi)) / (2 * math.log(base))

    low = max(math.floor(correction_dim(rs["beta_fast"])), 0)
    high = min(math.ceil(correction_dim(rs["beta_slow"])), d - 1)
    ramp = np.clip((np.arange(d // 2, dtype=np.float64) - low)
                   / max(high - low, 1e-3), 0.0, 1.0)
    keep = 1.0 - ramp
    return f / rs["factor"] * (1.0 - keep) + f * keep


def rope_gain(cfg):
    """What the published code multiplies cos and sin by."""
    rs = cfg["rope_scaling"]
    if not rs:
        return 1.0
    return yarn_mscale(rs["factor"], rs["mscale"]) \
        / yarn_mscale(rs["factor"], rs["mscale_all_dim"])


def softmax_scale(cfg):
    scale = (cfg["qk_nope_head_dim"] + cfg["qk_rope_head_dim"]) ** -0.5
    rs = cfg["rope_scaling"]
    if rs and rs.get("mscale_all_dim"):
        m = yarn_mscale(rs["factor"], rs["mscale_all_dim"])
        scale = scale * m * m
    return scale


def rope(x, pos, cfg):
    """x [L, ..., d] at positions pos [L]: pairs (2i, 2i+1) turned, written
    first members first (see the module's docstring)."""
    inv = jnp.asarray(yarn_inv_freq(cfg), jnp.float32)
    ang = pos.astype(jnp.float32)[:, None] * inv[None, :]      # [L, d/2]
    shape = (x.shape[0],) + (1,) * (x.ndim - 2) + (inv.shape[0],)
    cos = (jnp.cos(ang) * rope_gain(cfg)).reshape(shape)
    sin = (jnp.sin(ang) * rope_gain(cfg)).reshape(shape)
    a, b = x[..., 0::2], x[..., 1::2]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], axis=-1)


# ------------------------------------------------------------ the layers
def rms_norm(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(jnp.square(x), -1, keepdims=True)
                             + eps) * w


def _row_block(n, most=512):
    """The largest divisor of n that is at most `most`."""
    return max(d for d in range(1, min(n, most) + 1) if n % d == 0)


def mla(p, x, cfg, prec):
    """Materialised multi-head latent attention over x [L, h], causal, the
    queries in blocks of rows (the scores of all rows at once are
    heads x L x L float32)."""
    L = x.shape[0]
    heads = cfg["num_attention_heads"]
    dn, dr, dv = (cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"],
                  cfg["v_head_dim"])
    rkv, eps = cfg["kv_lora_rank"], cfg["rms_norm_eps"]
    pos = jnp.arange(L)
    cq = rms_norm(mm("lh,hr->lr", x, p["self_attn.q_a_proj.weight"], prec),
                  p["self_attn.q_a_layernorm.weight"], eps)
    q = mm("lr,rk->lk", cq, p["self_attn.q_b_proj.weight"], prec
           ).reshape(L, heads, dn + dr)
    q = jnp.concatenate([q[..., :dn], rope(q[..., dn:], pos, cfg)], -1)
    kva = mm("lh,hr->lr", x, p["self_attn.kv_a_proj_with_mqa.weight"], prec)
    ckv = rms_norm(kva[:, :rkv], p["self_attn.kv_a_layernorm.weight"], eps)
    k_rope = rope(kva[:, rkv:], pos, cfg)                      # [L, dr]
    kv = mm("lr,rk->lk", ckv, p["self_attn.kv_b_proj.weight"], prec
            ).reshape(L, heads, dn + dv)
    k = jnp.concatenate(
        [kv[..., :dn], jnp.broadcast_to(k_rope[:, None], (L, heads, dr))], -1)
    v = kv[..., dn:]
    scale = softmax_scale(cfg)
    rb = _row_block(L)

    def rows(lo):
        qb = jax.lax.dynamic_slice_in_dim(q, lo, rb, axis=0)
        s = mm("lhd,mhd->hlm", qb, k, prec) * scale
        seen = pos[None, :] <= (lo + jnp.arange(rb))[:, None]
        prob = jax.nn.softmax(jnp.where(seen[None], s, -1e30), axis=-1)
        return mm("hlm,mhd->lhd", prob, v, prec)

    o = jax.lax.map(rows, jnp.arange(0, L, rb)).reshape(L, heads * dv)
    return mm("lk,kh->lh", o, p["self_attn.o_proj.weight"], prec)


def swiglu(x, gate, up, down, prec):
    return mm("lf,fh->lh", jax.nn.silu(mm("lh,hf->lf", x, gate, prec))
              * mm("lh,hf->lf", x, up, prec), down, prec)


def route(scores, cfg):
    """Combine weights [L, router_width] of the group-limited greedy
    selection over softmax scores [L, router_width]: the `topk_group` groups
    with the largest best score, among their experts the
    `num_experts_per_tok` largest scores, each weighted by its score times
    `routed_scaling_factor` (`norm_topk_prob` false: not renormalised)."""
    n, groups = scores.shape[-1], cfg["n_group"]
    best = scores.reshape(-1, groups, n // groups).max(-1)
    _, gi = jax.lax.top_k(best, cfg["topk_group"])
    open_ = jax.nn.one_hot(gi, groups, dtype=scores.dtype).sum(-2)
    masked = scores * jnp.repeat(open_, n // groups, axis=-1)
    w, ei = jax.lax.top_k(masked, cfg["num_experts_per_tok"])
    if cfg["norm_topk_prob"]:
        w = w / (w.sum(-1, keepdims=True) + 1e-20)
    w = w * cfg["routed_scaling_factor"]
    return (jax.nn.one_hot(ei, n, dtype=scores.dtype) * w[..., None]).sum(-2)


def selected(scores, cfg):
    """Which experts each token selects: bool [L, router_width]."""
    return route(scores, cfg) > 0


def router_scores(p, x, prec):
    return jax.nn.softmax(mm("lh,he->le", x, p["mlp.gate.weight"], prec), -1)


def moe(p, x, cfg, prec, offset=None, held=None):
    """The expert layer's share: routed over the router's whole width, the
    held experts' part of the sum, the shared experts whole."""
    offset = cfg["expert_offset"] if offset is None else offset
    held = cfg["n_routed_experts"] if held is None else held
    w = route(router_scores(p, x, prec), cfg)[:, offset:offset + held]

    def one(acc, e):
        gate, up, down, we = e
        return acc + we[:, None] * swiglu(x, gate, up, down, prec), None

    routed, _ = jax.lax.scan(one, jnp.zeros_like(x), (
        p["mlp.experts.gate_proj"], p["mlp.experts.up_proj"],
        p["mlp.experts.down_proj"], w.T))
    return routed + shared(p, x, prec)


def shared(p, x, prec):
    return swiglu(x, p["mlp.shared_experts.gate_proj.weight"],
                  p["mlp.shared_experts.up_proj.weight"],
                  p["mlp.shared_experts.down_proj.weight"], prec)


def block(p, x, cfg, prec, dense):
    p = f32(p)
    eps = cfg["rms_norm_eps"]
    x = x + mla(p, rms_norm(x, p["input_layernorm.weight"], eps), cfg, prec)
    y = rms_norm(x, p["post_attention_layernorm.weight"], eps)
    if dense:
        return x + swiglu(y, p["mlp.gate_proj.weight"],
                          p["mlp.up_proj.weight"],
                          p["mlp.down_proj.weight"], prec)
    return x + moe(p, y, cfg, prec)


def final_logits(p, x, cfg, prec):
    p = f32(p)
    return mm("lh,hv->lv", rms_norm(x, p["norm.weight"], cfg["rms_norm_eps"]),
              p["lm_head.weight"], prec)


# -------------------------------------------------------------- serving
_jitted = {}


def _jit(key, fn):
    if key not in _jitted:
        _jitted[key] = jax.jit(fn)
    return _jitted[key]


def _layer_params(cfg, params, i):
    return {k: params[f"layers.{i}.{k}"] for k in layer_leaves(cfg, i)}


def hidden_states(cfg, params, ids, prec="f32"):
    """The stream [L, h] after the last layer, of the plain forward over
    `ids`. One layer at a time; layers of one kind share one program."""
    key = scalars(cfg)
    x = params["embed_tokens.weight"][jnp.asarray(ids, jnp.int32)
                                      ].astype(jnp.float32)
    for i in range(cfg["num_hidden_layers"]):
        dense = is_dense(cfg, i)
        x = _jit(("block", key, prec, dense),
                 lambda p, x_, d=dense: block(p, x_, cfg, prec, d))(
            _layer_params(cfg, params, i), x)
    return x


def served_rows_logits(cfg, params, ids, first_row, rows, prec="f32"):
    """Logits [rows, vocab] of positions first_row .. first_row+rows-1 of
    the plain forward pass over `ids` ([T] token ids, padded at the end to
    any length: attention is causal and every other part acts on one
    position alone, so what follows a position cannot reach it)."""
    x = hidden_states(cfg, params, ids, prec)
    head = _jit(("head", scalars(cfg), prec, rows), lambda p, x_, lo:
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


def selection_differs(cfg, params, ids, prec):
    """Of the (position, expert layer) pairs of the forward over `ids`, the
    share whose selected set of experts under `prec` differs from float32's
    (each on its own stream): what rounding does to the routing."""
    key = scalars(cfg)

    def sets(prec_):
        x = params["embed_tokens.weight"][jnp.asarray(ids, jnp.int32)
                                          ].astype(jnp.float32)
        out = []
        for i in range(cfg["num_hidden_layers"]):
            dense = is_dense(cfg, i)
            p = _layer_params(cfg, params, i)
            if not dense:
                out.append(_jit(("selected", key, prec_), lambda p_, x_:
                                _selected_of(p_, x_, cfg, prec_))(p, x))
            x = _jit(("block", key, prec_, dense),
                     lambda p_, x_, d=dense: block(p_, x_, cfg, prec_, d))(
                p, x)
        return jnp.stack(out)

    return float(jnp.mean(jnp.any(sets("f32") != sets(prec), axis=-1)))


def _selected_of(p, x, cfg, prec):
    p = f32(p)
    eps = cfg["rms_norm_eps"]
    x = x + mla(p, rms_norm(x, p["input_layernorm.weight"], eps), cfg, prec)
    y = rms_norm(x, p["post_attention_layernorm.weight"], eps)
    return selected(router_scores(p, y, prec), cfg)
