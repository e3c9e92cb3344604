"""Plain reference of the LongCat-Flash decoder as one chip of a deployment
holds it: float32 `jax.numpy` at `highest`, a full causal forward with no
cache, no kernels, no batching tricks. Imports nothing of the program.

One layer, as the published modeling code (`transformers`,
`models/longcat_flash`, `LongcatFlashDecoderLayer.forward`) computes it,
for input x, RMSNorm N (eps `rms_norm_eps`, gain only), all Linears
without bias:

    a0 = x  + MLA_0(N_in0(x))
    u  = N_post0(a0)
    s  = MoE(u)                          # the shortcut branch leaves here
    b0 = a0 + MLP_0(u)                   # dense SwiGLU, `ffn_hidden_size`
    a1 = b0 + MLA_1(N_in1(b0))
    b1 = a1 + MLP_1(N_post1(a1))
    out = b1 + s                         # and rejoins here

then a final RMSNorm and an untied head.

MLA (materialised form only): `q = q_b(N(q_a(y)))` split by head into nope
and rope parts, BOTH times `mla_scale_q_lora` = (hidden / q_lora_rank)^0.5;
`kv_a_proj_with_mqa(y)` split into a latent of `kv_lora_rank` and one
rotary key all heads share; the normed latent times `mla_scale_kv_lora` =
(hidden / kv_lora_rank)^0.5 goes through `kv_b_proj` to every head's nope
keys and values (so both carry the scale; the rotary key does not); plain
rotary positions at `rope_theta` (no scaling); softmax scale (nope +
rope)^-0.5; causal.

MoE: `p = softmax(u W_r)` in float32 over ALL `router_width` columns; the
`moe_topk` largest of `p + e_score_correction_bias` are selected; a
selected column i weighs `w_i = p_i x routed_scaling_factor` (the bias is
in the selection alone; nothing is renormalised). The first `router_width
- zero_expert_num` columns are SwiGLU experts of `expert_ffn_hidden_size`,
the last `zero_expert_num` identity experts: `MoE(u) = sum_{i real} w_i
E_i(u) + (sum_{i identity} w_i) u`. No shared expert, no groups, no
capacity, no dropped pair.

The chip's share: `n_routed_experts` of the configuration is the number of
experts HELD here (`expert_offset` .. `expert_offset + n_routed_experts` of
the router's real-expert columns); the layer routes over all of the
router's width and adds what held experts give and the identity sum whole
(an identity expert has no weights and lives where its token lives). What
the absent chips' experts would add is left out, and that partial result
goes on. The vocabulary is the slice the configuration states.

Departures from the published code, all of rounding or layout and none of
mathematics: (1) everything float32 (the published model computes in
bfloat16, its router in float32); (2) the rotary pairing: dimensions (2i,
2i+1) of the rotary ones are pair i, the result written with the pairs'
first members in the first half (the published `apply_rotary_pos_emb_
interleave` de-interleaves before its `rotate_half`); queries and keys
alike, so scores do not depend on it; (3) a layer's held experts are one
leaf [held, in, out] for each of gate, up and down, and a Linear's weight
is [in, out]; (4) `e_score_correction_bias` is a float32 leaf (a buffer
there), zeros in a fresh model; (5) the weights are drawn by JAX's `rbg`
generator (the seed's draw is this file's to define; it is several times
faster on the chip than the default at 5 G values).

Parameters are one flat dict, named as the published modules are
(`layers.<i>.self_attn.<j>.q_a_proj.weight`, `layers.<i>.mlps.<j>.
gate_proj.weight`, `layers.<i>.mlp.router.classifier.weight`, ...).
`q_b_proj`'s columns are laid out [heads, nope + rope], `kv_b_proj`'s
[heads, nope + v], `kv_a_proj_with_mqa`'s [latent | rope], as published.

At the published widths one layer's parameters in float32 are 5 GB beside
10 GB of bfloat16 weights, so the serving comparison runs a layer in five
programs (attention, the expert branch, a dense MLP, twice two of them),
each converting only the leaves it reads.
"""
import functools

import jax
import jax.numpy as jnp
import numpy as np

from .common import f32, mm, scalars
from .deepseek_v2 import _jit, _row_block, rms_norm, swiglu

_ATTN = ("q_a_proj.weight", "q_a_layernorm.weight", "q_b_proj.weight",
         "kv_a_proj_with_mqa.weight", "kv_a_layernorm.weight",
         "kv_b_proj.weight", "o_proj.weight")
_MLP = ("gate_proj.weight", "up_proj.weight", "down_proj.weight")
_MOE = ("mlp.router.classifier.weight", "mlp.router.e_score_correction_bias",
        "mlp.experts.gate_proj", "mlp.experts.up_proj",
        "mlp.experts.down_proj")
BIAS = "mlp.router.e_score_correction_bias"


def real_experts(cfg):
    """The router's SwiGLU columns (the identity ones follow them)."""
    return cfg["router_width"] - cfg["zero_expert_num"]


def layer_leaves():
    out = []
    for j in (0, 1):
        out.append(f"input_layernorm.{j}.weight")
        out += [f"self_attn.{j}.{k}" for k in _ATTN]
        out.append(f"post_attention_layernorm.{j}.weight")
        out += [f"mlps.{j}.{k}" for k in _MLP]
    return tuple(out) + _MOE


def leaf_shapes(cfg):
    h, heads = cfg["hidden_size"], cfg["num_attention_heads"]
    dn, dr, dv = (cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"],
                  cfg["v_head_dim"])
    rq, rkv = cfg["q_lora_rank"], cfg["kv_lora_rank"]
    f, fe = cfg["ffn_hidden_size"], cfg["expert_ffn_hidden_size"]
    held, width = cfg["n_routed_experts"], cfg["router_width"]
    attn = {"q_a_proj.weight": (h, rq), "q_a_layernorm.weight": (rq,),
            "q_b_proj.weight": (rq, heads * (dn + dr)),
            "kv_a_proj_with_mqa.weight": (h, rkv + dr),
            "kv_a_layernorm.weight": (rkv,),
            "kv_b_proj.weight": (rkv, heads * (dn + dv)),
            "o_proj.weight": (heads * dv, h)}
    mlp = {"gate_proj.weight": (h, f), "up_proj.weight": (h, f),
           "down_proj.weight": (f, h)}
    block = {"mlp.router.classifier.weight": (h, width), BIAS: (width,),
             "mlp.experts.gate_proj": (held, h, fe),
             "mlp.experts.up_proj": (held, h, fe),
             "mlp.experts.down_proj": (held, fe, h)}
    for j in (0, 1):
        block[f"input_layernorm.{j}.weight"] = (h,)
        block[f"post_attention_layernorm.{j}.weight"] = (h,)
        block.update({f"self_attn.{j}.{k}": v for k, v in attn.items()})
        block.update({f"mlps.{j}.{k}": v for k, v in mlp.items()})
    shapes = {"embed_tokens.weight": (cfg["vocab_size"], h)}
    for i in range(cfg["num_layers"]):
        for k in layer_leaves():
            shapes[f"layers.{i}.{k}"] = block[k]
    shapes["norm.weight"] = (h,)
    shapes["lm_head.weight"] = (h, cfg["vocab_size"])
    return shapes


@functools.lru_cache(maxsize=4)
def _make_init(cfg_items):
    cfg = dict(cfg_items)
    dt = jnp.dtype(cfg["dtype"])
    shapes = leaf_shapes(cfg)
    std = cfg["initializer_range"]

    def make(key):
        keys = jax.random.split(key, len(shapes))
        p = {}
        for k, (name, shape) in zip(keys, shapes.items()):
            if name.endswith(BIAS):
                p[name] = jnp.zeros(shape, jnp.float32)     # the buffer
            elif len(shape) == 1:
                p[name] = jnp.ones(shape, dt)               # a norm's gain
            else:
                # every matrix, the router's classifier among them, at
                # `initializer_range` (the published _init_weights)
                p[name] = (jax.random.normal(k, shape, jnp.float32)
                           * std).astype(dt)
        return p

    return jax.jit(make)


def init_params(cfg, seed):
    """The weights of a run, from its seed, in one jitted call on the
    device, in the type the configuration stores them in."""
    return _make_init(scalars(cfg))(
        jax.random.key(seed % (2 ** 31), impl="rbg"))


# ------------------------------------------------------------- positions
def inv_freq(cfg):
    d = cfg["qk_rope_head_dim"]
    return cfg["rope_theta"] ** (-np.arange(0, d, 2, dtype=np.float64) / d)


def rope(x, pos, cfg):
    """x [L, ..., d] at positions pos [L]: pairs (2i, 2i+1) turned, written
    first members first (see the module's docstring)."""
    inv = jnp.asarray(inv_freq(cfg), jnp.float32)
    ang = pos.astype(jnp.float32)[:, None] * inv[None, :]      # [L, d/2]
    shape = (x.shape[0],) + (1,) * (x.ndim - 2) + (inv.shape[0],)
    cos, sin = jnp.cos(ang).reshape(shape), jnp.sin(ang).reshape(shape)
    a, b = x[..., 0::2], x[..., 1::2]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], axis=-1)


# ------------------------------------------------------------ the layers
def lora_scales(cfg):
    h = cfg["hidden_size"]
    return ((h / cfg["q_lora_rank"]) ** 0.5 if cfg["mla_scale_q_lora"]
            else 1.0,
            (h / cfg["kv_lora_rank"]) ** 0.5 if cfg["mla_scale_kv_lora"]
            else 1.0)


def mla(p, x, cfg, prec):
    """Materialised multi-head latent attention over normed x [L, h],
    causal, the queries in blocks of rows. `p`: one attention's leaves by
    their names under `self_attn.<j>.`, float32."""
    L = x.shape[0]
    heads = cfg["num_attention_heads"]
    dn, dr, dv = (cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"],
                  cfg["v_head_dim"])
    rkv, eps = cfg["kv_lora_rank"], cfg["rms_norm_eps"]
    q_scale, kv_scale = lora_scales(cfg)
    pos = jnp.arange(L)
    cq = rms_norm(mm("lh,hr->lr", x, p["q_a_proj.weight"], prec),
                  p["q_a_layernorm.weight"], eps)
    q = mm("lr,rk->lk", cq, p["q_b_proj.weight"], prec
           ).reshape(L, heads, dn + dr) * q_scale
    q = jnp.concatenate([q[..., :dn], rope(q[..., dn:], pos, cfg)], -1)
    kva = mm("lh,hr->lr", x, p["kv_a_proj_with_mqa.weight"], prec)
    ckv = rms_norm(kva[:, :rkv], p["kv_a_layernorm.weight"], eps) * kv_scale
    k_rope = rope(kva[:, rkv:], pos, cfg)                      # [L, dr]
    kv = mm("lr,rk->lk", ckv, p["kv_b_proj.weight"], prec
            ).reshape(L, heads, dn + dv)
    k = jnp.concatenate(
        [kv[..., :dn], jnp.broadcast_to(k_rope[:, None], (L, heads, dr))], -1)
    v = kv[..., dn:]
    scale = (dn + dr) ** -0.5
    rb = _row_block(L)

    def rows(lo):
        qb = jax.lax.dynamic_slice_in_dim(q, lo, rb, axis=0)
        s = mm("lhd,mhd->hlm", qb, k, prec) * scale
        seen = pos[None, :] <= (lo + jnp.arange(rb))[:, None]
        prob = jax.nn.softmax(jnp.where(seen[None], s, -1e30), axis=-1)
        return mm("hlm,mhd->lhd", prob, v, prec)

    o = jax.lax.map(rows, jnp.arange(0, L, rb)).reshape(L, heads * dv)
    return mm("lk,kh->lh", o, p["o_proj.weight"], prec)


def router_scores(p, u, prec):
    """softmax over all the router's columns, [L, router_width]."""
    return jax.nn.softmax(
        mm("lh,he->le", u, p["mlp.router.classifier.weight"], prec), -1)


def selected(scores, bias, cfg):
    """Which columns each token selects: bool [L, router_width], the
    `moe_topk` columns with the largest `scores + bias`."""
    _, ei = jax.lax.top_k(scores + bias, cfg["moe_topk"])
    return jax.nn.one_hot(ei, scores.shape[-1], dtype=jnp.int32).sum(-2) > 0


def route(scores, bias, cfg):
    """Combine weights [L, router_width]: a selected column at its score
    times `routed_scaling_factor`, every other column 0."""
    return jnp.where(selected(scores, bias, cfg),
                     scores * cfg["routed_scaling_factor"], 0.0)


def moe(p, u, cfg, prec, offset=None, held=None, identity=True):
    """The expert branch's share: routed over the router's whole width,
    the held experts' part of the sum, and (`identity`) the identity
    experts' part, which every chip computes alike for its own tokens.
    `p`: the layer's `mlp.*` leaves; the experts' leaves hold the experts
    from `offset` on (the first `held` of them are used) and may be any
    precision (each is converted as it is used)."""
    offset = cfg["expert_offset"] if offset is None else offset
    held = cfg["n_routed_experts"] if held is None else held
    w = route(router_scores(f32({k: p[k] for k in _MOE[:1]}), u, prec),
              p[BIAS].astype(jnp.float32), cfg)

    def one(acc, e):
        gate, up, down, we = f32(e)
        return acc + we[:, None] * swiglu(u, gate, up, down, prec), None

    out, _ = jax.lax.scan(one, jnp.zeros_like(u), (
        p["mlp.experts.gate_proj"][:held], p["mlp.experts.up_proj"][:held],
        p["mlp.experts.down_proj"][:held], w[:, offset:offset + held].T))
    if identity:
        out = out + u * jnp.sum(w[:, real_experts(cfg):], -1, keepdims=True)
    return out


def _sub(p, prefix):
    return {k[len(prefix):]: v for k, v in p.items() if k.startswith(prefix)}


def attn_part(p, x, cfg, prec, j):
    """x + MLA_j(N_inj(x)); `p`: the layer's leaves."""
    a = f32(_sub(p, f"self_attn.{j}."))
    return x + mla(a, rms_norm(x, p[f"input_layernorm.{j}.weight"
                                    ].astype(jnp.float32),
                               cfg["rms_norm_eps"]), cfg, prec)


def post_norm(p, x, cfg, j):
    return rms_norm(x, p[f"post_attention_layernorm.{j}.weight"
                         ].astype(jnp.float32), cfg["rms_norm_eps"])


def mlp_part(p, x, y, prec, j):
    """x + MLP_j(y)."""
    m = f32(_sub(p, f"mlps.{j}."))
    return x + swiglu(y, m["gate_proj.weight"], m["up_proj.weight"],
                      m["down_proj.weight"], prec)


def block(p, x, cfg, prec, **share):
    """One double layer over x [L, h] (the module's docstring); `share`:
    `moe`'s offset, held, identity."""
    a0 = attn_part(p, x, cfg, prec, 0)
    u = post_norm(p, a0, cfg, 0)
    s = moe(p, u, cfg, prec, **share)
    b0 = mlp_part(p, a0, u, prec, 0)
    a1 = attn_part(p, b0, cfg, prec, 1)
    b1 = mlp_part(p, a1, post_norm(p, a1, cfg, 1), prec, 1)
    return b1 + s


def final_logits(p, x, cfg, prec):
    p = f32(p)
    return mm("lh,hv->lv", rms_norm(x, p["norm.weight"], cfg["rms_norm_eps"]),
              p["lm_head.weight"], prec)


# -------------------------------------------------------------- serving
def _layer_params(params, i, only=None):
    return {k: params[f"layers.{i}.{k}"] for k in layer_leaves()
            if only is None or k.startswith(only)}


def _layer_in_parts(cfg, params, i, x, prec, router=None):
    """`block` of layer i as five programs, each handed (and converting)
    only the leaves it reads. `router(p, u)` is called on the expert
    branch's input where given."""
    key = scalars(cfg)

    def attn(j, x):
        return _jit(("attn", key, prec, j), lambda p, x_: attn_part(
            p, x_, cfg, prec, j))(
            dict(_layer_params(params, i, f"self_attn.{j}."),
                 **_layer_params(params, i, f"input_layernorm.{j}.")),
            x)

    def mlp(j, x, y):
        return _jit(("mlp", key, prec, j), lambda p, x_, y_: mlp_part(
            p, x_, y_, prec, j))(
            _layer_params(params, i, f"mlps.{j}."), x, y)

    def norm(j, x):
        return _jit(("post_norm", key, j), lambda p, x_: post_norm(
            p, x_, cfg, j))(
            _layer_params(params, i, f"post_attention_layernorm.{j}."),
            x)

    a0 = attn(0, x)
    u = norm(0, a0)
    pm = _layer_params(params, i, "mlp.")
    if router is not None:
        router(pm, u)
    s = _jit(("moe", key, prec), lambda p, u_: moe(p, u_, cfg, prec))(pm, u)
    b0 = mlp(0, a0, u)
    a1 = attn(1, b0)
    b1 = mlp(1, a1, norm(1, a1))
    return b1 + s


def hidden_states(cfg, params, ids, prec="f32", router=None):
    """The stream [L, h] after the last layer, of the plain forward over
    `ids`: a layer at a time, a layer in five programs that all layers
    share."""
    x = params["embed_tokens.weight"][jnp.asarray(ids, jnp.int32)
                                      ].astype(jnp.float32)
    for i in range(cfg["num_layers"]):
        x = _layer_in_parts(cfg, params, i, x, prec, router)
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
    """Of the (position, layer) pairs of the forward over `ids`, the share
    whose selected set of columns under `prec` differs from float32's
    (each on its own stream): what rounding does to the routing."""
    key = scalars(cfg)

    def sets(prec_):
        out = []

        def look(pm, u):
            out.append(_jit(("selected", key, prec_), lambda p, u_: selected(
                router_scores(f32({k: p[k] for k in _MOE[:1]}), u_, prec_),
                p[BIAS].astype(jnp.float32), cfg))(pm, u))

        hidden_states(cfg, params, ids, prec_, router=look)
        return jnp.stack(out)

    return float(jnp.mean(jnp.any(sets("f32") != sets(prec), axis=-1)))
