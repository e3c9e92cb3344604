"""DeepSeek-V2-style decoder: multi-head latent attention (MLA), YaRN
rotary positions, RMSNorm, gated SiLU MLPs without biases, and a dropless
expert layer with group-limited routing and shared experts.

  * MLA caches ONE latent row a token a layer: `kv_lora_rank` values that
    every head's keys and values are projected from, and one rotary key of
    `qk_rope_head_dim` shared by all heads. Attention over that cache has
    two forms of the same function: MATERIALISED (project the latent rows
    up to per-head keys and values, then ordinary attention — cheap per
    query once a context's keys exist, what a prefill chunk wants) and
    ABSORBED (fold the up-projections into the query and the output, so
    the scores and the weighted sum run over the latent rows themselves —
    nothing per head is ever built, what a decode row wants). This file
    has the projections both forms share and the materialised full
    forward; the paged forms live in `ops/ragged_paged_attention.py`.
  * The expert layer scores by softmax over the router's whole width,
    keeps the `topk_group` groups whose best expert scores highest, takes
    the `num_experts_per_tok` best experts inside them, weights each by
    its score times `routed_scaling_factor` (not renormalised unless
    `norm_topk_prob`), drops no token and has no capacity. It is TOLD
    WHICH EXPERTS IT HOLDS (`experts_held` from `expert_offset`): it
    routes over all of them and adds only what held experts give, plus
    the shared experts whole — one chip's part of an expert-parallel
    layer, with no code standing in for the other chips or the exchange.
    `models/moe.py` (capacity-factor one-hot dispatch, drops on overflow,
    no shared expert) is a different layer and is left as it is.

Parameter names follow the published checkpoint's modules; a Linear's
weight is [in, out]; a layer's held experts are one parameter
[experts_held, in, out] for each of gate, up and down.
"""
import dataclasses
import math

import jax
from jax.experimental.pallas.ops.tpu.megablox.gmm import gmm
import jax.numpy as jnp
import numpy as np

from ..framework.core import Parameter, apply_op
from ..nn.layer_base import Layer

__all__ = ["DeepSeekV2Config", "DeepSeekV2", "DeepSeekV2MoE",
           "deepseek_v2_tiny", "yarn_inv_freq", "group_limited_route",
           "group_limited_topk", "moe_ffn", "held_expert_walk"]

HIGHEST = jax.lax.Precision.HIGHEST


@dataclasses.dataclass
class DeepSeekV2Config:
    family = "deepseek_v2"               # its entry in mla_decoder.FAMILIES
    q_lora_scale = kv_lora_scale = 1.0   # this family's MLA has no LoRA scales
    vocab_size: int = 102400
    hidden_size: int = 5120
    num_layers: int = 60
    num_heads: int = 128
    q_lora_rank: int = 1536
    kv_lora_rank: int = 512
    qk_nope_head_dim: int = 128
    qk_rope_head_dim: int = 64
    v_head_dim: int = 128
    intermediate_size: int = 12288       # the dense layers' MLP
    moe_intermediate_size: int = 1536    # one routed expert
    n_shared_experts: int = 2
    n_routed_experts: int = 160          # the router's width
    experts_held: int = 0                # 0 -> all of them
    expert_offset: int = 0               # the first expert held
    num_experts_per_tok: int = 6
    n_group: int = 8
    topk_group: int = 3
    routed_scaling_factor: float = 16.0
    norm_topk_prob: bool = False
    first_k_dense_replace: int = 1
    moe_layer_freq: int = 1
    rms_norm_eps: float = 1e-6
    rope_theta: float = 10000.0
    rope_scaling: dict = None            # the published "yarn" group
    max_seq_len: int = 163840
    dtype: str = "bfloat16"
    init_std: float = 0.02
    # the router is drawn uniform with this standard deviation; None is the
    # published code's own draw, 1/sqrt(3 x hidden_size) (kaiming-uniform)
    router_init_std: float = None

    def __post_init__(self):
        if self.router_init_std is None:
            self.router_init_std = (3.0 * self.hidden_size) ** -0.5
        if not self.experts_held:
            self.experts_held = self.n_routed_experts
        if self.n_routed_experts % self.n_group:
            raise ValueError("n_routed_experts must divide into n_group")
        if self.expert_offset + self.experts_held > self.n_routed_experts:
            raise ValueError(
                f"experts {self.expert_offset}..{self.expert_offset}+"
                f"{self.experts_held} lie outside the router's width "
                f"{self.n_routed_experts}")
        if self.qk_rope_head_dim % 2:
            raise ValueError("qk_rope_head_dim must be even")

    def is_dense(self, layer):
        return layer < self.first_k_dense_replace \
            or layer % self.moe_layer_freq != 0

    @property
    def latent_dim(self):
        """Values the cache holds a token a layer."""
        return self.kv_lora_rank + self.qk_rope_head_dim

    def attn_params(self):
        h, H = self.hidden_size, self.num_heads
        return (h * self.q_lora_rank + self.q_lora_rank
                + self.q_lora_rank * H * (self.qk_nope_head_dim
                                          + self.qk_rope_head_dim)
                + h * self.latent_dim + self.kv_lora_rank
                + self.kv_lora_rank * H * (self.qk_nope_head_dim
                                           + self.v_head_dim)
                + H * self.v_head_dim * h + 2 * h)

    def num_params(self):
        """Parameters HELD here (the held experts, not the router's
        width)."""
        h, fm = self.hidden_size, self.moe_intermediate_size
        n = 2 * self.vocab_size * h + h
        for i in range(self.num_layers):
            n += self.attn_params()
            if self.is_dense(i):
                n += 3 * h * self.intermediate_size
            else:
                n += h * self.n_routed_experts \
                    + 3 * h * fm * (self.experts_held
                                    + self.n_shared_experts)
        return n


def deepseek_v2_tiny(**kw):
    """A tiny preset for CPU tests: every mechanism, no published width."""
    d = dict(vocab_size=96, hidden_size=32, num_layers=3, num_heads=4,
             q_lora_rank=24, kv_lora_rank=16, qk_nope_head_dim=8,
             qk_rope_head_dim=4, v_head_dim=8, intermediate_size=48,
             moe_intermediate_size=16, n_shared_experts=2,
             n_routed_experts=16, num_experts_per_tok=3, n_group=4,
             topk_group=2, routed_scaling_factor=4.0, max_seq_len=256,
             dtype="float32", init_std=0.2, router_init_std=0.2,
             rope_scaling={"type": "yarn", "factor": 4, "beta_fast": 32,
                           "beta_slow": 1, "mscale": 0.707,
                           "mscale_all_dim": 0.707,
                           "original_max_position_embeddings": 64})
    d.update(kw)
    return DeepSeekV2Config(**d)


# ------------------------------------------------------------- positions
def _yarn_mscale(factor, mscale):
    return 1.0 if factor <= 1 else 0.1 * mscale * math.log(factor) + 1.0


def yarn_inv_freq(cfg):
    """Rotary frequencies [qk_rope_head_dim / 2], float64 numpy. Under
    YaRN a dimension that turns more than `beta_fast` times over the
    original context keeps its frequency, one that turns fewer than
    `beta_slow` times is interpolated (divided by `factor`), with a
    linear ramp over the dimensions between."""
    d, base, rs = cfg.qk_rope_head_dim, cfg.rope_theta, cfg.rope_scaling
    freq = base ** (-np.arange(0, d, 2, dtype=np.float64) / d)
    if not rs:
        return freq
    span = rs["original_max_position_embeddings"]

    def dim_of(turns):
        return d * math.log(span / (turns * 2 * math.pi)) \
            / (2 * math.log(base))

    low = max(math.floor(dim_of(rs["beta_fast"])), 0)
    high = min(math.ceil(dim_of(rs["beta_slow"])), d - 1)
    kept = 1.0 - np.clip((np.arange(d // 2) - low) / max(high - low, 1e-3),
                         0.0, 1.0)
    return freq / rs["factor"] * (1.0 - kept) + freq * kept


def rope_gain(cfg):
    """The factor on cos and sin: mscale(factor, mscale) over
    mscale(factor, mscale_all_dim) — 1 for the published values."""
    rs = cfg.rope_scaling
    if not rs:
        return 1.0
    return _yarn_mscale(rs["factor"], rs.get("mscale", 1.0)) \
        / _yarn_mscale(rs["factor"], rs.get("mscale_all_dim", 0.0))


def softmax_scale(cfg):
    scale = (cfg.qk_nope_head_dim + cfg.qk_rope_head_dim) ** -0.5
    rs = cfg.rope_scaling
    if rs and rs.get("mscale_all_dim"):
        scale *= _yarn_mscale(rs["factor"], rs["mscale_all_dim"]) ** 2
    return scale


def rope(x, pos, inv_freq, gain=1.0):
    """Rotate x [T, ..., d] to positions pos [T]: dimensions (2i, 2i+1)
    are pair i; the pairs' first members are written to the first half
    and their second members to the second, as the published code lays
    them out before its rotate_half. float32 inside, x's type out."""
    ang = pos.astype(jnp.float32)[:, None] * inv_freq[None, :]
    shape = (x.shape[0],) + (1,) * (x.ndim - 2) + (inv_freq.shape[0],)
    cos = (jnp.cos(ang) * gain).reshape(shape)
    sin = (jnp.sin(ang) * gain).reshape(shape)
    x32 = x.astype(jnp.float32)
    a, b = x32[..., 0::2], x32[..., 1::2]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin],
                           axis=-1).astype(x.dtype)


# ---------------------------------------------------------------- layers
def rms_norm(x, w, eps, gain=1.0):
    """RMSNorm in float32, x's type out; `gain` (a Python number) scales
    the result before it is rounded to that type."""
    x32 = x.astype(jnp.float32)
    y = x32 * jax.lax.rsqrt(jnp.mean(jnp.square(x32), -1, keepdims=True)
                            + eps)
    y = y * w.astype(jnp.float32)
    if gain != 1.0:
        y = y * gain
    return y.astype(x.dtype)


def _mm(x, w):
    return jnp.dot(x, w, preferred_element_type=jnp.float32).astype(x.dtype)


def swiglu(x, gate, up, down):
    g = jnp.dot(x, gate, preferred_element_type=jnp.float32)
    u = jnp.dot(x, up, preferred_element_type=jnp.float32)
    return _mm((jax.nn.silu(g) * u).astype(x.dtype), down)


def mla_project(w, y, pos, cfg, inv_freq):
    """What both forms of MLA share, for normed tokens y [T, h] at
    positions pos [T]: (q_nope [T, H, dn], q_rope [T, H, dr] rotated,
    latent [T, rank + dr] = the normed latent beside the rotated shared
    key — the row the cache holds). `w`: q_a, q_a_ln, q_b, kv_a, kv_a_ln.
    A config with LoRA scales (`q_lora_scale`, `kv_lora_scale`: 1 where
    the family has none) gets both parts of every query times the first and the
    normed latent times the second, so the keys' nope part and the values
    carry it, whichever form reads the row; the shared rotary key does
    not. Both are applied in float32, before the rounding the unscaled
    value would get anyway."""
    T, H = y.shape[0], cfg.num_heads
    dn, dr, r = cfg.qk_nope_head_dim, cfg.qk_rope_head_dim, cfg.kv_lora_rank
    gain = rope_gain(cfg)
    with jax.named_scope("mla_q"):
        cq = rms_norm(_mm(y, w["q_a"]), w["q_a_ln"], cfg.rms_norm_eps)
        if cfg.q_lora_scale == 1.0:
            q = _mm(cq, w["q_b"])
        else:
            q = (jnp.dot(cq, w["q_b"], preferred_element_type=jnp.float32)
                 * cfg.q_lora_scale).astype(cq.dtype)
        q = q.reshape(T, H, dn + dr)
        q_nope, q_rope = q[..., :dn], rope(q[..., dn:], pos, inv_freq, gain)
    kva = _mm(y, w["kv_a"])
    latent = jnp.concatenate(
        [rms_norm(kva[:, :r], w["kv_a_ln"], cfg.rms_norm_eps,
                  cfg.kv_lora_scale),
         rope(kva[:, r:], pos, inv_freq, gain)], axis=-1)
    return q_nope, q_rope, latent


def mla_materialised_full(w, y, pos, cfg, inv_freq):
    """Causal MLA of one whole sequence y [L, h] with no cache: the
    materialised form (the training/evaluation forward)."""
    L, H = y.shape[0], cfg.num_heads
    dn, r = cfg.qk_nope_head_dim, cfg.kv_lora_rank
    q_nope, q_rope, latent = mla_project(w, y, pos, cfg, inv_freq)
    kv = _mm(latent[:, :r], w["kv_b"]).reshape(L, H, -1)
    s = (jnp.einsum("lhd,mhd->hlm", q_nope, kv[..., :dn],
                    preferred_element_type=jnp.float32)
         + jnp.einsum("lhd,md->hlm", q_rope, latent[:, r:],
                      preferred_element_type=jnp.float32)) \
        * softmax_scale(cfg)
    seen = pos[None, :] <= pos[:, None]
    p = jax.nn.softmax(jnp.where(seen[None], s, -1e30), axis=-1)
    o = jnp.einsum("hlm,mhd->lhd", p.astype(y.dtype), kv[..., dn:],
                   preferred_element_type=jnp.float32).astype(y.dtype)
    return _mm(o.reshape(L, -1), w["o"])


def group_limited_topk(scores, cfg):
    """(weights [T, k], experts [T, k]) of the group-limited greedy
    selection over softmax scores [T, n_routed_experts]: group score = a
    group's largest score; the `topk_group` best groups stay open; among
    their experts the k = `num_experts_per_tok` largest scores are
    selected; a selected expert's weight is its score (over the selected
    sum if `norm_topk_prob`) times `routed_scaling_factor`."""
    n, g = cfg.n_routed_experts, cfg.n_group
    best = scores.reshape(-1, g, n // g).max(-1)
    _, gi = jax.lax.top_k(best, cfg.topk_group)
    open_ = jnp.zeros_like(best).at[
        jnp.arange(best.shape[0])[:, None], gi].set(1.0)
    masked = scores * jnp.repeat(open_, n // g, axis=-1)
    w, ei = jax.lax.top_k(masked, cfg.num_experts_per_tok)
    if cfg.norm_topk_prob:
        w = w / (jnp.sum(w, -1, keepdims=True) + 1e-20)
    return w * cfg.routed_scaling_factor, ei


def group_limited_route(scores, cfg):
    """`group_limited_topk` as combine weights [T, n_routed_experts]:
    every expert that is not selected has 0."""
    w, ei = group_limited_topk(scores, cfg)
    return jnp.zeros_like(scores).at[
        jnp.arange(scores.shape[0])[:, None], ei].set(w)


def _expert_tiles(rows, h, f, dtype):
    """(row tile, (k, n) tile of gate and up [h, f], (k, n) tile of down
    [f, h]) of the held experts' grouped products, from what the walk
    sees: `rows` pairs of `dtype`. A weight tile spans the whole
    contracted width, so consecutive row tiles of one expert find it in
    place, and as many columns (a multiple of 128 that divides the width,
    else the width) as keep it under 3 MiB. A few rows get the smallest
    tile the type's layout allows, since a hit expert then has a pair or
    two; many rows get 128."""
    item = jnp.dtype(dtype).itemsize

    def weight_tile(k, n):
        fits = [t for t in range(128, n + 1, 128)
                if n % t == 0 and k * t * item <= 3 << 20]
        return k, (fits[-1] if fits else n)

    tm = 128 if rows > 512 else 8 * (4 // item)
    return tm, weight_tile(h, f), weight_tile(f, h)


def held_expert_walk(w, x, cw, ei, held, offset, valid=None, layer=None):
    """The held experts' part of a routed sum, whatever router made the
    selection: tokens x [T, h], each with k selected columns `ei` [T, k]
    of combine weight `cw` [T, k]; this chip holds the experts `offset` ..
    `offset + held` (`w`: gate/up [held, h, f], down [held, f, h]; with
    `layer`: stacks [layers, held, ...] read at that layer). Of the
    (token, column) pairs only those of held experts are computed, and no
    pair is dropped: the pairs are sorted by expert, and each projection
    is ONE grouped product over the sorted rows (megablox `gmm`) whose
    kernel is handed the WHOLE stack as [layers x held, ...] and picks a
    row tile's expert, and the layer, in its block index map — a hit
    expert's matrix is read once, from where it lies, an expert no token
    selected is not read, and nothing is copied out of the stack; there
    is no capacity. A pair of any other column (another chip's expert, a
    column that is no expert at all) sorts behind the held ones and no
    tile reaches it. Returns (routed [T, h] float32, counts [held]: the
    pairs each held expert took), over `valid` tokens [T] (None: all)."""
    E, off, k = held, offset, ei.shape[1]
    T, h = x.shape
    A = T * k
    tm, up_tile, down_tile = _expert_tiles(A, h, w["gate"].shape[-1],
                                           x.dtype)
    M = -(-A // tm) * tm
    with jax.named_scope("moe_router"):
        here = (ei >= off) & (ei < off + E) & (cw > 0)
        if valid is not None:
            here = here & valid[:, None]
        # pairs sorted by held expert; those of other chips' experts last
        key = jnp.where(here, ei - off, E).reshape(A)
        order = jnp.argsort(key)
        token = (order // k).astype(jnp.int32)
        counts = jnp.zeros(E + 1, jnp.int32).at[key].add(1)[:E]
    with jax.named_scope("moe_experts"):
        # padded to whole row tiles
        xs = jnp.pad(x[token], ((0, M - A), (0, 0)))
        ws = jnp.pad(cw.reshape(A)[order], (0, M - A))
        # rows behind the last held pair are written by no tile
        live = (jnp.arange(M) < jnp.sum(counts))[:, None]
        sizes = counts
        if layer is not None:
            # this layer's experts among the groups of the whole stack
            sizes = jax.lax.dynamic_update_slice_in_dim(
                jnp.zeros(w["gate"].shape[0] * E, jnp.int32), counts,
                layer * E, 0)

        def product(a, name, tile):
            stack = w[name].reshape((-1,) + w[name].shape[-2:])
            return gmm(a, stack, sizes, preferred_element_type=jnp.float32,
                       tiling=(tm,) + tile,
                       interpret=jax.default_backend() == "cpu")

        g = product(xs, "gate", up_tile)
        u = product(xs, "up", up_tile)
        a = jnp.where(live, jax.nn.silu(g) * u * ws[:, None],
                      0.0).astype(x.dtype)
        out = jnp.where(live, product(a, "down", down_tile), 0.0)
        routed = jnp.zeros((T, h), jnp.float32).at[token].add(out[:A])
    return routed, counts


def moe_ffn(w, x, cfg, valid=None, layer=None):
    """One chip's part of the expert layer for tokens x [T, h]. `w`:
    router [h, n_routed_experts], gate/up [held, h, f], down [held, f, h]
    (with `layer`: stacks [layers, held, ...] read at that layer),
    s_gate/s_up/s_down (the shared experts as one MLP). Routed over the
    router's whole width in float32 (`group_limited_topk`); the held
    experts' pairs go through `held_expert_walk` (grouped products that
    read the stacks in place), the shared experts are added whole.
    Returns (y, assignments, experts_hit): the pairs held experts took
    and the held experts with at least one, both over `valid` tokens [T]
    (None: all)."""
    with jax.named_scope("moe_router"):
        logits = jnp.dot(x.astype(jnp.float32),
                         w["router"].astype(jnp.float32), precision=HIGHEST)
        cw, ei = group_limited_topk(jax.nn.softmax(logits, -1), cfg)
    routed, counts = held_expert_walk(w, x, cw, ei, cfg.experts_held,
                                      cfg.expert_offset, valid, layer)
    with jax.named_scope("moe_shared"):
        y = swiglu(x, w["s_gate"], w["s_up"], w["s_down"])
    return (routed.astype(x.dtype) + y, jnp.sum(counts),
            jnp.sum(counts > 0).astype(jnp.int32))


# ------------------------------------------------------------ the Layers
class _Leaves(Layer):
    """A module whose parameters are made by `make(name, shape, std)`."""

    def __init__(self, make, prefix, leaves):
        super().__init__()
        for name, (shape, std) in leaves.items():
            self.add_parameter(name, make(f"{prefix}.{name}", shape, std))


def _linear(make, prefix, n_in, n_out, std):
    return _Leaves(make, prefix, {"weight": ((n_in, n_out), std)})


def _norm(make, prefix, n):
    return _Leaves(make, prefix, {"weight": ((n,), None)})


class _MLP(Layer):
    def __init__(self, make, prefix, h, f, std):
        super().__init__()
        self.gate_proj = _linear(make, prefix + ".gate_proj", h, f, std)
        self.up_proj = _linear(make, prefix + ".up_proj", h, f, std)
        self.down_proj = _linear(make, prefix + ".down_proj", f, h, std)

    def leaves(self):
        return (self.gate_proj.weight, self.up_proj.weight,
                self.down_proj.weight)

    def forward(self, x):
        return apply_op(swiglu, x, *self.leaves())


class DeepSeekV2MoE(Layer):
    """The expert layer (see the module's docstring): `gate.weight`
    [h, n_routed_experts], `experts.{gate,up,down}_proj` over the
    `cfg.experts_held` experts from `cfg.expert_offset`, and
    `shared_experts`. `forward(x [T, h])` returns this chip's part."""

    def __init__(self, cfg, make=None, prefix="mlp"):
        super().__init__()
        make = make or _drawn(cfg)
        self.cfg = cfg
        h, fm, E = cfg.hidden_size, cfg.moe_intermediate_size, \
            cfg.experts_held
        self.gate = _linear(make, prefix + ".gate", h, cfg.n_routed_experts,
                            cfg.router_init_std)
        self.experts = _Leaves(make, prefix + ".experts", {
            "gate_proj": ((E, h, fm), cfg.init_std),
            "up_proj": ((E, h, fm), cfg.init_std),
            "down_proj": ((E, fm, h), cfg.init_std)})
        self.shared_experts = _MLP(make, prefix + ".shared_experts", h,
                                   fm * cfg.n_shared_experts, cfg.init_std)

    def leaves(self):
        e = self.experts
        return (self.gate.weight, e.gate_proj, e.up_proj, e.down_proj) \
            + self.shared_experts.leaves()

    def forward(self, x):
        return apply_op(lambda x, *w: moe_ffn(_moe_weights(w), x,
                                              self.cfg)[0],
                        x, *self.leaves())


def _moe_weights(w):
    return dict(zip(("router", "gate", "up", "down", "s_gate", "s_up",
                     "s_down"), w))


class _Attention(Layer):
    def __init__(self, make, prefix, cfg):
        super().__init__()
        h, H, std = cfg.hidden_size, cfg.num_heads, cfg.init_std
        dn, dr, dv = (cfg.qk_nope_head_dim, cfg.qk_rope_head_dim,
                      cfg.v_head_dim)
        rq, rkv = cfg.q_lora_rank, cfg.kv_lora_rank
        self.q_a_proj = _linear(make, prefix + ".q_a_proj", h, rq, std)
        self.q_a_layernorm = _norm(make, prefix + ".q_a_layernorm", rq)
        self.q_b_proj = _linear(make, prefix + ".q_b_proj", rq,
                                H * (dn + dr), std)
        self.kv_a_proj_with_mqa = _linear(
            make, prefix + ".kv_a_proj_with_mqa", h, rkv + dr, std)
        self.kv_a_layernorm = _norm(make, prefix + ".kv_a_layernorm", rkv)
        self.kv_b_proj = _linear(make, prefix + ".kv_b_proj", rkv,
                                 H * (dn + dv), std)
        self.o_proj = _linear(make, prefix + ".o_proj", H * dv, h, std)

    def leaves(self):
        return (self.q_a_proj.weight, self.q_a_layernorm.weight,
                self.q_b_proj.weight, self.kv_a_proj_with_mqa.weight,
                self.kv_a_layernorm.weight, self.kv_b_proj.weight,
                self.o_proj.weight)


class _Block(Layer):
    def __init__(self, make, i, cfg):
        super().__init__()
        p, h = f"layers.{i}", cfg.hidden_size
        self.cfg = cfg
        self.input_layernorm = _norm(make, p + ".input_layernorm", h)
        self.self_attn = _Attention(make, p + ".self_attn", cfg)
        self.post_attention_layernorm = _norm(
            make, p + ".post_attention_layernorm", h)
        self.mlp = _MLP(make, p + ".mlp", h, cfg.intermediate_size,
                        cfg.init_std) if cfg.is_dense(i) \
            else DeepSeekV2MoE(cfg, make, p + ".mlp")


def _drawn(cfg):
    """Parameters drawn from the config's own stds (norm gains 1)."""
    key = [jax.random.PRNGKey(0)]
    dt = jnp.dtype(cfg.dtype)

    def make(name, shape, std):
        if std is None:
            return Parameter(jnp.ones(shape, dt))
        key[0], k = jax.random.split(key[0])
        if name.endswith("mlp.gate.weight"):
            # the router, as the published MoEGate draws it: uniform
            # (kaiming, a = sqrt(5)) of standard deviation `std`
            b = math.sqrt(3.0) * std
            return Parameter(jax.random.uniform(k, shape, jnp.float32, -b, b
                                                ).astype(dt))
        return Parameter((jax.random.normal(k, shape, jnp.float32)
                          * std).astype(dt))
    return make


def _adopted(weights):
    """Parameters that ARE the given arrays (no draw, no copy); `weights`
    is emptied as they are taken."""
    def make(name, shape, std):
        if name not in weights:
            raise KeyError(f"no weight for parameter {name!r}")
        v = weights.pop(name)
        if tuple(v.shape) != tuple(shape):
            raise ValueError(f"{name}: shape {tuple(v.shape)} given, "
                             f"{tuple(shape)} expected")
        return Parameter(v)
    return make


class DeepSeekV2(Layer):
    """The decoder. `weights` ({parameter name: array}): adopt these
    arrays as the parameters instead of drawing fresh ones — the dict is
    emptied, nothing is copied (at the published widths a chip has no
    room for a drawn set beside a loaded one)."""

    def __init__(self, cfg: DeepSeekV2Config, weights=None):
        super().__init__()
        self.cfg = cfg
        make = _drawn(cfg) if weights is None else _adopted(weights)
        self.embed_tokens = _Leaves(make, "embed_tokens", {
            "weight": ((cfg.vocab_size, cfg.hidden_size), cfg.init_std)})
        from .. import nn
        self.layers = nn.LayerList(
            [_Block(make, i, cfg) for i in range(cfg.num_layers)])
        self.norm = _norm(make, "norm", cfg.hidden_size)
        self.lm_head = _linear(make, "lm_head", cfg.hidden_size,
                               cfg.vocab_size, cfg.init_std)
        if weights:
            raise ValueError(f"weights the model has no parameter for: "
                             f"{sorted(weights)}")

    def forward(self, input_ids):
        """Logits [B, L, vocab] (float32) of the full causal forward, MLA
        materialised, no cache."""
        cfg = self.cfg
        inv = jnp.asarray(yarn_inv_freq(cfg), jnp.float32)
        names = [n for n, _ in self.named_parameters()]

        def run(ids, *vals):
            p = dict(zip(names, vals))
            pos = jnp.arange(ids.shape[1])

            def seq(row):
                x = p["embed_tokens.weight"][row].astype(cfg.dtype)
                for i in range(cfg.num_layers):
                    x = _block_full(p, f"layers.{i}.", x, pos, cfg, inv,
                                    cfg.is_dense(i))
                x = rms_norm(x, p["norm.weight"], cfg.rms_norm_eps)
                return jnp.dot(x, p["lm_head.weight"],
                               preferred_element_type=jnp.float32)

            return jax.vmap(seq)(ids)

        return apply_op(run, input_ids,
                        *[v for _, v in self.named_parameters()])


def _block_full(p, pre, x, pos, cfg, inv, dense):
    a = {k: p[pre + "self_attn." + leaf] for k, leaf in ATTN_LEAVES.items()}
    y = rms_norm(x, p[pre + "input_layernorm.weight"], cfg.rms_norm_eps)
    x = x + mla_materialised_full(a, y, pos, cfg, inv)
    y = rms_norm(x, p[pre + "post_attention_layernorm.weight"],
                 cfg.rms_norm_eps)
    m = pre + "mlp."
    if dense:
        return x + swiglu(y, p[m + "gate_proj.weight"],
                          p[m + "up_proj.weight"], p[m + "down_proj.weight"])
    w = _moe_weights([p[m + "gate.weight"], p[m + "experts.gate_proj"],
                      p[m + "experts.up_proj"], p[m + "experts.down_proj"],
                      p[m + "shared_experts.gate_proj.weight"],
                      p[m + "shared_experts.up_proj.weight"],
                      p[m + "shared_experts.down_proj.weight"]])
    return x + moe_ffn(w, y, cfg)[0]


# ------------------------------------------- what the paged decoder walks
# an attention's weights by the keys `mla_project` and the paged forms
# read, as leaves under "<layer>.self_attn."
ATTN_LEAVES = {"q_a": "q_a_proj.weight", "q_a_ln": "q_a_layernorm.weight",
               "q_b": "q_b_proj.weight", "kv_a": "kv_a_proj_with_mqa.weight",
               "kv_a_ln": "kv_a_layernorm.weight",
               "kv_b": "kv_b_proj.weight", "o": "o_proj.weight"}


class Serving:
    """This family's entry in `serving.mla_decoder.FAMILIES`: the block's
    topology, its leaves, its cache entries and its counters, which
    `PagedMLADecoder` walks (that module's docstring has the contract).
    A layer is attention and then one MLP, dense or experts; one cache
    entry a layer, the latent row; an expert layer counts the pairs its
    held experts took and the held experts with at least one."""

    attention = "mla"
    cache_entries = 1
    counters = ("expert_assignments", "experts_hit")

    @staticmethod
    def inv_freq(cfg):
        return yarn_inv_freq(cfg)

    @staticmethod
    def entry_width(cfg):
        return cfg.latent_dim

    @classmethod
    def layer_entries(cls, cfg):
        return [cls.cache_entries] * cfg.num_layers

    @staticmethod
    def state_layers(cfg):
        """No layer keeps a per-slot state."""
        return [], None
    _KINDS = {
        "dense": {"gate": "mlp.gate_proj.weight",
                  "up": "mlp.up_proj.weight",
                  "down": "mlp.down_proj.weight"},
        "moe": {"router": "mlp.gate.weight",
                "gate": "mlp.experts.gate_proj",
                "up": "mlp.experts.up_proj",
                "down": "mlp.experts.down_proj",
                "s_gate": "mlp.shared_experts.gate_proj.weight",
                "s_up": "mlp.shared_experts.up_proj.weight",
                "s_down": "mlp.shared_experts.down_proj.weight"}}

    @staticmethod
    def runs(cfg):
        """[(kind, first layer, layers)]: each run of equal layers."""
        runs = []
        for i in range(cfg.num_layers):
            kind = "dense" if cfg.is_dense(i) else "moe"
            if runs and runs[-1][0] == kind:
                runs[-1][2] += 1
            else:
                runs.append([kind, i, 1])
        return [tuple(r) for r in runs]

    @classmethod
    def leaves(cls, kind):
        """{key: leaf under "layers.<i>."} of a layer of `kind`."""
        attn = {k: "self_attn." + v for k, v in ATTN_LEAVES.items()}
        return dict({"ln1": "input_layernorm.weight", **attn,
                     "ln2": "post_attention_layernorm.weight"},
                    **cls._KINDS[kind])

    @staticmethod
    def whole(kind):
        """The keys read by (layer, expert) inside the walk, so that an
        expert no token selected is not read at all; every other weight
        is the layer scan's per-layer slice."""
        return ("gate", "up", "down") if kind == "moe" else ()

    @staticmethod
    def block(cfg, kind, x, wl, seg, ri, attend, valid):
        """One layer over the stream x [T, h]. `wl`: the layer's sliced
        weights, `seg`: its run's stacks (for `whole` keys, read at `ri`);
        `attend(j, y, w)`: attention j of the layer over normed y through
        its own cache entry; `valid` [T]: the real tokens. Returns (x, the
        layer's counts in `counters`' order, or () if it counts none)."""
        eps = cfg.rms_norm_eps
        x = x + attend(0, rms_norm(x, wl["ln1"], eps), wl)
        y = rms_norm(x, wl["ln2"], eps)
        if kind == "dense":
            with jax.named_scope("mlp"):
                return x + swiglu(y, wl["gate"], wl["up"], wl["down"]), ()
        w = dict(wl, **{k: seg[k] for k in Serving.whole(kind)})
        out, assigned, hit = moe_ffn(w, y, cfg, valid=valid, layer=ri)
        return x + out, (assigned, hit)
