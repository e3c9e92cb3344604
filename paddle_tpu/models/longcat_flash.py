"""LongCat-Flash-style decoder: a DOUBLE layer of two latent attentions
(MLA) and two dense gated MLPs, with a shortcut-connected expert branch
that leaves the chain after the first attention and rejoins at the layer's
end, and a dropless softmax-top-k router whose last columns are
zero-compute (identity) experts.

One layer, for input x, RMSNorm N (gain only), all Linears without bias:

    a0 = x  + MLA_0(N_in0(x))
    u  = N_post0(a0)
    s  = MoE(u)                          # the shortcut branch leaves here
    b0 = a0 + MLP_0(u)
    a1 = b0 + MLA_1(N_in1(b0))
    b1 = a1 + MLP_1(N_post1(a1))
    out = b1 + s                         # and rejoins here

`s` depends on nothing after `u`, so a compiler may run the expert branch
beside MLP_0 -> MLA_1 -> MLP_1; nothing here orders them.

  * MLA is `models.deepseek_v2.mla_project`'s, with the two LoRA scales:
    both parts of every query times (hidden / q_lora_rank)^0.5, the normed
    latent times (hidden / kv_lora_rank)^0.5 (so the cached row carries
    it, and with it the keys' nope part and the values of both paged
    forms). Plain rotary positions (no scaling), DeepSeek's pairing. Each
    of a layer's two attentions has its own cache entry.
  * The router scores `p = softmax(u W_r)` in float32 over ALL its
    columns: `n_routed_experts` SwiGLU experts and then `zero_expert_num`
    identity experts. It selects the `moe_topk` largest of `p +
    e_score_correction_bias` (a buffer; it moves the selection and never
    a weight) and weighs a selected column by `p x routed_scaling_factor`,
    not renormalised; no groups, no shared expert, no capacity, no
    dropped pair. `MoE(u) = sum_{i real, selected} w_i E_i(u) +
    (sum_{i identity, selected} w_i) u`.
  * The expert layer is TOLD WHICH EXPERTS IT HOLDS (`experts_held` from
    `expert_offset`): it routes over every column, computes held experts'
    pairs through `deepseek_v2.held_expert_walk` (the one walk both
    families use) and adds the identity sum for every token: an identity
    expert has no weights and lives where its token lives. What the other
    chips' experts would add is left out; nothing stands in for them. An
    identity pair costs one elementwise pass `u x sum(w)`: to the walk it
    is a column this chip does not hold.

Parameter names follow the published modeling code's modules
(`layers.<i>.self_attn.<j>.q_a_proj.weight`, `layers.<i>.mlps.<j>...`,
`layers.<i>.mlp.router.classifier.weight`); a Linear's weight is [in,
out]; a layer's held experts are one parameter [experts_held, in, out]
for each of gate, up and down.
"""
import dataclasses

import jax
import jax.numpy as jnp

from ..framework.core import Parameter, apply_op
from ..nn.layer_base import Layer
from .deepseek_v2 import (ATTN_LEAVES, HIGHEST, _Attention, _adopted, _drawn,
                          _Leaves, _linear, _MLP, _norm, held_expert_walk,
                          mla_materialised_full, rms_norm, swiglu,
                          yarn_inv_freq)
from .deepseek_v2 import Serving as _LatentServing

__all__ = ["LongCatFlashConfig", "LongCatFlash", "longcat_flash_tiny",
           "zero_expert_route", "longcat_moe", "longcat_block"]


@dataclasses.dataclass
class LongCatFlashConfig:
    family = "longcat_flash"             # its entry in mla_decoder.FAMILIES
    vocab_size: int = 131072
    hidden_size: int = 6144
    num_layers: int = 28                 # double layers
    num_heads: int = 64
    q_lora_rank: int = 1536
    kv_lora_rank: int = 512
    qk_nope_head_dim: int = 128
    qk_rope_head_dim: int = 64
    v_head_dim: int = 128
    mla_scale_q_lora: bool = True
    mla_scale_kv_lora: bool = True
    ffn_hidden_size: int = 12288         # each of a layer's two dense MLPs
    expert_ffn_hidden_size: int = 2048   # one routed expert
    n_routed_experts: int = 512          # the router's SwiGLU columns
    zero_expert_num: int = 256           # its identity columns, after them
    experts_held: int = 0                # 0 -> all of them
    expert_offset: int = 0               # the first expert held
    moe_topk: int = 12
    routed_scaling_factor: float = 6.0
    rms_norm_eps: float = 1e-5
    rope_theta: float = 1e7
    rope_scaling: dict = None            # the published config has none
    max_seq_len: int = 131072
    dtype: str = "bfloat16"
    init_std: float = 0.02

    def __post_init__(self):
        if not self.experts_held:
            self.experts_held = self.n_routed_experts
        if self.expert_offset + self.experts_held > self.n_routed_experts:
            raise ValueError(
                f"experts {self.expert_offset}..{self.expert_offset}+"
                f"{self.experts_held} lie outside the router's "
                f"{self.n_routed_experts} expert columns")
        if self.moe_topk > self.router_width:
            raise ValueError("moe_topk is wider than the router")
        if self.qk_rope_head_dim % 2:
            raise ValueError("qk_rope_head_dim must be even")

    @property
    def router_width(self):
        return self.n_routed_experts + self.zero_expert_num

    @property
    def latent_dim(self):
        """Values the cache holds a token an attention."""
        return self.kv_lora_rank + self.qk_rope_head_dim

    @property
    def q_lora_scale(self):
        return (self.hidden_size / self.q_lora_rank) ** 0.5 \
            if self.mla_scale_q_lora else 1.0

    @property
    def kv_lora_scale(self):
        return (self.hidden_size / self.kv_lora_rank) ** 0.5 \
            if self.mla_scale_kv_lora else 1.0

    def attn_params(self):
        h, H = self.hidden_size, self.num_heads
        return (h * self.q_lora_rank + self.q_lora_rank
                + self.q_lora_rank * H * (self.qk_nope_head_dim
                                          + self.qk_rope_head_dim)
                + h * self.latent_dim + self.kv_lora_rank
                + self.kv_lora_rank * H * (self.qk_nope_head_dim
                                           + self.v_head_dim)
                + H * self.v_head_dim * h)

    def num_params(self):
        """Parameters HELD here (the held experts, not the router's
        width), the norms' gains and the router's bias buffer among
        them."""
        h = self.hidden_size
        layer = (2 * (self.attn_params() + 2 * h
                      + 3 * h * self.ffn_hidden_size)
                 + h * self.router_width + self.router_width
                 + 3 * h * self.expert_ffn_hidden_size * self.experts_held)
        return 2 * self.vocab_size * h + h + self.num_layers * layer


def longcat_flash_tiny(**kw):
    """A tiny preset for CPU tests: every mechanism, no published width."""
    d = dict(vocab_size=96, hidden_size=32, num_layers=2, num_heads=4,
             q_lora_rank=24, kv_lora_rank=16, qk_nope_head_dim=8,
             qk_rope_head_dim=4, v_head_dim=8, ffn_hidden_size=48,
             expert_ffn_hidden_size=16, n_routed_experts=8,
             zero_expert_num=4, moe_topk=3, routed_scaling_factor=3.0,
             max_seq_len=256, dtype="float32", init_std=0.2)
    d.update(kw)
    return LongCatFlashConfig(**d)


# ------------------------------------------------------------ the router
def zero_expert_route(logits, bias, cfg):
    """(weights [T, k], columns [T, k]) of router logits [T, router_width]
    (float32): p = softmax over ALL columns; the k = `moe_topk` largest of
    p + bias are selected; a selected column weighs p x
    `routed_scaling_factor` (the bias is in the selection alone; nothing
    is renormalised). Columns from `n_routed_experts` on are identity
    experts."""
    p = jax.nn.softmax(logits, -1)
    _, ei = jax.lax.top_k(p + bias.astype(p.dtype), cfg.moe_topk)
    cw = jnp.take_along_axis(p, ei, axis=-1) * cfg.routed_scaling_factor
    return cw, ei


def longcat_moe(w, u, cfg, valid=None, layer=None):
    """One chip's part of the expert branch for tokens u [T, h]. `w`:
    router [h, router_width], bias [router_width], gate/up [held, h, f],
    down [held, f, h] (with `layer`: stacks [layers, held, ...] read at
    that layer, in place, by `held_expert_walk`'s grouped products).
    Returns (s [T, h], (assignments, experts_hit,
    zero_assignments)): the pairs held experts took, the held experts
    with at least one, and the identity pairs selected, all over `valid`
    tokens [T] (None: all)."""
    with jax.named_scope("moe_router"):
        logits = jnp.dot(u.astype(jnp.float32),
                         w["router"].astype(jnp.float32), precision=HIGHEST)
        cw, ei = zero_expert_route(logits, w["bias"], cfg)
    routed, counts = held_expert_walk(w, u, cw, ei, cfg.experts_held,
                                      cfg.expert_offset, valid, layer)
    with jax.named_scope("moe_zero"):
        is_zero = ei >= cfg.n_routed_experts
        share = jnp.sum(jnp.where(is_zero, cw, 0.0), -1, keepdims=True)
        s = (routed + u.astype(jnp.float32) * share).astype(u.dtype)
        if valid is not None:
            is_zero = is_zero & valid[:, None]
    return s, (jnp.sum(counts), jnp.sum(counts > 0).astype(jnp.int32),
               jnp.sum(is_zero).astype(jnp.int32))


# ------------------------------------------------------------- the block
_MLP_LEAVES = {"gate": "gate_proj.weight", "up": "up_proj.weight",
               "down": "down_proj.weight"}
_EXPERT_KEYS = ("gate", "up", "down")


def _leaves():
    """{key: leaf under "layers.<i>."} of one double layer: attention and
    MLP j's keys end in `_j`; `router`, `bias` and the held experts'
    `gate`, `up`, `down` are the keys `longcat_moe` reads."""
    out = {}
    for j in (0, 1):
        out[f"ln1_{j}"] = f"input_layernorm.{j}.weight"
        out.update({f"{k}_{j}": f"self_attn.{j}.{v}"
                    for k, v in ATTN_LEAVES.items()})
        out[f"ln2_{j}"] = f"post_attention_layernorm.{j}.weight"
        out.update({f"{k}_{j}": f"mlps.{j}.{v}"
                    for k, v in _MLP_LEAVES.items()})
    out.update({"router": "mlp.router.classifier.weight",
                "bias": "mlp.router.e_score_correction_bias",
                "gate": "mlp.experts.gate_proj",
                "up": "mlp.experts.up_proj",
                "down": "mlp.experts.down_proj"})
    return out


LEAVES = _leaves()


def longcat_block(cfg, x, w, attend, experts):
    """The double layer of the module's docstring over x [T, h]. `w`: one
    layer's weights by `LEAVES`' keys; `attend(j, y, w_j)`: attention
    j's output for normed tokens y (`w_j`: its weights by `ATTN_LEAVES`'
    keys); `experts(u)` -> (s, counts): the expert branch. Returns (x,
    counts)."""
    eps = cfg.rms_norm_eps

    def attn(j, x):
        wj = {k: w[f"{k}_{j}"] for k in ATTN_LEAVES}
        with jax.named_scope(f"attn{j}"):
            return x + attend(j, rms_norm(x, w[f"ln1_{j}"], eps), wj)

    def mlp(j, x, y):
        with jax.named_scope(f"mlp{j}"):
            return x + swiglu(y, w[f"gate_{j}"], w[f"up_{j}"],
                              w[f"down_{j}"])

    a0 = attn(0, x)
    u = rms_norm(a0, w["ln2_0"], eps)
    s, counts = experts(u)
    b0 = mlp(0, a0, u)
    a1 = attn(1, b0)
    b1 = mlp(1, a1, rms_norm(a1, w["ln2_1"], eps))
    with jax.named_scope("shortcut_join"):
        return b1 + s, counts


class Serving(_LatentServing):
    """This family's entry in `serving.mla_decoder.FAMILIES` (that
    module's docstring has the contract): every layer is one double
    layer, with two cache entries (its two attentions' latent rows); the
    pool's width, the rotary frequencies and the absence of a state are
    DeepSeek-V2's (the base class); a layer counts
    the pairs its held experts took, the held experts with at least one,
    and the identity pairs selected."""

    cache_entries = 2
    counters = ("expert_assignments", "experts_hit", "zero_assignments")

    @staticmethod
    def runs(cfg):
        return [("double", 0, cfg.num_layers)]

    @staticmethod
    def leaves(kind):
        return LEAVES

    @staticmethod
    def whole(kind):
        return _EXPERT_KEYS

    @staticmethod
    def block(cfg, kind, x, wl, seg, ri, attend, valid):
        w = dict(wl, **{k: seg[k] for k in _EXPERT_KEYS})
        return longcat_block(
            cfg, x, wl, attend,
            lambda u: longcat_moe(w, u, cfg, valid=valid, layer=ri))


# ------------------------------------------------------------ the Layers
class LongCatFlashMoE(Layer):
    """The expert branch (see the module's docstring):
    `router.classifier.weight` [h, router_width],
    `router.e_score_correction_bias` [router_width] (float32, zeros when
    drawn) and `experts.{gate,up,down}_proj` over the `cfg.experts_held`
    experts from `cfg.expert_offset`. `forward(u [T, h])` returns this
    chip's part."""

    def __init__(self, cfg, make=None, prefix="mlp"):
        super().__init__()
        make = make or _make(cfg, None)
        self.cfg = cfg
        h, f, E = cfg.hidden_size, cfg.expert_ffn_hidden_size, \
            cfg.experts_held
        self.router = _Router(make, prefix + ".router", h, cfg.router_width,
                              cfg.init_std)
        self.experts = _Leaves(make, prefix + ".experts", {
            "gate_proj": ((E, h, f), cfg.init_std),
            "up_proj": ((E, h, f), cfg.init_std),
            "down_proj": ((E, f, h), cfg.init_std)})

    def leaves(self):
        r, e = self.router, self.experts
        return (r.classifier.weight, r.e_score_correction_bias,
                e.gate_proj, e.up_proj, e.down_proj)

    def forward(self, u):
        return apply_op(lambda u, *w: longcat_moe(_moe_weights(w), u,
                                                  self.cfg)[0],
                        u, *self.leaves())


def _moe_weights(w):
    return dict(zip(("router", "bias", "gate", "up", "down"), w))


class _Router(Layer):
    def __init__(self, make, prefix, h, width, std):
        super().__init__()
        self.classifier = _linear(make, prefix + ".classifier", h, width,
                                  std)
        self.add_parameter("e_score_correction_bias", make(
            prefix + ".e_score_correction_bias", (width,), None))


class _DoubleBlock(Layer):
    def __init__(self, make, i, cfg):
        super().__init__()
        from ..nn import LayerList
        p, h = f"layers.{i}", cfg.hidden_size
        self.input_layernorm = LayerList(
            [_norm(make, f"{p}.input_layernorm.{j}", h) for j in (0, 1)])
        self.self_attn = LayerList(
            [_Attention(make, f"{p}.self_attn.{j}", cfg) for j in (0, 1)])
        self.post_attention_layernorm = LayerList(
            [_norm(make, f"{p}.post_attention_layernorm.{j}", h)
             for j in (0, 1)])
        self.mlps = LayerList(
            [_MLP(make, f"{p}.mlps.{j}", h, cfg.ffn_hidden_size,
                  cfg.init_std) for j in (0, 1)])
        self.mlp = LongCatFlashMoE(cfg, make, p + ".mlp")


def _make(cfg, weights):
    """Parameters drawn from the config's own std (norm gains 1, the
    router's bias buffer float32 zeros), or adopted from `weights`."""
    if weights is not None:
        return _adopted(weights)
    drawn = _drawn(cfg)

    def make(name, shape, std):
        if name.endswith("e_score_correction_bias"):
            return Parameter(jnp.zeros(shape, jnp.float32))
        return drawn(name, shape, std)
    return make


class LongCatFlash(Layer):
    """The decoder. `weights` ({parameter name: array}): adopt these
    arrays as the parameters instead of drawing fresh ones — the dict is
    emptied, nothing is copied."""

    def __init__(self, cfg: LongCatFlashConfig, weights=None):
        super().__init__()
        self.cfg = cfg
        make = _make(cfg, weights)
        self.embed_tokens = _Leaves(make, "embed_tokens", {
            "weight": ((cfg.vocab_size, cfg.hidden_size), cfg.init_std)})
        from .. import nn
        self.layers = nn.LayerList(
            [_DoubleBlock(make, i, cfg) for i in range(cfg.num_layers)])
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
                    x = block_full(p, f"layers.{i}.", x, pos, cfg, inv)
                x = rms_norm(x, p["norm.weight"], cfg.rms_norm_eps)
                return jnp.dot(x, p["lm_head.weight"],
                               preferred_element_type=jnp.float32)

            return jax.vmap(seq)(ids)

        return apply_op(run, input_ids,
                        *[v for _, v in self.named_parameters()])


def block_full(p, pre, x, pos, cfg, inv):
    """One double layer of the full forward (no cache) over the flat
    parameter dict `p`, the layer's names starting with `pre`."""
    w = {k: p[pre + leaf] for k, leaf in LEAVES.items()}
    return longcat_block(
        cfg, x, w,
        lambda j, y, wj: mla_materialised_full(wj, y, pos, cfg, inv),
        lambda u: longcat_moe(w, u, cfg))[0]
