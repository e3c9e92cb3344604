"""LFM2-MoE-style decoder: gated short-convolution layers and grouped-query
attention layers in a fixed pattern, RMSNorm, and after the leading dense
layers a dropless expert layer routed by sigmoid scores with a selection
bias.

Layer l, for input x, RMSNorm N (gain only), all Linears without bias:

    h   = x + Op_l(N_op(x))          # ShortConv or Attention, by layer_types[l]
    out = h + F_l(N_ffn(h))          # dense SwiGLU below num_dense_layers, else MoE

  * ShortConv over tokens y [T, h]: `[B | C | v] = y W_in` (three column
    blocks of h), `z_t = B_t v_t`, a depthwise causal convolution of
    `conv_L_cache` = 3 taps `c_t = k0 z_{t-2} + k1 z_{t-1} + k2 z_t` (z
    before position 0 is 0), `out_t = (C_t c_t) W_out`. What a sequence
    carries from one token to the next is z of its two latest positions:
    in serving that is a per-slot STATE, not a page (`packed_conv_taps`).
  * Attention: q [H, D], k and v [Hk, D] (grouped: query head h reads
    key/value head h // (H / Hk)); each head's q and k RMS-normed over its
    D values (`q_layernorm`, `k_layernorm`), then rotated (rope_theta, the
    rotate-half pairing (i, i + D/2)); causal, scale D^-0.5.
  * MoE: `s = sigmoid(y W_r)` in float32; the `num_experts_per_tok`
    largest of `s + expert_bias` are selected (the bias moves the
    selection and never a weight); a selected expert weighs `s_i / (sum of
    the selected s + 1e-6)` (`norm_topk_prob`) x `routed_scaling_factor`;
    no shared expert, no groups, no capacity, no dropped pair. The expert
    layer is TOLD WHICH EXPERTS IT HOLDS (`experts_held` from
    `expert_offset`) and computes their pairs through
    `deepseek_v2.held_expert_walk`, the walk the other expert families
    use.
  * A final RMSNorm after the last layer and an untied head.

Parameter names follow the published modules (`layers.<i>.conv.in_proj`,
`layers.<i>.self_attn.q_layernorm`, `layers.<i>.feed_forward.w1`, ...), with
these differences of layout: a Linear's weight is [in, out]; the conv
kernel is [h, taps] (the published Conv1d's [h, 1, taps] without its
channel axis); a layer's held experts are one parameter [experts_held, in,
out] for each of w1 (gate), w3 (up) and w2 (down); the final norm is
`norm.weight` (the published `embedding_norm`).
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np

from ..framework.core import Parameter, apply_op
from ..nn.layer_base import Layer
from . import deepseek_v2
from .deepseek_v2 import (HIGHEST, _adopted, _Leaves, _linear, _mm, _norm,
                          held_expert_walk, rms_norm, swiglu)

__all__ = ["Lfm2MoeConfig", "Lfm2Moe", "lfm2_moe_tiny", "sigmoid_route",
           "lfm2_moe", "short_conv", "packed_conv_taps", "gqa_project",
           "rope_half", "PUBLISHED_LAYER_TYPES"]

# the published 40 layers: a conv pair, then (attention, 3 x conv) nine
# times, then an attention and a conv
PUBLISHED_LAYER_TYPES = ("conv", "conv") + ("full_attention", "conv", "conv",
                                            "conv") * 9 + ("full_attention",
                                                           "conv")


@dataclasses.dataclass
class Lfm2MoeConfig:
    family = "lfm2_moe"                  # its entry in mla_decoder.FAMILIES
    vocab_size: int = 65536
    hidden_size: int = 2048
    num_layers: int = 40
    num_heads: int = 32
    num_kv_heads: int = 8
    intermediate_size: int = 11776       # the dense layers' MLP
    moe_intermediate_size: int = 1536    # one routed expert
    num_experts: int = 64                # the router's width
    experts_held: int = 0                # 0 -> all of them
    expert_offset: int = 0               # the first expert held
    num_experts_per_tok: int = 4
    num_dense_layers: int = 2
    layer_types: tuple = PUBLISHED_LAYER_TYPES
    conv_L_cache: int = 3
    norm_topk_prob: bool = True
    routed_scaling_factor: float = 1.0
    rms_norm_eps: float = 1e-5
    rope_theta: float = 1e6
    max_seq_len: int = 128000
    dtype: str = "bfloat16"
    init_std: float = 0.02

    def __post_init__(self):
        self.layer_types = tuple(self.layer_types)
        if not self.experts_held:
            self.experts_held = self.num_experts
        if len(self.layer_types) != self.num_layers:
            raise ValueError(f"{len(self.layer_types)} layer_types for "
                             f"{self.num_layers} layers")
        if set(self.layer_types) - {"conv", "full_attention"}:
            raise ValueError(f"unknown layer types in {self.layer_types}")
        if self.expert_offset + self.experts_held > self.num_experts:
            raise ValueError(
                f"experts {self.expert_offset}..{self.expert_offset}+"
                f"{self.experts_held} lie outside the router's width "
                f"{self.num_experts}")
        if self.hidden_size % self.num_heads or \
                self.num_heads % self.num_kv_heads or self.head_dim % 2:
            raise ValueError("heads must divide the width, key/value heads "
                             "the heads, and the head size must be even")

    @property
    def head_dim(self):
        return self.hidden_size // self.num_heads

    @property
    def kv_width(self):
        """Values the cache holds a token an attention layer: keys, then
        values, of every key/value head."""
        return 2 * self.num_kv_heads * self.head_dim

    def is_dense(self, layer):
        return layer < self.num_dense_layers

    def is_attention(self, layer):
        return self.layer_types[layer] == "full_attention"

    def num_params(self):
        """Parameters HELD here (the held experts, not the router's
        width), the norms' gains and the selection bias among them."""
        h, H, K, D = (self.hidden_size, self.num_heads, self.num_kv_heads,
                      self.head_dim)
        n = 2 * self.vocab_size * h + h
        for i in range(self.num_layers):
            n += 2 * h
            if self.is_attention(i):
                n += 2 * h * H * D + 2 * h * K * D + 2 * D
            else:
                n += 4 * h * h + h * self.conv_L_cache
            if self.is_dense(i):
                n += 3 * h * self.intermediate_size
            else:
                n += (h + 1) * self.num_experts + 3 * h \
                    * self.moe_intermediate_size * self.experts_held
        return n


def lfm2_moe_tiny(**kw):
    """A tiny preset for CPU tests: every mechanism, no published width."""
    d = dict(vocab_size=96, hidden_size=32, num_layers=5, num_heads=4,
             num_kv_heads=2, intermediate_size=48, moe_intermediate_size=16,
             num_experts=8, num_experts_per_tok=3, num_dense_layers=1,
             layer_types=("conv", "full_attention", "conv", "conv",
                          "full_attention"),
             rope_theta=10000.0, max_seq_len=256, dtype="float32",
             init_std=0.2)
    d.update(kw)
    return Lfm2MoeConfig(**d)


# ------------------------------------------------------------- positions
def inv_freq(cfg):
    """Rotary frequencies [D / 2], float64 numpy: theta^(-2i / D)."""
    d = cfg.head_dim
    return cfg.rope_theta ** (-np.arange(0, d, 2, dtype=np.float64) / d)


def rope_half(x, pos, inv):
    """Rotate x [T, heads, D] to positions pos [T] with the rotate-half
    pairing: dimension i turns with i + D/2. float32 inside, x's type
    out."""
    ang = pos.astype(jnp.float32)[:, None, None] * inv[None, None, :]
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    x32 = x.astype(jnp.float32)
    half = x.shape[-1] // 2
    a, b = x32[..., :half], x32[..., half:]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin],
                           axis=-1).astype(x.dtype)


# ---------------------------------------------------------- the operators
def gqa_project(w, y, pos, cfg, inv):
    """What the cached and the full attention share, for normed tokens y
    [T, h] at positions pos [T]: (q [T, H, D] normed and rotated, the row
    the cache holds [T, 2 x Hk x D]: the normed, rotated keys and then
    the values). `w`: q, k, v, q_ln, k_ln."""
    T, H, K, D = y.shape[0], cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    eps = cfg.rms_norm_eps
    with jax.named_scope("qk_norm"):
        q = rms_norm(_mm(y, w["q"]).reshape(T, H, D), w["q_ln"], eps)
        k = rms_norm(_mm(y, w["k"]).reshape(T, K, D), w["k_ln"], eps)
    q, k = rope_half(q, pos, inv), rope_half(k, pos, inv)
    return q, jnp.concatenate([k.reshape(T, K * D), _mm(y, w["v"])], -1)


def gqa_full(w, y, pos, cfg, inv):
    """Causal grouped-query attention of one whole sequence y [L, h] with
    no cache (the full forward)."""
    L, H, K, D = y.shape[0], cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    q, row = gqa_project(w, y, pos, cfg, inv)
    k = jnp.repeat(row[:, :K * D].reshape(L, K, D), H // K, axis=1)
    v = jnp.repeat(row[:, K * D:].reshape(L, K, D), H // K, axis=1)
    s = jnp.einsum("lhd,mhd->hlm", q, k,
                   preferred_element_type=jnp.float32) * D ** -0.5
    seen = pos[None, :] <= pos[:, None]
    p = jax.nn.softmax(jnp.where(seen[None], s, -1e30), axis=-1)
    o = jnp.einsum("hlm,mhd->lhd", p.astype(y.dtype), v,
                   preferred_element_type=jnp.float32).astype(y.dtype)
    return _mm(o.reshape(L, H * D), w["o"])


def short_conv(w, y, taps):
    """The gated short convolution over tokens y [T, h]. `w`: in_proj [h,
    3h], conv [h, 3], out_proj [h, h]; `taps(z)` -> (z two positions back,
    z one position back), each [T, h], for z [T, h] (the full forward
    shifts the sequence; serving reads a slot's state where the stream
    holds no earlier token of the row)."""
    h = y.shape[-1]
    bcv = _mm(y, w["in_proj"])
    b, c, v = bcv[:, :h], bcv[:, h:2 * h], bcv[:, 2 * h:]
    z = b * v
    z2, z1 = taps(z)
    k = w["conv"].astype(jnp.float32)
    conv = (k[:, 0] * z2.astype(jnp.float32) + k[:, 1] * z1.astype(jnp.float32)
            + k[:, 2] * z.astype(jnp.float32))
    return _mm((c.astype(jnp.float32) * conv).astype(y.dtype), w["out_proj"])


def shifted_taps(z):
    """A whole sequence's taps: z shifted down by two and by one, zeros
    before position 0."""
    return (jnp.pad(z, ((2, 0), (0, 0)))[:-2],
            jnp.pad(z, ((1, 0), (0, 0)))[:-1])


def packed_conv_taps(z, state, rows, pos, row_new):
    """The taps of a PACKED stream and its rows' new state. z [T, h]: this
    layer's z of every stream token (token t of row `rows[t]` at position
    `pos[t]`; a row's tokens are contiguous, rows in increasing order,
    `row_new` [S] of them each); state [S, 2, h]: z of each row's two
    latest positions before the stream (index 1 the latest). A tap that
    lies inside the row's part of the stream reads the stream, one before
    it reads the state, one at a position below 0 reads zero (so a slot a
    new request takes needs no reset). A row with tokens keeps z of its
    two latest positions; a row without (frozen, padded, empty) keeps its
    state. Returns ((z2, z1), new state)."""
    T = z.shape[0]
    first = jnp.cumsum(row_new) - row_new                     # [S]
    within = jnp.arange(T) - first[rows]                      # [T]
    kept = state[rows]                                        # [T, 2, h]

    def tap(d):
        from_stream = z[jnp.maximum(jnp.arange(T) - d, 0)]
        from_state = kept[jnp.arange(T), jnp.clip(2 + within - d, 0, 1)]
        got = jnp.where((within >= d)[:, None], from_stream, from_state)
        return jnp.where((pos >= d)[:, None], got, jnp.zeros_like(got))

    last = jnp.clip(first + row_new - 1, 0, T - 1)            # [S]
    prev = jnp.where((row_new >= 2)[:, None], z[jnp.clip(last - 1, 0, T - 1)],
                     state[:, 1])
    new = jnp.stack([prev, z[last]], axis=1)
    state = jnp.where((row_new > 0)[:, None, None], new, state)
    return (tap(2), tap(1)), state


def sigmoid_route(logits, bias, cfg):
    """(weights [T, k], experts [T, k]) of router logits [T, experts]
    (float32): s = sigmoid(logits); the k = `num_experts_per_tok` largest
    of s + bias are selected; a selected expert weighs s (over the
    selected sum + 1e-6 if `norm_topk_prob`) x `routed_scaling_factor`:
    the bias is in the selection alone."""
    s = jax.nn.sigmoid(logits)
    _, ei = jax.lax.top_k(s + bias.astype(s.dtype), cfg.num_experts_per_tok)
    w = jnp.take_along_axis(s, ei, axis=-1)
    if cfg.norm_topk_prob:
        w = w / (jnp.sum(w, -1, keepdims=True) + 1e-6)
    return w * cfg.routed_scaling_factor, ei


def lfm2_moe(w, y, cfg, valid=None, layer=None):
    """One chip's part of the expert layer for tokens y [T, h]. `w`:
    router [h, experts], bias [experts] float32, gate/up [held, h, f],
    down [held, f, h] (with `layer`: stacks [layers, held, ...] read at
    that layer, in place, by `held_expert_walk`'s grouped products).
    Returns (y, (assignments, experts_hit)) over `valid` tokens [T]
    (None: all)."""
    with jax.named_scope("moe_router"):
        logits = jnp.dot(y.astype(jnp.float32),
                         w["router"].astype(jnp.float32), precision=HIGHEST)
        cw, ei = sigmoid_route(logits, w["bias"], cfg)
    routed, counts = held_expert_walk(w, y, cw, ei, cfg.experts_held,
                                      cfg.expert_offset, valid, layer)
    return routed.astype(y.dtype), (jnp.sum(counts),
                                    jnp.sum(counts > 0).astype(jnp.int32))


# ------------------------------------------------------------- the block
_OP_LEAVES = {
    "conv": {"in_proj": "conv.in_proj.weight", "conv": "conv.conv.weight",
             "out_proj": "conv.out_proj.weight"},
    "attn": {"q": "self_attn.q_proj.weight", "k": "self_attn.k_proj.weight",
             "v": "self_attn.v_proj.weight", "o": "self_attn.out_proj.weight",
             "q_ln": "self_attn.q_layernorm.weight",
             "k_ln": "self_attn.k_layernorm.weight"}}
_FFN_LEAVES = {
    "dense": {"gate": "feed_forward.w1.weight", "up": "feed_forward.w3.weight",
              "down": "feed_forward.w2.weight"},
    "moe": {"router": "feed_forward.gate.weight",
            "bias": "feed_forward.expert_bias",
            "gate": "feed_forward.experts.w1",
            "up": "feed_forward.experts.w3",
            "down": "feed_forward.experts.w2"}}
_EXPERT_KEYS = ("gate", "up", "down")


def layer_kind(cfg, i):
    """"<ffn>_<op>": dense or moe, conv or attn."""
    return ("dense" if cfg.is_dense(i) else "moe") + "_" + (
        "attn" if cfg.is_attention(i) else "conv")


def leaves(kind):
    """{key: leaf under "layers.<i>."} of a layer of `kind`."""
    ffn, op = kind.split("_")
    return dict({"ln_op": "operator_norm.weight",
                 "ln_ffn": "ffn_norm.weight"},
                **_OP_LEAVES[op], **_FFN_LEAVES[ffn])


def lfm2_block(cfg, kind, x, w, attend, taps, experts):
    """One layer of `kind` over x [T, h]. `w`: its weights by `leaves`'
    keys; `attend(y, w)`: the attention's output for normed tokens y;
    `taps(z)`: the conv's (z2, z1) (see `short_conv`); `experts(y)` ->
    (out, counts): the expert layer. Returns (x, counts: () for a dense
    layer)."""
    ffn, op = kind.split("_")
    eps = cfg.rms_norm_eps
    y = rms_norm(x, w["ln_op"], eps)
    if op == "attn":
        with jax.named_scope("gqa_attention"):
            x = x + attend(y, w)
    else:
        with jax.named_scope("short_conv"):
            x = x + short_conv(w, y, taps)
    y = rms_norm(x, w["ln_ffn"], eps)
    if ffn == "dense":
        with jax.named_scope("mlp"):
            return x + swiglu(y, w["gate"], w["up"], w["down"]), ()
    out, counts = experts(y)
    return x + out, counts


class Serving:
    """This family's entry in `serving.mla_decoder.FAMILIES` (that
    module's docstring has the contract). A layer is a short conv or a
    grouped-query attention and then a dense MLP or the expert layer; an
    attention layer has ONE pool entry of `kv_width` values (its keys,
    then its values), a conv layer none and a per-slot state of z at the
    row's two latest positions instead; an expert layer counts the pairs
    its held experts took and the held experts with at least one."""

    attention = "gqa"
    counters = ("expert_assignments", "experts_hit")

    @staticmethod
    def runs(cfg):
        """[(kind, first layer, layers)]: each run of equal layers."""
        runs = []
        for i in range(cfg.num_layers):
            kind = layer_kind(cfg, i)
            if runs and runs[-1][0] == kind:
                runs[-1][2] += 1
            else:
                runs.append([kind, i, 1])
        return [tuple(r) for r in runs]

    @staticmethod
    def leaves(kind):
        return leaves(kind)

    @staticmethod
    def whole(kind):
        return _EXPERT_KEYS if kind.startswith("moe") else ()

    @staticmethod
    def inv_freq(cfg):
        return inv_freq(cfg)

    @staticmethod
    def entry_width(cfg):
        return cfg.kv_width

    @staticmethod
    def layer_entries(cfg):
        return [int(cfg.is_attention(i)) for i in range(cfg.num_layers)]

    @staticmethod
    def state_layers(cfg):
        """The layers that keep a per-slot state, and its shape a slot."""
        return ([i for i in range(cfg.num_layers) if not cfg.is_attention(i)],
                (cfg.conv_L_cache - 1, cfg.hidden_size))

    @staticmethod
    def project(w, y, pos, cfg, inv):
        return gqa_project(w, y, pos, cfg, inv)

    @staticmethod
    def block(cfg, kind, x, wl, seg, ri, attend, valid, taps=None):
        w = dict(wl, **{k: seg[k] for k in Serving.whole(kind)})
        return lfm2_block(
            cfg, kind, x, wl, lambda y, w_: attend(0, y, w_), taps,
            lambda y: lfm2_moe(w, y, cfg, valid=valid, layer=ri))


# ------------------------------------------------------------ the Layers
def _drawn(cfg):
    """Parameters drawn from the config's std (norm gains 1, the
    selection bias float32 zeros)."""
    drawn = deepseek_v2._drawn(cfg)

    def make(name, shape, std):
        if name.endswith("expert_bias"):
            return Parameter(jnp.zeros(shape, jnp.float32))
        return drawn(name, shape, std)
    return make


class _Block(Layer):
    def __init__(self, make, i, cfg):
        super().__init__()
        p, h, std = f"layers.{i}", cfg.hidden_size, cfg.init_std
        H, K, D = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
        self.operator_norm = _norm(make, p + ".operator_norm", h)
        if cfg.is_attention(i):
            a = p + ".self_attn"
            self.self_attn = _Leaves(make, a, {})
            for name, n_in, n_out in (("q_proj", h, H * D),
                                      ("k_proj", h, K * D),
                                      ("v_proj", h, K * D),
                                      ("out_proj", H * D, h)):
                setattr(self.self_attn, name,
                        _linear(make, f"{a}.{name}", n_in, n_out, std))
            self.self_attn.q_layernorm = _norm(make, a + ".q_layernorm", D)
            self.self_attn.k_layernorm = _norm(make, a + ".k_layernorm", D)
        else:
            c = p + ".conv"
            self.conv = _Leaves(make, c, {})
            self.conv.in_proj = _linear(make, c + ".in_proj", h, 3 * h, std)
            self.conv.conv = _Leaves(make, c + ".conv", {
                "weight": ((h, cfg.conv_L_cache), std)})
            self.conv.out_proj = _linear(make, c + ".out_proj", h, h, std)
        self.ffn_norm = _norm(make, p + ".ffn_norm", h)
        f = p + ".feed_forward"
        if cfg.is_dense(i):
            n = cfg.intermediate_size
            self.feed_forward = _Leaves(make, f, {})
            self.feed_forward.w1 = _linear(make, f + ".w1", h, n, std)
            self.feed_forward.w3 = _linear(make, f + ".w3", h, n, std)
            self.feed_forward.w2 = _linear(make, f + ".w2", n, h, std)
        else:
            E, n = cfg.experts_held, cfg.moe_intermediate_size
            self.feed_forward = _Leaves(make, f, {
                "expert_bias": ((cfg.num_experts,), None)})
            self.feed_forward.gate = _linear(make, f + ".gate", h,
                                             cfg.num_experts, std)
            self.feed_forward.experts = _Leaves(make, f + ".experts", {
                "w1": ((E, h, n), std), "w3": ((E, h, n), std),
                "w2": ((E, n, h), std)})


class Lfm2Moe(Layer):
    """The decoder. `weights` ({parameter name: array}): adopt these
    arrays as the parameters instead of drawing fresh ones — the dict is
    emptied, nothing is copied."""

    def __init__(self, cfg: Lfm2MoeConfig, weights=None):
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
        """Logits [B, L, vocab] (float32) of the full causal forward, no
        cache."""
        cfg = self.cfg
        inv = jnp.asarray(inv_freq(cfg), jnp.float32)
        names = [n for n, _ in self.named_parameters()]

        def run(ids, *vals):
            p = dict(zip(names, vals))
            pos = jnp.arange(ids.shape[1])

            def seq(row):
                x = p["embed_tokens.weight"][row].astype(cfg.dtype)
                for i in range(cfg.num_layers):
                    x = block_full(p, i, x, pos, cfg, inv)
                x = rms_norm(x, p["norm.weight"], cfg.rms_norm_eps)
                return jnp.dot(x, p["lm_head.weight"],
                               preferred_element_type=jnp.float32)

            return jax.vmap(seq)(ids)

        return apply_op(run, input_ids,
                        *[v for _, v in self.named_parameters()])


def block_full(p, i, x, pos, cfg, inv):
    """Layer i of the full forward (no cache) over the flat parameter
    dict `p`."""
    kind = layer_kind(cfg, i)
    w = {k: p[f"layers.{i}.{leaf}"] for k, leaf in leaves(kind).items()}
    return lfm2_block(cfg, kind, x, w,
                      lambda y, w_: gqa_full(w_, y, pos, cfg, inv),
                      shifted_taps, lambda y: lfm2_moe(w, y, cfg))[0]
