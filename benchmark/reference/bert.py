"""Plain reference of the BERT encoder with its two pretraining heads
(post-LN blocks, learned positions and token types, exact GELU, MLM head
with tied decoder and bias, NSP head over the tanh pooler), as published.
float32 `jax.numpy`, no kernels. Imports nothing of the program.

Leaves are named as the program's BertForPretraining names them; a Linear's
weight is [in, out]. Departure from the program, noted in PERF.md: every
LayerNorm uses the published eps (the program's encoder layers use 1e-5).
"""
import functools

import jax
import jax.numpy as jnp
import numpy as np

from .common import attention, f32, layer_norm, mm, scalars, token_nll

_LAYER = {"self_attn.q_proj": ("h", "h"), "self_attn.k_proj": ("h", "h"),
          "self_attn.v_proj": ("h", "h"), "self_attn.out_proj": ("h", "h"),
          "linear1": ("h", "f"), "linear2": ("f", "h")}
_EMB = "bert.embeddings."


def leaf_parts(name):
    return 0, 1


def _layer_leaves():
    out = []
    for k in _LAYER:
        out += [k + ".weight", k + ".bias"]
    return out + ["norm1.weight", "norm1.bias", "norm2.weight", "norm2.bias"]


def leaf_shapes(cfg):
    h, f, v = cfg["hidden_size"], cfg["intermediate_size"], cfg["vocab_size"]
    dim = {"h": h, "f": f}
    s = {"mlm_bias": (v,),
         _EMB + "word_embeddings.weight": (v, h),
         _EMB + "position_embeddings.weight":
             (cfg["max_position_embeddings"], h),
         _EMB + "token_type_embeddings.weight": (cfg["type_vocab_size"], h),
         _EMB + "layer_norm.weight": (h,), _EMB + "layer_norm.bias": (h,)}
    for i in range(cfg["num_hidden_layers"]):
        p = f"bert.encoder.layers.{i}."
        for k, (a, b) in _LAYER.items():
            s[p + k + ".weight"] = (dim[a], dim[b])
            s[p + k + ".bias"] = (dim[b],)
        for k in ("norm1", "norm2"):
            s[p + k + ".weight"] = (h,)
            s[p + k + ".bias"] = (h,)
    s.update({"bert.pooler.weight": (h, h), "bert.pooler.bias": (h,),
              "mlm_transform.weight": (h, h), "mlm_transform.bias": (h,),
              "mlm_norm.weight": (h,), "mlm_norm.bias": (h,),
              "nsp.weight": (h, 2), "nsp.bias": (2,)})
    return s


@functools.lru_cache(maxsize=4)
def _make_init(cfg_items):
    cfg = dict(cfg_items)
    shapes = leaf_shapes(cfg)
    n, std = cfg["num_hidden_layers"], cfg["initializer_range"]
    dt = jnp.dtype(cfg["dtype"])

    def make(key):
        p = {}
        matrices = [k for k, s in shapes.items() if len(s) == 2]
        per_layer = [k for k in _LAYER]
        keys = jax.random.split(key, len(per_layer) + len(matrices))
        for j, k in enumerate(per_layer):
            a, b = shapes[f"bert.encoder.layers.0.{k}.weight"]
            draw = jax.random.normal(keys[j], (n, a, b), dt) * std
            for i in range(n):
                p[f"bert.encoder.layers.{i}.{k}.weight"] = draw[i].astype(dt)
        for j, k in enumerate(matrices):
            if k not in p:
                p[k] = (jax.random.normal(keys[len(per_layer) + j],
                                          shapes[k], dt) * std).astype(dt)
        for k, s in shapes.items():
            if k in p:
                continue
            gain = k.endswith("norm.weight") or k.endswith("norm1.weight") \
                or k.endswith("norm2.weight")
            p[k] = jnp.ones(s, dt) if gain else jnp.zeros(s, dt)
        return p

    return jax.jit(make)


def init_params(cfg, seed):
    return _make_init(scalars(cfg))(jax.random.PRNGKey(seed % (2 ** 31)))


def stages(cfg):
    return (["embed"] + [f"bert.encoder.layers.{i}"
                         for i in range(cfg["num_hidden_layers"])] + ["head"])


def stage_leaves(cfg, stage):
    if stage == "embed":
        return {k: _EMB + k for k in (
            "word_embeddings.weight", "position_embeddings.weight",
            "token_type_embeddings.weight", "layer_norm.weight",
            "layer_norm.bias")}
    if stage == "head":
        own = ("bert.pooler.weight", "bert.pooler.bias",
               "mlm_transform.weight", "mlm_transform.bias",
               "mlm_norm.weight", "mlm_norm.bias", "mlm_bias",
               "nsp.weight", "nsp.bias")
        return {**{k: k for k in own},
                "word_embeddings.weight": _EMB + "word_embeddings.weight"}
    return {k: f"{stage}.{k}" for k in _layer_leaves()}


def stage_fn(cfg, stage):
    eps = cfg["layer_norm_eps"]
    if stage == "embed":
        return functools.partial(embed, eps=eps), "embed"
    if stage == "head":
        return functools.partial(head_loss, eps=eps), "head"
    return functools.partial(block, heads=cfg["num_attention_heads"],
                             eps=eps), "block"


def embed(p, x, rows, prec, eps):
    del x, prec
    p = f32(p)
    ids = rows["input_ids"]
    x = (p["word_embeddings.weight"][ids]
         + p["position_embeddings.weight"][:ids.shape[1]][None]
         + p["token_type_embeddings.weight"][rows["token_type_ids"]])
    return layer_norm(x, p["layer_norm.weight"], p["layer_norm.bias"], eps)


def block(p, x, rows, prec, heads, eps):
    p = f32(p)
    b, l, h = x.shape

    def lin(name, v, spec="blh,hk->blk"):
        return mm(spec, v, p[name + ".weight"], prec) + p[name + ".bias"]

    def split(v):
        return v.reshape(b, l, heads, h // heads)

    bias = ((1.0 - rows["attention_mask"].astype(jnp.float32))
            * -1e4)[:, None, None, :]
    o = attention(split(lin("self_attn.q_proj", x)),
                  split(lin("self_attn.k_proj", x)),
                  split(lin("self_attn.v_proj", x)), bias, prec)
    x = layer_norm(x + lin("self_attn.out_proj", o.reshape(b, l, h)),
                   p["norm1.weight"], p["norm1.bias"], eps)
    y = jax.nn.gelu(lin("linear1", x), approximate=False)
    return layer_norm(x + lin("linear2", y),
                      p["norm2.weight"], p["norm2.bias"], eps)


def head_loss(p, x, rows, prec, denom, eps):
    """MLM: the rows' token losses over denom[0], the labelled positions of
    the whole batch. NSP: the rows' losses over denom[1], the batch's
    rows."""
    p = f32(p)
    t = jax.nn.gelu(mm("blh,hk->blk", x, p["mlm_transform.weight"], prec)
                    + p["mlm_transform.bias"], approximate=False)
    t = layer_norm(t, p["mlm_norm.weight"], p["mlm_norm.bias"], eps)
    logits = mm("blh,vh->blv", t, p["word_embeddings.weight"], prec) \
        + p["mlm_bias"]
    nll, _ = token_nll(logits, rows["labels"])
    pooled = jnp.tanh(mm("bh,hk->bk", x[:, 0], p["bert.pooler.weight"], prec)
                      + p["bert.pooler.bias"])
    nsp = mm("bh,hk->bk", pooled, p["nsp.weight"], prec) + p["nsp.bias"]
    nsp_nll, _ = token_nll(nsp, rows["nsp_labels"])
    return jnp.sum(nll) / denom[0] + jnp.sum(nsp_nll) / denom[1]


def count_labels(batch):
    return np.asarray([(batch["labels"] >= 0).sum(), len(batch["labels"])],
                      np.float32)


def tokens_in(batch):
    """Tokens of a batch that are not padding."""
    return int(batch["attention_mask"].sum())
