"""Plain reference of the GPT-2/3 decoder: pre-LN blocks, learned positions,
tanh-GELU, tied head, causal LM loss. float32 `jax.numpy`, no kernels, no
cache, no batching tricks. Imports nothing of the program.

Parameters are one flat dict, named as the published checkpoints name them
(`blocks.<i>.qkv.weight` ...; a Linear's weight is [in, out]); the qkv
columns are laid out [3, heads, head_dim]. `cfg` is the configuration file's
dict.
"""
import functools
import math

import jax
import jax.numpy as jnp

from .common import attention, f32, layer_norm, mm, scalars, token_nll

LN_EPS = 1e-5

_BLOCK_LEAVES = ("ln1.weight", "ln1.bias", "qkv.weight", "qkv.bias",
                 "proj.weight", "proj.bias", "ln2.weight", "ln2.bias",
                 "fc1.weight", "fc1.bias", "fc2.weight", "fc2.bias")


def leaf_parts(name):
    """(axis, parts) of a leaf that fuses several: q, k and v share one."""
    if name.endswith("qkv.weight"):
        return 1, 3
    if name.endswith("qkv.bias"):
        return 0, 3
    return 0, 1


def leaf_shapes(cfg):
    h, f = cfg["hidden_size"], cfg["intermediate_size"]
    shapes = {"wte.weight": (cfg["vocab_size"], h),
              "wpe.weight": (cfg["max_position_embeddings"], h)}
    block = {"ln1.weight": (h,), "ln1.bias": (h,),
             "qkv.weight": (h, 3 * h), "qkv.bias": (3 * h,),
             "proj.weight": (h, h), "proj.bias": (h,),
             "ln2.weight": (h,), "ln2.bias": (h,),
             "fc1.weight": (h, f), "fc1.bias": (f,),
             "fc2.weight": (f, h), "fc2.bias": (h,)}
    for i in range(cfg["num_hidden_layers"]):
        for k, s in block.items():
            shapes[f"blocks.{i}.{k}"] = s
    shapes["ln_f.weight"] = (h,)
    shapes["ln_f.bias"] = (h,)
    return shapes


@functools.lru_cache(maxsize=4)
def _make_init(cfg_items):
    cfg = dict(cfg_items)
    n = cfg["num_hidden_layers"]
    h, f = cfg["hidden_size"], cfg["intermediate_size"]
    std = cfg["initializer_range"]
    out_std = std / math.sqrt(2.0 * n)
    dt = jnp.dtype(cfg["dtype"])
    shapes = leaf_shapes(cfg)

    def make(key):
        ks = jax.random.split(key, 6)
        # one draw for each kind of matrix, all layers at once
        draws = {
            "qkv.weight": jax.random.normal(ks[0], (n, h, 3 * h), dt) * std,
            "proj.weight": jax.random.normal(ks[1], (n, h, h), dt) * out_std,
            "fc1.weight": jax.random.normal(ks[2], (n, h, f), dt) * std,
            "fc2.weight": jax.random.normal(ks[3], (n, f, h), dt) * out_std,
        }
        p = {"wte.weight": (jax.random.normal(
                 ks[4], shapes["wte.weight"], dt) * std).astype(dt),
             "wpe.weight": (jax.random.normal(
                 ks[5], shapes["wpe.weight"], dt) * std).astype(dt)}
        for i in range(n):
            for k in _BLOCK_LEAVES:
                name = f"blocks.{i}.{k}"
                if k in draws:
                    p[name] = draws[k][i].astype(dt)
                elif k.startswith("ln") and k.endswith("weight"):
                    p[name] = jnp.ones((h,), dt)
                else:
                    p[name] = jnp.zeros(shapes[name], dt)
        p["ln_f.weight"] = jnp.ones((h,), dt)
        p["ln_f.bias"] = jnp.zeros((h,), dt)
        return p

    return jax.jit(make)


def init_params(cfg, seed):
    """The weights of a run, from its seed, in one jitted call on the
    device, in the type the configuration stores them in. The program is
    given these; the reference makes them again for itself."""
    return _make_init(scalars(cfg))(jax.random.PRNGKey(seed % (2 ** 31)))


# ----------------------------------------------------------- the stages
# A stage is a function of (its own leaves under local names, the stream,
# the rows of the batch). The layer-by-layer reference trainer
# (reference/train.py) walks them forward and backward one at a time.

def stages(cfg):
    return (["embed"] + [f"blocks.{i}" for i in range(cfg["num_hidden_layers"])]
            + ["head"])


def stage_leaves(cfg, stage):
    """{local name: flat name} of the leaves a stage reads."""
    if stage == "embed":
        return {"wte.weight": "wte.weight", "wpe.weight": "wpe.weight"}
    if stage == "head":
        return {"wte.weight": "wte.weight", "ln_f.weight": "ln_f.weight",
                "ln_f.bias": "ln_f.bias"}
    return {k: f"{stage}.{k}" for k in _BLOCK_LEAVES}


def stage_fn(cfg, stage):
    """(function, key under which equal stages share one compiled program)"""
    if stage == "embed":
        return embed, "embed"
    if stage == "head":
        return head_loss, "head"
    return functools.partial(block, heads=cfg["num_attention_heads"]), "block"


def embed(p, x, rows, prec):
    del x, prec
    p = f32(p)
    ids = rows["input_ids"]
    return p["wte.weight"][ids] + p["wpe.weight"][:ids.shape[1]][None]


def block(p, x, rows, prec, heads):
    del rows
    p = f32(p)
    b, l, h = x.shape
    y = layer_norm(x, p["ln1.weight"], p["ln1.bias"], LN_EPS)
    qkv = mm("blh,hk->blk", y, p["qkv.weight"], prec) + p["qkv.bias"]
    qkv = qkv.reshape(b, l, 3, heads, h // heads)
    causal = jnp.tril(jnp.ones((l, l), bool))
    bias = jnp.where(causal, 0.0, -1e30)[None, None]
    o = attention(qkv[:, :, 0], qkv[:, :, 1], qkv[:, :, 2], bias, prec)
    x = x + mm("blh,hk->blk", o.reshape(b, l, h), p["proj.weight"], prec) \
        + p["proj.bias"]
    y = layer_norm(x, p["ln2.weight"], p["ln2.bias"], LN_EPS)
    y = jax.nn.gelu(mm("blh,hf->blf", y, p["fc1.weight"], prec)
                    + p["fc1.bias"], approximate=True)
    return x + mm("blf,fh->blh", y, p["fc2.weight"], prec) + p["fc2.bias"]


def final_logits(p, x, prec):
    p = f32(p)
    y = layer_norm(x, p["ln_f.weight"], p["ln_f.bias"], LN_EPS)
    return mm("blh,vh->blv", y, p["wte.weight"], prec)


def head_loss(p, x, rows, prec, denom):
    """Sum of the rows' token losses over `denom`, the count of labelled
    positions in the whole batch: the blocks of rows then add up to the
    batch's mean."""
    nll, _ = token_nll(final_logits(p, x, prec), rows["labels"])
    return jnp.sum(nll) / denom


def count_labels(batch):
    return int((batch["labels"] >= 0).sum())


def tokens_in(batch):
    """Tokens of a batch that are not padding (GPT's batches have none)."""
    return int(batch["input_ids"].size)


# -------------------------------------------------------------- serving
_jitted = {}


def _jit(key, fn):
    if key not in _jitted:
        _jitted[key] = jax.jit(fn)
    return _jitted[key]


def served_rows_logits(cfg, params, ids, first_row, rows, prec="f32"):
    """Logits [rows, vocab] of positions first_row .. first_row+rows-1 of the
    plain forward pass over `ids` ([T] token ids, padded at the end to any
    length: attention is causal, so what follows a position cannot reach
    it). One block at a time, all blocks through one compiled program."""
    heads = cfg["num_attention_heads"]
    ids = jnp.asarray(ids, jnp.int32)[None]
    x = _jit(("embed",), lambda p, r: embed(p, None, r, "f32"))(
        {k: params[f] for k, f in stage_leaves(cfg, "embed").items()},
        {"input_ids": ids})
    blk = _jit(("block", prec, heads),
               lambda p, x_: block(p, x_, None, prec, heads))
    for i in range(cfg["num_hidden_layers"]):
        x = blk({k: params[f] for k, f in
                 stage_leaves(cfg, f"blocks.{i}").items()}, x)
    head = _jit(("head", prec, rows), lambda p, x_, lo: final_logits(
        p, jax.lax.dynamic_slice_in_dim(x_, lo, rows, axis=1), prec)[0])
    return head({k: params[f] for k, f in
                 stage_leaves(cfg, "head").items()}, x, first_row)


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
