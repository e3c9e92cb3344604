"""GPT-3-style decoder — the flagship pretraining model.

Role parity: PaddleNLP gpt-3 recipe the reference benchmarks
(BASELINE.json: "GPT-3 1.3B tokens/sec/chip"). TPU-first design:

  * bf16 params/activations, fp32 LayerNorm + softmax + loss
  * flash attention (Pallas) on the causal path
  * per-block jax.checkpoint (remat) — activation memory ~O(L·1 block)
  * tensor parallel via GSPMD partition specs on qkv/proj/mlp/vocab
    (see distributed/fleet/meta_parallel.py for the mechanism)
  * sequence-parallel activation constraints over 'sp' when that axis >1
  * tied input/output embedding (logits = h @ E^T)
"""
import dataclasses
import math
from functools import partial

import jax
import jax.numpy as jnp

from .. import nn
from ..framework.core import Tensor, apply_op
from ..nn import functional as F
from ..nn.initializer import Constant, Normal
from ..nn.layer_base import Layer, functional_call

__all__ = ["GPTConfig", "GPT", "GPTPretrainingCriterion",
           "gpt_tiny", "gpt_125m", "gpt_350m", "gpt_760m", "gpt_1p3b"]


def _remat_policy(name):
    """Map config string -> jax.checkpoint policy. 'dots' saves matmul
    results so the backward skips recomputing the FLOPs-heavy ops (the 6N
    heuristic's extra-fwd cost) in exchange for per-layer matmul-activation
    memory."""
    if name == "dots":
        return jax.checkpoint_policies.dots_with_no_batch_dims_saveable
    if name not in ("full", "none"):
        raise ValueError(f"remat_policy must be 'full', 'dots' or 'none', "
                         f"got {name!r}")
    return jax.checkpoint_policies.nothing_saveable


@dataclasses.dataclass
class GPTConfig:
    vocab_size: int = 50304          # multiple of 128 → clean vocab sharding
    hidden_size: int = 768
    num_layers: int = 12
    num_heads: int = 12
    ffn_hidden: int = 0              # 0 → 4*hidden
    max_seq_len: int = 1024
    dropout: float = 0.0
    sp_mode: str = "ring"            # 'ring' | 'zigzag' | 'ulysses' seq par
    #   'zigzag': load-balanced causal ring (2x less attention compute at
    #   large sp; see ops/ring_attention.py)
    dtype: str = "bfloat16"          # compute/param dtype
    remat: bool = True               # jax.checkpoint each block
    remat_policy: str = "full"       # 'full' (recompute all) | 'dots' (save
    #   matmul outputs: ~4/3 fewer flops in bwd at the cost of ~per-layer
    #   matmul-activation memory) | 'none' ≈ remat=False
    tie_embeddings: bool = True
    init_std: float = 0.02
    tp_overlap: str = "off"          # tensor-parallel collective dispatch at
    #   the two row-parallel sites (attention proj, fc2): 'off' leaves the
    #   dots to GSPMD (bulk psum, the COLL-SERIALIZED shape), 'bulk' issues
    #   the explicit shard_map psum twin, 'ring' the chunked ring-overlapped
    #   path (ops/overlap.py) — bit-identical to 'bulk' by the twin pin
    tp_overlap_chunks: int = 4       # free-dim tiles per overlapped site

    def __post_init__(self):
        if self.ffn_hidden == 0:
            self.ffn_hidden = 4 * self.hidden_size
        if self.sp_mode not in ("ring", "zigzag", "ulysses"):
            raise ValueError(f"sp_mode must be 'ring', 'zigzag' or "
                             f"'ulysses', got {self.sp_mode!r}")
        if self.tp_overlap not in ("off", "bulk", "ring"):
            raise ValueError(f"tp_overlap must be 'off', 'bulk' or "
                             f"'ring', got {self.tp_overlap!r}")

    @property
    def head_dim(self):
        return self.hidden_size // self.num_heads

    def num_params(self):
        h, L, v = self.hidden_size, self.num_layers, self.vocab_size
        per_block = 4 * h * h + 2 * h * self.ffn_hidden + 9 * h + 2 * self.ffn_hidden
        return v * h + self.max_seq_len * h + L * per_block + 2 * h


class GPTBlock(Layer):
    """Pre-LN decoder block. qkv/out and mlp projections carry 'tp'
    partition specs; with tp=1 those specs are inert."""

    def __init__(self, cfg: GPTConfig, layer_idx: int):
        super().__init__()
        h = cfg.hidden_size
        self.cfg = cfg
        init = Normal(0.0, cfg.init_std)
        # scaled init on residual-out projections (GPT-2/3 recipe)
        out_init = Normal(0.0, cfg.init_std / math.sqrt(2.0 * cfg.num_layers))
        self.ln1 = nn.LayerNorm(h)
        self.qkv = nn.Linear(h, 3 * h, weight_attr=nn.ParamAttr(initializer=init))
        self.qkv.weight.partition_spec = (None, "tp")
        self.qkv.bias.partition_spec = ("tp",)
        self.proj = nn.Linear(h, h, weight_attr=nn.ParamAttr(initializer=out_init))
        self.proj.weight.partition_spec = ("tp", None)
        self.ln2 = nn.LayerNorm(h)
        self.fc1 = nn.Linear(h, cfg.ffn_hidden, weight_attr=nn.ParamAttr(initializer=init))
        self.fc1.weight.partition_spec = (None, "tp")
        self.fc1.bias.partition_spec = ("tp",)
        self.fc2 = nn.Linear(cfg.ffn_hidden, h, weight_attr=nn.ParamAttr(initializer=out_init))
        self.fc2.weight.partition_spec = ("tp", None)

    def forward(self, x, cache=None, pos=None):
        cfg = self.cfg
        B, L = x.shape[0], x.shape[1]
        res = x
        y = self.ln1(x)
        with jax.named_scope("attention"):
            qkv = self.qkv(y)
            from ..tensor.manipulation import reshape
            qkv = reshape(qkv, [B, L, 3, cfg.num_heads, cfg.head_dim])
            q, k, v = qkv[:, :, 0], qkv[:, :, 1], qkv[:, :, 2]
            if cache is not None:
                attn, cache = self._attend_cached(q, k, v, cache, pos)
            else:
                from ..distributed.mesh import get_mesh
                mesh = get_mesh(create_default=False)
                if mesh is not None and mesh.shape.get("sp", 1) > 1:
                    # sequence parallel over the 'sp' ICI axis: exact ring
                    # attention, or Ulysses all-to-all head-resharding when
                    # configured and the head count divides
                    if cfg.sp_mode == "ulysses":
                        # ops/ulysses.py raises if heads don't divide 'sp' —
                        # an explicit error beats silently measuring ring
                        from ..ops.ulysses import ulysses_attention
                        attn = apply_op(
                            lambda qv, kv, vv: ulysses_attention(
                                qv, kv, vv, mesh=mesh, causal=True), q, k, v)
                    else:
                        from ..ops.ring_attention import ring_attention
                        layout = ("zigzag" if cfg.sp_mode == "zigzag"
                                  else "contiguous")
                        attn = apply_op(
                            lambda qv, kv, vv: ring_attention(
                                qv, kv, vv, mesh=mesh, causal=True,
                                layout=layout),
                            q, k, v)
                else:
                    attn = F.scaled_dot_product_attention(q, k, v, is_causal=True,
                                                          dropout_p=cfg.dropout,
                                                          training=self.training)
            attn = reshape(attn, [B, L, cfg.hidden_size])
            x = res + self._row_parallel(self.proj, attn)
        res = x
        y = self.ln2(x)
        with jax.named_scope("mlp"):
            y = self._row_parallel(self.fc2, F.gelu(self.fc1(y),
                                                    approximate=True))
        out = res + y
        return out if cache is None else (out, cache)

    def _row_parallel(self, linear, x):
        """The two convicted COLL-SERIALIZED sites: a row-parallel dot
        whose tp all-reduce GSPMD dispatches as one bulk psum nothing
        can hide behind. With cfg.tp_overlap='ring' the dot+psum goes
        through ops/overlap.py's chunked ring (per-chunk ppermutes
        overlap the neighbour chunks' dots); 'bulk' is the explicit
        shard_map psum twin (the A/B reference, bit-identical to
        'ring'); 'off' keeps the plain Linear."""
        cfg = self.cfg
        if cfg.tp_overlap != "off":
            from ..distributed.mesh import get_mesh
            mesh = get_mesh(create_default=False)
            if mesh is not None and mesh.shape.get("tp", 1) > 1:
                from ..ops.overlap import overlap_matmul_all_reduce
                impl = "ring" if cfg.tp_overlap == "ring" else "bulk"
                return apply_op(
                    lambda a, wt, b: overlap_matmul_all_reduce(
                        a, wt, axis="tp",
                        n_chunks=cfg.tp_overlap_chunks,
                        mesh=mesh, impl=impl) + b,
                    x, linear.weight, linear.bias)
        return linear(x)

    def _attend_cached(self, q, k, v, cache, pos):
        """Decode-time attention against a static KV buffer (lengths stay
        compile-time constant; validity enforced by position mask)."""
        import math as _math

        def _f(qv, kv, vv, k_buf, v_buf, p):
            k_buf = jax.lax.dynamic_update_slice(k_buf, kv.astype(k_buf.dtype),
                                                 (0, p, 0, 0))
            v_buf = jax.lax.dynamic_update_slice(v_buf, vv.astype(v_buf.dtype),
                                                 (0, p, 0, 0))
            Lq = qv.shape[1]
            Lmax = k_buf.shape[1]
            scale = 1.0 / _math.sqrt(qv.shape[-1])
            qh = jnp.swapaxes(qv, 1, 2).astype(jnp.float32) * scale
            kh = jnp.swapaxes(k_buf, 1, 2).astype(jnp.float32)
            vh = jnp.swapaxes(v_buf, 1, 2).astype(jnp.float32)
            s = qh @ jnp.swapaxes(kh, -1, -2)  # [B,H,Lq,Lmax]
            q_pos = p + jax.lax.broadcasted_iota(jnp.int32, (Lq, Lmax), 0)
            k_pos = jax.lax.broadcasted_iota(jnp.int32, (Lq, Lmax), 1)
            s = jnp.where(q_pos >= k_pos, s, -1e30)
            probs = jax.nn.softmax(s, axis=-1)
            out = jnp.swapaxes(probs @ vh, 1, 2).astype(qv.dtype)
            return out, k_buf, v_buf

        pos_v = pos._value if isinstance(pos, Tensor) else pos
        res = apply_op(lambda qv, kv, vv, kb, vb: _f(qv, kv, vv, kb, vb, pos_v),
                       q, k, v, cache[0], cache[1])
        out, k_buf, v_buf = res
        return out, (k_buf, v_buf)


class GPT(Layer):
    def __init__(self, cfg: GPTConfig):
        super().__init__()
        self.cfg = cfg
        init = Normal(0.0, cfg.init_std)
        self.wte = nn.Embedding(cfg.vocab_size, cfg.hidden_size,
                                weight_attr=nn.ParamAttr(initializer=init))
        self.wte.weight.partition_spec = ("tp", None)  # vocab-parallel
        self.wpe = nn.Embedding(cfg.max_seq_len, cfg.hidden_size,
                                weight_attr=nn.ParamAttr(initializer=init))
        self.drop = nn.Dropout(cfg.dropout)
        self.blocks = nn.LayerList([GPTBlock(cfg, i) for i in range(cfg.num_layers)])
        self.ln_f = nn.LayerNorm(cfg.hidden_size)
        if not cfg.tie_embeddings:
            self.lm_head = nn.Linear(cfg.hidden_size, cfg.vocab_size,
                                     weight_attr=nn.ParamAttr(initializer=init),
                                     bias_attr=False)
            self.lm_head.weight.partition_spec = (None, "tp")

    def _run_block(self, block, x):
        """Apply one block, optionally under jax.checkpoint: the block's
        params become explicit inputs of a pure function so XLA rematerializes
        its activations in the backward pass instead of storing them."""
        if not self.cfg.remat or self.cfg.remat_policy == "none":
            return block(x)
        names = [n for n, _ in block.named_parameters()]
        vals = [p._value for _, p in block.named_parameters()]

        @partial(jax.checkpoint, policy=_remat_policy(self.cfg.remat_policy))
        def pure_block(pvals, xv):
            with functional_call(block, dict(zip(names, pvals))):
                out = block(Tensor(xv))
            return out._value

        return apply_op(lambda xv, *pv: pure_block(list(pv), xv), x, *vals)

    def init_cache(self, batch_size, max_len):
        """Decode KV cache: per-block (k, v) buffers [B, max_len, H, D]."""
        cfg = self.cfg
        d = jnp.dtype(cfg.dtype)
        shape = (batch_size, max_len, cfg.num_heads, cfg.head_dim)
        return [(jnp.zeros(shape, d), jnp.zeros(shape, d)) for _ in self.blocks]

    def forward(self, input_ids, cache=None, pos=0):
        cfg = self.cfg
        B, L = input_ids.shape[0], input_ids.shape[1]
        from ..tensor.creation import arange
        positions = arange(L, dtype="int32") + pos if cache is not None \
            else arange(L, dtype="int32")
        x = self.wte(input_ids) + self.wpe(positions)
        x = x.astype(cfg.dtype)
        # batch over data axes, sequence over 'sp' (GSPMD inserts the
        # gather/scatter collectives around attention when sp > 1)
        from ..distributed.sharding_utils import constraint
        from ..distributed.mesh import get_mesh
        if cache is None and get_mesh(create_default=False) is not None:
            x = constraint(x, ("dp", "fsdp"), "sp", None)
        x = self.drop(x)
        if cache is not None:
            new_cache = []
            for block, c in zip(self.blocks, cache):
                x, c = block(x, cache=c, pos=pos)
                new_cache.append(c)
        else:
            for block in self.blocks:
                x = self._run_block(block, x)
        x = self.ln_f(x)
        # tied head: [B,L,H] @ [H,V] — the big MXU matmul; fp32 accum via
        # preferred_element_type to keep loss numerics honest in bf16
        with jax.named_scope("lm_head"):
            if cfg.tie_embeddings:
                logits = apply_op(
                    lambda h, e: jax.lax.dot_general(
                        h, e, (((2,), (1,)), ((), ())),
                        preferred_element_type=jnp.float32),
                    x, self.wte.weight)
            else:
                logits = self.lm_head(x)
        return logits if cache is None else (logits, new_cache)


class GPTPretrainingCriterion(Layer):
    """Causal LM loss (fp32), ignoring pad label -100.

    On TPU-friendly shapes the loss runs through the fused Pallas
    softmax-cross-entropy kernel (ops/fused_ops.py — one vocab pass forward,
    (softmax - onehot)·g backward without a second fp32 prob tensor);
    otherwise the jnp cross_entropy path."""

    def forward(self, logits, labels):
        with jax.named_scope("loss"):
            V = logits.shape[-1]
            from ..tensor.manipulation import reshape
            flat = reshape(logits, [-1, V])
            flat_labels = reshape(labels, [-1])
            n = flat.shape[0]
            from ..ops.fused_ops import can_fuse_xent
            if can_fuse_xent(n, V):
                from ..framework.core import apply_op
                from ..ops.fused_ops import fused_softmax_cross_entropy

                def _f(lg, lab):
                    lab = lab.astype(jnp.int32)
                    valid = lab >= 0
                    rows = fused_softmax_cross_entropy(lg, jnp.maximum(lab, 0))
                    rows = jnp.where(valid, rows, 0.0)
                    return jnp.sum(rows) / jnp.maximum(jnp.sum(valid), 1)
                return apply_op(_f, flat, flat_labels)
            return F.cross_entropy(flat, flat_labels, ignore_index=-100, reduction="mean")


def _preset(kw, **defaults):
    """Config factory body: caller kwargs override the preset's fields."""
    defaults.update(kw)
    return GPTConfig(**defaults)


def gpt_tiny(**kw):
    return _preset(kw, vocab_size=1024, hidden_size=128, num_layers=2,
                   num_heads=4, max_seq_len=256)


def gpt_125m(**kw):
    return _preset(kw, hidden_size=768, num_layers=12, num_heads=12)


def gpt_350m(**kw):
    return _preset(kw, hidden_size=1024, num_layers=24, num_heads=16)


def gpt_760m(**kw):
    return _preset(kw, hidden_size=1536, num_layers=24, num_heads=16)


def gpt_1p3b(**kw):
    return _preset(kw, hidden_size=2048, num_layers=24, num_heads=16)


# ---------------------------------------------------------------------------
# Stacked-layer GPT: scan-over-layers (fast compile) + pipeline parallelism
# ---------------------------------------------------------------------------
class GPTStacked(Layer):
    """GPT with all decoder blocks stored as STACKED parameters
    ([num_layers, ...]).

    Why: (a) lax.scan over the layer dim compiles O(1) in depth instead of
    O(L); (b) the 'pp' mesh axis shards the layer dim, and the same stacked
    layout feeds the GPipe schedule in distributed/pipeline.py — the TPU
    rendering of reference fleet meta_parallel/pipeline_parallel.py.
    Attention uses the jnp path (GSPMD-sharded); dropout is not applied
    inside stacked blocks.

    With pp_schedule="interleaved" the layer stack is stored in virtual-
    chunk schedule order (permuted once at construction, so the compiled
    step never reshards it). Checkpoints saved from such a model are in
    that order: load them only into a model built with the same pp degree
    and pp_virtual, or convert rows via layer_storage_order(). Running the
    model under a different mesh raises.
    """

    def __init__(self, cfg: GPTConfig, pp_microbatches: int = 4,
                 pp_schedule: str = "1f1b", pp_virtual: int = 2):
        super().__init__()
        self.cfg = cfg
        self.pp_microbatches = pp_microbatches
        self.pp_schedule = pp_schedule
        self.pp_virtual = pp_virtual
        h, f, L = cfg.hidden_size, cfg.ffn_hidden, cfg.num_layers
        init = Normal(0.0, cfg.init_std)
        out_init = Normal(0.0, cfg.init_std / math.sqrt(2.0 * cfg.num_layers))
        self.wte = nn.Embedding(cfg.vocab_size, cfg.hidden_size,
                                weight_attr=nn.ParamAttr(initializer=init))
        self.wte.weight.partition_spec = ("tp", None)
        self.wpe = nn.Embedding(cfg.max_seq_len, cfg.hidden_size,
                                weight_attr=nn.ParamAttr(initializer=init))
        self.ln_f = nn.LayerNorm(h)

        def mk(name, shape, initializer, spec):
            p = self.create_parameter(shape, default_initializer=initializer)
            p.partition_spec = spec
            self.add_parameter(name, p)

        one, zero = Constant(1.0), Constant(0.0)
        mk("ln1_w", [L, h], one, ("pp", None))
        mk("ln1_b", [L, h], zero, ("pp", None))
        mk("qkv_w", [L, h, 3 * h], init, ("pp", None, "tp"))
        mk("qkv_b", [L, 3 * h], zero, ("pp", "tp"))
        mk("proj_w", [L, h, h], out_init, ("pp", "tp", None))
        mk("proj_b", [L, h], zero, ("pp", None))
        mk("ln2_w", [L, h], one, ("pp", None))
        mk("ln2_b", [L, h], zero, ("pp", None))
        mk("fc1_w", [L, h, f], init, ("pp", None, "tp"))
        mk("fc1_b", [L, f], zero, ("pp", "tp"))
        mk("fc2_w", [L, f, h], out_init, ("pp", "tp", None))
        mk("fc2_b", [L, h], zero, ("pp", None))

        # Interleaved schedule: store the layer stack in the device-major
        # virtual-chunk order ONCE, so the compiled step never reshards the
        # whole stack (a per-step all-to-all otherwise). state_dict() then
        # holds layers in schedule order; `layer_storage_order()` gives the
        # original-index-of-row mapping for checkpoint conversion.
        self._pp_perm = None
        self._pp_perm_stages = None
        if pp_schedule.startswith("interleaved"):
            from ..distributed.mesh import get_mesh
            from ..distributed.pipeline import _interleave_perm
            mesh = get_mesh(create_default=False)
            S = mesh.shape.get("pp", 1) if mesh is not None else 1
            if S > 1 and L % (S * pp_virtual) == 0:
                perm = _interleave_perm(L, S, pp_virtual)
                for k in self._BLOCK_KEYS:
                    p = self._parameters[k]
                    p._value = jnp.take(p._value, jnp.asarray(perm), axis=0)
                self._pp_perm = perm
                self._pp_perm_stages = S

    def layer_storage_order(self):
        """Row i of every stacked parameter holds the weights of ORIGINAL
        layer `layer_storage_order()[i]` (identity unless the interleaved
        schedule permuted storage at construction)."""
        import numpy as np
        if self._pp_perm is None:
            return np.arange(self.cfg.num_layers)
        return np.asarray(self._pp_perm)

    _BLOCK_KEYS = ("ln1_w", "ln1_b", "qkv_w", "qkv_b", "proj_w", "proj_b",
                   "ln2_w", "ln2_b", "fc1_w", "fc1_b", "fc2_w", "fc2_b")

    def _block_step(self, p, xv):
        """One decoder block on raw arrays. p: one layer's param dict."""
        cfg = self.cfg

        def ln(z, w, b):
            z32 = z.astype(jnp.float32)
            mu = jnp.mean(z32, -1, keepdims=True)
            var = jnp.mean(jnp.square(z32 - mu), -1, keepdims=True)
            return ((z32 - mu) * jax.lax.rsqrt(var + 1e-5)).astype(z.dtype) \
                * w.astype(z.dtype) + b.astype(z.dtype)

        B, L = xv.shape[0], xv.shape[1]
        y = ln(xv, p["ln1_w"], p["ln1_b"])
        qkv = y @ p["qkv_w"].astype(y.dtype) + p["qkv_b"].astype(y.dtype)
        qkv = qkv.reshape(B, L, 3, cfg.num_heads, cfg.head_dim)
        from ..ops.attention import flash_raw_or_reference
        attn = flash_raw_or_reference(qkv[:, :, 0], qkv[:, :, 1],
                                      qkv[:, :, 2], causal=True)
        attn = attn.reshape(B, L, cfg.hidden_size)
        xv = xv + attn @ p["proj_w"].astype(y.dtype) + p["proj_b"].astype(y.dtype)
        y = ln(xv, p["ln2_w"], p["ln2_b"])
        y = jax.nn.gelu(y @ p["fc1_w"].astype(y.dtype) + p["fc1_b"].astype(y.dtype),
                        approximate=True)
        return xv + y @ p["fc2_w"].astype(y.dtype) + p["fc2_b"].astype(y.dtype)

    def _stage_fn(self, params_local, xv):
        """Apply a contiguous slice of layers (scan + per-layer remat)."""
        step = self._block_step
        if self.cfg.remat and self.cfg.remat_policy != "none":
            step = jax.checkpoint(step, policy=_remat_policy(self.cfg.remat_policy))

        def body(carry, pslice):
            return step(pslice, carry), None

        out, _ = jax.lax.scan(body, xv, params_local)
        return out

    def forward(self, input_ids):
        cfg = self.cfg
        from ..tensor.creation import arange
        from ..distributed.mesh import get_mesh
        from ..distributed.pipeline import pipeline_apply

        L = input_ids.shape[1]
        pos = arange(L, dtype="int32")
        x = self.wte(input_ids) + self.wpe(pos)
        x = x.astype(cfg.dtype)
        mesh = get_mesh(create_default=False)
        if self._pp_perm is not None:
            # storage is baked in schedule order for a specific pp degree;
            # running under any other mesh would apply layers out of order
            pp_now = mesh.shape.get("pp", 1) if mesh is not None else 1
            if pp_now != self._pp_perm_stages:
                raise RuntimeError(
                    f"GPTStacked was built with interleaved layer storage "
                    f"for pp={self._pp_perm_stages}, but the current mesh "
                    f"has pp={pp_now}. Rebuild the model under the target "
                    f"mesh (see layer_storage_order() for checkpoint "
                    f"conversion).")
        stacked_names = list(self._BLOCK_KEYS)
        stacked_tensors = [self._parameters[k] for k in stacked_names]
        n_micro = self.pp_microbatches

        def run(xv, *pvals):
            stacked = dict(zip(stacked_names, pvals))
            if mesh is not None and mesh.shape.get("pp", 1) > 1:
                return pipeline_apply(self._stage_fn, stacked, xv, n_micro,
                                      mesh=mesh, schedule=self.pp_schedule,
                                      virtual=self.pp_virtual,
                                      pre_permuted=self._pp_perm is not None)
            return self._stage_fn(stacked, xv)

        x = apply_op(run, x, *stacked_tensors)
        x = self.ln_f(x)
        logits = apply_op(
            lambda h, e: jax.lax.dot_general(
                h, e, (((2,), (1,)), ((), ())), preferred_element_type=jnp.float32),
            x, self.wte.weight)
        return logits


def graph_contract(cfg):
    """Graph Doctor contract (paddle_tpu.analysis): dot_general count of
    the CPU-lowered eval forward — 4 projections per block (qkv, proj,
    fc1, fc2) + 2 attention matmuls (qk, av) per block on the reference
    attention path + the tied lm_head."""
    return {"dot_general": cfg.num_layers * 6 + 1}


# by-design activation transposes of the reference attention path: the
# [B,L,H,D]<->[B,H,L,D] head moves and the k^T flip. On TPU the Pallas
# flash kernel owns layout in-kernel; on the CPU-lowered graph these are
# the algorithm, not a layout regression (Graph Doctor exemptions).
ATTENTION_TRANSPOSES = (r"dims = \[0, 2, 1, 3\]", r"dims = \[0, 1, 3, 2\]")
