"""Paged decode executor for latent-attention (MLA) expert models: the
second decoder behind `ContinuousBatchingEngine`, beside `PagedGPTDecoder`.

What differs from the GPT decoder, by mechanism:

* ONE latent pool `[entries, pages, page_size, kv_lora_rank + rope_dim]`
  in place of `k_pages`/`v_pages` `[L, P, ps, H, D]`: a token costs
  `latent_dim x itemsize` bytes an attention whatever the number of heads
  (`kv_token_bytes`; 1,152 B at the published widths in bfloat16, where
  full keys and values would be 81,920 B). An entry is one ATTENTION's
  cache: a layer has as many as its family says (`cache_entries`; entry
  `layer x cache_entries + j` is attention j's).
* Attention in two forms over that one pool, chosen BY ROW KIND and never
  by a knob: a row that takes prompt chunks attends through MATERIALISED
  heads, a row that decodes through ABSORBED projections, side by side in
  one mixed horizon (`ops.mla_paged_attention_packed`).
* The BLOCK IS THE MODEL FAMILY'S (`FAMILIES`, by `cfg.family`): this
  decoder stacks each run of equal layers, scans it with the pool in the
  carry, and gives the family's `block` an `attend(j, y, w)` that
  projects, writes entry j of the layer in place at `[entry, page,
  offset]`, attends in both forms and projects out. What a family's
  entry states: `cache_entries` (a layer), `counters` (names of the
  int32 counts its blocks return, summed here over layers), `runs(cfg)`
  ([(kind, first layer, layers)]), `leaves(kind)` ({key: parameter name
  under "layers.<i>."}; keys that start with "kv_b" are laid out [rank,
  heads, nope + v]), `whole(kind)` (keys read by (layer, expert), not
  sliced by the scan) and `block(cfg, kind, x, wl, seg, ri, attend,
  valid)` -> (x, counts). DeepSeek-V2 (attention then one MLP, dense or
  group-limited experts with shared ones) is the first entry, LongCat-
  Flash (two attentions, two dense MLPs and a shortcut-connected expert
  branch with identity experts a layer) the second.
* RMSNorm, rotary positions in place of a position table, gated SiLU
  MLPs without biases, an untied head, and dropless expert layers told
  which experts they hold (`models.deepseek_v2.held_expert_walk`: one
  grouped product a projection over the pairs sorted by held expert,
  which reads a hit expert's matrices in place in the stack the scan
  does not slice).

What it keeps: the engine reaches a decoder only through `ragged_multi`,
`prefill_suffix_batch`, `copy_page`, `program_name`/`first_use`,
`pend_capacity`, `step_hbm_bytes`, `kv_page_bytes`, `cache_fingerprint`
and the plain attributes (`num_pages`, `page_size`, `max_batch`,
`max_pages`, `kv_quant`, `lora`, `n_adapters`, `sampling`,
`cfg.max_seq_len`, `cfg.num_params()`), and the packed token-stream
layout and tick themselves: `decoder.packed_tick` and
`decoder.packed_prefill_layout` are the one definition both decoders run.

Only the engine's default path exists here: packed ragged horizons and
packed chunked prefill, greedy. Every other option RAISES at construction
(`quant`, `kv_quant`, `use_kernel`, sampling, `mesh`/tp)
or when an engine is built over it (`engine_refusals`: prefix cache, host
tier, speculation, the dispatch-separate and per-tick loops); adapters
have no attach method. Nothing falls back silently.
"""
import functools
import weakref

import jax
import jax.numpy as jnp
import numpy as np

from ..models import deepseek_v2, longcat_flash
from ..models.deepseek_v2 import (mla_project, rms_norm, softmax_scale,
                                  yarn_inv_freq)
from ..ops.ragged_paged_attention import mla_paged_attention_packed
from .decoder import (RaggedMultiOut, _named_jit, packed_prefill_layout,
                      packed_tick, packed_window, pow2_at_least)

__all__ = ["PagedMLADecoder", "FAMILIES", "latent_token_bytes"]

# a model family's block, leaves, cache entries and counters, by the
# `family` its config names (the module's docstring has the contract)
FAMILIES = {"deepseek_v2": deepseek_v2.Serving,
            "longcat_flash": longcat_flash.Serving}


_stack = jax.jit(lambda *arrays: jnp.stack(arrays))


def latent_token_bytes(cfg, itemsize=2):
    """Cache bytes ONE token costs in one attention: the latent row."""
    return int(cfg.latent_dim * itemsize)


class PagedMLADecoder:
    """Stacked-weight MLA/expert decode executor over a paged latent
    pool (see the module's docstring)."""

    kind = "mla"
    # the absorbed form copies every column of the table it is handed
    # (see `PagedGPTDecoder.walk_block_pages`)
    walk_block_pages = None
    # engine options this decoder cannot serve: {option: why}. The engine
    # raises at construction when one of them is asked for.
    engine_refusals = {
        "prefix_cache": "the cache's save/load and its page audits read "
                        "k_pages/v_pages; the latent pool has neither",
        "host_tier": "the host tier moves k_pages/v_pages payloads",
        "speculation": "there is no verify program over the latent pool",
        "ragged=False": "only the mixed ragged horizon is built (no "
                        "decode_multi, no per-tick decode)",
    }

    def __init__(self, model, num_pages=128, page_size=16, max_batch=8,
                 max_pages_per_seq=None, quant=None, kv_quant=None,
                 use_kernel=False, dtype=None, temperature=0.0, top_k=0,
                 top_p=1.0, mesh=None, release_model=False):
        cfg = model.cfg
        refused = {
            "quant": quant, "kv_quant": kv_quant, "mesh": mesh,
            "use_kernel": use_kernel or None,
            "temperature": temperature or None, "top_k": top_k or None,
            "top_p": None if top_p == 1.0 else top_p,
            "dtype": None if dtype is None
            or jnp.dtype(dtype) == jnp.dtype(cfg.dtype) else dtype}
        asked = sorted(k for k, v in refused.items() if v is not None)
        if asked:
            raise NotImplementedError(
                f"PagedMLADecoder does not support {asked}: it serves "
                "greedy, unquantized, on one chip, through the packed "
                "layout, in the model's own dtype")
        from ..distributed.mesh import get_mesh
        m = get_mesh(create_default=False)
        if m is not None and m.shape.get("tp", 1) > 1:
            raise NotImplementedError(
                "PagedMLADecoder does not shard: a tp mesh is active")
        self.cfg = cfg
        self.family = fam = FAMILIES[cfg.family]
        # what `ragged_multi`'s `real` block carries beside each tick's
        # real token count, a column each: the engine sums them over a
        # horizon's ticks into its record under these names
        self.horizon_counters = tuple(fam.counters) + (
            "absorbed_rows", "materialised_tokens")
        self.page_size, self.num_pages = int(page_size), int(num_pages)
        self.max_batch = int(max_batch)
        self.max_pages = max_pages_per_seq or \
            (cfg.max_seq_len + page_size - 1) // page_size
        self.sampling = None                         # greedy: no seed used
        self.kv_quant = self.lora = None
        self.n_adapters = 0
        self.compute_dtype = dt = jnp.dtype(cfg.dtype)
        self.inv_freq = jnp.asarray(yarn_inv_freq(cfg), jnp.float32)
        self.weights = self._stack_weights(model, release_model)
        self.latent_pages = jnp.zeros(
            (cfg.num_layers * fam.cache_entries, num_pages, page_size,
             cfg.latent_dim), dt)
        self._packeds = {}          # (k, t, window, width) -> program
        self._packed_prefills = {}  # (t, window) -> program
        self._copy = None
        self._used = set()
        self._engines = weakref.WeakSet()

    # ------------------------------------------------------- the weights

    def _stack_weights(self, model, release):
        """The model's parameters as the programs' one argument: runs of
        equal layers stacked ([n, ...] a leaf), on the device, one kind of
        leaf at a time. The Layer stays as it was, so the device then
        holds its arrays beside the stacks. With `release` the decoder
        takes each leaf's arrays out of the Layer once their stack has
        landed: beside one set of weights there is never more than the
        stack being made (the held experts' `gate_proj` of five layers is
        1.6 GB at the published widths), and the Layer ends empty."""
        cfg = self.cfg
        named = dict(model.named_parameters())

        def take(names, stack=True):
            vals = [named[n]._value for n in names]
            # one program a stack: eager `jnp.stack` first copies every
            # array to its expanded shape, a second set of the leaf
            out = _stack(*vals) if stack else vals[0]
            if release:
                out.block_until_ready()
                for n in names:
                    named[n]._value = None
            return out

        fam = self.family
        H, r = cfg.num_heads, cfg.kv_lora_rank
        self._runs = fam.runs(cfg)
        segments = []
        for kind, first, n in self._runs:
            w = {k: take([f"layers.{i}.{leaf}"
                          for i in range(first, first + n)])
                 for k, leaf in fam.leaves(kind).items()}
            for k in w:
                if k.startswith("kv_b"):
                    w[k] = w[k].reshape(n, r, H, -1)
            segments.append(w)
        return {"embed": take(["embed_tokens.weight"], stack=False),
                "norm": take(["norm.weight"], stack=False),
                "head": take(["lm_head.weight"], stack=False),
                "segments": segments}

    # -------------------------------------------------------- identities

    def first_use(self, program):
        """True the first time `program` is asked about (see
        `PagedGPTDecoder.first_use`)."""
        if program in self._used:
            return False
        self._used.add(program)
        return True

    @staticmethod
    @functools.lru_cache(maxsize=None)
    def program_name(kind, k, x, width, window=None):
        """`PagedGPTDecoder.program_name`'s key under this decoder's
        kind: a trace tells an MLA horizon from a GPT one by its name."""
        if kind != "packed":
            raise NotImplementedError(
                f"PagedMLADecoder has no {kind!r} program")
        return f"mla_packed_multi_k{k}_t{x}_w{window}_p{width}"

    @property
    def pend_capacity(self):
        return self.max_pages * self.page_size

    @property
    def kv_token_bytes(self):
        """Cache bytes one token costs in one attention (the latent row;
        a layer has `family.cache_entries` of them)."""
        return latent_token_bytes(self.cfg, self.compute_dtype.itemsize)

    def kv_token_bytes_by_layer(self):
        return [self.kv_token_bytes * self.family.cache_entries] \
            * self.cfg.num_layers

    @property
    def kv_page_bytes(self):
        return int(self.page_size * sum(self.kv_token_bytes_by_layer()))

    def step_hbm_bytes(self, avg_ctx=None, batch=None):
        """HBM bytes ONE decode tick moves at most: every weight byte
        held (the held experts too: a tick reads those its rows select,
        `experts_hit` counts them) plus each slot's latent rows at
        `avg_ctx` (default: half a sequence's pool capacity)."""
        if avg_ctx is None:
            avg_ctx = max(self.pend_capacity // 2, 1)
        if batch is None:
            batch = self.max_batch
        return int(self.cfg.num_params() * self.compute_dtype.itemsize
                   + batch * avg_ctx * sum(self.kv_token_bytes_by_layer()))

    def cache_fingerprint(self):
        """Identity of this decoder's cache bytes (weights, shapes, page
        size, dtype): see `PagedGPTDecoder.cache_fingerprint`."""
        cfg = self.cfg
        probes = tuple(float(jnp.sum(v.astype(jnp.float32)))
                       for v in jax.tree_util.tree_leaves(self.weights))
        return repr((self.kind, cfg.family, cfg.num_layers, cfg.hidden_size,
                     cfg.num_heads, cfg.latent_dim, cfg.vocab_size,
                     cfg.expert_offset, cfg.experts_held, self.page_size,
                     str(self.compute_dtype), probes)).encode()

    # ------------------------------------------------------ the programs

    def _layer(self, kind, seg_w, pids, offs, table, rows, pos, row_new,
               mat_rows, valid, window):
        """One layer over the packed stream as a scan body: carry
        (x [T, h], the whole latent pool, the family's counters), xs
        (the layer's weights, its first entry in the pool, its index in
        its run). The family's block says where the layer's attentions
        sit; `attend` is what each of them is here."""
        cfg, fam = self.cfg, self.family
        T = rows.shape[0]
        scale = softmax_scale(cfg)

        def layer(carry, xs):
            x, pool, *counts = carry
            wl, entry0, ri = xs
            pools = [pool]

            def attend(j, y, w):
                """Attention j of this layer over normed tokens y: its
                rows written into ITS entry of the pool, read back through
                both forms, projected out. [T, h]."""
                entry = entry0 + j if j else entry0
                q_nope, q_rope, latent = mla_project(w, y, pos, cfg,
                                                     self.inv_freq)
                with jax.named_scope("latent_write"):
                    pools[0] = pools[0].at[entry, pids, offs].set(latent)
                attn = mla_paged_attention_packed(
                    q_nope, q_rope, pools[0], entry, w["kv_b"], table, rows,
                    pos, row_new, mat_rows, scale, window=window)
                return jnp.dot(attn.reshape(T, -1), w["o"],
                               preferred_element_type=jnp.float32
                               ).astype(y.dtype)

            x, added = fam.block(cfg, kind, x, wl, seg_w, ri, attend, valid)
            if added:
                counts = [c + a for c, a in zip(counts, added)]
            return (x, pools[0], *counts), None

        return layer

    def _packed_forward(self, weights, pool, ptok, pos, rows, write_ok,
                        table, last_idx, live, row_new, mat_rows, window):
        """The shared PACKED forward (the layout and the arguments of
        `PagedGPTDecoder._packed_forward`; `row_new` [S] the stream
        tokens of each row, `mat_rows` [S] the rows that take prompt
        chunks). Returns (next [S], pool, the family's counters)."""
        fam = self.family
        ps, MP = self.page_size, table.shape[1]
        x = weights["embed"][ptok].astype(self.compute_dtype)
        pids = table[rows, jnp.minimum(pos // ps, MP - 1)]
        pids = jnp.where(write_ok, pids, self.num_pages - 1)
        offs = pos % ps
        carry = (x, pool) + (jnp.int32(0),) * len(fam.counters)
        with jax.named_scope("layers"):
            for (kind, first, n), seg in zip(self._runs,
                                             weights["segments"]):
                whole = fam.whole(kind)
                xs = {k: v for k, v in seg.items() if k not in whole}
                entry0 = first + jnp.arange(n)
                if fam.cache_entries != 1:
                    entry0 = entry0 * fam.cache_entries
                carry, _ = jax.lax.scan(
                    self._layer(kind, seg, pids, offs, table, rows, pos,
                                row_new, mat_rows, write_ok, window),
                    carry, (xs, entry0, jnp.arange(n)))
        x, pool, *counts = carry
        x = rms_norm(x, weights["norm"], self.cfg.rms_norm_eps)
        last = x[jnp.clip(last_idx, 0, x.shape[0] - 1)]
        last = jnp.where(live[:, None], last, 0.0)
        with jax.named_scope("lm_head"):
            logits = jnp.dot(last, weights["head"],
                             preferred_element_type=jnp.float32)
        return jnp.argmax(logits, -1).astype(jnp.int32), pool, counts

    def _packed_multi_step(self, weights, pool, tokens, lens, table, done,
                           remaining, eos, pend, pend_n, w, *, k, t,
                           window):
        """K mixed ticks over the packed [t] stream: `decoder.packed_tick`
        (the layout and every per-row rule, shared with
        `PagedGPTDecoder`) over this decoder's forward. A row that holds
        prompt tokens (`pend_n > 0`) attends materialised, a row that
        decodes absorbed. Beside each tick's real token count the `real`
        block carries `horizon_counters`, a column each."""
        def tick(carry, _):
            done = carry[2]

            def forward(lay, pools):
                mat_rows = lay.is_pf & ~done
                nxt, pool, counts = self._packed_forward(
                    weights, pools[0], lay.ptok, lay.pos, lay.rows,
                    lay.write_ok, table, lay.last_idx, lay.live, lay.nl,
                    mat_rows, window)
                return nxt, (pool,), (
                    *counts, jnp.sum(lay.live & ~lay.is_pf),
                    jnp.sum(jnp.where(mat_rows, lay.nl, 0)))

            return packed_tick(carry, w, eos, t=t,
                               capacity=table.shape[1] * self.page_size,
                               forward=forward)

        carry = (tokens, lens, done, remaining, pend, pend_n, pool)
        carry, outs = jax.lax.scan(tick, carry, jnp.arange(k))
        return outs + carry

    def _prefill_packed_step(self, weights, pool, ptok, pos, rows, write_ok,
                             table, last_idx, live, row_new, *, window):
        nxt, pool, _ = self._packed_forward(
            weights, pool, ptok, pos, rows, write_ok, table, last_idx, live,
            row_new, live, window)
        return nxt, pool

    # ---------------------------------------------------- host-side API

    def ragged_multi(self, tokens, lens, table, k, w, pend, pend_n,
                     kids=None, done=None, remaining=None, eos=None,
                     t_tokens=None, aids=None):
        """`PagedGPTDecoder.ragged_multi` for this decoder: `k` mixed
        ticks in one dispatch, jitted per (k, t_tokens, window, table
        width) with w a traced scalar. Greedy: `kids` is not read.
        Returns a RaggedMultiOut whose `real` is [k, 1 +
        len(horizon_counters)]."""
        k, w = int(k), int(w)
        S = self.max_batch
        if aids is not None and np.any(np.asarray(aids)):
            raise NotImplementedError("PagedMLADecoder has no adapters")
        if done is None:
            done = np.zeros(S, bool)
        if remaining is None:
            remaining = np.full(S, np.iinfo(np.int32).max // 2, np.int32)
        if t_tokens is None:
            t_tokens = pow2_at_least(S * max(w, 1))
        t = max(int(t_tokens), 1)
        if t < S:
            raise ValueError(
                f"t_tokens {t} < max_batch {S}: the packed bucket must "
                "cover at least one token per slot")
        table = jnp.asarray(table, jnp.int32)
        width = table.shape[1]
        window = packed_window(w, t)
        key = (k, t, window, width)
        fn = self._packeds.get(key)
        if fn is None:
            fn = _named_jit(
                functools.partial(self._packed_multi_step, k=k, t=t,
                                  window=window),
                self.program_name("packed", k, t, width, window),
                donate_argnums=(1,))
            self._packeds[key] = fn
        out = fn(self.weights, self.latent_pages,
                 jnp.asarray(tokens, jnp.int32), jnp.asarray(lens, jnp.int32),
                 table, jnp.asarray(done, bool),
                 jnp.asarray(remaining, jnp.int32),
                 jnp.asarray(-1 if eos is None else int(eos), jnp.int32),
                 jnp.asarray(pend, jnp.int32),
                 jnp.asarray(pend_n, jnp.int32), jnp.asarray(w, jnp.int32))
        self.latent_pages = out[9]
        return RaggedMultiOut(*out[:9])

    def prefill_suffix_batch(self, requests, kids=None, aids=None):
        """Packed chunked prefill (`PagedGPTDecoder.prefill_suffix_batch`
        for this decoder): requests [(suffix_ids, start, pages), ...], up
        to max_batch of them a dispatch as ONE flat stream bucketed by
        total tokens; every row attends materialised. Returns each
        request's first generated token."""
        results = [None] * len(requests)
        S, MP, ps = self.max_batch, self.max_pages, self.page_size
        todo = list(enumerate(requests))
        while todo:
            chunk, todo = todo[:S], todo[S:]
            lay = packed_prefill_layout([req for _, req in chunk], S, MP,
                                        ps, self.num_pages - 1)
            t, window = lay.t, lay.window
            fn = self._packed_prefills.get((t, window))
            if fn is None:
                fn = _named_jit(
                    functools.partial(self._prefill_packed_step,
                                      window=window),
                    f"mla_prefill_packed_t{t}_w{window}",
                    donate_argnums=(1,))
                self._packed_prefills[t, window] = fn
            nxt, self.latent_pages = fn(
                self.weights, self.latent_pages, *map(jnp.asarray, (
                    lay.ptok, lay.pos, lay.rows, lay.ok, lay.table,
                    lay.last_idx, lay.live, lay.new)))
            nxt = np.asarray(nxt)
            for r, (i, _) in enumerate(chunk):
                results[i] = int(nxt[r])
        return results

    def copy_page(self, src, dst):
        """Device-side copy of one page's latent rows, every layer."""
        if self._copy is None:
            self._copy = _named_jit(
                lambda pool, s, d: pool.at[:, d].set(pool[:, s]),
                "mla_copy_page", donate_argnums=(0,))
        self.latent_pages = self._copy(
            self.latent_pages, jnp.asarray(int(src), jnp.int32),
            jnp.asarray(int(dst), jnp.int32))
