"""Paged decode executor for the expert model families: the second
decoder behind `ContinuousBatchingEngine`, beside `PagedGPTDecoder`.

What differs from the GPT decoder, by mechanism:

* ONE pool `[entries, pages, page_size, width]` in place of
  `k_pages`/`v_pages` `[L, P, ps, H, D]`, DESCRIBED BY THE FAMILY: how
  many entries each layer has (`layer_entries`) and how many values an
  entry holds a token (`entry_width`). A latent-attention (MLA) family
  has one or two entries a layer of `kv_lora_rank + rope_dim` values
  (1,152 B a token in bfloat16 where DeepSeek-V2's full keys and values
  would be 81,920 B); LFM2 has one entry an attention layer, its
  grouped keys and then values (2 x 8 x 64), and none a conv layer.
  Entry `e` of layer `l` sits at the layer's first entry plus `e`. A
  latent entry is stored in whole lanes (`LANES`: 576 values in 640),
  so the pool's default device layout is the layer loop's row-major one.
* The family's attention over its entries (`attention`): "mla" in two
  forms over one pool, chosen BY ROW KIND and never by a knob (a row
  that takes prompt chunks attends through MATERIALISED heads, a row
  that decodes through ABSORBED projections, side by side in one mixed
  horizon, `ops.mla_paged_attention_packed`), or "gqa", fewer
  key/value heads than query heads through the grouped form of the
  packed walk (`ops.ragged_paged_attention_packed` with one pool).
* A PER-SLOT STATE where the family keeps one (`state_layers`: LFM2's
  conv layers keep z of a row's two latest positions, [slots, 2, h]
  each). It rides the horizon's carry beside the pool, is read and
  written in the packed tick for chunk rows and decode rows alike
  (`models.lfm2_moe.packed_conv_taps`: a tap at a position below 0 reads
  zero, so a slot a new request takes needs no reset; a row with no
  tokens, frozen or padded, writes nothing), and stays on the device
  between horizons.
* The BLOCK IS THE MODEL FAMILY'S (`FAMILIES`, by `cfg.family`): this
  decoder stacks each run of equal layers, scans it with the pool (and
  the state) in the carry, and gives the family's `block` an `attend(j,
  y, w)` that projects, writes entry j of the layer in place at
  `[entry, page, offset]`, attends and projects out, and to a family
  with a state `taps(z)` (the conv's two earlier z of every stream
  token). What a family's entry states: `attention`, `entry_width(cfg)`,
  `layer_entries(cfg)` ([entries a layer]), `state_layers(cfg)` ((the
  layers that keep a state, its shape a slot), or ([], None)),
  `inv_freq(cfg)` (its rotary frequencies), `counters` (names of the
  int32 counts its blocks return, summed here over layers), `runs(cfg)`
  ([(kind, first layer, layers)]), `leaves(kind)` ({key: parameter name
  under "layers.<i>."}; keys that start with "kv_b" are laid out [rank,
  heads, nope + v]), `whole(kind)` (keys read by (layer, expert), not
  sliced by the scan) and `block(cfg, kind, x, wl, seg, ri, attend,
  valid[, taps])` -> (x, counts); a "gqa" family also `project(w, y,
  pos, cfg, inv_freq)` -> (q [T, H, D], the row its entry holds).
  DeepSeek-V2 (attention then one MLP, dense or group-limited experts
  with shared ones) is the first entry, LongCat-Flash (two attentions,
  two dense MLPs and a shortcut-connected expert branch with identity
  experts a layer) the second, LFM2-MoE (gated short convs and
  grouped-query attentions, sigmoid-routed experts) the third.
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
have no attach method. A family with a per-slot state has no packed
prefill outside the horizon either (`prefill_suffix_batch` raises: its
rows are not slots). Nothing falls back silently.
"""
import collections
import functools
import weakref

import jax
import jax.numpy as jnp
import numpy as np

from ..models import deepseek_v2, lfm2_moe, longcat_flash
from ..models.deepseek_v2 import mla_project, rms_norm, softmax_scale
from ..ops.ragged_paged_attention import (KEY_BLOCK_PAGES,
                                          mla_paged_attention_packed,
                                          ragged_paged_attention_packed)
from .decoder import (RaggedMultiOut, _named_jit, packed_prefill_layout,
                      packed_tick, packed_window, pow2_at_least)

__all__ = ["PagedMLADecoder", "FAMILIES", "latent_token_bytes"]

# a model family's block, leaves, cache entries and counters, by the
# `family` its config names (the module's docstring has the contract)
FAMILIES = {"deepseek_v2": deepseek_v2.Serving,
            "longcat_flash": longcat_flash.Serving,
            "lfm2_moe": lfm2_moe.Serving}


_stack = jax.jit(lambda *arrays: jnp.stack(arrays))

# A latent entry is stored padded to whole lanes of the chip's vector unit.
# The device's default layout of a pool whose entry is lane-aligned is
# row-major, the layout the layer loop reads and writes it in; a 576-wide
# entry's default puts the pages minor-most, and every program then copied
# the whole pool into the loop's layout and back out of it.
LANES = 128


def latent_token_bytes(cfg, itemsize=2):
    """Cache bytes ONE token costs in one attention: the latent row."""
    return int(cfg.latent_dim * itemsize)


def _mla_attend(dec, lay, pool, entry, y, w):
    """MLA over normed tokens y [T, h]: its rows written into entry
    `entry` of the pool, read back through both forms, projected out.
    Returns ([T, h], pool)."""
    q_nope, q_rope, latent = mla_project(w, y, lay.pos, dec.cfg,
                                         dec.inv_freq)
    with jax.named_scope("latent_write"):
        row = jnp.pad(latent, ((0, 0), (0, pool.shape[-1] - latent.shape[-1])))
        pool = pool.at[entry, lay.pids, lay.offs].set(row)
    attn = mla_paged_attention_packed(
        q_nope, q_rope, pool, entry, w["kv_b"], lay.table, lay.rows,
        lay.pos, lay.row_new, lay.mat_rows, softmax_scale(dec.cfg),
        window=lay.window)
    return jnp.dot(attn.reshape(y.shape[0], -1), w["o"],
                   preferred_element_type=jnp.float32).astype(y.dtype), pool


def _gqa_attend(dec, lay, pool, entry, y, w):
    """Grouped-query attention over normed tokens y [T, h]: the family's
    projection, its keys and values written into entry `entry`, the
    grouped packed walk over the rows' pages (query head h reads
    key/value head h // (H / Hk)), projected out. Returns ([T, h],
    pool)."""
    q, row = dec.family.project(w, y, lay.pos, dec.cfg, dec.inv_freq)
    with jax.named_scope("kv_write"):
        pool = pool.at[entry, lay.pids, lay.offs].set(row)
    attn = ragged_paged_attention_packed(
        q, pool, None, lay.table, lay.rows, lay.pos, window=lay.window,
        layer=entry)
    return jnp.dot(attn.reshape(y.shape[0], -1), w["o"],
                   preferred_element_type=jnp.float32).astype(y.dtype), pool


# a family's attention over its pool entries, by its `attention`
ATTENTIONS = {"mla": _mla_attend, "gqa": _gqa_attend}

# what one layer of the packed forward reads of the stream's layout
_Stream = collections.namedtuple(
    "_Stream", "pids offs table rows pos row_new mat_rows window")


class PagedMLADecoder:
    """Stacked-weight expert-model decode executor over a paged pool
    (and a per-slot state) that the model's family describes (see the
    module's docstring)."""

    kind = "mla"
    # the absorbed form copies every column of the table it is handed
    # (see `PagedGPTDecoder.walk_block_pages`); the grouped walk of a
    # "gqa" family ends at the deepest row's block, as the GPT walk does
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
        if fam.attention == "gqa":
            self.walk_block_pages = KEY_BLOCK_PAGES
        self._state_layers, state_shape = fam.state_layers(cfg)
        if self._state_layers:
            self.engine_refusals = dict(self.engine_refusals, **{
                k: why + "; and a layer's per-slot state is not a page "
                "(a mounted prefix, a restored or a spilled page would "
                "come without the state of the positions it holds)"
                for k, why in self.engine_refusals.items()
                if k in ("prefix_cache", "host_tier")})
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
        self.inv_freq = jnp.asarray(fam.inv_freq(cfg), jnp.float32)
        self.weights = self._stack_weights(model, release_model)
        # the first pool entry of each layer (a layer with none: its
        # place among the layers that keep a state)
        entries = fam.layer_entries(cfg)
        self._entries = entries
        firsts = np.cumsum([0] + entries[:-1])
        holder = {layer: i for i, layer in enumerate(self._state_layers)}
        self._index = [int(firsts[i]) if entries[i] else holder.get(i, 0)
                       for i in range(cfg.num_layers)]
        # a latent entry in whole lanes (`LANES`); the grouped walk reads
        # its entry whole, and LFM2's 1,024 values are whole lanes. What a
        # token costs (`kv_token_bytes`, the prices) counts its own values.
        width = fam.entry_width(cfg)
        if fam.attention == "mla":
            width = -(-width // LANES) * LANES
        pool = jnp.zeros((sum(entries), num_pages, page_size, width), dt)
        # what every program carries and returns: the pool, with the
        # per-slot state beside it where the family keeps one
        self.cache = pool if not self._state_layers else (pool, jnp.zeros(
            (len(self._state_layers), self.max_batch) + tuple(state_shape),
            dt))
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
        self._runs = fam.runs(cfg)
        segments = []
        for kind, first, n in self._runs:
            w = {k: take([f"layers.{i}.{leaf}"
                          for i in range(first, first + n)])
                 for k, leaf in fam.leaves(kind).items()}
            for k in w:
                if k.startswith("kv_b"):
                    w[k] = w[k].reshape(n, cfg.kv_lora_rank, cfg.num_heads,
                                        -1)
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
        """Cache bytes one token costs in one pool entry (a layer has
        `family.layer_entries` of them)."""
        return int(self.family.entry_width(self.cfg)
                   * self.compute_dtype.itemsize)

    def kv_token_bytes_by_layer(self):
        return [self.kv_token_bytes * e for e in self._entries]

    @property
    def state_slot_bytes(self):
        """Bytes of the per-slot state one slot holds, all layers."""
        if not self._state_layers:
            return 0
        state = self.cache[1]
        return int(state.size // state.shape[1] * state.dtype.itemsize)

    @property
    def kv_page_bytes(self):
        return int(self.page_size * sum(self.kv_token_bytes_by_layer()))

    def step_hbm_bytes(self, avg_ctx=None, batch=None):
        """HBM bytes ONE decode tick moves at most: every weight byte
        held (the held experts too: a tick reads those its rows select,
        `experts_hit` counts them) plus each slot's pool rows at
        `avg_ctx` (default: half a sequence's pool capacity) and its
        per-slot state."""
        if avg_ctx is None:
            avg_ctx = max(self.pend_capacity // 2, 1)
        if batch is None:
            batch = self.max_batch
        return int(self.cfg.num_params() * self.compute_dtype.itemsize
                   + batch * avg_ctx * sum(self.kv_token_bytes_by_layer())
                   + batch * self.state_slot_bytes)

    def cache_fingerprint(self):
        """Identity of this decoder's cache bytes (weights, shapes, page
        size, dtype): see `PagedGPTDecoder.cache_fingerprint`."""
        cfg = self.cfg
        probes = tuple(float(jnp.sum(v.astype(jnp.float32)))
                       for v in jax.tree_util.tree_leaves(self.weights))
        return repr((self.kind, cfg.family, cfg.num_layers, cfg.hidden_size,
                     cfg.num_heads, tuple(self._entries),
                     self.family.entry_width(cfg), cfg.vocab_size,
                     cfg.expert_offset, cfg.experts_held, self.page_size,
                     str(self.compute_dtype), probes)).encode()

    # ------------------------------------------------------ the programs

    def _layer(self, kind, seg_w, lay, valid):
        """One layer over the packed stream as a scan body: carry
        (x [T, h], the cache: the whole pool, and the whole state where
        the family keeps one, the family's counters), xs (the layer's
        weights, its first pool entry or its place among the layers that
        keep a state, its index in its run). The family's block says
        where the layer's attentions sit; `attend` is what each of them
        is here, `taps` what the state gives a conv."""
        cfg, fam = self.cfg, self.family
        attention = ATTENTIONS[fam.attention]
        stateful = bool(self._state_layers)

        def layer(carry, xs):
            x, cache, *counts = carry
            wl, entry0, ri = xs
            held = list(cache) if stateful else [cache]

            def attend(j, y, w):
                """Attention j of this layer over normed tokens y,
                through ITS entry of the pool. [T, h]."""
                entry = entry0 + j if j else entry0
                out, held[0] = attention(self, lay, held[0], entry, y, w)
                return out

            extra = {}
            if stateful:
                def taps(z):
                    """This layer's (z2, z1) of every stream token; the
                    rows' state moves on to their latest positions."""
                    with jax.named_scope("conv_state"):
                        st = held[1][entry0]
                        out, st = lfm2_moe.packed_conv_taps(
                            z, st, lay.rows, lay.pos, lay.row_new)
                        held[1] = held[1].at[entry0].set(st)
                    return out

                extra["taps"] = taps
            x, added = fam.block(cfg, kind, x, wl, seg_w, ri, attend, valid,
                                 **extra)
            if added:
                counts = [c + a for c, a in zip(counts, added)]
            return (x, tuple(held) if stateful else held[0], *counts), None

        return layer

    def _entry0(self, first, n):
        """xs' index of each layer of a run (see `_layer`)."""
        entries = self._entries[first:first + n]
        if len(set(self._entries)) == 1 and entries[0]:
            entry0 = first + jnp.arange(n)
            if entries[0] != 1:
                entry0 = entry0 * entries[0]
            return entry0
        return jnp.asarray(self._index[first:first + n], jnp.int32)

    def _packed_forward(self, weights, cache, ptok, pos, rows, write_ok,
                        table, last_idx, live, row_new, mat_rows, window):
        """The shared PACKED forward (the layout and the arguments of
        `PagedGPTDecoder._packed_forward`; `row_new` [S] the stream
        tokens of each row, `mat_rows` [S] the rows that take prompt
        chunks). Returns (next [S], cache, the family's counters)."""
        fam = self.family
        ps, MP = self.page_size, table.shape[1]
        x = weights["embed"][ptok].astype(self.compute_dtype)
        pids = table[rows, jnp.minimum(pos // ps, MP - 1)]
        pids = jnp.where(write_ok, pids, self.num_pages - 1)
        offs = pos % ps
        lay = _Stream(pids, offs, table, rows, pos, row_new, mat_rows, window)
        carry = (x, cache) + (jnp.int32(0),) * len(fam.counters)
        with jax.named_scope("layers"):
            for (kind, first, n), seg in zip(self._runs,
                                             weights["segments"]):
                whole = fam.whole(kind)
                xs = {k: v for k, v in seg.items() if k not in whole}
                carry, _ = jax.lax.scan(
                    self._layer(kind, seg, lay, write_ok),
                    carry, (xs, self._entry0(first, n), jnp.arange(n)))
        x, cache, *counts = carry
        x = rms_norm(x, weights["norm"], self.cfg.rms_norm_eps)
        last = x[jnp.clip(last_idx, 0, x.shape[0] - 1)]
        last = jnp.where(live[:, None], last, 0.0)
        with jax.named_scope("lm_head"):
            logits = jnp.dot(last, weights["head"],
                             preferred_element_type=jnp.float32)
        return jnp.argmax(logits, -1).astype(jnp.int32), cache, counts

    def _packed_multi_step(self, weights, cache, tokens, lens, table, done,
                           remaining, eos, pend, pend_n, w, *, k, t,
                           window):
        """K mixed ticks over the packed [t] stream: `decoder.packed_tick`
        (the layout and every per-row rule, shared with
        `PagedGPTDecoder`) over this decoder's forward. A row that holds
        prompt tokens (`pend_n > 0`) attends materialised, a row that
        decodes absorbed (an MLA family's forms). Beside each tick's real
        token count the `real` block carries `horizon_counters`, a column
        each."""
        def tick(carry, _):
            done = carry[2]

            def forward(lay, caches):
                mat_rows = lay.is_pf & ~done
                nxt, cache, counts = self._packed_forward(
                    weights, caches[0], lay.ptok, lay.pos, lay.rows,
                    lay.write_ok, table, lay.last_idx, lay.live, lay.nl,
                    mat_rows, window)
                return nxt, (cache,), (
                    *counts, jnp.sum(lay.live & ~lay.is_pf),
                    jnp.sum(jnp.where(mat_rows, lay.nl, 0)))

            return packed_tick(carry, w, eos, t=t,
                               capacity=table.shape[1] * self.page_size,
                               forward=forward)

        carry = (tokens, lens, done, remaining, pend, pend_n, cache)
        carry, outs = jax.lax.scan(tick, carry, jnp.arange(k))
        return outs + carry

    def _prefill_packed_step(self, weights, cache, ptok, pos, rows, write_ok,
                             table, last_idx, live, row_new, *, window):
        nxt, cache, _ = self._packed_forward(
            weights, cache, ptok, pos, rows, write_ok, table, last_idx, live,
            row_new, live, window)
        return nxt, cache

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
        out = fn(self.weights, self.cache,
                 jnp.asarray(tokens, jnp.int32), jnp.asarray(lens, jnp.int32),
                 table, jnp.asarray(done, bool),
                 jnp.asarray(remaining, jnp.int32),
                 jnp.asarray(-1 if eos is None else int(eos), jnp.int32),
                 jnp.asarray(pend, jnp.int32),
                 jnp.asarray(pend_n, jnp.int32), jnp.asarray(w, jnp.int32))
        self.cache = out[9]
        return RaggedMultiOut(*out[:9])

    def prefill_suffix_batch(self, requests, kids=None, aids=None):
        """Packed chunked prefill (`PagedGPTDecoder.prefill_suffix_batch`
        for this decoder): requests [(suffix_ids, start, pages), ...], up
        to max_batch of them a dispatch as ONE flat stream bucketed by
        total tokens; every row attends materialised. Returns each
        request's first generated token. A family with a per-slot state
        has none: a request's row here is its place in `requests`, not
        its slot, and its state would land in another slot's."""
        if self._state_layers:
            raise NotImplementedError(
                f"PagedMLADecoder over {self.cfg.family} has no packed "
                "prefill outside the ragged horizon: its per-slot state "
                "is kept by slot, and this layout's rows are not slots")
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
            nxt, self.cache = fn(
                self.weights, self.cache, *map(jnp.asarray, (
                    lay.ptok, lay.pos, lay.rows, lay.ok, lay.table,
                    lay.last_idx, lay.live, lay.new)))
            nxt = np.asarray(nxt)
            for r, (i, _) in enumerate(chunk):
                results[i] = int(nxt[r])
        return results

    def copy_page(self, src, dst):
        """Device-side copy of one page's pool rows, every entry (a
        per-slot state is no page's and stays)."""
        if self._copy is None:
            def copy(cache, s, d):
                pool = cache[0] if self._state_layers else cache
                pool = pool.at[:, d].set(pool[:, s])
                return (pool,) + cache[1:] if self._state_layers else pool

            self._copy = _named_jit(copy, "mla_copy_page",
                                    donate_argnums=(0,))
        self.cache = self._copy(
            self.cache, jnp.asarray(int(src), jnp.int32),
            jnp.asarray(int(dst), jnp.int32))
