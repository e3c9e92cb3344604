"""Paged-KV GPT decode executor: stacked weights, compiled decode /
prefill / verify programs over page pools (see package docstring in
`paddle_tpu/serving/__init__.py` for the architecture notes)."""
import collections
import functools
import hashlib
import weakref

import jax
import jax.numpy as jnp
import numpy as np

from ..ops.ragged_paged_attention import KEY_BLOCK_PAGES
from ..profiler import build_span

__all__ = ["PagedGPTDecoder", "MultiDecodeOut", "RaggedMultiOut",
           "_spec_accept", "_sample_tokens", "_ln", "_mm", "_mm_heads",
           "_quantize_w", "_quantize_kv", "_kv_set", "INT4_GROUP",
           "_quantize_kv_int4", "_dequantize_kv_int4", "_pack_int4",
           "_unpack_int4"]

# every live decoder, so the tier-1 conftest's module-boundary GC hook
# can trim compiled-program memos (the Trainer._LIVE_TRAINERS pattern)
_LIVE_DECODERS = weakref.WeakSet()


def clear_compiled_memos():
    """Drop every live decoder's lazily built compiled-program memos
    (fused multi/ragged loops, chunked prefill, verify, CoW copy). A
    finished test module's decoders no longer need them; anything
    still live recompiles on its next call. Returns entries dropped."""
    n = 0
    for dec in list(_LIVE_DECODERS):
        for memo in (dec._multis, dec._packeds,
                     dec._packed_prefills, dec._mount_multi):
            n += len(memo)
            memo.clear()
        for attr in ("_verify", "_probs", "_copy", "_mount"):
            if getattr(dec, attr) is not None:
                n += 1
                setattr(dec, attr, None)
    return n


def _named_jit(fn, name, **jit_kw):
    """`jax.jit(fn)` under `name`: the XLA module is called `jit_<name>`,
    so the profiler's "XLA Modules" line and a compile log tell one
    serving program from another by its key (every one of them would
    otherwise carry the name of the method it partially applies)."""
    def program(*args):
        return fn(*args)
    program.__name__ = program.__qualname__ = name
    return jax.jit(program, **jit_kw)


# decode_multi's result bundle: device arrays — the engine feeds
# tokens/lens/done/remaining straight into the next horizon's call and
# fetches tokens_block/done_before only at sync points
MultiDecodeOut = collections.namedtuple(
    "MultiDecodeOut", ["tokens_block", "done_before", "tokens", "lens",
                       "done", "remaining", "logits_block"])

# ragged_multi's result bundle: like MultiDecodeOut plus the device-
# resident prompt-suffix carry (pend/pend_n), the per-tick `emitted`
# mask (False for filler ticks of frozen slots AND for mid-prefill
# ticks, which consume prompt chunks without producing a token), and
# `real` [k] — the REAL token positions each tick consumed (live rows'
# new_len summed; frozen rows 0). The engine's pad-fraction ledger is
# dispatched-minus-real: the device is the one source of truth for how
# much of a padded dispatch was actual work (EOS can freeze a slot
# mid-horizon, which no host-side plan can predict).
RaggedMultiOut = collections.namedtuple(
    "RaggedMultiOut", ["tokens_block", "emitted", "real", "tokens",
                       "lens", "done", "remaining", "pend", "pend_n"])


def pow2_at_least(n):
    """Smallest power of two >= max(n, 1) — THE bucket-rounding rule
    shared by the packed dispatch (scheduler `t_tokens`, the decoder's
    default buckets, the packed prefill): one definition, so the
    scheduler's bucket and the decoder's coverage guarantee can never
    diverge on an off-by-one."""
    p = 1
    while p < max(int(n), 1):
        p *= 2
    return p


def packed_window(w, t):
    """Static bound on the tokens ONE row holds in a packed stream of
    `t` whose rows carry at most `w` each: the pow2 bucket of w, so the
    attention's per-row window (`ops.ragged_paged_attention_packed`)
    is at most twice the widest row and never wider than the stream.
    Part of a packed program's key and name."""
    return min(pow2_at_least(w), int(t))


PackedLayout = collections.namedtuple(
    "PackedLayout", "ptok pos rows write_ok last_idx true live nl is_pf")

PackedPrefill = collections.namedtuple(
    "PackedPrefill",
    "t window ptok pos rows ok table last_idx sample_pos live new")


def packed_tick(carry, w, eos, *, t, capacity, forward):
    """One MIXED tick over the PACKED [t] token stream, whatever the
    decoder: the layout built on device from the carry (cumsum +
    searchsorted over per-row token counts), the decoder's `forward`
    over it, and every per-row rule of the schedule (emit condition,
    freeze and budget updates, scratch routing, the dynamic shift of the
    prompt suffixes). ONE definition, so decoders cannot part on a rule.

    `carry` = (tokens, lens, done, remaining, pend, pend_n, *pools);
    `w` the traced per-row chunk cap; `capacity` the positions a row's
    table covers (past it a write goes to scratch).
    `forward(layout, pools)` -> (next [S], pools, counters): the
    decoder's model over the `PackedLayout` (token t = row `rows[t]`,
    position `pos[t]`; `true` [S] the rows' lengths after the tick) and
    int32 scalars that ride the tick's `real` output as further columns
    (none: `real` is the real token count alone).
    Returns (carry, (next, emit, real)) as a `lax.scan` body does."""
    tokens, lens, done, remaining, pend, pend_n = carry[:6]
    S = tokens.shape[0]
    P = pend.shape[1]
    is_pf = pend_n > 0
    # per-row stream share: decode 1, prefill min(pend_n, w),
    # frozen 0 (the packed layout simply skips frozen rows)
    nl = jnp.where(done, 0,
                   jnp.where(is_pf, jnp.minimum(pend_n, w), 1))
    csum = jnp.cumsum(nl)
    total = csum[-1]
    starts = csum - nl
    ti = jnp.arange(t)
    rows = jnp.clip(
        jnp.searchsorted(csum, ti, side="right"), 0, S - 1
    ).astype(jnp.int32)
    within = (ti - starts[rows]).astype(jnp.int32)
    valid = ti < total
    pos = lens[rows] + within                     # [t]
    ptok = jnp.where(
        is_pf[rows], pend[rows, jnp.clip(within, 0, P - 1)],
        tokens[rows])
    ptok = jnp.where(valid, ptok, 0)
    write_ok = valid & ~done[rows] & (pos < capacity)
    true = lens + nl                              # [S]
    last_idx = jnp.clip(csum - 1, 0, t - 1)
    live = ~done & (nl > 0)
    nxt, pools, counters = forward(
        PackedLayout(ptok, pos, rows, write_ok, last_idx, true, live, nl,
                     is_pf), tuple(carry[6:]))
    emit = ~done & (pend_n <= w)
    nxt = jnp.where(emit, nxt, tokens)
    rem = jnp.where(emit, remaining - 1, remaining)
    new_done = done | (emit & ((nxt == eos) | (rem <= 0)))
    new_lens = jnp.where(done, lens, lens + nl)
    real = total.astype(jnp.int32)
    if counters:
        real = jnp.stack([real, *counters]).astype(jnp.int32)
    # shift each row's suffix by the DYNAMIC w (a gather)
    idx = jnp.arange(P)[None, :] + w
    pend = jnp.where(idx < P,
                     pend[jnp.arange(S)[:, None],
                          jnp.clip(idx, 0, P - 1)], 0)
    pend_n = jnp.maximum(pend_n - w, 0)
    return (nxt, new_lens, new_done, rem, pend, pend_n) + tuple(pools), \
        (nxt, emit, real)


def packed_prefill_layout(chunk, slots, max_pages, page_size, scratch):
    """Host-side PACKED prefill layout of up to `slots` requests
    [(suffix_ids, start, pages), ...], whatever the decoder: flat tokens
    with per-token row ids and positions, bucketed to a pow2 total-token
    count `t` and a pow2 longest suffix (`packed_window`); each row's
    page table (the rest on the `scratch` page), last stream index,
    sampling position, liveness and token count. numpy throughout."""
    counts = [len(np.asarray(ids).reshape(-1)) for ids, _, _ in chunk]
    t = pow2_at_least(sum(counts))
    window = packed_window(max(counts), t)
    ptok = np.zeros(t, np.int32)
    pos = np.zeros(t, np.int32)
    rows = np.zeros(t, np.int32)
    ok = np.zeros(t, bool)
    last_idx = np.zeros(slots, np.int32)
    spos = np.zeros(slots, np.int32)
    live = np.zeros(slots, bool)
    new = np.zeros(slots, np.int32)
    tbl = np.full((slots, max_pages), scratch, np.int32)
    cur = 0
    for r, (ids, start, pages) in enumerate(chunk):
        ids = np.asarray(ids, np.int32).reshape(-1)
        n = len(ids)
        ptok[cur:cur + n] = ids
        pos[cur:cur + n] = int(start) + np.arange(n)
        rows[cur:cur + n] = r
        ok[cur:cur + n] = pos[cur:cur + n] < max_pages * page_size
        last_idx[r] = max(cur + n - 1, 0)
        spos[r] = int(start) + n - 1
        live[r] = n > 0
        new[r] = n
        m = min(len(pages), max_pages)
        tbl[r, :m] = pages[:m]       # rest stays on scratch
        cur += n
    return PackedPrefill(t, window, ptok, pos, rows, ok, tbl, last_idx,
                         spos, live, new)


def _ln(x, w, b):
    x32 = x.astype(jnp.float32)
    mu = jnp.mean(x32, -1, keepdims=True)
    var = jnp.var(x32, -1, keepdims=True)
    return ((x32 - mu) * jax.lax.rsqrt(var + 1e-5) * w + b).astype(x.dtype)


def _quantize_w(w):
    """Per-out-channel symmetric int8 via the shared quantization recipe
    (quantization.quantize_weight) — one implementation so serving a8w8
    can't drift from QuantizedLinearA8W8/PTQ."""
    from ..quantization import quantize_weight
    q, scale = quantize_weight(w, axis=0)
    return q, scale.reshape(-1)


def _quantize_kv(val):
    """Write-time per-token int8 quantization of K (or V) vectors: one
    symmetric scale per TOKEN from the token's own [H, D] amax
    (scale = amax/127, floored so an all-zero vector stays
    representable). The scale depends only on the token's values —
    which are position-local (row-local matmuls, per-position
    embeddings) — so a token's stored bytes depend only on (request,
    position), never on batch composition, chunk schedule or page
    assignment: the byte-identical-stream discipline survives
    quantization unchanged. val [..., H, D] -> (int8 [..., H, D],
    f32 scale [...])."""
    v32 = val.astype(jnp.float32)
    amax = jnp.max(jnp.abs(v32), axis=(-2, -1))
    scale = jnp.maximum(amax / 127.0, 1e-8)
    q = jnp.clip(jnp.round(v32 / scale[..., None, None]),
                 -127, 127).astype(jnp.int8)
    return q, scale.astype(jnp.float32)


# int4 KV quantization group: one f32 scale per GROUP of flattened
# head*dim elements (per-token scales, as int8, would leave int4's
# narrow range too coarse across heads with very different magnitudes;
# per-group recovers most of the accuracy at 4/GROUP bytes/elem of
# metadata). The pool stores (uint8 nibble pages, f32 group-scale
# planes); dequant happens inside the attention body
# (ops/ragged_paged_attention._dequant_page_int4), never in HBM.
INT4_GROUP = 32


def _pack_int4(q):
    """Pack int4 values (int8 in [-8, 7], even last dim) into uint8
    nibble pairs: element 2i rides the LOW nibble of byte i, 2i+1 the
    high — the same layout ops/w4_matmul unpacks, so an int4 pool can
    later share its in-kernel dequant idiom."""
    lo = (q[..., 0::2].astype(jnp.uint8)) & 0xF
    hi = (q[..., 1::2].astype(jnp.uint8)) & 0xF
    return lo | (hi << 4)


def _unpack_int4(nibbles):
    """Inverse of `_pack_int4`: uint8 nibble pairs -> int8 values in
    [-8, 7] (sign-extended), last dim doubled."""
    lo = (nibbles & 0xF).astype(jnp.int8)
    hi = ((nibbles >> 4) & 0xF).astype(jnp.int8)
    lo = jnp.where(lo >= 8, lo - 16, lo)
    hi = jnp.where(hi >= 8, hi - 16, hi)
    return jnp.stack([lo, hi], axis=-1).reshape(
        nibbles.shape[:-1] + (nibbles.shape[-1] * 2,))


def _quantize_kv_int4(val, group=INT4_GROUP):
    """Write-time int4 KV quantization with PER-GROUP scales: the
    token's [H, D] vector flattens to H*D elements, each `group`-run
    shares one symmetric f32 scale from its own amax (floored like
    `_quantize_kv`), values clip to [-7, 7] and pack two-per-byte
    (`_pack_int4`). Like the int8 path, the scales depend only on the
    token's own values, so stored bytes stay a pure function of
    (request, position) — the byte-identical-stream discipline carries
    over unchanged (`_kv_set` dispatches here for uint8 pools;
    `pool_token_bytes(kv_quant="int4")` prices the stored layout).
    val [..., H, D] -> (packed uint8
    [..., ceil(ceil(H*D/group)*group / 2)] — H*D zero-padded up to a
    whole number of groups and an even nibble count — f32 scales
    [..., ceil(H*D/group)])."""
    v32 = val.astype(jnp.float32)
    hd = v32.shape[-2] * v32.shape[-1]
    group = min(int(group), hd)
    flat = v32.reshape(v32.shape[:-2] + (hd,))
    n_groups = (hd + group - 1) // group      # ceil, like the pricing
    pad = n_groups * group - hd
    if pad:
        # zero-pad the tail group (zeros quantize to 0 under any
        # scale, so padding never moves a real element's scale and
        # stored bytes stay a pure function of the token's values)
        flat = jnp.concatenate(
            [flat, jnp.zeros(flat.shape[:-1] + (pad,), jnp.float32)],
            axis=-1)
    g = flat.reshape(flat.shape[:-1] + (n_groups, group))
    amax = jnp.max(jnp.abs(g), axis=-1)
    scale = jnp.maximum(amax / 7.0, 1e-8)
    q = jnp.clip(jnp.round(g / scale[..., None]), -7, 7).astype(jnp.int8)
    q = q.reshape(flat.shape)
    if q.shape[-1] % 2:                       # nibble pairs need even
        q = jnp.concatenate(
            [q, jnp.zeros(q.shape[:-1] + (1,), jnp.int8)], axis=-1)
    return _pack_int4(q), scale.astype(jnp.float32)


def _dequantize_kv_int4(nibbles, scale, heads_shape, group=INT4_GROUP):
    """Inverse of `_quantize_kv_int4` up to quantization error:
    unpack nibbles, multiply each group by its scale, reshape back to
    [..., H, D] (`heads_shape` = (H, D))."""
    q = _unpack_int4(nibbles).astype(jnp.float32)
    hd = int(heads_shape[0]) * int(heads_shape[1])
    group = min(int(group), hd)
    n_groups = scale.shape[-1]
    q = q[..., :n_groups * group]             # drop the pack-parity pad
    g = q.reshape(q.shape[:-1] + (n_groups, group)) * scale[..., None]
    flat = g.reshape(q.shape[:-1] + (n_groups * group,))[..., :hd]
    return flat.reshape(q.shape[:-1] + tuple(heads_shape))


def pool_token_bytes(cfg, kv_quant=None, itemsize=2):
    """KV bytes one context token costs PER LAYER under a pool layout
    (K and V together). int8 pools pay 1 B/elem payload + one 4 B f32
    write-time scale per plane; int4 pools pay 0.5 B/elem packed
    nibbles + one f32 scale per `INT4_GROUP` elements (per-group
    scales — see `_quantize_kv_int4`). THE byte model behind
    `PagedGPTDecoder.kv_token_bytes` / `step_hbm_bytes` — one definition,
    so a caller can price big-model shapes without building the model
    and can never drift from what the decoder reports."""
    if kv_quant not in (None, "int8", "int4"):
        raise ValueError(
            f"kv_quant must be None, 'int8' or 'int4', got {kv_quant!r} "
            "(an unquantized pool is kv_quant=None priced at `itemsize` "
            "bytes/elem — there is no 'bf16' spelling)")
    hd = cfg.num_heads * cfg.head_dim
    if kv_quant == "int4":
        group = min(INT4_GROUP, hd)
        n_groups = (hd + group - 1) // group
        # stored payload is ceil-padded to whole groups and an even
        # nibble count (`_quantize_kv_int4`) — price the stored bytes
        per_tensor = (n_groups * group + 1) // 2 + 4 * n_groups
    elif kv_quant == "int8":
        per_tensor = hd + 4          # one f32 write-time scale/token
    else:
        per_tensor = hd * itemsize
    return int(2 * per_tensor)


def _kv_set(pool, li, pids, offs, val):
    """Write `val` [..., H, D] at (pids, offs) of layer `li` of the
    WHOLE page pool [L, P, ps, ...] — the single KV write primitive
    behind every serving path (decode ticks, chunked suffix prefill,
    the verify window, ragged horizons; scratch routing is the
    caller's pids). The layer is an index of the scatter, not a slice:
    the layer loop carries the pool (`PagedGPTDecoder._scan_layers`)
    and this writes the new tokens' rows in place, every other byte of
    the pool untouched. A plain pool stores the
    cast value; a quantized pool (pages, scales) quantizes from the
    token's own amax and stores bytes + scales together, so no write
    site can ever drift from the others — int8 pools (int8 payload)
    take the per-token-scale path (`_quantize_kv`), int4 pools (uint8
    nibble payload) the per-group path (`_quantize_kv_int4`)."""
    if isinstance(pool, tuple):
        pages, scales = pool
        if pages.dtype == jnp.uint8:
            q, s = _quantize_kv_int4(val)
        else:
            q, s = _quantize_kv(val)
        return (pages.at[li, pids, offs].set(q),
                scales.at[li, pids, offs].set(s))
    return pool.at[li, pids, offs].set(val.astype(pool.dtype))


def _spec_accept(p_rows, q_rows, drafts, rng):
    """Rejection-sampling acceptance for ONE slot (Leviathan et al.):
    p_rows [n+1, V] target probs — row j is the target's conditional
    AFTER the tokens preceding draft j (row 0 judges drafts[0]),
    q_rows [n, V] draft probs, drafts [n] proposed tokens.  Accept draft
    j with prob min(1, p_j(d)/q_j(d)); on rejection emit a sample from
    norm(max(p_j - q_j, 0)); if every draft is accepted emit a fresh
    sample from the last target row.  The emitted tokens are distributed
    EXACTLY as target-only sampling (unit-tested by Monte Carlo).
    Returns (n_accepted, final_token)."""
    n = len(drafts)
    for j in range(n):
        d = int(drafts[j])
        q = q_rows[j, d]
        p = p_rows[j, d]
        if q <= 0.0 or rng.random() >= min(1.0, p / q):
            resid = np.maximum(p_rows[j] - q_rows[j], 0.0)
            tot = resid.sum()
            if tot <= 1e-12:       # p==q everywhere: any target sample
                resid, tot = p_rows[j], p_rows[j].sum()
            return j, int(rng.choice(len(resid), p=resid / tot))
    row = p_rows[n]
    return n, int(rng.choice(len(row), p=row / row.sum()))


def _sample_tokens(logits, sampling, keys):
    """Per-slot next-token choice: greedy, or seeded temperature/top-k/
    top-p sampling (keys: [S] per-slot PRNG keys derived from
    (seed, request id, position) — see PagedGPTDecoder._pos_keys — so a
    request's draws don't depend on batch composition or scheduling;
    the mask itself is shared with generate() via
    models.generation.mask_logits)."""
    if sampling is None:
        return jnp.argmax(logits, axis=-1).astype(jnp.int32)
    from ..models.generation import mask_logits
    temperature, top_k, top_p = sampling
    masked = mask_logits(logits, temperature, top_k, top_p)
    return jax.vmap(jax.random.categorical)(keys, masked).astype(jnp.int32)


def _lora_delta(wl, y, aids):
    """Per-token low-rank qkv delta over the SHARED base weights — the
    multi-LoRA primitive (tenancy: dozens of fine-tuned variants batch
    into one ragged horizon). `y` [T, h] are the flat post-ln1
    activations (the qkv projection's input), `aids` [T] per-token
    adapter ids (0 = base, an all-zero adapter), `wl["lora_A"]`
    [n_a, h, r] / `wl["lora_B"]` [n_a, r, 3*H*D] this layer's stacked
    adapter banks (alpha/r scaling folded into B at attach time).

    The adapter is resolved by a per-TOKEN gather of the small banks
    through `row_ids` (pages are NOT resolved so: the packed attention
    gathers them once a row) — so each token's delta
    is (y_t @ A_{a_t}) @ B_{a_t}: row-local math that never sees batch
    composition. A mixed-adapter horizon therefore emits bit-identical
    streams to per-adapter engines over the same bank (test-pinned),
    and adapter 0's zero bank contributes an exact 0.0 to every
    preactivation."""
    A = wl["lora_A"][aids]                      # [T, h, r]
    B = wl["lora_B"][aids]                      # [T, r, 3*H*D]
    y32 = y.astype(jnp.float32)
    z = jnp.einsum("th,thr->tr", y32, A)
    return jnp.einsum("tr,trd->td", z, B)


def _mm_heads(x, w, b, quant):
    """x [S, h] @ head-major qkv weight [h, 3, H, D] -> [S, 3, H, D]."""
    if not quant:
        return (jnp.einsum("sh,htnd->stnd", x, w.astype(x.dtype))
                + b.astype(x.dtype))
    if quant == "w4a16":
        from ..ops.w4_matmul import w4_matmul
        packed, sw = w             # [h/2, 3, H, D] packed, [3, H, D]
        out = w4_matmul(x, packed.reshape(packed.shape[0], -1),
                        sw.reshape(-1), x.shape[-1])
        return out.reshape(x.shape[0], *b.shape) + b.astype(x.dtype)
    qw, sw = w                     # [h,3,H,D] int8, [3,H,D] f32
    sx = jnp.max(jnp.abs(x.astype(jnp.float32)), axis=-1,
                 keepdims=True) / 127.0
    sx = jnp.maximum(sx, 1e-8)
    xq = jnp.clip(jnp.round(x.astype(jnp.float32) / sx), -127,
                  127).astype(jnp.int8)
    acc = jax.lax.dot_general(xq, qw, (((1,), (0,)), ((), ())),
                              preferred_element_type=jnp.int32)
    return (acc.astype(jnp.float32) * sx[:, :, None, None] * sw
            + b).astype(x.dtype)


def _mm(x, w, b, quant):
    """x [..., in] @ w -> [..., out].  Float path, weight-only int4
    (W4A16: Pallas in-VMEM dequant), or dynamic-A8 x W8 int8 MXU
    matmul with per-row activation scales."""
    if not quant:
        return (x @ w.astype(x.dtype) + b.astype(x.dtype)).astype(x.dtype)
    if quant == "w4a16":
        from ..ops.w4_matmul import w4_matmul
        out = w4_matmul(x, w[0], w[1], x.shape[-1])
        return (out + b.astype(x.dtype)).astype(x.dtype)
    qw, sw = w
    sx = jnp.max(jnp.abs(x.astype(jnp.float32)), axis=-1, keepdims=True) / 127.0
    sx = jnp.maximum(sx, 1e-8)
    xq = jnp.clip(jnp.round(x.astype(jnp.float32) / sx), -127, 127).astype(jnp.int8)
    acc = jax.lax.dot_general(xq, qw, (((xq.ndim - 1,), (0,)), ((), ())),
                              preferred_element_type=jnp.int32)
    return (acc.astype(jnp.float32) * sx * sw + b).astype(x.dtype)


class PagedGPTDecoder:
    """Stacked-weight GPT decode executor over paged KV pools."""

    # the interface both decoders keep towards the engine (see
    # `serving/mla_decoder.py`): counters that ride `ragged_multi`'s
    # `real` block beside the real token count (none here), and the
    # engine options this decoder cannot serve (none)
    horizon_counters = ()
    engine_refusals = {}
    # columns of the page table one step of the attention's walk copies:
    # the walk ends at the deepest row's block, so the engine's record
    # counts whole blocks (`pages_gathered`). None: the attention copies
    # the whole table it is handed (the latent decoder's absorbed form)
    walk_block_pages = KEY_BLOCK_PAGES

    @build_span("decoder.build")
    def __init__(self, model, num_pages=128, page_size=16, max_batch=8,
                 max_pages_per_seq=None, quant=None, kv_quant=None,
                 use_kernel=False, dtype=None, temperature=0.0, top_k=0,
                 top_p=1.0, seed=0, mesh=None):
        cfg = model.cfg
        self.cfg = cfg
        self.page_size = page_size
        self.num_pages = num_pages
        self.max_batch = max_batch
        self.max_pages = max_pages_per_seq or \
            (cfg.max_seq_len + page_size - 1) // page_size
        self.quant = quant
        self.kv_quant = kv_quant
        self.use_kernel = use_kernel
        assert quant in (None, "a8w8", "w4a16"), quant
        assert kv_quant in (None, "int8", "int4"), kv_quant
        # temperature 0 = greedy (reference decode convention)
        self.sampling = None if not temperature else \
            (float(temperature), int(top_k), float(top_p))
        self.seed = int(seed)
        self._draws = 0
        dtype = dtype or jnp.dtype(cfg.dtype)

        state = {k: np.asarray(v._value)
                 for k, v in model.state_dict().items()}
        L = cfg.num_layers

        def stack(fmt):
            return jnp.asarray(
                np.stack([state[fmt.format(i)] for i in range(L)]))

        H, D = cfg.num_heads, cfg.head_dim
        w = {
            "ln1_w": stack("blocks.{}.ln1.weight"),
            "ln1_b": stack("blocks.{}.ln1.bias"),
            # head-major qkv layout [L, h, 3, H, D]: under tp the shard
            # axis is the HEAD dim, which propagates cleanly through the
            # per-head attention and the head-sharded KV pages (a flat
            # [h, 3h] out-dim shard mixes q/k/v columns and costs an
            # all-gather per layer)
            "qkv_w": stack("blocks.{}.qkv.weight").reshape(
                cfg.num_layers, cfg.hidden_size, 3, H, D),
            "qkv_b": stack("blocks.{}.qkv.bias").reshape(
                cfg.num_layers, 3, H, D),
            "proj_w": stack("blocks.{}.proj.weight"),
            "proj_b": stack("blocks.{}.proj.bias"),
            "ln2_w": stack("blocks.{}.ln2.weight"),
            "ln2_b": stack("blocks.{}.ln2.bias"),
            "fc1_w": stack("blocks.{}.fc1.weight"),
            "fc1_b": stack("blocks.{}.fc1.bias"),
            "fc2_w": stack("blocks.{}.fc2.weight"),
            "fc2_b": stack("blocks.{}.fc2.bias"),
        }
        if quant:
            if quant == "w4a16":
                from ..ops.w4_matmul import quantize_w4 as quantizer
            else:
                quantizer = _quantize_w
            for k in ("qkv_w", "proj_w", "fc1_w", "fc2_w"):
                v = w[k]
                shp = v.shape
                if v.ndim > 3:          # qkv head-major: flatten to 2-D
                    v = v.reshape(shp[0], shp[1], -1)
                q, s = jax.vmap(quantizer)(v)
                # restore the head-major rank (w4's packed in-dim is
                # h/2) so _shard_for_tp's specs apply to both quant
                # modes exactly as to fp; the scan slices tuples
                # leaf-wise per layer
                w[k] = (q.reshape((shp[0], q.shape[1]) + shp[2:]),
                        s.reshape((shp[0],) + shp[2:]))
        self.weights = w
        self.wte = jnp.asarray(state["wte.weight"])
        self.wpe = jnp.asarray(state["wpe.weight"])
        self.ln_f_w = jnp.asarray(state["ln_f.weight"])
        self.ln_f_b = jnp.asarray(state["ln_f.bias"])
        self.lm_head = jnp.asarray(
            state.get("lm_head.weight", state["wte.weight"].T))

        H, D = cfg.num_heads, cfg.head_dim
        # activations/embeddings compute at this width whatever the
        # pool stores (the int8 pool dequantizes inside the attention
        # body, never in HBM)
        self.compute_dtype = dtype
        if kv_quant == "int4":
            # nibble-packed pages + one f32 write-time scale per
            # (layer, token, group) for each of K and V: the token's
            # H*D elements pack two-per-byte with a per-INT4_GROUP
            # scale plane next to them (`_quantize_kv_int4` pads the
            # tail group and the odd nibble) — the KV byte stream
            # behind the decode roofline drops ~4x vs bf16
            hd = H * D
            grp = min(INT4_GROUP, hd)
            G = (hd + grp - 1) // grp
            PB = (G * grp + 1) // 2
            self.k_pages = (
                jnp.zeros((L, num_pages, page_size, PB), jnp.uint8),
                jnp.zeros((L, num_pages, page_size, G), jnp.float32))
            self.v_pages = (
                jnp.zeros((L, num_pages, page_size, PB), jnp.uint8),
                jnp.zeros((L, num_pages, page_size, G), jnp.float32))
        elif kv_quant:
            # int8 pages + one f32 write-time scale per (layer, token)
            # for each of K and V: 4 bytes/token/layer of metadata per
            # plane next to the H*D int8 payload — the KV byte stream
            # behind the decode roofline halves vs bf16
            self.k_pages = (
                jnp.zeros((L, num_pages, page_size, H, D), jnp.int8),
                jnp.zeros((L, num_pages, page_size), jnp.float32))
            self.v_pages = (
                jnp.zeros((L, num_pages, page_size, H, D), jnp.int8),
                jnp.zeros((L, num_pages, page_size), jnp.float32))
        else:
            self.k_pages = jnp.zeros((L, num_pages, page_size, H, D),
                                     dtype)
            self.v_pages = jnp.zeros((L, num_pages, page_size, H, D),
                                     dtype)

        # tensor-parallel serving: shard the 3h/ffn/head dims of the
        # stacked weights and the HEAD dim of the KV pages over 'tp';
        # GSPMD inserts the all-reduces after proj/ffn2 — the Megatron
        # decode layout, no code changes in the step function
        self.mesh = mesh
        if mesh is None:
            from ..distributed.mesh import get_mesh
            m = get_mesh(create_default=False)
            if m is not None and m.shape.get("tp", 1) > 1:
                self.mesh = m
        if self.mesh is not None:
            self._shard_for_tp()

        self._decode = _named_jit(self._decode_step,
                                  self.program_name("tick", 1, 1, None),
                                  donate_argnums=(1, 2))
        self._multis = {}     # (k, return_logits) -> jitted fused loop
        # the mixed horizons are memoized per table width too (a shape:
        # it compiled a program of its own before as well), so that each
        # program carries its whole key in its name
        self._packeds = {}    # (k, t, window, width) -> jitted PACKED horizon
        # (w rides as a traced scalar — per-dispatch width changes
        # never compile a new program; dispatches bucket by total
        # token count t alone)
        self._packed_prefills = {}   # (t, window) -> jitted packed prefill
        self._verify = None   # jitted lazily (speculative decoding only)
        self._probs = None    # jitted lazily (sampled speculation)
        self._copy = None     # jitted lazily (copy-on-write page copy)
        self._mount = None    # jitted lazily (host-tier page restore)
        self._mount_multi = {}   # span length -> jitted batched restore
        # engines serving over this pool (weak): load_pool_state
        # refuses while any of them holds live refcounted pages —
        # swapping pool bytes under a live PrefixCache ledger would
        # silently orphan it
        self._engines = weakref.WeakSet()
        # multi-LoRA (serving.tenancy): stacked low-rank adapter banks
        # over the shared base weights, attached via attach_adapters.
        # None = no adapters — every compiled program keeps its exact
        # pre-tenancy signature and trace (the HLO regression pins).
        self.lora = None
        self.n_adapters = 0
        self._adapter_salts = [b""]
        _LIVE_DECODERS.add(self)

    @staticmethod
    @functools.lru_cache(maxsize=None)
    def program_name(kind, k, x, width, window=None):
        """The ONE name of a horizon's program, made of the key the
        decoder memoizes it by — the dispatch shape (`kind`, k ticks,
        `x` = the packed token bucket t), the
        page table's `width` in columns and, packed, the `window` one
        row's tokens are laid out in (`packed_window`): `jit_<name>` on
        a trace's "XLA Modules" line, `program` on the engine's
        `engine.dispatch` span, on the horizon's record and in the
        compile log (`profiler.compile_log`). Kinds: "packed", "decode"
        (`decode_multi`, on the whole table) and "tick" (the per-tick
        `decode`). Cached: a
        round pays a lookup, not a format."""
        if kind == "packed":
            return f"packed_multi_k{k}_t{x}_w{window}_p{width}"
        return f"decode_multi_k{k}" if kind == "decode" else "decode_step"

    # ---------------------------------------------------- multi-LoRA

    def attach_adapters(self, adapters, alpha=None):
        """Attach stacked low-rank (LoRA) adapter banks for multi-LoRA
        serving: `adapters` is a list of per-adapter (A, B) pairs with
        A [L, h, r] and B [L, r, 3, H, D] (or [L, r, 3*H*D]) — the
        low-rank qkv delta of one fine-tuned variant over the SHARED
        base weights. Adapter id 0 is reserved for the base model (an
        exact all-zero bank); caller adapters are ids 1..n. Mixed
        ranks zero-pad to the max (zero rows/cols contribute exact
        0.0). `alpha` scales every delta by alpha/r, folded into B at
        attach time (default: alpha == r, scale 1).

        Rows gather the bank per TOKEN (`_lora_delta`, through the
        packed layout's row ids), so one ragged
        horizon serves every variant through one compiled program; the
        jit wrappers retrace automatically (the weights pytree gains
        the bank leaves and an `aids` input). Per-adapter
        `adapter_salt` fingerprints keep prefix-cache page sharing
        sound across variants: pages never alias across differing
        adapter banks (the MEM-PAGE-REFCOUNT ledger audit checks the
        live engine's slot adapters)."""
        cfg = self.cfg
        L = cfg.num_layers
        hd3 = 3 * cfg.num_heads * cfg.head_dim
        h = cfg.hidden_size
        ranks = []
        pairs = []
        for a, b in adapters:
            a = np.asarray(a, np.float32)
            b = np.asarray(b, np.float32).reshape(L, a.shape[-1], hd3)
            if a.shape != (L, h, a.shape[-1]):
                raise ValueError(
                    f"adapter A must be [num_layers, hidden, r], got "
                    f"{a.shape}")
            ranks.append(a.shape[-1])
            pairs.append((a, b))
        R = max(ranks) if ranks else 1
        n = len(pairs)
        A = np.zeros((L, n + 1, h, R), np.float32)
        B = np.zeros((L, n + 1, R, hd3), np.float32)
        salts = [b""]
        for i, ((a, b), r) in enumerate(zip(pairs, ranks), start=1):
            scale = (float(alpha) / r) if alpha is not None else 1.0
            A[:, i, :, :r] = a
            B[:, i, :r, :] = b * scale
            # CONTENT hash, not content sums: two structurally related
            # fine-tunes (e.g. a row permutation) can share every sum,
            # and colliding salts would alias their cache pages — the
            # exact corruption the slot_adapters audit exists to catch
            h = hashlib.blake2b(digest_size=16)
            h.update(np.float32(scale).tobytes())
            h.update(a.tobytes())
            h.update(b.tobytes())
            salts.append(h.digest())
        self.lora = {"lora_A": jnp.asarray(A), "lora_B": jnp.asarray(B)}
        self.n_adapters = n
        self._adapter_salts = salts
        return self

    def adapter_salt(self, aid):
        """Prefix-cache key salt of adapter `aid` (b"" for the base
        model, id 0): KV bytes written under an adapter depend on its
        bank, so chain keys must fold it in or pages would alias
        across variants."""
        return self._adapter_salts[int(aid)]

    def _w(self):
        """Weights pytree the compiled programs consume: the stacked
        base weights, plus the LoRA banks when attached (the bank
        leaves ride the per-layer lax.scan next to the base stacks;
        `cache_fingerprint` keeps reading `self.weights` only — the
        BASE identity — with adapters salted separately)."""
        return {**self.weights, **self.lora} if self.lora else \
            self.weights

    def _aids_or_default(self, aids):
        """[S] int32 adapter ids (None -> all base) — only consulted
        when a bank is attached; without one the compiled programs
        never see an aids input."""
        if aids is None:
            return np.zeros(self.max_batch, np.int32)
        return np.asarray(aids, np.int32)

    def _probs_of(self, logits):
        """softmax over the decoder's sampling mask (the distribution its
        sampled tokens are actually drawn from)."""
        if self._probs is None:
            from ..models.generation import mask_logits
            if self.sampling:
                t, tk, tp = self.sampling
                self._probs = _named_jit(lambda lg: jax.nn.softmax(
                    mask_logits(lg, t, tk, tp), axis=-1), "sampling_probs")
            else:
                self._probs = _named_jit(
                    lambda lg: jax.nn.softmax(lg, axis=-1), "greedy_probs")
        return np.asarray(self._probs(logits))

    def _shard_for_tp(self):
        from jax.sharding import NamedSharding
        from jax.sharding import PartitionSpec as P

        mesh = self.mesh
        tp = mesh.shape.get("tp", 1)
        if self.cfg.num_heads % tp:
            raise ValueError(
                f"num_heads {self.cfg.num_heads} must divide over "
                f"tp={tp} for tensor-parallel serving")
        if self.cfg.ffn_hidden % tp:
            raise ValueError(
                f"ffn_hidden {self.cfg.ffn_hidden} must divide over "
                f"tp={tp} for tensor-parallel serving")

        def put(v, *spec):
            return jax.device_put(v, NamedSharding(mesh, P(*spec)))

        w = self.weights

        def put_w(key, *spec):
            if isinstance(w[key], tuple):      # a8w8 (q, per-out scale)
                q, s = w[key]
                w[key] = (put(q, *spec), put(s, spec[0], *spec[2:]))
            else:
                w[key] = put(w[key], *spec)

        # column-parallel qkv (HEAD axis — aligns with the per-head
        # attention and the head-sharded pages, no reshard) and fc1;
        # row-parallel proj/fc2; biases follow their out dims
        put_w("qkv_w", None, None, None, "tp", None)
        w["qkv_b"] = put(w["qkv_b"], None, None, "tp", None)
        put_w("proj_w", None, "tp", None)
        put_w("fc1_w", None, None, "tp")
        w["fc1_b"] = put(w["fc1_b"], None, "tp")
        put_w("fc2_w", None, "tp", None)
        self.wte = put(self.wte, None, None)
        if self.lm_head.shape[-1] % tp == 0:
            self.lm_head = put(self.lm_head, None, "tp")
        else:
            # odd vocab (e.g. 50257): keep the head replicated rather
            # than fail — logits are [S, V] and small at decode batch
            self.lm_head = put(self.lm_head, None, None)
        # KV pages: heads sharded — each tp shard holds its heads' pages
        # (int8 pools shard the byte payload the same way; the per-token
        # scale planes have no head axis and replicate — their amax
        # reduces over ALL heads, a tiny per-layer collective GSPMD
        # inserts at the write). int4 pools replicate BOTH leaves: the
        # nibble axis is the flattened H*D stream packed two-per-byte,
        # so a head boundary can land mid-byte and mid-group — there is
        # no clean head shard of the packed payload.
        def put_pool(pool):
            if isinstance(pool, tuple):
                if pool[0].dtype == jnp.uint8:
                    return (put(pool[0], None, None, None, None),
                            put(pool[1], None, None, None, None))
                return (put(pool[0], None, None, None, "tp", None),
                        put(pool[1], None, None, None))
            return put(pool, None, None, None, "tp", None)

        self.k_pages = put_pool(self.k_pages)
        self.v_pages = put_pool(self.v_pages)

    # -- compiled programs -------------------------------------------------

    def _forward_tokens(self, weights, k_pages, v_pages, tokens, lens,
                        table, pids, offs, aids=None):
        """Shared single-position forward over all slots: embed `tokens`
        at position `lens`, write K/V at (pids, offs) — callers route
        frozen slots' pids to the reserved scratch page — and attend
        over each slot's pages. Returns (logits [S, V], k_pages,
        v_pages). Both the per-tick step and every tick of the fused
        multi-step scan run THIS body, so they cannot drift. `aids`
        [S] selects each slot's LoRA adapter when a bank is attached
        (None with no bank — the program shape is then exactly the
        pre-tenancy one)."""
        cfg = self.cfg
        H, D = cfg.num_heads, cfg.head_dim
        S = tokens.shape[0]
        x = (self.wte[tokens] +
             self.wpe[jnp.clip(lens, 0, cfg.max_seq_len - 1)]
             ).astype(self.compute_dtype)                      # [S, h]
        quant = self.quant

        def layer(carry, xs):
            x, kp, vp = carry
            wl, li = xs
            y = _ln(x, wl["ln1_w"], wl["ln1_b"])
            qkv = _mm_heads(y, wl["qkv_w"], wl["qkv_b"], quant)  # [S,3,H,D]
            if aids is not None:
                qkv = qkv + _lora_delta(wl, y, aids).reshape(
                    S, 3, H, D).astype(qkv.dtype)
            q, k, v = qkv[:, 0], qkv[:, 1], qkv[:, 2]
            with jax.named_scope("kv_write"):
                kp = _kv_set(kp, li, pids, offs, k)
                vp = _kv_set(vp, li, pids, offs, v)
            # the ONE ragged kernel behind every serving path (decode is
            # the W=1 row kind): causal over kpos <= lens, i.e. the
            # slot's prefix plus the key written just above
            from ..ops.ragged_paged_attention import ragged_paged_attention
            attn = ragged_paged_attention(q[:, None], kp, vp, table, lens,
                                          use_kernel=self.use_kernel,
                                          layer=li)
            x = x + _mm(attn.reshape(S, H * D), wl["proj_w"], wl["proj_b"],
                        quant)
            y = _ln(x, wl["ln2_w"], wl["ln2_b"])
            h = jax.nn.gelu(_mm(y, wl["fc1_w"], wl["fc1_b"], quant),
                            approximate=True)
            x = x + _mm(h, wl["fc2_w"], wl["fc2_b"], quant)
            return (x, kp, vp), None

        x, k_pages, v_pages = self._scan_layers(layer, x, weights,
                                                k_pages, v_pages)
        x = _ln(x, self.ln_f_w, self.ln_f_b)
        logits = x.astype(jnp.float32) @ self.lm_head.astype(jnp.float32)
        return logits, k_pages, v_pages

    def _scan_layers(self, layer, x, weights, k_pages, v_pages):
        """THE layer loop of every compiled program of this decoder:
        `layer` (one of the three blocks: `_forward_tokens`',
        `_windowed_layer`, `_packed_layer`) runs as a `lax.scan` body
        whose CARRY is (x, k_pages, v_pages) — the whole pools,
        [L, P, ps, ...] in every leaf — and whose `xs` are a layer's
        weights and its index. A block writes its layer of the pools in
        place (`_kv_set`) and reads it through the page gather
        (`ragged_paged_attention*(layer=)`); the loop has no `ys`.
        Pools handed over as `xs` and taken back as `ys` are sliced out
        of the stack and written back into a fresh one layer by layer,
        and the fresh stack is copied into the tick scan's carry once a
        tick: a third of a serving wave's device time on one v5e, none
        of it arithmetic (`test_packed_horizon_moves_no_pool` holds the
        compiled horizon to none of the three).
        Returns (x, k_pages, v_pages)."""
        with jax.named_scope("layers"):
            carry, _ = jax.lax.scan(
                layer, (x, k_pages, v_pages),
                (weights, jnp.arange(self.cfg.num_layers)))
        return carry

    def _pos_keys(self, kids, pos):
        """Per-slot PRNG keys from (seed, kid, position): draws depend
        only on the decoder seed, the request identity (`kids` — the
        engine passes the request id; direct callers default to the
        slot index) and the position of the token being consumed.
        NOTHING about scheduling enters the key, so the same request
        sampled through the per-tick loop, the fused multi-step loop,
        or any admission/batch composition draws the same tokens."""
        base = jax.random.PRNGKey(self.seed)
        return jax.vmap(lambda kid, p: jax.random.fold_in(
            jax.random.fold_in(base, kid), p))(kids, pos)

    def _decode_step(self, weights, k_pages, v_pages, tokens, lens, table,
                     kids, aids=None):
        """tokens [S], lens [S] (tokens already counted, i.e. position of
        the incoming token), table [S, max_pages], kids [S] (sampling
        key ids, see _pos_keys) -> (next [S], logits [S, V], k_pages,
        v_pages)."""
        ps = self.page_size
        pids = jnp.take_along_axis(table, (lens // ps)[:, None],
                                   axis=1)[:, 0]                # [S]
        offs = lens % ps
        logits, k_pages, v_pages = self._forward_tokens(
            weights, k_pages, v_pages, tokens, lens, table, pids, offs,
            aids=aids)
        keys = None
        if self.sampling is not None:
            keys = self._pos_keys(kids, lens)
        nxt = _sample_tokens(logits, self.sampling, keys)
        return nxt, logits, k_pages, v_pages

    def _decode_multi_step(self, weights, k_pages, v_pages, tokens, lens,
                           table, kids, done, remaining, eos, aids=None,
                           *, k, return_logits=False):
        """K fused decode ticks inside ONE compiled program (lax.scan):
        each tick's sampled token feeds the next tick on device, so the
        host syncs once per K tokens instead of once per token.

        tokens/lens/table/kids as in `_decode_step`. Tick j draws with
        the (seed, kid, lens+j) key — exactly the keys the per-tick
        loop would use at those positions, so fused and per-tick decode
        emit byte-identical streams. `done` [S] bool freezes a slot
        from tick 0 (inactive or already finished); a slot also freezes
        itself after emitting its first `eos` (pass -1 for none) or
        after `remaining` [S] tokens (its budget). Frozen slots' `lens`
        stop advancing and their K/V writes route to the reserved
        scratch page, so the pages stay exactly as the per-tick engine
        would leave them.

        Returns (block [k, S] emitted tokens, done_before [k, S] — True
        where the slot was already frozen, i.e. the token is filler —
        final tokens/lens/done/remaining, k_pages, v_pages[, logits
        [k, S, V] when return_logits])."""
        ps = self.page_size
        scratch = self.num_pages - 1

        def tick(carry, _):
            tokens, lens, done, remaining, kp, vp = carry
            pids = jnp.take_along_axis(table, (lens // ps)[:, None],
                                       axis=1)[:, 0]
            pids = jnp.where(done, scratch, pids)
            offs = lens % ps
            logits, kp, vp = self._forward_tokens(
                weights, kp, vp, tokens, lens, table, pids, offs,
                aids=aids)
            keys = None
            if self.sampling is not None:
                keys = self._pos_keys(kids, lens)
            nxt = _sample_tokens(logits, self.sampling, keys)
            nxt = jnp.where(done, tokens, nxt)
            rem = jnp.where(done, remaining, remaining - 1)
            new_done = done | (nxt == eos) | (rem <= 0)
            new_lens = jnp.where(done, lens, lens + 1)
            out = (nxt, done, logits) if return_logits else (nxt, done)
            return (nxt, new_lens, new_done, rem, kp, vp), out

        carry = (tokens, lens, done, remaining, k_pages, v_pages)
        carry, outs = jax.lax.scan(tick, carry, jnp.arange(k))
        tokens, lens, done, remaining, k_pages, v_pages = carry
        ret = (outs[0], outs[1], tokens, lens, done, remaining,
               k_pages, v_pages)
        if return_logits:
            ret += (outs[2],)
        return ret

    def _windowed_layer(self, pos, pids, offs, table, aids=None):
        """ONE ragged-attention transformer layer over [n, W] windows,
        the verify window's (`_verify_step`): write each position's K/V at
        (pids, offs) — callers route out-of-range/padded positions to
        the scratch page — attend over the row's pages with
        per-position causality (kpos <= pos) through the shared
        `ops.ragged_paged_attention` primitive, then residual proj +
        FFN."""
        cfg = self.cfg
        H, D = cfg.num_heads, cfg.head_dim
        n, W = pos.shape
        quant = self.quant

        def layer(carry, xs):
            x, kp, vp = carry
            wl, li = xs
            y = _ln(x, wl["ln1_w"], wl["ln1_b"])
            yf = y.reshape(n * W, -1)
            qkv = _mm_heads(yf, wl["qkv_w"],
                            wl["qkv_b"], quant).reshape(n, W, 3, H, D)
            if aids is not None:
                # every window token of row i wears row i's adapter
                aid_tok = jnp.broadcast_to(
                    aids[:, None], (n, W)).reshape(-1)
                qkv = qkv + _lora_delta(wl, yf, aid_tok).reshape(
                    n, W, 3, H, D).astype(qkv.dtype)
            q, k, v = qkv[:, :, 0], qkv[:, :, 1], qkv[:, :, 2]
            with jax.named_scope("kv_write"):
                kp = _kv_set(kp, li, pids, offs, k)
                vp = _kv_set(vp, li, pids, offs, v)
            # pos rows are contiguous windows (start + arange(W)), so
            # the row's first entry IS its cached length
            from ..ops.ragged_paged_attention import ragged_paged_attention
            attn = ragged_paged_attention(
                q, kp, vp, table, pos[:, 0], use_kernel=self.use_kernel,
                layer=li).astype(x.dtype)
            o = _mm(attn.reshape(n * W, H * D), wl["proj_w"],
                    wl["proj_b"], quant).reshape(n, W, -1)
            x = x + o
            y = _ln(x, wl["ln2_w"], wl["ln2_b"])
            h = jax.nn.gelu(
                _mm(y.reshape(n * W, -1), wl["fc1_w"], wl["fc1_b"],
                    quant), approximate=True)
            x = x + _mm(h, wl["fc2_w"], wl["fc2_b"],
                        quant).reshape(n, W, -1)
            return (x, kp, vp), None

        return layer

    def _verify_step(self, weights, k_pages, v_pages, tokens, lens, table):
        """Speculative verify: tokens [S, W] (last accepted token + the
        draft proposals) are consumed in ONE forward — KV written at
        positions lens..lens+W-1, causal attention against the paged
        prefix — returning the target's greedy choice after every
        position ([S, W] argmaxes). Rejected positions need no cleanup:
        lens is the source of truth and stale entries are overwritten."""
        cfg, ps = self.cfg, self.page_size
        S, W = tokens.shape
        pos = lens[:, None] + jnp.arange(W)[None, :]            # [S, W]
        x = (self.wte[tokens] +
             self.wpe[jnp.clip(pos, 0, cfg.max_seq_len - 1)]
             ).astype(self.compute_dtype)                       # [S, W, h]
        MP = table.shape[1]
        # margin guard: window positions past the table's capacity (the
        # engine admits with a +k margin, so only pathological callers
        # get here) write to the reserved scratch page, never to a
        # clamped REAL page of the sequence
        in_range = pos < MP * ps
        pids = jnp.take_along_axis(table, jnp.minimum(pos // ps, MP - 1),
                                   axis=1)                      # [S, W]
        pids = jnp.where(in_range, pids, self.num_pages - 1)
        offs = pos % ps

        x, k_pages, v_pages = self._scan_layers(
            self._windowed_layer(pos, pids, offs, table), x, weights,
            k_pages, v_pages)
        x = _ln(x, self.ln_f_w, self.ln_f_b)
        logits = x.astype(jnp.float32) @ self.lm_head.astype(jnp.float32)
        return (jnp.argmax(logits, axis=-1).astype(jnp.int32), logits,
                k_pages, v_pages)

    def verify(self, tokens, lens, table, return_probs=False):
        """Batched speculative verify (see _verify_step)."""
        if self._verify is None:
            self._verify = _named_jit(self._verify_step, "verify_step",
                                      donate_argnums=(1, 2))
        out, logits, self.k_pages, self.v_pages = self._verify(
            self.weights, self.k_pages, self.v_pages,
            jnp.asarray(tokens, jnp.int32), jnp.asarray(lens, jnp.int32),
            jnp.asarray(table, jnp.int32))
        if return_probs:
            return np.asarray(out), self._probs_of(logits)
        return np.asarray(out)

    def _packed_layer(self, rows, pos, pids, offs, table, aids=None,
                      window=None):
        """ONE transformer layer over the PACKED token stream: x is
        [T, h] flat new tokens (token t of batch row `rows[t]` at
        absolute position `pos[t]`); K/V writes land at (pids, offs) —
        the caller routes padded/frozen/overflow tokens to scratch —
        and attention runs through the packed ragged primitive
        (`ops.ragged_paged_attention_packed`), which gathers each
        ROW's pages once and lays the row's tokens out as one window
        of at most `window` queries over them. Per-token math is
        row-local: the stream's other tokens never move a byte."""
        cfg = self.cfg
        H, D = cfg.num_heads, cfg.head_dim
        T = rows.shape[0]
        quant = self.quant

        def layer(carry, xs):
            x, kp, vp = carry
            wl, li = xs
            y = _ln(x, wl["ln1_w"], wl["ln1_b"])
            qkv = _mm_heads(y, wl["qkv_w"], wl["qkv_b"],
                            quant)                       # [T, 3, H, D]
            if aids is not None:
                # per-token adapter resolution via the row id
                qkv = qkv + _lora_delta(wl, y, aids[rows]).reshape(
                    T, 3, H, D).astype(qkv.dtype)
            q, k, v = qkv[:, 0], qkv[:, 1], qkv[:, 2]
            with jax.named_scope("kv_write"):
                kp = _kv_set(kp, li, pids, offs, k)
                vp = _kv_set(vp, li, pids, offs, v)
            from ..ops.ragged_paged_attention import \
                ragged_paged_attention_packed
            attn = ragged_paged_attention_packed(
                q, kp, vp, table, rows, pos, window=window,
                use_kernel=self.use_kernel, layer=li).astype(x.dtype)
            x = x + _mm(attn.reshape(T, H * D), wl["proj_w"],
                        wl["proj_b"], quant)
            y = _ln(x, wl["ln2_w"], wl["ln2_b"])
            h = jax.nn.gelu(_mm(y, wl["fc1_w"], wl["fc1_b"], quant),
                            approximate=True)
            x = x + _mm(h, wl["fc2_w"], wl["fc2_b"], quant)
            return (x, kp, vp), None

        return layer

    def _packed_forward(self, weights, k_pages, v_pages, ptok, pos, rows,
                        write_ok, table, last_idx, sample_pos, kids,
                        live, aids=None, window=None):
        """The shared PACKED forward: consume the flat token stream
        `ptok` [T] (token t = row `rows[t]`, position `pos[t]`),
        writing real tokens' K/V into the pages (`write_ok` [T] False
        routes to scratch: padded tail, frozen rows, table overflow)
        and attending each token over its own row's pages (a row's
        tokens are contiguous in the stream, at most `window` of them:
        None = the whole stream). `last_idx`
        [S] indexes each row's LAST stream token (garbage for rows with
        no tokens — masked by `live`), whose hidden state prices the
        row's logits; `sample_pos` [S] is the sampling position
        (true_len - 1, the standard (seed, kid, position) key walk).
        Returns (next [S], k_pages, v_pages)."""
        cfg, ps = self.cfg, self.page_size
        MP = table.shape[1]
        x = (self.wte[ptok] +
             self.wpe[jnp.clip(pos, 0, cfg.max_seq_len - 1)]
             ).astype(self.compute_dtype)                 # [T, h]
        pids = table[rows, jnp.minimum(pos // ps, MP - 1)]    # [T]
        pids = jnp.where(write_ok, pids, self.num_pages - 1)
        offs = pos % ps

        x, k_pages, v_pages = self._scan_layers(
            self._packed_layer(rows, pos, pids, offs, table, aids=aids,
                               window=window),
            x, weights, k_pages, v_pages)
        x = _ln(x, self.ln_f_w, self.ln_f_b)
        last = x[jnp.clip(last_idx, 0, x.shape[0] - 1)]   # [S, h]
        last = jnp.where(live[:, None], last, 0.0)
        logits = last.astype(jnp.float32) @ \
            self.lm_head.astype(jnp.float32)
        keys = None
        if self.sampling is not None:
            keys = self._pos_keys(kids, sample_pos)
        return _sample_tokens(logits, self.sampling, keys), \
            k_pages, v_pages

    def _packed_multi_step(self, weights, k_pages, v_pages, tokens, lens,
                           table, kids, done, remaining, eos, pend,
                           pend_n, w, aids=None, *, k, t, window=None):
        """K MIXED ticks over the PACKED [t] token stream — the
        tentpole layout (Ragged Paged Attention, arxiv 2604.15464): a
        tick's stream concatenates every live row's new tokens (decode
        rows exactly ONE token, prefilling rows their next min(pend_n,
        w) suffix tokens, frozen rows NOTHING), so nobody pays window
        padding: this dispatches at most t, bucketed by
        total token count alone. `w` is a TRACED scalar (the per-row
        chunk cap); `window` (static, >= w; None = t) is the bound the
        attention lays one row's tokens out in — `packed_window(w, t)`,
        the pow2 bucket of w, so the jit key is (k, t, window): w's
        pow2 steps between t/S and t, at most log2(S)+1 per t. The
        layout (cumsum + searchsorted
        over per-row token counts) is built on device each tick from
        the carry, so the program stays one host-sync-free lax.scan
        (SERVE-HOST-SYNC-DECODE gates it).

        Every per-row rule is `packed_tick`'s: streams and pool
        bytes are byte-identical to the per-tick
        engine (test-pinned). Returns the RaggedMultiOut tuple layout
        (tokens_block [k, S], emitted [k, S], real [k], finals...)."""
        def forward(lay, pools):
            nxt, kp, vp = self._packed_forward(
                weights, *pools, lay.ptok, lay.pos, lay.rows, lay.write_ok,
                table, lay.last_idx, lay.true - 1, kids, lay.live,
                aids=aids, window=window)
            return nxt, (kp, vp), ()

        def tick(carry, _):
            return packed_tick(carry, w, eos, t=t,
                               capacity=table.shape[1] * self.page_size,
                               forward=forward)

        carry = (tokens, lens, done, remaining, pend, pend_n,
                 k_pages, v_pages)
        carry, outs = jax.lax.scan(tick, carry, jnp.arange(k))
        tokens, lens, done, remaining, pend, pend_n, k_pages, v_pages = \
            carry
        return (outs[0], outs[1], outs[2], tokens, lens, done, remaining,
                pend, pend_n, k_pages, v_pages)

    def _prefill_packed_step(self, weights, k_pages, v_pages, ptok, pos,
                             rows, write_ok, table, last_idx, sample_pos,
                             kids, live, aids=None, *, window=None):
        """PACKED chunked prefill: one forward over the flat suffix
        stream of a whole admission batch — mixed suffix lengths share
        ONE compiled program per total-token bucket instead of one per
        (suffix-width, batch) pair (`prefill_suffix_batch` builds the
        layout host-side). The body is `_packed_forward`, the same
        program family as the packed horizon tick."""
        return self._packed_forward(weights, k_pages, v_pages, ptok,
                                    pos, rows, write_ok, table,
                                    last_idx, sample_pos, kids, live,
                                    aids=aids, window=window)

    # -- host-side API -----------------------------------------------------

    def prefill(self, ids, page_ids, kid=None):
        """Run one prompt through the model, writing KV into `page_ids`;
        returns the next token (greedy, or sampled per the decoder's
        temperature/top_k/top_p config)."""
        return self.prefill_batch([(ids, page_ids)],
                                  kids=None if kid is None else [kid])[0]

    def prefill_batch(self, requests, kids=None):
        """Prefill several prompts in full. requests: [(ids, page_ids),
        ...]; returns the first generated token per request (in order).
        `kids` are the per-request sampling key ids (see _pos_keys; the
        engine passes request ids — default: the request's index in
        this call).

        A thin wrapper over the chunked ragged body at start=0: the
        separate flash-attention length-bucketed prefill is GONE — ALL
        prefill runs through the same per-position program family as
        the mixed horizons (`_packed_forward`), so a prompt's
        KV bytes are identical across every admission path (flash vs
        chunked drift is structurally impossible)."""
        return self.prefill_suffix_batch(
            [(ids, 0, pages) for ids, pages in requests], kids=kids)

    def prefill_suffix_batch(self, requests, kids=None, aids=None):
        """Chunked prefill over page-table rows (the prefix-cache
        admission path). requests: [(suffix_ids, start, pages), ...] —
        `pages` is the sequence's page list in block order (cached
        prefix pages mounted by the engine + freshly allocated suffix
        pages), `start` the cached prefix length (0 = nothing cached:
        the suffix IS the prompt).

        PACKED: each group of up to max_batch requests
        dispatches ONE flat [total_tokens] stream
        (`_prefill_packed_step`) bucketed by total token count (pow2)
        — mixed suffix lengths share one compiled program instead of
        one per (suffix-width, batch) pair, and nobody pays
        pad-to-longest window columns: the
        layout — flat tokens, per-token row ids and positions — is
        built host-side (all lengths are known here), bucketed to a
        pow2 total-token count and a pow2 longest suffix (the
        attention's per-row window, `packed_window`), and jitted once
        per pair (`_packed_prefills`). Returns the first generated
        token per request (in order)."""
        results = [None] * len(requests)
        if kids is None:
            kids = list(range(len(requests)))
        if aids is None:
            aids = [0] * len(requests)
        S, MP, ps = self.max_batch, self.max_pages, self.page_size
        todo = list(enumerate(requests))
        while todo:
            chunk, todo = todo[:S], todo[S:]
            lay = packed_prefill_layout([req for _, req in chunk], S, MP,
                                        ps, self.num_pages - 1)
            t, window = lay.t, lay.window
            kd = np.zeros(S, np.int32)
            ad = np.zeros(S, np.int32)
            for r, (i, _) in enumerate(chunk):
                kd[r] = kids[i]
                ad[r] = aids[i]
            fn = self._packed_prefills.get((t, window))
            if fn is None:
                fn = _named_jit(
                    functools.partial(self._prefill_packed_step,
                                      window=window),
                    f"prefill_packed_t{t}_w{window}",
                    donate_argnums=(1, 2))
                self._packed_prefills[t, window] = fn
            self._draws += 1
            call = (jnp.asarray(lay.ptok), jnp.asarray(lay.pos),
                    jnp.asarray(lay.rows), jnp.asarray(lay.ok),
                    jnp.asarray(lay.table), jnp.asarray(lay.last_idx),
                    jnp.asarray(lay.sample_pos), jnp.asarray(kd),
                    jnp.asarray(lay.live))
            if self.lora is not None:
                call += (jnp.asarray(ad),)
            nxt, self.k_pages, self.v_pages = fn(
                self._w(), self.k_pages, self.v_pages, *call)
            nxt = np.asarray(nxt)
            for r, (i, _) in enumerate(chunk):
                results[i] = int(nxt[r])
        return results

    def copy_page(self, src, dst):
        """Device-side page copy (K and V, every layer): the engine's
        copy-on-write primitive — a request about to write into a page
        it mounted SHARED gets a private copy first, so cached prefix
        pages stay immutable for their whole cached life."""
        if self._copy is None:
            def cp(kp, vp, s, d):
                # tree_map: an int8 pool's page BYTES and its scale
                # plane rows move together — a copy that left the
                # scales behind would dequantize the private page with
                # the zero-initialized scales (garbage tokens; the
                # MEM-PAGE-REFCOUNT scale audit exists to catch it)
                def one(a):
                    return a.at[:, d].set(a[:, s])
                return (jax.tree_util.tree_map(one, kp),
                        jax.tree_util.tree_map(one, vp))
            self._copy = _named_jit(cp, "copy_page",
                                    donate_argnums=(0, 1))
        self.k_pages, self.v_pages = self._copy(
            self.k_pages, self.v_pages,
            jnp.asarray(int(src), jnp.int32),
            jnp.asarray(int(dst), jnp.int32))

    def fetch_page_payload(self, page):
        """D2H copy of ONE page's bytes across every layer — the
        host-tier SPILL primitive: ``{"k": (leaves...), "v": (...)}``
        with each leaf the pool leaf sliced at the page ([L, ps, H, D]
        bytes; int8 pools also carry their [L, ps] f32 scale rows, so
        the spill is already quantized — half the host bytes). The
        inverse is `mount_page_payload`; the round trip is lossless,
        which is what lets a restored page keep the byte-identical-
        stream invariant."""
        p = int(page)

        def grab(pool):
            leaves = pool if isinstance(pool, tuple) else (pool,)
            return tuple(np.asarray(leaf[:, p]) for leaf in leaves)

        return {"k": grab(self.k_pages), "v": grab(self.v_pages)}

    def fetch_page_payloads(self, pages):
        """D2H copy of a WHOLE eviction wave in one stacked transfer
        per pool leaf (`fetch_page_payload` batched): the pool leaf is
        gathered at all `pages` on device ([L, n, ps, ...]) and fetched
        once, then split host-side into the per-page payload dicts the
        host tier stores. Per-page D2H paid one blocking round trip per
        victim — a pressure wave of n evictions cost n syncs for bytes
        the device could have streamed together."""
        idx = jnp.asarray([int(p) for p in pages], jnp.int32)

        def grab(pool):
            leaves = pool if isinstance(pool, tuple) else (pool,)
            return [np.asarray(leaf[:, idx]) for leaf in leaves]

        k_stk, v_stk = grab(self.k_pages), grab(self.v_pages)
        return [{"k": tuple(leaf[:, i] for leaf in k_stk),
                 "v": tuple(leaf[:, i] for leaf in v_stk)}
                for i in range(len(pages))]

    def mount_page_payloads(self, pages, payloads):
        """H2D restore of a WHOLE restored span in one donated jitted
        scatter (`mount_page_payload` batched, jitted per span length):
        every pool leaf takes its [L, n, ps, ...] stacked values at the
        n page ids in one `.at[:, pids].set`. Like the single-page
        mount, the dispatch does not block — jax's functional pool
        threading orders every later horizon after the writes — but an
        n-block restore now pays ONE dispatch instead of n."""
        n = len(pages)
        if n == 1:
            return self.mount_page_payload(pages[0], payloads[0])
        fn = self._mount_multi.get(n)
        if fn is None:
            def mnt(kp, vp, pids, kvals, vvals):
                def setp(pool, vals):
                    leaves = pool if isinstance(pool, tuple) else (pool,)
                    out = [leaf.at[:, pids].set(v)
                           for leaf, v in zip(leaves, vals)]
                    return tuple(out) if isinstance(pool, tuple) \
                        else out[0]
                return setp(kp, kvals), setp(vp, vvals)
            fn = self._mount_multi[n] = _named_jit(
                mnt, f"mount_pages_n{n}", donate_argnums=(0, 1))

        def stack(part):
            n_leaves = len(payloads[0][part])
            return tuple(jnp.asarray(np.stack(
                [np.asarray(p[part][i]) for p in payloads], axis=1))
                for i in range(n_leaves))

        self.k_pages, self.v_pages = fn(
            self.k_pages, self.v_pages,
            jnp.asarray([int(p) for p in pages], jnp.int32),
            stack("k"), stack("v"))

    def mount_page_payload(self, page, payload):
        """H2D restore of a spilled page (`fetch_page_payload`'s
        inverse): scatter the payload leaves into page `page` of every
        pool leaf. One jitted donated update, dispatched WITHOUT
        blocking — jax's functional pool threading orders every later
        horizon after this write (the restored pool IS its input), so
        the H2D overlaps whatever the host does next and no reader can
        observe a half-mounted page."""
        if self._mount is None:
            def mnt(kp, vp, pid, kvals, vvals):
                def setp(pool, vals):
                    leaves = pool if isinstance(pool, tuple) else (pool,)
                    out = [leaf.at[:, pid].set(v)
                           for leaf, v in zip(leaves, vals)]
                    return tuple(out) if isinstance(pool, tuple) \
                        else out[0]
                return setp(kp, kvals), setp(vp, vvals)
            self._mount = _named_jit(mnt, "mount_page",
                                     donate_argnums=(0, 1))
        self.k_pages, self.v_pages = self._mount(
            self.k_pages, self.v_pages, jnp.asarray(int(page), jnp.int32),
            tuple(jnp.asarray(x) for x in payload["k"]),
            tuple(jnp.asarray(x) for x in payload["v"]))

    def pool_state(self):
        """Checkpointable KV-pool state: the page arrays (and, for an
        int8 pool, their scale planes) plus the quant config that
        produced them. `load_pool_state` refuses a mismatched config —
        int8 bytes interpreted as bf16 (or the reverse) would decode
        garbage tokens with no error anywhere downstream."""
        return {"kv_quant": self.kv_quant or "",
                "k_pages": self.k_pages, "v_pages": self.v_pages}

    def load_pool_state(self, state):
        """Restore a `pool_state()` snapshot into this decoder's pool.
        The stored quant config, leaf dtypes and shapes must all match
        this decoder's pool layout exactly — and no attached engine may
        hold pages over the pool: swapping the bytes under a slot
        table, a referenced PrefixCache entry, OR a parked one would
        orphan the page ledger with no error anywhere downstream (a
        parked entry outlives a drain, and its next hit would mount
        the checkpoint's bytes as if they were the chain key's
        write-time KV). Rebuild the decoder+cache pair instead —
        `PrefixCache.load` does exactly that."""
        for eng in list(self._engines):
            held = sum(len(p) for p in getattr(eng, "_slot_pages", ()))
            cache = getattr(eng, "cache", None)
            tracked = len(cache._entries) if cache is not None else 0
            if held or tracked:
                raise RuntimeError(
                    f"cannot load pool state: a live "
                    f"{type(eng).__name__} holds {held} slot page(s) "
                    f"and its prefix cache tracks {tracked} page(s) "
                    "over this pool — swapping the page bytes now "
                    "would orphan its ledger (a parked entry's next "
                    "hit would mount checkpoint bytes under the old "
                    "chain key); drain the engine and rebuild the "
                    "decoder+cache pair (PrefixCache.load) instead")
        quant = state.get("kv_quant", "") or None
        if quant != self.kv_quant:
            raise ValueError(
                f"KV pool quant config mismatch: this decoder stores "
                f"{self.kv_quant or 'unquantized (' + str(jnp.dtype(self.compute_dtype)) + ')'} "
                f"pages but the checkpointed pool was written "
                f"{quant or 'unquantized'} — reinterpreting the bytes "
                "would decode garbage tokens; rebuild the decoder with "
                f"kv_quant={quant!r} or re-prefill from tokens")
        for name in ("k_pages", "v_pages"):
            have = getattr(self, name)
            want = state[name]
            h_leaves = jax.tree_util.tree_leaves(have)
            w_leaves = jax.tree_util.tree_leaves(want)
            if len(h_leaves) != len(w_leaves) or any(
                    hl.shape != wl.shape or
                    jnp.dtype(hl.dtype) != jnp.dtype(wl.dtype)
                    for hl, wl in zip(h_leaves, w_leaves)):
                raise ValueError(
                    f"KV pool state mismatch on {name}: expected "
                    f"{[(tuple(l.shape), str(l.dtype)) for l in h_leaves]}, "
                    f"got "
                    f"{[(tuple(getattr(l, 'shape', ())), str(getattr(l, 'dtype', '?'))) for l in w_leaves]}")
        # jnp.array (copy), NOT jnp.asarray: a host numpy leaf can be
        # zero-copied into the CPU backend, and the decode programs
        # DONATE the pool — XLA must own the bytes it recycles
        self.k_pages = jax.tree_util.tree_map(
            lambda l: jnp.array(l), state["k_pages"])
        self.v_pages = jax.tree_util.tree_map(
            lambda l: jnp.array(l), state["v_pages"])

    @property
    def _pool_itemsize(self):
        """Bytes one stored K (or V) element costs in the pool."""
        leaf = self.k_pages[0] if isinstance(self.k_pages, tuple) \
            else self.k_pages
        return jnp.dtype(leaf.dtype).itemsize

    @property
    def kv_token_bytes(self):
        """KV bytes ONE token costs per layer (K and V together,
        scale-plane metadata included for the int8 pool) — the unit of
        every KV byte count this decoder reports (`kv_page_bytes`,
        `step_hbm_bytes`, ServeStats.kv_bytes_per_token)."""
        return pool_token_bytes(self.cfg, kv_quant=self.kv_quant,
                                itemsize=self._pool_itemsize)

    def kv_token_bytes_by_layer(self):
        """Per-LAYER KV bytes one token costs — the pricing hook for
        layer-mixed precision pools. Today every layer stores the same
        width, so this is `kv_token_bytes` repeated num_layers times;
        `step_hbm_bytes` sums THIS list for the live-pool leg, so the
        day a pool mixes widths across layers (e.g. int8 first/last,
        int4 middle) only this method changes and every capacity /
        horizon / admission consumer re-prices automatically."""
        return [self.kv_token_bytes] * self.cfg.num_layers

    @property
    def kv_page_bytes(self):
        """KV bytes one page holds across all layers (K and V, scale
        planes included) — the prefix cache's bytes-saved unit."""
        return int(self.cfg.num_layers * self.page_size *
                   self.kv_token_bytes)

    def cache_fingerprint(self):
        """Model/sampling-invariant identity of this decoder's KV bytes
        — the prefix cache's root-key salt. KV pages depend on the
        weights, architecture, page size, pool dtype and quant mode but
        NOT on temperature/seed, so two decoders may alias cached pages
        exactly when this matches. Weight identity rides on cheap
        content probes over EVERY stacked tensor (per-tensor f32 sums
        — embeddings alone would alias a frozen-embedding fine-tune
        with its base model)."""
        cfg = self.cfg

        def probe(v):
            if isinstance(v, tuple):         # quantized (q, scale)
                return tuple(probe(x) for x in v)
            return float(jnp.sum(v.astype(jnp.float32)))

        probes = tuple(probe(self.weights[k])
                       for k in sorted(self.weights))
        probes += (probe(self.wte), probe(self.wpe),
                   probe(self.lm_head), probe(self.ln_f_w),
                   probe(self.ln_f_b))
        pool_leaf = self.k_pages[0] if isinstance(self.k_pages, tuple) \
            else self.k_pages
        parts = (cfg.num_layers, cfg.hidden_size, cfg.num_heads,
                 cfg.head_dim, cfg.vocab_size, cfg.max_seq_len,
                 self.page_size, str(jnp.dtype(pool_leaf.dtype)),
                 self.quant or "", self.kv_quant or "", probes)
        return repr(parts).encode()

    def analysis_program(self, donate=True, k=None, prefix_w=None,
                         ragged=None, verify_w=None):
        """Graph Doctor view of the compiled decode program: one fresh
        trace with per-argument role capture — weights/embeddings are
        `param` (read-only across steps, NOT donated: that's correct
        for inference), the K/V page pools are `cache` with
        donated=True matching the real donate_argnums=(1,2) (the cache
        is the decode loop's carried state — an undonated cache is the
        MEM-NO-DONATION-KVCACHE lint), everything else is `input`.

        With `k` the FUSED multi-step program (`_decode_multi_step`, K
        device-resident ticks in one lax.scan) is traced instead of the
        single tick — the SERVE-HOST-SYNC-DECODE rule checks it for
        host transfers and kept cache donation. With `prefix_w` the
        chunked-prefill program is traced
        (`_prefill_packed_step`, one flat stream at total-token bucket
        S*prefix_w) — the prefix-cache
        admission path, gated by the same serving rules plus the
        MEM-PAGE-REFCOUNT ledger audit (`gpt_decode_prefix` PROGRAM
        config). With `ragged=(k, w)` the MIXED ragged horizon program
        is traced (`_packed_multi_step`: K ticks
        over the flat [t] token stream, t = the pow2 bucket of one
        w-wide chunk row next to S-1 decode rows, w a traced input) —
        the `gpt_decode_ragged` PROGRAM config gates it with
        SERVE-HOST-SYNC-DECODE and (via an engine schedule trace on
        the context) SERVE-PREFILL-STALL. `donate=False` traces the
        defective variant the planted-defect tests lint.

        With `verify_w` the SPECULATIVE verify-window program
        (`_verify_step`, the SpeculativeEngine's target forward over
        the last accepted token + W-1 draft proposals) is traced with
        the window tokens captured as "draft_tokens" — request-
        EXTRINSIC bytes under the Determinism Doctor's provenance
        lattice, so KV-WRITE-NONCANONICAL fires on its pool writes:
        the documented expected red (draft bytes land in real pages
        BEFORE acceptance; the ROADMAP's commit-on-accept work must
        turn this program green)."""
        from ..analysis.lowering import LoweredProgram, tree_arg_infos

        S = self.max_batch
        W_ALL = self._w()        # adapter banks ride along when attached
        kids = jnp.arange(S, dtype=jnp.int32)
        table = jnp.zeros((S, self.max_pages), jnp.int32)
        # with a LoRA bank attached, every traced program additionally
        # takes the per-slot adapter ids (the gpt_decode_mt PROGRAM
        # config traces the adapter-gather horizon through this)
        aid_in = (jnp.zeros((S,), jnp.int32)
                  if self.lora is not None else None)
        aid_tail = () if aid_in is None else (aid_in,)
        if sum(map(bool, (k, prefix_w, ragged, verify_w))) > 1:
            raise ValueError(
                "pass only one of k=, prefix_w=, ragged=, verify_w=")
        if verify_w:
            W = int(verify_w)
            draft = jnp.zeros((S, W), jnp.int32)
            lens = jnp.zeros((S,), jnp.int32)
            inputs = [("draft_tokens", draft), ("lens", lens),
                      ("table", table)]
            fn = jax.jit(self._verify_step,
                         donate_argnums=(1, 2) if donate else ())
            traced = fn.trace(self.weights, self.k_pages, self.v_pages,
                              draft, lens, table)
            name = f"verify_w{W}"
            infos = tree_arg_infos(self.weights, "param")
            infos += tree_arg_infos(self.k_pages, "cache",
                                    prefix="k_pages", donated=donate)
            infos += tree_arg_infos(self.v_pages, "cache",
                                    prefix="v_pages", donated=donate)
            for nm, v in inputs:
                infos += tree_arg_infos(v, "input", prefix=nm)
            return LoweredProgram(traced.lower().as_text(),
                                  jaxpr=traced.jaxpr, name=name,
                                  arg_infos=infos)
        if ragged:
            rk, rw = map(int, ragged)
            P = self.pend_capacity
            tokens = jnp.zeros((S,), jnp.int32)
            lens = jnp.zeros((S,), jnp.int32)
            done = jnp.zeros((S,), bool)
            remaining = jnp.full((S,), rk, jnp.int32)
            eos = jnp.asarray(-1, jnp.int32)
            pend = jnp.zeros((S, P), jnp.int32)
            pend_n = jnp.zeros((S,), jnp.int32)
            inputs = [("tokens", tokens), ("lens", lens),
                      ("table", table), ("kids", kids), ("done", done),
                      ("remaining", remaining), ("eos", eos),
                      ("pend", pend), ("pend_n", pend_n)]
            if aid_in is not None:
                inputs.append(("aids", aid_in))
            t = pow2_at_least(S - 1 + rw)   # a chunk beside decode rows
            w_in = jnp.asarray(rw, jnp.int32)
            inputs.append(("w", w_in))
            fn = jax.jit(functools.partial(self._packed_multi_step,
                                           k=rk, t=t,
                                           window=packed_window(rw, t)),
                         donate_argnums=(1, 2) if donate else ())
            traced = fn.trace(W_ALL, self.k_pages, self.v_pages, tokens,
                              lens, table, kids, done, remaining, eos,
                              pend, pend_n, w_in, *aid_tail)
            name = f"ragged_packed_k{rk}_t{t}"
        elif prefix_w:
            W = int(prefix_w)
            t = pow2_at_least(S * W)    # a full admission batch
            zt, zs = jnp.zeros((t,), jnp.int32), jnp.zeros((S,), jnp.int32)
            inputs = [("ptok", zt), ("pos", zt), ("rows", zt),
                      ("write_ok", jnp.zeros((t,), bool)), ("table", table),
                      ("last_idx", zs), ("sample_pos", zs),
                      ("kids", kids), ("live", jnp.ones((S,), bool))]
            if aid_in is not None:
                inputs.append(("aids", aid_in))
            fn = jax.jit(functools.partial(self._prefill_packed_step,
                                           window=packed_window(W, t)),
                         donate_argnums=(1, 2) if donate else ())
            traced = fn.trace(W_ALL, self.k_pages, self.v_pages,
                              *(v for _, v in inputs))
            name = f"prefill_packed_t{t}"
        elif k:
            tokens = jnp.zeros((S,), jnp.int32)
            lens = jnp.zeros((S,), jnp.int32)
            done = jnp.zeros((S,), bool)
            remaining = jnp.full((S,), int(k), jnp.int32)
            eos = jnp.asarray(-1, jnp.int32)
            inputs = [("tokens", tokens), ("lens", lens),
                      ("table", table), ("kids", kids), ("done", done),
                      ("remaining", remaining), ("eos", eos)]
            if aid_in is not None:
                inputs.append(("aids", aid_in))
            fn = jax.jit(functools.partial(self._decode_multi_step,
                                           k=int(k)),
                         donate_argnums=(1, 2) if donate else ())
            traced = fn.trace(W_ALL, self.k_pages, self.v_pages,
                              tokens, lens, table, kids, done, remaining,
                              eos, *aid_tail)
            name = f"decode_multi_k{int(k)}"
        else:
            tokens = jnp.zeros((S,), jnp.int32)
            lens = jnp.zeros((S,), jnp.int32)
            inputs = [("tokens", tokens), ("lens", lens),
                      ("table", table), ("kids", kids)]
            if aid_in is not None:
                inputs.append(("aids", aid_in))
            fn = jax.jit(self._decode_step,
                         donate_argnums=(1, 2) if donate else ())
            traced = fn.trace(W_ALL, self.k_pages, self.v_pages,
                              tokens, lens, table, kids, *aid_tail)
            name = "decode_step"
        infos = tree_arg_infos(W_ALL, "param")
        infos += tree_arg_infos(self.k_pages, "cache", prefix="k_pages",
                                donated=donate)
        infos += tree_arg_infos(self.v_pages, "cache", prefix="v_pages",
                                donated=donate)
        for nm, v in inputs:
            infos += tree_arg_infos(v, "input", prefix=nm)
        return LoweredProgram(traced.lower().as_text(),
                              jaxpr=traced.jaxpr, name=name,
                              arg_infos=infos)

    def step_hbm_bytes(self, avg_ctx=None, batch=None, kv_quant="pool"):
        """HBM bytes ONE decode tick moves: every weight byte plus each
        slot's KV prefix at `avg_ctx` (default: half the model's max
        sequence). The numerator of the decode tick roofline —
        `cost_model.decode_horizon` prices the default multi-step K
        from it. An int8 pool reports its TRUE byte stream
        (int8 payload + the f32 per-token scale planes), so the horizon
        K and the ragged chunk budget re-price
        automatically when the pool quantizes. `batch` overrides the
        slot count (capacity planning sweeps it to find the
        max slots under a fixed per-token p99). `kv_quant` overrides
        the pool's quant mode for WHAT-IF pricing — e.g.
        ``kv_quant="int4"`` prices the per-group-scale int4 pool
        (packed nibbles + f32 group scales, `pool_token_bytes`) on a
        decoder whose live pool runs another width, so capacity
        planning can rank bf16 vs int8 vs int4 streams from one
        decoder. The live-pool path sums `kv_token_bytes_by_layer`, so
        a future per-layer mixed-precision pool re-prices here with no
        caller changes."""
        cfg = self.cfg
        n = cfg.num_params()
        per = {"a8w8": 1.0, "w4a16": 0.5}.get(self.quant)
        if per is not None:
            h, f = cfg.hidden_size, cfg.ffn_hidden
            lin = cfg.num_layers * (4 * h * h + 2 * h * f)
            w_bytes = lin * per + (n - lin) * 2
        else:
            w_bytes = n * 2
        if avg_ctx is None:
            avg_ctx = max(cfg.max_seq_len // 2, 1)
        if batch is None:
            batch = self.max_batch
        if kv_quant == "pool":
            return int(w_bytes +
                       batch * avg_ctx * sum(self.kv_token_bytes_by_layer()))
        else:
            # what-if override: an UNQUANTIZED what-if must price the
            # compute dtype's width, not the live pool's leaf itemsize
            # (on an int8 pool that is 1 byte, which would rank the
            # "unquantized" stream CHEAPER than int8 — backwards)
            itemsize = self._pool_itemsize if self.kv_quant is None \
                else jnp.dtype(self.compute_dtype).itemsize
            tok_bytes = pool_token_bytes(cfg, kv_quant=kv_quant,
                                         itemsize=itemsize)
        kv = batch * cfg.num_layers * avg_ctx * tok_bytes
        return int(w_bytes + kv)

    def _kids_or_default(self, kids):
        if kids is None:
            return np.arange(self.max_batch, dtype=np.int32)
        return np.asarray(kids, np.int32)

    def decode(self, tokens, lens, table, kids=None, return_probs=False,
               aids=None):
        """One decode step for all slots (greedy, or the configured
        sampling with deterministic per-(seed, kid, position) keys —
        kid defaults to the slot index; the engine passes request ids
        so a request's draws are scheduling-independent).
        return_probs additionally yields the [S, V] distribution the
        token was drawn from (speculative acceptance needs it). `aids`
        [S] selects per-slot LoRA adapters when a bank is attached
        (`attach_adapters`); without one it must stay None."""
        self._draws += 1
        args = (self._w(), self.k_pages, self.v_pages,
                jnp.asarray(tokens, jnp.int32),
                jnp.asarray(lens, jnp.int32),
                jnp.asarray(table, jnp.int32),
                jnp.asarray(self._kids_or_default(kids)))
        if self.lora is not None:
            args += (jnp.asarray(self._aids_or_default(aids)),)
        nxt, logits, self.k_pages, self.v_pages = self._decode(*args)
        if return_probs:
            return nxt, self._probs_of(logits)
        return nxt

    def decode_multi(self, tokens, lens, table, k, kids=None, done=None,
                     remaining=None, eos=None, return_logits=False,
                     aids=None):
        """Run `k` decode ticks device-resident: ONE dispatch, zero
        intermediate host syncs (see `_decode_multi_step`). Jitted per
        (k, return_logits); the engine buckets k to powers of two so
        the compile count stays bounded like the prefill buckets.

        All inputs/outputs may stay on device: the engine feeds the
        returned tokens/lens/done/remaining straight into the next
        horizon's call and fetches tokens_block/done_before only at
        sync points. `kids` are per-slot sampling key ids (see
        `_pos_keys`; default slot index), `done` marks slots frozen
        from tick 0 (default none), `remaining` per-slot token budgets
        (default unlimited), `eos` the stop token (default none).
        Returns a MultiDecodeOut;
        `logits_block` is None unless return_logits (speculation wants
        the draft's distributions)."""
        k = int(k)
        S = self.max_batch
        key = (k, bool(return_logits))
        fn = self._multis.get(key)
        if fn is None:
            fn = _named_jit(
                functools.partial(self._decode_multi_step, k=k,
                                  return_logits=bool(return_logits)),
                self.program_name("decode", k, 1, None)
                + ("_logits" if return_logits else ""),
                donate_argnums=(1, 2))
            self._multis[key] = fn
        if done is None:
            done = np.zeros(S, bool)
        if remaining is None:
            remaining = np.full(S, np.iinfo(np.int32).max // 2, np.int32)
        self._draws += k             # dispatch telemetry, not key state
        args = (self._w(), self.k_pages, self.v_pages,
                jnp.asarray(tokens, jnp.int32),
                jnp.asarray(lens, jnp.int32),
                jnp.asarray(table, jnp.int32),
                jnp.asarray(self._kids_or_default(kids)),
                jnp.asarray(done, bool),
                jnp.asarray(remaining, jnp.int32),
                jnp.asarray(-1 if eos is None else int(eos), jnp.int32))
        if self.lora is not None:
            args += (jnp.asarray(self._aids_or_default(aids)),)
        out = fn(*args)
        self.k_pages, self.v_pages = out[6], out[7]
        return MultiDecodeOut(out[0], out[1], out[2], out[3], out[4],
                              out[5], out[8] if return_logits else None)

    @property
    def pend_capacity(self):
        """Static width of the ragged horizon's device-resident prompt
        suffix buffer: the pool's per-sequence token capacity (ONE
        compiled shape — no per-prompt-length buckets)."""
        return self.max_pages * self.page_size

    def ragged_multi(self, tokens, lens, table, k, w, pend, pend_n,
                     kids=None, done=None, remaining=None, eos=None,
                     t_tokens=None, aids=None):
        """Run `k` MIXED ragged ticks device-resident: decode rows and
        prefill-chunk rows serve together, up to w suffix tokens per
        prefilling slot per tick, ONE dispatch, zero intermediate host
        syncs.

        Each tick dispatches the flat [t_tokens] token stream
        (`_packed_multi_step`) — decode rows pay ONE token, not a
        w-wide window — jitted per (k, t_tokens, `packed_window(w,
        t_tokens)`, table width) with w riding as a traced scalar, so
        dispatches bucket by TOTAL token count (pow2; the scheduler's
        `HorizonPlan.t_tokens` prices it) and by the pow2 step of w.
        `t_tokens` must cover the largest per-tick total (live rows +
        chunk shares; defaults to the dense-equivalent S*w bound when
        the caller doesn't supply the tight bucket).

        All inputs/outputs may stay on device; `pend` [S, P] /
        `pend_n` [S] are the carried prompt suffixes
        (P = `pend_capacity`). Returns a RaggedMultiOut."""
        k, w = int(k), int(w)
        S = self.max_batch
        if done is None:
            done = np.zeros(S, bool)
        if remaining is None:
            remaining = np.full(S, np.iinfo(np.int32).max // 2, np.int32)
        self._draws += k             # dispatch telemetry, not key state
        args = (jnp.asarray(tokens, jnp.int32),
                jnp.asarray(lens, jnp.int32),
                jnp.asarray(table, jnp.int32),
                jnp.asarray(self._kids_or_default(kids)),
                jnp.asarray(done, bool),
                jnp.asarray(remaining, jnp.int32),
                jnp.asarray(-1 if eos is None else int(eos), jnp.int32),
                jnp.asarray(pend, jnp.int32),
                jnp.asarray(pend_n, jnp.int32))
        if t_tokens is None:        # callers pass the tight pow2 bucket
            t_tokens = pow2_at_least(S * max(w, 1))
        t = max(int(t_tokens), 1)
        if t < S:                   # a live slot's token would be dropped
            raise ValueError(
                f"t_tokens {t} < max_batch {S}: the packed bucket "
                "must cover at least one token per slot")
        width = args[2].shape[1]
        window = packed_window(w, t)
        key = (k, t, window, width)
        fn = self._packeds.get(key)
        if fn is None:
            fn = _named_jit(
                functools.partial(self._packed_multi_step, k=k, t=t,
                                  window=window),
                self.program_name("packed", k, t, width, window),
                donate_argnums=(1, 2))
            self._packeds[key] = fn
        call = args + (jnp.asarray(w, jnp.int32),)
        if self.lora is not None:
            call += (jnp.asarray(self._aids_or_default(aids)),)
        out = fn(self._w(), self.k_pages, self.v_pages, *call)
        self.k_pages, self.v_pages = out[9], out[10]
        return RaggedMultiOut(*out[:9])
