"""The five BASELINE configs (BASELINE.json) as lintable model specs.

Each entry builds a tiny, CPU-lowerable stand-in for a headline
workload (same architecture family, same graph invariants, shrunk
shapes) plus the AnalysisContext carrying its contracts: data format,
dtype policy, by-design transpose exemptions, f32 exemptions, and
expected op counts published by the model modules themselves
(GRAPH_CONTRACT / graph_contract next to each architecture).

Lowerings are cached per config for the process lifetime — the pytest
lint gate and the CLI share one trace per model.
"""
import jax.numpy as jnp

from .pass_manager import AnalysisContext

__all__ = ["BASELINE_CONFIGS", "PROGRAM_CONFIGS", "SCHEDULE_CONFIGS",
           "DETERMINISM_CONFIGS", "build_config", "lowered_program",
           "forward_fn", "tuning_report"]

_CACHE = {}   # name -> (LoweredProgram, AnalysisContext, forward fn)
_TUNING_CACHE = {}   # name -> AutotuneReport (autotune.autotune_layer)

# the ragged paged attention's by-design reorders (one body behind
# decode ticks, chunked prefill and the mixed horizon — see
# ops/ragged_paged_attention.py): the head-major flip of q, of a
# block's values and of the output. Shared by every serving PROGRAM
# config.
RAGGED_ATTENTION_TRANSPOSES = (r"dims = \[0, 2, 1, 3\]",)


def _fresh():
    import paddle_tpu as paddle
    from paddle_tpu.distributed import build_mesh
    paddle.seed(0)
    build_mesh(dp=1)
    return paddle


def _resnet50():
    paddle = _fresh()
    from paddle_tpu.vision.models import resnet
    model = paddle.vision.models.resnet50(num_classes=10,
                                          data_format="NHWC")
    model.bfloat16()
    model.eval()
    x = jnp.zeros((2, 64, 64, 3), jnp.bfloat16)
    ctx = AnalysisContext(
        name="resnet50", policy_dtype="bfloat16", data_format="NHWC",
        expected_counts=dict(resnet.GRAPH_CONTRACT),
        expect_collectives=False)
    return model, (x,), ctx


def _bert_base():
    paddle = _fresh()
    from paddle_tpu.models import bert as bert_mod
    cfg = bert_mod.bert_base(dtype="bfloat16")
    cfg.num_layers = 2          # graph shape per layer is what matters
    model = bert_mod.BertModel(cfg)
    model.bfloat16()
    model.train()               # dropout ACTIVE — that's the pin
    ids = jnp.zeros((2, 64), jnp.int32)
    from paddle_tpu.models.gpt import ATTENTION_TRANSPOSES
    ctx = AnalysisContext(
        name="bert_base", policy_dtype="bfloat16",
        allowed_activation_transposes=ATTENTION_TRANSPOSES,
        expected_counts=bert_mod.graph_contract(cfg),
        expect_collectives=False)
    return model, (ids,), ctx


def _gpt():
    paddle = _fresh()
    from paddle_tpu.models import GPT, gpt_tiny
    from paddle_tpu.models import gpt as gpt_mod
    cfg = gpt_tiny(dtype="bfloat16", remat=False)
    model = GPT(cfg)
    model.bfloat16()
    model.eval()
    ids = jnp.zeros((2, 32), jnp.int32)
    ctx = AnalysisContext(
        name="gpt", policy_dtype="bfloat16",
        allowed_activation_transposes=gpt_mod.ATTENTION_TRANSPOSES,
        expected_counts=gpt_mod.graph_contract(cfg),
        expect_collectives=False)
    return model, (ids,), ctx


def _ppocr_crnn():
    paddle = _fresh()
    from paddle_tpu.vision.models import CRNN
    from paddle_tpu.vision.models import ocr as ocr_mod
    model = CRNN(num_classes=97, data_format="NHWC")
    model.bfloat16()
    model.eval()
    x = jnp.zeros((2, 32, 64, 3), jnp.bfloat16)
    ctx = AnalysisContext(
        name="ppocr_crnn", policy_dtype="bfloat16", data_format="NHWC",
        # the single by-design [B,W',C]->[W',B,C] sequence-major flip
        allowed_activation_transposes=(
            r"dims = \[1, 0, 2\]",),
        expected_counts=dict(ocr_mod.GRAPH_CONTRACT),
        expect_collectives=False)
    return model, (x,), ctx


def _gpt_moe():
    paddle = _fresh()
    from paddle_tpu.models import GPTMoE
    from paddle_tpu.models import moe as moe_mod
    cfg = moe_mod.gpt_moe_tiny(dtype="bfloat16")
    model = GPTMoE(cfg)
    model.bfloat16()
    model.eval()
    ids = jnp.zeros((2, 32), jnp.int32)
    from paddle_tpu.models.gpt import ATTENTION_TRANSPOSES
    ctx = AnalysisContext(
        name="gpt_moe", policy_dtype="bfloat16",
        allowed_activation_transposes=ATTENTION_TRANSPOSES,
        f32_dot_allow=moe_mod.router_f32_allow(cfg),
        expect_collectives=False)
    return model, (ids,), ctx


# config name -> builder() -> (model, example_arrays, AnalysisContext)
BASELINE_CONFIGS = {
    "resnet50": _resnet50,        # ResNet-50 imgs/sec (vision config)
    "bert_base": _bert_base,      # ERNIE/BERT encoder config
    "gpt": _gpt,                  # GPT-3 1.3B pretraining family
    "ppocr_crnn": _ppocr_crnn,    # PP-OCR conv+RNN config
    "gpt_moe": _gpt_moe,          # GPT-MoE expert-parallel config
}


def _gpt_decode():
    """The SERVING config: the fused multi-step decode loop
    (PagedGPTDecoder.decode_multi, K=4 device-resident ticks) captured
    via analysis_program(k=4) — not an nn.Layer forward, so it lives in
    PROGRAM_CONFIGS (no tuning manifest: there is nothing to remat in a
    decode tick). The SERVE-HOST-SYNC-DECODE rule gates it: zero host
    transfers inside the loop, KV-cache donation kept."""
    paddle = _fresh()
    from paddle_tpu.models import GPT, gpt_tiny
    from paddle_tpu.models import gpt as gpt_mod
    from paddle_tpu.serving import PagedGPTDecoder
    cfg = gpt_tiny(max_seq_len=64, dtype="float32", remat=False)
    model = GPT(cfg)
    model.eval()
    dec = PagedGPTDecoder(model, num_pages=16, page_size=16, max_batch=2)
    program = dec.analysis_program(k=4)
    ctx = AnalysisContext(
        name="gpt_decode",
        # the ragged attention's gather/head reorders ride with the
        # dense model's by-design attention transposes
        allowed_activation_transposes=gpt_mod.ATTENTION_TRANSPOSES
        + RAGGED_ATTENTION_TRANSPOSES,
        expect_collectives=False,
        extra={"serving_decode": True})
    return program, ctx, PagedGPTDecoder._decode_multi_step


def _gpt_train_multi():
    """The fused multi-step TRAINING config: `Trainer.step_multi`'s
    N=4 scan over a leading-stacked batch (donated params/opt-state/
    consts carry, [N] lr vector, unfetched [N] loss output) captured
    via `Trainer.analysis_program(batch, n=4)` — a PROGRAM config like
    gpt_decode (the capture is a whole train step, not a Layer
    forward; no tuning manifest — the remat advisor already covers the
    single-step twin). The HOST-SYNC-TRAIN rule gates it: zero host
    transfers inside the scan, donated carry, a real device loop."""
    paddle = _fresh()
    from paddle_tpu.distributed.trainer import Trainer
    from paddle_tpu.models import GPT, GPTPretrainingCriterion, gpt_tiny
    from paddle_tpu.models import gpt as gpt_mod
    cfg = gpt_tiny(max_seq_len=32, dtype="float32", remat=False)
    model = GPT(cfg)
    model.train()
    crit = GPTPretrainingCriterion()

    def loss_fn(m, batch):
        logits = m(paddle.to_tensor(batch["input_ids"]))
        return crit(logits, paddle.to_tensor(batch["labels"]))

    opt = paddle.optimizer.AdamW(learning_rate=1e-3)
    trainer = Trainer(model, opt, loss_fn)
    batch = {"input_ids": jnp.zeros((2, 32), jnp.int32),
             "labels": jnp.zeros((2, 32), jnp.int32)}
    program = trainer.analysis_program(batch, n=4)
    ctx = AnalysisContext(
        name="gpt_train_multi",
        # backward pass: the weight-grad matmul (x^T . dy) flips one
        # 2-D operand — by-design in every train step, rides with the
        # dense model's attention transposes
        allowed_activation_transposes=gpt_mod.ATTENTION_TRANSPOSES
        + (r"dims = \[1, 0\] : \(tensor<\d+x\d+xf32>\)",),
        expect_collectives=False,
        extra={"train_multi": True})
    return program, ctx, Trainer._build_multi


def _gpt_decode_prefix():
    """The PREFIX-CACHE serving config: the PACKED suffix-prefill
    program (`PagedGPTDecoder._prefill_packed_step` — one flat token
    stream for a whole admission batch, bucketed by total token count;
    W=16 sizes the trace's bucket) captured via
    `analysis_program(prefix_w=16)`, plus a page LEDGER committed from
    a real TIERED shared-prefix workload: a full-hit copy-on-write, a
    pool-pressure eviction that SPILLS to a `HostKVTier`, and a
    host-only chain RESTORED back into the pool — so the committed
    ledger carries host-tier rows (a restored entry with its
    device-twin backref and a host-only spilled entry) next to the
    parked/shared device rows.  Gated by SERVE-HOST-SYNC-DECODE (zero
    host transfers, donated KV pool — the chunked prefill is part of
    the serving hot path) and by MEM-PAGE-REFCOUNT (the ledger audit:
    refcounted sharing frees every page exactly once, and a host
    entry's device twin is never on the free list)."""
    import numpy as np
    paddle = _fresh()
    from paddle_tpu.models import GPT, gpt_tiny
    from paddle_tpu.models import gpt as gpt_mod
    from paddle_tpu.serving import (ContinuousBatchingEngine,
                                    HostKVTier, PagedGPTDecoder,
                                    PrefixCache)
    cfg = gpt_tiny(max_seq_len=64, dtype="float32", remat=False)
    model = GPT(cfg)
    model.eval()
    # 3 allocatable pages: each request needs 2, each base block parks
    # 1 — the third distinct base forces an eviction (spill), and
    # re-referencing the first base restores its host-only chain
    dec = PagedGPTDecoder(model, num_pages=4, page_size=16, max_batch=2)
    eng = ContinuousBatchingEngine(
        dec, max_new_tokens=4, k_max=2, tier_policy="restore",
        prefix_cache=PrefixCache(16, salt=dec.cache_fingerprint(),
                                 tier=HostKVTier()))
    b1 = list(range(1, 17))              # full shareable blocks
    b2 = list(range(31, 47))
    b3 = list(range(51, 67))
    for prompt in (b1 + [21, 22, 23],    # miss + insert
                   b1,                   # FULL hit -> copy-on-write
                   b2 + [24],            # second template parks
                   b3 + [25],            # pressure: evicts+SPILLS b1
                   b1 + [26]):           # host-only chain -> RESTORE
        eng.submit(np.asarray(prompt, np.int32))
        eng.run()
    assert eng.stats.tier_spills and eng.stats.tier_restores, \
        "tiered ledger workload lost its spill/restore shape"
    program = dec.analysis_program(prefix_w=16)
    ctx = AnalysisContext(
        name="gpt_decode_prefix",
        # the chunked body's ragged-attention reorders ride with the
        # dense model's by-design attention transposes (same exemptions
        # as gpt_decode — one shared body)
        allowed_activation_transposes=gpt_mod.ATTENTION_TRANSPOSES
        + RAGGED_ATTENTION_TRANSPOSES,
        expect_collectives=False,
        extra={"serving_decode": True,
               "page_ledger": eng.page_ledger()})
    return program, ctx, PagedGPTDecoder._prefill_packed_step


def _gpt_decode_ragged():
    """The RAGGED serving config: the PACKED mixed chunked-prefill +
    decode horizon program (`PagedGPTDecoder._packed_multi_step`, K=4
    ticks over the flat [total_new_tokens] stream — the pow2 bucket of
    one w=8 chunk row next to S-1 decode rows; the per-row chunk cap w
    rides as a traced input) captured via `analysis_program(ragged=(4,
    8))`, plus a SCHEDULING TRACE committed from a real
    long-prompt-arrives-mid-stream workload (a short request decoding
    while a 40-token prompt streams into the same horizons as chunks).
    Gated by SERVE-HOST-SYNC-DECODE (zero host transfers inside the
    fused mixed scan, donated KV pool, a real device loop) and by
    SERVE-PREFILL-STALL (the trace must contain NO host-blocking
    prefill dispatch while decode slots run — the stall the ragged
    scheduler deletes)."""
    import numpy as np
    paddle = _fresh()
    from paddle_tpu.models import GPT, gpt_tiny
    from paddle_tpu.models import gpt as gpt_mod
    from paddle_tpu.serving import ContinuousBatchingEngine, PagedGPTDecoder
    cfg = gpt_tiny(max_seq_len=64, dtype="float32", remat=False)
    model = GPT(cfg)
    model.eval()
    dec = PagedGPTDecoder(model, num_pages=16, page_size=16, max_batch=2)
    eng = ContinuousBatchingEngine(dec, max_new_tokens=6, k_max=4,
                                   chunk_tokens=8)
    eng.submit(np.arange(1, 6, dtype=np.int32))          # short, decodes
    eng.submit(np.arange(1, 41, dtype=np.int32))         # long, chunks in
    eng.run()
    program = dec.analysis_program(ragged=(4, 8))
    ctx = AnalysisContext(
        name="gpt_decode_ragged",
        # the ragged page-scan attention's gather/head reorders ride
        # with the dense model's by-design attention transposes
        allowed_activation_transposes=gpt_mod.ATTENTION_TRANSPOSES
        + RAGGED_ATTENTION_TRANSPOSES,
        expect_collectives=False,
        extra={"serving_decode": True,
               "serve_schedule": eng.serve_schedule()})
    return program, ctx, PagedGPTDecoder._packed_multi_step


def _gpt_decode_kv8():
    """The INT8-KV serving config: the fused K=4 decode loop over an
    int8 KV pool with per-token f32 scale planes (`kv_quant="int8"` —
    the pool's byte stream behind the decode roofline halves, which is
    what `step_hbm_bytes`/`decode_horizon` re-price). Gated by the
    serving rules gpt_decode carries (SERVE-HOST-SYNC-DECODE: zero host
    transfers, donated pool — now FOUR cache leaves: pages + scale
    planes for K and V), by the new kv-quant rules
    (DTYPE-KV-SCALE-WIDTH: scale planes exactly f32;
    DTYPE-KV-DEQUANT-HBM: no full-pool dequantization materialized in
    HBM — dequant stays inside the shared per-page attention update),
    and by MEM-PAGE-REFCOUNT over a page ledger committed from a real
    shared-prefix int8 workload including a full-hit copy-on-write
    (CoW moves page bytes AND scale rows together)."""
    import numpy as np
    paddle = _fresh()
    from paddle_tpu.models import GPT, gpt_tiny
    from paddle_tpu.models import gpt as gpt_mod
    from paddle_tpu.serving import (ContinuousBatchingEngine,
                                    PagedGPTDecoder, PrefixCache)
    cfg = gpt_tiny(max_seq_len=64, dtype="float32", remat=False)
    model = GPT(cfg)
    model.eval()
    # a pool larger than ONE step's working set (2 rows x a block of 8
    # pages), as every real pool is: the step's own convert must stay
    # under the DTYPE-KV-DEQUANT-HBM threshold below
    dec = PagedGPTDecoder(model, num_pages=64, page_size=16, max_batch=2,
                          kv_quant="int8")
    eng = ContinuousBatchingEngine(
        dec, max_new_tokens=4, k_max=2,
        prefix_cache=PrefixCache(16, salt=dec.cache_fingerprint()))
    base = list(range(1, 17))            # one full shareable block
    for tail in ([21, 22, 23], []):      # miss+insert, then a FULL hit
        eng.submit(np.asarray(base + tail, np.int32))
        eng.run()
    program = dec.analysis_program(k=4)
    ctx = AnalysisContext(
        name="gpt_decode_kv8",
        # the shared ragged-attention reorders (the scale planes ride
        # the block copy as they are: no layout move of their own)
        allowed_activation_transposes=gpt_mod.ATTENTION_TRANSPOSES
        + RAGGED_ATTENTION_TRANSPOSES,
        expect_collectives=False,
        extra={"serving_decode": True,
               "kv_quant": "int8",
               # one per-layer [P, ps, H, D] pool tensor: a convert of
               # this many int8 elements to a wide float IS the
               # dequantized pool landing in HBM
               "kv_pool_block_elems": (dec.num_pages * dec.page_size *
                                       cfg.num_heads * cfg.head_dim),
               "page_ledger": eng.page_ledger()})
    return program, ctx, PagedGPTDecoder._decode_multi_step


def _gpt_decode_kv4():
    """The INT4-KV serving config: the fused K=4 decode loop over a
    nibble-packed int4 pool with per-GROUP f32 scale planes
    (`kv_quant="int4"` — uint8 pages [L,P,ps,PB] + scales [L,P,ps,G];
    the pool's byte stream behind the decode roofline drops ~4x vs
    bf16). Same gate set as gpt_decode_kv8, re-proven on the packed
    layout: SERVE-HOST-SYNC-DECODE (zero host transfers, four donated
    cache leaves), DTYPE-KV-SCALE-WIDTH (group-scale planes exactly
    f32), DTYPE-KV-DEQUANT-HBM (the nibble unpack's int8->f32 convert
    stays per-block inside the attention's walk — a full-pool
    dequant materialized in HBM is the defect), and MEM-PAGE-REFCOUNT
    over a page ledger committed from a real shared-prefix int4
    workload including a full-hit copy-on-write (CoW moves nibble
    bytes AND group-scale rows together)."""
    import numpy as np
    paddle = _fresh()
    from paddle_tpu.models import GPT, gpt_tiny
    from paddle_tpu.models import gpt as gpt_mod
    from paddle_tpu.serving import (ContinuousBatchingEngine,
                                    PagedGPTDecoder, PrefixCache)
    cfg = gpt_tiny(max_seq_len=64, dtype="float32", remat=False)
    model = GPT(cfg)
    model.eval()
    # a pool larger than one step's working set: see gpt_decode_kv8
    dec = PagedGPTDecoder(model, num_pages=64, page_size=16, max_batch=2,
                          kv_quant="int4")
    eng = ContinuousBatchingEngine(
        dec, max_new_tokens=4, k_max=2,
        prefix_cache=PrefixCache(16, salt=dec.cache_fingerprint()))
    base = list(range(1, 17))            # one full shareable block
    for tail in ([21, 22, 23], []):      # miss+insert, then a FULL hit
        eng.submit(np.asarray(base + tail, np.int32))
        eng.run()
    program = dec.analysis_program(k=4)
    ctx = AnalysisContext(
        name="gpt_decode_kv4",
        # the shared ragged-attention reorders (packed nibbles and
        # group scales ride the block copy as they are)
        allowed_activation_transposes=gpt_mod.ATTENTION_TRANSPOSES
        + RAGGED_ATTENTION_TRANSPOSES,
        expect_collectives=False,
        extra={"serving_decode": True,
               "kv_quant": "int4",
               # one per-layer [P, ps, H, D] pool's worth of ELEMENTS:
               # the packed payload holds 2*PB >= H*D nibbles per
               # token, so a convert of this many unpacked elements to
               # a wide float IS the dequantized pool landing in HBM
               # (a step's own convert is n x a block of 8 pages)
               "kv_pool_block_elems": (dec.num_pages * dec.page_size *
                                       cfg.num_heads * cfg.head_dim),
               "page_ledger": eng.page_ledger()})
    return program, ctx, PagedGPTDecoder._decode_multi_step


def _gpt_decode_mt():
    """The MULTI-TENANT serving config (serving.tenancy): the PACKED
    mixed horizon program WITH the multi-LoRA adapter gather —
    `_packed_multi_step` over a decoder carrying an attached 2-adapter
    bank, so the trace includes the per-token low-rank delta
    (`_lora_delta`) and the `aids` input — captured via
    `analysis_program(ragged=(4, 8))`, plus a page LEDGER and a
    scheduling trace committed from a REAL preempting multi-tenant
    workload: two throughput-tier requests on different adapters fill
    both slots, a latency-tier request arrives mid-stream, preempts a
    victim by page-spill (its blocks park in the prefix cache), and
    the ledger is captured at a sync where the preemption has landed
    and slots are live — so the committed ledger carries
    `slot_adapters` rows (the MEM-PAGE-REFCOUNT cross-variant
    aliasing check runs against real data) next to the parked victim
    blocks. Gated by SERVE-HOST-SYNC-DECODE (zero host transfers in
    the adapter-gather scan, donated KV pool), SERVE-PREFILL-STALL
    (preemption must not reintroduce a blocking prefill), and
    MEM-PAGE-REFCOUNT."""
    import numpy as np
    paddle = _fresh()
    from paddle_tpu.models import GPT, gpt_tiny
    from paddle_tpu.models import gpt as gpt_mod
    from paddle_tpu.serving import (SLO_LATENCY, SLO_THROUGHPUT,
                                    PagedGPTDecoder, PrefixCache,
                                    TenantEngine, make_lora_bank)
    cfg = gpt_tiny(max_seq_len=64, dtype="float32", remat=False)
    model = GPT(cfg)
    model.eval()
    # 6 allocatable pages: two 2-page throughput requests occupy both
    # slots, the 3-page latency arrival can only be served by
    # preempting a victim (slot exhaustion + page pressure)
    dec = PagedGPTDecoder(model, num_pages=7, page_size=16, max_batch=2)
    dec.attach_adapters(make_lora_bank(cfg, 2, rank=4, seed=5))
    eng = TenantEngine(
        dec, max_new_tokens=6, k_max=2,
        prefix_cache=PrefixCache(16, salt=dec.cache_fingerprint()))
    rng = np.random.RandomState(3)
    V = cfg.vocab_size
    lat_prompt = rng.randint(0, V, 36).astype(np.int32)
    eng.submit(rng.randint(0, V, 20).astype(np.int32), tenant="batch",
               slo=SLO_THROUGHPUT, adapter=1)
    eng.submit(rng.randint(0, V, 20).astype(np.int32), tenant="batch",
               slo=SLO_THROUGHPUT, adapter=2)
    cap = {}

    def on_sync(e):
        if "lat" not in cap and e.stats.tokens >= 2:
            cap["lat"] = e.submit(lat_prompt, tenant="chat",
                                  slo=SLO_LATENCY)
        if "ledger" not in cap and e.stats.preemptions and \
                any(r is not None for r in e._slot_req):
            cap["ledger"] = e.page_ledger()

    eng.run(on_sync=on_sync)
    assert eng.stats.preemptions and eng.stats.resumes, \
        "multi-tenant ledger workload lost its preemption shape"
    assert cap.get("ledger") and cap["ledger"]["slot_adapters"], \
        "ledger capture missed the live multi-adapter window"
    program = dec.analysis_program(ragged=(4, 8))
    ctx = AnalysisContext(
        name="gpt_decode_mt",
        # the shared ragged-attention reorders ride with the dense
        # model's by-design attention transposes (same body as
        # gpt_decode_ragged; the adapter gather adds none)
        allowed_activation_transposes=gpt_mod.ATTENTION_TRANSPOSES
        + RAGGED_ATTENTION_TRANSPOSES,
        expect_collectives=False,
        extra={"serving_decode": True,
               "page_ledger": cap["ledger"],
               "serve_schedule": eng.serve_schedule()})
    return program, ctx, PagedGPTDecoder._packed_multi_step


def _gpt_decode_fleet():
    """The FLEET serving config (serving.fleet): the ragged mixed
    horizon program served through a `FleetRouter` over TWO engine
    replicas sharing ONE file-backed `SharedHostKVTier`, captured with
    a page LEDGER from a replica whose pool overflowed into the
    shared tier mid-run — so the committed ledger's `host` rows are
    SHARED-tier rows (`"page": None`: a cross-process tier holds no
    device-twin backrefs, the audit must accept ownerless host
    entries) next to live slots. The workload is real fleet churn:
    three 2-block templates route by prefix affinity (pigeonhole
    lands >=2 on one replica), the 6-allocatable-page pool can't park
    both next to active slots, evictions spill to the shared tier,
    and a second admission round restores from it (asserted). Gated
    by SERVE-HOST-SYNC-DECODE, SERVE-PREFILL-STALL and
    MEM-PAGE-REFCOUNT like every serving capture; its determinism
    manifest additionally pins the fleet thread/lock discipline
    (analysis.threads covers serving/fleet.py)."""
    import tempfile

    import numpy as np
    paddle = _fresh()
    from paddle_tpu.models import GPT, gpt_tiny
    from paddle_tpu.models import gpt as gpt_mod
    from paddle_tpu.serving import (FleetRouter, PagedGPTDecoder,
                                    PrefixCache, SharedHostKVTier,
                                    TenantEngine)
    cfg = gpt_tiny(max_seq_len=64, dtype="float32", remat=False)
    model = GPT(cfg)
    model.eval()
    tier_dir = tempfile.mkdtemp(prefix="gpt_decode_fleet_tier_")
    engines = []
    for _ in range(2):
        dec = PagedGPTDecoder(model, num_pages=7, page_size=16,
                              max_batch=2)
        tier = SharedHostKVTier(tier_dir, fingerprint=dec)
        engines.append(TenantEngine(
            dec, max_new_tokens=6, k_max=2, tier_policy="restore",
            prefix_cache=PrefixCache(16, salt=dec.cache_fingerprint(),
                                     tier=tier)))
    router = FleetRouter(engines)
    rng = np.random.RandomState(3)
    V = cfg.vocab_size
    templates = [rng.randint(0, V, 32).tolist() for _ in range(3)]

    def round_of(seed):
        # two requests per template: the home replica of a doubled-up
        # template must evict parked blocks to admit the second wave,
        # which is what pushes them through the shared tier
        r = np.random.RandomState(seed)
        return [t + r.randint(0, V, 4).tolist()
                for t in templates for _ in range(2)]

    for p in round_of(11):
        router.submit(np.asarray(p, np.int32))
    cap = {}

    def on_sync(rt, i, eng):
        # the live window: this replica has spilled into the shared
        # tier AND still holds slots — the committed ledger carries
        # shared host rows next to live ownership
        if "ledger" not in cap and eng.stats.tier_spills and \
                any(r is not None for r in eng._slot_req):
            cap["ledger"] = eng.page_ledger()
            cap["schedule"] = eng.serve_schedule()
            cap["replica"] = i

    router.run(on_sync=on_sync, parallel=False)
    for p in round_of(12):                # re-admission: restores
        router.submit(np.asarray(p, np.int32))
    router.run(on_sync=on_sync, parallel=False)
    tier = engines[0].cache.tier
    merged = router.merged_stats()
    assert tier.n_entries and merged.tier_spills, \
        "fleet ledger workload lost its shared-tier spill shape"
    assert merged.tier_restores and not merged.tier_recomputes, \
        "fleet re-admission round did not restore from the shared tier"
    assert cap.get("ledger") and cap["ledger"].get("host"), \
        "ledger capture missed the live shared-tier window"
    dec = engines[cap["replica"]].d
    program = dec.analysis_program(ragged=(4, 8))
    ctx = AnalysisContext(
        name="gpt_decode_fleet",
        allowed_activation_transposes=gpt_mod.ATTENTION_TRANSPOSES
        + RAGGED_ATTENTION_TRANSPOSES,
        expect_collectives=False,
        extra={"serving_decode": True,
               "page_ledger": cap["ledger"],
               "serve_schedule": cap["schedule"],
               "fleet": {"replicas": 2,
                         "tier_entries": tier.n_entries,
                         "tier_bytes": tier.bytes_used,
                         "tier_restores": int(merged.tier_restores)}})
    return program, ctx, PagedGPTDecoder._packed_multi_step


TP_OVERLAP_SIZES = dict(B=2, L=512, H=1024, F=4096, head_dim=64)
TP_OVERLAP_AXIS = 4


def _tp_overlap_block(x, wqkv, wproj, w1, w2, n_chunks=4, impl="ring"):
    """Per-device body of ONE tensor-parallel GPT block — the two
    convicted row-parallel sites (attention proj, fc2) go through
    `ops.overlap.chunked_matmul_all_reduce`, so the capture carries the
    REAL decomposed ring the Schedule Doctor prices: per-chunk matmul
    tiles interleaved with single-hop collective_permutes instead of
    one bulk psum at the end.  `impl="bulk"` is the serial twin the
    COLL-SERIALIZED red test captures."""
    import jax
    from ..ops.overlap import chunked_matmul_all_reduce
    hd = TP_OVERLAP_SIZES["head_dim"]
    B, L, _ = x.shape
    qkv = x @ wqkv                          # column-parallel: local
    q, k, v = jnp.split(qkv, 3, axis=-1)
    hp = q.shape[-1] // hd                  # this device's heads

    def heads(t):
        return t.reshape(B, L, hp, hd).transpose(0, 2, 1, 3)
    q, k, v = heads(q), heads(k), heads(v)
    s = jax.nn.softmax((q @ k.transpose(0, 1, 3, 2)) / hd ** 0.5,
                       axis=-1)
    a = (s @ v).transpose(0, 2, 1, 3).reshape(B, L, hp * hd)
    y = chunked_matmul_all_reduce(a, wproj, "tp", n_chunks=n_chunks,
                                  impl=impl)
    h = jax.nn.gelu(y @ w1)                 # column-parallel: local
    return chunked_matmul_all_reduce(h, w2, "tp", n_chunks=n_chunks,
                                     impl=impl)


def gpt_tp_overlap_program(impl="ring", n_chunks=4):
    """LoweredProgram of the shard_map'd tp block above (tp=4 over the
    first 4 local devices; B=2 L=512 H=1024 F=4096 bf16 puts the MXU
    leg at ~2x the wire leg, so a hiding schedule has headroom). Also
    the front door for the bulk serial twin the red/green schedule
    test A/Bs against — same trace, impl flipped."""
    import functools

    import jax
    from jax.sharding import NamedSharding
    from jax.sharding import PartitionSpec as P

    from ..distributed.mesh import build_mesh
    from .lowering import LoweredProgram, tree_arg_infos
    if len(jax.devices()) < TP_OVERLAP_AXIS:
        raise RuntimeError(
            f"gpt_tp_overlap needs {TP_OVERLAP_AXIS} local devices for "
            "its tp mesh — run under XLA_FLAGS="
            "--xla_force_host_platform_device_count=8 (the test env "
            "default)")
    _fresh()
    mesh = build_mesh(tp=TP_OVERLAP_AXIS,
                      devices=jax.devices()[:TP_OVERLAP_AXIS])
    sz = TP_OVERLAP_SIZES
    B, L, H, F = sz["B"], sz["L"], sz["H"], sz["F"]
    args = {"x": jnp.zeros((B, L, H), jnp.bfloat16),
            "wqkv": jnp.zeros((H, 3 * H), jnp.bfloat16),
            "wproj": jnp.zeros((H, H), jnp.bfloat16),
            "w1": jnp.zeros((H, F), jnp.bfloat16),
            "w2": jnp.zeros((F, H), jnp.bfloat16)}
    specs = {"x": P(), "wqkv": P(None, "tp"), "wproj": P("tp", None),
             "w1": P(None, "tp"), "w2": P("tp", None)}
    body = functools.partial(_tp_overlap_block, n_chunks=n_chunks,
                             impl=impl)
    f = jax.shard_map(body, mesh=mesh,
                         in_specs=tuple(specs[k] for k in args),
                         out_specs=P(), axis_names={"tp"}, check_vma=False)
    shardings = tuple(NamedSharding(mesh, specs[k]) for k in args)
    traced = jax.jit(f, in_shardings=shardings).trace(*args.values())
    infos = []
    for (name, a), sh in zip(args.items(), shardings):
        role = "batch" if name == "x" else "param"
        infos += tree_arg_infos(a, role, prefix=name, shardings=sh)
    return LoweredProgram(traced.lower().as_text(), jaxpr=traced.jaxpr,
                          name=f"gpt_tp_overlap_{impl}",
                          arg_infos=infos)


def _gpt_tp_overlap():
    """The OVERLAPPED tensor-parallel config: the shard_map'd GPT block
    whose two row-parallel matmuls ride the chunked collective-matmul
    ring (ops/overlap.py) — the program PR 17's tentpole exists to
    produce. Its committed schedule manifest pins the wire-hiding
    fraction the bulk twin can't reach (the twin's two psums sit alone
    on the critical path: COLL-SERIALIZED red), and the collective/
    sharding passes account the per-chunk permutes' wire honestly."""
    from paddle_tpu.models import gpt as gpt_mod
    program = gpt_tp_overlap_program(impl="ring", n_chunks=4)
    program.name = "gpt_tp_overlap"
    ctx = AnalysisContext(
        name="gpt_tp_overlap",
        # the attention head split/merge transposes are the dense
        # model's by-design moves
        allowed_activation_transposes=gpt_mod.ATTENTION_TRANSPOSES,
        expect_collectives=True,
        mesh_axes={"tp": TP_OVERLAP_AXIS},
        # the ring IS made of collective_permutes by design — they are
        # the decomposed transfer, not a GSPMD spec-mismatch reshard
        allowed_resharding=(r"collective_permute",),
        # the block activations ([B,L,H] bf16, ~2 MiB) replicate across
        # tp by design (sequence stays whole); only model state is tp-
        # sharded here, so lift the replication bar above them
        replicated_bytes_threshold=8 << 20,
        extra={"tp_overlap": True})
    return program, ctx, _tp_overlap_block


# configs whose builder yields a READY LoweredProgram (serving decode
# loops and other non-Layer captures): builder() ->
# (LoweredProgram, AnalysisContext, source_fn). They ride the same
# lint/memory manifest + CI plumbing as BASELINE_CONFIGS but skip the
# tuning manifests (no grad program to replay).
PROGRAM_CONFIGS = {
    "gpt_decode": _gpt_decode,       # fused multi-step serving decode
    "gpt_decode_prefix": _gpt_decode_prefix,   # chunked prefix-cache prefill
    "gpt_decode_ragged": _gpt_decode_ragged,   # mixed chunked-prefill+decode
    "gpt_decode_kv8": _gpt_decode_kv8,         # int8 KV pool decode loop
    "gpt_decode_kv4": _gpt_decode_kv4,         # int4 nibble-packed KV pool
    "gpt_decode_mt": _gpt_decode_mt,           # multi-tenant + multi-LoRA
    "gpt_decode_fleet": _gpt_decode_fleet,     # fleet + shared host KV tier
    "gpt_train_multi": _gpt_train_multi,   # fused multi-step train scan
    "gpt_tp_overlap": _gpt_tp_overlap,     # chunked collective-matmul tp block
}

# configs whose schedule manifest is committed (schedule_manifests/):
# the five BASELINE model forwards plus the fused train scan — the
# programs whose step time the overlap-aware roofline prices — plus
# gpt_decode_mt: the one serving capture with a schedule manifest (the
# multi-tenant horizon is the program whose composition the tenancy
# scheduler prices, so its critical-path/overlap numbers are pinned
# even though a decode tick carries no collective to hide). The other
# serving captures stay excluded: their schedule estimate adds
# nothing the memory manifests don't already pin.
# ... plus gpt_tp_overlap: the chunked collective-matmul capture whose
# wire-hiding fraction IS the number the manifest exists to pin (the
# one SCHEDULE config with a real collective stream).
SCHEDULE_CONFIGS = tuple(BASELINE_CONFIGS) + ("gpt_train_multi",
                                              "gpt_decode_mt",
                                              "gpt_tp_overlap")

# configs whose determinism manifest is committed
# (determinism_manifests/): every SERVING capture — the programs whose
# byte-identical-stream invariant the Determinism Doctor proves
# statically (taint-canonical pool writes, clean RNG key derivation,
# no unprovable scatter overlap, no donated-alias outputs, and the
# host-side thread/lock discipline).  The training and tp-overlap
# captures stay excluded: no pool buffers, nothing for the pass to
# pin.  The SpeculativeEngine verify window is deliberately NOT here:
# it is the documented expected red (tests/test_determinism_lint.py
# pins it red until commit-on-accept lands).
DETERMINISM_CONFIGS = ("gpt_decode", "gpt_decode_prefix",
                       "gpt_decode_ragged", "gpt_decode_kv8",
                       "gpt_decode_kv4", "gpt_decode_mt",
                       "gpt_decode_fleet")


def build_config(name):
    try:
        builder = BASELINE_CONFIGS[name]
    except KeyError:
        raise KeyError(f"unknown BASELINE config {name!r}; known: "
                       f"{sorted(BASELINE_CONFIGS)}")
    return builder()


def lowered_program(name):
    """(LoweredProgram, AnalysisContext, forward fn) for a BASELINE or
    PROGRAM config — lowered once per process (the lint gate's time
    budget rides on this cache). The context is a fresh copy per call:
    consumers set run-local fields on it (manifest, mesh_axes) and a
    shared instance would leak one run's manifest into the next —
    e.g. baking transition-run DRIFT findings into a regenerated
    manifest."""
    import dataclasses
    if name not in _CACHE:
        if name in PROGRAM_CONFIGS:
            _CACHE[name] = PROGRAM_CONFIGS[name]()
        else:
            from .lowering import lower_layer
            model, examples, ctx = build_config(name)
            program = lower_layer(model, *examples, name=name)
            _CACHE[name] = (program, ctx, type(model).forward)
    program, ctx, fwd = _CACHE[name]
    return program, dataclasses.replace(ctx), fwd


def forward_fn(name):
    return lowered_program(name)[2]


def tuning_report(name):
    """The remat advisor's AutotuneReport for a BASELINE config —
    what-if peak + recompute per policy over a fresh seeded grad trace,
    roofline-priced against the fixed v5e spec (deterministic: this is
    what tuning_manifests/<name>.json pins). Cached per process like
    the lowerings."""
    if name not in _TUNING_CACHE:
        from .autotune import autotune_layer
        model, examples, ctx = build_config(name)
        _TUNING_CACHE[name] = autotune_layer(model, *examples,
                                             chip="v5e", name=name)
    return _TUNING_CACHE[name]
