"""Continuous-batching decode engine over the paged KV cache.

Reference role: the fluid inference API's batched decode serving path
(paddle/fluid/inference/api/paddle_inference_api.h + PaddleNLP FasterGPT
decoding).  TPU-native design, split across this package:

- `decoder.py` — ONE compiled decode step for a fixed slot count:
  [max_batch] tokens in, [max_batch] next tokens out (greedy, or seeded
  temperature/top-k/top-p sampling).  Slots hold independent sequences
  at different lengths; position/page state rides in arrays, so
  admission and retirement never recompile.  KV lives in paged pools
  [L, P, page_size, H, D] (ops/paged_attention); decode attention
  gathers each slot's pages (optionally via the scalar-prefetch Pallas
  kernel); page allocation is host-side.  Prefill is a second compiled
  program per prompt-length bucket (powers of two) writing the prompt's
  K/V straight into the pages; the CHUNKED prefill
  (`prefill_suffix_batch`) consumes only a prompt's uncached suffix,
  attending against already-mounted prefix pages.  Multi-step decode
  (`decode_multi`) fuses K decode ticks into ONE compiled `lax.scan` —
  sampled tokens feed back on device, per-slot done masks freeze
  finished slots — so the engine syncs the host once per K tokens
  instead of once per token (cf. Ragged Paged Attention, arXiv
  2604.15464; T3's overlap analysis, arXiv 2401.16677).  Mixed
  horizons and the chunked prefill dispatch the PACKED
  [total_new_tokens] token-stream layout (per-token row
  ids, pow2 total-token buckets — docs/serving.md "Packed ragged
  layout"): the one layout.
- `engine.py` — `ContinuousBatchingEngine.run()` schedules horizons of
  `k = min(K_max, smallest remaining budget)` ticks and overlaps each
  block's host fetch with the NEXT block's dispatch (one-horizon-
  delayed retirement); `cost_model.decode_horizon` prices the default
  K from the chip's tick roofline vs the measured host sync cost;
  an explicit `k_max=1` runs the per-tick loop, the tests' reference.
  `SpeculativeEngine` layers draft-propose/target-verify decoding on
  top.
- `prefix_cache.py` — content-addressed KV page sharing: hash (token
  block chain, model-invariant config) -> page id with refcounts,
  copy-on-write on the first divergent-token write, and LRU eviction of
  refcount-0 pages under pool pressure.  Requests sharing a system
  prompt / few-shot prefix skip prefill for the shared span entirely
  (the Gemma-on-TPU serving comparison, PAPERS.md, leans on exactly
  this page-level reuse).
- `kv_tier.py` — the memory hierarchy BEHIND the prefix cache:
  refcount-0 pages evicted under pool pressure spill their bytes to a
  capacity-bounded pinned-host-RAM LRU (`HostKVTier`; int8 pools spill
  quantized — half the host bytes), and admissions whose chain
  continues onto host entries restore via H2D only when
  `cost_model.kv_restore_s` beats the span's prefill recompute.
  `PrefixCache.save(dir)`/`load(dir, decoder)` persist the cache
  across engine restarts, keyed by `cache_fingerprint()` (mismatch
  refuses).  docs/serving.md "Tiered KV".
- `tenancy.py` — multi-tenant serving over the same machinery:
  per-request SLO classes (`TenantEngine`: latency-tier requests admit
  ahead of the throughput backlog; `TenantScheduler` composes horizons
  per class through `cost_model.slo_horizon`), preemption by
  page-spill (a latency admission out of slots/pages parks a
  throughput victim's KV blocks into the prefix cache — whence the
  host tier — and the victim resumes byte-identically), and
  multi-LoRA (per-token adapter gathers over shared base weights —
  `PagedGPTDecoder.attach_adapters` — with per-adapter chain-key salts
  so pages never alias across variants).  docs/serving.md
  "Multi-tenant serving".
- `fleet.py` — fleet-scale serving on one host: `SharedHostKVTier`
  re-homes the host tier onto a file/shm-backed store every replica
  on the host shares (same chain keys, same `PrefixCache.save` byte
  format, flock + atomic-replace discipline; restores price a
  host-RAM read leg via `cost_model.kv_restore_s(shared=True)`), and
  `FleetRouter` fronts N `TenantEngine` replicas with prefix-affinity
  routing (the cache's chain keys ARE the routing key) + SLO-aware
  least-loaded escape, global rid allocation (N-replica streams are
  byte-identical to the 1-replica twin), `run(on_sync=)` admission
  churn, kill/respawn warm-start, and fleet-wide observability
  (`ServeStats.merge`, pooled `tenancy_summary`, one Perfetto
  timeline with per-(replica, tenant) pids).  docs/serving.md
  "Fleet serving".
- `stats.py` — per-engine `ServeStats` (host syncs/token, prefix-cache
  hit/evict/bytes-saved counters, tiered-KV spill/restore/recompute
  counters, tenancy preemption/resume counters, TTFT/queue-wait/
  occupancy windows) behind `debug.serving_stats()`; per-tenant
  `TenantStats` behind `TenantEngine.tenancy_summary()`.

quant="a8w8": per-(layer, out-channel) int8 weights with dynamic
per-row int8 activations — matmuls run int8xint8->int32 on the MXU
(same recipe as quantization.QuantizedLinearA8W8).  quant="w4a16":
weight-only int4 (ops/w4_matmul.py): nibbles unpack in VMEM, bf16
activations — half the weight HBM traffic of a8w8.

The engine applies to GPT-family models (uniform pre-LN blocks); weights
are extracted once into stacked per-layer arrays and the model object is
no longer needed — pair with jit.load-style artifacts for serving.
"""
from .decoder import (MultiDecodeOut, PagedGPTDecoder, RaggedMultiOut,
                      _kv_set, _ln, _mm, _mm_heads, _quantize_kv,
                      _quantize_w, _sample_tokens,
                      _spec_accept)
from .engine import ContinuousBatchingEngine, SpeculativeEngine
from .fleet import FleetRouter, SharedHostKVTier
from .kv_tier import HostKVTier, restore_beats_recompute
from .prefix_cache import PrefixCache
from .scheduler import RaggedScheduler
from .stats import _ENGINES, _STATS_WINDOW, ServeStats, serving_stats
from .tenancy import (SLO_LATENCY, SLO_THROUGHPUT,
                      PrecisionRoutedEngine, TenantEngine,
                      TenantScheduler, TenantStats, make_lora_bank)
from .trace import (FlightRecorder, export_chrome_trace,
                    validate_chrome_trace)

__all__ = ["PagedGPTDecoder", "ContinuousBatchingEngine",
           "SpeculativeEngine", "ServeStats", "serving_stats",
           "PrefixCache", "HostKVTier", "restore_beats_recompute",
           "SharedHostKVTier", "FleetRouter",
           "MultiDecodeOut", "RaggedMultiOut",
           "RaggedScheduler", "FlightRecorder", "export_chrome_trace",
           "validate_chrome_trace",
           "SLO_LATENCY", "SLO_THROUGHPUT", "TenantEngine",
           "PrecisionRoutedEngine",
           "TenantScheduler", "TenantStats", "make_lora_bank"]
