#!/usr/bin/env python
"""Headline benchmark: GPT pretraining train-step throughput on one TPU chip.

Prints ONE JSON line:
  {"metric": ..., "value": tokens/sec/chip, "unit": ..., "vs_baseline": ...}

vs_baseline = achieved MFU / 0.35 (BASELINE.json north-star: GPT-3 1.3B
pretraining at >=35% MFU on v5e). Falls back to smaller GPT configs if the
1.3B Adam state can't fit the chip.
"""
import json
import os
import sys
import time


def log(*a):
    print(*a, file=sys.stderr, flush=True)


def _stage(batch, trainer=None):
    """device_put once, outside the timed loop: steady-state training keeps
    batches device-resident via the input pipeline's async prefetch
    (io.DeviceLoader); timing a synchronous 77MB host->device copy per step
    would measure the host link, not the chip. With a trainer given the
    batch lands with the trainer's OWN GSPMD batch sharding (the layout its
    step pins via in_shardings), so the timed loop dispatches with zero
    copies and zero reshards — exactly what DeviceLoader feeds in
    production."""
    if trainer is not None:
        placed, _, _ = trainer.place_batch(batch)
        return placed
    import jax.numpy as jnp
    return {k: jnp.asarray(v) for k, v in batch.items()}


# Best-so-far JSON line for the hard-exit watchdog: if the process must be
# killed mid-wedge, the driver still gets the results banked up to that
# point rather than nothing. main() updates this as configs complete.
_PARTIAL = None


def _publish_partial(d):
    global _PARTIAL
    _PARTIAL = d


def _default_result():
    return {"metric": "gpt_train_tokens_per_sec_per_chip", "value": 0.0,
            "unit": "tokens/s/chip", "vs_baseline": 0.0}


def _alarm(seconds, label):
    """Mid-run hang guard, two layers, for a device sync that never
    returns.

    Layer 1 — SIGALRM raising TimeoutError: works when the main thread is
    executing Python bytecode (dispatch loops, host-side work).
    Layer 2 — a backup watchdog THREAD at seconds+60: CPython only delivers
    the signal-handler exception when bytecode next runs, and a blocked jax
    sync is a C call that never returns, so the alarm alone can sail past
    it. The thread prints the best-so-far JSON line (_PARTIAL) with the
    error attached and hard-exits — the driver gets a parseable line
    either way.

    Nesting-safe: re-arms the enclosing guard's remaining time on exit.
    Signal layer is skipped off the main thread (signal restriction); the
    thread layer still applies."""
    import contextlib
    import json as _json
    import signal
    import threading

    @contextlib.contextmanager
    def guard():
        def hard_exit():
            import os
            out = dict(_PARTIAL) if _PARTIAL else _default_result()
            out["error"] = (f"{label} hard-wedged >{seconds + 60}s "
                            "(device sync never returned)")
            log(f"bench hard-exit: {out['error']}")
            print(_json.dumps(out), flush=True)
            os._exit(3)

        backup = threading.Timer(seconds + 60, hard_exit)
        backup.daemon = True
        backup.start()
        on_main = threading.current_thread() is threading.main_thread()
        old_handler = prev_remaining = None
        t0 = time.time()
        if on_main:
            def handler(signum, frame):
                raise TimeoutError(
                    f"{label} exceeded {seconds}s (TPU wedged mid-run?)")

            old_handler = signal.signal(signal.SIGALRM, handler)
            prev_remaining = signal.alarm(seconds)
        try:
            yield
        finally:
            backup.cancel()
            if on_main:
                signal.alarm(0)
                signal.signal(signal.SIGALRM, old_handler)
                if prev_remaining:  # restore the enclosing guard's budget
                    signal.alarm(max(1, int(prev_remaining -
                                            (time.time() - t0))))

    return guard()


def _measure(trainer, batch, steps, label):
    """Shared timing harness: compile+first step, one warm step, timed loop
    (async dispatch, single trailing sync). Returns seconds/step."""
    batch = _stage(batch, trainer)   # mesh-sharded, matches step in_shardings
    t0 = time.time()
    with _alarm(600, f"{label} compile+first step"):
        loss = trainer.step(batch)
        float(loss)
    log(f"{label} compile+first step: {time.time()-t0:.1f}s, loss={float(loss):.3f}")
    with _alarm(300, f"{label} measure loop"):
        float(trainer.step(batch))  # warm
        t0 = time.time()
        for _ in range(steps):
            loss = trainer.step(batch)
        float(loss)  # sync
    return (time.time() - t0) / steps


def _static_hbm(trainer, batch):
    """Static per-device peak-HBM estimate of the REAL compiled step
    (Memory Doctor liveness over the traced jaxpr, shardings + donation
    captured) — banked next to the measured throughput so a perf run
    also records how close the config sits to the HBM ceiling. Pure
    host-side tracing: no extra compile, no device work."""
    try:
        from paddle_tpu.analysis import estimate_jaxpr_memory
        program = trainer.analysis_program(batch)
        est = estimate_jaxpr_memory(program.jaxpr,
                                    arg_infos=program.arg_infos)
        log(f"static per-device peak HBM: {est.peak_bytes / 2**30:.2f} "
            f"GiB (args {est.args_bytes / 2**30:.2f}, donated credit "
            f"{est.donated_bytes / 2**30:.2f})")
        return est.peak_bytes
    except Exception as e:
        log(f"static memory estimate failed: "
            f"{type(e).__name__}: {str(e)[:200]}")
        return 0


def _fwd_flops(trainer, batch):
    """Executed FLOPs of ONE forward pass (XLA cost analysis of the traced
    loss computation): the roofline denominator for configs like detection
    or routed-MoE where a 6N params heuristic misstates the compute. Train
    step ≈ 3x forward (fwd + ~2x bwd)."""
    import jax

    from paddle_tpu.distributed.trainer import batch_to_arrays, make_compute_loss
    try:
        cl = make_compute_loss(trainer.model, trainer.loss_fn)
        lowered = jax.jit(cl).lower(trainer.params, trainer.consts,
                                    batch_to_arrays(batch))
        cost = lowered.compile().cost_analysis()
        if isinstance(cost, list):
            cost = cost[0]
        return float(cost.get("flops", 0.0))
    except Exception as e:
        log(f"fwd flops analysis failed: {type(e).__name__}: {str(e)[:200]}")
        return 0.0


def chip_peak_flops():
    """bf16 peak FLOP/s for the attached chip (table now lives in
    cost_model.CHIP_SPECS — one source for MFU, decode rooflines AND
    the autotuner's step-time model)."""
    from paddle_tpu.cost_model import chip_spec
    return chip_spec().peak_flops


def chip_hbm_bw():
    """HBM bytes/s for the attached chip (decode is bandwidth-bound).
    Same cost_model.CHIP_SPECS row as chip_peak_flops."""
    from paddle_tpu.cost_model import chip_spec
    return chip_spec().hbm_bw


def decode_roofline_tok_s(cfg, batch, avg_ctx, quant=None, kv_bytes=2):
    """Decode tokens/s ceiling from HBM bytes moved per step: every step
    reads ALL weights plus each sequence's KV cache up to its current
    length. tok/s_max = BW * batch / bytes_step. This is the honest
    denominator for decode (not MFU — the MXU idles).

    a8w8/w4a16 quantize only the per-block linears (qkv/proj/fc1/fc2)
    at 1 and 0.5 bytes/param; embeddings, position table, layernorms and
    the tied lm_head read at bf16 width (per-channel scales are a few KB
    — ignored)."""
    n = cfg.num_params()
    if quant in ("a8w8", "w4a16"):
        h, f = cfg.hidden_size, cfg.ffn_hidden
        lin = cfg.num_layers * (4 * h * h + 2 * h * f)
        per = 1 if quant == "a8w8" else 0.5
        w_bytes = lin * per + (n - lin) * 2
    else:
        w_bytes = n * 2
    kv = batch * cfg.num_layers * 2 * avg_ctx * cfg.hidden_size * kv_bytes
    return chip_hbm_bw() * batch / (w_bytes + kv)


def run_config(cfg_name, batch_size, seq_len, steps=10, remat_policy="full",
               grad_accum=1):
    import numpy as np

    import paddle_tpu as paddle
    from paddle_tpu.distributed import build_mesh
    from paddle_tpu.distributed.trainer import Trainer
    from paddle_tpu.models import gpt as gpt_mod
    from paddle_tpu.models import GPT, GPTPretrainingCriterion

    cfg = getattr(gpt_mod, cfg_name)(max_seq_len=seq_len,
                                     remat_policy=remat_policy)
    paddle.seed(0)
    build_mesh(dp=1)
    log(f"building {cfg_name}: {cfg.num_params()/1e6:.0f}M params, "
        f"batch={batch_size} seq={seq_len}"
        + (f" accum={grad_accum}" if grad_accum > 1 else ""))
    model = GPT(cfg)
    model.bfloat16()
    crit = GPTPretrainingCriterion()
    opt = paddle.optimizer.AdamW(
        learning_rate=2e-4, weight_decay=0.1,
        grad_clip=paddle.nn.ClipGradByGlobalNorm(1.0),
        accumulator_dtype="bfloat16")

    def loss_fn(m, batch):
        logits = m(paddle.to_tensor(batch["input_ids"]))
        return crit(logits, paddle.to_tensor(batch["labels"]))

    trainer = Trainer(model, opt, loss_fn, grad_accum_steps=grad_accum)
    rng = np.random.RandomState(0)
    ids = rng.randint(0, cfg.vocab_size, (batch_size, seq_len + 1))
    batch = {"input_ids": ids[:, :-1].astype("int32"),
             "labels": ids[:, 1:].astype("int32")}
    static_hbm = _static_hbm(trainer, batch)
    dt = _measure(trainer, batch, steps, cfg_name)   # _measure stages
    tokens_per_sec = batch_size * seq_len / dt
    n_params = cfg.num_params()
    flops_per_token = 6 * n_params  # fwd+bwd heuristic
    mfu = flops_per_token * tokens_per_sec / chip_peak_flops()
    log(f"{cfg_name}: {dt*1e3:.1f} ms/step, {tokens_per_sec:.0f} tok/s, MFU={mfu:.3f}")
    return tokens_per_sec, mfu, n_params, static_hbm


def run_resnet50(batch_size=128, steps=10):
    """BASELINE.json config 1: ResNet-50 train step, imgs/sec/chip."""
    import numpy as np

    import paddle_tpu as paddle
    from paddle_tpu.distributed import build_mesh
    from paddle_tpu.distributed.trainer import Trainer

    paddle.seed(0)
    build_mesh(dp=1)
    # NHWC: the TPU-native layout (channels on the lane dim) — NCHW makes
    # XLA materialize transposes around every conv
    model = paddle.vision.models.resnet50(num_classes=1000, data_format="NHWC")
    model.bfloat16()
    model.train()
    opt = paddle.optimizer.Momentum(learning_rate=0.1, momentum=0.9,
                                    weight_decay=1e-4)

    def loss_fn(m, batch):
        logits = m(paddle.to_tensor(batch["image"]))
        return paddle.nn.functional.cross_entropy(
            logits, paddle.to_tensor(batch["label"]))

    trainer = Trainer(model, opt, loss_fn)
    rng = np.random.RandomState(0)
    batch = {"image": rng.randn(batch_size, 224, 224, 3).astype("float32"),
             "label": rng.randint(0, 1000, (batch_size,)).astype("int64")}
    dt = _measure(trainer, batch, steps, "resnet50")
    imgs_s = batch_size / dt
    # ~4.09e9 MACs fwd at 224^2 -> 8.2 GFLOP fwd, x3 for train
    mfu = 3 * 8.2e9 * imgs_s / chip_peak_flops()
    log(f"resnet50: {dt*1e3:.1f} ms/step, {imgs_s:.0f} imgs/s, MFU={mfu:.3f}")
    return imgs_s, mfu


def run_bert_base(batch_size=32, seq_len=512, steps=10):
    """BASELINE.json config 2: BERT-base MLM+NSP pretraining, seqs/sec/chip."""
    import numpy as np

    import paddle_tpu as paddle
    from paddle_tpu.distributed import build_mesh
    from paddle_tpu.distributed.trainer import Trainer
    from paddle_tpu.models.bert import (
        BertForPretraining,
        BertPretrainingCriterion,
        bert_base,
    )

    paddle.seed(0)
    build_mesh(dp=1)
    cfg = bert_base(dtype="bfloat16")
    model = BertForPretraining(cfg)
    model.bfloat16()
    model.train()
    crit = BertPretrainingCriterion(cfg.vocab_size)
    opt = paddle.optimizer.AdamW(learning_rate=1e-4, weight_decay=0.01,
                                 accumulator_dtype="bfloat16")

    def loss_fn(m, batch):
        mlm_logits, nsp_logits = m(paddle.to_tensor(batch["input_ids"]),
                                   attention_mask=paddle.to_tensor(batch["attention_mask"]))
        return crit(mlm_logits, nsp_logits,
                    paddle.to_tensor(batch["mlm_labels"]),
                    paddle.to_tensor(batch["nsp_labels"]))

    trainer = Trainer(model, opt, loss_fn)
    rng = np.random.RandomState(0)
    labels = rng.randint(0, cfg.vocab_size, (batch_size, seq_len))
    labels[rng.rand(batch_size, seq_len) > 0.15] = -100  # MLM masking rate
    # ~12% padding per sequence: masked flash attention is the measured path
    lengths = rng.randint(int(seq_len * 0.75), seq_len + 1, (batch_size,))
    attn_mask = (np.arange(seq_len)[None, :] < lengths[:, None])
    batch = {"input_ids": rng.randint(0, cfg.vocab_size,
                                      (batch_size, seq_len)).astype("int32"),
             "attention_mask": attn_mask.astype("int32"),  # [B, L]: model expands
             "mlm_labels": labels.astype("int32"),
             "nsp_labels": rng.randint(0, 2, (batch_size,)).astype("int64")}
    dt = _measure(trainer, batch, steps, "bert_base")
    seqs_s = batch_size / dt
    n_params = sum(int(np.prod(p.shape)) for p in model.parameters())
    mfu = 6 * n_params * seqs_s * seq_len / chip_peak_flops()
    log(f"bert_base: {dt*1e3:.1f} ms/step, {seqs_s:.1f} seqs/s, MFU={mfu:.3f}")
    return seqs_s, mfu


def run_yolov3(batch_size=16, size=320, steps=10):
    """BASELINE.json config 4: PP-OCR/detection family — YOLOv3-DarkNet53
    train step, imgs/sec/chip."""
    import numpy as np

    import paddle_tpu as paddle
    from paddle_tpu.distributed import build_mesh
    from paddle_tpu.distributed.trainer import Trainer
    from paddle_tpu.vision.models import yolov3_darknet53

    paddle.seed(0)
    build_mesh(dp=1)
    model = yolov3_darknet53(num_classes=80, data_format="NHWC")
    model.bfloat16()
    model.train()
    opt = paddle.optimizer.Momentum(learning_rate=0.01, momentum=0.9,
                                    weight_decay=5e-4)

    def loss_fn(m, b):
        outs = m(paddle.to_tensor(b["image"]))
        return m.loss(outs, paddle.to_tensor(b["gt_box"]),
                      paddle.to_tensor(b["gt_label"]))

    trainer = Trainer(model, opt, loss_fn)
    rng = np.random.RandomState(0)
    nb = 8
    batch = {"image": rng.randn(batch_size, size, size, 3).astype("float32"),
             "gt_box": np.clip(rng.rand(batch_size, nb, 4) * 0.5 + 0.1, 0, 1)
             .astype("float32"),
             "gt_label": rng.randint(0, 80, (batch_size, nb)).astype("int32")}
    fwd = _fwd_flops(trainer, batch)
    dt = _measure(trainer, batch, steps, "yolov3")
    imgs_s = batch_size / dt
    # roofline: measured fwd FLOPs x3 for train (bwd ~2x fwd)
    mfu = 3 * fwd / batch_size * imgs_s / chip_peak_flops() if fwd else 0.0
    log(f"yolov3: {dt*1e3:.1f} ms/step, {imgs_s:.0f} imgs/s, MFU={mfu:.3f} "
        f"(fwd {fwd/batch_size/1e9:.1f} GFLOP/img)")
    return imgs_s, mfu


def run_crnn(batch_size=64, width=320, steps=10):
    """BASELINE.json config 4, OCR half — CRNN recognition (CTC) train
    step at PP-OCR's 32xW crop shape, imgs/sec/chip."""
    import numpy as np

    import paddle_tpu as paddle
    from paddle_tpu.distributed import build_mesh
    from paddle_tpu.distributed.trainer import Trainer
    from paddle_tpu.vision.models import CRNN

    paddle.seed(0)
    build_mesh(dp=1)
    model = CRNN(num_classes=97, data_format="NHWC")
    model.bfloat16()
    model.train()
    opt = paddle.optimizer.Adam(learning_rate=1e-3,
                                accumulator_dtype="bfloat16")

    def loss_fn(m, b):
        logits = m(paddle.to_tensor(b["image"]))
        return m.loss(logits, paddle.to_tensor(b["label"]),
                      paddle.to_tensor(b["length"]))

    trainer = Trainer(model, opt, loss_fn)
    rng = np.random.RandomState(0)
    # CTC needs T (=width/4 columns) comfortably above the label length
    max_len = max(2, min(24, width // 16))
    lens = rng.randint(max(1, max_len // 4), max_len + 1, batch_size)
    labels = rng.randint(1, 97, (batch_size, max_len))
    labels *= (np.arange(max_len)[None, :] < lens[:, None])
    batch = {
        "image": rng.randn(batch_size, 32, width, 3).astype("float32"),
        "label": labels.astype("int32"),
        "length": lens.astype("int32")}
    fwd = _fwd_flops(trainer, batch)
    dt = _measure(trainer, batch, steps, "crnn")
    imgs_s = batch_size / dt
    mfu = 3 * fwd / batch_size * imgs_s / chip_peak_flops() if fwd else 0.0
    log(f"crnn: {dt*1e3:.1f} ms/step, {imgs_s:.0f} imgs/s, MFU={mfu:.3f} "
        f"(fwd {fwd/batch_size/1e9:.2f} GFLOP/img)")
    return imgs_s, mfu


def run_gpt_moe(batch_size=8, seq_len=1024, steps=10, gate=None):
    """BASELINE.json config 5: GPT-MoE (top-2 routed experts), tokens/s/chip.
    Single-chip: measures the dispatch/combine einsums + expert FFs; the ep
    mesh path is validated by dryrun_multichip and tests/test_moe.py.
    Gate family selectable via arg or PADDLE_TPU_MOE_GATE=topk|switch|gshard."""
    import numpy as np

    import paddle_tpu as paddle
    from paddle_tpu.distributed import build_mesh
    from paddle_tpu.distributed.trainer import Trainer
    from paddle_tpu.models import GPTMoE, GPTPretrainingCriterion
    from paddle_tpu.models.moe import gpt_moe_small

    paddle.seed(0)
    build_mesh(dp=1)
    gate = gate or os.environ.get("PADDLE_TPU_MOE_GATE", "topk")
    cfg = gpt_moe_small(max_seq_len=seq_len, gate=gate)
    model = GPTMoE(cfg)
    model.bfloat16()
    crit = GPTPretrainingCriterion()
    opt = paddle.optimizer.AdamW(learning_rate=2e-4,
                                 accumulator_dtype="bfloat16")

    def loss_fn(m, b):
        logits = m(paddle.to_tensor(b["input_ids"]))
        return crit(logits, paddle.to_tensor(b["labels"])) + m.aux_loss()

    trainer = Trainer(model, opt, loss_fn)
    rng = np.random.RandomState(0)
    ids = rng.randint(0, cfg.vocab_size, (batch_size, seq_len + 1))
    batch = {"input_ids": ids[:, :-1].astype("int32"),
             "labels": ids[:, 1:].astype("int32")}
    dt = _measure(trainer, batch, steps, "gpt_moe")
    tok_s = batch_size * seq_len / dt
    # roofline on ACTIVATED params (top_k of E experts): 6N_active per token
    n_active = cfg.num_active_params()
    mfu = 6 * n_active * tok_s / chip_peak_flops()
    log(f"gpt_moe: {dt*1e3:.1f} ms/step, {tok_s:.0f} tok/s, MFU={mfu:.3f} "
        f"({n_active/1e6:.0f}M active / {cfg.num_params()/1e6:.0f}M total)")
    return tok_s, mfu


def run_decode(batch=8, prompt_len=128, gen=128, quant=None):
    """Serving decode throughput: continuous-batching greedy decode over
    the paged-KV Pallas kernel (GPT-1.3B bf16, falls back to 350M/125M if
    the chip can't hold it). Reported as generated tokens/sec/chip."""
    import numpy as np

    import paddle_tpu as paddle
    from paddle_tpu.distributed import build_mesh
    from paddle_tpu.models import GPT, gpt_125m, gpt_350m, gpt_1p3b
    from paddle_tpu.serving import ContinuousBatchingEngine, PagedGPTDecoder

    paddle.seed(0)
    build_mesh(dp=1)
    rng = np.random.RandomState(0)
    last_err = None
    import os
    models = (gpt_1p3b, gpt_350m, gpt_125m)
    if os.environ.get("PADDLE_TPU_BENCH_SMOKE"):
        from paddle_tpu.models import gpt_tiny
        models = (gpt_tiny,)
        batch, prompt_len, gen = 2, 16, 8
    for mk in models:
        try:
            cfg = mk(max_seq_len=max(512, prompt_len + gen))
            model = GPT(cfg)
            model.bfloat16()
            model.eval()
            page_size = 32
            pages_per_seq = (prompt_len + gen + page_size - 1) // page_size
            dec = PagedGPTDecoder(
                model, num_pages=batch * pages_per_seq + 2,
                page_size=page_size, max_batch=batch, quant=quant)

            def run_batch(step_times=None):
                eng = ContinuousBatchingEngine(dec, max_new_tokens=gen)
                for _ in range(batch):
                    eng.submit(rng.randint(
                        0, cfg.vocab_size, prompt_len).astype(np.int32))
                return eng.run(step_times=step_times), eng

            t0 = time.time()
            run_batch()              # compile prefill bucket + decode step
            log(f"decode[{mk.__name__}] compile+first batch: "
                f"{time.time()-t0:.1f}s")
            steps = []
            t0 = time.time()
            outs, eng = run_batch(steps)
            dt = time.time() - t0
            n_tok = sum(len(v) for v in outs.values())
            tok_s = n_tok / dt
            # HBM roofline at the mean context length of the run
            ceil = decode_roofline_tok_s(cfg, batch, prompt_len + gen / 2,
                                         quant=quant)
            # per-token p50/p99 come from ServeStats (wall per emitted
            # token). The first step_times entry contains the full-batch
            # prefill — orders of magnitude more work than a decode
            # tick — so it's reported separately, not in the
            # percentiles; on the multi-step path that first sync also
            # spans the first K-tick horizon (the engine overlaps fetch
            # with the next dispatch), hence "first_sync" not
            # "admission"
            summary = eng.stats.summary()
            lat = {
                "p50_ms": summary.get("token_p50_ms", 0.0),
                "p99_ms": summary.get("token_p99_ms", 0.0),
                "first_sync_ms": round(steps[0] * 1e3, 2),
            }
            log(f"decode[{mk.__name__}{'/' + quant if quant else ''}]: "
                f"{n_tok} tokens in {dt:.2f}s = {tok_s:.0f} tok/s "
                f"({tok_s / ceil:.0%} of {ceil:.0f} tok/s HBM roofline; "
                f"per-token p50 {lat['p50_ms']}ms p99 {lat['p99_ms']}ms; "
                f"K={eng.k_max}, "
                f"{summary['host_syncs_per_token']:.3f} host syncs/token; "
                f"batch={batch}, prompt={prompt_len}, gen={gen})")
            return {"tok_s": tok_s, "model": mk.__name__,
                    "vs_roofline": round(tok_s / ceil, 4),
                    "roofline_tok_s": round(ceil, 1), "latency": lat,
                    "k_max": eng.k_max,
                    "host_syncs_per_token":
                        summary["host_syncs_per_token"]}
        except TimeoutError:
            # the _alarm wrapping this whole call fired: one-shot, so the
            # fallback model would run unguarded — propagate instead. Null
            # the HBM-pinning locals first: the raised traceback keeps this
            # frame alive, and a still-referenced 1.3B model would OOM the
            # caller's next quant variant.
            model = dec = run_batch = cfg = eng = None
            import gc
            gc.collect()
            raise
        except Exception as e:
            last_err = f"{type(e).__name__}: {str(e)[:200]}"
            log(f"decode {mk.__name__} failed: {last_err}")
            # the failed attempt's weights/pages must be freed BEFORE the
            # smaller model allocates, or the fallback OOMs too
            model = dec = run_batch = cfg = eng = None
            del e
            import gc
            gc.collect()
    raise RuntimeError(last_err or "decode bench failed")


def run_prefix_cache(n_requests=24, prompt_len=44, gen=4, zipf_a=1.2):
    """Prefix-cache serving scenario: requests draw a shared prompt
    template from a Zipf distribution (the real-fleet shape: a few
    system prompts / few-shot templates dominate) and append a private
    suffix. Sweeps the template pool size — unique prompts (hit rate 0)
    up to one universal template — on ONE decoder (compiles shared
    across scenarios; each scenario gets a fresh engine + cache) and
    reports achieved hit rate vs TTFT and prefill FLOPs. Requests run
    sequentially so TTFT is per-request clean. CPU-runnable (tiny GPT):
    the committed evidence is the CURVE — TTFT and prefill FLOPs
    decreasing monotonically with hit rate — not the absolute ms."""
    import numpy as np

    import paddle_tpu as paddle
    from paddle_tpu.distributed import build_mesh
    from paddle_tpu.models import GPT, gpt_tiny
    from paddle_tpu.serving import (ContinuousBatchingEngine,
                                    PagedGPTDecoder, PrefixCache)

    paddle.seed(0)
    build_mesh(dp=1)
    cfg = gpt_tiny(max_seq_len=max(128, prompt_len + gen + 16),
                   dtype="float32", remat=False)
    model = GPT(cfg)
    model.eval()
    page_size = 16
    pages_per_seq = (prompt_len + gen + page_size - 1) // page_size
    dec = PagedGPTDecoder(model, num_pages=8 * pages_per_seq + 2,
                          page_size=page_size, max_batch=2)
    fpt = 2 * cfg.num_params()       # matmul FLOPs per prefill token
    # block-aligned shared prefix + PARTIAL-block private suffix (a
    # partial trailing block is never cacheable, so unique suffixes
    # can't pollute the cache and the max hit rate approaches 1)
    prefix_len = (prompt_len // page_size) * page_size
    if prefix_len >= prompt_len:
        prefix_len -= page_size
    suffix_len = prompt_len - prefix_len
    rng = np.random.RandomState(0)

    def scenario(n_templates):
        cache = PrefixCache(page_size, salt=dec.cache_fingerprint())
        eng = ContinuousBatchingEngine(dec, max_new_tokens=gen,
                                       prefix_cache=cache)
        templates = [rng.randint(0, cfg.vocab_size, prefix_len).tolist()
                     for _ in range(max(n_templates, 1))]
        total_prompt = 0
        for _ in range(n_requests):
            if n_templates == 0:     # no sharing: every prefix unique
                prefix = rng.randint(0, cfg.vocab_size,
                                     prefix_len).tolist()
            else:
                z = min(int(rng.zipf(zipf_a)), len(templates)) - 1
                prefix = templates[z]
            suffix = rng.randint(0, cfg.vocab_size, suffix_len).tolist()
            eng.submit(np.asarray(prefix + suffix, np.int32))
            eng.run()                # sequential: clean per-request TTFT
            total_prompt += prompt_len
        s = eng.stats
        computed = total_prompt - s.prefix_tokens_saved
        return {"templates": n_templates,
                "hit_rate": round(s.prefix_hit_rate, 4),
                # MEAN, not p50: TTFT = miss_frac * t_full +
                # hit_frac * t_suffix, so the mean tracks the hit rate
                # structurally; p50 collapses to the hit path as soon
                # as hits pass 50% and stops moving
                "ttft_ms": round(float(np.mean(s.ttft_s)) * 1e3, 2),
                "ttft_p50_ms": round(
                    float(np.percentile(s.ttft_s, 50)) * 1e3, 2),
                "prefill_flops": int(computed * fpt),
                "prefill_flops_saved": int(s.prefix_tokens_saved * fpt),
                "prefix_tokens_saved": int(s.prefix_tokens_saved),
                "evictions": s.prefix_evictions,
                "cow": s.prefix_cow}

    scenario(1)                      # warm every bucket compile
    rows = sorted((scenario(n) for n in (0, 8, 2, 1)),
                  key=lambda r: r["hit_rate"])
    for r in rows:
        log(f"prefix[{r['templates']} templates]: hit_rate "
            f"{r['hit_rate']:.2f}, ttft mean {r['ttft_ms']}ms "
            f"(p50 {r['ttft_p50_ms']}ms), "
            f"prefill {r['prefill_flops']:.3g} FLOPs "
            f"(saved {r['prefill_flops_saved']:.3g}; "
            f"{r['evictions']} evictions)")
        print(json.dumps({"metric": "gpt_prefill_ttft_vs_hit_rate",
                          "value": r["ttft_ms"], "unit": "ms",
                          **r}), flush=True)
    best = rows[-1]
    print(json.dumps({"metric": "gpt_prefill_flops_saved",
                      "value": best["prefill_flops_saved"],
                      "unit": "FLOPs",
                      "hit_rate": best["hit_rate"],
                      "ttft_ms": best["ttft_ms"],
                      "n_requests": n_requests,
                      "prompt_len": prompt_len}), flush=True)
    return rows


def run_kv_tier(n_requests=48, prompt_len=44, gen=4, zipf_s=0.7,
                n_templates=12):
    """Tiered-KV serving scenario: the SAME Zipf shared-template
    workload as run_prefix_cache, but with a template working set that
    does NOT fit the page pool — the failure mode production fleets
    hit at scale. Three measured runs:

      fits   — a pool big enough to park every template (the
               reference hit rate: only first-touch misses),
      cliff  — a small pool, no tier: eviction at the HBM cliff
               destroys parked templates and the hit rate collapses,
      tiered — the SAME small pool + a HostKVTier: evictions demote
               to host RAM and later admissions RESTORE, so the hit
               rate stays within 10% of `fits` (the acceptance bar).

    All three emit byte-identical streams (asserted — pool size, tier
    and spills never change a token). The restore policy is pinned
    "restore" here: the auto policy prices tiny-model recompute
    cheaper than the PCIe wire (correctly — the decision flips with
    model scale, unit-tested in tests/test_kv_tier.py), and the CPU
    bench's claim is the no-cliff hit-rate curve, not the pricing."""
    import numpy as np

    import paddle_tpu as paddle
    from paddle_tpu.distributed import build_mesh
    from paddle_tpu.models import GPT, gpt_tiny
    from paddle_tpu.serving import (ContinuousBatchingEngine, HostKVTier,
                                    PagedGPTDecoder, PrefixCache)

    paddle.seed(0)
    build_mesh(dp=1)
    cfg = gpt_tiny(max_seq_len=max(128, prompt_len + gen + 16),
                   dtype="float32", remat=False)
    model = GPT(cfg)
    model.eval()
    page_size = 16
    pages_per_seq = (prompt_len + gen + page_size - 1) // page_size
    prefix_len = (prompt_len // page_size) * page_size
    if prefix_len >= prompt_len:
        prefix_len -= page_size
    suffix_len = prompt_len - prefix_len
    blocks_per_template = prefix_len // page_size
    # fits: every template parks + one active request; small: ~3
    # templates' worth of parked pages — the working set is >3x it
    fits_pages = n_templates * blocks_per_template + pages_per_seq + 2
    small_pages = 2 * blocks_per_template + pages_per_seq + 2
    rng0 = np.random.RandomState(0)
    templates = [rng0.randint(0, cfg.vocab_size, prefix_len).tolist()
                 for _ in range(n_templates)]

    # explicit Zipf(s) weights over the template ranks (rng.zipf with
    # a near 1 degenerates under the clamp — most draws exceed the
    # pool and pile onto one index): s=0.7 is the flat-ish head/tail
    # mix where the whole working set stays live — the regime where a
    # small pool's LRU actually thrashes
    probs = np.array([1.0 / (i + 1) ** zipf_s
                      for i in range(n_templates)])
    probs /= probs.sum()

    def workload():
        rng = np.random.RandomState(1)
        for _ in range(n_requests):
            z = int(rng.choice(n_templates, p=probs))
            suffix = rng.randint(0, cfg.vocab_size, suffix_len).tolist()
            yield templates[z] + suffix

    def scenario(num_pages, tier=None, policy="auto"):
        dec = PagedGPTDecoder(model, num_pages=num_pages,
                              page_size=page_size, max_batch=2)
        cache = PrefixCache(page_size, salt=dec.cache_fingerprint(),
                            tier=tier)
        eng = ContinuousBatchingEngine(dec, max_new_tokens=gen,
                                       prefix_cache=cache,
                                       tier_policy=policy)
        outs = []
        for prompt in workload():
            rid = eng.submit(np.asarray(prompt, np.int32))
            outs.append(eng.run()[rid])   # sequential: clean TTFT
        assert eng.audit_pages() == [], "page ledger audit failed"
        s = eng.stats
        return {"num_pages": num_pages,
                "hit_rate": round(s.prefix_hit_rate, 4),
                "ttft_ms": round(float(np.mean(s.ttft_s)) * 1e3, 2),
                "evictions": s.prefix_evictions,
                "tier_spills": s.tier_spills,
                "tier_restores": s.tier_restores,
                "tier_recomputes": s.tier_recomputes,
                "host_tier_bytes": s.host_tier_bytes}, outs

    fits, out_f = scenario(fits_pages)
    cliff, out_c = scenario(small_pages)
    tiered, out_t = scenario(small_pages, tier=HostKVTier(),
                             policy="restore")
    # pool size, eviction and the tier never change a token
    assert out_f == out_c == out_t, "streams diverged across tiers"
    for name, r in (("fits", fits), ("cliff", cliff),
                    ("tiered", tiered)):
        log(f"kv_tier[{name}]: pool {r['num_pages']} pages, hit_rate "
            f"{r['hit_rate']:.3f}, ttft mean {r['ttft_ms']}ms, "
            f"{r['evictions']} evictions, {r['tier_spills']} spills / "
            f"{r['tier_restores']} restores")
    row = {"metric": "gpt_prefix_hit_rate_tiered",
           "value": tiered["hit_rate"], "unit": "hit_rate",
           "fits_hit_rate": fits["hit_rate"],
           "cliff_hit_rate": cliff["hit_rate"],
           "tier_spills": tiered["tier_spills"],
           "tier_restores": tiered["tier_restores"],
           "host_tier_bytes": tiered["host_tier_bytes"],
           "n_requests": n_requests, "n_templates": n_templates,
           "small_pool_pages": small_pages, "fits_pool_pages": fits_pages,
           "streams_equal": True,
           # the acceptance bar: no eviction cliff with the tier on
           "within_10pct_of_fits":
               bool(tiered["hit_rate"] >= 0.9 * fits["hit_rate"])}
    print(json.dumps(row), flush=True)
    return {"fits": fits, "cliff": cliff, "tiered": tiered, **row}


def run_fleet(n_replicas=3, n_requests=48, n_templates=8, template_len=32,
              suffix_len=12, gen=32, zipf_s=0.7, waves=5):
    """Fleet serving scenario (serving.fleet): the Zipf shared-template
    workload from run_kv_tier, served by a FleetRouter over N engine
    replicas that share ONE host KV tier. Three measured fleets:

      N=1        — a single replica: the per-core reference rate,
      N   (seq)  — N replicas drained round-robin: pure routing and
                   shared-tier overhead, no thread concurrency,
      N   (par)  — N replicas on threads: the production topology.

    All three emit byte-identical streams (asserted — the router's
    global rid order makes fleet size invisible to the bytes). Each
    fleet serves `waves` identical request waves and the LAST wave is
    the timed one: each replica owns its own jit cache and sees ~1/N
    of the traffic, so rare ragged shapes compile stragglers for
    several waves — timing an early wave measures XLA, not serving.

    The scaling bar is honest about the host: ideal aggregate rate is
    tok_s_1 x min(N, cpu_cores) — on a 1-core box N replicas time-
    slice one core and the ideal is flat, while on an N-core box it
    is linear. The acceptance bar is >=0.8x that ideal.

    The page pool is sized at the HBM cliff for ONE replica: the
    single engine can't park the whole template working set, so it
    spills to the shared tier and restores on re-admission (the tier
    stats prove the tier leg ran). The fleet's prefix-affinity
    routing splits the working set N ways, each replica's share fits,
    and the cliff disappears — the second fleet-scale effect beyond
    raw throughput. The restore policy is pinned (see run_kv_tier on
    why auto correctly recomputes at toy scale)."""
    import tempfile

    import numpy as np

    import paddle_tpu as paddle
    from paddle_tpu.distributed import build_mesh
    from paddle_tpu.models import GPT, gpt_tiny
    from paddle_tpu.serving import (FleetRouter, PagedGPTDecoder,
                                    PrefixCache, SharedHostKVTier,
                                    TenantEngine)

    paddle.seed(0)
    build_mesh(dp=1)
    cfg = gpt_tiny(max_seq_len=max(128, template_len + suffix_len + gen),
                   dtype="float32", remat=False)
    model = GPT(cfg)
    model.eval()
    page_size = 16
    rng0 = np.random.RandomState(0)
    templates = [rng0.randint(0, cfg.vocab_size, template_len).tolist()
                 for _ in range(n_templates)]
    probs = np.array([1.0 / (i + 1) ** zipf_s
                      for i in range(n_templates)])
    probs /= probs.sum()

    def wave(seed):
        rng = np.random.RandomState(seed)
        out = []
        for _ in range(n_requests):
            z = int(rng.choice(n_templates, p=probs))
            suffix = rng.randint(0, cfg.vocab_size, suffix_len).tolist()
            out.append(templates[z] + suffix)
        return out

    def build_fleet(n):
        tier_dir = tempfile.mkdtemp(prefix="bench_fleet_tier_")
        engines = []
        for _ in range(n):
            dec = PagedGPTDecoder(model, num_pages=24,
                                  page_size=page_size, max_batch=4)
            tier = SharedHostKVTier(tier_dir, capacity_bytes=64 << 20,
                                    fingerprint=dec)
            cache = PrefixCache(page_size, salt=dec.cache_fingerprint(),
                                tier=tier)
            engines.append(TenantEngine(dec, max_new_tokens=gen,
                                        prefix_cache=cache,
                                        tier_policy="restore"))
        return FleetRouter(engines)

    def scenario(n, parallel):
        r = build_fleet(n)
        toks = dt = 0
        streams = None
        for w in range(waves):
            gids = [r.submit(p) for p in wave(1 + w)]
            t0 = time.perf_counter()
            out = r.run(parallel=parallel)
            dt = time.perf_counter() - t0
            toks = sum(len(out[g]) for g in gids)
            streams = [out[g] for g in gids]
        s = r.merged_stats().summary()
        tier = r.engines[0].cache.tier
        res = {"replicas": n, "parallel": parallel,
               "tok_s": round(toks / dt, 1),
               "wave_s": round(dt, 3),
               "hit_rate": round(s.get("prefix_hit_rate", 0.0), 4),
               "tier_spills": s.get("tier_spills", 0),
               "tier_restores": s.get("tier_restores", 0),
               "tier_entries": tier.n_entries,
               "tier_bytes": tier.bytes_used}
        return res, streams

    one, out_1 = scenario(1, parallel=False)
    seq, out_s = scenario(n_replicas, parallel=False)
    par, out_p = scenario(n_replicas, parallel=True)
    # fleet size, drain order and threading never change a token
    assert out_1 == out_s == out_p, "streams diverged across fleet sizes"
    cores = os.cpu_count() or 1
    ideal = one["tok_s"] * min(n_replicas, cores)
    eff = par["tok_s"] / ideal if ideal else 0.0
    for name, r in (("1", one), (f"{n_replicas}seq", seq),
                    (f"{n_replicas}par", par)):
        log(f"fleet[{name}]: {r['tok_s']} tok/s steady wave "
            f"({r['wave_s']}s), hit_rate {r['hit_rate']:.3f}, "
            f"{r['tier_spills']} spills / {r['tier_restores']} "
            f"restores, shared tier {r['tier_entries']} entries / "
            f"{r['tier_bytes']}B")
    log(f"fleet: scaling {par['tok_s']:.0f} / ideal {ideal:.0f} "
        f"(tok_s_1 x min({n_replicas}, {cores} cores)) = {eff:.2f}x")
    row = {"metric": "gpt_fleet_tokens_per_sec", "value": par["tok_s"],
           "unit": "tokens/s", "replicas": n_replicas,
           "tok_s_1": one["tok_s"], "tok_s_n_seq": seq["tok_s"],
           "cores": cores, "ideal_tok_s": round(ideal, 1),
           "scaling_efficiency": round(eff, 3),
           "hit_rate": par["hit_rate"],
           "hit_rate_1": one["hit_rate"],
           "tier_restores_1": one["tier_restores"],
           "shared_tier_entries_1": one["tier_entries"],
           "n_requests": n_requests, "waves": waves,
           "streams_equal": True,
           "linear_at_0_8": bool(eff >= 0.8)}
    print(json.dumps(row), flush=True)
    return {"one": one, "seq": seq, "par": par, **row}


def run_multi_tenant(n_throughput=16, n_latency=5, prompt_len=24,
                     lat_prompt_len=36, gen=16, n_adapters=3):
    """Bursty multi-tenant serving scenario (serving.tenancy): a
    throughput-tier FLOOD (n_throughput requests from batch tenants,
    rotating over n_adapters LoRA variants on shared base weights)
    saturates a small pool, while latency-tier chat requests arrive
    MID-STREAM at deterministic points in the token stream. Two
    engines serve the identical workload:

      blind  — the class-blind `ContinuousBatchingEngine`: every
               request FIFOs through the same queue, so a latency
               arrival waits out the backlog (its TTFT tail IS the
               flood drain time),
      tenant — the `TenantEngine`: latency requests admit ahead of the
               backlog, preempt throughput victims by page-spill when
               the pool is full (pages park in the prefix cache,
               victims resume byte-identically), and horizons compose
               per class (`TenantScheduler`).

    The headline is latency-tier TTFT p99 under the flood — the
    acceptance bar is >= 2x better than the class-blind engine at
    comparable aggregate tokens/s (>= 0.85x; the tenant engine does
    the same total work plus preemption overhead). Every request's
    stream is asserted byte-identical across the two engines (the
    preempted-and-resumed victims included), and the page ledger
    (slot_adapters rows included) audits clean. TTFT is measured
    client-side (submit -> first token observed at a sync), so both
    engines are scored by the same clock."""
    import numpy as np

    import paddle_tpu as paddle
    from paddle_tpu.distributed import build_mesh
    from paddle_tpu.models import GPT, gpt_tiny
    from paddle_tpu.serving import (SLO_LATENCY, SLO_THROUGHPUT,
                                    ContinuousBatchingEngine,
                                    PagedGPTDecoder, PrefixCache,
                                    TenantEngine, make_lora_bank)

    paddle.seed(0)
    build_mesh(dp=1)
    cfg = gpt_tiny(max_seq_len=max(128, lat_prompt_len + gen + 16),
                   dtype="float32", remat=False)
    model = GPT(cfg)
    model.eval()
    page_size = 16
    bank = make_lora_bank(cfg, n_adapters, rank=4, seed=9)
    rng = np.random.RandomState(1)
    V = cfg.vocab_size
    tp_prompts = [rng.randint(0, V, prompt_len).tolist()
                  for _ in range(n_throughput)]
    lat_prompts = [rng.randint(0, V, lat_prompt_len).tolist()
                   for _ in range(n_latency)]
    tp_adapters = [1 + i % n_adapters for i in range(n_throughput)]
    # latency arrivals at deterministic TOKEN-COUNT points spread over
    # the flood's drain — the same thresholds drive both engines, so
    # the burst pattern is identical
    approx_total = (n_throughput + n_latency) * gen
    arrive_at = [int(approx_total * (i + 1) / (n_latency + 2))
                 for i in range(n_latency)]
    # 2 slots x 2-page throughput requests fill a 7-page pool; a
    # 3-page latency arrival must preempt
    num_pages = 7

    def scenario(tenant_aware):
        dec = PagedGPTDecoder(model, num_pages=num_pages,
                              page_size=page_size, max_batch=2)
        dec.attach_adapters(bank)
        cache = PrefixCache(page_size, salt=dec.cache_fingerprint())
        cls = TenantEngine if tenant_aware else ContinuousBatchingEngine
        eng = cls(dec, max_new_tokens=gen, prefix_cache=cache)
        rids = []
        for i, p in enumerate(tp_prompts):
            kw = (dict(tenant=f"batch{i % 2}", slo=SLO_THROUGHPUT)
                  if tenant_aware else {})
            rids.append(eng.submit(np.asarray(p, np.int32),
                                   adapter=tp_adapters[i], **kw))
        lat_rids = []
        state = {"submit_t": {}, "ttft": {}, "next": 0}

        def on_sync(e):
            now = time.perf_counter()
            while state["next"] < n_latency and \
                    e.stats.tokens >= arrive_at[state["next"]]:
                j = state["next"]
                kw = (dict(tenant="chat", slo=SLO_LATENCY)
                      if tenant_aware else {})
                r = e.submit(np.asarray(lat_prompts[j], np.int32),
                             **kw)
                lat_rids.append(r)
                state["submit_t"][r] = now
                state["next"] += 1
            for r, t0 in state["submit_t"].items():
                if r not in state["ttft"] and e._outputs.get(r):
                    state["ttft"][r] = now - t0

        t0 = time.perf_counter()
        outs = eng.run(on_sync=on_sync)
        wall = time.perf_counter() - t0
        assert eng.audit_pages() == [], "page ledger audit failed"
        assert len(state["ttft"]) == n_latency, \
            "a latency request never produced a token"
        ttfts = [state["ttft"][r] for r in lat_rids]
        res = {"lat_ttft_p50_ms":
               round(float(np.percentile(ttfts, 50)) * 1e3, 2),
               "lat_ttft_p99_ms":
               round(float(np.percentile(ttfts, 99)) * 1e3, 2),
               "agg_tok_s": round(eng.stats.tokens / wall, 1),
               "preemptions": eng.stats.preemptions,
               "resumes": eng.stats.resumes}
        if tenant_aware:
            res["tenancy"] = eng.tenancy_summary()
        streams = [outs[r] for r in rids] + [outs[r] for r in lat_rids]
        return res, streams

    blind, out_b = scenario(False)
    tenant, out_t = scenario(True)
    # classes, preemption and resume never change a token
    assert out_b == out_t, "streams diverged blind vs tenant-aware"
    assert tenant["preemptions"] > 0, \
        "flood never forced a preemption — scenario too gentle"
    speedup = blind["lat_ttft_p99_ms"] / max(tenant["lat_ttft_p99_ms"],
                                             1e-9)
    for name, r in (("blind", blind), ("tenant", tenant)):
        log(f"multi_tenant[{name}]: latency-tier ttft p99 "
            f"{r['lat_ttft_p99_ms']}ms (p50 {r['lat_ttft_p50_ms']}ms), "
            f"{r['agg_tok_s']} tok/s aggregate, "
            f"{r['preemptions']} preemptions")
    row = {"metric": "gpt_decode_mt_p99_ms",
           "value": tenant["lat_ttft_p99_ms"], "unit": "ms",
           "blind_p99_ms": blind["lat_ttft_p99_ms"],
           "p99_speedup": round(speedup, 2),
           "agg_tok_s_ratio": round(tenant["agg_tok_s"] /
                                    max(blind["agg_tok_s"], 1e-9), 3),
           "preemptions": tenant["preemptions"],
           "resumes": tenant["resumes"],
           "n_throughput": n_throughput, "n_latency": n_latency,
           "n_adapters": n_adapters,
           "tenancy": tenant["tenancy"],
           "streams_equal": True,
           # the acceptance bar: >=2x latency-tier p99 at comparable
           # aggregate throughput
           "meets_2x_bar": bool(speedup >= 2.0)}
    print(json.dumps(row), flush=True)
    return {"blind": blind, "tenant": tenant, **row}


def run_ragged_stall(gen=48, long_prompt=448, chunk=16, k_max=2):
    """Long-prompt-arrival serving scenario: decode p99 per-token
    latency of an ALREADY-RUNNING slot while a long prompt streams in.
    The dispatch-separate baseline admits the prompt with ONE
    host-blocking prefill — the running slot's next tokens wait out
    the whole forward (the stall the ROADMAP calls the biggest lever
    on throughput-under-load). The ragged engine admits it as
    token-budgeted chunks INSIDE the decode horizon, so the running
    slot pays at most one slightly-longer tick per chunk. CPU-runnable:
    the committed evidence is the RATIO — post-arrival decode p99
    improving >= 1.5x — not the absolute ms. Latency is measured
    client-side (token arrival gaps via run(on_sync=...)), so the
    baseline's stall cannot hide behind ServeStats' prefill exclusion;
    every compiled program is warmed on the SHARED decoder before the
    measured runs, so the ratio compares steady-state schedules, not
    one-time XLA compiles.

    Operating point: a 4-layer/256-hidden GPT (prompt-token compute
    must dominate CPU per-tick dispatch overhead or the ratio measures
    graph-launch noise), K=2 horizons and 16-token chunks — the
    per-token stall bound is ~(L/K)/w, and K also sizes the shared
    horizon-granularity tail both engines pay, so small K both
    concentrates the baseline's stall and shrinks the ragged floor."""
    import numpy as np

    import paddle_tpu as paddle
    from paddle_tpu.distributed import build_mesh
    from paddle_tpu.models import GPT, gpt_tiny
    from paddle_tpu.serving import ContinuousBatchingEngine, PagedGPTDecoder

    paddle.seed(0)
    build_mesh(dp=1)
    cfg = gpt_tiny(hidden_size=256, num_layers=4, num_heads=8,
                   max_seq_len=long_prompt + gen + 64, dtype="float32",
                   remat=False)
    model = GPT(cfg)
    model.eval()
    page_size = 32
    rng = np.random.RandomState(0)
    streamer = rng.randint(0, cfg.vocab_size, 8).astype(np.int32)
    long_ids = rng.randint(0, cfg.vocab_size, long_prompt).astype(np.int32)
    pages = (long_prompt + gen + 8 + gen) // page_size + 4

    # ONE decoder shared by every scenario run: compiled programs are
    # per-decoder-instance (jitted bound partials), so warmup only
    # warms the measured runs if they reuse the same instance (the
    # run_prefix_cache discipline) — otherwise the mixed-horizon /
    # suffix-prefill compiles land INSIDE the post-arrival latency
    # window and the committed ratio compares compile times
    dec = PagedGPTDecoder(model, num_pages=pages + 2,
                          page_size=page_size, max_batch=2)

    def scenario(ragged, trace=None):
        eng = ContinuousBatchingEngine(dec, max_new_tokens=gen,
                                       k_max=k_max, ragged=ragged,
                                       chunk_tokens=chunk, trace=trace)
        rid = eng.submit(streamer)
        state = {"submit_t": None, "events": []}

        def on_sync(e):
            now = time.perf_counter()
            state["events"].append((now, len(e._outputs.get(rid, []))))
            if state["submit_t"] is None and \
                    len(e._outputs.get(rid, [])) >= gen // 4:
                e.submit(long_ids)       # the long prompt arrives NOW,
                state["submit_t"] = now  # mid-stream of the other slot

        outs = eng.run(on_sync=on_sync)
        assert len(outs[rid]) == gen and state["submit_t"] is not None
        lats = []
        prev = None
        for t, n in state["events"]:
            if prev is not None and n > prev[1] and t > state["submit_t"]:
                lats.extend([(t - prev[0]) / (n - prev[1])] * (n - prev[1]))
            prev = (t, n)
        return ({"p50_ms": round(float(np.percentile(lats, 50)) * 1e3, 3),
                 "p99_ms": round(float(np.percentile(lats, 99)) * 1e3, 3)},
                eng)

    scenario(True)                       # warm every compile
    scenario(False)
    ragged, eng_r = scenario(True)
    base, eng_b = scenario(False)
    improvement = base["p99_ms"] / max(ragged["p99_ms"], 1e-9)
    row = {"baseline_p99_ms": base["p99_ms"],
           "baseline_p50_ms": base["p50_ms"],
           "ragged_p99_ms": ragged["p99_ms"],
           "ragged_p50_ms": ragged["p50_ms"],
           "p99_improvement": round(improvement, 2),
           "long_prompt": long_prompt, "chunk_tokens": chunk,
           "k_max": k_max,
           # the other half of the claim: the ragged engine paid ZERO
           # host-blocking prefill syncs; the baseline stalled
           "baseline_prefill_stall_syncs":
               eng_b.stats.prefill_stall_syncs,
           "ragged_prefill_stall_syncs":
               eng_r.stats.prefill_stall_syncs,
           "ragged_prefill_chunks": eng_r.stats.prefill_chunks}
    log(f"ragged_stall: post-arrival decode p99 {base['p99_ms']}ms -> "
        f"{ragged['p99_ms']}ms ({improvement:.2f}x) with a "
        f"{long_prompt}-token prompt arriving mid-stream "
        f"(chunk={chunk}, K={k_max}; baseline stalls: "
        f"{eng_b.stats.prefill_stall_syncs}, ragged: 0)")
    print(json.dumps({"metric": "gpt_decode_stall_p99_ms",
                      "value": ragged["p99_ms"], "unit": "ms",
                      **row}), flush=True)
    # PADDLE_TPU_BENCH_TRACE=/path.json: replay the ragged scenario
    # once more with a flight recorder attached (AFTER the measured
    # runs — the committed ratio stays untraced) and export the
    # chrome-trace timeline + the roofline-drift ledger. On CPU the
    # drift ratio is dominated by the host gap (predictions price the
    # target chip); on-chip this line is the mispricing detector
    # (docs/observability.md).
    trace_path = os.environ.get("PADDLE_TPU_BENCH_TRACE")
    if trace_path:
        from paddle_tpu.serving import FlightRecorder, export_chrome_trace
        rec = FlightRecorder()
        scenario(True, trace=rec)
        export_chrome_trace(trace_path, recorders=rec)
        drift = rec.drift_report()
        # worst departure in EITHER direction (the analyzer's
        # worst_ratio convention): overpriced shapes must not read as
        # near-clean just because their ratio sits below 1
        worst = max((max(d["ratio"], 1.0 / d["ratio"])
                     for d in drift if d["ratio"] > 0), default=0.0)
        # drifting shapes whose measured tick sits INSIDE the serial
        # sum of the priced legs are a SERIALIZED schedule, not a
        # mispriced leg (the ROOFLINE-DRIFT verdict split — the fix is
        # the schedule pass / COLL-SERIALIZED, not re-fitting inputs)
        n_serialized = sum(1 for d in drift
                           if d.get("verdict") == "serialized")
        log(f"ragged_stall: flight trace -> {trace_path} "
            f"({len(rec.events)} events, worst drift {worst:.1f}x, "
            f"{n_serialized} serialized shape(s))")
        print(json.dumps({"metric": "serving_roofline_drift",
                          "value": round(worst, 2),
                          "unit": "measured_over_predicted",
                          "shapes": len(drift),
                          "serialized_shapes": n_serialized,
                          "trace_events": len(rec.events),
                          "path": trace_path}), flush=True)
    return row


def run_ragged_pad(gen=40, long_prompt=224, chunk=16, k_max=2,
                   streamers=15):
    """Mixed-horizon PACKED-vs-DENSE layout A/B: pad fraction, CPU
    wall-clock and compiled-variant count of the same workload run
    through the packed [total_new_tokens] token-stream dispatch and
    the dense [S, w] window twin (`packed=False`). The workload is the
    packed layout's motivating shape: many decode rows sharing
    horizons with one long chunking prompt — on the dense layout every
    decode row pays w-1 padded window columns per mixed tick (S*w
    dispatched for ~S-1+w real tokens), on the packed layout the tick
    pays its pow2 total-token bucket. Two short odd-length prompts
    arrive late so the dense path re-buckets on the (S, w) grid (extra
    compiled variants) while the packed path's totals collapse into
    existing buckets (w rides as a traced scalar).

    Streams are byte-identical between the two engines (the layout
    twin invariant, test-pinned); this scenario banks the THREE
    layout claims: pad fraction drops >= 3x, wall-clock no worse,
    compiled-variant count (jit cache entries) strictly lower."""
    import numpy as np

    import paddle_tpu as paddle
    from paddle_tpu.distributed import build_mesh
    from paddle_tpu.models import GPT, gpt_tiny
    from paddle_tpu.serving import ContinuousBatchingEngine, PagedGPTDecoder

    paddle.seed(0)
    build_mesh(dp=1)
    S = streamers + 1
    cfg = gpt_tiny(hidden_size=256, num_layers=4, num_heads=8,
                   max_seq_len=long_prompt + gen + 64, dtype="float32",
                   remat=False)
    model = GPT(cfg)
    model.eval()
    page_size = 32
    rng = np.random.RandomState(0)
    stream_ids = [rng.randint(0, cfg.vocab_size, 2).astype(np.int32)
                  for _ in range(2 * streamers)]
    long_ids = rng.randint(0, cfg.vocab_size, long_prompt).astype(np.int32)
    odd_ids = [rng.randint(0, cfg.vocab_size, n).astype(np.int32)
               for n in (5, 3)]
    per_seq = (long_prompt + gen) // page_size + 2
    pages = S * ((8 + gen) // page_size + 2) + per_seq + 8

    # ONE decoder shared by every run (the run_ragged_stall compile
    # discipline: jit memos are per-instance, so only a shared
    # instance lets the warm-up runs warm the measured runs)
    dec = PagedGPTDecoder(model, num_pages=pages + 2,
                          page_size=page_size, max_batch=S)

    def scenario(packed):
        eng = ContinuousBatchingEngine(dec, max_new_tokens=gen,
                                       k_max=k_max, ragged=True,
                                       chunk_tokens=chunk, packed=packed)
        # one slot stays FREE so later odd-length arrivals admit (and
        # chunk) at different times — each distinct suffix cover is a
        # fresh (S, w) bucket for the dense grid, while the packed
        # totals keep collapsing into the same pow2 buckets
        rids = [eng.submit(ids) for ids in stream_ids[:streamers - 1]]
        state = {"sent": 0}

        def on_sync(e):
            n = len(e._outputs.get(rids[0], []))
            # the long prompt lands mid-stream; the odd short prompts
            # arrive later (staggered); a SECOND streamer wave keeps
            # the batch full while the long prompt drains its decode
            # budget (a near-empty batch pads both layouts alike — a
            # production engine at load is the comparison that matters)
            if state["sent"] == 0 and n >= gen // 4:
                e.submit(long_ids)
                state["sent"] = 1
            elif state["sent"] == 1 and n >= 3 * gen // 4:
                e.submit(odd_ids[0])
                state["sent"] = 2
            elif state["sent"] == 2 and n >= 3 * gen // 4 + 4:
                e.submit(odd_ids[1])
                state["sent"] = 3
            elif state["sent"] == 3 and n >= gen - 2:
                # wave 2 rides into the slots wave 1 frees (an
                # overflow request would drain ALONE at the end —
                # padding both layouts alike); sized so the admission's
                # token total stays inside the mixed horizons' pow2
                # bucket
                for ids in stream_ids[streamers:2 * streamers - 4]:
                    e.submit(ids)
                state["sent"] = 4

        t0 = time.perf_counter()
        outs = eng.run(on_sync=on_sync)
        wall = time.perf_counter() - t0
        assert state["sent"] == 4 and len(outs) == 2 * streamers - 2
        return ({"pad_fraction": round(eng.stats.pad_fraction, 4),
                 "tokens_dispatched": eng.stats.tokens_dispatched,
                 "tokens_padded": eng.stats.tokens_padded,
                 "wall_s": round(wall, 3)}, outs)

    def jit_entries(memos):
        return sum(fn._cache_size() for memo in memos
                   for fn in memo.values())

    scenario(True)                       # warm every packed compile
    scenario(False)                      # ... and every dense one
    packed, outs_p = scenario(True)
    dense, outs_d = scenario(False)
    assert outs_p == outs_d, "packed/dense twin streams diverged"
    # compiled-variant count per layout: the decoder memos are the jit
    # objects, their internal cache entries count per-shape variants
    # (table-width buckets included) — the (S, w) grid vs total-token
    # buckets claim, measured
    packed_entries = jit_entries([dec._packeds])
    dense_entries = jit_entries([dec._raggeds])
    drop = dense["pad_fraction"] / max(packed["pad_fraction"], 1e-9)
    row = {"packed_pad_fraction": packed["pad_fraction"],
           "dense_pad_fraction": dense["pad_fraction"],
           "pad_drop_x": round(drop, 2),
           "packed_tokens_dispatched": packed["tokens_dispatched"],
           "dense_tokens_dispatched": dense["tokens_dispatched"],
           "packed_wall_s": packed["wall_s"],
           "dense_wall_s": dense["wall_s"],
           "packed_jit_entries": packed_entries,
           "dense_jit_entries": dense_entries,
           "slots": S, "long_prompt": long_prompt,
           "chunk_tokens": chunk, "k_max": k_max}
    log(f"ragged_pad: pad fraction {dense['pad_fraction']:.3f} dense -> "
        f"{packed['pad_fraction']:.3f} packed ({drop:.1f}x less padding; "
        f"{dense['tokens_dispatched']} -> {packed['tokens_dispatched']} "
        f"positions dispatched), wall {dense['wall_s']}s -> "
        f"{packed['wall_s']}s, jit entries {dense_entries} -> "
        f"{packed_entries}")
    print(json.dumps({"metric": "gpt_ragged_pad_fraction",
                      "value": packed["pad_fraction"],
                      "unit": "padded/dispatched", **row}), flush=True)
    return row


def run_decode_capacity(model_scale="gpt_1p3b", gen=24, p99_batch=8):
    """Concurrent-slot capacity at a fixed per-token p99: bf16 vs int8
    vs int4 KV pool.  Decode is HBM-bound, so at a per-token latency
    SLO the admissible slot count is set by how many KV byte-streams
    fit under the tick budget: slots = (p99·BW − weight_bytes) /
    ctx·kv_bytes_tok.
    The SLO is anchored at the BF16 pool's tick with `p99_batch` slots
    at avg_ctx = max_seq/2 (the KV-bound operating point — each slot's
    prefix, not the weights, dominates the stream), so the bf16 column
    reads back ~p99_batch, the int8 column shows the capacity the
    halved KV stream buys, and the int4 column what the nibble-packed
    pool (0.5 B/elem + per-group scales) banks on top under the SAME
    SLO.  Priced on the v5e chip spec
    (`PagedGPTDecoder.step_hbm_bytes(batch=...)` — deterministic,
    CPU-runnable); the measured half runs all three pools through a
    real tiny-GPT engine for tokens/s (CPU numbers carry dispatch
    overhead, the committed evidence is the SLOTS ratio like the other
    serving scenarios' ratios)."""
    import numpy as np

    import paddle_tpu as paddle
    from paddle_tpu.cost_model import chip_spec
    from paddle_tpu.distributed import build_mesh
    from paddle_tpu.models import GPT, gpt_tiny
    from paddle_tpu.models import gpt as gpt_mod
    from paddle_tpu.serving import ContinuousBatchingEngine, PagedGPTDecoder
    from paddle_tpu.serving.decoder import pool_token_bytes

    paddle.seed(0)
    build_mesh(dp=1)
    # the PRICED half needs only shapes: the decoder's own byte model
    # (serving.decoder.pool_token_bytes — the ONE definition behind
    # step_hbm_bytes/kv_token_bytes) applied to the big config, so the
    # bench prices exactly what the decoder would report without
    # building a 1.3B model on the host
    cfg_big = getattr(gpt_mod, model_scale)(max_seq_len=2048)
    cfg = gpt_tiny(max_seq_len=128, dtype="float32", remat=False)
    model = GPT(cfg)
    model.eval()
    chip = chip_spec()
    avg_ctx = cfg_big.max_seq_len // 2
    w_bytes = cfg_big.num_params() * 2   # bf16 weights (the a8w8/w4a16
    # weight legs compose orthogonally; the KV pool is this scenario)
    kv16 = cfg_big.num_layers * avg_ctx * pool_token_bytes(cfg_big)
    kv8 = cfg_big.num_layers * avg_ctx * pool_token_bytes(
        cfg_big, kv_quant="int8")
    kv4 = cfg_big.num_layers * avg_ctx * pool_token_bytes(
        cfg_big, kv_quant="int4")
    # the fixed SLO: the bf16 pool's tick with p99_batch slots. Slots
    # are recovered in INTEGER byte arithmetic (a float divide/multiply
    # round-trip through p99_s can floor the bf16 column to
    # p99_batch-1 and silently flatter the ratio); p99_s is reporting
    # only.
    budget_bytes = w_bytes + p99_batch * kv16
    p99_s = budget_bytes / chip.hbm_bw
    slots = {"bf16": (budget_bytes - w_bytes) // kv16,
             "int8": (budget_bytes - w_bytes) // kv8,
             "int4": (budget_bytes - w_bytes) // kv4}
    assert slots["bf16"] == p99_batch
    ratio = slots["int8"] / max(slots["bf16"], 1)
    ratio4 = slots["int4"] / max(slots["bf16"], 1)
    dec16 = PagedGPTDecoder(model, num_pages=32, page_size=16,
                            max_batch=2)
    dec8 = PagedGPTDecoder(model, num_pages=32, page_size=16,
                           max_batch=2, kv_quant="int8")
    dec4 = PagedGPTDecoder(model, num_pages=32, page_size=16,
                           max_batch=2, kv_quant="int4")

    # measured half: all pools through a real engine (tiny GPT, CPU)
    rng = np.random.RandomState(0)
    prompts = [rng.randint(0, cfg.vocab_size, 12).astype(np.int32)
               for _ in range(4)]
    tok_s = {}
    for name, dec in (("bf16", dec16), ("int8", dec8), ("int4", dec4)):
        def run_once():
            eng = ContinuousBatchingEngine(dec, max_new_tokens=gen,
                                           k_max=8)
            for p in prompts:
                eng.submit(p)
            t0 = time.time()
            outs = eng.run()
            dt = time.time() - t0
            return sum(len(v) for v in outs.values()) / dt, eng
        run_once()                       # warm the compiles
        tok_s[name], _ = run_once()
    row = {"slots_bf16": slots["bf16"], "slots_int8": slots["int8"],
           "slots_int4": slots["int4"],
           "slots_ratio": round(ratio, 2),
           "slots_ratio_int4": round(ratio4, 2),
           "p99_budget_ms": round(p99_s * 1e3, 3),
           "avg_ctx": avg_ctx, "model": model_scale,
           # KV bytes one context token costs across ALL layers (the
           # ServeStats.kv_bytes_per_token view at cfg_big shapes)
           "kv_bytes_per_token_bf16": kv16 // avg_ctx,
           "kv_bytes_per_token_int8": kv8 // avg_ctx,
           "kv_bytes_per_token_int4": kv4 // avg_ctx,
           # measured on the tiny-GPT engines only — keep tiny-scale
           # stats (pool bytes, resident slots) OUT of this row: every
           # other field describes cfg_big shapes, and mixing scales
           # invites misreading (debug.serving_stats() has them live)
           "measured_tok_s_bf16": round(tok_s["bf16"], 1),
           "measured_tok_s_int8": round(tok_s["int8"], 1),
           "measured_tok_s_int4": round(tok_s["int4"], 1)}
    log(f"decode_capacity[{model_scale}]: {slots['bf16']} -> "
        f"{slots['int8']} -> {slots['int4']} slots ({ratio:.2f}x / "
        f"{ratio4:.2f}x) at p99 "
        f"{p99_s*1e3:.2f} ms, avg_ctx={avg_ctx} (KV "
        f"{row['kv_bytes_per_token_bf16']} -> "
        f"{row['kv_bytes_per_token_int8']} -> "
        f"{row['kv_bytes_per_token_int4']} B/token; measured tiny-GPT "
        f"{tok_s['bf16']:.0f} vs {tok_s['int8']:.0f} vs "
        f"{tok_s['int4']:.0f} tok/s on this host)")
    print(json.dumps({"metric": "gpt_decode_capacity",
                      "value": slots["int4"], "unit": "slots",
                      **row}), flush=True)
    return row


def run_train_multi(steps=48, n=None):
    """Multi-step TRAINING throughput: the per-step Trainer.step loop vs
    the fused `step_multi` scan (N steps, one dispatch, losses drained at
    horizon boundaries) on the same config and batches. The training twin
    of run_decode's K-tick story — reported as train steps/sec with the
    horizon N and achieved host syncs/step attached."""
    import numpy as np

    import paddle_tpu as paddle
    from paddle_tpu.cost_model import train_horizon
    from paddle_tpu.distributed import build_mesh
    from paddle_tpu.distributed.trainer import LossBuffer, Trainer
    from paddle_tpu.models import (GPT, GPTPretrainingCriterion, gpt_125m,
                                   gpt_tiny)

    smoke = bool(os.environ.get("PADDLE_TPU_BENCH_SMOKE")) or \
        _on_cpu_backend()
    mk = gpt_tiny if smoke else gpt_125m
    bs, seq = (2, 64) if smoke else (8, 512)
    paddle.seed(0)
    build_mesh(dp=1)
    cfg = mk(max_seq_len=seq, remat=False)
    crit = GPTPretrainingCriterion()

    def loss_fn(m, b):
        return crit(m(paddle.to_tensor(b["input_ids"])),
                    paddle.to_tensor(b["labels"]))

    def make_trainer():
        paddle.seed(0)
        m = GPT(cfg)
        if not smoke:
            m.bfloat16()
        return Trainer(m, paddle.optimizer.AdamW(learning_rate=3e-4),
                       loss_fn)

    rng = np.random.RandomState(0)
    ids = rng.randint(0, cfg.vocab_size, (bs, seq + 1)).astype(np.int32)
    batch = {"input_ids": ids[:, :-1], "labels": ids[:, 1:]}

    # per-step loop: dispatch `steps` steps, one trailing drain
    tr = make_trainer()
    t0 = time.time()
    with _alarm(600, "train_multi compile per-step"):
        float(tr.step(batch))
    log(f"train_multi[{mk.__name__}] per-step compile: {time.time()-t0:.1f}s")
    with _alarm(300, "train_multi per-step measure"):
        buf = LossBuffer(drain_every=steps + 1)
        t0 = time.time()
        for _ in range(steps):
            buf.append(tr.step(batch))
        buf.drain()
        dt_per = (time.time() - t0) / steps

    # fused horizon: one dispatch per N steps, drain per horizon
    if n is None:
        # measured per-step time is the honest upper bound of the step
        # roofline here (the CPU "tick" IS mostly host overhead); the
        # priced horizon caps at 32 like decode
        n = train_horizon(dt_per)
        n = max(2, min(int(n), 8))
    tr2 = make_trainer()
    horizon = [batch] * n
    t0 = time.time()
    with _alarm(600, "train_multi compile fused"):
        np.asarray(tr2.step_multi(horizon))
    log(f"train_multi[{mk.__name__}] fused N={n} compile: "
        f"{time.time()-t0:.1f}s")
    with _alarm(300, "train_multi fused measure"):
        buf2 = LossBuffer(drain_every=n)      # one real sync per horizon
        t0 = time.time()
        for _ in range(steps // n):
            buf2.append(tr2.step_multi(horizon))
        buf2.drain()
        dt_multi = (time.time() - t0) / (steps // n * n)
    syncs_per_step = buf2.fetches / max(steps // n * n, 1)
    log(f"train_multi[{mk.__name__}]: per-step {dt_per*1e3:.2f} ms/step "
        f"vs fused N={n} {dt_multi*1e3:.2f} ms/step = "
        f"{dt_per/dt_multi:.2f}x ({syncs_per_step:.3f} host syncs/step; "
        f"bs={bs}, seq={seq})")
    return {"steps_per_sec": 1.0 / dt_multi, "model": mk.__name__,
            "multi_step": int(n),
            "host_syncs_per_step": round(syncs_per_step, 4),
            "speedup_vs_per_step": round(dt_per / dt_multi, 3),
            "per_step_ms": round(dt_per * 1e3, 3),
            "fused_step_ms": round(dt_multi * 1e3, 3)}


def run_speculative(batch=4, prompt_len=64, gen=64, k=4):
    """Speculative decode WALL-CLOCK speedup vs plain continuous
    batching, same prompts. Zero-egress means no trained checkpoint
    pair, so agreement is CONSTRUCTED: the target's tail blocks are
    zeroed to residual passthrough (their matmuls still run — full
    target cost) and the draft is the live prefix, so greedy draft ==
    greedy target and acceptance is total. This measures the mechanical
    ceiling at the given target/draft depth ratio; real-model speedup =
    ceiling scaled by the actual agreement rate."""
    import os

    import jax.numpy as jnp
    import numpy as np

    import paddle_tpu as paddle
    from paddle_tpu.distributed import build_mesh
    from paddle_tpu.models import GPT, gpt_350m, gpt_tiny
    from paddle_tpu.serving import (ContinuousBatchingEngine,
                                    PagedGPTDecoder, SpeculativeEngine)

    smoke = bool(os.environ.get("PADDLE_TPU_BENCH_SMOKE")) or \
        _on_cpu_backend()
    mk = gpt_tiny if smoke else gpt_350m
    if smoke:
        batch, prompt_len, gen = 2, 16, 16
    paddle.seed(0)
    build_mesh(dp=1)
    cfg = mk(max_seq_len=max(256, prompt_len + gen + k + 8))
    target = GPT(cfg)
    draft_layers = max(1, cfg.num_layers // 4)
    # tail blocks -> residual passthrough: proj/fc2 zeroed, cost intact
    for block in list(target.blocks)[draft_layers:]:
        for lin in (block.proj, block.fc2):
            lin.weight._value = jnp.zeros_like(lin.weight._value)
            lin.bias._value = jnp.zeros_like(lin.bias._value)
    dcfg = mk(max_seq_len=cfg.max_seq_len)
    dcfg.num_layers = draft_layers
    draft = GPT(dcfg)
    tstate = target.state_dict()
    draft.set_state_dict({k2: tstate[k2] for k2 in
                          draft.state_dict() if k2 in tstate})
    for m in (target, draft):
        if not smoke:
            m.bfloat16()
        m.eval()
    page_size = 16
    pages = (prompt_len + gen + k + page_size - 1) // page_size
    rng = np.random.RandomState(0)
    prompts = [rng.randint(0, cfg.vocab_size, prompt_len).astype(np.int32)
               for _ in range(batch)]

    def make_dec(m):
        return PagedGPTDecoder(m, num_pages=batch * pages + 2,
                               page_size=page_size, max_batch=batch)

    def timed(build):
        eng = build()
        for p in prompts:
            eng.submit(p)
        eng.run()                    # compile
        eng = build()
        for p in prompts:
            eng.submit(p)
        t0 = time.perf_counter()
        out = eng.run()
        return time.perf_counter() - t0, out

    dt_plain, out_plain = timed(
        lambda: ContinuousBatchingEngine(make_dec(target),
                                         max_new_tokens=gen))
    dt_spec, out_spec = timed(
        lambda: SpeculativeEngine(make_dec(target), make_dec(draft),
                                  max_new_tokens=gen, k=k))
    assert out_plain == out_spec, \
        "speculative greedy output diverged from target-only decode"
    speedup = dt_plain / dt_spec
    log(f"speculative[{mk.__name__}] k={k} "
        f"draft={draft_layers}/{cfg.num_layers} layers: "
        f"plain {dt_plain:.2f}s vs spec {dt_spec:.2f}s = "
        f"{speedup:.2f}x wall-clock (full-agreement ceiling)")
    return {"wallclock_speedup": round(speedup, 3), "k": k,
            "model": mk.__name__,
            "draft_layers": draft_layers, "target_layers": cfg.num_layers,
            "mode": "constructed full-agreement ceiling",
            "plain_s": round(dt_plain, 3), "spec_s": round(dt_spec, 3)}


def _on_cpu_backend():
    import jax
    try:
        return jax.devices()[0].platform == "cpu"
    except Exception:
        return True


def _record_failure(extras, key, label, e):
    """Log + record a stage failure, then drop every reference to the
    exception: its traceback pins the failed run's frames (trainer params,
    KV pages) in HBM, which would OOM the next stage's allocation."""
    msg = f"{type(e).__name__}: {str(e)[:300]}"
    log(f"{label} bench failed: {msg}")
    extras[key] = msg[:160]
    # the caller's `except ... as e` binding still exists until its block
    # exits, so `del e` here can't free anything — cut the traceback (and
    # any chained exception's) off the object itself
    e.__traceback__ = None
    if e.__context__ is not None:
        e.__context__.__traceback__ = None
    del e
    import gc
    gc.collect()


def main():
    only = sys.argv[1] if len(sys.argv) > 1 else None

    from paddle_tpu.sysconfig import use_compile_cache
    use_compile_cache()
    # each group: variants of the same headline config, best first by an
    # earlier round's log (bs6/dots > bs4/dots > bs8/full; bs8/dots does
    # not fit the chip: 17.27 G of 15.75 G).  The first variant that runs
    # IS the group's answer.
    groups = [
        [("gpt_1p3b", 6, 1024, "dots"),
         ("gpt_1p3b", 4, 1024, "dots"),
         ("gpt_1p3b", 8, 1024, "full")],
        [("gpt_1p3b", 4, 1024, "full")],
        [("gpt_760m", 8, 1024, "full")],
        [("gpt_350m", 16, 1024, "full")],
        [("gpt_125m", 16, 1024, "full")],
    ]
    # PADDLE_TPU_BENCH_ADVISE=1: let the static remat/microbatch
    # advisor (paddle_tpu.analysis.autotune — host-side tracing only,
    # no device work) reorder the headline group before any compiles.
    # Off by default because the hand ordering above IS measured truth;
    # the advisor is for fresh configs the grid never tried.
    if os.environ.get("PADDLE_TPU_BENCH_ADVISE") == "1":
        try:
            from paddle_tpu.analysis.autotune import rank_gpt_candidates
            seqs = {(n, bs, rp): s for n, bs, s, rp in groups[0]}
            if len(set(seqs.values())) != 1:
                # the probe prices ONE seq; a mixed-seq group would be
                # silently re-priced at the wrong length — keep the
                # measured hand ordering instead
                raise ValueError(
                    f"mixed seq lengths {sorted(set(seqs.values()))}")
            grid = [(n, bs, rp, 1) for n, bs, _s, rp in groups[0]]
            ranked = rank_gpt_candidates(grid, seq=next(iter(seqs.values())),
                                         top=len(grid), log=log)
            groups[0] = [(n, bs, seqs[(n, bs, rp)], rp)
                         for n, bs, rp, _a in ranked]
            log(f"advisor reordered headline group: {groups[0]}")
        except Exception as e:
            log(f"advisor failed ({type(e).__name__}: {str(e)[:160]}); "
                "keeping measured ordering")
    result, last_err = None, None
    if only in (None, "gpt"):
        for group in groups:
            for cfg_name, bs, seq, rp in group:
                try:
                    with _alarm(900, f"{cfg_name} bs{bs}/{rp}"):
                        tok_s, mfu, n_params, static_hbm = run_config(
                            cfg_name, bs, seq, remat_policy=rp)
                except Exception as e:  # OOM → try smaller
                    # keep only the STRING: holding the exception pins its
                    # traceback frames, which pin the failed Trainer's params
                    # and opt state in HBM — every later attempt then OOMs
                    last_err = f"{type(e).__name__}: {str(e)[:200]}"
                    log(f"{cfg_name}/{rp} failed: {last_err}")
                    del e
                    import gc
                    gc.collect()
                    continue
                result = {
                    "metric": f"{cfg_name}_train_tokens_per_sec_per_chip",
                    "value": round(tok_s, 1),
                    "unit": "tokens/s/chip",
                    "vs_baseline": round(mfu / 0.35, 4),
                    "mfu": round(mfu, 4),
                    "params": n_params,
                    "batch": bs, "seq": seq, "remat": rp,
                    "static_peak_hbm_per_device_bytes": static_hbm,
                }
                break               # best-first: first success is the answer
            if result is not None:
                _publish_partial(result)
                break
    if result is None:
        if only in (None, "gpt"):   # real failure of the headline config
            result = _default_result()
            if last_err is not None:
                result["error"] = last_err
        else:                       # gpt intentionally skipped via CLI filter
            result = {"metric": f"bench_only_{only}", "value": 0.0,
                      "unit": "see extras", "vs_baseline": 0.0}
    # secondary BASELINE.json configs ride along in the same JSON line
    _publish_partial(result)
    extras = {}
    result["extras"] = extras  # live reference: hard-exit sees each banked stage
    if only in (None, "resnet"):
        try:
            with _alarm(900, "resnet50"):
                imgs_s, mfu = run_resnet50()
            extras["resnet50_imgs_per_sec_per_chip"] = round(imgs_s, 1)
            extras["resnet50_mfu"] = round(mfu, 4)
        except Exception as e:
            _record_failure(extras, "resnet50_error", "resnet50", e)
    if only in (None, "bert"):
        try:
            with _alarm(900, "bert_base"):
                seqs_s, mfu = run_bert_base()
            extras["bert_base_seqs_per_sec_per_chip"] = round(seqs_s, 2)
            extras["bert_base_mfu"] = round(mfu, 4)
        except Exception as e:
            _record_failure(extras, "bert_base_error", "bert", e)
    if only in (None, "yolo"):
        try:
            with _alarm(900, "yolov3"):
                imgs_s, mfu = run_yolov3()
            extras["yolov3_imgs_per_sec_per_chip"] = round(imgs_s, 1)
            extras["yolov3_mfu"] = round(mfu, 4)
        except Exception as e:
            _record_failure(extras, "yolov3_error", "yolov3", e)
    if only in (None, "yolo", "ocr"):
        try:
            with _alarm(600, "crnn"):
                imgs_s, mfu = run_crnn()
            extras["crnn_imgs_per_sec_per_chip"] = round(imgs_s, 1)
            extras["crnn_mfu"] = round(mfu, 4)
        except Exception as e:
            _record_failure(extras, "crnn_error", "crnn", e)
    if only in (None, "moe"):
        try:
            with _alarm(900, "gpt_moe"):
                tok_s, mfu = run_gpt_moe()
            extras["gpt_moe_tokens_per_sec_per_chip"] = round(tok_s, 1)
            extras["gpt_moe_mfu"] = round(mfu, 4)
        except Exception as e:
            _record_failure(extras, "gpt_moe_error", "moe", e)
    if only in (None, "train_multi"):
        try:
            with _alarm(900, "train_multi"):
                r = run_train_multi()
            extras["train_multi_steps_per_sec"] = round(r["steps_per_sec"], 2)
            extras["train_multi_n"] = r["multi_step"]
            extras["train_multi_speedup"] = r["speedup_vs_per_step"]
            # the multi-step training headline: fused-scan step
            # throughput + how rarely the host interposes
            print(json.dumps({
                "metric": "gpt_train_steps_per_sec",
                "value": round(r["steps_per_sec"], 2),
                "unit": "steps/s/chip",
                "model": r["model"], "multi_step": r["multi_step"],
                "host_syncs_per_step": r["host_syncs_per_step"],
                "speedup_vs_per_step": r["speedup_vs_per_step"]}),
                flush=True)
        except Exception as e:
            _record_failure(extras, "train_multi_error", "train_multi", e)
    if only in (None, "decode"):
        for q in (None, "a8w8", "w4a16"):
            pfx = "decode" + (f"_{q}" if q else "")
            try:
                with _alarm(900, pfx):
                    r = run_decode(quant=q)
                extras[f"{pfx}_tokens_per_sec_per_chip"] = \
                    round(r["tok_s"], 1)
                extras[f"{pfx}_model"] = r["model"]
                extras[f"{pfx}_vs_hbm_roofline"] = r["vs_roofline"]
                extras[f"{pfx}_roofline_tok_s"] = r["roofline_tok_s"]
                extras[f"{pfx}_token_latency_ms"] = r["latency"]
                if q is None:
                    # the multi-step serving headline: fused-engine
                    # decode throughput + how rarely the host interposes
                    print(json.dumps({
                        "metric": "gpt_decode_tokens_per_sec",
                        "value": round(r["tok_s"], 1),
                        "unit": "tokens/s/chip",
                        "model": r["model"], "k_max": r["k_max"],
                        "host_syncs_per_token":
                            round(r["host_syncs_per_token"], 4),
                        "vs_hbm_roofline": r["vs_roofline"]}),
                        flush=True)
            except Exception as e:
                _record_failure(extras, f"{pfx}_error", pfx, e)
        try:
            with _alarm(900, "speculative"):
                extras["speculative"] = run_speculative()
        except Exception as e:
            _record_failure(extras, "speculative_error", "speculative", e)
    if only in (None, "decode", "capacity"):
        try:
            with _alarm(600, "decode_capacity"):
                extras["decode_capacity"] = run_decode_capacity()
        except Exception as e:
            _record_failure(extras, "decode_capacity_error", "capacity", e)
    if only in (None, "decode", "prefix"):
        try:
            with _alarm(600, "prefix_cache"):
                extras["prefix_cache"] = run_prefix_cache()
        except Exception as e:
            _record_failure(extras, "prefix_cache_error", "prefix", e)
        try:
            with _alarm(600, "kv_tier"):
                extras["kv_tier"] = run_kv_tier()
        except Exception as e:
            _record_failure(extras, "kv_tier_error", "kv_tier", e)
    if only in (None, "decode", "fleet"):
        try:
            with _alarm(600, "fleet"):
                extras["fleet"] = run_fleet()
        except Exception as e:
            _record_failure(extras, "fleet_error", "fleet", e)
    if only in (None, "decode", "tenancy"):
        try:
            with _alarm(600, "multi_tenant"):
                extras["multi_tenant"] = run_multi_tenant()
        except Exception as e:
            _record_failure(extras, "multi_tenant_error", "tenancy", e)
    if only in (None, "decode", "ragged"):
        try:
            with _alarm(600, "ragged_stall"):
                extras["ragged_stall"] = run_ragged_stall()
        except Exception as e:
            _record_failure(extras, "ragged_stall_error", "ragged", e)
        try:
            with _alarm(600, "ragged_pad"):
                extras["ragged_pad"] = run_ragged_pad()
        except Exception as e:
            _record_failure(extras, "ragged_pad_error", "ragged", e)
    if not extras:
        result.pop("extras", None)
    print(json.dumps(result))


if __name__ == "__main__":
    main()
