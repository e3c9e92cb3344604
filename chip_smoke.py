#!/usr/bin/env python3
"""The quickest proof that paddle_tpu still starts on the chip.

    python chip_smoke.py --seed 0            # one TPU: train, then serve
    python chip_smoke.py --seed 0 --chips 4  # four TPUs: sharded training only

One process and no children. GPT-1.3B at its published width and depth,
random weights from `--seed`, through the entry points a user calls:
`Trainer.step` for six steps, the trained weights kept on the host as a
checkpoint, then `PagedGPTDecoder` behind a `ContinuousBatchingEngine` for
eight requests, each checked against the plain `GPT` forward. One JSON line
per phase, and a last line

    {"ok": true, "device": {"platform": "tpu", "kind": "...", "count": 1}}

`ok` is true only on a TPU that `cost_model.chip_spec` knows, with every
gate passed; anything else exits non-zero. `--tiny` swaps in `gpt_tiny`
sizes so the same functions and gates can be rehearsed under
`JAX_PLATFORMS=cpu`, where the run must fail at the device check and
nowhere earlier. The numbers in the phase lines are observations of one
run, not a benchmark.
"""
import argparse
import gc
import json
import math
import statistics
import sys
import time

# how far a generated token's logit may lie under the maximum of its row in
# the reference, as a share of that row's (maximum - mean). At this init a
# row's spread is about 0.9 and its maximum sits near +3.6; a token picked
# for any wrong reason lies about a whole (maximum - mean) below it. Two bf16
# computations of one row (the decoder's paged attention and the GPT
# forward's flash attention round differently through 24 layers) differ by a
# percent or two of the logits' size, so the decoder's pick may be the
# reference's runner-up but never lies this far under its maximum
LOGIT_TOL = 0.15
# two bf16 runs of the same batch on different shardings reduce in different
# orders; the loss is a mean over thousands of fp32 rows
LOSS_TOL = 0.05
TRAIN_STEPS = 6
SHARDED_BATCH, SHARDED_STEPS = 4, 2


def emit(obj):
    print(json.dumps(obj), flush=True)


def sizes(tiny):
    if tiny:
        return dict(model="gpt_tiny", seq=128, batch=2, requests=4,
                    max_new=8, prompt=(8, 48), slots=4)
    return dict(model="gpt_1p3b", seq=1024, batch=8, requests=8,
                max_new=32, prompt=(32, 512), slots=8)


def build_model(sz, seed):
    import paddle_tpu as paddle
    from paddle_tpu.models import GPT, gpt

    cfg = getattr(gpt, sz["model"])(max_seq_len=sz["seq"],
                                    remat_policy="full")
    paddle.seed(seed)
    model = GPT(cfg)
    model.bfloat16()
    return model


def load_model(sz, seed, checkpoint):
    """A fresh model holding the trained weights (host arrays by name)."""
    model = build_model(sz, seed)
    missing, unexpected = model.set_state_dict(checkpoint)
    if missing or unexpected:
        raise RuntimeError(f"checkpoint does not fit the model: missing "
                           f"{missing}, unexpected {unexpected}")
    model.eval()
    return model


def build_trainer(model, mesh=None):
    import paddle_tpu as paddle
    from paddle_tpu.distributed.trainer import Trainer
    from paddle_tpu.models import GPTPretrainingCriterion

    crit = GPTPretrainingCriterion()
    opt = paddle.optimizer.AdamW(
        learning_rate=2e-4, weight_decay=0.1,
        grad_clip=paddle.nn.ClipGradByGlobalNorm(1.0),
        accumulator_dtype="bfloat16")

    def loss_fn(m, batch):
        logits = m(paddle.to_tensor(batch["input_ids"]))
        return crit(logits, paddle.to_tensor(batch["labels"]))

    return Trainer(model, opt, loss_fn, mesh=mesh)


def token_batch(model, batch, seq, seed):
    import numpy as np

    ids = np.random.RandomState(seed).randint(
        0, model.cfg.vocab_size, (batch, seq + 1))
    return {"input_ids": ids[:, :-1].astype("int32"),
            "labels": ids[:, 1:].astype("int32")}


def timed_steps(trainer, batch, n):
    """n steps on one batch: (losses, seconds of each step, fetch included)."""
    losses, secs = [], []
    for _ in range(n):
        t0 = time.perf_counter()
        losses.append(float(trainer.step(batch)))
        secs.append(time.perf_counter() - t0)
    return losses, secs


def device_bytes(dev):
    stats = dev.memory_stats() or {}
    return {k: stats[k] for k in ("bytes_in_use", "peak_bytes_in_use")
            if k in stats}


def finite(xs):
    return all(math.isfinite(x) for x in xs)


def phase_train(sz, seed, dev):
    """Six steps; returns the gates and the trained weights on the host."""
    import numpy as np

    model = build_model(sz, seed)
    cfg = model.cfg
    trainer = build_trainer(model)
    batch = token_batch(model, sz["batch"], sz["seq"], seed)
    losses, secs = timed_steps(trainer, batch, TRAIN_STEPS)
    # the program step() dispatched, compiled again (from the cache when the
    # backend keeps one) for its text and its memory
    compiled = trainer.lower_step(batch).compile()
    pallas_calls = compiled.as_text().count("tpu_custom_call")
    mem = compiled.memory_analysis()
    trainer.sync_to_model()
    checkpoint = {name: np.asarray(t._value)
                  for name, t in model.state_dict().items()}
    del trainer, model
    gc.collect()
    gates = {
        "losses_finite": finite(losses),
        "loss_fell": losses[-1] < losses[0],
        # a CPU rehearsal has no Mosaic kernels to count; it fails at the
        # device check instead
        "pallas_calls": pallas_calls > 0 or dev.platform != "tpu",
    }
    emit({"phase": "train", "model": sz["model"],
          "layers": cfg.num_layers, "hidden": cfg.hidden_size,
          "batch": sz["batch"], "seq": sz["seq"], "remat_policy": "full",
          "steps": len(losses), "losses": losses,
          "pallas_calls": pallas_calls,
          "first_step_s": secs[0],
          "compile_s": secs[0] - statistics.median(secs[1:]),
          "step_ms_median": 1e3 * statistics.median(secs[1:]),
          "step_program_bytes": {
              "arguments": mem.argument_size_in_bytes,
              "temporaries": mem.temp_size_in_bytes,
              "outputs": mem.output_size_in_bytes,
              "aliased": mem.alias_size_in_bytes} if mem else None,
          "device_bytes": device_bytes(dev),
          "device": device_dict(), "gates": gates})
    return gates, checkpoint


def serve_once(decoder, prompts, max_new):
    from paddle_tpu.serving.engine import ContinuousBatchingEngine

    engine = ContinuousBatchingEngine(decoder, max_new_tokens=max_new)
    rids = [engine.submit(p) for p in prompts]
    horizon_s = []
    t0 = time.perf_counter()
    out = engine.run(step_times=horizon_s)
    return ([list(map(int, out[r])) for r in rids],
            time.perf_counter() - t0, horizon_s, engine.stats.summary())


def reference_margins(model, prompts, streams, seq):
    """For every generated token, how far its logit lies under the maximum
    of its position in the plain GPT forward over prompt + generated: the
    worst gap, and the worst gap as a share of its row's (maximum - mean)."""
    import numpy as np

    import jax

    import paddle_tpu as paddle
    from paddle_tpu.nn import functional_call, state_pytree

    forward = jax.jit(lambda params, ids: functional_call(
        model, params, paddle.Tensor(ids))._value)
    params = state_pytree(model)
    worst = worst_share = 0.0
    for prompt, stream in zip(prompts, streams):
        ids = np.zeros((1, seq), np.int32)
        n, g = len(prompt), len(stream)
        ids[0, :n] = prompt
        ids[0, n:n + g] = stream
        logits = np.asarray(forward(params, ids)[0], np.float32)
        rows = logits[n - 1:n + g - 1]        # row i predicts token i + 1
        top = rows.max(axis=-1)
        gap = top - rows[np.arange(g), stream]
        worst = max(worst, float(gap.max()))
        worst_share = max(worst_share,
                          float((gap / (top - rows.mean(axis=-1))).max()))
    return worst, worst_share


def phase_serve(checkpoint, sz, seed, dev):
    """Serve from the checkpoint, then check the streams against the plain
    model. The decoder keeps its own stacked copy of the weights, so the
    Layer is dropped while it serves and loaded again for the reference:
    the largest horizon's program needs 12 GiB of the chip's 16."""
    import numpy as np

    from paddle_tpu.serving.decoder import PagedGPTDecoder

    model = load_model(sz, seed, checkpoint)
    cfg = model.cfg
    page = 16
    pages_per_seq = sz["seq"] // page
    decoder = PagedGPTDecoder(model, num_pages=sz["slots"] * pages_per_seq + 2,
                              page_size=page, max_batch=sz["slots"])
    del model
    gc.collect()
    rng = np.random.RandomState(seed + 1)
    lo, hi = sz["prompt"]
    lengths = rng.randint(lo, hi + 1, sz["requests"])
    lengths[0], lengths[-1] = lo, hi
    vocab = cfg.vocab_size
    prompts = [rng.randint(0, vocab, int(n)).tolist() for n in lengths]

    streams, cold_s, _, _ = serve_once(decoder, prompts, sz["max_new"])
    again, warm_s, horizon_s, stats = serve_once(decoder, prompts,
                                                 sz["max_new"])
    alone, _, _, _ = serve_once(decoder, prompts[:1], sz["max_new"])
    pool_pages = decoder.num_pages
    serving_bytes = device_bytes(dev)
    del decoder
    gc.collect()
    worst, worst_share = reference_margins(
        load_model(sz, seed, checkpoint), prompts, streams, sz["seq"])
    gates = {
        "all_finished": all(len(s) == sz["max_new"] for s in streams),
        "tokens_in_vocab": all(0 <= t < vocab for s in streams for t in s),
        "repeat_identical": again == streams,
        "reference_margin": worst_share <= LOGIT_TOL,
    }
    emit({"phase": "serve", "model": sz["model"],
          "layers": cfg.num_layers, "hidden": cfg.hidden_size,
          "slots": sz["slots"], "pool_pages": pool_pages,
          "page_size": page, "requests": len(prompts),
          "prompt_tokens": [int(n) for n in lengths],
          "generated_tokens": [len(s) for s in streams],
          "worst_logit_margin": worst,
          "worst_logit_margin_share": worst_share,
          "logit_tolerance_share": LOGIT_TOL,
          "alone_equals_batched": alone[0] == streams[0],
          "cold_run_s": cold_s, "warm_run_s": warm_s,
          "compile_s": cold_s - warm_s,
          "horizon_ms_median": 1e3 * statistics.median(horizon_s),
          "engine": stats, "device_bytes": serving_bytes,
          "device": device_dict(), "gates": gates})
    return gates


def placement(tree):
    """Bytes each device holds of `tree`, and whether every leaf that is
    split at all has a shard on every device."""
    import jax

    held, spread = {}, True
    for leaf in jax.tree_util.tree_leaves(tree):
        shards = leaf.addressable_shards
        for s in shards:
            held[s.device.id] = held.get(s.device.id, 0) + s.data.nbytes
        if not leaf.sharding.is_fully_replicated:
            spread &= len({s.device.id for s in shards}) == len(shards) \
                == len(leaf.sharding.device_set)
    return held, spread


def phase_sharded(sz, seed, dev):
    """Two steps on fsdp=2 x tp=2 over every device, then the same seed and
    batch on a mesh of the first device alone."""
    import jax

    from paddle_tpu.distributed import build_mesh

    runs = {}
    for name, kw in (("fsdp2_tp2", dict(fsdp=2, tp=2)),
                     ("one_device", dict(devices=jax.devices()[:1]))):
        mesh = build_mesh(**kw)
        model = build_model(sz, seed)
        trainer = build_trainer(model, mesh)
        batch = token_batch(model, SHARDED_BATCH, sz["seq"], seed)
        losses, secs = timed_steps(trainer, batch, SHARDED_STEPS)
        state = (trainer.params, trainer.opt_state)
        held, spread = placement(state)
        split = sum(not leaf.sharding.is_fully_replicated
                    for leaf in jax.tree_util.tree_leaves(state))
        runs[name] = dict(losses=losses, step_s=secs, held=held,
                          spread=spread, split_leaves=split)
        del trainer, model, state
        gc.collect()

    sharded, single = runs["fsdp2_tp2"], runs["one_device"]
    total = sum(single["held"].values())
    shares = {str(d): b / total for d, b in sorted(sharded["held"].items())}
    gates = {
        "losses_finite": finite(sharded["losses"] + single["losses"]),
        "losses_agree": all(abs(a - b) <= LOSS_TOL for a, b in
                            zip(sharded["losses"], single["losses"])),
        "four_devices_hold_state": len(shares) == 4,
        "sharded_leaves_on_every_device":
            sharded["spread"] and sharded["split_leaves"] > 0,
        # a quarter each, but for the few small leaves that stay replicated
        "near_equal_shares": max(shares.values()) - min(shares.values())
            < 0.02 and max(shares.values()) < 0.35,
    }
    emit({"phase": "sharded_train", "model": sz["model"],
          "mesh": {"fsdp": 2, "tp": 2}, "batch": SHARDED_BATCH,
          "seq": sz["seq"], "steps": SHARDED_STEPS,
          "losses": sharded["losses"], "losses_one_device": single["losses"],
          "loss_tolerance": LOSS_TOL,
          "split_leaves": sharded["split_leaves"],
          "state_bytes_one_device": total,
          "state_share_by_device": shares,
          "step_s": sharded["step_s"], "step_s_one_device": single["step_s"],
          "device_bytes": device_bytes(dev),
          "device": device_dict(), "gates": gates})
    return gates


def device_dict():
    import jax

    d = jax.devices()[0]
    return {"platform": d.platform, "kind": d.device_kind,
            "count": len(jax.devices())}


def main():
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--tiny", action="store_true",
                    help="gpt_tiny sizes: the CPU rehearsal")
    ap.add_argument("--chips", type=int, default=1, choices=(1, 4),
                    help="4: only the sharded phase and its comparison")
    args = ap.parse_args()

    from paddle_tpu.sysconfig import use_compile_cache
    use_compile_cache()
    import jax

    dev = jax.devices()[0]
    device = device_dict()
    emit({"phase": "device", **device})

    def fail(reason):
        emit({"ok": False, "reason": reason, "device": device})
        return 1

    if dev.platform != "tpu" and not args.tiny:
        return fail(f"no TPU: JAX's platform is {dev.platform!r}")
    if device["count"] != args.chips:
        return fail(f"--chips {args.chips} but JAX sees "
                    f"{device['count']} device(s)")

    sz = sizes(args.tiny)
    try:
        if dev.platform != "cpu":
            from paddle_tpu.cost_model import chip_spec
            chip_spec(dev.device_kind)    # raises on a kind it cannot price
        if args.chips == 4:
            gates = phase_sharded(sz, args.seed, dev)
        else:
            train, checkpoint = phase_train(sz, args.seed, dev)
            serve = phase_serve(checkpoint, sz, args.seed, dev)
            gates = {f"train.{k}": v for k, v in train.items()}
            gates.update({f"serve.{k}": v for k, v in serve.items()})
    except BaseException as e:
        fail(f"{type(e).__name__}: {e}")
        raise

    failed = sorted(k for k, v in gates.items() if not v)
    if failed:
        return fail("gates failed: " + ", ".join(failed))
    if dev.platform != "tpu":
        return fail(f"no TPU: JAX's platform is {dev.platform!r}")
    emit({"ok": True, "device": device})
    return 0


if __name__ == "__main__":
    sys.exit(main())
