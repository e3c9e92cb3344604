"""A training cell: `Trainer.step` on a fresh seeded batch every step.

Set-up builds ONE trainer, drives it through its first steps by the
window's own call and feed (their losses, the first gradient and the
parameters' change are what `correct` compares), and hands that same
trainer to the window. The reference runs once the window has closed, the
peak has been read and the trainer is freed.
"""
import gc
import time

import numpy as np

from .. import compare, generate
from ..reference import train as ref_train

IN_FLIGHT = 2      # steps dispatched ahead of the one waited for


def _first_steps(trainer, batches, cell, seed, step):
    """The program's side of the comparison, read from the trainer's own
    state between its first steps."""
    fam, cfg = cell.family, cell.config
    names = fam.leaf_names(cfg)
    b1 = cell.job["optimizer"]["beta1"]
    parts = fam.reference.leaf_parts
    losses, grad_norms = [], None
    for batch in batches:
        losses.append(float(step(trainer, batch)))
        if grad_norms is None:
            # Adam's first moment after one step is (1 - beta1) * the
            # gradient the optimizer was given (clipped)
            slots = trainer.opt_state["slots"]
            m = ref_train.part_norms(
                {k: slots[p]["moment1"] for k, p in names.items()}, parts)
            grad_norms = {k: v / (1 - b1) for k, v in m.items()}
    # the parameters as the steps left them go to the host: their change is
    # measured once the window has closed (`with_change`), so that no second
    # copy of the model lies on the chip beside the trainer's state
    return {"losses": losses, "grad_norms": grad_norms,
            "after": {k: np.asarray(trainer.params[p])
                      for k, p in names.items()}}


def with_change(program, cell, seed):
    """`program` with the norm of each leaf's change since the weights the
    seed made, in place of the parameters kept for it."""
    ref = cell.family.reference
    start = ref.init_params(cell.config, seed)
    program["change_norms"] = ref_train.change_norms(
        program.pop("after"), start, ref.leaf_parts)
    return program


def default_step(trainer, batch):
    return trainer.step(batch)


def run(ctx):
    """ctx: the harness's Run (cell, seed, seconds, trace, clock, spans)."""
    import jax

    cell, seed = ctx.cell, ctx.seed
    fam, cfg, job, traffic = cell.family, cell.config, cell.job, cell.traffic
    step = ctx.faults.get("step", default_step)

    model = fam.build_model(cfg, seed, job)
    ctx.mark("model_built")
    trainer = fam.build_trainer(model, cfg, job)
    del model
    ctx.mark("trainer_built")
    batches = generate.of(traffic)(traffic, cfg, seed)
    checked = [next(batches) for _ in range(job["checked_steps"])]
    program = _first_steps(trainer, checked, cell, seed, step)
    ctx.mark("first_steps_read")
    # one more step through the window's loop shape, so that nothing is
    # first-time inside the window
    jax.block_until_ready(step(trainer, next(batches)))
    ctx.setup_done()

    tokens, ends, pending = 0, [], []
    with ctx.window() as w:
        t0 = time.perf_counter()
        while True:
            with ctx.span("batch_made"):
                batch = next(batches)
            with ctx.span("step_dispatched"):
                pending.append((step(trainer, batch),
                                fam.reference.tokens_in(batch)))
            if len(pending) >= IN_FLIGHT:
                with ctx.span("step_waited"):
                    loss, n = pending.pop(0)
                    jax.block_until_ready(loss)
                ends.append(time.perf_counter())
                tokens += n
                if ends[-1] - t0 >= w.seconds:
                    break
        with ctx.span("loss_fetched"):
            for loss, n in pending:
                jax.block_until_ready(loss)
                ends.append(time.perf_counter())
                tokens += n
            last_loss = float(loss)
        elapsed = time.perf_counter() - t0
    steps = len(ends)
    ctx.attempted, ctx.failed = steps, 0 if np.isfinite(last_loss) else steps
    ctx.measured.update(
        train_tokens=tokens, train_seconds=elapsed, steps=steps,
        step_ends=ends, window_t0=t0, last_loss=last_loss,
        flops_per_token=fam.flops.train_flops_per_token(cfg, traffic),
        kernel_calls=fam.flops.kernel_calls(cfg, traffic))
    ctx.read_memory_peak()
    ctx.e2e["train_tokens_per_s"] = tokens / elapsed
    ctx.mark("window_closed")

    trainer.params = trainer.opt_state = trainer.consts = None
    del trainer, pending, loss
    gc.collect()
    program = with_change(program, cell, seed)
    reference = ref_train.follow(
        fam.reference, cfg, job["optimizer"], seed, checked,
        row_block=job["reference_row_block"])
    ctx.judge(compare.train_numbers(program, reference))
    ctx.mark("compared")
