"""The faults a timed path can have, planted in the feed or around the
program's own call. The tests drive the harness with each and see `correct`
come out false; `rehearse/readings.py` reads them on the chip at the cell's own
size, where a limit's upper reading needs them."""


def half_batch_left_out(trainer, batch):
    """Half of the batch left out, the mean taken over the rest."""
    b = dict(batch)
    labels = b["labels"].copy()
    labels[len(labels) // 2:] = -100
    b["labels"] = labels
    return trainer.step(b)


def state_unchanged(trainer, batch):
    """A step that returns its state as it was (copied first: the step
    donates its buffers)."""
    import jax
    import jax.numpy as jnp

    kept = jax.tree_util.tree_map(
        jnp.copy, (trainer.params, trainer.opt_state))
    loss = trainer.step(batch)
    trainer.params, trainer.opt_state = kept
    return loss


def token_altered(tokens):
    """One served token altered where it is produced."""
    out = list(tokens)
    out[len(out) // 2] += 1
    return out
