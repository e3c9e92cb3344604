"""What the families share: the optimizer a cell's file states, built as a
user of the program would build it."""


def build_adamw(job):
    import paddle_tpu as paddle

    o = job["optimizer"]
    return paddle.optimizer.AdamW(
        learning_rate=o["learning_rate"], beta1=o["beta1"], beta2=o["beta2"],
        epsilon=o["epsilon"], weight_decay=o["weight_decay"],
        grad_clip=paddle.nn.ClipGradByGlobalNorm(o["clip_global_norm"]),
        accumulator_dtype=None if o["moment_dtype"] == "float32"
        else o["moment_dtype"])


def load_weights(model, weights, family):
    """Hand the Layer the weights the benchmark made; the names must be the
    reference's."""
    from paddle_tpu.nn.layer_base import load_state_pytree

    names = {n for n, _ in model.named_parameters()}
    if names != set(weights):
        raise RuntimeError(
            f"the program's {family} and the reference name different "
            f"leaves: {sorted(names ^ set(weights))}")
    load_state_pytree(model, weights)
