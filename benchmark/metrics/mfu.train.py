"""The whole step's share of the chip's peak: operations the forward and
backward need for a token (flops/<family>.py: matrix products and attention,
causal counted once, nothing recomputed) times tokens a second, over the
peak of the chips used."""


def read(run):
    m = run.measured
    if not m.get("train_seconds"):
        return None
    rate = m["train_tokens"] / m["train_seconds"]
    return 100.0 * rate * m["flops_per_token"] / (
        run.peaks["bf16_flops"] * run.cell.chips)
