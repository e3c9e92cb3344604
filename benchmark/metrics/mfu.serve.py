"""The serving step's share of the chip's peak: two operations a parameter
for every prompt and generated token processed, plus attention over the live
context (flops/<family>.py), over the window, over the peak."""


def read(run):
    m = run.measured
    if not m.get("serve_seconds"):
        return None
    return 100.0 * m["serve_flops"] / m["serve_seconds"] / (
        run.peaks["bf16_flops"] * run.cell.chips)
