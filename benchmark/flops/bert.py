"""Operations of the BERT encoder with its pretraining heads, from the
configuration's sizes."""
from .common import train_kernel_calls


def forward_flops_per_token(cfg, keys, head_share=1.0):
    """One forward pass for one token that is not padding and attends over
    `keys` positions that are not padding (attention is full, not causal).
    The MLM head (transform and decoder) counts for `head_share` of the
    tokens: only a labelled position needs it."""
    h, f = cfg["hidden_size"], cfg["intermediate_size"]
    per_layer = 2 * (4 * h * h + 2 * h * f) + 4 * keys * h
    head = 2 * h * h + 2 * h * cfg["vocab_size"]      # transform, decoder
    return cfg["num_hidden_layers"] * per_layer + head_share * head


def train_flops_per_token(cfg, traffic):
    """Forward and backward (twice the forward) that a step needs, for one
    token that is not padding: padded positions are no work, whatever the
    program computes at them; a token attends over its own row's tokens
    (the mean over the step's tokens of their rows' lengths); the MLM head
    counts at the labelled positions (`mask_share` of the tokens, and each
    row's first)."""
    lengths = traffic.get("lengths") or [traffic["seq"]] * traffic["batch"]
    real = sum(lengths)
    keys = sum(n * n for n in lengths) / real
    share = traffic["mask_share"]
    labelled = share * real + (1 - share) * len(lengths)
    return 3.0 * forward_flops_per_token(cfg, keys, labelled / real)


def kernel_calls(cfg, traffic):
    return train_kernel_calls(cfg, traffic, causal=False)
