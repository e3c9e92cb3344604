"""Operations of the GPT-2/3 decoder, from the configuration's sizes."""
from .common import train_kernel_calls


def forward_flops_per_token(cfg, context, causal_half=True):
    """Matrix products and attention of one forward pass for one token that
    attends over `context` positions (causal counted once when
    `causal_half`: the mean over a sequence of that length)."""
    h, f = cfg["hidden_size"], cfg["intermediate_size"]
    per_layer = 2 * (4 * h * h + 2 * h * f)          # qkv, proj, fc1, fc2
    attn = 4 * context * h * (0.5 if causal_half else 1.0)   # QK^T, PV
    head = 2 * h * cfg["vocab_size"]
    return cfg["num_hidden_layers"] * (per_layer + attn) + head


def train_flops_per_token(cfg, traffic):
    """Forward and backward (twice the forward) for a token of a sequence
    of `seq` tokens; nothing recomputed."""
    return 3.0 * forward_flops_per_token(cfg, traffic["seq"])


def serve_flops(cfg, prompt_len, generated):
    """Operations to process a prompt and generate `generated` tokens after
    it: every processed token passes all weights once (the head only for
    positions that emit) and attends over the context live at its
    position."""
    h, f = cfg["hidden_size"], cfg["intermediate_size"]
    n = cfg["num_hidden_layers"]
    processed = prompt_len + generated - 1       # the last token is not fed
    weights = processed * n * 2 * (4 * h * h + 2 * h * f)
    # token at position i attends over i + 1 keys
    attn = n * 4 * h * processed * (processed + 1) / 2
    head = generated * 2 * h * cfg["vocab_size"]
    return weights + attn + head


def kernel_calls(cfg, traffic):
    return train_kernel_calls(cfg, traffic, causal=True)
