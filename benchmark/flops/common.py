"""Operations and bytes the algorithms need, from shapes alone. Shares of a
peak and of a roofline are computed from these and from nothing the program
says about itself.

Conventions: a multiply-add is two operations; causal attention is counted
once (half the square); nothing recomputed counts towards MFU. A kernel's
roofline counts every call the trace shows, a recomputed forward included,
because the chip did that work.
"""


def flash_call(kernel, batch, heads, seq_q, seq_k, head_dim, causal,
               bytes_per=2):
    """(operations, bytes) of ONE call of a flash-attention kernel.
    fwd: S = QK^T and PV (2 products); bwd_dq: S, dP = dO V^T, dQ = dS K
    (3); bwd_dkv: S, dP, dV = P^T dO, dK = dS^T Q (4). Bytes: each of the
    [batch, seq, heads, head_dim] operands read or written once."""
    products = {"fwd": 2, "bwd_dq": 3, "bwd_dkv": 4}[kernel]
    square = batch * heads * seq_q * seq_k * (0.5 if causal else 1.0)
    ops = 2.0 * products * square * head_dim
    q = batch * heads * seq_q * head_dim * bytes_per
    kv = batch * heads * seq_k * head_dim * bytes_per
    tensors = {"fwd": 2 * q + 2 * kv,             # q, o; k, v
               "bwd_dq": 3 * q + 2 * kv,          # q, do, dq; k, v
               "bwd_dkv": 2 * q + 4 * kv}[kernel]  # q, do; k, v, dk, dv
    return ops, float(tensors)


def xent_call(kernel, rows, vocab, logit_bytes=4):
    """(operations, bytes) of ONE call of the softmax-cross-entropy pair
    over float32 logits [rows, vocab]: the forward reads them once, the
    backward reads them and writes their gradient. About 4 operations an
    element (subtract, exponential, add, scale); bytes bound it."""
    passes = {"fwd": 1, "bwd": 2}[kernel]
    return 4.0 * rows * vocab, float(passes * rows * vocab * logit_bytes)


def roofline_seconds(ops, nbytes, peak):
    """Least time the chip could take: the larger of operations over the
    peak rate and bytes over the peak bandwidth."""
    return max(ops / peak["bf16_flops"], nbytes / peak["hbm_bytes_per_s"])


def _t(dtype, *dims):
    return dtype + r"\[" + ",".join(str(d) for d in dims) + r"\]"


def flash_signature(kernel, batch, heads, seq, head_dim, dtype="bf16"):
    """Regular expression that tells a flash-attention kernel's call in a
    TPU trace, where an operation's name is its HLO instruction with the
    layouts taken out. A Pallas call carries no name there, so it is told
    by its types: the forward gives (o, lse) from q, k, v; dq gives one
    [batch, heads, seq, head_dim]; dkv gives two."""
    x = _t(dtype, batch, heads, seq, head_dim)
    lse = _t("f32", batch, heads, seq, 1)
    return {"fwd": rf"= \({x}, {lse}\) custom-call\({x}",
            "bwd_dq": rf"= {x} custom-call\({x}.*{lse}",
            "bwd_dkv": rf"= \({x}, {x}\) custom-call\({x}.*{lse}"}[kernel]


def xent_signature(kernel, rows, vocab):
    """The same for the cross-entropy pair over float32 logits: the forward
    gives (loss, lse) by row, the backward the logits' gradient."""
    logits, col = _t("f32", rows, vocab), _t("f32", rows, 1)
    return {"fwd": rf"= \({col}, {col}\) custom-call\({logits}",
            "bwd": rf"= {logits} custom-call\({logits}"}[kernel]


def train_kernel_calls(cfg, traffic, causal):
    """{kernel: (operations, bytes, signature in the trace)} of one call of
    each kernel of a training step at this traffic's batch and sequence."""
    b, s = traffic["batch"], traffic["seq"]
    heads = cfg["num_attention_heads"]
    d = cfg["hidden_size"] // heads
    rows, vocab = b * s, cfg["vocab_size"]
    out = {}
    for k in ("fwd", "bwd_dq", "bwd_dkv"):
        out[f"_{k}_kernel"] = flash_call(k, b, heads, s, s, d, causal) + (
            flash_signature(k, b, heads, s, d),)
    for k in ("fwd", "bwd"):
        out[f"_xent_{k}_kernel"] = xent_call(k, rows, vocab) + (
            xent_signature(k, rows, vocab),)
    return out
