"""Operations and bytes of the LFM2-MoE stage as one chip holds it, from the
configuration's sizes alone, and the same whatever form the program takes:
attention over the live context in its plain causal form, the short conv's
taps as three multiply-adds a channel, the routed experts at the
expectation of an even router."""


def _stage(cfg):
    return [(kind == "full_attention", i < cfg["num_dense_layers"])
            for i, kind in enumerate(cfg["stage_layer_types"])]


def expert_layers(cfg):
    return sum(1 for _, dense in _stage(cfg) if not dense)


def attention_layers(cfg):
    return sum(1 for attn, _ in _stage(cfg) if attn)


def operator_params(cfg, attn):
    """Weights of one layer's operator that a token passes in products:
    q, k, v and out of an attention; in, out and the kernel's taps of a
    conv."""
    h, heads, kv = (cfg["hidden_size"], cfg["num_attention_heads"],
                    cfg["num_key_value_heads"])
    d = h // heads
    if attn:
        return 2 * h * heads * d + 2 * h * kv * d
    return 4 * h * h + h * cfg["conv_L_cache"]


def expert_params(cfg):
    """One expert's three matrices."""
    return 3 * cfg["hidden_size"] * cfg["moe_intermediate_size"]


def params_per_token(cfg):
    """Parameters one token passes in the products of all layers (norms
    and the head left out): each layer's operator, a dense MLP or the
    router's whole width and `num_experts_per_tok` x held / num_experts
    experts (what an even router sends to the experts held here)."""
    h = cfg["hidden_size"]
    routed = (cfg["num_experts_per_tok"] * cfg["n_routed_experts"]
              / cfg["num_experts"])
    n = 0
    for attn, dense in _stage(cfg):
        n += operator_params(cfg, attn)
        n += (3 * h * cfg["intermediate_size"] if dense
              else h * cfg["num_experts"] + routed * expert_params(cfg))
    return n


def attention_flops_per_key(cfg):
    """One query over one key in all heads of ONE attention layer: the
    score and the weighted value."""
    return 2.0 * cfg["hidden_size"] * 2


def serve_flops(cfg, prompt_len, generated):
    """Operations to process a prompt and generate `generated` tokens after
    it: two a parameter for every processed token, every attention layer
    over the context live at each position, the head at the positions that
    emit."""
    processed = prompt_len + generated - 1       # the last token is not fed
    weights = processed * 2.0 * params_per_token(cfg)
    attn = (attention_layers(cfg) * attention_flops_per_key(cfg)
            * processed * (processed + 1) / 2)
    head = generated * 2.0 * cfg["hidden_size"] * cfg["vocab_size"]
    return weights + attn + head


def decode_tick_weight_bytes(cfg, bytes_per=2):
    """Weight bytes one decode tick reads once whatever its rows route to:
    every operator and its norms, the dense MLPs, the routers and their
    biases, the final norm and the head (the embedding's rows are
    gathered, not read whole). The experts are counted by the tick's
    `experts_hit` (`expert_bytes`)."""
    h, heads = cfg["hidden_size"], cfg["num_attention_heads"]
    d = h // heads
    n = h + h * cfg["vocab_size"]                # final norm, head
    for attn, dense in _stage(cfg):
        n += 2 * h + operator_params(cfg, attn) + (2 * d if attn else 0)
        n += (3 * h * cfg["intermediate_size"] if dense
              else h * cfg["num_experts"])
    routers = expert_layers(cfg) * cfg["num_experts"] * 4   # float32 biases
    return n * bytes_per + routers


def expert_bytes(cfg, bytes_per=2):
    return expert_params(cfg) * bytes_per
