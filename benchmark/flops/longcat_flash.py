"""Operations of the LongCat-Flash decoder as one chip holds it, from the
configuration's sizes alone, and the same whatever form the program takes:
attention is counted in its materialised form (the absorbed form does more
operations a key and none to build keys; a program that uses it is not
credited for them), each attention's key/value up-projection once a token,
the routed experts at the expectation of an even router, an identity
expert nothing."""


def expert_layers(cfg):
    """Every (double) layer has one expert branch."""
    return cfg["num_layers"]


def params_per_token(cfg):
    """Parameters one token passes in the matrix products of all layers
    (norms and the head left out): in each layer two MLAs with W_kvb once,
    two dense MLPs, the router's whole width, and `moe_topk` x held /
    router_width routed experts: of the `moe_topk` columns an even router
    selects, the share that are experts held here (the rest are other
    chips' experts and identity experts, which cost nothing)."""
    h, heads = cfg["hidden_size"], cfg["num_attention_heads"]
    dn, dr, dv = (cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"],
                  cfg["v_head_dim"])
    rq, rkv = cfg["q_lora_rank"], cfg["kv_lora_rank"]
    mla = (h * rq + rq * heads * (dn + dr) + h * (rkv + dr)
           + rkv * heads * (dn + dv) + heads * dv * h)
    dense = 3 * h * cfg["ffn_hidden_size"]
    routed = cfg["moe_topk"] * cfg["n_routed_experts"] / cfg["router_width"]
    moe = (h * cfg["router_width"]
           + routed * 3 * h * cfg["expert_ffn_hidden_size"])
    return cfg["num_layers"] * (2 * mla + 2 * dense + moe)


def attention_flops_per_key(cfg):
    """One query over one key in all heads of ONE attention, materialised:
    the score (nope + rope wide) and the weighted value."""
    return 2.0 * cfg["num_attention_heads"] * (
        cfg["qk_nope_head_dim"] + cfg["qk_rope_head_dim"]
        + cfg["v_head_dim"])


def serve_flops(cfg, prompt_len, generated):
    """Operations to process a prompt and generate `generated` tokens after
    it: two a parameter for every processed token, both attentions of every
    layer over the context live at each position, the head's columns held
    here at the positions that emit."""
    processed = prompt_len + generated - 1       # the last token is not fed
    weights = processed * 2.0 * params_per_token(cfg)
    # token at position i attends over i + 1 keys, in 2 attentions a layer
    attn = (2 * cfg["num_layers"] * attention_flops_per_key(cfg)
            * processed * (processed + 1) / 2)
    head = generated * 2.0 * cfg["hidden_size"] * cfg["vocab_size"]
    return weights + attn + head
