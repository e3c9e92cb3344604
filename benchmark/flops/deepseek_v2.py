"""Operations of the DeepSeek-V2 decoder as one chip holds it, from the
configuration's sizes alone, and the same whatever form the program takes:
attention is counted in its materialised form (the absorbed form does more
operations a key and none to build keys; a program that uses it is not
credited for them), the key/value up-projection once a token, the routed
experts at the expectation of an even router."""


def expert_layers(cfg):
    return sum(1 for i in range(cfg["num_hidden_layers"])
               if i >= cfg["first_k_dense_replace"]
               and i % cfg["moe_layer_freq"] == 0)


def params_per_token(cfg):
    """Parameters one token passes in the matrix products of all layers
    (norms and the head left out): MLA's projections with W_kvb once,
    layer-0-style dense MLPs, and in an expert layer the router, the
    shared experts and num_experts_per_tok x held / router_width routed
    experts (what an even router sends to the experts held here)."""
    h, heads = cfg["hidden_size"], cfg["num_attention_heads"]
    dn, dr, dv = (cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"],
                  cfg["v_head_dim"])
    rq, rkv = cfg["q_lora_rank"], cfg["kv_lora_rank"]
    mla = (h * rq + rq * heads * (dn + dr) + h * (rkv + dr)
           + rkv * heads * (dn + dv) + heads * dv * h)
    one = 3 * h * cfg["moe_intermediate_size"]
    routed = (cfg["num_experts_per_tok"] * cfg["n_routed_experts"]
              / cfg["router_width"])
    moe = (h * cfg["router_width"] + cfg["n_shared_experts"] * one
           + routed * one)
    n_moe = expert_layers(cfg)
    n_dense = cfg["num_hidden_layers"] - n_moe
    return (cfg["num_hidden_layers"] * mla
            + n_dense * 3 * h * cfg["intermediate_size"] + n_moe * moe)


def attention_flops_per_key(cfg):
    """One query over one key in all heads of one layer, materialised:
    the score (nope + rope wide) and the weighted value."""
    return 2.0 * cfg["num_attention_heads"] * (
        cfg["qk_nope_head_dim"] + cfg["qk_rope_head_dim"]
        + cfg["v_head_dim"])


def serve_flops(cfg, prompt_len, generated):
    """Operations to process a prompt and generate `generated` tokens after
    it: two a parameter for every processed token, attention over the
    context live at each position, the head's columns held here at the
    positions that emit."""
    processed = prompt_len + generated - 1       # the last token is not fed
    weights = processed * 2.0 * params_per_token(cfg)
    # token at position i attends over i + 1 keys
    attn = (cfg["num_hidden_layers"] * attention_flops_per_key(cfg)
            * processed * (processed + 1) / 2)
    head = generated * 2.0 * cfg["hidden_size"] * cfg["vocab_size"]
    return weights + attn + head
