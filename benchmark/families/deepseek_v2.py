"""How the DeepSeek-V2 family is built and served by the program, and which
plain reference and FLOP count go with it: through `DeepSeekV2`,
`PagedMLADecoder` and `ContinuousBatchingEngine`, as a user would. Serving
only: the family has no training cell (PERF.md section 4 says why).
"""
from ..flops import deepseek_v2 as flops            # noqa: F401  (found by name)
from ..reference import deepseek_v2 as reference    # noqa: F401


def program_config(cfg):
    """The program's config of the configuration file's share: the router
    keeps its published width (`router_width`), `n_routed_experts` of the
    file is what is held here."""
    from paddle_tpu.models.deepseek_v2 import DeepSeekV2Config

    return DeepSeekV2Config(
        vocab_size=cfg["vocab_size"], hidden_size=cfg["hidden_size"],
        num_layers=cfg["num_hidden_layers"],
        num_heads=cfg["num_attention_heads"],
        q_lora_rank=cfg["q_lora_rank"], kv_lora_rank=cfg["kv_lora_rank"],
        qk_nope_head_dim=cfg["qk_nope_head_dim"],
        qk_rope_head_dim=cfg["qk_rope_head_dim"],
        v_head_dim=cfg["v_head_dim"],
        intermediate_size=cfg["intermediate_size"],
        moe_intermediate_size=cfg["moe_intermediate_size"],
        n_shared_experts=cfg["n_shared_experts"],
        n_routed_experts=cfg["router_width"],
        experts_held=cfg["n_routed_experts"],
        expert_offset=cfg["expert_offset"],
        num_experts_per_tok=cfg["num_experts_per_tok"],
        n_group=cfg["n_group"], topk_group=cfg["topk_group"],
        routed_scaling_factor=cfg["routed_scaling_factor"],
        norm_topk_prob=cfg["norm_topk_prob"],
        first_k_dense_replace=cfg["first_k_dense_replace"],
        moe_layer_freq=cfg["moe_layer_freq"],
        rms_norm_eps=cfg["rms_norm_eps"], rope_theta=cfg["rope_theta"],
        rope_scaling=cfg["rope_scaling"],
        max_seq_len=cfg["max_position_embeddings"], dtype=cfg["dtype"],
        init_std=cfg["initializer_range"],
        router_init_std=cfg["router_init_std"])


def build_model(cfg, seed, job):
    """The program's Layer over the weights `reference.init_params` makes
    from the seed: it adopts the arrays, so the chip holds them once."""
    from paddle_tpu.models.deepseek_v2 import DeepSeekV2

    model = DeepSeekV2(program_config(cfg),
                       weights=reference.init_params(cfg, seed))
    model.eval()
    return model


def build_decoder(cfg, seed, job):
    """The paged decoder over seeded weights. The Layer is this function's
    own, so the decoder is told to release it as it stacks: at 7.6 GB the
    chip has no room for the Layer's set beside the decoder's."""
    from paddle_tpu.serving.mla_decoder import PagedMLADecoder

    e = job["engine"]
    pages_per_seq = e["positions"] // e["page_size"]
    return PagedMLADecoder(
        build_model(cfg, seed, job),
        num_pages=e["slots"] * pages_per_seq + 2, page_size=e["page_size"],
        max_batch=e["slots"], max_pages_per_seq=pages_per_seq,
        release_model=True)


def build_engine(decoder, job):
    from paddle_tpu.serving.engine import ContinuousBatchingEngine

    e = job["engine"]
    return ContinuousBatchingEngine(
        decoder, max_new_tokens=e["max_new_tokens"],
        host_sync_s=e["host_sync_s"])
