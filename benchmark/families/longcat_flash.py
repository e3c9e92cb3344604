"""How the LongCat-Flash family is built and served by the program, and
which plain reference and FLOP count go with it: through `LongCatFlash`,
`PagedMLADecoder` and `ContinuousBatchingEngine`, as a user would. Serving
only: the family has no training cell (PERF.md section 4 says why).
"""
from ..cells import BenchmarkError
from .deepseek_v2 import build_engine               # noqa: F401  (the same engine)
from ..flops import longcat_flash as flops          # noqa: F401  (found by name)
from ..reference import longcat_flash as reference  # noqa: F401


def _program():
    """The program's module of this family; a checkout whose program has
    none (this family's parent commit) cannot run the cell."""
    try:
        from paddle_tpu.models import longcat_flash
    except ImportError as e:
        raise BenchmarkError(f"the program has no LongCat-Flash model: {e}")
    return longcat_flash


def program_config(cfg):
    """The program's config of the configuration file's share: the router
    keeps its published columns (`router_width`, the last `zero_expert_num`
    of them identity experts), `n_routed_experts` of the file is what is
    held here."""
    return _program().LongCatFlashConfig(
        vocab_size=cfg["vocab_size"], hidden_size=cfg["hidden_size"],
        num_layers=cfg["num_layers"], num_heads=cfg["num_attention_heads"],
        q_lora_rank=cfg["q_lora_rank"], kv_lora_rank=cfg["kv_lora_rank"],
        qk_nope_head_dim=cfg["qk_nope_head_dim"],
        qk_rope_head_dim=cfg["qk_rope_head_dim"],
        v_head_dim=cfg["v_head_dim"],
        mla_scale_q_lora=cfg["mla_scale_q_lora"],
        mla_scale_kv_lora=cfg["mla_scale_kv_lora"],
        ffn_hidden_size=cfg["ffn_hidden_size"],
        expert_ffn_hidden_size=cfg["expert_ffn_hidden_size"],
        n_routed_experts=cfg["router_width"] - cfg["zero_expert_num"],
        zero_expert_num=cfg["zero_expert_num"],
        experts_held=cfg["n_routed_experts"],
        expert_offset=cfg["expert_offset"], moe_topk=cfg["moe_topk"],
        routed_scaling_factor=cfg["routed_scaling_factor"],
        rms_norm_eps=cfg["rms_norm_eps"], rope_theta=cfg["rope_theta"],
        max_seq_len=cfg["max_position_embeddings"], dtype=cfg["dtype"],
        init_std=cfg["initializer_range"])


def build_model(cfg, seed, job):
    """The program's Layer over the weights `reference.init_params` makes
    from the seed: it adopts the arrays, so the chip holds them once."""
    model = _program().LongCatFlash(
        program_config(cfg), weights=reference.init_params(cfg, seed))
    model.eval()
    return model


def build_decoder(cfg, seed, job):
    """The paged decoder over seeded weights. The Layer is this function's
    own, so the decoder is told to release it as it stacks: at 10.4 GB the
    chip has no room for the Layer's set beside the decoder's."""
    from paddle_tpu.serving.mla_decoder import PagedMLADecoder

    e = job["engine"]
    pages_per_seq = e["positions"] // e["page_size"]
    return PagedMLADecoder(
        build_model(cfg, seed, job),
        num_pages=e["slots"] * pages_per_seq + 2, page_size=e["page_size"],
        max_batch=e["slots"], max_pages_per_seq=pages_per_seq,
        release_model=True)
