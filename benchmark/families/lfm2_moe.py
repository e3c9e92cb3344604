"""How the LFM2-MoE family is built and served by the program, and which
plain reference and FLOP count go with it: through `Lfm2Moe`,
`PagedMLADecoder` and `ContinuousBatchingEngine`, as a user would. Serving
only: the family has no training cell (PERF.md section 4 says why).
"""
import contextlib

from ..cells import BenchmarkError
from .deepseek_v2 import build_engine               # noqa: F401  (the same engine)
from ..flops import lfm2_moe as flops               # noqa: F401  (found by name)
from ..reference import lfm2_moe as reference


def _program():
    """The program's module of this family; a checkout whose program has
    none (this family's parent commit) cannot run the cell."""
    try:
        from paddle_tpu.models import lfm2_moe
    except ImportError as e:
        raise BenchmarkError(f"the program has no LFM2-MoE model: {e}")
    return lfm2_moe


def program_config(cfg):
    """The program's config of the configuration file's stage: its layers'
    kinds from the published list, `n_routed_experts` of the file is what
    is held here of the router's `num_experts`."""
    return _program().Lfm2MoeConfig(
        vocab_size=cfg["vocab_size"], hidden_size=cfg["hidden_size"],
        num_layers=cfg["num_hidden_layers"],
        num_heads=cfg["num_attention_heads"],
        num_kv_heads=cfg["num_key_value_heads"],
        intermediate_size=cfg["intermediate_size"],
        moe_intermediate_size=cfg["moe_intermediate_size"],
        num_experts=cfg["num_experts"], experts_held=cfg["n_routed_experts"],
        expert_offset=cfg["expert_offset"],
        num_experts_per_tok=cfg["num_experts_per_tok"],
        num_dense_layers=cfg["num_dense_layers"],
        layer_types=reference.layer_types(cfg),
        conv_L_cache=cfg["conv_L_cache"],
        norm_topk_prob=cfg["norm_topk_prob"],
        routed_scaling_factor=cfg["routed_scaling_factor"],
        rms_norm_eps=cfg["norm_eps"],
        rope_theta=cfg["rope_parameters"]["rope_theta"],
        max_seq_len=cfg["max_position_embeddings"], dtype=cfg["dtype"],
        init_std=cfg["initializer_range"])


def build_model(cfg, seed, job):
    """The program's Layer over the weights `reference.init_params` makes
    from the seed: it adopts the arrays, so the chip holds them once."""
    model = _program().Lfm2Moe(
        program_config(cfg), weights=reference.init_params(cfg, seed))
    model.eval()
    return model


def build_decoder(cfg, seed, job):
    """The paged decoder over seeded weights. The Layer is this function's
    own, so the decoder is told to release it as it stacks: at 10.6 GB the
    chip has no room for the Layer's set beside the decoder's."""
    from paddle_tpu.serving.mla_decoder import PagedMLADecoder

    e = job["engine"]
    pages_per_seq = e["positions"] // e["page_size"]
    return PagedMLADecoder(
        build_model(cfg, seed, job),
        num_pages=e["slots"] * pages_per_seq + 2, page_size=e["page_size"],
        max_batch=e["slots"], max_pages_per_seq=pages_per_seq,
        release_model=True)


# ------------------------------------------------------------ the faults
# What `rehearse/serve_faults.py` plants in the program, one at a time, to
# read what the cell's limit says of each (PERF.md section 6). Each
# is a context manager around building and playing a decoder; what it
# yields is applied to the decoder once built.
@contextlib.contextmanager
def state_reset_every_tick():
    """Every conv layer's carried state zeroed before each horizon's
    dispatch: a decode row convolves as if it had just begun, a later chunk
    as if it were the first. A horizon is one tick where K is 1, as in the
    real cell (a horizon of K ticks resets once in K). The state is zeroed
    on the host, so the programs are the sound ones and nothing compiles
    anew; `tests/benchmark/test_lfm2_moe_cell.py` also zeroes it inside
    the program, at every tick of any K."""
    import jax.numpy as jnp

    def alter(decoder):
        real = decoder.ragged_multi

        def reset(*a, **kw):
            pool, state = decoder.cache
            decoder.cache = (pool, jnp.zeros_like(state))
            return real(*a, **kw)

        decoder.ragged_multi = reset

    yield alter


@contextlib.contextmanager
def kv_head_h_mod_8():
    """Query head h reads key/value head h % kv_heads in place of h //
    (heads / kv_heads): the query projection's head blocks and the output
    projection's rows laid out so that original head j sits where key/value
    head j % kv_heads is read."""
    def alter(decoder):
        H, K = decoder.cfg.num_heads, decoder.cfg.num_kv_heads
        D, G = decoder.cfg.head_dim, decoder.cfg.num_heads // K
        # place p holds original head (p % G) x K + p // G
        heads = [(p % G) * K + p // G for p in range(H)]
        cols = [h * D + d for h in heads for d in range(D)]
        for seg in decoder.weights["segments"]:
            if "q" in seg:
                seg["q"] = seg["q"][..., cols]
                seg["o"] = seg["o"][:, cols]

    yield alter


@contextlib.contextmanager
def qk_norm_dropped():
    """The per-head RMSNorm of q and k left out (rotary positions kept)."""
    import jax.numpy as jnp

    lfm = _program()
    real = lfm.gqa_project

    def unnormed(w, y, pos, cfg, inv):
        T, K, D = y.shape[0], cfg.num_kv_heads, cfg.head_dim
        q = lfm.rope_half(lfm._mm(y, w["q"]).reshape(T, cfg.num_heads, D),
                          pos, inv)
        k = lfm.rope_half(lfm._mm(y, w["k"]).reshape(T, K, D), pos, inv)
        return q, jnp.concatenate([k.reshape(T, K * D), lfm._mm(y, w["v"])],
                                  -1)

    lfm.gqa_project = unnormed
    try:
        yield lambda decoder: None
    finally:
        lfm.gqa_project = real


@contextlib.contextmanager
def bias_ignored():
    """The selection bias left out of the selection (zeros in its place)."""
    def alter(decoder):
        for seg in decoder.weights["segments"]:
            if "bias" in seg:
                seg["bias"] = seg["bias"] * 0

    yield alter


# those that compile nothing new first
FAULTS = {"bias_ignored": bias_ignored, "kv_head_h_mod_8": kv_head_h_mod_8,
          "state_reset_every_tick": state_reset_every_tick,
          "qk_norm_dropped": qk_norm_dropped}
