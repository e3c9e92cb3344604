"""How the GPT family is built, trained and served by the program, and which
plain reference and FLOP count go with it. This is the only place where the
benchmark touches the program's GPT: through `GPT`, `Trainer`, the
optimizer, `PagedGPTDecoder` and `ContinuousBatchingEngine`, as a user
would.
"""
import gc

from ..flops import gpt as flops            # noqa: F401  (found by name)
from ..reference import gpt as reference    # noqa: F401
from .common import build_adamw, load_weights


def build_model(cfg, seed, job):
    """The program's Layer, holding the weights `reference.init_params`
    makes from the seed."""
    from paddle_tpu.models import GPT, GPTConfig

    pcfg = GPTConfig(
        vocab_size=cfg["vocab_size"], hidden_size=cfg["hidden_size"],
        num_layers=cfg["num_hidden_layers"],
        num_heads=cfg["num_attention_heads"],
        ffn_hidden=cfg["intermediate_size"],
        max_seq_len=cfg["max_position_embeddings"],
        dropout=cfg["hidden_dropout_prob"], dtype=cfg["dtype"],
        remat_policy=job.get("remat_policy", "full"),
        tie_embeddings=cfg["tie_word_embeddings"],
        init_std=cfg["initializer_range"])
    model = GPT(pcfg)
    model.astype(cfg["dtype"])
    load_weights(model, reference.init_params(cfg, seed), "GPT")
    return model


def leaf_names(cfg):
    """{reference's leaf: program's leaf}"""
    return {k: k for k in reference.leaf_shapes(cfg)}


def build_trainer(model, cfg, job):
    import paddle_tpu as paddle
    from paddle_tpu.distributed.trainer import Trainer
    from paddle_tpu.models import GPTPretrainingCriterion

    crit = GPTPretrainingCriterion()
    opt = build_adamw(job)

    def loss_fn(m, batch):
        logits = m(paddle.to_tensor(batch["input_ids"]))
        return crit(logits, paddle.to_tensor(batch["labels"]))

    model.train()
    return Trainer(model, opt, loss_fn)


def build_decoder(cfg, seed, job):
    """The paged decoder over seeded weights. The Layer is dropped once the
    decoder has stacked its own copy: the largest horizon's program leaves
    no room for both."""
    from paddle_tpu.serving.decoder import PagedGPTDecoder

    model = build_model(cfg, seed, job)
    model.eval()
    e = job["engine"]
    pages_per_seq = cfg["max_position_embeddings"] // e["page_size"]
    decoder = PagedGPTDecoder(
        model, num_pages=e["slots"] * pages_per_seq + 2,
        page_size=e["page_size"], max_batch=e["slots"])
    del model
    gc.collect()
    return decoder


def build_engine(decoder, job):
    from paddle_tpu.serving.engine import ContinuousBatchingEngine

    e = job["engine"]
    return ContinuousBatchingEngine(
        decoder, max_new_tokens=e["max_new_tokens"],
        host_sync_s=e["host_sync_s"])
