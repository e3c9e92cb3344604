"""How the BERT family is built and trained by the program, and which plain
reference and FLOP count go with it."""
from ..flops import bert as flops            # noqa: F401  (found by name)
from ..reference import bert as reference    # noqa: F401
from .common import build_adamw, load_weights


def build_model(cfg, seed, job):
    from paddle_tpu.models.bert import BertConfig, BertForPretraining

    pcfg = BertConfig(
        vocab_size=cfg["vocab_size"], hidden_size=cfg["hidden_size"],
        num_layers=cfg["num_hidden_layers"],
        num_heads=cfg["num_attention_heads"],
        intermediate_size=cfg["intermediate_size"],
        max_position_embeddings=cfg["max_position_embeddings"],
        type_vocab_size=cfg["type_vocab_size"],
        hidden_dropout=cfg["hidden_dropout_prob"],
        attention_dropout=cfg["attention_probs_dropout_prob"],
        pad_token_id=cfg["pad_token_id"], dtype=cfg["dtype"])
    model = BertForPretraining(pcfg)
    load_weights(model, reference.init_params(cfg, seed), "BERT")
    return model


def leaf_names(cfg):
    return {k: k for k in reference.leaf_shapes(cfg)}


def build_trainer(model, cfg, job):
    import paddle_tpu as paddle
    from paddle_tpu.distributed.trainer import Trainer
    from paddle_tpu.models.bert import BertPretrainingCriterion

    crit = BertPretrainingCriterion(cfg["vocab_size"])
    opt = build_adamw(job)

    def loss_fn(m, batch):
        mlm, nsp = m(paddle.to_tensor(batch["input_ids"]),
                     paddle.to_tensor(batch["token_type_ids"]),
                     paddle.to_tensor(batch["attention_mask"]))
        return crit(mlm, nsp, paddle.to_tensor(batch["labels"]),
                    paddle.to_tensor(batch["nsp_labels"]))

    model.train()
    return Trainer(model, opt, loss_fn)
