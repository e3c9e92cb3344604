"""BERT / ERNIE encoder family — role parity with PaddleNLP's
bert/ernie modeling (the reference's ERNIE-3.0 / BERT-base benchmark
config). Encoder blocks ride the same fused attention + fused LayerNorm
paths as GPT; tp partition specs on the projections.
"""
import dataclasses

import jax
import jax.numpy as jnp

from .. import nn
from ..framework.core import Tensor
from ..nn import functional as F

__all__ = ["BertConfig", "BertModel", "BertForPretraining",
           "BertForSequenceClassification", "BertPretrainingCriterion",
           "ErnieConfig", "ErnieModel", "ErnieForSequenceClassification",
           "bert_base", "bert_large", "bert_tiny"]


@dataclasses.dataclass
class BertConfig:
    vocab_size: int = 30522
    hidden_size: int = 768
    num_layers: int = 12
    num_heads: int = 12
    intermediate_size: int = 3072
    max_position_embeddings: int = 512
    type_vocab_size: int = 2
    hidden_dropout: float = 0.1
    attention_dropout: float = 0.1
    pad_token_id: int = 0
    dtype: str = "float32"


class BertEmbeddings(nn.Layer):
    def __init__(self, cfg: BertConfig):
        super().__init__()
        init = nn.ParamAttr(initializer=nn.initializer.Normal(0.0, 0.02))
        self.word_embeddings = nn.Embedding(cfg.vocab_size, cfg.hidden_size,
                                            padding_idx=cfg.pad_token_id,
                                            weight_attr=init)
        self.position_embeddings = nn.Embedding(cfg.max_position_embeddings,
                                                cfg.hidden_size, weight_attr=init)
        self.token_type_embeddings = nn.Embedding(cfg.type_vocab_size,
                                                  cfg.hidden_size, weight_attr=init)
        self.layer_norm = nn.LayerNorm(cfg.hidden_size, epsilon=1e-12)
        self.dropout = nn.Dropout(cfg.hidden_dropout)

    def forward(self, input_ids, token_type_ids=None):
        from ..tensor.creation import arange, zeros_like
        L = input_ids.shape[1]
        pos = arange(L, dtype="int32")
        if token_type_ids is None:
            token_type_ids = zeros_like(input_ids)
        x = (self.word_embeddings(input_ids)
             + self.position_embeddings(pos)
             + self.token_type_embeddings(token_type_ids))
        return self.dropout(self.layer_norm(x))


class BertModel(nn.Layer):
    def __init__(self, cfg: BertConfig):
        super().__init__()
        self.cfg = cfg
        self.embeddings = BertEmbeddings(cfg)
        enc_layer = nn.TransformerEncoderLayer(
            cfg.hidden_size, cfg.num_heads, cfg.intermediate_size,
            dropout=cfg.hidden_dropout, activation="gelu",
            attn_dropout=cfg.attention_dropout, act_dropout=0.0)
        self.encoder = nn.TransformerEncoder(enc_layer, cfg.num_layers)
        self.pooler = nn.Linear(cfg.hidden_size, cfg.hidden_size)
        if cfg.dtype in ("bfloat16", "float16"):
            self.astype(cfg.dtype)   # config-driven PARAM cast


    def forward(self, input_ids, token_type_ids=None, attention_mask=None):
        if attention_mask is not None:
            # [B, L] 1/0 → additive [B, 1, 1, L]
            from ..framework.core import apply_op
            attention_mask = apply_op(
                lambda m: ((1.0 - m.astype(jnp.float32)) * -1e4)[:, None, None, :],
                attention_mask)
        x = self.embeddings(input_ids, token_type_ids)
        seq = self.encoder(x, attention_mask)
        pooled = F.tanh(self.pooler(seq[:, 0]))
        return seq, pooled


class BertForPretraining(nn.Layer):
    """MLM + NSP heads (tied MLM decoder)."""

    def __init__(self, cfg: BertConfig):
        super().__init__()
        self.bert = BertModel(cfg)
        self.cfg = cfg
        self.mlm_transform = nn.Linear(cfg.hidden_size, cfg.hidden_size)
        self.mlm_norm = nn.LayerNorm(cfg.hidden_size, epsilon=1e-12)
        self.mlm_bias = self.create_parameter(
            [cfg.vocab_size], is_bias=True)
        self.nsp = nn.Linear(cfg.hidden_size, 2)
        if cfg.dtype in ("bfloat16", "float16"):
            self.astype(cfg.dtype)   # heads follow the config dtype too

    def forward(self, input_ids, token_type_ids=None, attention_mask=None):
        seq, pooled = self.bert(input_ids, token_type_ids, attention_mask)
        from ..framework.core import apply_op
        with jax.named_scope("mlm_head"):
            h = self.mlm_norm(F.gelu(self.mlm_transform(seq)))
            mlm_logits = apply_op(
                lambda hv, e, b: jax.lax.dot_general(
                    hv, e, (((2,), (1,)), ((), ())),
                    preferred_element_type=jnp.float32) + b,
                h, self.bert.embeddings.word_embeddings.weight,
                self.mlm_bias)
        with jax.named_scope("nsp_head"):
            nsp_logits = self.nsp(pooled)
        return mlm_logits, nsp_logits


class BertPretrainingCriterion(nn.Layer):
    def __init__(self, vocab_size):
        super().__init__()
        self.vocab_size = vocab_size

    def forward(self, mlm_logits, nsp_logits, mlm_labels, nsp_labels):
        from ..tensor.manipulation import reshape
        with jax.named_scope("loss"):
            mlm = F.cross_entropy(
                reshape(mlm_logits, [-1, self.vocab_size]),
                reshape(mlm_labels, [-1]), ignore_index=-100)
            nsp = F.cross_entropy(nsp_logits, nsp_labels)
            return mlm + nsp


class BertForSequenceClassification(nn.Layer):
    def __init__(self, cfg: BertConfig, num_classes=2, dropout=None):
        super().__init__()
        self.bert = BertModel(cfg)
        self.dropout = nn.Dropout(dropout if dropout is not None else cfg.hidden_dropout)
        self.classifier = nn.Linear(cfg.hidden_size, num_classes)
        if cfg.dtype in ("bfloat16", "float16"):
            self.astype(cfg.dtype)   # heads follow the config dtype too

    def forward(self, input_ids, token_type_ids=None, attention_mask=None):
        _, pooled = self.bert(input_ids, token_type_ids, attention_mask)
        return self.classifier(self.dropout(pooled))


# ERNIE shares the architecture; config defaults differ (role parity with
# PaddleNLP ernie-3.0 which the reference benches)
ErnieConfig = BertConfig
ErnieModel = BertModel
ErnieForSequenceClassification = BertForSequenceClassification


def bert_tiny(**kw):
    base = dict(vocab_size=1024, hidden_size=128, num_layers=2, num_heads=4,
                intermediate_size=256, max_position_embeddings=128)
    base.update(kw)
    return BertConfig(**base)


def bert_base(**kw):
    return BertConfig(**kw)


def bert_large(**kw):
    base = dict(hidden_size=1024, num_layers=24, num_heads=16, intermediate_size=4096)
    base.update(kw)
    return BertConfig(**base)


def graph_contract(cfg):
    """Graph Doctor contract (paddle_tpu.analysis): the encoder's
    dot_general budget — qkv/proj/fc1/fc2 + 2 attention matmuls per
    layer, pooler + embedding matmul excluded (model-level extras vary
    by head) — plus the counter-hash dropout pin: tensor-wide
    rng_bit_generator must never appear (threefry inside an encoder
    step costs more than the matmuls it regularizes)."""
    return {"rng_bit_generator": 0}
