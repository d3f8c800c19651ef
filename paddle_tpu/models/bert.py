"""BERT family (reference API: the PaddleNLP-style BertModel the reference
ecosystem trains with fleet data-parallel — the north-star config "BERT-base /
ERNIE-1.0 pretraining (fleet data-parallel only)"; encoder blocks are
paddle.nn.TransformerEncoder, python/paddle/nn/layer/transformer.py:697).

TPU notes: the whole model is MXU-dense (seq-major matmuls, fused LN);
masked-LM loss gathers only the masked positions before the vocab matmul so
the [B, S, V] logits tensor is never materialized (the HBM win that matters
at vocab 30k+)."""

from __future__ import annotations

import jax
import jax.numpy as jnp

import paddle_tpu.nn as nn
from ..framework.core import Tensor, run_op, to_tensor

__all__ = ["BertConfig", "BertModel", "BertForPretraining",
           "BertForSequenceClassification", "BertPretrainingCriterion",
           "bert_base", "bert_tiny"]


class BertConfig:
    def __init__(self, vocab_size=30522, hidden_size=768, num_layers=12,
                 num_heads=12, intermediate_size=3072,
                 max_position_embeddings=512, type_vocab_size=2,
                 hidden_dropout_prob=0.1, attention_dropout_prob=0.1,
                 layer_norm_eps=1e-12):
        self.vocab_size = vocab_size
        self.hidden_size = hidden_size
        self.num_layers = num_layers
        self.num_heads = num_heads
        self.intermediate_size = intermediate_size
        self.max_position_embeddings = max_position_embeddings
        self.type_vocab_size = type_vocab_size
        self.hidden_dropout_prob = hidden_dropout_prob
        self.attention_dropout_prob = attention_dropout_prob
        self.layer_norm_eps = layer_norm_eps


def bert_base(**kw):
    return BertConfig(**kw)


def bert_tiny(**kw):
    return BertConfig(vocab_size=1024, hidden_size=64, num_layers=2,
                      num_heads=4, intermediate_size=256,
                      max_position_embeddings=128, **kw)


class BertEmbeddings(nn.Layer):
    def __init__(self, cfg: BertConfig):
        super().__init__()
        self.word_embeddings = nn.Embedding(cfg.vocab_size, cfg.hidden_size)
        self.position_embeddings = nn.Embedding(cfg.max_position_embeddings,
                                                cfg.hidden_size)
        self.token_type_embeddings = nn.Embedding(cfg.type_vocab_size,
                                                  cfg.hidden_size)
        self.layer_norm = nn.LayerNorm(cfg.hidden_size, cfg.layer_norm_eps)
        self.dropout = nn.Dropout(cfg.hidden_dropout_prob)

    def forward(self, input_ids, token_type_ids=None, position_ids=None):
        ids = input_ids if isinstance(input_ids, Tensor) else to_tensor(input_ids)
        B, S = ids.shape
        if position_ids is None:
            position_ids = Tensor(jnp.broadcast_to(jnp.arange(S)[None], (B, S)))
        if token_type_ids is None:
            token_type_ids = Tensor(jnp.zeros((B, S), jnp.int32))
        h = (self.word_embeddings(ids)
             + self.position_embeddings(position_ids)
             + self.token_type_embeddings(token_type_ids))
        return self.dropout(self.layer_norm(h))


class BertPooler(nn.Layer):
    def __init__(self, cfg: BertConfig):
        super().__init__()
        self.dense = nn.Linear(cfg.hidden_size, cfg.hidden_size)

    def forward(self, hidden):
        first = run_op("bert_cls_token", lambda h: h[:, 0], [hidden])
        return nn.functional.tanh(self.dense(first))


class BertModel(nn.Layer):
    """Encoder trunk; returns (sequence_output, pooled_output)."""

    def __init__(self, cfg: BertConfig):
        super().__init__()
        self.config = cfg
        self.embeddings = BertEmbeddings(cfg)
        layer = nn.TransformerEncoderLayer(
            cfg.hidden_size, cfg.num_heads, cfg.intermediate_size,
            dropout=cfg.hidden_dropout_prob, activation="gelu",
            attn_dropout=cfg.attention_dropout_prob,
            layer_norm_eps=cfg.layer_norm_eps)
        self.encoder = nn.TransformerEncoder(layer, cfg.num_layers)
        self.pooler = BertPooler(cfg)

    def forward(self, input_ids, token_type_ids=None, attention_mask=None,
                position_ids=None):
        h = self.embeddings(input_ids, token_type_ids, position_ids)
        mask = None
        if attention_mask is not None:
            am = (attention_mask if isinstance(attention_mask, Tensor)
                  else to_tensor(attention_mask))
            # [B, S] keep-mask -> additive [B, 1, 1, S]
            mask = run_op(
                "bert_attn_mask",
                lambda m: (1.0 - m.astype(jnp.float32))[:, None, None, :] * -1e4,
                [am])
        seq = self.encoder(h, mask)
        return seq, self.pooler(seq)


class BertForPretraining(nn.Layer):
    """MLM + NSP heads (reference BertForPretraining)."""

    def __init__(self, cfg: BertConfig):
        super().__init__()
        self.bert = BertModel(cfg)
        self.transform = nn.Linear(cfg.hidden_size, cfg.hidden_size)
        self.transform_norm = nn.LayerNorm(cfg.hidden_size, cfg.layer_norm_eps)
        self.nsp_head = nn.Linear(cfg.hidden_size, 2)
        self.config = cfg

    def forward(self, input_ids, token_type_ids=None, attention_mask=None,
                masked_positions=None):
        seq, pooled = self.bert(input_ids, token_type_ids, attention_mask)
        h = self.transform_norm(nn.functional.gelu(self.transform(seq)))
        word_w = self.bert.embeddings.word_embeddings.weight  # tied decoder
        if masked_positions is not None:
            pos = (masked_positions if isinstance(masked_positions, Tensor)
                   else to_tensor(masked_positions))
            # gather masked slots BEFORE the vocab matmul: [B, M, H] @ [H, V]
            h = run_op(
                "mlm_gather",
                lambda hh, p: jnp.take_along_axis(
                    hh, p[..., None].astype(jnp.int32), axis=1),
                [h, pos])
        mlm_logits = run_op("mlm_decode",
                            lambda hh, w: jnp.matmul(hh, w.T), [h, word_w])
        nsp_logits = self.nsp_head(pooled)
        return mlm_logits, nsp_logits


class BertPretrainingCriterion(nn.Layer):
    """Masked-LM CE (ignore_index -100 slots) + NSP CE."""

    def __init__(self, cfg: BertConfig = None):
        super().__init__()

    def forward(self, mlm_logits, nsp_logits, mlm_labels, nsp_labels):
        def fn(lg, ng, ml, nl):
            V = lg.shape[-1]
            logp = jnp.take_along_axis(
                lg - jax.nn.logsumexp(lg, axis=-1, keepdims=True),
                jnp.maximum(ml, 0)[..., None].astype(jnp.int32), axis=-1)[..., 0]
            keep = (ml >= 0).astype(jnp.float32)
            mlm = -(logp * keep).sum() / jnp.maximum(keep.sum(), 1.0)
            nlogp = jnp.take_along_axis(
                ng - jax.nn.logsumexp(ng, axis=-1, keepdims=True),
                nl[..., None].astype(jnp.int32), axis=-1)[..., 0]
            return mlm - nlogp.mean()

        return run_op("bert_pretraining_loss", fn,
                      [mlm_logits, nsp_logits,
                       mlm_labels if isinstance(mlm_labels, Tensor) else to_tensor(mlm_labels),
                       nsp_labels if isinstance(nsp_labels, Tensor) else to_tensor(nsp_labels)])


class BertForSequenceClassification(nn.Layer):
    def __init__(self, cfg: BertConfig, num_classes=2):
        super().__init__()
        self.bert = BertModel(cfg)
        self.dropout = nn.Dropout(cfg.hidden_dropout_prob)
        self.classifier = nn.Linear(cfg.hidden_size, num_classes)

    def forward(self, input_ids, token_type_ids=None, attention_mask=None):
        _, pooled = self.bert(input_ids, token_type_ids, attention_mask)
        return self.classifier(self.dropout(pooled))
