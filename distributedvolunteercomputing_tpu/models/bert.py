"""BERT-base masked-LM — reference config 3 (BASELINE.json:9).

Post-LN encoder (original BERT) with learned positions and a tied-embedding
MLM head. Only the MLM objective is implemented — that is the workload the
reference trains (4 volunteers, async gossip averaging).
"""

from __future__ import annotations

import dataclasses
from typing import Dict, Tuple

import jax
import jax.numpy as jnp

from distributedvolunteercomputing_tpu.models import common

MASK_ID = 103  # [MASK] in the BERT-base vocab


@dataclasses.dataclass(frozen=True)
class BertConfig:
    vocab: int = 30522
    max_len: int = 512
    d_model: int = 768
    n_heads: int = 12
    n_layers: int = 12
    d_ff: int = 3072
    remat: bool = True  # see GPT2Config.remat


def _layer_init(rng: jax.Array, cfg: BertConfig) -> common.Params:
    k = jax.random.split(rng, 4)
    return {
        "qkv": common.dense_init(k[0], cfg.d_model, 3 * cfg.d_model, scale=0.02),
        "attn_out": common.dense_init(k[1], cfg.d_model, cfg.d_model, scale=0.02),
        "ln1": common.layernorm_init(cfg.d_model),
        "mlp_in": common.dense_init(k[2], cfg.d_model, cfg.d_ff, scale=0.02),
        "mlp_out": common.dense_init(k[3], cfg.d_ff, cfg.d_model, scale=0.02),
        "ln2": common.layernorm_init(cfg.d_model),
    }


def init(rng: jax.Array, cfg: BertConfig) -> common.Params:
    keys = jax.random.split(rng, 4)
    return {
        "wte": common.embed_init(keys[0], cfg.vocab, cfg.d_model),
        "wpe": common.embed_init(keys[1], cfg.max_len, cfg.d_model, scale=0.01),
        "ln_emb": common.layernorm_init(cfg.d_model),
        "blocks": common.stacked_init(
            lambda k: _layer_init(k, cfg), keys[3], cfg.n_layers
        ),
        "mlm_dense": common.dense_init(keys[2], cfg.d_model, cfg.d_model, scale=0.02),
        "ln_mlm": common.layernorm_init(cfg.d_model),
    }


def _block(p: common.Params, x: jax.Array, cfg: BertConfig) -> jax.Array:
    attn = common.fused_qkv_attention(p["qkv"], x, cfg.n_heads)
    x = common.layernorm(p["ln1"], x + common.dense(p["attn_out"], attn))
    h = common.dense(p["mlp_out"], jax.nn.gelu(common.dense(p["mlp_in"], x)))
    return common.layernorm(p["ln2"], x + h)


def hidden(params: common.Params, tokens: jax.Array, cfg: BertConfig) -> jax.Array:
    """MLM-head hidden states [B, T, d] (before the tied vocab projection)."""
    dtype = common.compute_dtype()
    t = tokens.shape[1]
    x = (params["wte"][tokens] + params["wpe"][:t][None]).astype(dtype)
    x = common.layernorm(params["ln_emb"], x)
    x = common.scan_blocks(
        lambda p, h: _block(p, h, cfg), params["blocks"], x, remat=cfg.remat
    )
    h = jax.nn.gelu(common.dense(params["mlm_dense"], x))
    return common.layernorm(params["ln_mlm"], h)


def forward(params: common.Params, tokens: jax.Array, cfg: BertConfig) -> jax.Array:
    h = hidden(params, tokens, cfg)
    return jnp.einsum(
        "btd,vd->btv", h, params["wte"].astype(h.dtype)
    ).astype(jnp.float32)


def loss_fn(
    params: common.Params, batch: Dict[str, jax.Array], rng: jax.Array, cfg: BertConfig
) -> Tuple[jax.Array, Dict[str, jax.Array]]:
    h = hidden(params, batch["tokens"], cfg)
    loss = common.lm_xent_chunked(
        h, params["wte"], batch["targets"], mask=batch["mask"], head_layout="vd"
    )
    return loss, {"loss": loss}
