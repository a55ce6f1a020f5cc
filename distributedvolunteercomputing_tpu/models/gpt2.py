"""GPT-2 small — reference config 4 and the north-star workload
(BASELINE.json:10, north_star: GPT-2-small on 4x v4-8 volunteer slices).

Pre-LN transformer decoder with learned positional embeddings and tied
input/output embeddings. Flagship model for the benchmark's cells and __graft_entry__.

TPU-first layout decisions:
- Blocks are ONE stacked pytree scanned with ``lax.scan`` (common.scan_blocks)
  — each block's HLO appears once in the XLA program instead of n_layers
  times, which cuts compile time and program size on-chip.
- The loss never materializes the [B, T, 50257] f32 logits tensor
  (1.6 GB at bench shapes); it streams vocab projection + cross-entropy over
  time chunks (common.lm_xent_chunked) and makes both of the head's gradients
  in that same loop, so nothing of a chunk is kept or recomputed.
"""

from __future__ import annotations

import dataclasses
from typing import Dict, Tuple

import jax
import jax.numpy as jnp

from distributedvolunteercomputing_tpu.models import common
from distributedvolunteercomputing_tpu.ops.attention import keep_tp_reduced


@dataclasses.dataclass(frozen=True)
class GPT2Config:
    vocab: int = 50257
    max_len: int = 1024
    d_model: int = 768
    n_heads: int = 12
    n_layers: int = 12
    d_ff: int = 3072
    # Rematerialize each block in backward: trades ~30% FLOPs for O(layers x
    # activations) HBM — required to train at bs>=8, seq 1024 on one 16GB chip.
    remat: bool = True
    # Time-chunk size for the streamed vocab projection + xent.
    xent_chunk: int = 128

    @classmethod
    def medium(cls) -> "GPT2Config":
        """GPT-2 medium (~355M params): the next standard rung above the
        flagship; with ``--fsdp`` over dp it fits comfortably per chip."""
        return cls(d_model=1024, n_heads=16, n_layers=24, d_ff=4096)

    @classmethod
    def large(cls) -> "GPT2Config":
        """GPT-2 large (~774M params): Adam state pushes past one 16 GB
        chip in f32 — the regime ZeRO-1/FSDP exist for."""
        return cls(d_model=1280, n_heads=20, n_layers=36, d_ff=5120)


def _layer_init(rng: jax.Array, cfg: GPT2Config) -> common.Params:
    k = jax.random.split(rng, 4)
    # GPT-2 uses fused qkv; residual projections scaled by 1/sqrt(2*n_layers)
    res_scale = 1.0 / ((2 * cfg.n_layers) ** 0.5 * cfg.d_model ** 0.5)
    return {
        "ln1": common.layernorm_init(cfg.d_model),
        "qkv": common.dense_init(k[0], cfg.d_model, 3 * cfg.d_model, scale=0.02),
        "attn_out": common.dense_init(k[1], cfg.d_model, cfg.d_model, scale=res_scale),
        "ln2": common.layernorm_init(cfg.d_model),
        "mlp_in": common.dense_init(k[2], cfg.d_model, cfg.d_ff, scale=0.02),
        "mlp_out": common.dense_init(k[3], cfg.d_ff, cfg.d_model, scale=res_scale),
    }


def init(rng: jax.Array, cfg: GPT2Config) -> common.Params:
    keys = jax.random.split(rng, 3)
    return {
        "wte": common.embed_init(keys[0], cfg.vocab, cfg.d_model),
        "wpe": common.embed_init(keys[1], cfg.max_len, cfg.d_model, scale=0.01),
        "blocks": common.stacked_init(
            lambda k: _layer_init(k, cfg), keys[2], cfg.n_layers
        ),
        "ln_f": common.layernorm_init(cfg.d_model),
    }


def _block(p: common.Params, x: jax.Array, cfg: GPT2Config) -> jax.Array:
    # The scopes name the two sublayers in the compiled step's op metadata
    # (a profiler trace otherwise shows anonymous ``fusion.N``).
    with jax.named_scope("attention"):
        h = common.layernorm(p["ln1"], x)
        attn = common.fused_qkv_attention(p["qkv"], h, cfg.n_heads, causal=True)  # [B, T, d] in the products' own layout
        x = x + keep_tp_reduced(common.dense(p["attn_out"], attn))
    with jax.named_scope("mlp"):
        h = common.layernorm(p["ln2"], x)
        h = common.dense(p["mlp_out"], jax.nn.gelu(common.dense(p["mlp_in"], h)))
        return x + h


def embed(params: common.Params, tokens: jax.Array, cfg: GPT2Config) -> jax.Array:
    """Token + position embeddings [B, T, d] in compute dtype — the trunk's
    input. Public so parallel/pipeline.py can wrap just the block trunk."""
    dtype = common.compute_dtype()
    t = tokens.shape[1]
    return (params["wte"][tokens] + params["wpe"][:t][None]).astype(dtype)


def block_fn(p: common.Params, x: jax.Array, cfg: GPT2Config) -> jax.Array:
    """One block's pure function (public for the pipeline trunk)."""
    return _block(p, x, cfg)


def lm_loss_from_hidden(
    params: common.Params, x: jax.Array, batch: Dict[str, jax.Array], cfg: GPT2Config
) -> Tuple[jax.Array, Dict[str, jax.Array]]:
    """Final LN + streamed tied-vocab xent, from post-trunk hidden states."""
    x = common.layernorm(params["ln_f"], x)
    loss = common.lm_xent_chunked(
        x, params["wte"], batch["targets"], chunk=cfg.xent_chunk, head_layout="vd"
    )
    return loss, {"loss": loss}


def _trunk(params: common.Params, tokens: jax.Array, cfg: GPT2Config) -> jax.Array:
    """embed -> scanned blocks (pre-ln_f). Shared by loss_fn and hidden so
    the training and inference trunks can never drift apart."""
    x = embed(params, tokens, cfg)
    return common.scan_blocks(
        lambda p, h: _block(p, h, cfg), params["blocks"], x, remat=cfg.remat,
        rows_independent=True,  # nothing in a dense block couples two rows
    )


def hidden(params: common.Params, tokens: jax.Array, cfg: GPT2Config) -> jax.Array:
    """Final-layer hidden states [B, T, d] (after ln_f, pre vocab projection)."""
    return common.layernorm(params["ln_f"], _trunk(params, tokens, cfg))


def forward(params: common.Params, tokens: jax.Array, cfg: GPT2Config) -> jax.Array:
    """Full logits [B, T, V] — for tests/inference; the train loss uses the
    chunked path in loss_fn and never builds this tensor."""
    x = hidden(params, tokens, cfg)
    # tied output embedding
    return jnp.einsum(
        "btd,vd->btv", x, params["wte"].astype(x.dtype)
    ).astype(jnp.float32)


def loss_fn(
    params: common.Params, batch: Dict[str, jax.Array], rng: jax.Array, cfg: GPT2Config
) -> Tuple[jax.Array, Dict[str, jax.Array]]:
    return lm_loss_from_hidden(params, _trunk(params, batch["tokens"], cfg), batch, cfg)
