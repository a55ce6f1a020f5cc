"""Vision Transformer (ViT) image classifier — beyond-parity zoo member.

The reference's vision workloads are CNNs (configs 1-2, BASELINE.json:7-8);
ViT is the TPU-preferred vision architecture: patchification turns an image
into one [B, N, p²·c] @ [p²·c, d] projection plus the SAME pre-LN attention
trunk the language models use — pure large matmuls on the MXU, no
small-window conv shapes, and the whole stack reuses `ops/attention.py`
(flash-kernel routing, sequence-parallel contexts) and
`common.scan_blocks` (one block's HLO, remat knob) unchanged.

Architecture: Dosovitskiy et al., "An Image is Worth 16x16 Words" — CLS
token, learned positions, pre-LN encoder blocks, classification head on the
CLS hidden state. Defaults are a CIFAR-scale ViT-Tiny.
"""

from __future__ import annotations

import dataclasses
from typing import Dict, Tuple

import jax
import jax.numpy as jnp

from distributedvolunteercomputing_tpu.models import common


@dataclasses.dataclass(frozen=True)
class ViTConfig:
    image_size: int = 32
    patch_size: int = 4
    channels: int = 3
    n_classes: int = 10
    d_model: int = 192
    n_heads: int = 3
    n_layers: int = 12
    d_ff: int = 768
    remat: bool = True  # see GPT2Config.remat

    @property
    def n_patches(self) -> int:
        side = self.image_size // self.patch_size
        return side * side

    @property
    def patch_dim(self) -> int:
        return self.patch_size * self.patch_size * self.channels


def _layer_init(rng: jax.Array, cfg: ViTConfig) -> common.Params:
    k = jax.random.split(rng, 4)
    return {
        "ln1": common.layernorm_init(cfg.d_model),
        "qkv": common.dense_init(k[0], cfg.d_model, 3 * cfg.d_model, scale=0.02),
        "attn_out": common.dense_init(k[1], cfg.d_model, cfg.d_model, scale=0.02),
        "ln2": common.layernorm_init(cfg.d_model),
        "mlp_in": common.dense_init(k[2], cfg.d_model, cfg.d_ff, scale=0.02),
        "mlp_out": common.dense_init(k[3], cfg.d_ff, cfg.d_model, scale=0.02),
    }


def init(rng: jax.Array, cfg: ViTConfig) -> common.Params:
    if cfg.image_size % cfg.patch_size != 0:
        raise ValueError(
            f"patch_size {cfg.patch_size} must divide image_size {cfg.image_size}"
        )
    k = jax.random.split(rng, 5)
    return {
        "patch_proj": common.dense_init(k[0], cfg.patch_dim, cfg.d_model, scale=0.02),
        "cls": common.embed_init(k[1], 1, cfg.d_model)[None],  # [1, 1, d]
        # +1 position for the CLS token.
        "pos": common.embed_init(k[2], cfg.n_patches + 1, cfg.d_model),
        "blocks": common.stacked_init(lambda kk: _layer_init(kk, cfg), k[3], cfg.n_layers),
        "ln_out": common.layernorm_init(cfg.d_model),
        "head": common.dense_init(k[4], cfg.d_model, cfg.n_classes, scale=0.02),
    }


def _patchify(x: jax.Array, cfg: ViTConfig) -> jax.Array:
    """[B, H, W, C] -> [B, N, p*p*C]: a reshape/transpose, no gather — XLA
    lowers it to a layout change feeding one big MXU matmul."""
    b = x.shape[0]
    s, p = cfg.image_size // cfg.patch_size, cfg.patch_size
    x = x.reshape(b, s, p, s, p, cfg.channels)
    x = x.transpose(0, 1, 3, 2, 4, 5)  # [B, s, s, p, p, C]
    return x.reshape(b, s * s, cfg.patch_dim)


def _block(p: common.Params, x: jax.Array, cfg: ViTConfig) -> jax.Array:
    # Pre-LN (ViT standard): residuals stay un-normalized.
    h = common.layernorm(p["ln1"], x)
    x = x + common.dense(p["attn_out"], common.fused_qkv_attention(p["qkv"], h, cfg.n_heads))
    h = common.layernorm(p["ln2"], x)
    return x + common.dense(p["mlp_out"], jax.nn.gelu(common.dense(p["mlp_in"], h)))


def forward(params: common.Params, x: jax.Array, cfg: ViTConfig) -> jax.Array:
    """Class logits [B, n_classes]."""
    dtype = common.compute_dtype()
    patches = _patchify(x.astype(jnp.float32), cfg)
    h = common.dense(params["patch_proj"], patches.astype(dtype))  # [B, N, d]
    cls = jnp.broadcast_to(
        params["cls"].astype(dtype), (h.shape[0], 1, cfg.d_model)
    )
    h = jnp.concatenate([cls, h], axis=1) + params["pos"].astype(dtype)[None]
    h = common.scan_blocks(
        lambda p, hh: _block(p, hh, cfg), params["blocks"], h, remat=cfg.remat
    )
    h = common.layernorm(params["ln_out"], h[:, 0])  # CLS hidden state
    return common.dense(params["head"], h, dtype=jnp.float32)


def loss_fn(
    params: common.Params, batch: Dict[str, jax.Array], rng: jax.Array, cfg: ViTConfig
) -> Tuple[jax.Array, Dict[str, jax.Array]]:
    logits = forward(params, batch["x"], cfg)
    loss = common.softmax_xent(logits, batch["y"])
    return loss, {"loss": loss, "accuracy": common.accuracy(logits, batch["y"])}
