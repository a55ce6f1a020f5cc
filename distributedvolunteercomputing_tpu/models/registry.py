"""Model registry: one bundle per reference workload (BASELINE.json:7-11),
and the public architectures run at their published sizes beyond them
(``olmoe_1b_7b``, ``laguna_xs2``, ``smallthinker_21b_a3b``, ``lfm2_24b_a2b``,
``glm4_7_flash``, ``nemotron3_nano_30b_a3b``, ``kimi_linear_48b_a3b``, ``sdar_30b_a3b``, ``ouro_2_6b``: each takes the overrides that cut it to one chip's share without touching a
width).

Bundles are built lazily so importing the registry never pays for the whole
zoo. Each bundle closes over its config and exposes:

    init(rng) -> params
    loss_fn(params, batch, rng) -> (loss, metrics)
    make_batch(rng, batch_size) -> synthetic batch with the right shapes
"""

from __future__ import annotations

import dataclasses
import functools
import importlib
from typing import Any, Callable, Dict, Mapping, Optional, Tuple

import jax

from distributedvolunteercomputing_tpu.models.common import SteppedLeaves, StepSpan

Batch = Dict[str, jax.Array]
Metrics = Dict[str, jax.Array]


def _identity_select(params: Any) -> Any:
    return params


def _identity_merge(params: Any, averaged: Any) -> Any:
    return averaged


@dataclasses.dataclass(frozen=True)
class ModelBundle:
    name: str
    config: Any
    init: Callable[[jax.Array], Any]
    loss_fn: Callable[[Any, Batch, jax.Array], Tuple[jax.Array, Metrics]]
    make_batch: Callable[[jax.Array, int], Batch]
    # What the swarm averages: select the payload subtree out of the params
    # (identity for full averaging; the LoRA bundle selects adapters only so
    # the WAN round ships ~1000x less) and merge the averaged result back.
    avg_select: Callable[[Any], Any] = _identity_select
    avg_merge: Callable[[Any, Any], Any] = _identity_merge
    # Leaves the train step moves by the model's own rule and keeps the
    # optimizer off; None for every model whose parameters all follow a gradient.
    stepped: Optional[SteppedLeaves] = None
    # Spans the train loop records of the step's metrics, by name; empty for a
    # model whose step says nothing beyond its loss. (No part of the hash: a
    # bundle is the key of more than one cache.)
    spans: Mapping[str, StepSpan] = dataclasses.field(default_factory=dict, hash=False)


def _mlp(**overrides: Any) -> ModelBundle:
    from distributedvolunteercomputing_tpu.models import mlp
    from distributedvolunteercomputing_tpu.training import data

    cfg = dataclasses.replace(mlp.MLPConfig(), **overrides)
    return ModelBundle(
        name="mnist_mlp",
        config=cfg,
        init=lambda rng: mlp.init(rng, cfg),
        loss_fn=lambda p, b, rng: mlp.loss_fn(p, b, rng, cfg),
        make_batch=lambda rng, bs: data.synthetic_image_batch(
            rng, bs, shape=(28, 28, 1), n_classes=cfg.n_classes
        ),
    )


def _resnet18(**overrides: Any) -> ModelBundle:
    from distributedvolunteercomputing_tpu.models import resnet
    from distributedvolunteercomputing_tpu.training import data

    cfg = dataclasses.replace(resnet.ResNetConfig(), **overrides)
    return ModelBundle(
        name="cifar10_resnet18",
        config=cfg,
        init=lambda rng: resnet.init(rng, cfg),
        loss_fn=lambda p, b, rng: resnet.loss_fn(p, b, rng, cfg),
        make_batch=lambda rng, bs: data.synthetic_image_batch(
            rng, bs, shape=(32, 32, 3), n_classes=cfg.n_classes
        ),
    )


def _bert(**overrides: Any) -> ModelBundle:
    from distributedvolunteercomputing_tpu.models import bert
    from distributedvolunteercomputing_tpu.training import data

    cfg = dataclasses.replace(bert.BertConfig(), **overrides)
    return ModelBundle(
        name="bert_mlm",
        config=cfg,
        init=lambda rng: bert.init(rng, cfg),
        loss_fn=lambda p, b, rng: bert.loss_fn(p, b, rng, cfg),
        make_batch=lambda rng, bs: data.synthetic_mlm_batch(
            rng, bs, seq_len=cfg.max_len, vocab=cfg.vocab, mask_id=bert.MASK_ID
        ),
    )


def _vit(**overrides: Any) -> ModelBundle:
    from distributedvolunteercomputing_tpu.models import vit
    from distributedvolunteercomputing_tpu.training import data

    cfg = dataclasses.replace(vit.ViTConfig(), **overrides)
    return ModelBundle(
        name="cifar10_vit",
        config=cfg,
        init=lambda rng: vit.init(rng, cfg),
        loss_fn=lambda p, b, rng: vit.loss_fn(p, b, rng, cfg),
        make_batch=lambda rng, bs: data.synthetic_image_batch(
            rng, bs,
            shape=(cfg.image_size, cfg.image_size, cfg.channels),
            n_classes=cfg.n_classes,
        ),
    )


# The language models: name -> (its module under models/, what makes its
# config there: a class, or one of a class's presets). The expert families run at
# their published sizes; each takes the overrides that cut it to one chip's
# share without touching a width (the module's docstring says what the cut
# leaves out).
_LANGUAGE_MODELS: Dict[str, Tuple[str, str]] = {
    "gpt2_small": ("gpt2", "GPT2Config"),
    # the scale rungs above the flagship, nameable from the CLI (--model) and
    # a benchmark configuration without a config-override incantation
    "gpt2_medium": ("gpt2", "GPT2Config.medium"),
    "gpt2_large": ("gpt2", "GPT2Config.large"),
    "gpt2_moe": ("gpt2_moe", "GPT2MoEConfig"),
    # 16 layers need a four-chip host; ``n_layers`` cuts the depth to what a chip holds
    "olmoe_1b_7b": ("olmoe", "OlmoeConfig"),
    # 40 layers of 256 experts: ``n_layers``, ``experts_held`` / ``expert_offset``, ``vocab``
    "laguna_xs2": ("laguna", "LagunaConfig"),
    # 52 layers of 64 experts: ``n_layers`` (whole periods of four), ``experts_held`` /
    # ``expert_offset``, ``vocab``
    "smallthinker_21b_a3b": ("smallthinker", "SmallThinkerConfig"),
    # 40 layers of 64 experts: ``layer_types`` with ``dense_layers``, ``experts_held`` /
    # ``expert_offset``, ``vocab``; its routers' selection biases are the step's to move
    "lfm2_24b_a2b": ("lfm2", "LFM2Config"),
    # 47 layers of latent attention and 64 experts: ``n_layers``, ``experts_held`` /
    # ``expert_offset``, ``vocab``; its routers' selection biases are the step's to move
    "glm4_7_flash": ("glm4_moe_lite", "Glm4MoeLiteConfig"),
    # 52 one-mixer blocks (state-space, experts, attention) of 128 experts: ``n_layers`` (the
    # pattern's first blocks), ``experts_held`` / ``expert_offset``, ``vocab``; its routers'
    # selection biases are the step's to move
    "nemotron3_nano_30b_a3b": ("nemotron_h", "NemotronHConfig"),
    # 27 layers (delta-rule linear attention, every fourth latent attention without positions) of 256
    # experts: ``n_layers`` (the first so many), ``experts_held`` / ``expert_offset``, ``vocab``; its
    # routers' selection biases are the step's to move
    "kimi_linear_48b_a3b": ("kimi_linear", "KimiLinearConfig"),
    # 48 Qwen3-MoE layers of 128 experts under a block-diffusion objective (a clean and a noised
    # copy of every sequence under one three-part mask): ``n_layers``, ``experts_held`` /
    # ``expert_offset``, ``vocab`` with ``mask_id`` inside it; its loss draws the noise from the step's rng
    "sdar_30b_a3b": ("sdar_moe", "SdarMoeConfig"),
    # 48 dense layers run four times over the same weights, a head and an exit gate after every pass:
    # ``n_layers``, ``max_len`` (``passes`` is not depth and is not cut)
    "ouro_2_6b": ("ouro", "OuroConfig"),
    # 48 layers (a gated delta rule under one decay a head, every fourth output-gated attention at a head of
    # 256) of 512 experts beside a gated shared one: ``n_layers`` (whole periods of four), ``experts_held`` /
    # ``expert_offset``, ``vocab``
    "qwen3_next_80b_a3b": ("qwen3_next", "Qwen3NextConfig"),
    # 40 layers of latent attention (a key of 192 over a value of 128, YaRN) and 64 experts around FOUR residual
    # streams mixed by Sinkhorn-normalised maps: ``n_layers`` with ``dense_layers``, ``experts_held`` /
    # ``expert_offset``, ``vocab``; its routers' selection biases are the step's to move
    "xing4_29b_a4b": ("xing4", "Xing4Config"),
    "llama_lora": ("llama", "LlamaConfig"),
}


def _language_model(name: str, **overrides: Any) -> ModelBundle:
    """The bundle of one of ``_LANGUAGE_MODELS``. A module brings ``init(rng,
    cfg)`` and either ``loss_fn(params, batch, rng, cfg)`` or
    ``loss_and_routes(params, batch, cfg)`` (an expert family whose loss draws
    nothing: the loss is its first two results); ``stepped(cfg)`` where the step moves leaves of its own;
    ``spans(cfg)`` where the loop records spans of the step's metrics; and,
    where its config has a ``lora_rank`` above 0, the subtree the swarm
    averages (``lora_subtree`` / ``with_lora_subtree``)."""
    from distributedvolunteercomputing_tpu.training import data

    module_name, make_config = _LANGUAGE_MODELS[name]
    module = importlib.import_module(f"{__package__}.{module_name}")
    cfg = dataclasses.replace(functools.reduce(getattr, make_config.split("."), module)(), **overrides)
    if hasattr(module, "loss_fn"):  # a loss that draws from the step's rng
        def loss_fn(params, batch, rng):
            return module.loss_fn(params, batch, rng, cfg)
    else:
        def loss_fn(params, batch, rng):
            return module.loss_and_routes(params, batch, cfg)[:2]
    lora_on = getattr(cfg, "lora_rank", 0) > 0
    return ModelBundle(
        name=name,
        config=cfg,
        init=lambda rng: module.init(rng, cfg),
        loss_fn=loss_fn,
        make_batch=lambda rng, bs: data.synthetic_lm_batch(
            rng, bs, seq_len=cfg.max_len, vocab=cfg.vocab
        ),
        avg_select=module.lora_subtree if lora_on else _identity_select,
        avg_merge=module.with_lora_subtree if lora_on else _identity_merge,
        stepped=module.stepped(cfg) if hasattr(module, "stepped") else None,
        spans=module.spans(cfg) if hasattr(module, "spans") else {},
    )


_REGISTRY: Dict[str, Callable[..., ModelBundle]] = {
    "mnist_mlp": _mlp,
    "cifar10_resnet18": _resnet18,
    "cifar10_vit": _vit,
    "bert_mlm": _bert,
    **{name: functools.partial(_language_model, name) for name in _LANGUAGE_MODELS},
}


def get_model(name: str, **overrides: Any) -> ModelBundle:
    if name not in _REGISTRY:
        raise KeyError(f"unknown model {name!r}; known: {sorted(_REGISTRY)}")
    return _REGISTRY[name](**overrides)


def list_models() -> Tuple[str, ...]:
    return tuple(sorted(_REGISTRY))
