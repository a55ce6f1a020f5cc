"""Model registry: one bundle per reference workload (BASELINE.json:7-11),
and the public architectures run at their published sizes beyond them
(``olmoe_1b_7b``, ``laguna_xs2``, ``smallthinker_21b_a3b``, ``lfm2_24b_a2b``:
each takes the overrides that cut it to one chip's share without touching a
width).

Bundles are built lazily so importing the registry never pays for the whole
zoo. Each bundle closes over its config and exposes:

    init(rng) -> params
    loss_fn(params, batch, rng) -> (loss, metrics)
    make_batch(rng, batch_size) -> synthetic batch with the right shapes
"""

from __future__ import annotations

import dataclasses
from typing import Any, Callable, Dict, Optional, Tuple

import jax

Batch = Dict[str, jax.Array]
Metrics = Dict[str, jax.Array]


def _identity_select(params: Any) -> Any:
    return params


def _identity_merge(params: Any, averaged: Any) -> Any:
    return averaged


@dataclasses.dataclass(frozen=True)
class SteppedLeaves:
    """Leaves of the parameters that the STEP moves, by the model's own rule
    and from the step's own metrics, and the optimizer does not: a state that
    no gradient reaches (a router's selection bias, which load balancing
    without an auxiliary loss raises and lowers by the experts' loads). A
    model's bundle names them (``ModelBundle.stepped``); every builder of a
    step takes them (``stepped=``) and hands them to ``train_step_body``, the
    one place they act. A bundle that names none compiles to the program it
    compiled to before there was such a thing.

    ``signal``: the key of the loss function's metrics that the rule reads. It
    need not be a scalar; the step takes it out of the metrics it returns.
    ``owns(params)``: a tree of bools shaped like ``params``, True on the
    leaves the rule owns. Their gradient is zeroed before the optimizer sees
    it (no share of a global-norm clip) and whatever the optimizer makes of
    them is discarded: an owned leaf after the step is ``rule``'s.
    ``rule(params, signal)``: a tree shaped like ``params`` whose owned leaves
    are the new values, from the parameters as they were BEFORE the update."""

    signal: str
    owns: Callable[[Any], Any]
    rule: Callable[[Any, Any], Any]


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


def _gpt2(**overrides: Any) -> ModelBundle:
    from distributedvolunteercomputing_tpu.models import gpt2
    from distributedvolunteercomputing_tpu.training import data

    cfg = dataclasses.replace(gpt2.GPT2Config(), **overrides)
    return ModelBundle(
        name="gpt2_small",
        config=cfg,
        init=lambda rng: gpt2.init(rng, cfg),
        loss_fn=lambda p, b, rng: gpt2.loss_fn(p, b, rng, cfg),
        make_batch=lambda rng, bs: data.synthetic_lm_batch(
            rng, bs, seq_len=cfg.max_len, vocab=cfg.vocab
        ),
    )


def _gpt2_preset(preset: str, **overrides: Any) -> ModelBundle:
    """gpt2_medium / gpt2_large as first-class registry names: the scale
    rungs above the flagship (GPT2Config.medium/.large presets), nameable
    from the CLI (--model) and a benchmark configuration without a
    config-override incantation. Overrides still apply on top."""
    from distributedvolunteercomputing_tpu.models import gpt2
    from distributedvolunteercomputing_tpu.training import data

    base = getattr(gpt2.GPT2Config, preset)()
    cfg = dataclasses.replace(base, **overrides)
    return ModelBundle(
        name=f"gpt2_{preset}",
        config=cfg,
        init=lambda rng: gpt2.init(rng, cfg),
        loss_fn=lambda p, b, rng: gpt2.loss_fn(p, b, rng, cfg),
        make_batch=lambda rng, bs: data.synthetic_lm_batch(
            rng, bs, seq_len=cfg.max_len, vocab=cfg.vocab
        ),
    )


def _gpt2_moe(**overrides: Any) -> ModelBundle:
    from distributedvolunteercomputing_tpu.models import moe
    from distributedvolunteercomputing_tpu.training import data

    cfg = dataclasses.replace(moe.GPT2MoEConfig(), **overrides)
    return ModelBundle(
        name="gpt2_moe",
        config=cfg,
        init=lambda rng: moe.init(rng, cfg),
        loss_fn=lambda p, b, rng: moe.loss_fn(p, b, rng, cfg),
        make_batch=lambda rng, bs: data.synthetic_lm_batch(
            rng, bs, seq_len=cfg.max_len, vocab=cfg.vocab
        ),
    )


def _olmoe(**overrides: Any) -> ModelBundle:
    """OLMoE-1B-7B at its published sizes (models/olmoe.py): 16 layers need a
    four-chip host; ``n_layers`` cuts the depth to what a chip holds."""
    from distributedvolunteercomputing_tpu.models import olmoe
    from distributedvolunteercomputing_tpu.training import data

    cfg = dataclasses.replace(olmoe.OlmoeConfig(), **overrides)
    return ModelBundle(
        name="olmoe_1b_7b",
        config=cfg,
        init=lambda rng: olmoe.init(rng, cfg),
        loss_fn=lambda p, b, rng: olmoe.loss_fn(p, b, rng, cfg),
        make_batch=lambda rng, bs: data.synthetic_lm_batch(
            rng, bs, seq_len=cfg.max_len, vocab=cfg.vocab
        ),
    )


def _laguna(**overrides: Any) -> ModelBundle:
    """Laguna-XS.2 at its published sizes (models/laguna.py): 40 layers of 256
    experts are many chips' work; ``n_layers``, ``experts_held`` /
    ``expert_offset`` and ``vocab`` cut it to one chip's share."""
    from distributedvolunteercomputing_tpu.models import laguna
    from distributedvolunteercomputing_tpu.training import data

    cfg = dataclasses.replace(laguna.LagunaConfig(), **overrides)
    return ModelBundle(
        name="laguna_xs2",
        config=cfg,
        init=lambda rng: laguna.init(rng, cfg),
        loss_fn=lambda p, b, rng: laguna.loss_fn(p, b, rng, cfg),
        make_batch=lambda rng, bs: data.synthetic_lm_batch(
            rng, bs, seq_len=cfg.max_len, vocab=cfg.vocab
        ),
    )


def _smallthinker(**overrides: Any) -> ModelBundle:
    """SmallThinker-21BA3B-Instruct at its published sizes
    (models/smallthinker.py): 52 layers of 64 experts are many chips' work;
    ``n_layers`` (whole periods of four), ``experts_held`` / ``expert_offset``
    and ``vocab`` cut it to one chip's share."""
    from distributedvolunteercomputing_tpu.models import smallthinker
    from distributedvolunteercomputing_tpu.training import data

    cfg = dataclasses.replace(smallthinker.SmallThinkerConfig(), **overrides)
    return ModelBundle(
        name="smallthinker_21b_a3b",
        config=cfg,
        init=lambda rng: smallthinker.init(rng, cfg),
        loss_fn=lambda p, b, rng: smallthinker.loss_fn(p, b, rng, cfg),
        make_batch=lambda rng, bs: data.synthetic_lm_batch(
            rng, bs, seq_len=cfg.max_len, vocab=cfg.vocab
        ),
    )


def _lfm2(**overrides: Any) -> ModelBundle:
    """LFM2-24B-A2B at its published sizes (models/lfm2.py): 40 layers of 64
    experts are many chips' work; ``layer_types`` with ``dense_layers``,
    ``experts_held`` / ``expert_offset`` and ``vocab`` cut it to one chip's
    share. Its routers' selection biases are the step's to move."""
    from distributedvolunteercomputing_tpu.models import lfm2
    from distributedvolunteercomputing_tpu.training import data

    cfg = dataclasses.replace(lfm2.LFM2Config(), **overrides)
    return ModelBundle(
        name="lfm2_24b_a2b",
        config=cfg,
        init=lambda rng: lfm2.init(rng, cfg),
        loss_fn=lambda p, b, rng: lfm2.loss_fn(p, b, rng, cfg),
        make_batch=lambda rng, bs: data.synthetic_lm_batch(
            rng, bs, seq_len=cfg.max_len, vocab=cfg.vocab
        ),
        stepped=lfm2.stepped(cfg),
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


def _llama_lora(**overrides: Any) -> ModelBundle:
    from distributedvolunteercomputing_tpu.models import llama
    from distributedvolunteercomputing_tpu.training import data

    cfg = dataclasses.replace(llama.LlamaConfig(), **overrides)
    lora_on = cfg.lora_rank > 0
    return ModelBundle(
        name="llama_lora",
        config=cfg,
        init=lambda rng: llama.init(rng, cfg),
        loss_fn=lambda p, b, rng: llama.loss_fn(p, b, rng, cfg),
        make_batch=lambda rng, bs: data.synthetic_lm_batch(
            rng, bs, seq_len=cfg.max_len, vocab=cfg.vocab
        ),
        avg_select=llama.lora_subtree if lora_on else _identity_select,
        avg_merge=llama.with_lora_subtree if lora_on else _identity_merge,
    )


_REGISTRY: Dict[str, Callable[..., ModelBundle]] = {
    "mnist_mlp": _mlp,
    "cifar10_resnet18": _resnet18,
    "cifar10_vit": _vit,
    "bert_mlm": _bert,
    "gpt2_small": _gpt2,
    "gpt2_medium": lambda **kw: _gpt2_preset("medium", **kw),
    "gpt2_large": lambda **kw: _gpt2_preset("large", **kw),
    "gpt2_moe": _gpt2_moe,
    "olmoe_1b_7b": _olmoe,
    "laguna_xs2": _laguna,
    "smallthinker_21b_a3b": _smallthinker,
    "lfm2_24b_a2b": _lfm2,
    "llama_lora": _llama_lora,
}


def get_model(name: str, **overrides: Any) -> ModelBundle:
    if name not in _REGISTRY:
        raise KeyError(f"unknown model {name!r}; known: {sorted(_REGISTRY)}")
    return _REGISTRY[name](**overrides)


def list_models() -> Tuple[str, ...]:
    return tuple(sorted(_REGISTRY))
