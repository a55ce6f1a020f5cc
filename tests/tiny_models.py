"""The tiny model of each expert family (the manifest's ``tiny-rehearsal-*``
overrides), built once a process, and its loss, loss-and-gradient and train
step each under ONE ``jax.jit`` that every test of the process shares.

A tiny share model costs a test nothing to run and 10 to 60 s to compile (four
or five traced layer bodies, each rematerialised, custom-VJP kernels
interpreted); called eagerly it compiles a program an operation instead. So
the tests read the programs here: parameters and batches are arguments, and a
test that wants other values scales or perturbs the tree it passes. What a
test changes ABOUT the trace is part of a program's key: the bundle's
overrides, and the module constants that tests patch (``_traced_constants``);
a test that patches anything else traces its own.
"""

import functools
import importlib
import types

import jax

from benchmark.manifest import Manifest
from distributedvolunteercomputing_tpu.models import common, get_model
from distributedvolunteercomputing_tpu.models.registry import _LANGUAGE_MODELS
from distributedvolunteercomputing_tpu.ops import moe_dispatch
from distributedvolunteercomputing_tpu.training.optim import make_optimizer
from distributedvolunteercomputing_tpu.training.steps import make_train_step


@functools.lru_cache(maxsize=None)
def rehearsal(family: str) -> dict:
    """The configuration file of ``tiny-rehearsal-<family>`` (shared: read it, do not write it)."""
    return Manifest().load_config(f"tiny-rehearsal-{family}")


def _hashable(value):
    return tuple(_hashable(v) for v in value) if isinstance(value, (list, tuple)) else value


@functools.lru_cache(maxsize=None)
def _bundle(family: str, overrides: tuple):
    cfg = rehearsal(family)
    return get_model(cfg["registry_model"], **{**cfg["model_overrides"], **dict(overrides)})


def bundle(family: str, **overrides):
    """The family's tiny bundle with ``overrides`` over the rehearsal's, one object a process."""
    return _bundle(family, tuple(sorted((k, _hashable(v)) for k, v in overrides.items())))


def module_of(bundle):
    """The model's module (``models/<family>.py``)."""
    return importlib.import_module(f"{common.__package__}.{_LANGUAGE_MODELS[bundle.name][0]}")


def _traced_constants(bundle) -> tuple:
    """What a trace of ``bundle``'s loss reads from module globals that tests
    patch: the chunk's slack (the dispatch's default and the model's own) and
    the compute dtype."""
    return (moe_dispatch.SHARE_ROWS_SLACK, getattr(module_of(bundle), "SHARE_ROWS_SLACK", None), common.compute_dtype())


@functools.lru_cache(maxsize=None)
def _programs(bundle, constants: tuple):
    del constants  # the key alone: the trace reads them where they live
    module = module_of(bundle)

    def whole(params, batch):
        loss, metrics, routes = module.loss_and_routes(params, batch, bundle.config)
        return loss, (metrics, routes)

    forward = jax.jit(whole)
    backward = jax.jit(jax.value_and_grad(whole, has_aux=True))

    def loss_metrics_and_grad(params, batch):
        (loss, (metrics, _)), grads = backward(params, batch)
        return (loss, metrics), grads

    def loss_routes_and_grad(params, batch):
        (loss, (_, routes)), grads = backward(params, batch)
        return (loss, routes), grads

    def loss_and_routes(params, batch):
        loss, (metrics, routes) = forward(params, batch)
        return loss, metrics, routes

    def loss_and_grad(params, batch):
        (loss, _), grads = backward(params, batch)
        return loss, grads

    return types.SimpleNamespace(
        loss=lambda params, batch: forward(params, batch)[0],
        loss_and_routes=loss_and_routes,
        loss_and_grad=loss_and_grad,
        loss_metrics_and_grad=loss_metrics_and_grad,
        loss_routes_and_grad=loss_routes_and_grad,
    )


def programs(bundle):
    """``bundle``'s programs, all of ``(params, batch)``: ``loss``,
    ``loss_and_routes`` (the module's own three results), ``loss_and_grad``,
    ``loss_metrics_and_grad`` and ``loss_routes_and_grad`` (``value_and_grad``
    with the metrics or the routes beside the loss). Two compiles stand behind
    the five, the module's ``loss_and_routes`` and its gradient, each traced at
    most once a process for the module constants in force."""
    return _programs(bundle, _traced_constants(bundle))


@functools.lru_cache(maxsize=None)
def _train_step(bundle, constants: tuple, optimizer: str, lr: float, weight_decay: float):
    del constants
    tx = make_optimizer(optimizer, lr=lr, weight_decay=weight_decay)
    return tx, make_train_step(bundle.loss_fn, tx, donate=False, stepped=bundle.stepped)


def train_step(bundle, optimizer: str = "adam", lr: float = 1e-3, weight_decay: float = 0.0):
    """``(tx, step)``: an optimizer and the family's train step under it
    (nothing donated: a test reads the state it passed), one compile a process
    for these arguments."""
    return _train_step(bundle, _traced_constants(bundle), optimizer, lr, weight_decay)


def reference_programs(ref, hp):
    """``program(grad=False, **static)`` for a family's plain reference
    (``benchmark/references/<family>.py``) at the hyperparameters ``hp``:
    ``ref.loss`` of ``(params, tokens, targets[, routes])``, with its gradient
    beside it where asked, as ONE program a set of ``static`` arguments
    (``variant``, ``with_routes``); evaluated eagerly its nested checkpoints and
    scans are hundreds of small programs. The last few programs only: a
    variant's is used once, holds some 1,200 memory maps while it lives, and
    ``tests/conftest.py`` drops EVERY cached program of the process once they
    sum to 40,000."""
    @functools.lru_cache(maxsize=8)
    def program(grad=False, **static):
        def fn(params, tokens, targets, routes=None):
            return ref.loss(params, tokens, targets, hp, routes, **static)

        return jax.jit(jax.value_and_grad(fn) if grad else fn)

    return program
