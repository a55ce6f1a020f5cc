"""The peak table and the FLOP arithmetic (copied from ``bench.py:27-30,167-170``
and extended by the attention term). One table, keyed by the exact
``device_kind`` JAX reports; a kind that is not here is an error."""

from __future__ import annotations

from typing import Dict

PEAKS: Dict[str, Dict[str, float]] = {
    # Google Cloud documentation, "TPU v5e": 197 TFLOP/s bf16 per chip.
    "TPU v5 lite": {"bf16_flops": 197e12},
}


class UnknownDevice(LookupError):
    """The device is not a TPU, or its kind has no published peak here."""


def peak_for(platform: str, device_kind: str) -> Dict[str, float]:
    if platform != "tpu":
        raise UnknownDevice(f"device platform is {platform!r}, not 'tpu': nothing measured")
    if device_kind not in PEAKS:
        raise UnknownDevice(
            f"device_kind {device_kind!r} is not in the peak table {sorted(PEAKS)}; "
            "add it with its source"
        )
    return PEAKS[device_kind]


def train_flops_per_token(n_params: int, n_layer: int, seq_len: int, d_model: int) -> float:
    """Model FLOPs a token costs in training: 6 N for the matrix
    multiplications (2 N forward, 4 N backward) plus 12 L T d for attention's
    score and value products (forward 4 T d a layer, backward twice that).
    Recomputation (remat) is not counted: these are the operations the
    algorithm requires."""
    return 6.0 * n_params + 12.0 * n_layer * seq_len * d_model


def mfu_percent(tokens_per_step: float, flops_per_token: float, step_s: float,
                chips: int, peak_flops: float) -> float:
    return 100.0 * tokens_per_step * flops_per_token / (step_s * chips * peak_flops)
