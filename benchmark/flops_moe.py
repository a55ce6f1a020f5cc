"""FLOP and byte counts of a sparse-expert decoder (OLMoE's shape: attention
with QK-norm, a router, E SwiGLU experts of which k work on a token, an untied
head), from a configuration file's published keys. Beside ``flops.py``, whose
``train_flops_per_token`` is 6 N over ALL parameters: for this model 3.4 times
the FLOPs a token's work needs (3.7 times the parameters), so ``step.mfu`` does
not list its cell and ``step.mfu_active`` reads these.

The peak table's bf16 entry is ``flops.PEAKS``; the memory bandwidth lives here
because ``flops.py`` holds none."""

from __future__ import annotations

from typing import Any, Dict, Optional

from benchmark import flops
from benchmark.references import olmoe as reference

# Google Cloud documentation, "TPU v5e": 16 GB of HBM at 819 GB/s per chip.
HBM_BYTES_PER_S: Dict[str, float] = {"TPU v5 lite": 819e9}


def hbm_bytes_per_s(peak: Dict[str, float]) -> Optional[float]:
    """The bandwidth of the device whose entry of ``flops.PEAKS`` a run holds
    as ``run["peak"]`` (the run does not carry the device's kind)."""
    for kind, entry in flops.PEAKS.items():
        if entry == peak:
            return HBM_BYTES_PER_S.get(kind)
    return None


def _dims(cfg: Dict[str, Any]):
    layers = reference.sizes(cfg)["n_layer"]  # the depth the cell runs, not the published one
    return (layers, int(cfg["hidden_size"]), int(cfg["intermediate_size"]),
            int(cfg["num_experts"]), int(cfg["num_experts_per_tok"]), int(cfg["vocab_size"]))


def total_params(cfg: Dict[str, Any]) -> int:
    """Every parameter the program holds: per layer q/k/v/o, E experts of three
    matrices, the router, four norm vectors (two of the block, q and k norm);
    the embedding, the untied head, the final norm."""
    layers, d, f, e, _, v = _dims(cfg)
    return layers * (4 * d * d + e * 3 * d * f + e * d + 4 * d) + 2 * v * d + d


def active_params(cfg: Dict[str, Any]) -> int:
    """Parameters whose matrix products a token's forward pass runs: per layer
    q/k/v/o, k of the E experts, the router; the head. The embedding is a
    lookup and the norms are vectors: neither is counted."""
    layers, d, f, e, k, v = _dims(cfg)
    return layers * (4 * d * d + k * 3 * d * f + e * d) + d * v


def train_flops_per_token_active(cfg: Dict[str, Any], seq_len: int) -> float:
    """6 N_active for the matrix products (2 forward, 4 backward) plus
    12 L T d for attention's score and value products, as ``flops.py`` counts
    them; recomputation (remat) is not counted."""
    layers, d = _dims(cfg)[:2]
    return 6.0 * active_params(cfg) + 12.0 * layers * seq_len * d


def gmm_flops(cfg: Dict[str, Any], tokens_per_step: int) -> float:
    """One grouped matmul call of the step, whichever: the k x tokens routed
    rows times one expert matrix each. Gate, up and down, their two backward
    products each (by the rows' and by the weights' side) all multiply
    ``rows x d x f`` once: 2 rows d f. Tile padding is not counted."""
    _, d, f, _, k, _ = _dims(cfg)
    return 2.0 * tokens_per_step * k * d * f


def gmm_bytes(cfg: Dict[str, Any], tokens_per_step: int, itemsize: int = 2) -> float:
    """The least one call moves: its rows in, its rows out, every expert's
    matrix once (bf16)."""
    _, d, f, e, k, _ = _dims(cfg)
    rows = tokens_per_step * k
    return float(itemsize) * (rows * d + rows * f + e * d * f)


def gmm_least_seconds(cfg: Dict[str, Any], tokens_per_step: int, peak_flops: float,
                      hbm_bytes_per_s: float) -> float:
    """The roofline of one call: the larger of FLOPs over the peak and bytes
    over the bandwidth (at OLMoE's sizes the FLOPs, 2.79 ms against 1.31)."""
    return max(gmm_flops(cfg, tokens_per_step) / peak_flops,
               gmm_bytes(cfg, tokens_per_step) / hbm_bytes_per_s)
