"""What the TPU compiler reserves for each benchmark cell's train step.

Compiles the step of every cell at its real depth and batch for a v5e that is
described and not attached (no chip: `tests/test_tpu_compile.py` has the
method) and prints one JSON line a cell with ``memory_analysis()``'s bytes:
the arguments (state and batch), the temporaries, their sum with the
outputs that do not alias an argument, and the size of the step's entry in the
compile cache (the serialized executable under zstd). ``memory_stats()`` on the chip counts
live buffers and not a program's temporaries (PERF.md section 4), so this is
where a change to what the step keeps (``models/common.remat_layer``) shows.

    JAX_PLATFORMS=cpu python experiments/step_memory.py [cell ...]
"""

import json
import os
import sys

os.environ.setdefault("TPU_LOG_DIR", "disabled")
sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)), ".."))

XING4_LEN = 4096
# cell -> (registry model, dp, tp, global batch, overrides): benchmark/configs/*.json
CELLS = {
    "medium-solo": ("gpt2_medium", 1, 1, 16, {"n_layers": 24}),
    "large-solo-4chip": ("gpt2_large", 2, 2, 32, {"n_layers": 36}),
    "olmoe-solo": ("olmoe_1b_7b", 1, 1, 4, {"n_layers": 1}),
    "laguna-solo-8k": ("laguna_xs2", 1, 1, 4, {"n_layers": 5, "experts_held": 16, "vocab": 12544}),
    "smallthinker-solo-16k": ("smallthinker_21b_a3b", 1, 1, 2,
                              {"n_layers": 4, "experts_held": 8, "vocab": 18992}),
    "lfm2-solo-8k": ("lfm2_24b_a2b", 1, 1, 4,
                     {"n_layers": None, "layer_types": "conv,full_attention,conv,conv,conv", "dense_layers": 1,
                      "experts_held": 8, "vocab": 8192}),
    "glm47-flash-solo-8k": ("glm4_7_flash", 1, 1, 2, {"n_layers": 5, "experts_held": 8, "vocab": 19360}),
    # what the cell's batch of 2 was chosen against (PERF.md section 4)
    "glm47-flash-batch4": ("glm4_7_flash", 1, 1, 4, {"n_layers": 5, "experts_held": 8, "vocab": 19360}),
    "nemotron3-nano-solo-8k": ("nemotron3_nano_30b_a3b", 1, 1, 2, {"n_layers": 7, "experts_held": 8, "vocab": 16384}),
    # what the cell's batch of 2 was chosen against (PERF.md section 4): 17.21e9, over the chip
    "nemotron3-nano-batch4": ("nemotron3_nano_30b_a3b", 1, 1, 4, {"n_layers": 7, "experts_held": 8, "vocab": 16384}),
    "kimi-linear-solo-8k": ("kimi_linear_48b_a3b", 1, 1, 2, {"n_layers": 5, "experts_held": 8, "vocab": 20480}),
    "sdar-solo-4k": ("sdar_30b_a3b", 1, 1, 2,
                     {"n_layers": 5, "experts_held": 16, "vocab": 18992, "mask_id": 18991}),
    # what the cell's five layers were chosen against (PERF.md section 4): six hold 645.6 M parameters
    "sdar-six-layers": ("sdar_30b_a3b", 1, 1, 2,
                        {"n_layers": 6, "experts_held": 16, "vocab": 18992, "mask_id": 18991}),
    "ouro-solo-4k": ("ouro_2_6b", 1, 1, 2, {"n_layers": 6, "max_len": 4096}),
    # what the cell's six layers were chosen against (PERF.md section 4): seven hold 561.0 M parameters
    "ouro-seven-layers": ("ouro_2_6b", 1, 1, 2, {"n_layers": 7, "max_len": 4096}),
    "qwen3-next-solo-8k": ("qwen3_next_80b_a3b", 1, 1, 2, {"n_layers": 4, "experts_held": 16, "vocab": 18992}),
    "xing4-solo": ("xing4_29b_a4b", 1, 1, 1,
                   {"n_layers": 5, "dense_layers": 1, "experts_held": 8, "vocab": 16384, "max_len": XING4_LEN}),
    # the other length of the cell's rule (PERF.md section 4): one sequence of 8,192 if the step reads at or
    # under 16.4e9, else of 4,096
    "xing4-solo-8k-tried": ("xing4_29b_a4b", 1, 1, 1,
                            {"n_layers": 5, "dense_layers": 1, "experts_held": 8, "vocab": 16384, "max_len": 8192}),
    "xing4-solo-4k-tried": ("xing4_29b_a4b", 1, 1, 1,
                            {"n_layers": 5, "dense_layers": 1, "experts_held": 8, "vocab": 16384, "max_len": 4096}),
}


def described_v5e():
    """The devices of a v5e:2x2 that is described and not attached, with the
    program's backend checks answering as they do on the chip."""
    from jax.experimental import topologies

    from distributedvolunteercomputing_tpu.ops import kda, moe_dispatch, pallas_attention, short_conv, ssd
    from distributedvolunteercomputing_tpu.utils import jaxenv

    jaxenv.tpu_backend = pallas_attention.tpu_backend = moe_dispatch.tpu_backend = lambda: True
    short_conv.tpu_backend = ssd.tpu_backend = kda.tpu_backend = jaxenv.tpu_backend
    moe_dispatch.grouped_matmul_impl = lambda m, k, n: "megablox"
    return topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2").devices


def cache_entry_mb(compiled) -> float:
    """What the program's entry of the compile cache weighs: the chip's own listing read the same (PR 39)."""
    import zstandard
    from jax.experimental.serialize_executable import serialize

    return round(len(zstandard.ZstdCompressor().compress(serialize(compiled)[0])) / 1e6, 2)


def main(cells) -> None:
    from tests.test_tpu_compile import _kernel_calls, _kernel_names, _lowered_step

    v5e = described_v5e()
    for cell in cells:
        model, dp, tp, batch, overrides = CELLS[cell]
        compiled = _lowered_step(v5e, model, dp, tp, batch, **overrides).compile()
        mem = compiled.memory_analysis()
        calls = _kernel_calls(compiled.as_text())
        print(json.dumps({
            "cell": cell,
            "argument_bytes": mem.argument_size_in_bytes,
            "temp_bytes": mem.temp_size_in_bytes,
            "total_bytes": mem.argument_size_in_bytes + mem.temp_size_in_bytes
            + mem.output_size_in_bytes - mem.alias_size_in_bytes,
            "code_bytes": mem.generated_code_size_in_bytes,
            "cache_entry_MB": cache_entry_mb(compiled),
            "kernel_calls": len(calls),
            "kernel_names": sorted({n.split(".")[0] for n in _kernel_names(calls)}),
        }), flush=True)


if __name__ == "__main__":
    main(sys.argv[1:] or list(CELLS))
