"""What the harness's reference check would reserve on a chip, read without one.

    JAX_PLATFORMS=cpu python experiments/check_memory.py glm-4.7-flash [seq_len]

Compiles, for a described v5e (as ``experiments/step_memory.py`` does), the two programs that
``benchmark/probe.py:reference_check`` runs on one seeded sequence: the program's loss and gradient
(bf16 compute, the flash kernels) and the configuration's float32 reference; prints each one's
temporaries, outputs and compile-cache entry, and what the check holds beside them (the initial
parameters, and the first gradient tree while the second is made; the training state has left the
chip since PR 69). ``xing4.0-29b-a4b``: 9.35e9 and 13.59e9 at its 4,096 tokens (PR 70)."""
import json
import os
import sys

sys.path.insert(0, os.getcwd())


def main(config: str, seq_len=None) -> None:
    import jax
    import jax.numpy as jnp
    from jax.sharding import SingleDeviceSharding

    from benchmark import references
    from benchmark.manifest import Manifest
    from distributedvolunteercomputing_tpu.models import get_model
    from experiments.step_memory import cache_entry_mb, described_v5e

    chip = SingleDeviceSharding(described_v5e()[0])
    cfg = Manifest().load_config(config)
    t = int(seq_len or cfg["reference_check"]["seq_len"])
    bundle = get_model(cfg["registry_model"], **cfg["model_overrides"])
    placed = lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=chip)  # noqa: E731
    params = jax.tree_util.tree_map(placed, jax.eval_shape(bundle.init, jax.random.PRNGKey(0)))
    tok = jax.ShapeDtypeStruct((1, t), jnp.int32, sharding=chip)
    n_bytes = sum(x.size * x.dtype.itemsize for x in jax.tree_util.tree_leaves(params))
    rng = jax.random.PRNGKey(0)

    def program(p, tokens, targets):
        return jax.value_and_grad(lambda q: bundle.loss_fn(q, {"tokens": tokens, "targets": targets}, rng)[0])(p)

    held = {"initial_parameters": n_bytes}  # since PR 69 the training state is released before the check
    for name, fn, beside in (("program", program, 0), ("reference", references.load(cfg["family"]).make_loss_and_grad(cfg), n_bytes)):
        compiled = jax.jit(fn).lower(params, tok, tok).compile()
        mem = compiled.memory_analysis()
        print(json.dumps({
            "config": config, "seq_len": t, "what": name, "temp_bytes": mem.temp_size_in_bytes,
            "output_bytes": mem.output_size_in_bytes, "held_beside_bytes": sum(held.values()) + beside,
            "total_bytes": sum(held.values()) + beside + mem.temp_size_in_bytes + mem.output_size_in_bytes,
            "cache_entry_MB": cache_entry_mb(compiled),
        }), flush=True)


if __name__ == "__main__":
    main(*sys.argv[1:3])
