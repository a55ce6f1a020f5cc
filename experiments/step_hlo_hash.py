"""sha256 of each benchmark configuration's lowered train step and of its lowered ``bundle.init``
(StableHLO text, one chip, no mesh): run at two commits, equal hashes say a change left a
configuration's program, and the program that makes its initial parameters, as they were.

    JAX_PLATFORMS=cpu python experiments/step_hlo_hash.py [config ...]

On the chip the lowering holds the Pallas kernels, each a serialised module WITH its operations' locations: with
Python frames in them (JAX's default, ten a location) the hash would follow the line numbers of every file on the
way to the kernel and the checkout's path, so locations here are the name stacks alone.

A configuration the checkout's registry does not know is skipped with a line that says so."""
import hashlib, json, sys, os
sys.path.insert(0, os.getcwd())
import jax
jax.config.update("jax_traceback_in_locations_limit", 0)
from distributedvolunteercomputing_tpu.models import get_model
from distributedvolunteercomputing_tpu.training.optim import make_optimizer
from distributedvolunteercomputing_tpu.training.steps import TrainState, make_train_step
CELLS = {"gpt2-medium": ("gpt2_medium", {}, 16),
         "gpt2-large": ("gpt2_large", {}, 8),
         "olmoe-1b-7b": ("olmoe_1b_7b", {"n_layers": 1}, 4),
         "smallthinker-21b-a3b": ("smallthinker_21b_a3b", {"n_layers": 4, "experts_held": 8, "expert_offset": 0, "vocab": 18992}, 2),
         "laguna-xs2": ("laguna_xs2", {"n_layers": 5, "experts_held": 16, "vocab": 12544}, 4),
         "lfm2-24b-a2b": ("lfm2_24b_a2b", {"layer_types": "conv,full_attention,conv,conv,conv", "dense_layers": 1,
                                           "experts_held": 8, "expert_offset": 0, "vocab": 8192}, 4),
         "glm-4.7-flash": ("glm4_7_flash", {"n_layers": 5, "experts_held": 8, "expert_offset": 0, "vocab": 19360}, 2),
         "nemotron-3-nano-30b-a3b": ("nemotron3_nano_30b_a3b", {"n_layers": 7, "experts_held": 8, "expert_offset": 0, "vocab": 16384}, 2),
         "kimi-linear-48b-a3b": ("kimi_linear_48b_a3b", {"n_layers": 5, "experts_held": 8, "expert_offset": 0, "vocab": 20480}, 2),
         "sdar-30b-a3b-chat": ("sdar_30b_a3b", {"n_layers": 5, "experts_held": 16, "expert_offset": 0, "vocab": 18992, "mask_id": 18991}, 2),
         "ouro-2.6b": ("ouro_2_6b", {"n_layers": 6, "max_len": 4096}, 2),
         "qwen3-next-80b-a3b": ("qwen3_next_80b_a3b", {"n_layers": 4, "experts_held": 16, "expert_offset": 0, "vocab": 18992}, 2),
         "xing4.0-29b-a4b": ("xing4_29b_a4b", {"n_layers": 5, "dense_layers": 1, "experts_held": 8, "expert_offset": 0, "vocab": 16384, "max_len": 4096}, 1)}
for name in sys.argv[1:] or CELLS:
    model, ov, bs = CELLS[name]
    try:
        bundle = get_model(model, **ov)
    except KeyError as e:
        print(json.dumps({"config": name, "skipped": str(e)[:60]}))
        continue
    tx = make_optimizer("adam", lr=1e-3, total_steps=1000000)
    state = jax.eval_shape(lambda: TrainState.create(bundle.init(jax.random.PRNGKey(0)), tx, jax.random.PRNGKey(1)))
    batch = jax.eval_shape(lambda: bundle.make_batch(jax.random.PRNGKey(2), bs))
    kw = {"stepped": bundle.stepped} if hasattr(bundle, "stepped") else {}
    text = make_train_step(bundle.loss_fn, tx, **kw).lower(state, batch).as_text()
    init = jax.jit(bundle.init).lower(jax.random.PRNGKey(0)).as_text()
    print(json.dumps({"config": name, "device": jax.devices()[0].platform, "lines": len(text.splitlines()),
                      "sha256": hashlib.sha256(text.encode()).hexdigest(), "init_lines": len(init.splitlines()),
                      "init_sha256": hashlib.sha256(init.encode()).hexdigest()}))
