import hashlib, json, sys, os
sys.path.insert(0, os.getcwd())
import jax
from distributedvolunteercomputing_tpu.models import get_model
from distributedvolunteercomputing_tpu.training.optim import make_optimizer
from distributedvolunteercomputing_tpu.training.steps import TrainState, make_train_step
CELLS = {"gpt2-medium": ("gpt2_medium", {}, 16),
         "smallthinker-21b-a3b": ("smallthinker_21b_a3b", {"n_layers": 4, "experts_held": 8, "expert_offset": 0, "vocab": 18992}, 2),
         "laguna-xs2": ("laguna_xs2", {"n_layers": 5, "experts_held": 16, "vocab": 12544}, 4)}
for name, (model, ov, bs) in CELLS.items():
    bundle = get_model(model, **ov)
    tx = make_optimizer("adam", lr=1e-3, total_steps=1000000)
    state = jax.eval_shape(lambda: TrainState.create(bundle.init(jax.random.PRNGKey(0)), tx, jax.random.PRNGKey(1)))
    batch = jax.eval_shape(lambda: bundle.make_batch(jax.random.PRNGKey(2), bs))
    kw = {"stepped": bundle.stepped} if hasattr(bundle, "stepped") else {}
    text = make_train_step(bundle.loss_fn, tx, **kw).lower(state, batch).as_text()
    print(json.dumps({"config": name, "lines": len(text.splitlines()), "sha256": hashlib.sha256(text.encode()).hexdigest()}))
