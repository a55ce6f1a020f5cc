"""Tests for the minimum end-to-end slice: utils, MLP, train step, trainer.

Mirrors reference config 1: MNIST MLP, single volunteer, local SGD, no
averaging (BASELINE.json:7).
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from distributedvolunteercomputing_tpu.models import get_model, list_models
from distributedvolunteercomputing_tpu.training.steps import TrainState, make_train_step
from distributedvolunteercomputing_tpu.training.optim import make_optimizer
from distributedvolunteercomputing_tpu.training.trainer import Trainer
from distributedvolunteercomputing_tpu.utils.pytree import (
    flatten_to_buffer,
    unflatten_from_buffer,
    tree_size_bytes,
)


class TestPytreeSerde:
    def test_roundtrip(self, rng):
        tree = {
            "a": jnp.arange(6, dtype=jnp.float32).reshape(2, 3),
            "b": {"c": jnp.ones((4,), jnp.bfloat16), "d": jnp.zeros((2, 2, 2), jnp.int32)},
        }
        buf, specs, treedef = flatten_to_buffer(tree)
        assert buf.dtype == np.float32
        assert buf.size == 6 + 4 + 8
        out = unflatten_from_buffer(buf, specs, treedef)
        for orig, rec in zip(jax.tree_util.tree_leaves(tree), jax.tree_util.tree_leaves(out)):
            assert np.asarray(orig).dtype == rec.dtype
            np.testing.assert_allclose(np.asarray(orig, np.float32), rec.astype(np.float32))

    def test_empty_tree(self):
        buf, specs, treedef = flatten_to_buffer({})
        assert buf.size == 0
        assert unflatten_from_buffer(buf, specs, treedef) == {}

    def test_size_mismatch_raises(self):
        tree = {"a": jnp.ones((3,))}
        buf, specs, treedef = flatten_to_buffer(tree)
        with pytest.raises(ValueError):
            unflatten_from_buffer(buf[:-1], specs, treedef)

    def test_tree_size_bytes(self):
        assert tree_size_bytes({"a": jnp.ones((4,), jnp.float32)}) == 16


class TestMLP:
    def test_registry_lists_all_configs(self):
        names = list_models()
        for expected in ("mnist_mlp", "cifar10_resnet18", "bert_mlm", "gpt2_small", "llama_lora"):
            assert expected in names

    def test_forward_shapes_and_loss(self, rng):
        bundle = get_model("mnist_mlp")
        params = bundle.init(rng)
        batch = bundle.make_batch(rng, 16)
        loss, metrics = bundle.loss_fn(params, batch, rng)
        assert loss.shape == ()
        assert np.isfinite(float(loss))
        assert 0.0 <= float(metrics["accuracy"]) <= 1.0

    def test_train_step_reduces_loss(self):
        # NB: the step donates its input state, so every TrainState.create gets
        # fresh key/param buffers — never reuse a donated array.
        bundle = get_model("mnist_mlp")
        tx = make_optimizer("adam", lr=1e-2)
        step = make_train_step(bundle.loss_fn, tx)
        batch = bundle.make_batch(jax.random.PRNGKey(7), 64)
        state = TrainState.create(bundle.init(jax.random.PRNGKey(8)), tx, jax.random.PRNGKey(9))
        _, m0 = step(state, batch)
        state = TrainState.create(bundle.init(jax.random.PRNGKey(8)), tx, jax.random.PRNGKey(9))
        for _ in range(30):
            state, m = step(state, batch)
        assert float(m["loss"]) < float(m0["loss"])
        assert int(state.step) == 30


class TestTrainerLocalSGD:
    def test_mnist_convergence_smoke(self):
        # Config 1: single volunteer, no averaging, bounded steps to target loss.
        t = Trainer(get_model("mnist_mlp"), batch_size=64, lr=1e-2, optimizer="adam", seed=0)
        summary = t.run(steps=200, target_loss=0.3, log_every=0)
        assert summary["final_loss"] <= 0.3, summary
        assert summary["steps"] < 200, "should hit target before budget"

    def test_target_loss_stops_early(self):
        t = Trainer(get_model("mnist_mlp"), batch_size=32, lr=1e-2, optimizer="adam", seed=1)
        summary = t.run(steps=500, target_loss=10.0, log_every=0)  # trivially satisfied
        assert summary["steps"] == 1
        assert summary["target_crossed_step"] == 1
        assert summary["target_crossed_s"] is not None

    def test_target_mode_record_trains_full_budget(self):
        """time-to-target-loss (BASELINE.json:2): record mode reports the
        first crossing but keeps training the full step budget, so one run
        yields BOTH the fixed-steps throughput row and the crossing time."""
        t = Trainer(get_model("mnist_mlp"), batch_size=32, lr=1e-2, optimizer="adam", seed=1)
        summary = t.run(steps=12, target_loss=10.0, target_mode="record", log_every=0)
        assert summary["steps"] == 12  # did NOT stop at the (trivial) target
        assert summary["target_crossed_step"] == 1
        assert summary["target_crossed_s"] >= 0.0
        # an unreachable target records a null crossing, not a crash
        t2 = Trainer(get_model("mnist_mlp"), batch_size=32, lr=1e-2, optimizer="adam", seed=1)
        s2 = t2.run(steps=3, target_loss=-1.0, target_mode="record", log_every=0)
        assert s2["target_crossed_step"] is None and s2["target_crossed_s"] is None
        import pytest

        with pytest.raises(ValueError, match="target_mode"):
            t2.run(steps=1, target_mode="bogus")

    def test_outer_optimizer_nesterov_math(self):
        """DiLoCo outer step, hand-checked over three rounds: with anchor a,
        round average v, g = a - v, m' = mu*m + g, a' = a - lr*(mu*m' + g).
        Round 1 seeds the anchor and passes the average through."""
        import numpy as np

        t = Trainer(
            get_model("mnist_mlp", d_hidden=4), batch_size=8,
            outer_optimizer="nesterov", outer_lr=0.5, outer_momentum=0.9,
        )
        lr, mu = 0.5, 0.9

        def tree(x):
            return {"w": np.full((3,), x, np.float32)}

        # round 1: seed anchor, pass through
        out1 = t._outer_transform(tree(10.0))
        np.testing.assert_allclose(out1["w"], 10.0)
        # round 2: v=7 -> g = 10-7 = 3; m = 3; a' = 10 - 0.5*(0.9*3 + 3) = 7.15
        out2 = t._outer_transform(tree(7.0))
        np.testing.assert_allclose(out2["w"], 7.15, rtol=1e-6)
        # round 3: v=7 -> g = 7.15-7 = 0.15; m = 0.9*3 + 0.15 = 2.85
        #          a' = 7.15 - 0.5*(0.9*2.85 + 0.15) = 7.15 - 1.3575 = 5.7925
        out3 = t._outer_transform(tree(7.0))
        np.testing.assert_allclose(out3["w"], 5.7925, rtol=1e-6)

    def test_outer_optimizer_identity_config_matches_plain_averaging(self):
        """lr=1, mu=0 reduces the outer step to plain adoption of the round
        average — the safety property that makes the default parameters a
        strict generalization."""
        import numpy as np

        t = Trainer(
            get_model("mnist_mlp", d_hidden=4), batch_size=8,
            outer_optimizer="nesterov", outer_lr=1.0, outer_momentum=0.0,
        )
        for v in (4.0, -2.0, 11.5):
            out = t._outer_transform({"w": np.full((5,), v, np.float32)})
            np.testing.assert_allclose(out["w"], v, rtol=1e-6)

    def test_outer_optimizer_reset_on_adoption(self):
        """A state-sync adoption invalidates the momentum stream: the next
        round must re-seed the anchor instead of differencing against a
        pre-adoption one."""
        import numpy as np

        t = Trainer(
            get_model("mnist_mlp", d_hidden=4), batch_size=8,
            outer_optimizer="nesterov", outer_lr=0.5, outer_momentum=0.9,
        )
        t._outer_transform({"w": np.full((3,), 10.0, np.float32)})
        assert t._outer_anchor is not None
        t.adopt_params(t.state.params, step=50)
        assert t._outer_anchor is None and t._outer_m is None
        # next round re-seeds: passes the average through unchanged
        out = t._outer_transform({"w": np.full((3,), 3.0, np.float32)})
        np.testing.assert_allclose(out["w"], 3.0)

    def test_outer_optimizer_overlap_path(self):
        """The overlap merge must apply the outer step to the ROUND result
        and ride the local-progress delta on top — and a staleness-dropped
        round must not touch the momentum stream. Drives the real
        _finish_overlap_round with fabricated completed futures, so the
        ordering (ok/staleness checks BEFORE the outer transform) is pinned
        deterministically."""
        import concurrent.futures

        import numpy as np

        t = Trainer(
            get_model("mnist_mlp", d_hidden=4), batch_size=8,
            averager=lambda p, s: p, overlap=True,
            outer_optimizer="nesterov", outer_lr=0.5, outer_momentum=0.9,
        )

        def payload_like(value):
            return jax.tree_util.tree_map(
                lambda x: np.full_like(np.asarray(x), value),
                t.bundle.avg_select(t.state.params),
            )

        def finish_with(averaged, launch_step, step_no):
            p0 = jax.tree_util.tree_map(
                np.asarray, t.bundle.avg_select(t.state.params)
            )
            fut = concurrent.futures.Future()
            fut.set_result((averaged, 0.01))
            t._inflight = (launch_step, p0, fut)
            t._finish_overlap_round(step_no)

        # round 1: seeds the anchor; no local steps taken since snapshot, so
        # params land exactly on the averaged tree
        finish_with(payload_like(10.0), 1, 1)
        for leaf in jax.tree_util.tree_leaves(t.state.params):
            np.testing.assert_allclose(np.asarray(leaf), 10.0)
        # round 2: v=7 -> Nesterov a' = 10 - 0.5*(0.9*3 + 3) = 7.15
        finish_with(payload_like(7.0), 2, 2)
        for leaf in jax.tree_util.tree_leaves(t.state.params):
            np.testing.assert_allclose(np.asarray(leaf), 7.15, rtol=1e-6)
        anchor_before = jax.tree_util.tree_leaves(t._outer_anchor)[0].copy()
        m_before = jax.tree_util.tree_leaves(t._outer_m)[0].copy()
        # stale round: dropped BEFORE the outer transform — anchor, momentum
        # and params all untouched
        t.max_staleness = 1
        finish_with(payload_like(0.0), 10, 20)
        np.testing.assert_array_equal(
            jax.tree_util.tree_leaves(t._outer_anchor)[0], anchor_before
        )
        np.testing.assert_array_equal(
            jax.tree_util.tree_leaves(t._outer_m)[0], m_before
        )
        for leaf in jax.tree_util.tree_leaves(t.state.params):
            np.testing.assert_allclose(np.asarray(leaf), 7.15, rtol=1e-6)

    def test_outer_optimizer_state_survives_checkpoint_resume(self, tmp_path):
        """The momentum stream persists across preemption (sidecar .npz
        beside the orbax snapshot): a resumed trainer continues the Nesterov
        sequence exactly where the saved one would have."""
        import numpy as np

        from distributedvolunteercomputing_tpu.training import checkpoint

        def make():
            return Trainer(
                get_model("mnist_mlp", d_hidden=4), batch_size=8,
                outer_optimizer="nesterov", outer_lr=0.5, outer_momentum=0.9,
            )

        def payload_like(t, value):
            return jax.tree_util.tree_map(
                lambda x: np.full_like(np.asarray(x), value),
                t.bundle.avg_select(t.state.params),
            )

        a = make()
        a._outer_transform(payload_like(a, 10.0))
        a._outer_transform(payload_like(a, 7.0))  # anchor now 7.15, m = 3
        checkpoint.save(a, str(tmp_path))
        b = make()
        assert checkpoint.maybe_restore(b, str(tmp_path))
        for la, lb in zip(
            jax.tree_util.tree_leaves(a._outer_anchor),
            jax.tree_util.tree_leaves(b._outer_anchor),
        ):
            np.testing.assert_array_equal(la, lb)
        # both continue identically: round 3 lands on the hand-checked 5.7925
        out_a = a._outer_transform(payload_like(a, 7.0))
        out_b = b._outer_transform(payload_like(b, 7.0))
        for la, lb in zip(
            jax.tree_util.tree_leaves(out_a), jax.tree_util.tree_leaves(out_b)
        ):
            np.testing.assert_allclose(la, lb, rtol=1e-7)
            np.testing.assert_allclose(np.asarray(lb), 5.7925, rtol=1e-6)
        # a mismatched schema re-seeds instead of loading garbage
        c = Trainer(
            get_model("mnist_mlp", d_hidden=8), batch_size=8,
            outer_optimizer="nesterov",
        )
        # restore params will fail template match before outer state matters;
        # drive the sidecar path directly with the wrong-schema trainer
        import os

        snap = os.path.join(str(tmp_path), f"step_{int(a.state.step)}")
        checkpoint._maybe_restore_outer_state(c, snap)
        assert c._outer_anchor is None  # re-seeded, not mis-loaded

    def test_outer_optimizer_rejects_grads_mode(self):
        import pytest

        with pytest.raises(ValueError, match="params"):
            Trainer(
                get_model("mnist_mlp", d_hidden=4), batch_size=8,
                averager=lambda p, s: p, average_what="grads",
                outer_optimizer="nesterov",
            )

    def test_checkpoint_gc_keeps_last_n(self, tmp_path, monkeypatch):
        """Periodic saves must not grow the directory without bound: after
        each save, all but the newest KEEP_LAST snapshots are removed, and
        restore still loads the newest."""
        import os

        from distributedvolunteercomputing_tpu.training import checkpoint

        monkeypatch.setattr(checkpoint, "KEEP_LAST", 3)
        t = Trainer(get_model("mnist_mlp", d_hidden=8), batch_size=8, lr=1e-2)
        batch_iter = iter(t.data_iter())
        for _ in range(5):
            t.state, _ = t._step_fn(t.state, next(batch_iter))
            checkpoint.save(t, str(tmp_path))
        dirs = sorted(os.listdir(tmp_path))
        assert dirs == ["step_3", "step_4", "step_5"], dirs
        t2 = Trainer(get_model("mnist_mlp", d_hidden=8), batch_size=8, lr=1e-2)
        assert checkpoint.maybe_restore(t2, str(tmp_path))
        assert int(t2.state.step) == 5
        # Stale HIGHER-step entries (reused dir / lagging second writer)
        # must never make GC eat the snapshot just written.
        os.makedirs(tmp_path / "step_1000")
        t.state, _ = t._step_fn(t.state, next(batch_iter))
        checkpoint.save(t, str(tmp_path))  # step 6
        assert "step_6" in os.listdir(tmp_path)
        assert "step_1000" in os.listdir(tmp_path)

    def test_eval_hook_records_held_out_loss(self, tmp_path):
        """eval_every: periodic held-out loss without updating params —
        recorded as 'eval' metrics events, params untouched by eval."""
        import json

        mpath = str(tmp_path / "m.jsonl")
        t = Trainer(
            get_model("mnist_mlp"), batch_size=32, lr=1e-2, optimizer="adam",
            seed=0, metrics_path=mpath, eval_every=5, eval_batches=2,
        )
        before_eval = t.evaluate()  # public API works standalone
        assert np.isfinite(before_eval)
        summary = t.run(steps=10, log_every=0)
        events = [
            json.loads(l) for l in open(mpath)
            if '"eval"' in l and "eval_loss" in l
        ]
        assert len(events) == 2  # steps 5 and 10
        losses = [e["eval_loss"] for e in events]
        assert all(np.isfinite(v) for v in losses)
        # training reduces held-out loss on the synthetic blobs task
        assert losses[-1] < before_eval
        # eval stream is held-out: a fresh trainer's eval batches differ from
        # its training batches (different fold of the seed)
        t2 = Trainer(get_model("mnist_mlp"), batch_size=4, seed=3)
        train_batch = next(iter(t2.data_iter()))
        import jax as _jax

        rng, k = _jax.random.split(t2._eval_rng)
        eval_batch = t2.bundle.make_batch(k, 4)
        assert not np.array_equal(np.asarray(train_batch["x"]), np.asarray(eval_batch["x"]))

    def test_init_seed_pins_shared_base_across_volunteer_seeds(self):
        # Config-5 semantics (BASELINE.json:11): every volunteer finetunes ONE
        # shared base, so different per-volunteer --seed values must still
        # produce IDENTICAL initial params (the frozen LoRA base is never
        # averaged), while the data streams differ.
        tiny = dict(vocab=64, max_len=16, d_model=32, n_heads=2, n_kv_heads=2,
                    n_layers=2, d_ff=64, lora_rank=2, remat=False)
        t0 = Trainer(get_model("llama_lora", **tiny), batch_size=4, seed=0)
        t1 = Trainer(get_model("llama_lora", **tiny), batch_size=4, seed=1)
        for a, b in zip(
            jax.tree_util.tree_leaves(t0.state.params),
            jax.tree_util.tree_leaves(t1.state.params),
        ):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
        b0 = next(iter(t0.data_iter()))
        b1 = next(iter(t1.data_iter()))
        assert not np.array_equal(np.asarray(b0["tokens"]), np.asarray(b1["tokens"]))
        # a distinct init_seed changes the init (it's a real knob, not dead)
        t2 = Trainer(get_model("llama_lora", **tiny), batch_size=4, seed=0, init_seed=7)
        leaves0 = jax.tree_util.tree_leaves(t0.state.params)
        leaves2 = jax.tree_util.tree_leaves(t2.state.params)
        assert any(
            not np.array_equal(np.asarray(a), np.asarray(b))
            for a, b in zip(leaves0, leaves2)
        )

    def test_overlap_round_runs_concurrently_and_merges_delta(self):
        """Overlapped averaging: the device keeps stepping while the WAN
        round is in flight, and the result is merged Moshpit-style as
        new = averaged + (current - snapshot)."""
        import threading

        def make_trainer(averager):
            return Trainer(
                get_model("mnist_mlp"), batch_size=8, seed=0,
                average_every=9, averager=averager, overlap=True,
            )

        def run_with(offset):
            release = threading.Event()
            seen = {}

            def averager(payload, step):
                seen["launch_step"] = step
                # True only if the train loop reached the LAST step while this
                # round was still in flight — i.e. compute really overlapped.
                seen["released_by_training"] = release.wait(timeout=60)
                return jax.tree_util.tree_map(
                    lambda x: np.asarray(x, np.float32) + offset, payload
                )

            t = make_trainer(averager)
            t.on_step = lambda tr, s: release.set() if s >= 10 else None
            t.run(steps=10, log_every=0)
            assert seen["launch_step"] == 9
            assert seen["released_by_training"], "train loop blocked on the round"
            return jax.tree_util.tree_map(np.asarray, t.state.params)

        # offset 0: averaged == snapshot -> merge must be a no-op vs local
        # trajectory; offset 1: every leaf exactly +1 vs the offset-0 run
        # (merge is the last action: the round drains after the final step).
        p_identity = run_with(0.0)
        p_shifted = run_with(1.0)
        for a, b in zip(
            jax.tree_util.tree_leaves(p_identity), jax.tree_util.tree_leaves(p_shifted)
        ):
            np.testing.assert_allclose(np.asarray(b), np.asarray(a) + 1.0, rtol=1e-6)

    @pytest.mark.parametrize("in_flight", [True, False])
    def test_loop_stays_one_step_ahead_while_a_round_is_in_flight(
        self, monkeypatch, in_flight
    ):
        """With an overlapped round in flight the loop waits, after each
        dispatch, for the step BEFORE the one just dispatched (the round's
        device programs and the merge must not wait a whole cadence behind
        steps dispatched ahead); with none in flight it never waits."""
        import threading

        release = threading.Event()

        def averager(payload, step):
            release.wait(timeout=60)
            return jax.tree_util.tree_map(lambda x: np.asarray(x, np.float32), payload)

        t = Trainer(
            get_model("mnist_mlp"), batch_size=8, seed=0,
            # a launch at step 3 that stays in flight to the end, or none at all
            average_every=3 if in_flight else 1000, averager=averager, overlap=True,
        )
        waited, dispatched = [], []
        real_step, real_block = t._step_fn, jax.block_until_ready

        def step_fn(state, batch):
            out = real_step(state, batch)
            dispatched.append(out[1]["loss"])
            return out

        def block(x):
            if not release.is_set():
                waited.append(x)
            return real_block(x)

        t._step_fn = step_fn
        monkeypatch.setattr(jax, "block_until_ready", block)
        t.on_step = lambda tr, s: release.set() if s >= 8 else None
        t.run(steps=8, log_every=0)
        if in_flight:
            # steps 4..8 ran with the round in flight; each waited for its predecessor
            assert [id(x) for x in waited] == [id(x) for x in dispatched[2:7]]
        else:
            assert waited == []

    def test_averager_callback_applied(self):
        calls = []

        def fake_averager(params, step):
            calls.append(step)
            # returns zeros — trainer must adopt them
            return jax.tree_util.tree_map(lambda x: np.zeros_like(np.asarray(x)), params)

        t = Trainer(
            get_model("mnist_mlp"),
            batch_size=8,
            average_every=5,
            averager=fake_averager,
        )
        t.run(steps=10, log_every=0)
        assert calls == [5, 10]
        # params adopted from averager at step 10... then no further steps ran
        leaf = jax.tree_util.tree_leaves(t.state.params)[0]
        assert float(jnp.abs(leaf).sum()) == 0.0


def test_trainer_param_dtype_bf16():
    """--param-dtype bfloat16: params AND optimizer moments run in bf16;
    training stays finite and integer leaves keep their dtypes."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from distributedvolunteercomputing_tpu.models import get_model
    from distributedvolunteercomputing_tpu.training.trainer import Trainer

    t = Trainer(
        get_model("mnist_mlp"), batch_size=16, lr=1e-2, optimizer="adam",
        param_dtype="bfloat16",
    )
    s = t.run(steps=5, log_every=0)
    assert np.isfinite(s["final_loss"])
    leaves = jax.tree_util.tree_leaves(t.state.params)
    assert all(
        l.dtype == jnp.bfloat16
        for l in leaves if jnp.issubdtype(l.dtype, jnp.floating)
    )
    # ...and the optimizer moments followed (the "halves param/optimizer
    # HBM" claim): every floating leaf of the opt state is bf16 too.
    opt_leaves = [
        l for l in jax.tree_util.tree_leaves(t.state.opt_state)
        if hasattr(l, "dtype") and jnp.issubdtype(l.dtype, jnp.floating)
    ]
    assert opt_leaves and all(l.dtype == jnp.bfloat16 for l in opt_leaves)
    # config-time validation of the dtype name
    import pytest

    from distributedvolunteercomputing_tpu.swarm.volunteer import VolunteerConfig

    with pytest.raises(ValueError, match="param-dtype"):
        VolunteerConfig(coordinator="x:1", param_dtype="float17")
    assert VolunteerConfig(coordinator="x:1", param_dtype="bfloat16").param_dtype


def test_param_dtype_reapplied_on_restore(tmp_path):
    """A snapshot taken at f32 restored into a --param-dtype bfloat16
    trainer must come back CAST: restoring the old dtype verbatim would
    flip the averaging schema hash away from same-config peers and strand
    the volunteer solo (round-5 review finding)."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from distributedvolunteercomputing_tpu.models import get_model
    from distributedvolunteercomputing_tpu.training import checkpoint
    from distributedvolunteercomputing_tpu.training.trainer import Trainer

    t1 = Trainer(get_model("mnist_mlp"), batch_size=8, lr=1e-2)
    t1.run(steps=2, log_every=0)
    checkpoint.save(t1, str(tmp_path))

    t2 = Trainer(
        get_model("mnist_mlp"), batch_size=8, lr=1e-2, param_dtype="bfloat16"
    )
    assert checkpoint.maybe_restore(t2, str(tmp_path))
    assert int(t2.state.step) == 2
    leaves = jax.tree_util.tree_leaves(t2.state.params)
    assert all(
        l.dtype == jnp.bfloat16
        for l in leaves if jnp.issubdtype(l.dtype, jnp.floating)
    )
    s = t2.run(steps=2, log_every=0)
    assert np.isfinite(s["final_loss"])
