"""The spans inside the program: ``parent`` and self time, the train loop's
launch / merge / snapshot phases, the codec's device ops under the round that
called them, and the names the compiled programs carry into a profiler trace.

Counts and structure only: nothing here times a CPU run.
"""

import asyncio
import json
import os
import re

import jax
import numpy as np
import pytest

from distributedvolunteercomputing_tpu.models import get_model
from distributedvolunteercomputing_tpu.ops.mesh_codec import MeshCodec
from distributedvolunteercomputing_tpu.swarm import telemetry as T
from distributedvolunteercomputing_tpu.training.trainer import Trainer

from test_telemetry import run, spawn, teardown

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


# -- parent and self time ------------------------------------------------------


def _sp(name, t0, dur, parent=None, trace="r1"):
    d = {"trace": trace, "name": name, "peer": "v", "t0": t0, "dur_s": dur}
    if parent:
        d["parent"] = parent
    return d


class TestParentAndSelfTime:
    def test_self_time_on_a_hand_built_tree(self):
        spans = [
            _sp("loop.merge", 10.0, 4.0),
            _sp("loop.merge.d2h", 10.0, 1.0, "loop.merge"),
            _sp("loop.merge.host", 11.0, 1.5, "loop.merge"),
            # overlaps .host by 0.5 s: the union counts once
            _sp("loop.merge.h2d", 12.0, 1.0, "loop.merge"),
            # same names in another round do not leak into this one
            _sp("loop.merge", 50.0, 2.0, trace="r2"),
            _sp("loop.merge.host", 50.5, 1.0, "loop.merge", trace="r2"),
            # a child that outlives its parent is clipped to it
            _sp("codec.op", 20.0, 1.0),
            _sp("codec.run", 20.8, 0.5, "codec.op"),
            # names it but starts outside its interval: not its child
            _sp("codec.run", 30.0, 0.5, "codec.op"),
            _sp("wire", 40.0, None),
        ]
        got = T.self_seconds(spans)
        assert got[0] == pytest.approx(1.0)            # 4.0 - [10, 13]
        assert got[1:4] == [pytest.approx(x) for x in (1.0, 1.5, 1.0)]
        assert got[4] == pytest.approx(1.0) and got[5] == pytest.approx(1.0)
        assert got[6] == pytest.approx(0.8)
        assert got[7] == pytest.approx(0.5) and got[8] == pytest.approx(0.5)
        assert got[9] is None

    def test_parent_is_the_open_span_of_the_same_context_and_trace(self):
        tr = T.Tracer(peer_id="v")
        with tr.trace_scope("r1"), tr.span("encode"):
            with tr.phase("codec.op", op="x") as op:
                with tr.phase("codec.run"):
                    pass
            other = tr.start("wire", trace="r2")  # another trace: no parent
            other.end()
            late = tr.start("fold")
        late.end()  # started inside encode, ended after it
        after = tr.start("commit", trace="r1")  # encode has ended
        after.end()
        by = {s["name"]: s for s in tr.spans()}
        assert op.parent == "encode"
        assert by["codec.run"]["parent"] == "codec.op" and by["codec.run"]["trace"] == "r1"
        assert by["codec.op"]["parent"] == "encode"
        assert by["fold"]["parent"] == "encode"
        for name in ("encode", "wire", "commit"):
            assert "parent" not in by[name]

    def test_to_thread_carries_trace_and_parent(self):
        tr = T.Tracer(peer_id="v")

        def work():
            with tr.phase("codec.op"):
                pass

        async def main():
            with tr.trace_scope("r9"), tr.span("fetch"):
                await asyncio.to_thread(work)

        run(main())
        op = next(s for s in tr.spans() if s["name"] == "codec.op")
        assert op["trace"] == "r9" and op["parent"] == "fetch"

    def test_a_pending_span_is_recorded_once_adopted(self):
        tr = T.Tracer(registry=T.MetricsRegistry(), peer_id="v")
        with tr.phase("loop.launch", tr.PENDING) as launch:
            with tr.phase("loop.launch.d2h", tr.PENDING) as d2h:
                pass
        assert tr.spans() == [] and launch.dur_s is not None
        for sp in (launch, d2h):
            tr.adopt(sp, "r3")
        tr.adopt(launch, "r4")  # adopted once
        got = tr.spans()
        assert [(s["name"], s["trace"], s.get("parent")) for s in got] == [
            ("loop.launch", "r3", None), ("loop.launch.d2h", "r3", "loop.launch")]
        assert tr.registry.histogram("swarm.span_seconds").snapshot(
            span="loop.launch")["count"] == 1


# -- the train loop --------------------------------------------------------------


class FakeAverager:
    """Returns the payload itself as the average and leaves the round's key
    on the trainer, as the volunteer's callback does."""

    def __init__(self, keys):
        self.keys = list(keys)
        self.calls = 0
        self.trainer = None

    def __call__(self, payload, step):
        key = self.keys[self.calls]
        self.calls += 1
        self.trainer.round_trace = key
        return payload if key is not None else None


def _train(tracer, overlap, keys=("round-a",), steps=7, every=4):
    avg = FakeAverager(keys)
    tr = Trainer(
        get_model("mnist_mlp"), batch_size=8, optimizer="sgd", lr=1e-2,
        averager=avg, average_every=every, overlap=overlap, tracer=tracer,
    )
    avg.trainer = tr
    tr.run(steps=steps, log_every=5)
    return tr, avg


def merged_bytes(tr):
    return sum(x.nbytes for x in jax.tree_util.tree_leaves(tr.state.params))


def _inside(child, parent):
    eps = 1e-3  # t0 is rounded to the microsecond, and is another clock than dur_s
    return (parent["t0"] - eps <= child["t0"]
            and child["t0"] + child["dur_s"] <= parent["t0"] + parent["dur_s"] + eps)


class TestTrainLoopSpans:
    @pytest.mark.parametrize("overlap", [True, False], ids=["overlap", "blocking"])
    def test_one_round_is_one_tree_from_launch_to_merge(self, overlap):
        tracer = T.Tracer(registry=T.MetricsRegistry(), peer_id="v")
        tr, avg = _train(tracer, overlap)
        assert avg.calls == 1
        spans = tracer.spans()
        by_name = {}
        for s in spans:
            by_name.setdefault(s["name"], []).append(s)
        (launch,), (merge,) = by_name["loop.launch"], by_name["loop.merge"]
        assert launch["trace"] == merge["trace"] == "round-a"
        assert "parent" not in launch and "parent" not in merge
        (d2h,) = by_name["loop.launch.d2h"]
        assert d2h["trace"] == "round-a" and d2h["parent"] == "loop.launch"
        assert _inside(d2h, launch)
        children = [s for s in spans if s.get("parent") == "loop.merge"]
        # the live parameters never leave the device: no loop.merge.d2h
        want = {"loop.merge.host", "loop.merge.h2d", "loop.snapshot"}
        assert {c["name"] for c in children} == want
        assert len(children) == len(want)
        for c in children:
            assert c["trace"] == "round-a" and _inside(c, merge)
        assert merge["attrs"]["where"] == ("device" if overlap else "host")
        assert merge["attrs"]["bytes"] == merged_bytes(tr)
        assert launch["t0"] + launch["dur_s"] <= merge["t0"] + 1e-3
        # one snapshot per cadence boundary under `loop`; the merge's own
        # carries the round's key, the constructor's belongs to start-up
        loose = [s for s in by_name["loop.snapshot"] if s["trace"] == "loop"]
        assert all("parent" not in s for s in loose)
        # overlap: boundary 4 (the launch's); blocking: the boundary's
        # snapshot IS the merge's
        assert sorted(s["attrs"]["step"] for s in loose) == ([4] if overlap else [])
        (built,) = [s for s in by_name["loop.snapshot"] if s["trace"] == "lifecycle"]
        assert built["attrs"]["step"] == 0 and built["parent"] == "lifecycle.init"
        merged = [s for s in by_name["loop.snapshot"] if s["trace"] == "round-a"]
        assert len(merged) == 1 and merged[0]["attrs"]["bytes"] == merged_bytes(tr)
        # every device-side copy lands once, under `loop`, with no parent:
        # the launch's (which is boundary 4's snapshot too: one copy) and the
        # merge's; the constructor's snapshot reads the live buffers
        lands = by_name["loop.snapshot.land"]
        assert all(s["trace"] == "loop" and "parent" not in s for s in lands)
        merge_step = merged[0]["attrs"]["step"]
        assert sorted(s["attrs"]["step"] for s in lands) == sorted(
            [4, merge_step] if overlap else [merge_step])
        assert all(s["attrs"]["bytes"] == merged_bytes(tr) for s in lands)
        assert tr.host_snapshot()[0] == merge_step
        (sync,) = by_name["loop.log_sync"]
        assert sync["trace"] == "loop" and sync["attrs"] == {"step": 5}
        assert tracer.registry.histogram("swarm.span_seconds").snapshot(
            span="loop.merge")["count"] == 1

    def test_a_round_without_a_group_is_filed_under_loop(self):
        tracer = T.Tracer(peer_id="v")
        _train(tracer, overlap=True, keys=(None,))
        names = [(s["name"], s["trace"]) for s in tracer.spans()]
        assert ("loop.launch", "loop") in names and ("loop.launch.d2h", "loop") in names
        assert not any(n == "loop.merge" for n, _ in names)

    @pytest.mark.parametrize("tracer", [None, "disabled"])
    def test_no_span_and_no_annotation_without_a_live_tracer(self, tracer, monkeypatch):
        opened = []
        monkeypatch.setattr(T, "annotation", lambda name: opened.append(name))
        if tracer == "disabled":
            tracer = T.Tracer(registry=T.MetricsRegistry(), peer_id="v", enabled=False)
            hook = []
            tracer.on_record = hook.append
        tr, avg = _train(tracer, overlap=True)
        assert avg.calls == 1 and tr.mutation_counter == 1  # the round did merge
        assert opened == []
        if tracer is not None:
            assert tracer.spans() == [] and hook == []
            assert tracer.registry.histogram("swarm.span_seconds").snapshot(
                span="loop.merge") is None

    def test_the_per_step_phases_are_annotations_and_not_spans(self, monkeypatch):
        import contextlib

        opened = []

        def fake(name):
            opened.append(name)
            return contextlib.nullcontext()

        monkeypatch.setattr(T, "annotation", fake)
        tracer = T.Tracer(peer_id="v")
        _train(tracer, overlap=True, steps=7)
        assert opened.count("data") == opened.count("dispatch") == 7
        names = {s["name"] for s in tracer.spans()}
        assert "data" not in names and "dispatch" not in names
        # every span's annotation was opened under the same name, but for the
        # two that `Tracer.start` opens and another function or thread ends
        assert names - {"lifecycle", "lifecycle.first_batch"} <= set(opened)


# -- the codec under the round ---------------------------------------------------


class TestCodecSpans:
    def test_codec_ops_carry_the_rounds_trace_id(self):
        """A two-peer bf16 sync round on the mesh codec: every hop from the
        round into the codec is ``asyncio.to_thread``, which copies the
        context. This fails if one stops carrying it."""
        async def main():
            vols = await spawn(2, wire="bf16")
            for v in vols:
                v["avg"]._mesh_codec = MeshCodec(backend="mesh")
                v["avg"]._register_telemetry()
                assert v["avg"].mesh_codec.backend == "mesh"
            try:
                # payloads above one wire chunk (1 MiB of bf16): the member's
                # push encodes inside the transport's chunk iterator
                trees = [{"w": np.full((700_000,), float(i), np.float32)} for i in range(2)]
                for round_no in range(4):  # a leader may skip a round on a loaded machine
                    res = await asyncio.gather(
                        *(v["avg"].average(trees[i], round_no=round_no)
                          for i, v in enumerate(vols)))
                    if all(r is not None for r in res):
                        break
            finally:
                await teardown(vols)
            return vols, res

        vols, res = run(main())
        assert all(r is not None for r in res)
        for v in vols:
            spans = v["tele"].tracer.spans()
            rounds = {s["trace"] for s in spans if s["name"] == "round"}
            key = v["avg"].last_trace  # the round that committed
            assert key in rounds
            ops = [s for s in spans if s["name"] == "codec.op"]
            assert ops and v["avg"].mesh_codec.stats()["ops_mesh"] == len(ops)
            for s in spans:
                if s["name"].startswith("codec."):
                    assert s["trace"] in rounds, s
            for op in ops:
                assert op["attrs"]["op"] in ("encode_bf16", "decode_bf16", "folder_dense",
                                             "folder_flush")
                assert op["attrs"]["elems"] > 0, op
            spans = [s for s in spans if s["trace"] == key]
            ops = [s for s in spans if s["name"] == "codec.op"]
            parts = [s for s in spans if s.get("parent") == "codec.op"]
            assert {s["name"] for s in parts} >= {"codec.h2d", "codec.run", "codec.d2h"}
            assert all(any(_inside(p, op) for op in ops) for p in parts)
            # a codec span is never called `encode`: round.encode_s reads that name
            assert len([s for s in spans if s["name"] == "encode"]) == 1
        member = next(v for v in vols if any(
            s["name"] == "wire" and s["trace"] == v["avg"].last_trace
            for s in v["tele"].tracer.spans()))
        # the member's ops hang under the protocol phase that called them
        under = {(s["attrs"]["op"], s.get("parent")) for s in member["tele"].tracer.spans()
                 if s["name"] == "codec.op" and s["trace"] == member["avg"].last_trace}
        assert ("encode_bf16", "wire") in under  # the push, inside the chunk iterator
        assert {parent for _, parent in under} <= {"encode", "wire", "fetch"}

    def test_stats_no_longer_report_a_host_clock_as_device_time(self):
        codec = MeshCodec(backend="mesh")
        codec.encode_bf16(np.ones(1024, np.float32))
        assert "device_s" not in codec.stats() and not hasattr(codec, "device_s")
        assert codec.stats()["ops_mesh"] == 1


# -- names on the device -------------------------------------------------------------


class TestNamesOnTheDevice:
    def test_the_lowered_step_holds_the_four_scope_names(self):
        from distributedvolunteercomputing_tpu.training.optim import make_optimizer
        from distributedvolunteercomputing_tpu.training.steps import TrainState, make_train_step

        bundle = get_model("gpt2_small", n_layers=2, d_model=32, n_heads=2, d_ff=64,
                           vocab=128, max_len=32)
        tx = make_optimizer("adamw", lr=1e-3, total_steps=10)
        state = TrainState.create(bundle.init(jax.random.PRNGKey(0)), tx, jax.random.PRNGKey(1))
        batch = bundle.make_batch(jax.random.PRNGKey(2), 2)
        text = make_train_step(bundle.loss_fn, tx).lower(state, batch).as_text(debug_info=True)
        for scope in ("attention", "mlp", "loss_head", "optimizer"):
            assert re.search(rf'"jit\(step\)/[^"]*\b{scope}\b[^"]*"', text), scope

    def test_codec_program_names_are_stable_and_match_the_metrics_pattern(self):
        with open(os.path.join(REPO_ROOT, "benchmark", "layer_metrics",
                               "codec.device_ms.json")) as fh:
            pattern = re.compile(json.load(fh)["read"]["program"])
        codec = MeshCodec(backend="mesh")
        x = np.linspace(-1, 1, 4096, dtype=np.float32)
        bits = codec.encode_bf16(x)
        codec.decode_bf16(bits)
        codec.decode_axpy(np.zeros_like(x), bits, 0.5)
        stack = np.stack([x, x + 1, x + 2])
        codec.aggregate(stack, "mean")
        codec.aggregate(stack, "median")
        codec.aggregate_bits(np.stack([bits, bits, bits]), "trimmed_mean", trim=1)
        m = np.arange(32, dtype=np.float32).reshape(8, 4)
        p, q = codec.low_rank_iterate(m, np.ones((4, 2), np.float32))
        codec.lowrank_reconstruct(p, q)
        folder = codec.mean_folder(4096, 1024, 4, "bf16")
        folder.add_dense(x, 1.0)
        folder.add(0, 1.0, bits[:1024].tobytes())
        folder.result()
        assert codec.stats()["ops_host"] == 0 and not codec.degraded
        names = {"jit_" + fn.__name__ for fn in codec._jit_cache.values()}
        assert {"jit_encode_bf16", "jit_decode_bf16", "jit_body_dec_axpy", "jit_body_wmean",
                "jit_body_median", "jit_body_trimmed_mean", "jit_decode_bf16_stack",
                "jit_body_psgd_iter", "jit_body_psgd_rec", "jit_body_folder_dense",
                "jit_body_folder_flush"} == names
        for name in names:
            assert pattern.search(name), name
        # and that is the name XLA gives the module a trace shows
        enc = codec._jit_cache[("enc", False)].__wrapped__
        assert "module @jit_encode_bf16" in enc.lower(x).as_text()
