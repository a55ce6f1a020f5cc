"""Unit tests for the small utils that earned their keep the hard way."""

import asyncio

from distributedvolunteercomputing_tpu.utils.jaxenv import enable_compile_cache
from distributedvolunteercomputing_tpu.utils.logging import errstr


class TestErrstr:
    def test_empty_message_exceptions_show_type(self):
        # The round-4 hardware overlap run logged 'averaging at step 90
        # failed: ' — a bare asyncio.TimeoutError whose str() is "".
        assert errstr(asyncio.TimeoutError()) == "TimeoutError"
        assert errstr(asyncio.CancelledError()) == "CancelledError"

    def test_message_exceptions_show_both(self):
        assert errstr(ValueError("boom")) == "ValueError: boom"
        assert errstr(OSError("plain")) == "OSError: plain"


class TestCompileCache:
    def test_disabled_off_tpu(self):
        # The XLA:CPU AOT cache failed machine-feature checks at load and
        # broke a swarm e2e when enabled unconditionally (see
        # utils/jaxenv.enable_compile_cache) — off TPU it must no-op.
        # conftest pins the suite to the CPU backend.
        import jax

        before = jax.config.jax_compilation_cache_dir
        assert enable_compile_cache() is None
        assert jax.config.jax_compilation_cache_dir == before

    def test_trainer_records_where_the_cache_is(self):
        # Off TPU: nowhere. The trainer carries the answer for its record.
        from distributedvolunteercomputing_tpu.models import get_model
        from distributedvolunteercomputing_tpu.training.trainer import Trainer

        t = Trainer(get_model("mnist_mlp"), batch_size=4)
        assert t.compile_cache_dir is None
        assert t.compile_summary()["cache_dir"] is None
