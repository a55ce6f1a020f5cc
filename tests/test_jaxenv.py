"""utils/jaxenv.py (where JAX runs, where it caches) and chip_smoke.py.

The smoke's phase functions run here at tiny size on the CPU: the test
supplies the device it expects and the kernels' interpret lowering through
``chip_smoke.Config`` — the script itself has no option for either, and run
as a script on a machine without a chip it must fail.
"""

import dataclasses
import json
import os
import subprocess
import sys

import jax
import pytest

import chip_smoke
from distributedvolunteercomputing_tpu.utils import jaxenv

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


class TestCompileCache:
    @pytest.fixture
    def on_tpu(self, monkeypatch):
        """enable_compile_cache as it behaves on the chip; the cache config
        it may touch is restored afterwards."""
        monkeypatch.setattr(jaxenv, "tpu_backend", lambda: True)
        before = (
            jax.config.jax_compilation_cache_dir,
            jax.config.jax_persistent_cache_min_compile_time_secs,
        )
        yield
        jax.config.update("jax_compilation_cache_dir", before[0])
        jax.config.update("jax_persistent_cache_min_compile_time_secs", before[1])

    def test_env_dir_is_left_to_jax(self, on_tpu, monkeypatch, tmp_path):
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path / "outside"))
        before = jax.config.jax_compilation_cache_dir
        assert jaxenv.enable_compile_cache() == str(tmp_path / "outside")
        assert jax.config.jax_compilation_cache_dir == before  # no directory set in code
        assert not (tmp_path / "outside").exists()

    def test_default_is_one_fixed_path_in_the_checkout(self, on_tpu, monkeypatch):
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
        first = jaxenv.enable_compile_cache()
        assert first == jaxenv.enable_compile_cache() == os.path.join(REPO, ".jax_cache")
        assert jax.config.jax_compilation_cache_dir == first
        # The same path from another process, started somewhere else.
        out = subprocess.run(
            [sys.executable, "-c",
             "from distributedvolunteercomputing_tpu.utils.jaxenv import "
             "COMPILE_CACHE_DIR; print(COMPILE_CACHE_DIR)"],
            cwd="/", env=dict(os.environ, PYTHONPATH=REPO), capture_output=True,
            text=True, timeout=120, check=True,
        )
        assert out.stdout.strip() == first

    def test_cache_dir_is_git_ignored(self):
        with open(os.path.join(REPO, ".gitignore")) as fh:
            assert ".jax_cache/" in fh.read().split()


def test_tpu_backend_false_on_cpu():
    assert jax.default_backend() == "cpu"  # conftest pins the suite there
    assert jaxenv.tpu_backend() is False


def test_device_record_names_the_cpu():
    assert jaxenv.device_record() == {
        "platform": "cpu", "device_kind": "cpu", "device_count": len(jax.devices()),
    }


def test_compile_log_counts_a_program_once():
    log = jaxenv.compile_log()
    assert log is jaxenv.compile_log()  # one per process

    def smoke_probe(x):
        return x * 2 + 1

    f = jax.jit(smoke_probe)
    f(1.0), f(2.0)  # second call: same shape, no recompilation
    s = log.summary("jit(smoke_probe)")
    assert s["program_compiles"] == 1 and s["programs"] >= 1
    f(jax.numpy.ones(3))  # a new shape compiles again
    assert log.summary("jit(smoke_probe)")["program_compiles"] == 2


# ---------------------------------------------------------------------------
# chip_smoke.py
# ---------------------------------------------------------------------------

TINY = ("n_layers=2", "d_model=64", "n_heads=4", "d_ff=128", "vocab=256", "max_len=64")


@pytest.fixture
def tiny_cfg(tmp_path):
    return chip_smoke.Config(
        platform="cpu", out_dir=str(tmp_path), model_overrides=TINY, vocab=256,
        seq_len=64, lr=1e-2, solo_steps=8, batch_size=4, attn_shape=(1, 2, 64, 16),
        # On the CPU the kernels can only run interpreted.
        codec_kwargs=(("backend", "mesh"), ("pallas", "interpret"), ("collective", "ring")),
        ring_tile_elems=2048, mesh_steps=3, mesh_loss_rtol=1e-4,
        solo_timeout_s=240, kernels_timeout_s=240, mesh_timeout_s=240,
        round_join_timeout_s=60, round_gather_timeout_s=30, round_timeout_s=240,
    )


CPU = {"platform": "cpu", "device_kind": "cpu"}


@pytest.mark.parametrize("phase", ["solo", "kernels", "round", "mesh"])
def test_chip_smoke_phase_at_tiny_size(tiny_cfg, phase, capsys, eight_devices):
    device = getattr(chip_smoke, f"phase_{phase}")(tiny_cfg)
    assert {k: device[k] for k in CPU} == CPU
    report = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert report["phase"] == phase and report["ok"] is True


def test_chip_smoke_phase_fails_on_the_wrong_device(tiny_cfg):
    """The children run on the CPU here; a config that expects the chip
    must fail the phase, not report it."""
    wrong = dataclasses.replace(tiny_cfg, platform="tpu")
    with pytest.raises(RuntimeError, match="rc="):  # JAX_PLATFORMS=tpu: no backend
        chip_smoke.phase_kernels(wrong)


def test_chip_smoke_script_fails_without_a_chip(tmp_path):
    """As the driver runs it, in a sandbox: non-zero, and no ok line."""
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO, "chip_smoke.py")],
        env=dict(os.environ, JAX_PLATFORMS="cpu"), cwd=str(tmp_path),
        capture_output=True, text=True, timeout=300,
    )
    assert proc.returncode != 0
    assert '"ok": true' not in proc.stdout
