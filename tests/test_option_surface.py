"""The option surface: which environment variables and flags exist at all.

Each kernel and backend is chosen in one function from what the process
observes (``mesh_codec.choose_data_path``, ``RingMeanFolder._resolve_lower``,
``attention._route_to_flash``, ``native.get_lib``); a test forces a path by a
constructor argument or by patching that function. A new variable or flag has
to be argued for in a diff of this file.
"""

import importlib.util
import os
import re
import sys

import pytest

from distributedvolunteercomputing_tpu import native
from distributedvolunteercomputing_tpu.ops import attention, mesh_codec, mesh_collective

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
PACKAGE = os.path.join(REPO, "distributedvolunteercomputing_tpu")
ROOT_SCRIPTS = ("run_volunteer.py", "coordinator.py", "chip_smoke.py", "__graft_entry__.py")

# Logging, fault injection for tests, an operator's profiler hook, the
# slow-test switch and the one C++ opt-in. None selects a kernel or a backend.
DVC_NAMES = {
    "DVC_ASYNC_DEBUG",
    "DVC_CHAOS_CONTRIB_SCALE",
    "DVC_CHAOS_LEADER_DIE_PHASE",
    "DVC_CHAOS_SHARD_DIE_PHASE",
    "DVC_CHAOS_STATE_POISON",
    "DVC_CKPT_KEEP",
    "DVC_CLOCK_SKEW_S",
    "DVC_LOGLEVEL",
    "DVC_LOG_JSON",
    "DVC_PROFILE_DIR",
    "DVC_PROFILE_START",
    "DVC_PROFILE_STEPS",
    "DVC_STEP_DELAY_MS",
    "DVC_TOPK_NATIVE",
    "DVC_RUN_SLOW",
}


def _read(path):
    with open(path, encoding="utf-8") as f:
        return f.read()


def test_the_dvc_variables_are_these_fifteen():
    paths = [os.path.join(REPO, name) for name in ROOT_SCRIPTS]
    paths.append(os.path.join(REPO, "tests", "conftest.py"))
    for root, _dirs, files in os.walk(PACKAGE):
        paths += [os.path.join(root, f) for f in files if f.endswith((".py", ".cpp"))]
    found = set()
    for path in paths:
        found |= set(re.findall(r"DVC_[A-Z0-9_]+", _read(path)))
    assert found == DVC_NAMES
    assert not os.path.exists(os.path.join(REPO, "bench.py"))


def test_run_volunteer_has_68_options():
    # The parser is built inside main(): count the calls in the source.
    # 68 since PR 39: --warmup-steps, which make_optimizer always took and no caller could set. The
    # lfm2-solo-8k cell's volunteer needs 2,000 where the six older cells' need 0 (a router's selection
    # bias levels the load inside an LR warm-up and is outrun without one: PERF.md section 6).
    assert _read(os.path.join(REPO, "run_volunteer.py")).count("add_argument(") == 68


@pytest.mark.parametrize(
    "argv", [["--mesh-codec", "host"], ["--mesh-collective", "off"]], ids=lambda a: a[0]
)
def test_removed_flags_are_refused_before_anything_is_built(monkeypatch, argv):
    import run_volunteer

    def built(cfg):
        raise AssertionError(f"a volunteer was built under {argv}")

    monkeypatch.setattr(run_volunteer, "run_volunteer", built)
    monkeypatch.setattr(native, "ensure_built", lambda *a, **k: pytest.fail("native built"))
    monkeypatch.setattr(sys, "argv", ["run_volunteer.py", "--averaging", "none", *argv])
    with pytest.raises(SystemExit) as e:
        run_volunteer.main()
    assert e.value.code == 2


def _fresh(module):
    """A second copy of ``module`` executed now, under the environment as it
    is: what an import-time read would see. The imported module (and the
    classes other tests hold from it) stays as it was."""
    spec = importlib.util.spec_from_file_location("_fresh_" + module.__name__, module.__file__)
    copy = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(copy)
    return copy


def _codec_choice():
    s = mesh_codec.MeshCodec().stats()
    return s["configured"], s["pallas"], s["collective"]


def _ring_lowering():
    # the CPU's own choice of codec: no kernels, so the ring is left to XLA
    return mesh_collective.RingMeanFolder._resolve_lower(mesh_codec.MeshCodec())


def _ring_vmem_cap():
    return _fresh(mesh_collective)._VMEM_CAP_BYTES


def _attention_impl():
    return _fresh(attention).get_attention_impl()


def _native_loads():
    return _fresh(native).ensure_built()


# Each at a value that changed the answer on the CPU these tests run on.
@pytest.mark.parametrize(
    "setting,choice",
    [
        pytest.param(setting, choice, id=setting)
        for setting, choice in (
            ("DVC_MESH_CODEC=1", _codec_choice),
            ("DVC_MESH_PALLAS=interpret", _codec_choice),
            ("DVC_MESH_COLLECTIVE=ring", _codec_choice),
            ("DVC_RING_LOWER=pallas", _ring_lowering),
            ("DVC_RING_VMEM_MB=1", _ring_vmem_cap),
            ("DVC_ATTN_IMPL=flash", _attention_impl),
            ("DVC_NATIVE=0", _native_loads),
        )
    ],
)
def test_a_removed_variable_changes_no_choice(monkeypatch, setting, choice):
    name, value = setting.split("=")
    monkeypatch.delenv(name, raising=False)
    unset = choice()
    monkeypatch.setenv(name, value)
    assert choice() == unset


def test_choices_on_the_cpu_and_as_on_the_chip(monkeypatch):
    from distributedvolunteercomputing_tpu.utils import jaxenv

    assert mesh_codec.choose_data_path(4) == ("host", "off", "off")
    assert _ring_lowering() == "xla" and _ring_vmem_cap() == 10 << 20
    assert _attention_impl() == "auto"
    monkeypatch.setattr(jaxenv, "tpu_backend", lambda: True)
    assert mesh_codec.choose_data_path(1) == ("mesh", "compiled", "off")
    assert mesh_codec.choose_data_path(4) == ("mesh", "compiled", "ring")
    chip = mesh_codec.MeshCodec()
    assert mesh_collective.RingMeanFolder._resolve_lower(chip) == "compiled"
    # an argument overrides its part and only that
    forced = mesh_codec.MeshCodec(backend="host", collective="ring").stats()
    assert (forced["configured"], forced["pallas"], forced["collective"]) == (
        "host", "compiled", "ring")


@pytest.mark.parametrize(
    "kwargs",
    [{"backend": "auto"}, {"pallas": "on"}, {"pallas": "1"},
     {"collective": "auto"}, {"collective": "host"}],
    ids=lambda k: "%s=%s" % next(iter(k.items())),
)
def test_codec_takes_only_the_values_it_reports(kwargs):
    with pytest.raises(ValueError, match="unknown mesh-codec"):
        mesh_codec.MeshCodec(**kwargs)
