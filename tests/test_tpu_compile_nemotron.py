"""nemotron3-nano-solo-8k's whole train step, compiled for the described v5e of
``test_tpu_compile.py``.

A file of its own, as each of the six cells' steps that take one and a half to
two minutes to compile and share nothing with another test: under
``--dist loadfile`` the workers compile them side by side (3.5 to 6 GB of
host memory a compile) instead of one worker all six, and, being the files
with the fewest tests, after the files of many short tests.
"""

import pytest

from tests.test_tpu_compile import (  # noqa: F401 — the fixtures are used by name
    as_on_the_chip,
    _CORE,
    _KEPT,
    _kernel_calls,
    _kernel_names,
    _lowered_step,
    _noted,
    no_persistent_cache,
    _step_holds_the_groups_its_cell_lists,
    v5e,
)


# ``slow`` since PR 58: one cell-size compile for a described v5e, 72 s of the tier-1 run's six
# workers and 3.5 to 6 GB of host memory, that shares nothing with another test; the run's other tests did not fit
# the command's limit beside the eight such compiles (ROADMAP D3). Run it before any chip run of a PR that touches a
# model's step: ``python -m pytest -m slow tests/test_tpu_compile*.py`` (the verify skill).
@pytest.mark.slow
def test_nemotron_step_holds_its_kernels_one_trace_a_unit_shape(v5e, as_on_the_chip, monkeypatch):
    """nemotron3-nano-solo-8k's step (published blocks 0-6, MEMEM*E, of
    Nemotron-3-Nano-30B-A3B at its published widths, eight of 128 experts held,
    an eighth of the vocabulary, 2 x 8,192 tokens): two traced unit shapes, a
    scan over the two ``ME`` and one ``M*E``, every block rematerialised by
    itself. Each traced state-space block runs the scan's kernel forward, again
    in its recomputed forward (it keeps nothing) and backward, and the
    convolution's likewise: two traces, six calls each. The attention block
    takes the flash kernel at a head of 128 with SIXTEEN query heads a key/value
    head, forward and backward only. The experts' width of 1,856 is no whole
    number of megablox's 128-column tiles (14.5), so the share's grouped
    products run padded: ``[7680, 3072] x [8, 3072, 2048]`` in tiles of 512 x
    1,024 x 1,024, seven a traced expert block, over the levelled router's
    chunk of 7,680 rows (the even share of 6,144 and a quarter), never the
    S x k = 98,304. That it compiles says it fits the chip."""
    from distributedvolunteercomputing_tpu.models import nemotron_h
    from distributedvolunteercomputing_tpu.ops import moe_dispatch, ssd

    monkeypatch.setattr(ssd, "tpu_backend", lambda: True)
    monkeypatch.setattr(moe_dispatch, "tpu_backend", lambda: True)
    monkeypatch.setattr(moe_dispatch, "grouped_matmul_impl", lambda m, k, n: "megablox")
    assert moe_dispatch._megablox_tiling(7680, 2688, 1856) is None and moe_dispatch._megablox_tiling(7680, 1856, 2688) is None
    assert (moe_dispatch._padded(2688), moe_dispatch._padded(1856)) == (3072, 2048)
    assert moe_dispatch._megablox_tiling(7680, 3072, 2048) == (512, 1024, 1024)
    with _noted("attention_core", *_CORE) as seen, _noted("remat_kept", *_KEPT) as kept:
        compiled = _lowered_step(v5e, "nemotron3_nano_30b_a3b", 1, 1, 2, n_layers=7, experts_held=8, vocab=16384).compile()
    assert seen == [("flash", 8192, 128, "none", 2, "heads", "none")], seen  # the model calls attention_core itself
    # what the blocks keep: the attention block's output and row statistics; a state-space block nothing
    assert kept == [(1, 2 * 32 * 8192 * (128 * 2 + 4))], kept
    text = compiled.as_text()
    _step_holds_the_groups_its_cell_lists(text, "nemotron3-nano-solo-8k")
    calls = _kernel_calls(text)
    names = [n.split(".")[0] for n in _kernel_names(calls)]
    assert sorted(n for n in names if n.startswith("dvc_flash")) == ["dvc_flash_bwd", "dvc_flash_fwd"]
    assert all("bf16[2,32,8192,128]" in ln and "bf16[2,2,8192,128]" in ln for ln in calls if "dvc_flash_" in ln)
    assert sorted(n for n in names if n.startswith("dvc_ssd")) == ["dvc_ssd_bwd"] * 2 + ["dvc_ssd_fwd"] * 4
    assert all("bf16[2,8192,6144]" in ln and "bf16[2,8192,4096]" in ln for ln in calls if "dvc_ssd_" in ln)
    assert "[2,64,8192,64]" not in text and "[2,8192,64,64]" not in text   # no stream by head: nothing to transpose
    assert sorted(n for n in names if n.startswith("dvc_short_conv")) == ["dvc_short_conv_bwd"] * 2 + ["dvc_short_conv_fwd"] * 4
    assert all("bf16[2,8192,6144]" in ln for ln in calls if "dvc_short_conv" in ln)
    rows = moe_dispatch.share_rows_bound(2 * 8192, 6, 8, 128, nemotron_h.SHARE_ROWS_SLACK)
    assert rows == 7680  # the even share of 6,144 and a quarter: fifteen row tiles
    assert f"[{rows},2688]" in text and f"[{rows},1856]" in text and "[98304,2688]" not in text
    from benchmark import moe_trace

    gmm = [n for n in names if moe_trace.GMM_RE.search(n)]
    assert len(gmm) == 7 * 2 and "ragged-dot" not in text, gmm      # two traced expert blocks, seven products each
    assert f"bf16[{rows},3072]" in text and "bf16[8,3072,2048]" in text and "bf16[8,2048,3072]" in text
    mem = compiled.memory_analysis()
    assert mem.argument_size_in_bytes == pytest.approx(6.3373e9, rel=1e-3)  # float32 parameters and two Adam moments
    assert mem.argument_size_in_bytes + mem.temp_size_in_bytes < 14.3e9     # at 4 x 8,192: 17.21e9, over the chip
