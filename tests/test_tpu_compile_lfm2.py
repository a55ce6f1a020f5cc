"""lfm2-solo-8k's whole train step, compiled for the described v5e of
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
    _kernel_calls,
    _kernel_names,
    _lowered_step,
    _noted,
    no_persistent_cache,
    _share_chunks_hold_seven_grouped_matmuls,
    _step_holds_the_groups_its_cell_lists,
    v5e,
)


# ``slow`` since PR 58: one cell-size compile for a described v5e, 72 s of the tier-1 run's six
# workers and 3.5 to 6 GB of host memory, that shares nothing with another test; the run's other tests did not fit
# the command's limit beside the eight such compiles (ROADMAP D3). Run it before any chip run of a PR that touches a
# model's step: ``python -m pytest -m slow tests/test_tpu_compile*.py`` (the verify skill).
@pytest.mark.slow
def test_lfm2_step_holds_its_kernels_one_trace_a_layer_shape(v5e, as_on_the_chip, monkeypatch):
    """lfm2-solo-8k's step (published layers 0 and 2-5 of LFM2-24B-A2B at its
    published widths, eight of 64 experts held, an eighth of the vocabulary,
    4 x 8,192 tokens): three traced layer shapes, the dense conv layer, the
    attention expert layer and ONE scanned conv expert layer for the three.
    The attention layer takes the flash kernel at head dim 64 with four query
    heads a key/value head, forward and backward only (its recomputed forward
    holds none: ``remat_layer`` kept the output and row statistics); each conv
    layer shape runs the convolution's kernel forward, again in the recomputed
    forward (it keeps nothing of its mixer) and backward: two shapes, six
    calls; the share's grouped matmuls see the levelled router's chunk of 20,480
    rows (the even share of 16,384 and a quarter: the model passes
    ``SHARE_ROWS_SLACK_LEVELLED``), never the dispatch's default of 49,152 nor
    the S x k = 131,072, seven a traced expert layer. That it compiles says it
    fits the chip; its temporaries are 6.186e9 (7.359e9 at 49,152 rows, PR 39)."""
    from distributedvolunteercomputing_tpu.models import lfm2
    from distributedvolunteercomputing_tpu.ops import moe_dispatch

    monkeypatch.setattr(moe_dispatch, "tpu_backend", lambda: True)
    monkeypatch.setattr(moe_dispatch, "grouped_matmul_impl", lambda m, k, n: "megablox")
    with _noted("attention_core", *_CORE) as seen:
        compiled = _lowered_step(
            v5e, "lfm2_24b_a2b", 1, 1, 4, n_layers=None, layer_types="conv,full_attention,conv,conv,conv",
            dense_layers=1, experts_held=8, vocab=8192).compile()
    assert set(seen) == {("flash", 8192, 64, "none", 8, "heads", "none")}, seen  # D = 64: the by-head entry
    text = compiled.as_text()
    _step_holds_the_groups_its_cell_lists(text, "lfm2-solo-8k")
    calls = _kernel_calls(text)
    names = _kernel_names(calls)
    flash = sorted(n.split(".")[0] for n in names if n.startswith("dvc_flash"))
    assert flash == ["dvc_flash_bwd", "dvc_flash_fwd"], flash
    assert all("bf16[4,32,8192,64]" in ln and "bf16[4,8,8192,64]" in ln for ln in calls if "dvc_flash_" in ln)
    conv = sorted(n.split(".")[0] for n in names if n.startswith("dvc_short_conv"))
    assert conv == ["dvc_short_conv_bwd"] * 2 + ["dvc_short_conv_fwd"] * 4, conv
    assert all("bf16[4,8192,6144]" in ln for ln in calls if "dvc_short_conv" in ln)
    assert moe_dispatch.share_rows_bound(4 * 8192, 4, 8, 64) == 49152  # the dispatch's default, three even shares
    rows = moe_dispatch.share_rows_bound(4 * 8192, 4, 8, 64, lfm2.SHARE_ROWS_SLACK)
    assert rows == 20480  # the even share of 16,384 and a quarter: forty megablox row tiles
    assert f"[{rows},2048]" in text and "[131072,2048]" not in text and "[49152,2048]" not in text
    _share_chunks_hold_seven_grouped_matmuls(names, text, layers=2, rows=rows, d=2048, f=1536)
    mem = compiled.memory_analysis()
    total = mem.argument_size_in_bytes + mem.temp_size_in_bytes + mem.output_size_in_bytes - mem.alias_size_in_bytes
    assert total < 13.5e9, total
    assert mem.temp_size_in_bytes <= 6.22e9, mem.temp_size_in_bytes  # 6.186e9; at 49,152 rows (PR 39) 7.359e9
