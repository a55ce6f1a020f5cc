"""smallthinker-solo-16k's whole train step, compiled for the described v5e of
``test_tpu_compile.py``.

A file of its own, as each of the six cells' steps that take one and a half to
two minutes to compile and share nothing with another test: under
``--dist loadfile`` the workers compile them side by side (3.5 to 6 GB of
host memory a compile) instead of one worker all six, and, being the files
with the fewest tests, after the files of many short tests.
"""

import jax.numpy as jnp

import pytest

from tests.test_tpu_compile import (  # noqa: F401 — the fixtures are used by name
    as_on_the_chip,
    _CORE,
    _kernel_calls,
    _kernel_names,
    _lowered_step,
    _noted,
    _no_pass_over_a_head_shaped_array,
    no_persistent_cache,
    _share_chunks_hold_seven_grouped_matmuls,
    _step_holds_the_groups_its_cell_lists,
    v5e,
)


# ``slow`` since PR 58: one cell-size compile for a described v5e, 135 s of the tier-1 run's six
# workers and 3.5 to 6 GB of host memory, that shares nothing with another test; the run's other tests did not fit
# the command's limit beside the eight such compiles (ROADMAP D3). Run it before any chip run of a PR that touches a
# model's step: ``python -m pytest -m slow tests/test_tpu_compile*.py`` (the verify skill).
@pytest.mark.slow
def test_smallthinker_step_runs_every_layer_on_the_flash_kernels_at_16k(v5e, as_on_the_chip, monkeypatch):
    """smallthinker-solo-16k's step (one period of SmallThinker-21BA3B at its
    published widths, eight of 64 experts held, an eighth of the vocabulary,
    2 x 16,384 tokens): at T=16,384 the whole-head-resident kernels still fit
    their VMEM budget (1,024 x 1,024 blocks with or without the 4,096 window),
    so both layer kinds take them, 28 query heads over 4 key/value heads, and
    no layer falls to the XLA core's [28, 16384, 16384] scores. The model is
    scanned by period with an inner scan over the three sliding layers: ONE
    windowed and ONE full kernel, each forward and backward, whatever the
    depth. The share's grouped matmuls see the bounded chunk of 104,448 rows
    (the model's own slack, 4.25 times the even share: models/smallthinker.py),
    never the S x k = 196,608, seven a traced layer; arguments and temporaries
    stay under 15.0e9. The temporaries: 9.5213e9 before PR 36, 9.3057e9 with
    it, 9.6039e9 since PR 38, whose step needs LESS at once (XLA's live-range
    peak 10.598e9 against 10.781e9 with the arguments; two ``[rows, d]``
    buffers in a run's sum where the shifted adds held three) and whose heap
    packs worse: the scheduler now runs the down stack's ``tgmm`` after the
    run's product, the heap simulator lays 0.24e9 more out, and a tile of 256
    rows compiles to the same (PERF.md, Findings of PR 38)."""
    from distributedvolunteercomputing_tpu.models import smallthinker
    from distributedvolunteercomputing_tpu.ops import moe_dispatch, pallas_attention

    t, d = 16384, 128
    for window in (None, 4096):
        assert pallas_attention.choose_blocks(t, t, d, jnp.bfloat16, window) == (1024, 1024)
    used = pallas_attention.vmem_bytes(t, t, d, jnp.bfloat16, 1024, 1024)
    assert 0.9 * pallas_attention.VMEM_BUDGET_BYTES < used <= pallas_attention.VMEM_BUDGET_BYTES
    # the sliding layers turn their q: the forward's table blocks on top still fit (63.0 of 64 MiB)
    assert pallas_attention.choose_blocks(t, t, d, jnp.bfloat16, 4096, turned=True) == (1024, 1024)
    assert pallas_attention.choose_blocks(2 * t, 2 * t, d, jnp.bfloat16) is None  # the next doubling does not fit
    monkeypatch.setattr(moe_dispatch, "tpu_backend", lambda: True)
    monkeypatch.setattr(moe_dispatch, "grouped_matmul_impl", lambda m, k, n: "megablox")
    with _noted("attention_core", *(label for label in _CORE if label != "D")) as seen:
        compiled = _lowered_step(
            v5e, "smallthinker_21b_a3b", 1, 1, 2, n_layers=4, experts_held=8, vocab=18992).compile()
    # both layer kinds on the projections' own arrays; the sliding layers' q turned on the kernel's tile
    assert sorted(set(seen), key=str) == [
        ("flash", t, "none", 4, "merged", "none"), ("flash", t, 4096, 4, "merged", "kernel")], seen
    text = compiled.as_text()
    _step_holds_the_groups_its_cell_lists(text, "smallthinker-solo-16k")
    calls = _kernel_calls(text)
    flash = sorted(n.split(".")[0] for n in _kernel_names(calls) if n.startswith("dvc_flash"))
    assert flash == ["dvc_flash_bwd", "dvc_flash_fwd", "dvc_flash_win_bwd", "dvc_flash_win_fwd"], flash
    # since PR 59 on the projections' own arrays: 28 query heads and 4 key/value heads side by side
    assert all("bf16[2,16384,3584]" in ln and "bf16[2,16384,512]" in ln for ln in calls if "dvc_flash_" in ln)
    _no_pass_over_a_head_shaped_array(text, 2, t, (28, 4))
    assert moe_dispatch.share_rows_bound(2 * t, 6, 8, 64) == 73728  # the dispatch's default, three even shares
    rows = moe_dispatch.share_rows_bound(2 * t, 6, 8, 64, smallthinker.SHARE_ROWS_SLACK)
    assert rows == 104448  # 3.19 S: three held experts that each take every token fit one chunk
    assert f"[{rows},2560]" in text and "[196608,2560]" not in text and "[73728,2560]" not in text
    # one trace a layer kind: the scan's body holds each kind's loops once
    _share_chunks_hold_seven_grouped_matmuls(_kernel_names(calls), text, layers=2, rows=rows, d=2560, f=768)
    mem = compiled.memory_analysis()
    assert mem.argument_size_in_bytes + mem.temp_size_in_bytes < 15.0e9, (
        mem.argument_size_in_bytes, mem.temp_size_in_bytes)
    # 9.6039e9 until PR 59, 9.0362e9 since (no by-head copy of q, no float32 halves around the sliding layers' kernels)
    assert mem.temp_size_in_bytes <= 9.05e9, mem.temp_size_in_bytes
