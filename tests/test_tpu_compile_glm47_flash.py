"""glm47-flash-solo-8k's whole train step, compiled for the described v5e of
``test_tpu_compile.py``.

A file of its own, as each of the six cells' steps that take one and a half to
two minutes to compile and share nothing with another test: under
``--dist loadfile`` the workers compile them side by side (3.5 to 6 GB of
host memory a compile) instead of one worker all six, and, being the files
with the fewest tests, after the files of many short tests.
"""

import re

import jax.numpy as jnp
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
    _share_chunks_hold_seven_grouped_matmuls,
    _step_holds_the_groups_its_cell_lists,
    v5e,
)


# ``slow`` since PR 58: one cell-size compile for a described v5e, 133 s of the tier-1 run's six
# workers and 3.5 to 6 GB of host memory, that shares nothing with another test; the run's other tests did not fit
# the command's limit beside the eight such compiles (ROADMAP D3). Run it before any chip run of a PR that touches a
# model's step: ``python -m pytest -m slow tests/test_tpu_compile*.py`` (the verify skill).
@pytest.mark.slow
def test_glm47_flash_step_runs_latent_attention_on_the_flash_kernels_at_a_head_of_256(v5e, as_on_the_chip, monkeypatch):
    """glm47-flash-solo-8k's step (published layers 0-4 of GLM-4.7-Flash at its
    published widths, eight of 64 experts held, an eighth of the vocabulary,
    2 x 8,192 tokens). A head of 256 at T=8,192 is the edge of what the
    whole-head-resident kernels hold: 1,024 x 1,024 blocks are 66.06e6 of the
    67.1e6-byte budget (the same resident bytes as D=128 at T=16,384), and the
    next doubling of either does not fit, and neither do the rotary tables of a
    call that turns q on the kernel's tile. Since PR 65 the model makes q, k and
    v ``[2, 8192, 20 x 256]`` by the projections' own products (the column
    orders, the key's zero lanes and the shared rotary key's 0/1 spread taken
    of the weights), turns q by one pass in that layout BESIDE the kernels
    (``dvc_rotary``, its cotangent ``dvc_rotary_back``) and calls
    ``attention_merged`` with no rotary: both traced layer shapes, the dense
    layer and ONE scanned expert layer for the four, are noted ``merged``, the
    kernels read ``bf16[2,8192,5120]`` where it lies, forward and backward only
    (``remat_layer`` kept the output and row statistics), and NOTHING by head
    (``[2,20,8192,..]``, ``[2,8192,20,..]``, the ``448``-wide key-value array)
    is left anywhere in the compiled step, loops' bodies included. The share's grouped matmuls see the
    dispatch's default chunk of 24,576 rows (three even shares of 8,192: the
    model's own reading refuted the levelled quarter), seven a traced expert
    layer. Arguments and temporaries are 15.55e9 (7.096 + 8.451; 16.18e9 until
    PR 64, which the chip still loaded and ran: PERF.md, Findings of PR 42 and
    PR 65): still OVER the 15.0e9 line of the other share cells' tests."""
    from distributedvolunteercomputing_tpu.models import glm4_moe_lite
    from distributedvolunteercomputing_tpu.ops import attention, moe_dispatch, pallas_attention

    t, d = 8192, 256
    assert 256 in attention._AUTO_FLASH_HEAD_DIMS
    assert pallas_attention.choose_blocks(t, t, d, jnp.bfloat16) == (1024, 1024)
    used = pallas_attention.vmem_bytes(t, t, d, jnp.bfloat16, 1024, 1024)
    assert used == 66_060_288 and 0.98 * pallas_attention.VMEM_BUDGET_BYTES < used <= pallas_attention.VMEM_BUDGET_BYTES
    # D=128 at T=16,384 (smallthinker-solo-16k) holds the same resident bytes and smaller streamed blocks: 61.0 MiB
    assert 63.9e6 < pallas_attention.vmem_bytes(2 * t, 2 * t, 128, jnp.bfloat16, 1024, 1024) < used
    assert pallas_attention.choose_blocks(2 * t, 2 * t, d, jnp.bfloat16) is None   # a head of 256 beyond 8,192: none
    assert pallas_attention.choose_blocks(t, t, 2 * d, jnp.bfloat16) is None
    monkeypatch.setattr(moe_dispatch, "tpu_backend", lambda: True)
    monkeypatch.setattr(moe_dispatch, "grouped_matmul_impl", lambda m, k, n: "megablox")
    with _noted("attention_core", *_CORE) as seen, _noted("remat_kept", *_KEPT) as kept:
        compiled = _lowered_step(v5e, "glm4_7_flash", 1, 1, 2, n_layers=5, experts_held=8, vocab=19360).compile()
    assert seen == [("flash", t, d, "none", 20, "merged", "none")] * 2, seen         # one traced dense layer, one traced scan body
    # the output at 20 x 256 a token and the f32 row statistics: 168.8 MB a layer, 845.4 MB a step
    assert kept == [(1, 169_082_880), (4, 4 * 169_082_880)], kept
    text = compiled.as_text()
    _step_holds_the_groups_its_cell_lists(text, "glm47-flash-solo-8k")
    calls = _kernel_calls(text)
    names = _kernel_names(calls)
    flash = sorted(n.split(".")[0] for n in names if n.startswith("dvc_flash"))
    assert flash == ["dvc_flash_bwd"] * 2 + ["dvc_flash_fwd"] * 2, flash
    assert all("bf16[2,8192,5120]" in ln and "[2,20,8192" not in ln for ln in calls if "dvc_flash_" in ln)
    # q alone is turned beside the kernels (the key's one vector of 64 by XLA before its spread): forward and
    # recomputed forward, and the cotangent's way back, a traced layer; delta in the merged layout
    turns = sorted(n.split(".")[0] for n in names if n.startswith("dvc_rotary"))
    assert turns == ["dvc_rotary"] * 4 + ["dvc_rotary_back"] * 2, turns
    assert sum(n.startswith("dvc_attn_delta") for n in names) == 2
    by_head = sorted(set(re.findall(r"\w+\[2,(?:20,8192|8192,20),\d+\]", text)))
    assert not by_head, by_head                          # a result or an operand, in the entry computation or any loop's body
    assert "f32[2,8192,5120]" not in text                # nor a float32 copy of a merged array
    fwd_blocks = pallas_attention.choose_blocks(t, t, d, jnp.bfloat16, turned=False)
    assert fwd_blocks == (1024, 1024) and pallas_attention.choose_blocks(t, t, d, jnp.bfloat16, turned=True) is None
    assert moe_dispatch.share_rows_bound(2 * t, 4, 8, 64, moe_dispatch.SHARE_ROWS_SLACK_LEVELLED) == 10240
    rows = moe_dispatch.share_rows_bound(2 * t, 4, 8, 64, glm4_moe_lite.SHARE_ROWS_SLACK)
    assert rows == 24576  # three even shares of 8,192: forty-eight megablox row tiles
    assert f"[{rows},2048]" in text and "[65536,2048]" not in text   # never the S x k assignments
    _share_chunks_hold_seven_grouped_matmuls(names, text, layers=1, rows=rows, d=2048, f=1536)
    mem = compiled.memory_analysis()
    assert mem.argument_size_in_bytes == pytest.approx(7.0957e9, rel=1e-3)  # float32 parameters and two Adam moments
    total = mem.argument_size_in_bytes + mem.temp_size_in_bytes
    # 15.548e9 since PR 65 (16.176e9 with q, k and v built by head: "nothing more fits", and the chip, which takes
    # about 16.9e9, ran it): the by-head copies and the 448-wide key-value array are what left
    assert total < 15.6e9 < 16.18e9, total
    # 8,452,265,984 (9,079,866,368 until PR 64; 9.219e9 at the levelled chunk)
    assert mem.temp_size_in_bytes < 8.46e9, mem.temp_size_in_bytes
