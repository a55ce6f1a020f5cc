"""qwen3-next-solo-8k's whole train step, compiled for the described v5e of
``test_tpu_compile.py``.

A file of its own, as each of the cells' steps that take one and a half to two
minutes to compile and share nothing with another test: under ``--dist
loadfile`` the workers compile them side by side (3.5 to 6 GB of host memory a
compile) instead of one worker all of them.
"""

import pytest

from tests.test_tpu_compile import (  # noqa: F401 — the fixtures are used by name
    as_on_the_chip,
    _CORE,
    _KEPT,
    _kernel_calls,
    _kernel_names,
    _level_products_by_loop,
    _lowered_step,
    _noted,
    no_persistent_cache,
    _step_holds_the_groups_its_cell_lists,
    v5e,
)


# ``slow`` as its neighbours since PR 58: one cell-size compile for a described v5e (about two minutes and 4 GB of
# host memory) that shares nothing with another test. Run it before any chip run of a PR that touches this model's
# step: ``python -m pytest -m slow tests/test_tpu_compile*.py`` (the verify skill).
@pytest.mark.slow
def test_qwen3_next_step_holds_its_kernels_and_no_q_or_k_at_the_value_heads_count(v5e, as_on_the_chip, monkeypatch):
    """qwen3-next-solo-8k's step (published layers 0-3 of
    Qwen3-Next-80B-A3B-Instruct at its published widths, sixteen of 512 experts
    held, an eighth of the vocabulary, 2 x 8,192 tokens): two traced layer
    shapes (the three delta layers as one scanned body, the attention layer),
    every layer rematerialised. The traced delta layer runs the scalar-decay
    scan's loop forward, again in its recomputed forward and backward, and its
    ONE convolution over all 8,192 channels likewise; the attention layer takes
    the flash kernels on the merged layout at a head of 256 in groups of eight,
    forward and backward only, q and k turned beside them. The lowered step holds
    no q or k at 32 heads at a stream's size and no decay by channel. That it
    compiles says it fits the chip."""
    from benchmark import gdn_trace, kda_trace, moe_trace
    from distributedvolunteercomputing_tpu.ops import moe_dispatch

    monkeypatch.setattr(moe_dispatch, "tpu_backend", lambda: True)
    monkeypatch.setattr(moe_dispatch, "grouped_matmul_impl", lambda m, k, n: "megablox")
    with _noted("attention_core", *_CORE) as seen, _noted("remat_kept", *_KEPT) as kept:
        compiled = _lowered_step(v5e, "qwen3_next_80b_a3b", 1, 1, 2, n_layers=4, experts_held=16, vocab=18992).compile()
    assert seen == [("flash", 8192, 256, "none", 2, "merged", "none")], seen
    # what the layers keep: the attention layer's output at 16 x 256 a token and its row statistics; a delta layer nothing
    assert kept == [(1, 2 * 16 * 8192 * (256 * 2 + 4))], kept
    text = compiled.as_text()
    _step_holds_the_groups_its_cell_lists(text, "qwen3-next-solo-8k")
    calls = _kernel_calls(text)
    names = [n.split(".")[0] for n in _kernel_names(calls)]
    assert sorted(n for n in names if n.startswith("dvc_flash")) == ["dvc_flash_bwd", "dvc_flash_fwd"]
    # q and k turned beside the kernels: forward, again in the recomputed forward (the layer keeps the kernel's results,
    # not its operands), and their cotangents turned back
    assert sorted(n for n in names if n.startswith("dvc_rotary")) == ["dvc_rotary"] * 4 + ["dvc_rotary_back"] * 2
    assert sorted(n for n in names if n.startswith("dvc_short_conv")) == ["dvc_short_conv_bwd"] + ["dvc_short_conv_fwd"] * 2
    assert all("bf16[2,8192,8192]" in ln for ln in calls if "dvc_short_conv" in ln)
    # the scan's loops, told as benchmark/gdn_trace.py tells them in a trace: ONE traced delta layer, forward twice
    # and backward, by the whole streams each carries (forward qkv, g, beta, o; backward those three, dO, three cotangents)
    scans = [shapes for shapes in (kda_trace.carried(ln.strip()) for ln in text.splitlines() if " while(" in ln)
             if (2, 32, 128, 128) in shapes]
    whole = sorted(sum(len(s) == 3 and s[:2] == (2, 8192) for s in shapes) for shapes in scans)
    assert whole == [4, 4, 7] and whole[1] <= gdn_trace.FORWARD_CARRIES_AT_MOST < whole[2], whole
    # q and k never at 32 heads at a stream's size (a loop's only [2, 8192, 4096] arrays are o or dO), the decay
    # never by channel, nothing of a stream's size by head
    for shapes in scans:
        assert sum(s == (2, 8192, 4096) for s in shapes) == 1, shapes
    # the mechanism of PR 68: a chunk's triangular inverse (five levels of two [64, 64] x [64, 64] products past the
    # first) is made in the two forward loops and NOT in the backward one, which takes every chunk's T over from the
    # recomputed forward ([128, 2, 16, 2, 64, 64] bfloat16 by chunk: a scan's xs, so the streams' counts above stand)
    assert sorted(_level_products_by_loop(text, (2, 32, 128, 128), 64)) == [0, 10, 10]
    assert "bf16[128,2,16,2,64,64]" in text
    for never in ("f32[2,8192,32,128]", "bf16[2,8192,32,128]", "bf16[2,32,8192,128]", "f32[2,8192,16,128]",
                  "bf16[2,8192,16,256]", "bf16[2,16,8192,256]"):
        assert never not in text, never
    rows = moe_dispatch.share_rows_bound(2 * 8192, 10, 16, 512)
    assert rows == 15360 and f"[{rows},2048]" in text and "[163840,2048]" not in text   # never the S x k assignments
    gmm = [n for n in names if moe_trace.GMM_RE.search(n)]
    assert len(gmm) == 7 * 2 and "ragged-dot" not in text, gmm      # two traced expert layers, seven products each
    mem = compiled.memory_analysis()
    assert mem.argument_size_in_bytes == pytest.approx(5.0922e9, rel=1e-3)  # float32 parameters and two Adam moments
    # 12.54e9 by this analysis (7.45e9 of temporaries): my compile, PR 68 (12.27e9 until a delta layer's backward
    # held its 128 chunks' T beside the states)
    assert mem.argument_size_in_bytes + mem.temp_size_in_bytes < 12.8e9
