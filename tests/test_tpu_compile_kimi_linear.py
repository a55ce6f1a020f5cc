"""kimi-linear-solo-8k's whole train step, compiled for the described v5e of
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


# ``slow`` since PR 58: one cell-size compile for a described v5e, 135 s of the tier-1 run's six
# workers and 3.5 to 6 GB of host memory, that shares nothing with another test; the run's other tests did not fit
# the command's limit beside the eight such compiles (ROADMAP D3). Run it before any chip run of a PR that touches a
# model's step: ``python -m pytest -m slow tests/test_tpu_compile*.py`` (the verify skill).
@pytest.mark.slow
def test_kimi_linear_step_holds_its_kernels_one_trace_a_layer_shape(v5e, as_on_the_chip, monkeypatch):
    """kimi-linear-solo-8k's step (published layers 1-5 of
    Kimi-Linear-48B-A3B-Instruct at its published widths, eight of 256 experts
    held, an eighth of the vocabulary, 2 x 8,192 tokens): four traced layer
    shapes (layer 1, layers 2-3 as one scanned body, layer 4, layer 5), every
    layer rematerialised. Each traced KDA layer runs the scan's loop forward,
    again in its recomputed forward (it keeps nothing) and backward, and each of
    its three convolutions' kernels likewise; the latent layer takes the flash kernel at
    keys of 192 over values of 128, forward and backward only. The share's
    grouped products see the dispatch's default chunk of 12,288 rows (three even
    shares of 4,096), seven a traced expert layer. That it compiles says it fits
    the chip."""
    from benchmark import kda_trace
    from distributedvolunteercomputing_tpu.models import kimi_linear
    from distributedvolunteercomputing_tpu.ops import kda, moe_dispatch

    monkeypatch.setattr(kda, "tpu_backend", lambda: True)     # bfloat16 products as the chip takes them
    monkeypatch.setattr(moe_dispatch, "tpu_backend", lambda: True)
    monkeypatch.setattr(moe_dispatch, "grouped_matmul_impl", lambda m, k, n: "megablox")
    with _noted("attention_core", *_CORE) as seen, _noted("remat_kept", *_KEPT) as kept:
        compiled = _lowered_step(v5e, "kimi_linear_48b_a3b", 1, 1, 2, n_layers=5, experts_held=8, vocab=20480).compile()
    assert seen == [("flash", 8192, 192, "none", 32, "heads", "none")], seen  # a key of 192: the by-head entry
    # what the layers keep: the latent layer's output at 32 x 128 a token and its row statistics; a KDA layer nothing
    assert kept == [(1, 2 * 32 * 8192 * (128 * 2 + 4))], kept
    text = compiled.as_text()
    _step_holds_the_groups_its_cell_lists(text, "kimi-linear-solo-8k")
    calls = _kernel_calls(text)
    names = [n.split(".")[0] for n in _kernel_names(calls)]
    assert sorted(n for n in names if n.startswith("dvc_flash")) == ["dvc_flash_bwd", "dvc_flash_fwd"]
    # the scan's loops, told as benchmark/kda_trace.py tells them in a trace: three traced KDA layers, each forward twice and backward
    scans = [shapes for shapes in (kda_trace.carried(ln.strip()) for ln in text.splitlines() if " while(" in ln)
             if (2, 32, 128, 128) in shapes]
    # nine loops carry the heads' states. Since PR 55 each takes its chunks out of the whole streams it carries
    # ([2, 8192, 4096]) and holds by chunk the states alone, so the reader's count (more than FORWARD_HOLDS_AT_MOST
    # arrays by chunk: a backward loop) calls none of the nine backward: kda.roofline's least time is nine forward
    # loops' where three are backward ones (PERF.md section 7). By the whole streams they carry the three are plain:
    # q, k, v, g and o forward; q, k, v, g, dO and the four cotangents backward
    assert [sum(s[:2] == (128, 2) for s in shapes) for shapes in scans] == [1] * 9 and kda_trace.FORWARD_HOLDS_AT_MOST == 8
    assert sorted(sum(s == (2, 8192, 4096) for s in shapes) for shapes in scans) == [5] * 6 + [9] * 3
    assert sorted(n for n in names if n.startswith("dvc_short_conv")) == ["dvc_short_conv_bwd"] * 9 + ["dvc_short_conv_fwd"] * 18
    assert all("bf16[2,8192,4096]" in ln for ln in calls if "dvc_short_conv" in ln)
    rows = moe_dispatch.share_rows_bound(2 * 8192, 8, 8, 256, kimi_linear.SHARE_ROWS_SLACK)
    assert rows == 12288 and f"[{rows},2304]" in text and "[131072,2304]" not in text   # never the S x k assignments
    from benchmark import moe_trace

    gmm = [n for n in names if moe_trace.GMM_RE.search(n)]
    assert len(gmm) == 7 * 3 and "ragged-dot" not in text, gmm      # three traced expert layers, seven products each
    mem = compiled.memory_analysis()
    assert mem.argument_size_in_bytes == pytest.approx(7.2296e9, rel=1e-3)  # float32 parameters and two Adam moments
    # 15.37e9 by this analysis (8.14e9 of temporaries; 17.16e9 and 9.93e9 until PR 55 took the streams' by-chunk and
    # by-head copies out): under the 17.16e9 that the chip's own compile loaded and ran beside the reference check
    # (memory_peak_bytes 15.04e9 of 16.9e9 then, 15.02e9 now: my chip runs, PR 52 calls 9-10, PR 55 call 1)
    assert mem.argument_size_in_bytes + mem.temp_size_in_bytes < 15.6e9
    # 8,142,901,760: no more than before the head made its gradients in its loss's loop (8,142,934,016 at PR 60)
    assert mem.temp_size_in_bytes <= 8_142_934_016, mem.temp_size_in_bytes
