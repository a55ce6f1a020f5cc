"""xing4-solo's whole train step, compiled for the described v5e of
``test_tpu_compile.py``.

A file of its own, as each of the cells' steps that take minutes to compile and
share nothing with another test (``tests/test_tpu_compile_glm47_flash.py`` has
the reason).
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
    _step_holds_the_groups_its_cell_lists,
    v5e,
)


# ``slow`` as every cell-size compile since PR 58: four minutes of one worker and 6 GB of host memory that
# share nothing with another test. Run it before any chip run of a PR that touches this model's step:
# ``python -m pytest -m slow tests/test_tpu_compile*.py`` (the verify skill).
@pytest.mark.slow
def test_xing4_step_carries_four_streams_in_bf16_and_runs_latent_attention_merged_at_a_padded_key(
        v5e, as_on_the_chip, monkeypatch):
    """xing4-solo's step (published layers 1-5 of Xing4.0-29B-A4B at its
    published widths, eight of 64 experts held, an eighth of the vocabulary,
    1 x 4,096 tokens). The four residual streams are ONE ``bf16[1,4096,14336]``
    array: what a rematerialised layer keeps at its boundary is that (the
    scanned layers' stack ``bf16[4,1,4096,14336]``) and never a float32 copy
    of it, and nothing ``[.., 4, 3584]`` (the streams on the sublanes) exists.
    Latent attention reaches the flash kernels in the merged layout with the
    key padded to 256 lanes through the weights (q and k ``bf16[1,4096,8192]``,
    v and the output ``bf16[1,4096,4096]``): both traced layer shapes are noted
    ``merged``, q turned beside the kernels as GLM's, and nothing by head
    (``[1,32,4096,..]``, ``[1,4096,32,..]``) is left in the compiled step. The
    step's scope map holds the ``residual`` group forward, recomputed and
    backward. Arguments and temporaries: 16.50e9 at this length (the rule's
    other length, 8,192, reads 19.30e9: ``experiments/step_memory.py``)."""
    from benchmark import moe_trace
    from distributedvolunteercomputing_tpu.models import xing4
    from distributedvolunteercomputing_tpu.ops import moe_dispatch, pallas_attention
    from distributedvolunteercomputing_tpu.utils import step_scopes

    t = 4096
    cfg = xing4.Xing4Config()
    assert (cfg.head_dim, cfg.head_pad) == (192, 64)
    assert pallas_attention.heads_a_block(256, 128, 32, 32) == 1 and pallas_attention.heads_a_block(192, 128, 32, 32) == 0
    assert pallas_attention.choose_blocks(t, t, 256, jnp.bfloat16) == (1024, 1024)
    monkeypatch.setattr(moe_dispatch, "tpu_backend", lambda: True)
    monkeypatch.setattr(moe_dispatch, "grouped_matmul_impl", lambda m, k, n: "megablox")
    with _noted("attention_core", *_CORE) as seen, _noted("remat_kept", *_KEPT) as kept:
        compiled = _lowered_step(v5e, "xing4_29b_a4b", 1, 1, 1, n_layers=5, dense_layers=1, experts_held=8,
                                 vocab=16384, max_len=t).compile()
    assert seen == [("flash", t, 256, "none", 32, "merged", "none")] * 2, seen   # one traced dense layer, one scan body
    # the output at 32 x 128 a token and the f32 row statistics a head: 34.1 MB a layer
    assert kept == [(1, t * (4096 * 2 + 32 * 4)), (4, 4 * t * (4096 * 2 + 32 * 4))], kept
    text = compiled.as_text()
    calls = _kernel_calls(text)
    names = _kernel_names(calls)
    flash = sorted(n.split(".")[0] for n in names if n.startswith("dvc_flash"))
    assert flash == ["dvc_flash_bwd"] * 2 + ["dvc_flash_fwd"] * 2, flash
    assert all("bf16[1,4096,8192]" in ln and "bf16[1,4096,4096]" in ln for ln in calls if "dvc_flash_" in ln)
    turns = sorted(n.split(".")[0] for n in names if n.startswith("dvc_rotary"))
    assert turns == ["dvc_rotary"] * 4 + ["dvc_rotary_back"] * 2, turns
    by_head = sorted(set(re.findall(r"\w+\[1,(?:32,4096|4096,32),\d+\]", text)))
    assert not by_head, by_head
    # the streams: one bf16 array of 14,336 lanes a token; no float32 copy of it outlives a fusion, none by stream
    # (of the instructions that write a buffer of their own: a float32 value INSIDE a fusion is a register's)
    got = step_scopes.scope_map(text)
    written = " ".join(rec["result"] for rec in got.values())
    assert "bf16[1,4096,14336]" in written and "bf16[4,1,4096,14336]" in written
    assert "f32[1,4096,14336]" not in written and "f32[4,1,4096,14336]" not in written
    assert "[1,4096,4,3584]" not in written
    rows = moe_dispatch.share_rows_bound(t, 4, 8, 64, xing4.SHARE_ROWS_SLACK)
    assert rows == 6144 and f"[{rows},3584]" in text     # three even shares of 2,048
    # NINE grouped matmuls a traced expert layer where GLM's runs seven: the mix that follows the experts reads
    # their result again in its backward (dHpost = <dX', y>), so the recomputed forward runs the two forward
    # products once more (``moe`` refwd in a traced run; ROADMAP R5 (n): keeping y would cost 29 MB a layer)
    gmm = [n.split(".")[0] for n in names if moe_trace.GMM_RE.search(n)]
    assert sorted(gmm) == sorted(["gmm"] * 4 + ["jvp_jit_gmm__"] + ["transpose_jvp_jit_gmm___"] * 2
                                 + ["transpose_jvp_jit_tgmm___"] * 2), gmm
    assert f"bf16[{rows},2048]" in text and f"[{rows + 1},3584]" not in text   # gate and up one product 2 f wide
    _step_holds_the_groups_its_cell_lists(text, "xing4-solo")
    passes = {rec["pass"] for rec in got.values() if rec["scope"] == "hc"}
    assert passes == {"fwd", "refwd", "bwd"}, passes
    mem = compiled.memory_analysis()
    assert mem.argument_size_in_bytes == pytest.approx(9.1139e9, rel=1e-3)  # float32 parameters and two Adam moments
    total = mem.argument_size_in_bytes + mem.temp_size_in_bytes
    assert total < 16.6e9, total            # 16.500e9: under the allocator's 16.91e9, which loads and runs it
    assert mem.temp_size_in_bytes < 7.45e9, mem.temp_size_in_bytes
