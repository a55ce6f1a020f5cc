"""laguna-solo-8k's whole train step, compiled for the described v5e of
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
    _kernel_calls,
    _kernel_names,
    _lowered_step,
    _no_pass_over_a_head_shaped_array,
    no_persistent_cache,
    _share_chunks_hold_seven_grouped_matmuls,
    _step_holds_the_groups_its_cell_lists,
    v5e,
)


# ``slow`` since PR 58: one cell-size compile for a described v5e, 158 s of the tier-1 run's six
# workers and 3.5 to 6 GB of host memory, that shares nothing with another test; the run's other tests did not fit
# the command's limit beside the eight such compiles (ROADMAP D3). Run it before any chip run of a PR that touches a
# model's step: ``python -m pytest -m slow tests/test_tpu_compile*.py`` (the verify skill).
@pytest.mark.slow
def test_laguna_step_holds_the_windowed_and_the_full_kernel(v5e, as_on_the_chip, monkeypatch):
    """laguna-solo-8k's step (five layers of Laguna-XS.2 at its published
    widths, sixteen of 256 experts held, an eighth of the vocabulary,
    4 x 8,192 tokens): the full-causal kernel at 48 query heads over 8
    key/value heads (layers 0 and 4) and the windowed one at 64 (layers 1-3),
    each forward and backward (the recomputed forward holds no kernel), under
    the names a device trace tells them by; the expert layers' grouped matmuls over the bounded
    chunk of rows, never the S x k = 262,144, seven a layer. That it compiles
    says the step fits the chip beside its state; its temporaries are what
    they were before PR 38 (8.1079e9 then, 8.1090e9 after it: the float32 carry
    of a run over a tile's edge, ``[rows / 128, d]`` a call; 8.1105e9 since PR 46).
    Since PR 59 the kernels are handed q, k and v where the projections leave
    them, ``[4, 8192, H * 128]``, and write the output the same way: the step
    holds no array by head and no float32 copy of a merged one (the rotary
    halves, the head transposes and the float32 query products are gone), the
    gate reaches its heads through a 0/1 product, and the temporaries fall to
    6.870e9. Since PR 73 the windowed kernels run their edge tiles as strips
    over one slab (same names, same blocks, one kernel each way a layer), and
    the executable is no larger for it."""
    import jax.numpy as jnp

    from distributedvolunteercomputing_tpu.ops import moe_dispatch, pallas_attention

    # both layer kinds keep their blocks with a turned call's table blocks counted (25.5 and 46.0 MiB)
    assert pallas_attention.choose_blocks(8192, 8192, 128, jnp.bfloat16, 512, turned=True) == (512, 512)
    assert pallas_attention.choose_blocks(8192, 8192, 128, jnp.bfloat16, turned=True) == (1024, 1024)
    assert pallas_attention.vmem_bytes(8192, 8192, 128, jnp.bfloat16, 1024, 1024, turned=True) <= 46.1 * 2 ** 20

    monkeypatch.setattr(moe_dispatch, "tpu_backend", lambda: True)
    monkeypatch.setattr(moe_dispatch, "grouped_matmul_impl", lambda m, k, n: "megablox")
    compiled = _lowered_step(
        v5e, "laguna_xs2", 1, 1, 4, n_layers=5, experts_held=16, vocab=12544).compile()
    text = compiled.as_text()
    _step_holds_the_groups_its_cell_lists(text, "laguna-solo-8k")
    calls = _kernel_calls(text)
    names = _kernel_names(calls)
    full = [n for n in names if n.startswith(("dvc_flash_fwd", "dvc_flash_bwd"))]
    win = [n for n in names if n.startswith("dvc_flash_win_")]
    assert len(full) == 4 and sum(n.startswith("dvc_flash_bwd") for n in full) == 2, names
    assert len(win) == 6 and sum(n.startswith("dvc_flash_win_bwd") for n in win) == 3, names
    assert all("bf16[4,8192,6144]" in ln for ln in calls if "dvc_flash_fwd" in ln or "dvc_flash_bwd" in ln)
    assert all("bf16[4,8192,8192]" in ln for ln in calls if "dvc_flash_win_" in ln)
    assert all("bf16[4,8192,1024]" in ln for ln in calls if "dvc_flash_" in ln)  # 8 key/value heads
    _no_pass_over_a_head_shaped_array(text, 4, 8192, (64, 48, 8))
    # beside the kernels, a layer: the backward's delta rows, and one merged-layout rotary pass for k,
    # its recomputation, the backward's q, dq and dk (the forward's q is turned on the kernel's tile)
    beside = [n.split(".")[0] for n in names if n.startswith(("dvc_attn_", "dvc_rotary"))]
    assert sorted(set(beside)) == ["dvc_attn_delta", "dvc_rotary", "dvc_rotary_back"], names
    assert (beside.count("dvc_attn_delta"), beside.count("dvc_rotary"), beside.count("dvc_rotary_back")) == (5, 15, 10)
    rows = moe_dispatch.share_rows_bound(4 * 8192, 8, 16, 256)
    assert rows == 49152  # three times the even share of 16,384: one chunk a layer on the chip
    assert f"[{rows},2048]" in text and "[262144,2048]" not in text
    _share_chunks_hold_seven_grouped_matmuls(names, text, layers=4, rows=rows, d=2048, f=512)
    mem = compiled.memory_analysis()
    total = mem.argument_size_in_bytes + mem.temp_size_in_bytes + mem.output_size_in_bytes - mem.alias_size_in_bytes
    assert total < 15.75e9, total
    # the parent of PR 36: 8.1125e9; of PR 38: 8.1079e9; of PR 46: 8.1090e9, and 8.1105e9 since (three select passes
    # a layer fewer and the same buffers alive: the heap packs 1.5 MB worse)
    # and 6.870e9 since PR 59 (the float32 [4,8192,8192] products and the by-head copies are gone)
    assert mem.temp_size_in_bytes <= 6.90e9, mem.temp_size_in_bytes
    # the executable: 272.82e6 with the windowed kernels' whole tiles, 271.31e6 since PR 73 with a window of one
    # block as two strips a grid step, each a body of its own (270.79e6 as a loop over them): start-up reads it
    assert mem.generated_code_size_in_bytes <= 275e6, mem.generated_code_size_in_bytes
