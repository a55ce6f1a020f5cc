"""moe.device_ms (ms): device time a step spends in the expert layer, summed
over the whole executions of the train step's program on chip 0 and divided
by their number, as attention.device_ms does. Layer: compiled step. Moves
tok_s_chip.

Counted: the grouped matrix multiplications (forward, the recomputed forward,
both backward products; ``benchmark/moe_trace.py`` lists the names the chip
prints for megablox and for ragged_dot), the sort of the S x k assignments,
the router's top-k, the grouped matmuls' bookkeeping, and every operation
whose result is a tensor over the S x k routed rows: the dispatch's row
gathers in both directions, silu x up, the sums of cotangents. Those carry no
name of their own; only the expert layer has tensors of that many rows.
NOT counted: the router's product and softmax and the forward of the weighted
sum over a token's choices (results over the S tokens, like the rest of the
step) and the casts of the expert weights to bf16. A program with no grouped matmul (a
dense model) gives nothing."""

from benchmark import moe_trace, trace


def compute(run):
    found = moe_trace.events_in_whole_steps(run)
    if found is None:
        return None
    n_steps, ops = found
    if not any(moe_trace.GMM_RE.search(trace.op_name(e.name)) for e in ops):
        return None
    return moe_trace.layer_ns(ops, moe_trace.routed_rows(run)) / n_steps / 1e6
