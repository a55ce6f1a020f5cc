"""scope.residual_ms (ms): device time a step spends on the residual path of a
model that carries several streams, under the scope ``hc`` (group residual of
``utils/step_scopes.VOCABULARY``): every sublayer's maps from the token's own
state (the stream norm's sum, ``u Phi``, the sigmoids and the Sinkhorn steps),
the sum into the sublayer's input and the mix of the streams with its result,
forward, recomputed and backward; reduced as ``scope.attention_ms`` is
(``benchmark/scope_trace.py``). Layer: compiled step. Moves tok_s_chip.

A program that does not offer its scope map, or whose step has nothing under
that group (every model with one residual stream, the parent of PR 70), gives
nothing."""

from benchmark import scope_trace


def compute(run):
    got = scope_trace.by_scope(run)
    if got is None or not got["table"].get("residual"):
        return None
    return scope_trace.group_ms(run, "residual")
