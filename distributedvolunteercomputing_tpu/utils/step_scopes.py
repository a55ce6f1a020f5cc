"""The compiled step's instructions by the program's own scopes.

``jax.named_scope`` leaves its word in every instruction's ``op_name`` of the
optimized HLO (``metadata={op_name="jit(step)/transpose(jvp())/while/body/
closed_call/checkpoint/rematted_computation/mlp/dot_general"}``), and a device
trace names an ``XLA Ops`` event by that instruction's text. ``scope_map``
turns the module's text into instruction name -> scope, pass and result type,
so a reader of a trace can say how many milliseconds of a step go to
attention, the mixers, the MLP, the experts, the loss head and the optimizer,
forward, recomputed and backward (``docs/OBSERVABILITY.md``, "Scope vocabulary
(compiled step)").

The trainer keeps, at the first call of each step function, its arguments'
shapes, dtypes and shardings (``remember``) and nothing of the live state,
which the call donates. ``step_scopes`` lowers and compiles that function for
those abstract arguments when somebody asks, never on the thread that trains.
The arguments are described as the call saw them (a committed array's
sharding, none for an uncommitted one), so the lowering is the call's own and
jax's in-process caches answer it: no second compile, and the text is of the
executable that runs.
"""

from __future__ import annotations

import re
import threading
import time
from typing import Any, Callable, Dict, List, Optional, Tuple

# The scope words the models and the step use, and the group each is summed
# under: the one statement of both. A new word (a new mixer) goes here; a
# reader takes the table from ``step_scopes``'s result.
VOCABULARY: Dict[str, str] = {
    "attention": "attention",
    "kda": "mixer",
    "gdn": "mixer",
    "conv_mixer": "mixer",
    "mamba": "mixer",
    "mlp": "mlp",
    "moe": "moe",
    "moe_route": "moe",
    "loss_head": "loss_head",
    "optimizer": "optimizer",
    "noising": "other",  # a block-diffusion step's draw of its noise and its noised copy (models/sdar_moe.py)
    # a looped model's loop over its passes (models/ouro.py): what lies in it and under no block's word is the loop's
    # own (the carry's copies, the stack of the passes' states, the shared weights' gradient sums, the passes' norm)
    "recur": "other",
    # the residual path of a model that carries several streams (models/xing4.py): a sublayer's maps from the
    # token's own state, the sum into its input and the mix of the streams with its result
    "hc": "residual",
}
OTHER = "other"  # resolved, under no word of a group's own: embedding, final norm, the layer scan's own slices
GROUPS: Tuple[str, ...] = tuple(dict.fromkeys((*VOCABULARY.values(), OTHER)))
PASSES = ("fwd", "refwd", "bwd")

# A word as a whole identifier: a path element (``/mlp/``) or the argument of
# a transform (``jvp(loss_head)``, ``transpose(jvp(loss_head))``).
_WORD_RE = re.compile(
    r"(?<![A-Za-z0-9_])(" + "|".join(sorted(VOCABULARY, key=len, reverse=True)) + r")(?![A-Za-z0-9_])"
)
_COMPUTATION_RE = re.compile(r"^(?:ENTRY )?%?([^\s(]+) \(.*\{\s*$")
_INSTRUCTION_RE = re.compile(r"^\s+(?:ROOT )?%?([^\s=]+) = (.*)$")
_OP_NAME_RE = re.compile(r'op_name="([^"]*)"')
_CALLED_RE = re.compile(r"\b(?:calls|body|condition|to_apply)=%?([^\s,)}]+)")
_BRANCHES_RE = re.compile(r"\bbranch_computations=\{([^}]*)\}")
_OPCODE_RE = re.compile(r"\s*([a-z][a-z0-9\-]*)\(")
_MODULE_RE = re.compile(r"^HloModule ([^\s,]+)")


def group_of(scope: Optional[str]) -> str:
    return VOCABULARY.get(scope or "", OTHER)


def scope_and_pass(op_name: str) -> Tuple[Optional[str], str]:
    """(the innermost vocabulary word of an ``op_name``, or None; its pass).
    Of origins joined with ``;`` (instructions XLA merged) the first counts."""
    origin = op_name.split(";", 1)[0]
    words = _WORD_RE.findall(origin)
    if "rematted_computation" in origin:
        which = "refwd"
    elif "transpose(" in origin:
        which = "bwd"
    else:
        which = "fwd"
    return (words[-1] if words else None), which


def _split_result(rest: str) -> Tuple[str, str]:
    """``f32[8,64]{1,0} fusion(...)...`` -> (result type as printed, the rest
    from the opcode on); a tuple's type runs to its closing parenthesis."""
    if rest.startswith("("):
        depth = 0
        for i, ch in enumerate(rest):
            depth += (ch == "(") - (ch == ")")
            if depth == 0:
                return rest[: i + 1], rest[i + 1:]
        return rest, ""
    result, _, tail = rest.partition(" ")
    return result, " " + tail


def scope_map(hlo_text: str) -> Dict[str, Dict[str, Any]]:
    """An optimized HLO module's text -> ``{instruction name: {"scope",
    "pass", "result", "mixed"}}`` for every instruction that can be an event
    of its own (those of a fusion's computation are inside the fusion's).

    ``result`` is the result type as printed. An instruction the compiler made
    and gave no ``op_name`` (a loop's own slices and copies) takes the origin
    of the instruction that calls its computation, the ``while`` around it.
    ``mixed`` says, for a fusion, whether the instructions of the computation
    it calls name more than one of the vocabulary's groups (a residual add
    under ``attention`` fused into the next block's norm under ``mlp``): XLA
    gives a fusion one origin, and the fusion is attributed to it whatever it
    swallowed. What is under no word there (constants, the layer scan's
    slices, casts) blurs nothing."""
    # computation -> [(instruction, result, opcode, op_name, the computations it calls)]
    computations: Dict[str, List[Tuple[str, str, str, str, List[str]]]] = {}
    current = None
    for line in hlo_text.splitlines():
        if current is None:
            m = _COMPUTATION_RE.match(line)
            if m:
                current = computations.setdefault(m.group(1), [])
            continue
        if line.startswith("}"):
            current = None
            continue
        m = _INSTRUCTION_RE.match(line)
        if not m:
            continue
        result, tail = _split_result(m.group(2))
        opcode = _OPCODE_RE.match(tail)
        op_name = _OP_NAME_RE.search(tail)
        called = _CALLED_RE.findall(tail)
        for branches in _BRANCHES_RE.findall(tail):
            called += [b.strip().lstrip("%") for b in branches.split(",")]
        current.append((m.group(1), result, opcode.group(1) if opcode else "",
                        op_name.group(1) if op_name else "", called))
    fused = {called[0] for instrs in computations.values()
             for _, _, opcode, _, called in instrs if opcode == "fusion" and called}
    groups_in = {
        name: {group_of(scope_and_pass(op_name)[0]) for _, _, _, op_name, _ in computations[name]} - {OTHER}
        for name in fused if name in computations
    }
    caller: Dict[str, Tuple[str, str]] = {}  # computation -> (computation, op_name) of the instruction that calls it
    for comp, instrs in computations.items():
        for _, _, _, op_name, called in instrs:
            for name in called:
                caller.setdefault(name, (comp, op_name))

    def origin(comp: str, op_name: str) -> str:
        seen = set()
        while not op_name and comp in caller and comp not in seen:
            seen.add(comp)
            comp, op_name = caller[comp]
        return op_name

    out: Dict[str, Dict[str, Any]] = {}
    for comp, instrs in computations.items():
        if comp in fused:
            continue
        for name, result, opcode, op_name, called in instrs:
            scope, which = scope_and_pass(origin(comp, op_name))
            out[name] = {
                "scope": scope, "pass": which, "result": result,
                "mixed": opcode == "fusion" and len(groups_in.get(called[0] if called else "", ())) > 1,
            }
    return out


# -- what the trainer remembers, and the accessor --------------------------------

_lock = threading.Lock()
# program (``jit(step)``) -> (the jitted function, its abstract arguments, the thread that trains)
_remembered: Dict[str, Tuple[Callable, tuple, threading.Thread]] = {}
_built: Dict[str, Dict[str, Any]] = {}


def _abstract(leaf: Any) -> Any:
    import jax
    import numpy as np

    if not hasattr(leaf, "shape") or not hasattr(leaf, "dtype"):
        leaf = np.asarray(leaf)
    # An uncommitted array's sharding is where it happens to be, not something the call specifies:
    # handed over, it would lower to another module text (an `sdy.sharding` an argument) and compile again.
    sharding = leaf.sharding if getattr(leaf, "committed", False) else None
    return jax.ShapeDtypeStruct(leaf.shape, leaf.dtype, sharding=sharding)


def remember(fn: Callable, args: tuple) -> None:
    """Keep ``fn`` (a jitted step function) with the shapes, dtypes and
    (committed) shardings of ``args``, under its program's name; the calling thread is
    taken for the one that trains. Call it before ``fn(*args)``: a donated
    argument is gone afterwards."""
    import jax

    program = f"jit({fn.__name__})"
    abstract = jax.tree_util.tree_map(_abstract, tuple(args))
    with _lock:
        _remembered[program] = (fn, abstract, threading.current_thread())  # the object: an ident is reused
        _built.pop(program, None)


def remembered() -> List[str]:
    """The programs a trainer of this process has remembered, oldest first."""
    with _lock:
        return list(_remembered)


def step_scopes(program: str = "jit(step)") -> Optional[Dict[str, Any]]:
    """``{"program", "module", "vocabulary", "map", "seconds"}`` of a
    remembered step program, or None for one no trainer here has called:
    ``map`` is ``scope_map`` of its compiled module, ``module`` the name a
    trace's ``XLA Modules`` line gives it, ``seconds`` what lowering,
    compiling and parsing took and where the executable came from (``cache``:
    ``in_process`` is the expected answer). Built at the first request and
    kept by program name.

    Not for the thread that trains (``RuntimeError`` there): a compile would
    stall its dispatch; nor before a measured window closes."""
    with _lock:
        held = _remembered.get(program)
        done = _built.get(program)
    if held is None:
        return None
    fn, abstract, train_thread = held
    if threading.current_thread() is train_thread:
        raise RuntimeError(f"step_scopes({program!r}) on the thread that trains: ask from another")
    if done is not None:
        return done
    from distributedvolunteercomputing_tpu.utils.jaxenv import compile_log

    began, t0 = time.time(), time.perf_counter()
    lowered = fn.lower(*abstract)
    t1 = time.perf_counter()
    text = lowered.compile().as_text()
    t2 = time.perf_counter()
    heard = compile_log().summary(since=began, thread=threading.get_ident())
    module = _MODULE_RE.match(text)
    doc = {
        "program": program,
        "module": module.group(1) if module else "",
        "vocabulary": dict(VOCABULARY),
        "map": scope_map(text),
    }
    doc["seconds"] = {
        "lower": round(t1 - t0, 3), "compile": round(t2 - t1, 3),
        "parse": round(time.perf_counter() - t2, 3),
        # `in_process`: jax's own caches held the executable and nothing was compiled or fetched; else the
        # persistent cache's part of `compile` and whether it hit (`off`: compiled, with no such cache on)
        "cache_load": heard["cache_load_seconds"],
        "cache": ("in_process" if not heard["programs"] else "miss" if heard["cache_misses"]
                  else "hit" if heard["cache_hits"] else "off"),
    }
    with _lock:
        if _remembered.get(program) is held:  # no newer trainer has taken the name
            _built[program] = doc
    return doc
