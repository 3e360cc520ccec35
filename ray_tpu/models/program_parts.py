"""Which part of the model every operation of a compiled program came
from: the map a device trace is read through.

The model code opens a ``jax.named_scope`` where each part's work is
written (:data:`VOCABULARY`: one name a thing a performance change would
touch on its own, the same in every block). A TPU trace's operations do
not carry those scopes, the compiled program's text does: every
instruction's ``metadata={op_name="jit(decode_chunk)/while/body/mlp/
dot_general"}``, under the instruction names the trace's events begin
with. :func:`parts_of` reads that text into ``{instruction: part}``;
:func:`program_parts` makes it for the programs an engine runs, at the
shapes it runs them (``LLMServer.start_trace`` / ``stop_trace`` write it
as :data:`FILE` beside the capture). Nothing here runs unless a capture
asks for it.
"""

from __future__ import annotations

import collections
import re

# A part is opened where its work is written; PERF.md §3 says where each
# is, what it covers and which metric or table reads it.
VOCABULARY = (
    "embed",        # the token rows out of the embedding
    "qkv",          # input norm, q / k / v (latent, KDA input) projections,
                    # q / k norms, rotary, KDA's convolution
    "cache",        # the step's or the prompt's rows (ring rows, recurrent
                    # state) into the slots' state
    "attn",         # attention proper and nothing else
    "attn_out",     # wo
    "mlp",          # the dense SwiGLU with its norm
    "mhc",          # several residual streams' coefficients (the norm
                    # over all of them, the product with phi, the
                    # Sinkhorn rounds) and their three mixes round every
                    # sublayer (models/glm_next.py)
    "moe_router", "moe_experts", "moe_shared",
    "lm_head",      # final norm and head
    "sample",       # _sample_from_logits, the greedy argmax
    "loss", "optimizer",  # the train step's
)
# the kinds of attention, a second level under ``attn``
ATTN_KINDS = ("attn_window", "attn_full", "attn_latent", "attn_linear",
              "attn_ssm", "attn_index", "attn_sparse", "attn_conv")
LOOP, UNSCOPED = "loop", "unscoped"
FILE = "program_parts.json"

_COMPUTATION = re.compile(r"^(?:ENTRY )?%?([\w.\-]+) \(.*\) -> .*\{$")
_INSTRUCTION = re.compile(r"^\s+(?:ROOT )?%?([\w.\-]+) = ")
_OP_NAME = re.compile(r'op_name="([^"]*)"')
_CALLS = re.compile(r"\bcalls=%?([\w.\-]+)")
_APPLIED = re.compile(r"\bto_apply=%?([\w.\-]+)")
_LOOPED = re.compile(r"\b(?:body|condition)=%?([\w.\-]+)")
_OPERAND = re.compile(r"%([\w.\-]+)")
_WRAPPED = re.compile(r"^((?:\w+\()*)(.*?)\)*$")
# what the device never runs as an operation of its own
_NO_OPERATION = ("parameter", "constant", "get-tuple-element", "tuple",
                 "bitcast")


def _scope(segment: str) -> str | None:
    """The scope a segment of an ``op_name`` names, transformations
    looked through (``transpose(jvp(mlp))`` -> ``mlp``); a jitted
    function's name (``jit(step)``) is no scope."""
    wrappers, name = _WRAPPED.match(segment).groups()
    return None if "jit(" in wrappers else name


def part_of(op_name: str) -> str:
    """The part an ``op_name`` puts its operation in: the outermost name
    of the vocabulary on the path (under ``attn`` with its kind, where
    one follows: ``attn/attn_window``); with none, ``loop`` inside a
    ``while`` (a scan's slicing of its input, its counters), else
    ``unscoped``."""
    scopes = [_scope(s) for s in op_name.split("/")]
    for i, name in enumerate(scopes):
        if name in VOCABULARY:
            kind = next((s for s in scopes[i + 1:] if s in ATTN_KINDS),
                        None) if name == "attn" else None
            return f"{name}/{kind}" if kind else name
    return LOOP if "while" in scopes else UNSCOPED


def _closing(text: str, start: int) -> int:
    """Index of the ``)`` that closes the ``(`` at ``text[start]``."""
    depth = 0
    for i in range(start, len(text)):
        depth += (text[i] == "(") - (text[i] == ")")
        if not depth:
            return i
    return len(text)


def _instruction(line: str):
    """One line of a computation's body -> (name, opcode, operand names,
    op_name or None, called computation or None), or None."""
    m = _INSTRUCTION.match(line)
    if not m:
        return None
    # the result's type stands before the opcode: a tuple's in
    # parentheses, any other without a space
    at = _closing(line, m.end()) + 2 if line[m.end()] == "(" \
        else line.find(" ", m.end()) + 1
    args = line.find("(", at)
    end = _closing(line, args)
    own, calls = _OP_NAME.search(line, end), _CALLS.search(line, end)
    # (a bare ``gather`` or ``reduce_window_sum``, an argument's name: an
    # expansion of the compiler's that lost the path says nothing)
    return (m[1], line[at:args], _OPERAND.findall(line, args, end),
            own[1] if own and "/" in own[1] else None,
            calls[1] if calls else None)


def parts_of(hlo_text: str) -> dict:
    """``compiled.as_text()`` -> {instruction name: part} for every
    instruction a trace can show (those of fused computations and of
    reducers never run on their own, a parameter, a constant, a tuple,
    its element or a bitcast is no operation: all left out).

    An instruction's part is :func:`part_of` its own ``op_name``. A
    fusion or call WITHOUT one takes the part most of the instructions
    of the computation it calls name. An instruction with neither (a
    copy, a relayout or a slice the compiler made) is charged to what it
    was made for: the part most of its users have, through other such
    instructions, and where it has no such user (the loop carries it on)
    its operands'; with neither, in a ``while``'s body, it is the
    ``loop``'s. ``unscoped`` is what is then left (with the ``op_name``
    it did have: ``unscoped:jit(f)/mul``). An instruction
    whose called computation names MORE THAN ONE part of the vocabulary
    carries a ``+mixed`` suffix on the part it is charged to: XLA fused
    across a boundary, and the time is one part's in the table though it
    is several parts' work."""
    computations: dict[str, list] = {}
    inner: set = set()  # computations that never show as events
    looped: set = set()  # a while's body or condition
    body = None
    for line in hlo_text.splitlines():
        if body is None or not line.startswith(" "):
            m = _COMPUTATION.match(line)
            body = computations.setdefault(m[1], []) if m else None
            continue
        inst = _instruction(line)
        if inst is None:
            continue
        body.append(inst)
        if inst[1] == "fusion" and inst[4]:
            inner.add(inst[4])
        inner.update(_APPLIED.findall(line))
        if inst[1] == "while":
            looped.update(_LOOPED.findall(line))

    votes_of: dict[str, collections.Counter] = {}

    def votes(computation: str) -> collections.Counter:
        """How many instructions of ``computation`` (and of what they
        call) name each part of the vocabulary."""
        if computation not in votes_of:
            votes_of[computation] = tally = collections.Counter()
            for _, _, _, op_name, callee in computations.get(computation, ()):
                if op_name is not None \
                        and (part := part_of(op_name)) not in (LOOP, UNSCOPED):
                    tally[part] += 1
                if callee is not None:
                    tally.update(votes(callee))
        return votes_of[computation]

    def most(tally) -> str | None:
        # (on a tie the name decides, so that the choice repeats)
        return max((n, p) for p, n in tally.items())[1] if tally else None

    out = {}
    for computation, instructions in computations.items():
        if computation in inner:
            continue
        decided, mixed = {}, set()
        for name, _, _, op_name, callee in instructions:
            tally = votes(callee) if callee else {}
            if op_name is not None:
                decided[name] = part_of(op_name)
            elif tally:
                decided[name] = most(tally)
            if len({p.split("/")[0] for p in tally}) > 1:
                mixed.add(name)
        users = collections.defaultdict(list)
        operands = {}
        for name, _, ops, _, _ in instructions:
            operands[name] = ops
            for op in ops:
                users[op].append(name)

        def reach(name, edges, seen):
            """The parts of the nearest decided instructions from
            ``name`` along ``edges``."""
            tally = collections.Counter()
            for other in edges.get(name, ()):
                if other in decided:
                    tally[decided[other]] += 1
                elif other not in seen:
                    seen.add(other)
                    tally.update(reach(other, edges, seen))
            return tally

        def inherited(name) -> str:
            near = [reach(name, edges, {name}) for edges in (users, operands)]
            for tally in near:  # a part of the vocabulary first
                named = {p: n for p, n in tally.items()
                         if p not in (LOOP, UNSCOPED)}
                if named:
                    return most(named)
            # (what a loop only carries on: a copy of a carried operand
            # between two memories, iteration after iteration)
            return LOOP if computation in looped \
                or any(t[LOOP] for t in near) else UNSCOPED

        for name, opcode, _, op_name, _ in instructions:
            if opcode in _NO_OPERATION:
                continue
            part = decided.get(name) or inherited(name)
            if part == UNSCOPED and op_name:  # what it said instead
                part = f"{UNSCOPED}:{op_name}"
            out[name] = part + "+mixed" if name in mixed else part
    return out


def compiled_text(jitted, *args, **kwargs) -> str | None:
    """The optimised HLO of ``jitted`` at a signature it has ALREADY run,
    or None where it has not. A jitted function keeps one lowering a
    signature and the executable on it: ``lower`` of the same signature
    gives that lowering back and ``compile`` the executable it holds
    (jax 0.9: ``Lowered._lowering._executable``), so this compiles
    nothing, asks the compile cache nothing and loads nothing onto the
    device. Only shapes, types and placement of ``args`` are read: an
    array a program has since been donated will do."""
    lowered = jitted.lower(*args, **kwargs)
    if getattr(lowered._lowering, "_executable", None) is None:
        return None
    return lowered.compile().as_text()


def program_name(hlo_text: str) -> str:
    """``HloModule jit_decode_chunk, is_scheduled=true, ...`` ->
    ``jit_decode_chunk``: the name a trace's ``XLA Modules`` line has."""
    return re.match(r"HloModule ([\w.\-]+)", hlo_text)[1]
