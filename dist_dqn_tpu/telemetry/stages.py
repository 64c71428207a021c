"""Stage names of the fused chunk program, and the table that joins a
device trace to them.

One vocabulary, :data:`STAGES`, each name entered as a ``jax.named_scope``
where the work happens (train_loop.py, replay/device.py,
replay/prioritized_device.py, replay/sequence_device.py, agents/dqn.py,
agents/r2d2.py). A scope is metadata: it lands in every HLO instruction's
``op_name`` and leaves the optimized program as it was.

Stage ``loss_grad`` has child names, in four groups that each split it
another way: :data:`PASSES`, scopes the recurrent learner enters
(agents/r2d2.py ``_unrolled_q``); :data:`PARTS`, which nobody enters —
they are the names the recurrent networks give their two sub-modules
(parameter keys, so they cannot drift), and Flax puts a module's name on the
op path; :data:`CORE_PARTS`, which splits ``core`` by the scopes the
hybrid core's mixers enter (models/sequence_core.py); and :data:`LOOPS`,
the one scope a looped core enters around its turns, so that what the loop
costs outside every mixer can be read (under it, under no name of
:data:`CORE_PARTS`).
A child is read (:func:`child_of`) through the wrappers a transform puts
around the outermost name inside it (``transpose(jvp(online_unroll))``),
and counts only on an instruction whose STAGE is ``loss_grad``
(:func:`children`): ``torso`` is on the acting path too. The stage table
never sees a child, so a child takes no time away from its stage.

A device trace names each op by its HLO instruction (``fusion.584``), so the
join back to a stage goes through the executable that actually ran:
``train.train`` hands :func:`keep` the executable it compiles ahead of the
first dispatch (``_compile_chunk``), and :func:`table` — called by a reader
AFTER a traced run, never by the trainer — takes that executable's text and
maps every instruction to its stage. Until somebody calls it nothing is
printed, walked or written.

Stdlib only, like the rest of the package.
"""
from __future__ import annotations

import functools
import re
import time
from collections import Counter
from typing import Dict, Iterable, Iterator, List, Optional, Tuple

STAGES = ("act", "env", "insert", "sample", "gather", "loss_grad",
          "allreduce", "optimizer", "target_sync", "writeback")
#: Children of stage ``loss_grad``, entered: the recurrent learner's three
#: network passes (burn-in of either network; online loss + bootstrap
#: region, forward and backward; the target's, forward only).
PASSES = ("burn_in", "online_unroll", "target_unroll")
#: Children of stage ``loss_grad``, read: models/recurrent.py's sub-modules
#: (convolutions + ``embed``; the scanned cell), forward and backward.
PARTS = ("torso", "core")
#: Children of stage ``loss_grad``, read: the torso, and the scopes the
#: hybrid sequence core's mixers enter (models/sequence_core.py) — the
#: state-space layers, the attention layer (``attention``: no positions;
#: ``attention_window`` / ``attention_full``: rotary, gated, over the last
#: steps / the whole episode), the dense MLP, and an expert layer's three
#: parts. Norms, residual adds, the heads and the loss stay under no child.
CORE_PARTS = ("torso", "ssm", "attention", "moe_router", "moe_routed",
              "moe_shared", "attention_window", "attention_full",
              "mlp_dense")
#: Child of stage ``loss_grad``, entered: the scope a core that runs its
#: stack several times enters around all of its turns
#: (``HybridQNetwork.turns``; one name whatever the loop's form), the
#: mixers' scopes inside it. What lies under it and under no name of
#: :data:`CORE_PARTS` is the looped stack OUTSIDE every mixer: the
#: sublayers' norms on input and output, the residual adds, the norm after
#: each turn, and what the loop's form adds (the turns are written out: the
#: sum of a weight's gradients over the turns).
LOOPS = ("loops",)
#: The stage whose instructions the child names split.
PARENT = "loss_grad"
#: A fusion whose instructions come from more than one stage (or child).
MIXED = "mixed"

_STAGE_SET = frozenset(STAGES)
# ``%fusion.584 = u8[..]{..} fusion(%a, %b), kind=kLoop,
#   calls=%fused_computation.3, metadata={op_name="jit(run_chunk)/.."}``
_INSTRUCTION = re.compile(
    r"^\s*(?:ROOT )?%?(?P<inst>[\w.\-]+) = .*? (?P<op>[a-z][a-z\-]*)\("
    r"(?P<operands>[^)]*)\)")
_COMPUTATION = re.compile(r"^(?:ENTRY )?%?([\w.\-]+) \(.*\) -> .* \{\s*$")
_NAME = re.compile(r"%([\w.\-]+)")
# A path part as a transform wraps it: ``jvp(..)``, ``transpose(jvp(..))``,
# ``jit(..)``, ``vmap(..)``.
_WRAPPED = re.compile(r"\w+\((.*)\)")
_OP_NAME = re.compile(r'op_name="([^"]*)"')
_CALLS = re.compile(r"\bcalls=%?([\w.\-]+)")
# Inside a fusion these do no work of their own and do not vote (a shared
# constant carries the op_name of whichever stage traced it first).
_NO_VOTE = frozenset(("parameter", "constant", "broadcast", "iota", "bitcast",
                      "tuple", "get-tuple-element"))
# The op a fusion is built around; where one is fused in, it alone decides.
_HEROES = frozenset(("convolution", "dot", "scatter", "gather", "sort",
                     "dynamic-update-slice", "reduce-window",
                     "select-and-scatter"))
# Without heroes, the stage that holds this share of a fusion's votes.
_MAJORITY = 0.75
# Data movement the compiler inserts (layout changes, prefetches into fast
# memory) carries no op_name: it takes the stage of what consumes it, else
# of what produced its operand.
_MOVES = frozenset(("copy", "copy-start", "copy-done", "bitcast", "transpose",
                    "reshape", "get-tuple-element"))

_program = None                     # the chunk program's Compiled
_tables: Dict[Tuple[str, ...], Dict[str, str]] = {}     # by vocabulary
_seconds: Dict[Tuple[str, ...], float] = {}
_children: Dict[Tuple[str, ...], Dict[str, Optional[str]]] = {}


def keep(compiled) -> None:
    """Remember the chunk program (a reference; nothing is computed)."""
    global _program
    _program = compiled
    for memo in (_tables, _seconds, _children):
        memo.clear()


def table_built(names: Tuple[str, ...] = STAGES) -> bool:
    return names in _tables


def table_seconds(names: Tuple[str, ...] = STAGES) -> Optional[float]:
    """Seconds the one build of that vocabulary's table took; None before
    it."""
    return _seconds.get(names)


def table(names: Tuple[str, ...] = STAGES) -> Dict[str, str]:
    """``{instruction name: name}`` of the kept executable over one
    vocabulary (:data:`STAGES` or a group of child names), built on
    the first call for it: the executable's text and one walk over it.
    Empty where no program was kept."""
    if names not in _tables and _program is not None:
        t0 = time.perf_counter()
        _tables[names] = table_from_text(_program.as_text(), names)
        _seconds[names] = time.perf_counter() - t0
    return _tables.get(names, {})


def children(names: Tuple[str, ...]) -> Dict[str, Optional[str]]:
    """``{instruction: child}`` over the instructions of stage
    :data:`PARENT` and no others: a name of ``names``, :data:`MIXED`, or
    None where the group names nothing (the stage's own time). One dict a
    group for as long as the program is kept."""
    if names not in _children:
        _children[names] = _under_parent(table(), table(names))
    return _children[names]


def children_from_text(hlo_text: str, names: Tuple[str, ...]
                       ) -> Dict[str, Optional[str]]:
    return _under_parent(table_from_text(hlo_text),
                         table_from_text(hlo_text, names))


def _under_parent(stage: Dict[str, str], child: Dict[str, str]
                  ) -> Dict[str, Optional[str]]:
    return {inst: child.get(inst) for inst, s in stage.items()
            if s == PARENT}


def stage_of(op_name: str) -> Optional[str]:
    """The innermost stage on an ``op_name`` path
    (``jit(run_chunk)/while/body/.../gather/...``), or None."""
    for part in reversed(op_name.split("/")):
        if part in _STAGE_SET:
            return part
    return None


def child_of(op_name: str, names: Iterable[str]) -> Optional[str]:
    """The innermost part of an ``op_name`` path that is one of ``names``
    once the transforms' wrappers are taken off it: inside
    ``value_and_grad`` the outermost name reads ``jvp(online_unroll)`` on
    the forward ops and ``transpose(jvp(online_unroll))`` on the backward
    ones; the names under it (``torso``, ``core``) stay whole."""
    for part in reversed(op_name.split("/")):
        wrapped = _WRAPPED.fullmatch(part)
        while wrapped:
            part = wrapped.group(1)
            wrapped = _WRAPPED.fullmatch(part)
        if part in names:
            return part
    return None


def _agree(stages: Iterable[Optional[str]]) -> Optional[str]:
    found = {s for s in stages if s is not None}
    if not found:
        return None
    return found.pop() if len(found) == 1 else MIXED


def _fusion_stage(votes: List[Tuple[str, Optional[str]]]) -> Optional[str]:
    """The stage of a fused computation from its ``(opcode, stage)`` pairs:
    its heroes' if it has any, else the stage nearly all of its working
    instructions share, else :data:`MIXED`."""
    staged = [(op, s) for op, s in votes
              if s is not None and op not in _NO_VOTE]
    heroes = [s for op, s in staged if op in _HEROES]
    if heroes:
        return _agree(heroes)
    if not staged:
        return None
    stage, count = Counter(s for _, s in staged).most_common(1)[0]
    return stage if count >= _MAJORITY * len(staged) else MIXED


def instructions(hlo_text: str) -> Iterator[Tuple[str, "re.Match", str]]:
    """``(computation name, match, line)`` for every instruction line of an
    HLO module's text; the match has ``inst``, ``op`` and ``operands``."""
    current = None
    for line in hlo_text.splitlines():
        if current is None:
            m = _COMPUTATION.match(line)
            if m:
                current = m.group(1)
        elif line.startswith("}"):
            current = None
        else:
            m = _INSTRUCTION.match(line)
            if m:
                yield current, m, line


def table_from_text(hlo_text: str, names: Tuple[str, ...] = STAGES
                    ) -> Dict[str, str]:
    """Map the instructions of an optimized HLO module to the names of one
    vocabulary: the stages (read by :func:`stage_of`, whole path parts) or
    a group of child names (:func:`child_of`). "Stage" below is whichever.

    An instruction takes the stage its own ``op_name`` carries. One that
    calls a computation (a fusion above all) takes the stage of the
    instructions inside it (:func:`_fusion_stage`). Compiler-inserted data
    movement under no stage takes its consumers' stage, else its
    producer's. Instructions still under no stage are left out."""
    read = (stage_of if names == STAGES
            else functools.partial(child_of, names=frozenset(names)))
    opcode: Dict[str, str] = {}
    own: Dict[str, Optional[str]] = {}      # instruction -> its own stage
    operands: Dict[str, List[str]] = {}
    calls: Dict[str, str] = {}              # instruction -> computation
    members: Dict[str, List[str]] = {}      # computation -> instructions
    for computation, m, line in instructions(hlo_text):
        inst = m.group("inst")
        members.setdefault(computation, []).append(inst)
        opcode[inst] = m.group("op")
        operands[inst] = _NAME.findall(m.group("operands"))
        name = _OP_NAME.search(line)
        own[inst] = read(name.group(1)) if name else None
        called = _CALLS.search(line)
        if called:
            calls[inst] = called.group(1)
    stage = dict(own)
    for inst, computation in calls.items():
        inner = _fusion_stage([(opcode[i], own[i])
                               for i in members.get(computation, ())])
        stage[inst] = inner or own[inst]
    users: Dict[str, List[str]] = {}
    for inst, ops in operands.items():
        for o in ops:
            users.setdefault(o, []).append(inst)
    moves = [i for i, op in opcode.items()
             if op in _MOVES and stage[i] is None]
    for neighbours in (users, operands):    # consumers first, then producers
        changed = True
        while changed:
            changed = False
            for inst in moves:
                if stage[inst] is None:
                    found = _agree(stage.get(n)
                                   for n in neighbours.get(inst, ()))
                    if found is not None:
                        stage[inst], changed = found, True
    return {inst: s for inst, s in stage.items() if s is not None}
