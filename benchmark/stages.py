"""Device seconds per stage of the fused program in one capture.

The program names its stages with ``jax.named_scope`` (``nns.pre/<el>``,
``nns.model/backbone/block03``, ``nns.model/layer07/attn``,
``nns.post/overlay``); the scope reaches the compiled program only as the
``op_name`` metadata of its instructions, and neither the TPU's
``XLA Ops`` events nor the CPU backend's thunk events carry it (checked
on the v5e and on the CPU, jax 0.9.0).  A device event is named by its
instruction, so the stage is looked up by instruction name in the
program's optimised HLO text, which the traffic kind asks the filter for
(:func:`program_text`).  From the program come the text and the scopes'
names; the arithmetic is here, a copy of
``nnstreamer_tpu/utils/profile.py``'s ``stage_of`` / ``stage_map`` /
``stage_seconds`` on the plain form ``benchmark/trace.py`` ``load_xplane``
reads a capture into, so that a test checks it on a synthetic trace and
no later change to the program moves the yardstick.
"""

from __future__ import annotations

import re
import time

STAGE_ROOT = "nns."
NO_SCOPE = "(no nns scope)"
NO_METADATA = "(no metadata)"

_INSTR = re.compile(r"^\s*(?:ROOT )?%?([\w.\-]+) = ")
_OP_NAME = re.compile(r"metadata=\{[^}]*?op_name=\"([^\"]*)\"")
_COMPUTATION = re.compile(r"^(?:ENTRY )?%?([\w.\-]+) \(.*\) -> .*\{\s*$")
_CALLED = re.compile(r"(?:calls|to_apply|body|condition)=%?([\w.\-]+)")
_WRAPPED = re.compile(r"^(\w+)\((.*)\)$")
_SCOPE = re.compile(r"^[\w.\-]+$")
_EVENT_INSTR = re.compile(r"^%?([\w.\-]+)")


def _unwrapped(part: str) -> str:
    """``vmap(nms)`` -> ``nms``; an inner ``jit(...)`` is no scope and
    stays as it is."""
    while True:
        m = _WRAPPED.match(part)
        if m is None or m.group(1) in ("jit", "pjit"):
            return part
        part = m.group(2)


def stage_of(op_name: str) -> str:
    """``jit(f)/nns.model/vmap(nms)/jit(_where)/select_n`` ->
    ``nns.model/nms``: the scopes from the ``nns.`` root down, without
    the primitive at the end, transformations unwrapped, and anything
    under an inner ``jit`` (or a name that is no scope's) left out."""
    parts = [_unwrapped(part) for part in op_name.split("/")]
    for i, part in enumerate(parts):
        if part.startswith(STAGE_ROOT):
            break
    else:
        return NO_SCOPE
    stage = [parts[i]]
    for part in parts[i + 1:-1]:
        if not _SCOPE.match(part):
            break
        stage.append(part)
    return "/".join(stage)


def stage_map(executable_text: str) -> dict:
    """Instruction name -> stage, from an optimised HLO text.  An
    instruction the compiler made itself (a reduction it split, a copy
    it wrapped) has no metadata and takes the stage most instructions of
    the computations it calls have; a fusion is booked to its own
    metadata, which is its root's."""
    own: dict = {}
    called: dict = {}
    members: dict = {}
    computation = None
    for line in executable_text.splitlines():
        m = _COMPUTATION.match(line)
        if m is not None:
            computation = m.group(1)
            continue
        m = _INSTR.match(line)
        if m is None:
            continue
        name = m.group(1)
        op = _OP_NAME.search(line)
        own[name] = stage_of(op.group(1)) if op else None
        called[name] = _CALLED.findall(line)
        members.setdefault(computation, []).append(name)

    def resolve(name: str, depth: int = 0):
        if own.get(name) not in (None, NO_SCOPE) or depth > 8:
            return own.get(name)
        votes: dict = {}
        for comp in called.get(name, ()):
            for member in members.get(comp, ()):
                stage = resolve(member, depth + 1)
                if stage not in (None, NO_SCOPE):
                    votes[stage] = votes.get(stage, 0) + 1
        if votes:
            return max(sorted(votes), key=votes.get)
        return own.get(name)

    out = {}
    for name in own:
        stage = resolve(name)
        if stage is not None:
            out[name] = stage
    return out


def _self_ns(events: list) -> list:
    """[(event, ns not covered by events nested in it)] of one line."""
    out, stack = [], []
    for ev in sorted(events, key=lambda e: (e[1], -e[2])):
        while stack and stack[-1][0][1] + stack[-1][0][2] <= ev[1]:
            out.append(stack.pop())
        if stack:
            stack[-1][1] -= ev[2]
        stack.append([ev, ev[2]])
    out.extend(stack)
    return [(ev, max(ns, 0.0)) for ev, ns in out]


def _instruction(event_name: str):
    """The instruction a device event is named by: the TPU names an
    operation by its whole instruction, the CPU's thunks by its name."""
    m = _EVENT_INSTR.match(event_name)
    return m.group(1) if m else None


def stage_seconds(planes: list, executable_text: str, chips: int,
                  device_plane: str, ops_line: str) -> dict:
    """{stage: seconds}, per chip: the mean over the first ``chips``
    device planes that ran anything (the planes ``reduce_trace`` takes
    its busy time from).  Nested events count once (self time), so the
    stages sum to the union of the operation intervals.  An operation
    whose instruction the text does not name is booked under
    ``(no metadata)``, one whose op_name has no ``nns.`` scope under
    ``(no nns scope)``."""
    by_name = stage_map(executable_text)
    per_plane = []
    for plane in sorted(planes, key=lambda p: p["name"]):
        if not plane["name"].startswith(device_plane):
            continue
        totals: dict = {}
        for line in plane["lines"]:
            if not line["name"].startswith(ops_line):
                continue
            events = [(by_name.get(_instruction(name), NO_METADATA),
                       start, dur) for name, start, dur in line["events"]]
            for (stage, _s, _d), ns in _self_ns(events):
                totals[stage] = totals.get(stage, 0.0) + ns * 1e-9
        if totals:
            per_plane.append(totals)
    per_plane = per_plane[:chips]
    stages = sorted({k for t in per_plane for k in t})
    return {k: sum(t.get(k, 0.0) for t in per_plane) / len(per_plane)
            for k in stages}


def program_text(run, pipe):
    """The optimised HLO of the cell's filter program, asked of the
    filter element (``<element_prefix>net``) while the pipeline is still
    up: the program traces, lowers and compiles (or loads) it again, so
    a traffic kind calls this after the window has closed, in a traced
    run only.  ``None`` where the filter's sub-plugin has no
    ``executable_text`` (the stage metrics are then left out)."""
    name = run.workload.get("element_prefix", "el_") + "net"
    ask = getattr(pipe[name].subplugin, "executable_text", None)
    if ask is None:
        run.log(f"{name}: the sub-plugin gives no program text; no stages")
        return None
    t0 = time.perf_counter()
    text = ask()
    run.log(f"program text of {name}: {len(text)} characters in "
            f"{time.perf_counter() - t0:.2f} s (after the window; a "
            "traced run only)")
    return text
