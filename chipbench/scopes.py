"""From the program's named scopes to the phases of a train step.

The program opens ``jax.named_scope`` at each phase of its step
(``src/repro/train/scopes.py``); each compiled instruction carries the path
of scopes it was traced under in its ``op_name`` metadata, with the
transformations applied to it: ``jvp(forward)`` in the forward pass,
``transpose(jvp(forward))`` in the backward, ``checkpoint/
rematted_computation`` where ``remat`` recomputes the forward inside the
backward. A trace's ``XLA Ops`` events are named by the same instructions
(``trace.op_name``), so each event gets its phase from the compiled
program's text.

``phase_map`` reads that text; ``phase_seconds`` adds up device self time
per phase inside the measured window. A program without these scopes (an
older commit) gives an empty map and no phase times. ``scope_paths`` and
``scope_seconds`` do the same for any one scope, named or not in
``SCOPES``: the self time of the ops with that name on their path.

``harness.run`` keeps both maps of a traced run; ``run.Facts`` gives the
phase times (``phases``) and ``scope_s(name)`` to the per-layer readers
(``metrics/*_share.py``)."""
from __future__ import annotations

import re
from typing import NamedTuple

from chipbench import trace

PHASES = ("forward", "recompute", "backward", "optimizer", "bucket_views",
          "grad_exchange", "unattributed")
MODEL_SCOPES = ("embed", "attention", "mlp", "head")
SCOPES = ("bucket_views", "forward", "optimizer", "grad_reduce",
          "param_gather") + MODEL_SCOPES

COMPUTATION = re.compile(r"^(ENTRY )?%([A-Za-z0-9_.\-]+) .*\{\s*$")
INSTRUCTION = re.compile(r"^\s*(?:ROOT )?%([A-Za-z0-9_.\-]+) = (.*)$")
OPCODE = re.compile(r"\s([a-z][a-z0-9\-]*)\(")
OP_NAME = re.compile(r'op_name="((?:[^"\\]|\\.)*)"')
REFERENCE = re.compile(r"%([A-Za-z0-9_.\-]+)")
# computations that run as a sequence of their own ops (not fused)
CALLED = re.compile(r"\b(?:body|condition|true_computation|"
                    r"false_computation)=%([A-Za-z0-9_.\-]+)")
FUSED = re.compile(r"\bcalls=%([A-Za-z0-9_.\-]+)")
BRANCHES = re.compile(r"branch_computations=\{([^}]*)\}")
WRAPPED = re.compile(r"^[A-Za-z_][\w\-]*\((.*)\)$")


class Scope(NamedTuple):
    phase: str        # one of PHASES
    leaf: str         # innermost of SCOPES on the path, "-" if none
    head: bool        # the vocabulary head (final norm, logits, loss)
    mixed: bool       # a fused op whose names disagree on the phase


class Instruction(NamedTuple):
    opcode: str
    names: tuple      # op_name paths, own or inherited from the caller


def _components(path: str) -> list:
    """``jit(f)/transpose(jvp(forward))/mlp/dot`` → the scope names with
    their transformations peeled: ``f``, ``forward``, ``mlp``, ``dot``."""
    out = []
    for part in path.split("/"):
        while (m := WRAPPED.match(part)) is not None:
            part = m.group(1)
        out.append(part)
    return out


def phase_of(path: str) -> str:
    parts = _components(path)
    if "grad_reduce" in parts or "param_gather" in parts:
        return "grad_exchange"
    if "optimizer" in parts:
        return "optimizer"
    if "bucket_views" in parts:
        return "bucket_views"
    if "rematted_computation" in parts:
        return "recompute"
    if "transpose(" in path:
        return "backward"
    # a model scope without ``forward`` is forward work that no gradient
    # reaches (the rotary tables, say), which the autodiff lifts out of
    # the ``jvp``
    if "forward" in parts or any(s in parts for s in MODEL_SCOPES):
        return "forward"
    return "unattributed"


def leaf_of(path: str) -> str:
    parts = [p for p in _components(path) if p in SCOPES]
    return parts[-1] if parts else "-"


def _computations(hlo_text: str):
    """({computation: [(instruction, opcode, op_names, called computations,
    operands, fused computation)]}, entry computation) of the program's
    text."""
    comps, entry, cur = {}, None, None
    for line in hlo_text.splitlines():
        m = COMPUTATION.match(line)
        if m:
            cur = comps.setdefault(m.group(2), [])
            if m.group(1):
                entry = m.group(2)
            continue
        m = INSTRUCTION.match(line)
        if m is None or cur is None:
            continue
        op = OPCODE.search(" " + m.group(2))
        names = OP_NAME.search(line)
        called = CALLED.findall(line)
        for b in BRANCHES.findall(line):
            called += [c.strip().lstrip("%") for c in b.split(",")]
        fused = FUSED.search(line) if op and op.group(1) == "fusion" else None
        cur.append((m.group(1), op.group(1) if op else "?",
                    tuple(names.group(1).split(";")) if names else (),
                    called, REFERENCE.findall(m.group(2)),
                    fused.group(1) if fused else None))
    return comps, entry


def _fused_names(comps: dict, comp: str) -> tuple:
    """A fusion whose root (printed last) is a bitcast is named by the
    nearest op upstream of it that names a phase, what the fusion
    computes; its own name is the bitcast's (the gradients' packing into
    a bucket, under the optimizer's reshape to the kernel's tiles).
    Otherwise nothing: its own name holds."""
    ops = {name: (opcode, names, refs)
           for name, opcode, names, _, refs, _ in comps.get(comp, ())}
    if not ops or ops[comps[comp][-1][0]][0] != "bitcast":
        return ()
    name = comps[comp][-1][0]
    while ops[name][0] == "bitcast" or all(
            phase_of(n) == "unattributed" for n in ops[name][1]):
        refs = [r for r in ops[name][2] if r in ops]
        if not refs:
            return ()
        name = refs[0]
    return ops[name][1]


def _borrowed(comp: list) -> dict:
    """{instruction: op_names} of one computation, where an instruction the
    compiler added without ``op_name`` (a layout copy, a zero fill, a
    loop it built) takes the names of the nearest op it feeds, else of the
    nearest op that feeds it."""
    own = {name: names for name, _, names, _, _, _ in comp}
    operands = {name: [r for r in refs if r in own and r != name]
                for name, _, _, _, refs, _ in comp}
    users: dict = {name: [] for name in own}
    for name, ops in operands.items():
        for o in ops:
            users[o].append(name)

    def nearest(start, edges):
        seen, frontier = {start}, [start]
        while frontier:
            nxt = []
            for x in frontier:
                for y in edges[x]:
                    if y in seen:
                        continue
                    if own[y]:
                        return own[y]
                    seen.add(y)
                    nxt.append(y)
            frontier = nxt
        return ()

    return {name: names or nearest(name, users) or nearest(name, operands)
            for name, names in own.items()}


def instructions(hlo_text: str) -> dict:
    """{instruction: Instruction} of every computation that runs as its own
    ops: the entry and what its loops and conditionals call. A fusion that
    ends in a bitcast is named by what it computes (``_fused_names``). An
    instruction without ``op_name`` borrows its neighbours'
    (``_borrowed``); one inside a called computation with none to borrow
    takes its caller's."""
    comps, entry = _computations(hlo_text)
    comps = {c: [(name, opcode, (_fused_names(comps, fused) if fused
                                 else ()) or names, called, refs, fused)
                 for name, opcode, names, called, refs, fused in ops]
             for c, ops in comps.items()}
    out = {}
    todo = [(entry, ())] if entry is not None else []
    seen = set()
    while todo:
        comp, inherited = todo.pop()
        if comp in seen or comp not in comps:
            continue
        seen.add(comp)
        names = _borrowed(comps[comp])
        for name, opcode, _, called, _, _ in comps[comp]:
            out[name] = Instruction(opcode, names[name] or inherited)
            todo += [(c, out[name].names) for c in called]
    return out


def _phase_names(ins: Instruction) -> list:
    """Those of an op's ``;``-joined names (a fused op's) that name a
    phase, in order."""
    return [n for n in ins.names if phase_of(n) != "unattributed"]


def phase_map(hlo_text: str) -> dict:
    """{instruction: Scope} of the compiled program's text, empty where the
    program opens none of the step's scopes. Of an op's names those that
    name a phase decide: where they disagree the op takes the first one's
    phase and is marked ``mixed``."""
    out = {}
    for name, ins in instructions(hlo_text).items():
        named = _phase_names(ins)
        if not named:
            out[name] = Scope("unattributed", "-", False, False)
            continue
        phases = {phase_of(n) for n in named}
        head = any("head" in _components(n) for n in named)
        out[name] = Scope(phase_of(named[0]), leaf_of(named[0]), head,
                          len(phases) > 1)
    return out if any(sc.leaf != "-" for sc in out.values()) else {}


def scope_paths(hlo_text: str) -> dict:
    """{instruction: frozenset of the names on its path}, transformations
    peeled (``_components``), of the name ``phase_map`` reads: the first of
    an op's names that names a phase, else its first."""
    out = {}
    for name, ins in instructions(hlo_text).items():
        named = _phase_names(ins) or list(ins.names)
        out[name] = frozenset(_components(named[0])) if named else frozenset()
    return out


def _window_self_times(events: dict):
    """(per chip, [(op, self ns)] of the ops in the ``chipbench.window``
    span, clipped to it; the number of chips). Self time is an op's time
    less that of the ops nested in it, so a loop counts once."""
    win = [h for h in events["host"] if h[0] == trace.HOST_PREFIX + "window"]
    if len(win) != 1:
        raise RuntimeError(f"expected one window span, found {len(win)}")
    w0, w1 = win[0][1], win[0][1] + win[0][2]
    devs = sorted(events["devices"])
    if not devs:
        raise RuntimeError("no device ran an operation in the window")
    per_chip = []
    for d in devs:
        ops = [[name, max(s, w0), min(s + t, w1) - max(s, w0)]
               for name, s, t in events["devices"][d]
               if s + t > w0 and s < w1]
        self_t, _ = trace.nesting(ops)
        per_chip.append([(op[0], st) for op, st in zip(ops, self_t)])
    return per_chip, len(devs)


def scope_seconds(events: dict, paths: dict, scope: str):
    """Device self time in the window, seconds per chip (mean over chips),
    of the ops with ``scope`` on their path (``scope_paths``); None where
    no instruction of the program has it."""
    under = {name for name, p in paths.items() if scope in p}
    if not under:
        return None
    per_chip, n = _window_self_times(events)
    return sum(st for ops in per_chip for name, st in ops
               if name in under) / (n * 1e9)


def phase_seconds(events: dict, pmap: dict, top: int = 10):
    """Device self time per phase inside the ``chipbench.window`` span, in
    seconds per chip (mean over chips): {"phases": {phase: s}, "head": s,
    "mixed": s, "scopes": [[phase/leaf, s], ...] (the ``top`` largest)};
    None for an empty map. An op the map does not know is
    ``unattributed``."""
    if not pmap:
        return None
    per_chip, n_chips = _window_self_times(events)
    phases = dict.fromkeys(PHASES, 0.0)
    by_scope: dict = {}
    head = mixed = 0.0
    none = Scope("unattributed", "-", False, False)
    for ops in per_chip:
        for name, st in ops:
            sc = pmap.get(name, none)
            phases[sc.phase] += st
            key = f"{sc.phase}/{sc.leaf}"
            by_scope[key] = by_scope.get(key, 0.0) + st
            head += st if sc.head else 0.0
            mixed += st if sc.mixed else 0.0
    n = n_chips * 1e9
    ranked = sorted(by_scope.items(), key=lambda kv: -kv[1])[:top]
    return {"phases": {k: v / n for k, v in phases.items()},
            "head": head / n, "mixed": mixed / n,
            "scopes": [[k, v / n] for k, v in ranked]}
