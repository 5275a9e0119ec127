"""Device time by the program's own scopes.

The program traces its train step under a fixed set of ``jax.named_scope``
names (``src/repro/scopes.py``); each becomes a component of the
``op_name`` of every HLO instruction made under it, as a path component
(``.../stage/.../attn/dot_general``) or inside a transform's parentheses
(``transpose(jvp(head_loss))/...``).  This module gives every instruction
of the compiled step:

- its scopes: the names of the fixed set on its ``op_name``, outermost
  first; the innermost is the op's scope.  A fusion that computes a
  matrix product (a ``dot``, ``convolution`` or custom call in the
  computation it calls) takes the ``op_name`` of that product, not of its
  root: a matmul fused into a tick loop's ``dynamic-update-slice`` counts
  as the layer that does the matmul.
- its phase: ``recompute`` where the name holds ``rematted_computation``
  (``jax.checkpoint``'s second forward) or is forward work inside a
  backward task of the fused executor, else ``backward`` where it holds
  ``transpose(``, else ``forward``.

Times are the ops' self times (a loop less its body) in the traced window,
inside the runs of the compiled step, per chip; the readers divide by the
window's steps and average over the chips.
"""
from __future__ import annotations

import bisect
import re
from typing import Callable, Dict, Optional, Tuple

from bench import trace as tr

NAMES = ("embed", "stage", "attn", "mlp", "norm", "head_loss", "pipe",
         "pipe_hop", "pipe_f", "pipe_b", "pipe_bx", "pipe_bw", "grad_reduce",
         "optimizer")
MODEL = ("stage", "attn", "mlp", "norm", "embed", "head_loss")
BACKWARD_TASKS = ("pipe_b", "pipe_bx", "pipe_bw")
MATMUL = ("dot", "convolution", "custom-call")

_COMP = re.compile(r"^(?:ENTRY )?%([\w.\-]+) .*\{$")
_WRAP = re.compile(r"^(?:jvp|transpose|vmap)\((.*)\)$")
_OP_NAME = re.compile(r'op_name="([^"]*)"')


def _opcode(line: str) -> str:
    rest = line[tr._INSTR.match(line).end():]
    if rest.startswith("("):                   # a tuple shape
        depth = 0
        for i, ch in enumerate(rest):
            depth += (ch == "(") - (ch == ")")
            if depth == 0:
                break
        rest = rest[i + 1:]
    else:
        rest = rest.partition(" ")[2]
    m = re.match(r"\s*([\w\-]+)\(", rest)
    return m.group(1) if m else ""


def op_names(hlo: str) -> Dict[str, str]:
    """Each instruction's ``op_name`` by instruction name, a fusion's
    being that of the first matrix product in what it calls."""
    comps: Dict[str, list] = {}
    current = None
    for line in hlo.splitlines():
        m = _COMP.match(line)
        if m:
            current = comps.setdefault(m.group(1), [])
        elif line.startswith("}"):
            current = None
        elif current is not None and tr._INSTR.match(line):
            current.append(line)
    own, calls, matmul = {}, {}, {}
    for comp, lines in comps.items():
        for line in lines:
            name = tr._INSTR.match(line).group(1)
            m = _OP_NAME.search(line)
            own[name] = m.group(1) if m else ""
            op = _opcode(line)
            if op == "fusion":
                calls[name] = re.search(r"calls=%([\w.\-]+)", line).group(1)
            elif op in MATMUL and own[name]:
                matmul.setdefault(comp, own[name])

    def product(comp: str, seen: frozenset) -> Optional[str]:
        if comp in matmul:
            return matmul[comp]
        for line in comps.get(comp, ()):
            inner = calls.get(tr._INSTR.match(line).group(1))
            if inner and inner not in seen:
                found = product(inner, seen | {inner})
                if found:
                    return found
        return None

    return {name: (product(calls[name], frozenset([calls[name]]))
                   or own[name]) if name in calls else own[name]
            for name in own}


def scopes(op_name: str) -> Tuple[str, ...]:
    """The fixed set's names on ``op_name``, outermost first."""
    out = []
    for part in op_name.split("/"):
        while True:
            m = _WRAP.match(part)
            if not m:
                break
            part = m.group(1)
        if part in NAMES:
            out.append(part)
    return tuple(out)


def phase(op_name: str) -> str:
    if "rematted_computation" in op_name:
        return "recompute"
    if "transpose(" in op_name:
        return "backward"
    if any(s in BACKWARD_TASKS for s in scopes(op_name)):
        return "recompute"
    return "forward"


def step_times(ctx) -> Optional[Dict[int, Dict[str, float]]]:
    """Per chip, the seconds of self time of each instruction of the
    compiled step in the traced window; None without a trace."""
    if ctx.trace is None or not ctx.trace.devices or not ctx.hlo:
        return None
    lo, hi = ctx.trace.window()
    prefix = tr.module_name(ctx.hlo) + "("
    out = {}
    for d, evs in ctx.trace.devices.items():
        runs = tr.intervals([m for m in ctx.trace.modules.get(d, [])
                             if m.name.startswith(prefix)], lo, hi)
        starts = [a for a, _ in runs]

        def inside(e):
            i = bisect.bisect_right(starts, e.start) - 1
            return i >= 0 and e.end <= runs[i][1]
        mine = [e for e in evs if inside(e)]
        out[d] = tr.self_times(mine, lo, hi)
    return out


def step_ms(ctx, keep: Callable[[str], bool]) -> Optional[float]:
    """Milliseconds a step, averaged over the chips, in the ops whose
    ``op_name`` ``keep`` accepts.  None where no instruction of the
    compiled step is kept (the program lacks the scope) or there is no
    trace; 0.0 where the kept ops took no time."""
    times = step_times(ctx)
    if times is None or ctx.steps == 0:
        return None
    kept = {n for n, op in op_names(ctx.hlo).items() if keep(op)}
    if not kept:
        return None
    per_chip = [sum(s for n, s in own.items() if n in kept)
                for own in times.values()]
    return 1e3 * sum(per_chip) / len(per_chip) / ctx.steps
