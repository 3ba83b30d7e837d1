"""Which named scope each operation of a compiled program runs under, and
the device time of a scope's operations in a trace.

JAX writes each operation's scope path into the HLO's
``metadata={op_name="jit(f)/.../mla/dot_general"}``: the
``jax.named_scope`` names, bare or wrapped in the transformations that
produced the operation (``transpose(jvp(mla))``).  XLA keeps
that metadata on the instructions of the compiled program, a fusion taking
its root's.  The device trace names each operation by its instruction
(``%fusion.12``, see ``bench/xplane.py``), so the compiled program's text
ties every traced operation to the scope it ran under.  Control flow
(``while``, ``conditional``, ``call``) is left out: its events span its
bodies' operations, which are traced and mapped one by one.
"""
from __future__ import annotations

import re
from typing import Dict, Iterable, Optional

from bench.xplane import clip, length, union

# the scopes the program's expert and latent-attention layers open
SCOPES = ("mla", "moe.route", "moe.dispatch", "moe.experts", "moe.combine",
          "moe.shared")
CONTROL = frozenset(("while", "conditional", "call"))

_INSTRUCTION = re.compile(r"^\s*(?:ROOT\s+)?%?([\w.\-]+)\s*=\s*(.*)$")
_OP_NAME = re.compile(r'metadata=\{[^}]*?op_name="([^"]*)"')


def _opcode(rest: str) -> str:
    """The opcode of an instruction's text after ``name = ``: the word
    before the operands, past the shape (a tuple shape is bracketed)."""
    i = 0
    if rest.startswith("("):
        depth = 0
        for i, ch in enumerate(rest):
            depth += (ch == "(") - (ch == ")")
            if depth == 0:
                break
        i += 1
    else:
        i = rest.find(" ")
    m = re.match(r"\s*([\w\-]+)\(", rest[i:])
    return m.group(1) if m else ""


_WRAPPED = re.compile(r"^[\w.\-]+\((.*)\)$")


def scope_of(op_name: str, scopes: Iterable[str]) -> Optional[str]:
    """The innermost of ``scopes`` that is a component of ``op_name``, bare
    or inside transformations (``transpose(jvp(moe.experts))``)."""
    names = set(scopes)
    for part in reversed(op_name.split("/")):
        while part not in names and _WRAPPED.match(part):
            part = _WRAPPED.match(part).group(1)
        if part in names:
            return part
    return None


def scope_map(hlo_text: str, scopes: Iterable[str] = SCOPES
              ) -> Dict[str, str]:
    """``instruction name -> scope`` for every instruction of the compiled
    text (``Compiled.as_text()``) that runs under one of ``scopes``,
    control flow left out."""
    scopes = tuple(scopes)
    out: Dict[str, str] = {}
    for line in hlo_text.splitlines():
        m = _INSTRUCTION.match(line)
        meta = _OP_NAME.search(line)
        if not m or not meta or _opcode(m.group(2)) in CONTROL:
            continue
        scope = scope_of(meta.group(1), scopes)
        if scope is not None:
            out[m.group(1)] = scope
    return out


def scope_ms(run, names: Iterable[str]) -> Optional[float]:
    """Device milliseconds per traced unit of work in the operations that
    ran under any of ``names``, averaged over the devices; None where the
    run has no trace or no scope map (a program without these scopes), or
    no traced operation ran under them."""
    scopes = run.records[0].get("scopes") if run.records else None
    if run.trace is None or not scopes:
        return None
    names = set(names)
    per = []
    for d in run.trace.devices:
        spans = [(s, e) for n, s, e in run.trace.ops[d]
                 if scopes.get(n.lstrip("%")) in names]
        per.append(length(clip(union(spans), run.trace.lo, run.trace.hi)))
    if not any(per):
        return None
    return 1e3 * sum(per) / (1e9 * len(per) * len(run.trace.steps))


def traced_records(run) -> list:
    """The records of the traced units of work (the window's first)."""
    return run.records[:len(run.trace.steps)] if run.trace else []
