"""RPL005 — engine-parity drift across the numpy and jax engines.

The repo ships two implementations of the same simulation contract: the
numpy reference engine (``core/c3sim.py`` + ``core/cluster.py`` /
``core/topology.py``) and the jax engine (``core/jax_engine.py``).  The
parity tests assert float-identical iterations and statistically equal
runs — but they can only catch drift in behavior they exercise.  This
rule catches the *config* form of drift mechanically: a ``SimConfig`` / ``ClusterConfig`` /
``Workload`` field that one engine side reads and the other silently
ignores means a knob that changes one engine's output and not the
other's.

Usage is over-approximated per module (any ``x.field`` attribute read,
``d["field"]`` literal subscript, or ``getattr(x, "field")``), so a
field consumed under a different object of the same name still counts —
false negatives are preferred over false positives here.  Fields that
legitimately flow indirectly (e.g. comm parameters folded into the jax
engine's ``comm_const`` by ``make_topology``) are accepted in the
reviewed baseline with a reason, keyed ``Class.field`` so the entry
expires if the declaration disappears.
"""
from __future__ import annotations

import ast
from typing import Dict, Iterator, List, Optional, Tuple

from repro.analysis.linter import FileCtx, Finding
from repro.analysis.rules import (Rule, dataclass_fields, path_in,
                                  used_field_names)

# (class, declaring module, (side-A modules, label), (side-B modules, label))
CONTRACTS: List[Tuple[str, str, Tuple[Tuple[str, ...], str],
                      Tuple[Tuple[str, ...], str]]] = [
    ("SimConfig", "src/repro/core/c3sim.py",
     (("src/repro/core/c3sim.py",), "the numpy engine (c3sim)"),
     (("src/repro/core/jax_engine.py",), "the jax engine")),
    ("ClusterConfig", "src/repro/core/cluster.py",
     (("src/repro/core/cluster.py", "src/repro/core/topology.py"),
      "the numpy cluster engine (cluster/topology)"),
     (("src/repro/core/jax_engine.py",), "the jax engine")),
    ("Workload", "src/repro/core/workload.py",
     (("src/repro/core/c3sim.py",), "the numpy engine (c3sim)"),
     (("src/repro/core/jax_engine.py",), "the jax engine")),
]

_SCOPE_PATHS = sorted({p for _, decl, (a, _l1), (b, _l2) in CONTRACTS
                       for p in (decl, *a, *b)})


def _field_node(tree: ast.AST, class_name: str,
                field: str) -> Optional[ast.AST]:
    for n in ast.walk(tree):
        if isinstance(n, ast.ClassDef) and n.name == class_name:
            for s in n.body:
                if (isinstance(s, ast.AnnAssign)
                        and isinstance(s.target, ast.Name)
                        and s.target.id == field):
                    return s
            return n
    return None


def _check_project(ctxs: Dict[str, FileCtx]) -> Iterator[Finding]:
    for cls, decl, (a_paths, a_label), (b_paths, b_label) in CONTRACTS:
        needed = (decl, *a_paths, *b_paths)
        if any(p not in ctxs for p in needed):
            continue                    # partial lint run: contract n/a
        fields = dataclass_fields(ctxs[decl].tree, cls)
        if fields is None:
            yield ctxs[decl].finding(
                "RPL005", ctxs[decl].tree,
                f"parity contract expects class {cls} declared in {decl} "
                f"— it is gone; update CONTRACTS in rules/parity.py",
                snippet=f"{cls}")
            continue
        used_a = set()
        for p in a_paths:
            used_a |= used_field_names(ctxs[p].tree)
        used_b = set()
        for p in b_paths:
            used_b |= used_field_names(ctxs[p].tree)
        for f in fields:
            one, other = None, None
            if f in used_a and f not in used_b:
                one, other = a_label, b_label
            elif f in used_b and f not in used_a:
                one, other = b_label, a_label
            if one is None:
                continue
            anchor = _field_node(ctxs[decl].tree, cls, f) \
                or ctxs[decl].tree
            yield ctxs[decl].finding(
                "RPL005", anchor,
                f"{cls}.{f} is read by {one} but not by {other} — the "
                f"engines would diverge when it changes; consume it on "
                f"both sides or baseline it with the indirect-flow "
                f"justification",
                snippet=f"{cls}.{f}")


RPL005 = Rule(
    id="RPL005",
    title="config field consumed by one engine but not the other",
    rationale="float-identical engine parity requires every SimConfig/"
              "ClusterConfig/Workload knob to influence both engines; a "
              "one-sided read is silent divergence",
    scope=path_in(*_SCOPE_PATHS),
    check_project=_check_project,
)
