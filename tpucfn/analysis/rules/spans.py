"""span-balance: every emitted trace-span family is balanced and read.

The trace plane's analogue of the lost-Summary rule (ROADMAP
correctness follow-on, landed with ISSUE 13 — which adds the
``compile_fetch`` span and is exactly the kind of change that could
ship a write-only span).  Two rots, both silent at runtime:

* **unbalanced span** — a ``tracer.record(name, start=...)`` call that
  passes neither ``end=`` nor ``dur_s=`` writes a zero-duration span:
  the start was observed, the end never was, and every downstream
  percentile over that family reads 0.  (``queue_wait``'s retroactive
  record is the sanctioned *pattern* — start observed on another
  thread — and it is balanced: it passes ``end=``.  Point events go
  through ``.event()`` / ``kind="event"`` and are exempt: zero
  duration is their contract.)
* **write-only span** — a literal span name emitted somewhere but
  consumed by no reader in the package (``obs.aggregate``'s views, the
  postmortem, anything matching on the record's ``name``): the span
  costs a JSONL line per occurrence and tells nobody anything.
* **unpinned cross-host span** (ISSUE 20) — an emission passing
  ``remote_parent=`` (a cross-host causal link) whose name is not in
  the package's ``CROSS_HOST_SPAN_NAMES`` tuple: the merged timeline's
  link stats select carriers by that
  vocabulary, so an unpinned carrier's flow arrows silently vanish
  from the coverage accounting.  The reverse drifts too: a name pinned
  in the tuple that no emission site carries is a stale vocabulary
  entry — same contract as event kinds.

Emitters are ``X.record("lit", ..., start=...)`` and ``X.span("lit",
...)`` call sites (the ``start=`` keyword is what distinguishes a
trace-span record from the flight ring's same-named method).
Consumers are string literals compared (``==``/``in``/...) against a
``name`` field lookup — ``e.get("name")``, ``e["name"]``, a variable
bound from one — including comparisons against a module-level string
tuple (``CONTROL_SPAN_NAMES``), whose elements then all count as
consumed.  A package emitting no literal spans gets no findings.
"""

from __future__ import annotations

import ast

from tpucfn.analysis.core import Analysis, Finding
from tpucfn.analysis.rules.vocab import (
    _compared_literals,
    _is_field_lookup,
    _lookup_bound_names,
    _scope_walk,
)

RULE_ID = "span-balance"


def _literal_str(node: ast.expr) -> str | None:
    if isinstance(node, ast.Constant) and isinstance(node.value, str):
        return node.value
    return None


def _kw(call: ast.Call, name: str) -> ast.expr | None:
    for kw in call.keywords:
        if kw.arg == name:
            return kw.value
    return None


def _span_emissions(analysis: Analysis):
    """``(mod, call, name, balanced, is_event, is_carrier)`` for every
    literal-named trace-span emission in the package (``is_carrier``:
    the call passes ``remote_parent=`` — a cross-host link)."""
    for mod in analysis.modules:
        for node in ast.walk(mod.tree):
            if not isinstance(node, ast.Call) \
                    or not isinstance(node.func, ast.Attribute) \
                    or not node.args:
                continue
            name = _literal_str(node.args[0])
            if name is None:
                continue
            carrier = _kw(node, "remote_parent") is not None
            if node.func.attr == "record":
                if _kw(node, "start") is None:
                    continue  # flight-ring / SLO record, not a trace span
                kind = _kw(node, "kind")
                is_event = (_literal_str(kind) == "event"
                            if kind is not None else False)
                balanced = (_kw(node, "end") is not None
                            or _kw(node, "dur_s") is not None)
                yield mod, node, name, balanced, is_event, carrier
            elif node.func.attr == "span":
                # context-managed spans time their own end
                yield mod, node, name, True, False, carrier


def _module_str_tuples(analysis: Analysis) -> dict[str, list[str]]:
    """Module-level ``NAME = ("a", "b", ...)`` string tuples,
    package-wide — comparison sides naming one consume its elements."""
    out: dict[str, list[str]] = {}
    for mod in analysis.modules:
        for stmt in mod.tree.body:
            if not isinstance(stmt, ast.Assign):
                continue
            if not isinstance(stmt.value, (ast.Tuple, ast.List)):
                continue
            vals = []
            ok = True
            for e in stmt.value.elts:
                s = _literal_str(e)
                if s is None:
                    ok = False
                    break
                vals.append(s)
            if not ok or not vals:
                continue
            for t in stmt.targets:
                if isinstance(t, ast.Name):
                    out[t.id] = vals
    return out


def _consumed_names(analysis: Analysis) -> set[str]:
    """Every span name some reader in the package matches on."""
    tuples = _module_str_tuples(analysis)
    consumed: set[str] = set()
    for mod in analysis.modules:
        scopes = [mod.tree.body]
        for _qual, info in analysis.functions(mod).items():
            if not isinstance(info.node, ast.Lambda):
                scopes.append(info.node.body)
        for body in scopes:
            name_vars = _lookup_bound_names(body, "name")

            def is_name(e: ast.expr) -> bool:
                if _is_field_lookup(e, "name"):
                    return True
                return isinstance(e, ast.Name) and e.id in name_vars

            for node in _scope_walk(body):
                if not isinstance(node, ast.Compare):
                    continue
                sides = [node.left, *node.comparators]
                if not any(is_name(s) for s in sides):
                    continue
                consumed.update(_compared_literals(node, is_name))
                for s in sides:
                    if isinstance(s, ast.Name) and s.id in tuples:
                        consumed.update(tuples[s.id])
    return consumed


def check(analysis: Analysis):
    findings: list[Finding] = []
    emissions = list(_span_emissions(analysis))
    if not emissions:
        return findings
    consumed = _consumed_names(analysis)
    pinned = _module_str_tuples(analysis).get("CROSS_HOST_SPAN_NAMES", [])
    flagged_unconsumed: set[str] = set()
    flagged_unpinned: set[str] = set()
    carried: set[str] = set()
    for mod, call, name, balanced, is_event, carrier in emissions:
        if carrier:
            carried.add(name)
            if pinned and name not in pinned \
                    and name not in flagged_unpinned:
                flagged_unpinned.add(name)
                findings.append(Finding(
                    RULE_ID, mod.rel, call.lineno,
                    f"span {name!r} carries remote_parent= (a cross-host "
                    "causal link) but is not pinned in "
                    "CROSS_HOST_SPAN_NAMES — the merged timeline's link "
                    "stats count carriers by that vocabulary, so this "
                    "span's flow arrows silently vanish from coverage "
                    "accounting (add the name to the tuple)",
                    key=f"unpinned-crosshost:{name}"))
        if not is_event and not balanced:
            findings.append(Finding(
                RULE_ID, mod.rel, call.lineno,
                f"span {name!r} records a start but neither end= nor "
                "dur_s= — the end path was never observed, so every "
                "duration percentile over this family reads 0 (pass the "
                "measured end/duration, or make it an explicit "
                "kind=\"event\" point marker)",
                key=f"unbalanced:{name}"))
        if is_event:
            continue  # point events are an open vocabulary by contract
        if name not in consumed and name not in flagged_unconsumed:
            flagged_unconsumed.add(name)
            findings.append(Finding(
                RULE_ID, mod.rel, call.lineno,
                f"span {name!r} is emitted here but no reader in the "
                "package ever matches on it — a write-only span costs a "
                "JSONL line per occurrence and tells nobody anything "
                "(consume it in an obs.aggregate view, or stop emitting "
                "it)",
                key=f"unconsumed:{name}"))
    # Reverse drift: a name pinned in CROSS_HOST_SPAN_NAMES that no
    # emission site in the package carries or even emits is a stale
    # vocabulary entry (the forward check above keeps carriers pinned;
    # this keeps the pin honest).  Emitted-but-not-carrying is fine —
    # e.g. data_wait carries remote_parent only on remote batches.
    emitted = {name for _m, _c, name, _b, _e, _cr in emissions}
    for stale in pinned:
        if stale in emitted:
            continue
        for mod in analysis.modules:
            loc = None
            for stmt in mod.tree.body:
                if isinstance(stmt, ast.Assign) and any(
                        isinstance(t, ast.Name)
                        and t.id == "CROSS_HOST_SPAN_NAMES"
                        for t in stmt.targets):
                    loc = stmt.lineno
                    break
            if loc is not None:
                findings.append(Finding(
                    RULE_ID, mod.rel, loc,
                    f"CROSS_HOST_SPAN_NAMES pins {stale!r} but no "
                    "emission site in the package records a span by "
                    "that name — stale vocabulary entry (drop it, or "
                    "restore the emitter)",
                    key=f"stale-pin:{stale}"))
                break
    return findings
