"""Device time by the program's own parts over device busy time.

The program's ``step_program`` span (``tpucfn/obs/program.py``) holds, for
every instruction of the step it compiled, a class ``[pass, scope, root,
product]``: ``attrs["ops"]`` maps the instruction's name to an index into
``attrs["classes"]``.  A trace's event is named by its instruction's text,
``%<name> = ...``.  Over each device's cut, every event's self time
(``trace.self_seconds``: an instant goes to the operation that started last,
so a loop's shell does not count its body twice and the classes sum to busy
time) goes to the class of the instruction its name begins with; an event
whose instruction the map does not hold is *unmapped*.

The span read is the newest of label ``train_step`` that closed before the
traced interval: the program the traced steps ran.
"""

import re

from benchmark.readers import trace

INSTRUCTION = re.compile(r"^%([\w.\-]+) = ")


def program(ctx, label: str = "train_step"):
    if ctx.host_interval is None:
        return None
    found = [s for s in ctx.spans
             if s["name"] == "step_program"
             and s.get("attrs", {}).get("label") == label
             and s["start"] + s["dur_s"] <= ctx.host_interval[0]]
    return max(found, key=lambda s: s["start"]) if found else None


def events(ctx):
    """``([(event name, class index or None for unmapped, self seconds)],
    classes)`` over the devices' cuts; None where there is no span or no
    cut."""
    span, cuts = program(ctx), ctx.cut()
    if span is None or cuts is None:
        return None
    ops, rows = span["attrs"]["ops"], []
    for d, (t0, t1, _) in zip(ctx.devices, cuts):
        for name, s in trace.self_seconds(trace.clip(d.ops, t0, t1)).items():
            m = INSTRUCTION.match(name)
            rows.append((name, ops.get(m.group(1)) if m else None, s))
    return rows, span["attrs"]["classes"]


def by_class(ctx):
    """``({class index, or None for unmapped: seconds}, busy seconds,
    classes)``; None where there is no span or no cut."""
    found = events(ctx)
    if found is None:
        return None
    seconds: dict = {}
    for _, cls, s in found[0]:
        seconds[cls] = seconds.get(cls, 0.0) + s
    return seconds, sum(seconds.values()), found[1]


def selects(cls, passes, scope, root, product) -> bool:
    return ((passes is None or cls[0] in passes)
            and (scope is None or re.match(scope, cls[1]) is not None)
            and (root is None or cls[2] == root)
            and (product is None or cls[3] == product))


def read(ctx, passes=None, scope=None, root=None, product=None,
         unmapped=False):
    """``passes`` is a list of pass names, ``scope`` a pattern the scope
    begins with, ``root`` an opcode, ``product`` true or false; each one
    given narrows the classes selected.  ``unmapped=True`` adds the events
    the map does not hold to them, and alone selects those events only."""
    found = by_class(ctx)
    if found is None:
        return None
    seconds, busy, classes = found
    if not busy:
        return None
    narrowed = any(x is not None for x in (passes, scope, root, product))
    total = seconds.get(None, 0.0) if unmapped else 0.0
    if narrowed or not unmapped:
        total += sum(s for i, s in seconds.items() if i is not None
                     and selects(classes[i], passes, scope, root, product))
    return 100.0 * total / busy
