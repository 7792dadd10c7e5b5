"""The duration, or one attribute, of the newest of the program's spans of
one name (and, where given, one ``label`` attribute), wherever in the run it
lies: what a thing done once a run cost (``step_program``: lowering and
compiling the step and reading what it is made of)."""


def read(ctx, span: str, label=None, attr=None):
    found = [s for s in ctx.spans if s["name"] == span
             and (label is None or s.get("attrs", {}).get("label") == label)]
    if not found:
        return None
    newest = max(found, key=lambda s: s["start"])
    if attr is None:
        return float(newest["dur_s"])
    value = newest.get("attrs", {}).get(attr)
    return None if value is None else float(value)
