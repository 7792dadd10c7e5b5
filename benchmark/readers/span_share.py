"""Share of the traced interval that the program's spans of one name cover."""


def read(ctx, span: str):
    if ctx.host_interval is None:
        return None
    t0, t1 = ctx.host_interval
    covered = 0.0
    found = False
    for s in ctx.spans:
        if s["name"] != span:
            continue
        a, b = max(s["start"], t0), min(s["start"] + s["dur_s"], t1)
        if b > a:
            covered += b - a
            found = True
    if not found or t1 <= t0:
        return None
    return 100.0 * covered / (t1 - t0)
