"""Mean of one attribute over the program's spans of one name that start
inside the traced interval: a counter the step reports once a step."""


def values(ctx, span: str, attr: str) -> list[float]:
    if ctx.host_interval is None:
        return []
    t0, t1 = ctx.host_interval
    return [float(s["attrs"][attr]) for s in ctx.spans
            if s["name"] == span and attr in s.get("attrs", {})
            and t0 <= s["start"] <= t1]


def read(ctx, span: str, attr: str):
    found = values(ctx, span, attr)
    return sum(found) / len(found) if found else None
