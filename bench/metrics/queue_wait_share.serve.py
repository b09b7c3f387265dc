"""Queue wait over latency, summed over the window's ``QueryResult``s,
in %."""


def read(ctx):
    q = ctx.get("queries") or []
    total = sum(lat for lat, _ in q)
    if not total:
        return None
    return 100.0 * sum(w for _, w in q) / total
