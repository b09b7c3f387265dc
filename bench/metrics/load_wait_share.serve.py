"""Exposed shard-load wait (the ``shard.wait`` spans) over sweep-iteration
time, on the serve worker, over the traced window only, in %."""


def read(ctx):
    spans = [s for s in ctx.get("spans") or [] if s[0] == ctx.get("worker")]
    total = sum(e - s for _, n, s, e, _ in spans if n == "sweep.iter")
    if not total:
        return None
    return 100.0 * sum(e - s for _, n, s, e, _ in spans
                       if n == "shard.wait") / total
