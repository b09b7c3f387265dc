"""The serve worker's block on the device (the ``exec.wait`` spans, inside
each ragged collect) over sweep-iteration time, over the traced window
only, in %.  ``None`` where the program records no ``exec.wait``."""


def read(ctx):
    spans = [s for s in ctx.get("spans") or [] if s[0] == ctx.get("worker")]
    total = sum(e - s for _, n, s, e, _ in spans if n == "sweep.iter")
    waits = [e - s for _, n, s, e, _ in spans if n == "exec.wait"]
    if not total or not waits:
        return None
    return 100.0 * sum(waits) / total
