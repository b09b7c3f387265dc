"""Host staging of shard dispatches on the serve worker: packing (the
``exec.stage`` spans) and copies to the device (``exec.put``), over
sweep-iteration time, over the traced window only, in %.  ``None`` where
the program records neither span."""

STAGING = ("exec.stage", "exec.put")


def read(ctx):
    spans = [s for s in ctx.get("spans") or [] if s[0] == ctx.get("worker")]
    total = sum(e - s for _, n, s, e, _ in spans if n == "sweep.iter")
    staging = [e - s for _, n, s, e, _ in spans if n in STAGING]
    if not total or not staging:
        return None
    return 100.0 * sum(staging) / total
