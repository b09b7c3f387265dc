"""Lanes that carry a query over lanes launched, weighted by the ELL slots
each launch covers: sum(lanes_live * slots) / sum(lanes_pad * slots) over
the serve worker's ``exec.dispatch`` spans in the traced window, in %.
``None`` where no dispatch records its lanes."""


def read(ctx):
    ds = [a for th, n, _, _, a in ctx.get("spans") or []
          if th == ctx.get("worker") and n == "exec.dispatch"
          and "lanes_pad" in a]
    launched = sum(a["lanes_pad"] * a["slots"] for a in ds)
    if not launched:
        return None
    return 100.0 * sum(a["lanes_live"] * a["slots"] for a in ds) / launched
