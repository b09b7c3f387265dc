"""Real edges over the ELL slots launched, weighted by the lanes each
launch covers: sum(edges * lanes_pad) / sum(slots * lanes_pad) over the
serve worker's ``exec.dispatch`` spans in the traced window, in %.
``None`` where no dispatch records its slots."""


def read(ctx):
    ds = [a for th, n, _, _, a in ctx.get("spans") or []
          if th == ctx.get("worker") and n == "exec.dispatch"
          and "slots" in a]
    launched = sum(a["slots"] * a["lanes_pad"] for a in ds)
    if not launched:
        return None
    return 100.0 * sum(a["edges"] * a["lanes_pad"] for a in ds) / launched
