"""Gather rounds the ragged kernel ran over those of its full schedule
(every window-table row for every tile), weighted by the lanes each launch
covers: sum(rounds * lanes_pad) / sum(rounds_full * lanes_pad) over the
serve worker's ``exec.dispatch`` spans in the traced window, in %.
``None`` where no dispatch records its rounds."""


def read(ctx):
    ds = [a for th, n, _, _, a in ctx.get("spans") or []
          if th == ctx.get("worker") and n == "exec.dispatch"
          and "rounds" in a]
    full = sum(a["rounds_full"] * a["lanes_pad"] for a in ds)
    if not full:
        return None
    return 100.0 * sum(a["rounds"] * a["lanes_pad"] for a in ds) / full
