"""Executor time (host staging, dispatch, kernel and copy back: ``exec_s``
ends in ``np.asarray``) over iteration time, summed over the window's
``IterStats``, in %."""


def read(ctx):
    its = ctx.get("iter_stats") or []
    total = sum(i.time_s for i in its)
    if not total:
        return None
    return 100.0 * sum(i.exec_s for i in its) / total
