"""The ELL pull-update kernel's share of its HBM roofline, in %: the least
time the dispatched shards' bytes allow (``bench/roofline.py``) over the
device time of the ``ell_partials`` kernel events in the window."""

from bench.roofline import kernel_share as read  # noqa: F401
