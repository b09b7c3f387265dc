"""Share of the window in which no operation ran on the device (the union
of device op intervals in the profile, averaged over chips), in %."""

from bench.devtrace import idle_share as read  # noqa: F401
