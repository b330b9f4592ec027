"""Share of the traced window in which no operation ran on the device
(%), averaged over the chips: what the one-client loop leaves idle."""
from bench.trace import idle_share as read  # noqa: F401
