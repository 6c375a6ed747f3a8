"""Share of the traced window in which no op ran on the device, mean over
the chips, in %."""
from harness.readers import idle_share as read  # noqa: F401
