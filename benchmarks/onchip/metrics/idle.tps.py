"""Share of the traced sub-window in which no operation ran on the chip."""
from harness.mfu import idle as read  # noqa: F401
