"""Model FLOPs of the tokens processed in the traced sub-window over the
device's busy time at peak (see ``harness.mfu``)."""
from harness.mfu import read  # noqa: F401
