"""Set-up: process start to the window's opening (imports, the chip,
weights, engine, every program compiled or loaded, warm traffic)."""


def read(run):
    return run.setup_s
