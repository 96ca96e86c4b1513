"""Mean over the window's steps of the slots that entered the decode tick
live, as a share of all slots (a step without a decode tick counts 0)."""


def read(run):
    if not run.steps:
        return None
    live = sum(c.live[0] for c in run.decode_calls if c.live)
    return 100.0 * live / (len(run.steps) * run.slots)
