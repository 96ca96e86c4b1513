"""Least time of the decode steps' live work over the time the program
spent in its blocking decode calls, in the window (``decode_s``, which
also holds any prefill queued on the chip before the call's sync).

Per step the least time is the larger of its bytes over HBM bandwidth
(every weight, the live slots' state read and written, their KV read)
and its model FLOPs over peak; only live slots count, so a program that
skips empty slots still reads at most 100%."""


def bound(run):
    b = f = 0.0
    for c in run.decode_calls:
        for live, ctx in zip(c.live, c.ctx):
            if live:
                b += run.cost.decode_step_bytes(live, ctx)
                f += run.cost.decode_step_flops(live, ctx)
    return b / run.peak["hbm_bytes_per_s"], f / run.peak["bf16_flops"]


def read(run):
    if run.peak is None or not run.decode_calls or run.decode_s <= 0:
        return None
    t_bytes, t_flops = bound(run)
    return 100.0 * max(t_bytes, t_flops) / run.decode_s
