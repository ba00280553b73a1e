"""Engine host time around the decode program, per decode step: the
mean ``IterationRecord.wall`` of the window's decode steps (input
preparation, the program, per-sequence sampling with its syncs) less the
mean device time of one ``paged_decode_step`` run in the trace."""


def read(ctx):
    steps = [s for s in ctx.timeline.window_steps() if s.kind == "decode"]
    runs = ctx.trace.calls("paged_decode_step") if ctx.trace else 0
    if not steps or not runs:
        return None
    device = ctx.trace.device_s("paged_decode_step") / runs
    return (sum(s.wall for s in steps) / len(steps) - device) * 1e3
