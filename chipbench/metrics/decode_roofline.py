"""Share of its roofline that the decode program reaches: the mean
bound time of the window's decode steps (the cell's ``decode_work`` over
their real rows and real contexts) over the mean device time of one
``paged_decode_step`` run in the trace."""
from chipbench import work


def read(ctx):
    steps = [s for s in ctx.timeline.window_steps() if s.kind == "decode"]
    runs = ctx.trace.calls("paged_decode_step") if ctx.trace else 0
    if not steps or not runs:
        return None
    bound = sum(work.bound_seconds(
        *ctx.reference.decode_work(ctx.sizes, s.contexts), ctx.peak)
        for s in steps) / len(steps)
    return 100.0 * bound / (ctx.trace.device_s("paged_decode_step") / runs)
