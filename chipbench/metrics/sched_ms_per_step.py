"""Host time per engine step outside the model step: the scheduler's
plan, the block manager and the engine's bookkeeping. The client's wall
of ``step()`` less the engine's own ``IterationRecord.wall`` (which
covers input preparation, the model programs and sampling), averaged
over the window's steps."""


def read(ctx):
    steps = ctx.timeline.window_steps()
    if not steps:
        return None
    return sum((s.end - s.start) - s.wall for s in steps) / len(steps) * 1e3
