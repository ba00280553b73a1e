"""The whole step's share of the chip's peak: the FLOPs that every
prefill and decode token of the window's steps needs (``work``: real
lengths, routed experts, causal attention) over window seconds times
the bf16 peak."""
from chipbench import work


def read(ctx):
    steps = ctx.timeline.window_steps()
    if not steps:
        return None
    flops = sum(work.decode_work(ctx.sizes, s.contexts)[0] if s.kind ==
                "decode" else sum(work.prefill_work(ctx.sizes, p)[0]
                                  for p in s.prompts) for s in steps)
    return 100.0 * flops / (ctx.timeline.window_s * ctx.peak["flops_bf16"])
