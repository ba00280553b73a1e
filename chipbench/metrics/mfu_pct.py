"""The whole step's share of the chip's peak: the FLOPs that every
prefill and decode token of the window's steps needs (the cell's
``decode_work`` and ``prefill_work``: real lengths, routed experts,
causal attention) over window seconds times the bf16 peak."""


def read(ctx):
    steps = ctx.timeline.window_steps()
    if not steps:
        return None
    arch, sizes = ctx.reference, ctx.sizes
    flops = sum(arch.decode_work(sizes, s.contexts)[0] if s.kind ==
                "decode" else sum(arch.prefill_work(sizes, p)[0]
                                  for p in s.prompts) for s in steps)
    return 100.0 * flops / (ctx.timeline.window_s * ctx.peak["flops_bf16"])
