"""The whole step's share of the chip's peak: model FLOPs only, published
peak, the rate of the groups that ran with the profiler off."""


def read(ctx):
    rate = ctx["window"].get("items_per_s_profiler_off")
    if not rate or not ctx.get("flops_per_item"):
        return None
    per_chip = rate / ctx["chips"]
    return 100.0 * ctx["flops_per_item"] * per_chip / \
        ctx["peak"]["bf16_flops_per_s"]
