"""A kernel's share of its roofline, in %: the least time the chip could
take for the work a step needs of it, over the time the named scopes took.

``scopes`` (globs, as ``scope_time_share``'s) names the rows of
``ctx["trace"]["scope_s"]`` the kernel runs under, all phases; their seconds
over the traced steps are the kernel's time a step. ``work`` names what the
configuration's reference module counts for it:
``kernel_work(conf_text, cfg, work, ctx) -> {"flops", "bytes"}`` a step, the
model's operations and the bytes it cannot avoid, whatever implements them.
The least time is the larger of flops over the chip's peak rate and bytes
over its memory's (``peaks.json``); which of the two it was is written to
``ctx["said"]`` and so to the line's ``run.readers``, with whatever else
``kernel_work`` says of its count. A reference without
``kernel_work``, a name it does not know and a trace with no matching row
give nothing to read, never 0."""

from benchmark import trace_reduce


def read(ctx, scopes, work):
    trace = ctx.get("trace")
    if not trace or not trace.get("steps"):
        return None
    seconds = trace_reduce.scope_seconds(trace.get("scope_s"), scopes)
    kernel_work = getattr(ctx.get("reference"), "kernel_work", None)
    if not seconds or kernel_work is None:
        return None
    need = kernel_work(ctx["conf_text"], ctx["cfg"], work, ctx)
    if not need:
        return None
    by_bound = {"compute": need["flops"] / ctx["peak"]["bf16_flops_per_s"],
                "memory": need["bytes"] / ctx["peak"]["hbm_bytes_per_s"]}
    bound = max(by_bound, key=by_bound.get)
    step_s = seconds / trace["steps"]
    ctx.setdefault("said", {})["roofline/" + work] = {
        **{k: v for k, v in need.items() if k not in ("flops", "bytes")},
        "bound": bound, "flops_a_step": need["flops"],
        "bytes_a_step": need["bytes"], "kernel_s_a_step": step_s}
    return 100.0 * by_bound[bound] / step_s
