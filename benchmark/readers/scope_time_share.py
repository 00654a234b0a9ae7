"""The named scopes' share of device busy time, in %: ``scopes`` is a list
of globs over the scope names of ``ctx["trace"]["scope_s"]`` (``*_att/core``),
``phases`` the phases counted (all of them where it is left out). Where no
row matches there is nothing to read: a program that opens no such scope, or
an executable from a cache that predates the names, never reads 0."""

from benchmark import trace_reduce


def read(ctx, scopes, phases=None):
    trace = ctx.get("trace")
    if not trace or not trace.get("busy_s"):
        return None
    seconds = trace_reduce.scope_seconds(trace.get("scope_s"), scopes,
                                         phases)
    if seconds is None:
        return None
    return 100.0 * seconds / trace["busy_s"]
