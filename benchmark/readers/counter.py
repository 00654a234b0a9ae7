"""A count the harness took (``ctx["counters"]``)."""


def read(ctx, name):
    return ctx["counters"].get(name)
