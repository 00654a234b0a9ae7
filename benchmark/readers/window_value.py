"""A value of the window's own result, such as the median of a host span."""


def read(ctx, name):
    return ctx["window"].get(name)
