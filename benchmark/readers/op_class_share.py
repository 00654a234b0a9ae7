"""One class of device operations' share of device busy time."""


def read(ctx, **args):
    trace = ctx.get("trace")
    if not trace or not trace["busy_s"]:
        return None
    seconds = trace["class_s"].get(args["class"])
    if seconds is None:
        return None
    return 100.0 * seconds / trace["busy_s"]
