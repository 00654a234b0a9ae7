"""One entry of the program's own path account: how many traced layers took
a named lowering (``cxxnet_tpu.utils.telemetry``'s always-on ``paths()``:
``moe.dense``, ``attn.flash``). ``of`` lists the lowerings of the same kind
of layer: where the program counted one of them, a name it did not count
reads 0; where it counted none (no such layer in the model, a program
without the account, no run made) there is nothing to read. The account is
looked up in the modules the program has loaded, as ``program_phase`` does."""

import sys


def read(ctx, name, of):
    if not ctx.get("window"):
        return None
    telemetry = sys.modules.get("cxxnet_tpu.utils.telemetry")
    paths = getattr(telemetry, "paths", None)
    if paths is None:
        return None
    counted = paths()
    if not any(n in counted for n in of):
        return None
    return counted.get(name, 0)
