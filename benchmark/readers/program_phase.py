"""One entry of the program's own phase account: seconds the program spent,
in this process, in a named phase of set-up (``cxxnet_tpu.utils.telemetry``'s
always-on ``phases()``: ``init.model``, ``jit.build/jit.train_step``). The
account is looked up in the modules the program has loaded, nothing is
imported for it; a program without the account, or without the entry, gives
nothing to read. The account is the process's, not the run's, so there is
nothing to read either where ``ctx`` holds no window: no run was made."""

import sys


def read(ctx, name):
    if not ctx.get("window"):
        return None
    telemetry = sys.modules.get("cxxnet_tpu.utils.telemetry")
    phases = getattr(telemetry, "phases", None)
    if phases is None:
        return None
    return phases().get(name)
