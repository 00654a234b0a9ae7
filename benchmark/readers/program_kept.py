"""The program's own kept spans (``cxxnet_tpu.utils.telemetry``'s always-on
``kept()``: name -> the ``(t0, dur)`` of the name's last occurrences, in
seconds on one clock), over the window's own calls: the last
``ctx["window"]["steps"]`` occurrences of a name (nothing calls the program
after the window's last sync, so those are the window's).

``name`` and ``q`` give a quantile of the name's durations, in ms (0.5 the
median, 0.25 the lower quartile; between two of the sorted values it lies on
the line through them, position ``q (n - 1)``).

``parent`` and ``parts`` give the host's share of a step, in %: the sum of
each part's quantile (``[[name, q], ...]``: the spans opened inside the
parent) and of the median of the parent's self time (a call's duration less
its parts' that lie inside it), over the step's period. The period is the
harness's, not the program's: ``ctx["cfg"]["batch_per_chip"]`` x
``ctx["chips"]`` items over ``ctx["window"]["items_per_s_profiler_off"]``.

The account is looked up in the modules the program has loaded, as
``program_phase`` does, and nothing is imported for it. A program without
the account, a ``ctx`` without a window, a name with fewer than two
occurrences and a window with no profiler-off rate give nothing to read,
never 0."""

import sys


def _quantile(values, q):
    v = sorted(values)
    pos = q * (len(v) - 1)
    lo = int(pos)
    hi = min(lo + 1, len(v) - 1)
    return v[lo] + (v[hi] - v[lo]) * (pos - lo)


def read(ctx, name=None, q=0.5, parent=None, parts=()):
    steps = (ctx.get("window") or {}).get("steps")
    telemetry = sys.modules.get("cxxnet_tpu.utils.telemetry")
    kept = getattr(telemetry, "kept", None)
    if not steps or kept is None:
        return None
    kept = {n: spans[-steps:] for n, spans in kept().items()}
    if any(len(kept.get(n, ())) < 2 for n in [name or parent] +
           [part for part, _ in parts]):
        return None

    def quantile(span_name, at):
        return _quantile([dur for _, dur in kept[span_name]], at)
    if name is not None:
        return 1e3 * quantile(name, q)
    rate = ctx["window"].get("items_per_s_profiler_off")
    if not rate:
        return None
    inside = [span for part, _ in parts for span in kept[part]]
    self_s = [dur - sum(d for t, d in inside if t0 <= t and t + d <= t0 + dur)
              for t0, dur in kept[parent]]
    host_s = _quantile(self_s, 0.5) + sum(quantile(*part) for part in parts)
    period_s = ctx["cfg"]["batch_per_chip"] * ctx["chips"] / rate
    return 100.0 * host_s / period_s
