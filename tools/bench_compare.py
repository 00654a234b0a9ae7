#!/usr/bin/env python
"""Gate on benchmark throughput regressions.

Usage:
    python tools/bench_compare.py [--threshold 0.10] [--dir REPO]
                                  [--bench FILE] [--baseline FILE]

Diffs the newest ``BENCH_*.json`` (the driver's per-round bench capture:
``{"parsed": <last line>, "tail": "<all emitted lines>"}`` — raw
``bench.py`` output files work too) against the committed numbers in
``BASELINE.json``'s ``"published"`` map (metric name -> value). Exit
codes:

* 0 — no regression, or nothing comparable: a metric whose measured value
  is ``null`` (a row that was not measured) or that has no published
  baseline is SKIPPED cleanly, never failed — a missing measurement is a
  structured non-result, not a regression.
* 1 — usage / unreadable input.
* 2 — at least one metric regressed by more than ``--threshold``
  (default 10%). "Regressed" respects the metric's direction: lower is
  worse for throughput rows, HIGHER is worse for latency rows (unit
  ``ms`` or a metric name containing ``latency``).

To start gating a metric, copy a trusted run's value into
``BASELINE.json``: ``"published": {"alexnet_imagenet_images_per_sec_per_chip":
15047.0}``. Sub-fields of a row gate too, opt-in per field, when the
baseline publishes ``"<metric>.<field>"`` — e.g.
``"serve_loopback_p99_latency_ms.ttft_p99_ms": 40.0`` gates the serve
row's TTFT tail, and
``"serve_fleet_p99_latency_ms.ttft_p99_ms"`` /
``".retry_rate"`` gate the routed-fleet row's tail and retry pressure
(the fleet TTFT comes from the router↔replica trace-id join), and
``"serve_throughput_rps.autopsy_compile_stall_pct"`` /
``".books_violations"`` gate the flood's compile-stall share and the
conservation-law auditor's violation count (both worse when HIGHER)
(direction-aware: ``*_ms`` / ``*_rate`` sub-fields are
worse when higher; null values skip cleanly like headline rows).
"""

import glob
import json
import os
import re
import sys


def find_newest_bench(dirname):
    """Newest BENCH_*.json by the rNN round number (mtime breaks ties —
    and orders any non-rNN names)."""
    cands = glob.glob(os.path.join(dirname, "BENCH_*.json"))
    if not cands:
        return None

    def key(p):
        m = re.search(r"BENCH_r(\d+)\.json$", os.path.basename(p))
        return (int(m.group(1)) if m else -1, os.path.getmtime(p))

    return max(cands, key=key)


def extract_lines(doc, raw_text=""):
    """Bench result lines from either capture shape: the driver wrapper
    ({"parsed": ..., "tail": "..."}) or raw bench.py JSONL output."""
    lines = []
    if isinstance(doc, dict) and "metric" in doc:
        lines.append(doc)
    if isinstance(doc, dict):
        for blob in (doc.get("tail") or "", raw_text):
            for ln in blob.splitlines():
                ln = ln.strip()
                if not ln.startswith("{"):
                    continue
                try:
                    d = json.loads(ln)
                except ValueError:
                    continue
                if isinstance(d, dict) and "metric" in d:
                    lines.append(d)
        parsed = doc.get("parsed")
        if isinstance(parsed, dict) and "metric" in parsed:
            lines.append(parsed)
    if isinstance(doc, list):
        lines.extend(d for d in doc if isinstance(d, dict)
                     and "metric" in d)
    # last occurrence of each metric wins (the driver keeps the headline
    # line last; tail may repeat it)
    out = {}
    for d in lines:
        out[d["metric"]] = d
    return list(out.values())


def lower_is_better(line):
    m = str(line.get("metric", ""))
    # the cold-start family (serve_cold_start_to_ready_s /
    # serve_scale_up_to_first_token_s / serve_reload_capacity_dip):
    # seconds-to-useful and capacity lost to recompiles — worse when
    # HIGHER even though the unit is s / ratio, not ms
    if (m.endswith("_to_ready_s") or m.endswith("_to_first_token_s")
            or m.endswith("_capacity_dip")):
        return True
    return line.get("unit") == "ms" or "latency" in m


def sub_lower_is_better(key, line):
    """Direction for a sub-field gated as ``<metric>.<key>``: latency
    sub-fields (``*_ms``, ``*latency*``) and failure-rate sub-fields
    (``*_rate``) are worse when HIGHER, whatever the parent row's unit —
    ``ttft_p99_ms`` on a throughput row still gates as a latency.
    Conversely throughput/capacity sub-fields (``*_rps``,
    ``*tokens_per_s*``, ``*occupancy*``) are worse when LOWER even on a
    latency row — ``mean_batch_occupancy`` on the serve rows gates as
    the coalescing win it measures. ``noisy_shed_rate`` (the
    serve_tenant_isolation row) is the one rate that is worse when
    LOWER: it measures the weighted-fair policy actually shedding the
    flooding tenant — a drop means the flood is getting through to the
    victim. (``fleet_scale_admission_latency_s`` needs no special
    case: the ``latency`` rule already gates it as worse-when-higher.)
    Utilization sub-fields (``*_live_pct`` — kv_live_pct on the
    throughput row: the live share of the decode KV cache) are worse
    when LOWER too: a drop means more padding/dead-slot waste, the
    regression the paged-KV before/after baseline (ROADMAP item 2)
    watches. (``queue_age_p99_ms`` needs no special case: the
    ``*_ms`` rule already gates it as worse-when-higher.)"""
    k = str(key)
    if (k.endswith("_to_ready_s") or k.endswith("_to_first_token_s")
            or k.endswith("_capacity_dip")):
        # the cold-start family as sub-fields: same direction as the
        # headline rule — time-to-useful and recompile capacity loss
        # are worse when HIGHER whatever the parent row measures
        return True
    if "ready_programs_pct" in k:
        # warm-grid readiness (the compile-cliff account): a drop means
        # more of the program grid is cold at admission — worse LOWER
        return False
    if k == "autopsy_compile_stall_pct":
        # the autopsy's compile-stall share of flood wall time (the
        # serve_throughput_rps row): a rise means more of the flood sat
        # behind cold programs — worse when HIGHER, unlike the other
        # _pct sub-fields that measure utilization
        return True
    if k == "books_violations":
        # the conservation-law auditor's violation count for the run:
        # any rise above the published 0 is bookkeeping corruption
        return True
    if k == "noisy_shed_rate":
        return False
    if k.endswith("_rps") or "tokens_per_s" in k or "occupancy" in k \
            or k.endswith("_live_pct") or k.endswith("hit_rate") \
            or k.endswith("retained_pct") or k.endswith("_speedup"):
        # prefix_hit_rate (the paged-KV shared-prefix reuse share) is
        # the other rate that is worse when LOWER: a drop means prompt
        # tokens are being re-prefilled instead of shared.
        # kv_retained_pct (the retained-cache share on the multiturn
        # row) and ttft_speedup (warm/cold ratio) gate the same way: a
        # drop means the retained conversation cache stopped holding
        # mass / stopped paying
        return False
    if "ttft" in k:
        # ttft sub-fields are time-to-first-token latencies — worse
        # when HIGHER even when the name lacks the _ms suffix
        # (checked after _speedup: ttft_speedup is a ratio, not a time)
        return True
    if "availability" in k or k in ("replays", "hedges", "hedge_wins"):
        # failover health (the serve_chaos_availability /
        # serve_hedged_tail rows): availability percentages and the
        # replay/hedge engagement counters are worse when LOWER — a
        # drop toward zero means the failover datapath stopped firing
        # while the error-rate sub-fields rose to tell the same story
        return False
    if k.endswith("_ms") or "latency" in k or k.endswith("_rate"):
        return True
    return lower_is_better(line)


def compare(lines, published, threshold):
    """-> (regressions, compared, skipped) lists of printable rows."""
    regressions, compared, skipped = [], [], []

    def gate(name, value, base, lower_better, null_detail):
        """Classify one (measured, published) pair into exactly one of
        the three row lists — shared by headline values and sub-fields
        so null-safety and direction handling cannot drift."""
        if value is None:
            skipped.append((name, "measured value is null (%s)"
                            % null_detail))
            return
        if not base:
            skipped.append((name, "baseline is zero/null"))
            return
        try:
            value, base = float(value), float(base)
        except (TypeError, ValueError):
            # placeholder strings ('TBD') etc.: not comparable, never
            # a gate failure
            skipped.append((name, "non-numeric value/baseline "
                            "(%r vs %r)" % (value, base)))
            return
        if not base:
            skipped.append((name, "baseline is zero"))
            return
        ratio = value / base
        bad = (ratio > 1.0 + threshold) if lower_better \
            else (ratio < 1.0 - threshold)
        row = (name, base, value, ratio - 1.0)
        (regressions if bad else compared).append(row)

    for line in lines:
        metric = line.get("metric")
        base = published.get(metric)
        if base is None:
            if line.get("value") is None:
                # count the null separately even unbaselined: the
                # end-of-run summary tallies how many rows the backend
                # never measured
                skipped.append((metric, "no published baseline; "
                                "measured value is null (%s)"
                                % line.get("error", "no error recorded")))
            else:
                skipped.append((metric, "no published baseline"))
        else:
            gate(metric, line.get("value"), base, lower_is_better(line),
                 line.get("error", "no error recorded"))
        # sub-fields (ttft_p99_ms, queue_wait_p99_ms, p50_ms, shed_rate,
        # ...) gate when the baseline publishes "<metric>.<key>" —
        # opt-in per sub-field, null-safe like the headline value.
        # Driven by the PUBLISHED keys, not the line's: a bench refactor
        # that renames or drops a gated sub-field must surface as a
        # visible skip, not silently retire the gate
        for name in sorted(k for k in published
                           if k.startswith(metric + ".")):
            key = name[len(metric) + 1:]
            if key in line:
                gate(name, line.get(key), published[name],
                     sub_lower_is_better(key, line),
                     "sub-field not measured")
            else:
                skipped.append((name, "sub-field absent from bench "
                                "line (renamed or no longer emitted?)"))
    return regressions, compared, skipped


def main(argv):
    threshold = 0.10
    dirname = os.path.join(os.path.dirname(os.path.abspath(__file__)), "..")
    bench_path = None
    baseline_path = None
    i = 0
    while i < len(argv):
        a = argv[i]
        if a == "--threshold" and i + 1 < len(argv):
            threshold = float(argv[i + 1])
            i += 2
        elif a == "--dir" and i + 1 < len(argv):
            dirname = argv[i + 1]
            i += 2
        elif a == "--bench" and i + 1 < len(argv):
            bench_path = argv[i + 1]
            i += 2
        elif a == "--baseline" and i + 1 < len(argv):
            baseline_path = argv[i + 1]
            i += 2
        else:
            print(__doc__, file=sys.stderr)
            return 1
    if bench_path is None:
        bench_path = find_newest_bench(dirname)
        if bench_path is None:
            print("bench_compare: no BENCH_*.json in %s — nothing to "
                  "compare (ok)" % dirname)
            return 0
    if baseline_path is None:
        baseline_path = os.path.join(dirname, "BASELINE.json")
    try:
        with open(bench_path) as f:
            raw = f.read()
    except OSError as e:
        print("bench_compare: cannot read %s: %s" % (bench_path, e),
              file=sys.stderr)
        return 1
    try:
        doc = json.loads(raw)
    except ValueError:
        # raw bench.py output is one JSON object PER LINE, not one
        # document: extract_lines parses it line-by-line
        doc = {}
    published = {}
    if os.path.exists(baseline_path):
        try:
            with open(baseline_path) as f:
                published = json.load(f).get("published", {}) or {}
        except (OSError, ValueError) as e:
            print("bench_compare: cannot read %s: %s" % (baseline_path, e),
                  file=sys.stderr)
            return 1
    lines = extract_lines(doc, raw)
    if not lines:
        print("bench_compare: no bench result lines in %s (ok: nothing "
              "to gate)" % bench_path)
        return 0
    regressions, compared, skipped = compare(lines, published, threshold)
    print("bench_compare: %s vs %s (threshold %.0f%%)"
          % (os.path.basename(bench_path), os.path.basename(baseline_path),
             100 * threshold))
    for metric, base, value, delta in compared:
        print("  ok    %-48s %12.2f -> %12.2f (%+.1f%%)"
              % (metric, base, value, 100 * delta))
    for metric, why in skipped:
        print("  skip  %-48s %s" % (metric, why))
    for metric, base, value, delta in regressions:
        print("  REGRESSION %-43s %12.2f -> %12.2f (%+.1f%% > %.0f%%)"
              % (metric, base, value, 100 * delta, 100 * threshold))
    # HEADLINE nulls only: a null sub-field of a row that DID measure
    # (e.g. mfu_pct absent because the card analysis errored) is not a
    # backend outage and must not be labeled one
    nulls = [m for m, why in skipped
             if "measured value is null" in why
             and "sub-field not measured" not in why]
    if nulls:
        # the gate must SAY how much of the trajectory it is not
        # checking: an all-null round otherwise reads as a clean pass
        # indistinguishable from a genuinely-gated one
        print("bench_compare: %d row(s) skipped: backend unreachable "
              "(measured value null) — %d row(s) actually gated"
              % (len(nulls), len(compared) + len(regressions)))
    if regressions:
        return 2
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
