"""The benchmark's one command:

    python3 benchmark/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

One run, one process: resolve the cell from BENCHMARK.json and the data
files beside this one, hand it to the window code its traffic mix names
(``traffic/<mix>.json``'s ``kind`` -> ``windows/<kind>.py``), which sets up,
measures the window and compares with the plain reference, and print one JSON
line last on standard output. Nothing here knows a kind, a program or a
reference by name: a later PR adds any of them as a file.

It refuses anything but a TPU whose ``device_kind`` is in ``peaks.json``, and
fewer chips than the cell asks for, with a non-zero exit and no result.
"""

from __future__ import annotations

import time

_T0 = time.perf_counter()      # set-up is counted from here

import argparse                 # noqa: E402
import importlib.util           # noqa: E402
import json                     # noqa: E402
import os                       # noqa: E402
import shutil                   # noqa: E402
import sys                      # noqa: E402
import tempfile                 # noqa: E402

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH_DIR)
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
_MISS_EVENT = "/jax/compilation_cache/cache_misses"


class Refused(Exception):
    """The run cannot be made here: no result is printed."""


def _load_json(path):
    with open(path) as f:
        return json.load(f)


def resolve(workload: str, bench_dir: str = BENCH_DIR,
            manifest_path: str = None) -> dict:
    """Everything one cell needs, found by the names in the manifest."""
    manifest = _load_json(manifest_path or
                          os.path.join(os.path.dirname(bench_dir),
                                       "BENCHMARK.json"))
    cells = {w["name"]: w for w in manifest["workloads"]}
    if workload not in cells:
        raise Refused("no workload %r in BENCHMARK.json (it has: %s)"
                      % (workload, ", ".join(cells)))
    cell = cells[workload]
    entry = next(c for c in manifest["configs"] if c["name"] == cell["config"])
    cfg = _load_json(os.path.join(os.path.dirname(bench_dir), entry["file"]))
    conf_text = None              # the configuration as text, where it has one
    if "conf" in cfg:
        with open(os.path.join(bench_dir, cfg["conf"])) as f:
            conf_text = f.read()
    traffic = load_traffic(bench_dir, cell["traffic"])
    limits_path = os.path.join(bench_dir, "limits", workload + ".json")
    limits = _load_json(limits_path)["limits"] \
        if os.path.exists(limits_path) else {}

    def reports(metric):
        return "workloads" not in metric or workload in metric["workloads"]
    return {
        "cell": cell, "cfg": cfg, "conf_text": conf_text, "traffic": traffic,
        "limits": limits, "bench_dir": bench_dir,
        "end_to_end": [m for m in manifest["end_to_end"] if reports(m)],
        "per_layer": [m for m in manifest["per_layer"] if reports(m)],
    }


def load_traffic(bench_dir: str, name: str) -> dict:
    """A mix's parameters. ``"like": <mix>`` takes another mix's and changes
    only what this file states."""
    mix = _load_json(os.path.join(bench_dir, "traffic", name + ".json"))
    if "like" in mix:
        mix = dict(load_traffic(bench_dir, mix.pop("like")), **mix)
    return mix


def load_part(bench_dir: str, directory: str, name: str):
    """The module ``<directory>/<name>.py``: a reader, a window kind, a
    program or a reference, found by the name a data file gives."""
    path = os.path.join(bench_dir, directory, name + ".py")
    if not os.path.exists(path):
        raise Refused("no %s/%s.py under %s" % (directory, name, bench_dir))
    spec = importlib.util.spec_from_file_location(
        "bench_%s_%s" % (directory, name.replace("-", "_")), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def load_reader(bench_dir: str, name: str):
    return load_part(bench_dir, "readers", name).read


def per_layer_metrics(spec: dict, ctx: dict) -> dict:
    """Each of the cell's per-layer metrics through its own reader; one that
    finds nothing to read is left out of the line."""
    out = {}
    for m in spec["per_layer"]:
        desc = _load_json(os.path.join(spec["bench_dir"], "metrics",
                                       m["name"] + ".json"))
        value = load_reader(spec["bench_dir"], desc["reader"])(
            ctx, **desc.get("args", {}))
        if value is not None:
            out[m["name"]] = {"value": float(value), "unit": m["unit"]}
    return out


def check_device(chips: int, require_tpu: bool = True) -> dict:
    import jax
    try:
        devs = jax.devices()
    except RuntimeError as e:
        raise Refused("jax found no device: %s" % e)
    d = devs[0]
    if require_tpu and d.platform != "tpu":
        raise Refused("the benchmark measures a TPU; jax found platform %r"
                      % d.platform)
    if len(devs) < chips:
        raise Refused("the cell asks for %d chips, jax found %d"
                      % (chips, len(devs)))
    peaks = _load_json(os.path.join(BENCH_DIR, "peaks.json"))
    if require_tpu and d.device_kind not in peaks:
        raise Refused("device_kind %r is not in benchmark/peaks.json"
                      % d.device_kind)
    return {"platform": d.platform, "kind": d.device_kind,
            "count": len(devs), "peak": peaks.get(d.device_kind),
            "used": devs[:chips]}


def memory_peak(device) -> int:
    """The most of this chip's memory that was held at once: live arrays
    plus the running program's own scratch (its activations). On a TPU
    ``peak_bytes_in_use`` counts the arrays alone and ``peak_bytes_reserved``
    the scratch alone (it equals the step's ``memory_analysis()`` temp size,
    and the largest free block is the limit less both: PERF.md, PR 26), and
    a step holds both, so the peak is their sum."""
    stats = device.memory_stats() or {}
    return int(stats.get("peak_bytes_in_use", 0)
               + stats.get("peak_bytes_reserved", 0))


class Counters:
    """Compiles and cache misses of this process, from jax's own events."""

    def __init__(self):
        import jax
        self.compiles = 0
        self.misses = 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event, _secs, **_kw):
        if event == _COMPILE_EVENT:
            self.compiles += 1

    def _event(self, event, **_kw):
        if event == _MISS_EVENT:
            self.misses += 1


def enable_compile_cache() -> str:
    """jax's persistent cache at ``JAX_COMPILATION_CACHE_DIR`` where that is
    set, else at the fixed ``<checkout>/.jax_cache``: the path is part of
    the cache's key, so it never moves."""
    import jax
    d = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not d:
        d = os.path.join(ROOT, ".jax_cache")
        jax.config.update("jax_compilation_cache_dir", d)
    if "JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS" not in os.environ:
        # every program, however small: a warm run then misses nothing
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    return d


class Tracer:
    """The profiler around one sync-group, its file reduced and removed."""

    def __init__(self, step_module: str, keep_to: str = None):
        self.step_module = step_module
        self.keep_to = keep_to        # a copy of the file, for a fixture
        self.dir = None
        self.reduced = None

    def start(self):
        import jax
        self.dir = tempfile.mkdtemp(prefix="bench_trace_")
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0      # spans come from TraceAnnotation
        jax.profiler.start_trace(self.dir, profiler_options=opts)

    def stop(self):
        import glob
        import jax
        from benchmark import trace_reduce
        jax.profiler.stop_trace()
        try:
            files = sorted(glob.glob(os.path.join(
                self.dir, "plugins", "profile", "*", "*.xplane.pb")))
            if files:
                self.reduced = trace_reduce.reduce_trace(files[-1],
                                                         self.step_module)
                if self.keep_to:
                    shutil.copy(files[-1], self.keep_to)
        finally:
            shutil.rmtree(self.dir, ignore_errors=True)


class Phases(dict):
    """Seconds from one ``mark`` to the next, by name."""

    def __init__(self):
        super().__init__()
        self._last = time.perf_counter()

    def mark(self, name: str) -> None:
        now = time.perf_counter()
        self[name], self._last = now - self._last, now


class Harness:
    """What a window kind gets from the command."""

    def __init__(self, spec, dev, phases, counters, trace, program_factory):
        import contextlib
        import jax
        self.spec, self.dev = spec, dev
        self.phases, self.counters = phases, counters
        self.program_factory = program_factory    # tests plant faults here
        self._trace = trace
        self.span = jax.profiler.TraceAnnotation if trace else (
            lambda name: contextlib.nullcontext())
        self.traced = None
        self.setup_s = self.peak = None

    def part(self, directory: str, name: str):
        return load_part(self.spec["bench_dir"], directory, name)

    def tracer(self, step_module: str):
        """The profiler for one stretch of the window, in a traced run."""
        if self._trace:
            self.traced = Tracer(step_module)
        return self.traced

    def setup_done(self) -> None:
        """The window's first step comes next: set-up is the time from the
        start of this process to here, the accelerator's own start-up (the
        first ``jax.devices()``, ``chip_start_s`` among the phases) with it."""
        self.setup_s = time.perf_counter() - _T0
        self.misses_at_setup = self.counters.misses
        self._compiles = self.counters.compiles

    def window_closed(self) -> None:
        """Read the peak before anything else (the reference) runs."""
        self.window_compiles = self.counters.compiles - self._compiles
        self.peak = max(memory_peak(d) for d in self.dev["used"])


def run_cell(spec: dict, seed: int, seconds: float, trace: bool,
             require_tpu: bool = True, program_factory=None,
             log=sys.stderr, compile_cache: bool = True) -> dict:
    """One run of one cell; returns the result line as a dict. The four
    last arguments are for tests: any platform, a program with a fault
    planted in it, somewhere to keep the compared rows, and no change to
    this process's jax configuration."""
    import jax              # noqa: F401  (its import is a phase of set-up)
    from benchmark import compare

    cell = spec["cell"]
    kind = load_part(spec["bench_dir"], "windows", spec["traffic"]["kind"])
    phases = Phases()
    phases["imports_s"] = time.perf_counter() - _T0
    dev = check_device(cell["chips"], require_tpu)
    phases.mark("chip_start_s")
    if compile_cache:
        enable_compile_cache()
    h = Harness(spec, dev, phases, Counters(), trace, program_factory)
    out = kind.run(spec, seed, seconds, h)
    if h.setup_s is None or h.peak is None:
        raise RuntimeError("window kind %r never said when set-up was done "
                           "or the window closed" % spec["traffic"]["kind"])

    nums = dict(out["numbers"], window_compiles={
        "value": float(h.window_compiles), "at": ""})
    limits = dict(spec["limits"], window_compiles=0.0, **out.get("limits", {}))
    rows = compare.judge(nums, limits)
    reduced = h.traced.reduced if h.traced else None
    said = {}                 # what a reader says beside its number
    if trace:
        ctx = dict(out["ctx"], trace=reduced, chips=cell["chips"],
                   peak=dev["peak"], said=said,
                   counters={"compile_cache_misses": h.misses_at_setup})
        metrics = per_layer_metrics(spec, ctx)
    else:
        values = dict(out["end_to_end"], setup_s=h.setup_s)
        metrics = {m["name"]: {"value": values[m["name"]], "unit": m["unit"]}
                   for m in spec["end_to_end"]}
    device = {"platform": dev["platform"], "kind": dev["kind"],
              "count": dev["count"], "memory_peak_bytes": int(h.peak)}
    result = {"correct": all(r["ok"] for r in rows),
              "attempted": out["attempted"], "failed": out["failed"],
              "metrics": metrics, "device": device}
    if reduced:
        device["busy_s"] = reduced["busy_s_mean"]
        device["window_s"] = reduced["window_s"]
        result["breakdown"] = {"device_ops": reduced["device_ops"],
                               "idle_gaps": reduced["idle_gaps"]}
    result["run"] = dict(out["run"], workload=cell["name"], seed=seed,
                         setup_s=h.setup_s, setup_phases=phases)
    if reduced:
        result["run"]["class_s"] = reduced["class_s"]
        result["run"]["scope_s"] = reduced["scope_s"]
    if said:
        result["run"]["readers"] = said
    result["compared"] = {r["name"]: [r["value"], r["limit"]] for r in rows}
    for r in rows:
        print("compared %-20s %-12.6g limit %-10s %s %s"
              % (r["name"], float("nan") if r["value"] is None else r["value"],
                 "not compared" if r["limit"] is None and r["ok"]
                 else r["limit"], "ok" if r["ok"] else "NOT OK", r["at"]),
              file=log)
    return result


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    try:
        spec = resolve(args.workload)
        result = run_cell(spec, args.seed, args.seconds, bool(args.trace))
    except Refused as e:
        print("benchmark refused: %s" % e, file=sys.stderr)
        return 2
    sys.stderr.flush()
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
