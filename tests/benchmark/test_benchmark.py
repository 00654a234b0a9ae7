"""Tests of the benchmark under ``benchmark/``: all on the CPU at tiny
sizes. None touches a TPU topology, at import or later."""

import json
import os
import re
import shutil
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark import (compare, inputs, model_flops, netconf,  # noqa: E402
                       trace_reduce)
from benchmark import run as bench_run  # noqa: E402
from benchmark.programs import cxxnet_trainer  # noqa: E402
from benchmark.references import convnet as reference  # noqa: E402
from benchmark.windows import resident as window  # noqa: E402

BENCH = os.path.join(ROOT, "benchmark")
FIXTURES = os.path.join(BENCH, "fixtures")
_NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
_UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")


def _manifest():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def _conf(name):
    with open(os.path.join(BENCH, "configs", name + ".conf")) as f:
        return f.read()


# --------------------------------------------------------------- (a) lint
def lint(manifest, root):
    """The rules of the manifest that a file can be checked against."""
    errs = []
    if set(manifest) != {"command", "paths", "run_seconds", "configs",
                         "workloads", "end_to_end", "per_layer"}:
        errs.append("keys")
    e2e = {m["name"]: m for m in manifest["end_to_end"]}
    cells = {w["name"]: w for w in manifest["workloads"]}
    configs = {c["name"]: c for c in manifest["configs"]}
    names = ([m["name"] for m in manifest["end_to_end"]]
             + [m["name"] for m in manifest["per_layer"]])
    if len(set(names)) != len(names) or len(cells) != len(
            manifest["workloads"]):
        errs.append("duplicate name")
    for n in list(names) + list(cells) + list(configs) + [
            w["traffic"] for w in cells.values()]:
        if not _NAME.match(n):
            errs.append("name %r" % n)
    for m in manifest["end_to_end"] + manifest["per_layer"]:
        if not _UNIT.match(m["unit"]):
            errs.append("unit %r" % m["unit"])
        if m["better"] not in ("lower", "higher"):
            errs.append("better of %s" % m["name"])
    if "setup_s" not in e2e:
        errs.append("no setup_s")
    for m in manifest["end_to_end"]:
        if not 0.01 <= m["bound"] <= 0.1:
            errs.append("bound of %s" % m["name"])
        if m["source"] not in ("host_clock", "device_trace"):
            errs.append("source of %s" % m["name"])

    def reporters(metric):
        return set(metric.get("workloads", cells))
    for m in manifest["per_layer"]:
        if set(m) - {"name", "unit", "better", "source", "layer", "moves",
                     "workloads"}:
            errs.append("keys of %s" % m["name"])
        if m["moves"] not in e2e:
            errs.append("%s moves no end-to-end metric" % m["name"])
            continue
        if not reporters(m) <= set(cells):
            errs.append("%s lists a cell that is not there" % m["name"])
        if not reporters(m) <= reporters(e2e[m["moves"]]):
            errs.append("%s: a listed cell does not report %s"
                        % (m["name"], m["moves"]))
    for w in cells.values():
        if w["config"] not in configs:
            errs.append("cell %s: config" % w["name"])
        if not os.path.exists(os.path.join(root, "benchmark", "traffic",
                                           w["traffic"] + ".json")):
            errs.append("cell %s: traffic file" % w["name"])
        if w["chips"] not in (1, 4) or not 0 < len(w["why"]) <= 200:
            errs.append("cell %s: chips or why" % w["name"])
    pairs = [(w["config"], w["traffic"]) for w in cells.values()]
    if len(set(pairs)) != len(pairs):
        errs.append("a pair of config and traffic is given twice")
    if sum(w["chips"] == 4 for w in cells.values()) > max(
            1, len(cells) // 4):
        errs.append("too many four-chip cells")
    used = {w["config"] for w in cells.values()}
    for c in configs.values():
        if c["name"] not in used:
            errs.append("config %s is used by no cell" % c["name"])
        if not os.path.exists(os.path.join(root, c["file"])):
            errs.append("config %s: file" % c["name"])
        if not any(c["file"].startswith(p + "/") for p in manifest["paths"]):
            errs.append("config %s: file outside paths" % c["name"])
    return errs


def test_manifest_lints():
    assert lint(_manifest(), ROOT) == []


def test_lint_catches_a_bad_manifest():
    m = _manifest()
    m["per_layer"][0]["moves"] = "nothing"
    m["end_to_end"][0]["unit"] = "items per s"
    m["workloads"][0]["traffic"] = "no-such-mix"
    assert len(lint(m, ROOT)) == 3


def test_every_metric_and_limit_file_is_there():
    m = _manifest()
    for metric in m["per_layer"]:
        desc = json.load(open(os.path.join(BENCH, "metrics",
                                           metric["name"] + ".json")))
        assert callable(bench_run.load_reader(BENCH, desc["reader"]))
    for w in m["workloads"]:
        limits = bench_run.resolve(w["name"])["limits"]
        assert set(limits) == {"loss1", "loss2", "loss3", "grad_worst",
                               "change_worst"}
        # None: read and shown, not compared (PERF.md section 2 says why)
        assert all(v is None or 0 < v < 1 for v in limits.values())
        assert limits["grad_worst"] and limits["change_worst"]
        assert [k for k, v in limits.items() if v is None] == (
            ["loss2"] if w["name"] == "googlenet-resident" else [])


# ------------------------------------------- (b) the harness is driven by data
def test_new_cell_metric_and_reader_are_found_by_name(tmp_path):
    before = {}
    for d, _, files in os.walk(BENCH):
        for f in files:
            if "__pycache__" not in d:
                p = os.path.join(d, f)
                before[os.path.relpath(p, BENCH)] = open(p, "rb").read()
    bench = str(tmp_path / "benchmark")
    shutil.copytree(BENCH, bench,
                    ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(os.path.join(bench, "configs", "alexnet-b2048.conf"),
                os.path.join(bench, "configs", "other-net.conf"))
    cfg = json.load(open(os.path.join(bench, "configs",
                                      "alexnet-b2048.json")))
    cfg.update(name="other-net", conf="configs/other-net.conf",
               batch_per_chip=64)
    json.dump(cfg, open(os.path.join(bench, "configs", "other-net.json"),
                        "w"))
    mix = json.load(open(os.path.join(bench, "traffic", "resident.json")))
    mix["sync_every"] = 4
    json.dump(mix, open(os.path.join(bench, "traffic", "short-groups.json"),
                        "w"))
    json.dump({"reader": "steps_seen", "args": {"scale": 2.0}},
              open(os.path.join(bench, "metrics", "steps_twice.json"), "w"))
    with open(os.path.join(bench, "readers", "steps_seen.py"), "w") as f:
        f.write("def read(ctx, scale):\n"
                "    return scale * ctx['window']['steps']\n")
    m = _manifest()
    m["configs"].append({"name": "other-net", "source": "a paper",
                         "file": "benchmark/configs/other-net.json",
                         "reduced": [], "why": "a test"})
    m["workloads"].append({"name": "other-cell", "config": "other-net",
                           "traffic": "short-groups", "chips": 1,
                           "why": "a test"})
    m["per_layer"].append({"name": "steps_twice", "unit": "count",
                           "better": "higher", "source": "program_counter",
                           "layer": "train step, whole",
                           "moves": "train_items_per_s_per_chip",
                           "workloads": ["other-cell"]})
    manifest_path = str(tmp_path / "BENCHMARK.json")
    json.dump(m, open(manifest_path, "w"))
    assert lint(m, str(tmp_path)) == []

    spec = bench_run.resolve("other-cell", bench, manifest_path)
    assert spec["cfg"]["batch_per_chip"] == 64
    assert spec["traffic"]["sync_every"] == 4
    assert [x["name"] for x in spec["per_layer"]] == ["steps_twice"]
    got = bench_run.per_layer_metrics(spec, {"window": {"steps": 21}})
    assert got == {"steps_twice": {"value": 42.0, "unit": "count"}}
    # an old cell does not report the new metric, and no file was edited
    old = bench_run.resolve("alexnet-resident", bench, manifest_path)
    assert "steps_twice" not in [x["name"] for x in old["per_layer"]]
    for rel, data in before.items():
        assert open(os.path.join(bench, rel), "rb").read() == data


def test_new_window_kind_program_and_reference_run_with_no_edit(tmp_path):
    """What a later PR's LM or forward-only cell needs: window code, a
    program adapter and a reference of its own, each a new file that a data
    file names, driven by ``run_cell`` as it stands."""
    import io
    bench = str(tmp_path / "benchmark")
    shutil.copytree(BENCH, bench,
                    ignore=shutil.ignore_patterns("__pycache__"))
    before = {os.path.join(d, f): open(os.path.join(d, f), "rb").read()
              for d, _, files in os.walk(bench) for f in files}

    def write(rel, text):
        with open(os.path.join(bench, rel), "w") as f:
            f.write(text)
    write("windows/count.py", """
def run(spec, seed, seconds, h):
    cfg, mix = spec["cfg"], spec["traffic"]
    assert spec["conf_text"] is None
    ref = h.part("references", cfg["reference"])
    program = h.part("programs", cfg["program"]).Program(seed)
    h.phases.mark("build_s")
    h.setup_done()
    n = program.count(mix["n"])
    h.window_closed()
    return {"attempted": n, "failed": 0,
            "end_to_end": {"train_items_per_s_per_chip": n / seconds},
            "numbers": {"count_gap": {"value": abs(n - ref.count(mix["n"])),
                                      "at": ""}},
            "ctx": {"window": {"steps": n}}, "run": {"kind": mix["kind"]}}
""")
    write("programs/counter.py", """
class Program:
    def __init__(self, seed):
        self.off = seed % 2
    def count(self, n):
        return n + self.off
""")
    write("references/counter.py", "def count(n):\n    return n\n")
    write("configs/counted.json", json.dumps(
        {"name": "counted", "program": "counter", "reference": "counter"}))
    write("traffic/count.json", json.dumps({"kind": "count", "n": 7}))
    write("traffic/count-more.json", json.dumps({"like": "count", "n": 9}))
    write("limits/count-cell.json", json.dumps({"limits": {"count_gap": 0}}))
    m = _manifest()
    m["configs"].append({"name": "counted", "source": "a paper",
                         "file": "benchmark/configs/counted.json",
                         "reduced": [], "why": "a test"})
    for name, mix in (("count-cell", "count"), ("count-more-cell",
                                                "count-more")):
        m["workloads"].append({"name": name, "config": "counted",
                               "traffic": mix, "chips": 1, "why": "a test"})
    manifest_path = str(tmp_path / "BENCHMARK.json")
    json.dump(m, open(manifest_path, "w"))
    assert lint(m, str(tmp_path)) == []

    spec = bench_run.resolve("count-cell", bench, manifest_path)
    r = bench_run.run_cell(spec, seed=4, seconds=2.0, trace=False,
                           require_tpu=False, log=io.StringIO(),
                           compile_cache=False)
    assert r["correct"] is True and r["attempted"] == 7
    assert r["metrics"]["train_items_per_s_per_chip"]["value"] == 3.5
    assert r["metrics"]["setup_s"]["value"] > 0
    assert r["run"]["kind"] == "count" and "build_s" in r["run"]["setup_phases"]
    assert r["compared"] == {"count_gap": [0, 0],
                             "window_compiles": [0.0, 0.0]}
    # an odd seed makes this program count one too many: not correct
    r = bench_run.run_cell(spec, seed=5, seconds=2.0, trace=False,
                           require_tpu=False, log=io.StringIO(),
                           compile_cache=False)
    assert r["correct"] is False and r["compared"]["count_gap"] == [1, 0]
    # a mix that is "like" another changes only what it states
    more = bench_run.resolve("count-more-cell", bench, manifest_path)
    assert more["traffic"] == {"kind": "count", "n": 9}
    # count-more-cell has no limits file: nothing passes by default
    r = bench_run.run_cell(more, seed=4, seconds=2.0, trace=False,
                           require_tpu=False, log=io.StringIO(),
                           compile_cache=False)
    assert r["correct"] is False and r["attempted"] == 9
    for path, data in before.items():
        assert open(path, "rb").read() == data


def test_a_mix_of_a_kind_with_no_window_code_is_refused():
    spec = bench_run.resolve("alexnet-resident")
    spec["traffic"] = dict(spec["traffic"], kind="no-such-kind")
    with pytest.raises(bench_run.Refused, match="windows/no-such-kind.py"):
        bench_run.run_cell(spec, 1, 1.0, False, require_tpu=False)


def test_the_sharded_mix_is_resident_with_the_batch_laid_over_data():
    one = bench_run.resolve("alexnet-resident")["traffic"]
    four = bench_run.resolve("alexnet-dp4")["traffic"]
    assert "batch_sharding" not in one and four["batch_sharding"] == "data"
    same = lambda mix: {k: v for k, v in mix.items()       # noqa: E731
                        if k not in ("what", "batch_sharding")}
    assert same(one) == same(four)


def test_a_reader_that_finds_nothing_leaves_its_metric_out():
    spec = bench_run.resolve("alexnet-resident")
    ctx = {"counters": {}, "window": {}, "trace": None, "chips": 1,
           "peak": {"bf16_flops_per_s": 197e12}, "flops_per_item": 1.0}
    assert bench_run.per_layer_metrics(spec, ctx) == {}


# ------------------------------------------------------ (c) trace reduction
def _ev(name, start, dur):
    return (name, float(start), float(dur))


def test_reduce_events_busy_classes_and_named_gaps():
    conv = "%fusion.1 = bf16[8,8]{1,0} fusion(bf16[8,8]{1,0} %a), " \
           "kind=kOutput, calls=%fused_computation"
    loop = "%add.2 = f32[8]{0} fusion(f32[8]{0} %b), kind=kLoop, calls=%f"
    pool = "%select-and-scatter.3 = bf16[8]{0} select-and-scatter(" \
           "bf16[8]{0} %c, bf16[8]{0} %d, bf16[] %e)"
    ar = "%all-reduce.4 = f32[8]{0} all-reduce(f32[8]{0} %g), replica_groups={}"
    dev = {"modules": [_ev("jit_step(123)", 100, 400),
                       _ev("jit_other(9)", 20, 10),
                       _ev("jit_step(123)", 600, 400)],
           "ops": [_ev(loop, 20, 10),               # before the stretch
                   _ev(conv, 100, 200), _ev(loop, 300, 100),
                   _ev(pool, 400, 100),             # busy to 500
                   _ev(conv, 600, 300), _ev(ar, 650, 50),   # nested
                   _ev(loop, 950, 50)]}             # gap 900..950
    spans = [_ev("bench.update_call", 0, 520), _ev("bench.sync", 520, 600),
             _ev("bench.update_call", 900, 40)]
    r = trace_reduce.reduce_events({"/device:TPU:0": dev,
                                    "/device:TPU:1": {"modules": []}},
                                   spans, "jit_step")
    assert r["steps"] == 2 and r["devices"] == 1
    assert r["window_s"] == pytest.approx(900e-9)
    assert r["busy_s"] == pytest.approx(750e-9)
    assert r["class_s"] == pytest.approx(
        {"matmul": 450e-9, "loop": 150e-9, "pool_bwd": 100e-9,
         "collective": 50e-9})
    assert r["device_ops"][0] == ["fusion.1__matmul_",
                                  pytest.approx(450e-9)]
    assert r["idle_gaps"][0] == ["bench.sync", pytest.approx(100e-9)]
    assert r["idle_gaps"][1] == ["bench.update_call", pytest.approx(50e-9)]


def test_reduce_events_returns_nothing_without_two_steps():
    dev = {"modules": [_ev("jit_step(1)", 0, 10)], "ops": [_ev("%a", 0, 5)]}
    assert trace_reduce.reduce_events({"/device:TPU:0": dev}, [],
                                      "jit_step") is None


def test_reduce_recorded_trace_matches_what_is_written_beside_it():
    path = os.path.join(FIXTURES, "tiny.xplane.pb")
    want = json.load(open(os.path.join(FIXTURES, "tiny.expected.json")))
    got = trace_reduce.reduce_trace(path, want["step_module"])
    assert got["steps"] == want["steps"]
    for key in ("window_s", "busy_s"):
        assert got[key] == pytest.approx(want[key], rel=1e-9)
    assert got["class_s"] == pytest.approx(want["class_s"], rel=1e-9)
    assert [g[0] for g in got["idle_gaps"]] == \
        [g[0] for g in want["idle_gaps"]]
    assert got["device_ops"][0][0] == want["device_ops"][0][0]
    assert 0 < got["busy_s"] <= got["window_s"]


# ------------------------------------------------------------ (d) FLOP count
def test_model_flops_agrees_with_a_hand_count():
    layers, _ = netconf.parse(_conf("alexnet-b2048"))
    macs = dict(model_flops.forward_macs(layers, (3, 227, 227)))
    assert macs["conv1"] == 55 * 55 * 96 * 3 * 11 * 11
    assert macs["conv2"] == 27 * 27 * 256 * (96 // 2) * 5 * 5     # grouped
    assert macs["fc6"] == (256 * 6 * 6) * 4096
    assert model_flops.train_flops_per_item(
        _conf("alexnet-b2048"), (3, 227, 227)) == 6.0 * sum(macs.values())

    layers, _ = netconf.parse(_conf("googlenet-b512"))
    macs = dict(model_flops.forward_macs(layers, (3, 224, 224)))
    px = 28 * 28                      # inception 3a sees 192 x 28 x 28
    hand = px * (64 * 192 + 96 * 192 + 128 * 96 * 9 + 16 * 192
                 + 32 * 16 * 25 + 32 * 192)
    assert sum(v for k, v in macs.items() if k.startswith("i3a_")) == hand
    assert macs["loss_fc"] == 1024 * 1000


# --------------------------------------------------- (f) the window's rate
class FakeClock:
    def __init__(self):
        self.now = 0.0

    def __call__(self):
        return self.now


def test_window_rate_is_all_items_over_all_time():
    clock = FakeClock()
    # one stalled group among fast ones: best-of would hide it
    step_cost = iter([0.1] * 10 + [1.0] * 10 + [0.1] * 100)

    def step():
        clock.now += 0.001            # the call returns at once

    def sync():
        for _ in range(10):
            clock.now += next(step_cost)
        return 1.5
    r = window.run_window(step, sync, items_per_step=32, seconds=12.0,
                          sync_every=10, clock=clock)
    # groups of 1.01 s, 10.01 s, 1.01 s: the third starts before 12 s pass
    assert r["groups"] == 3 and r["steps"] == 30
    assert r["elapsed_s"] == pytest.approx(12.03)
    assert r["items_per_s"] == pytest.approx(30 * 32 / 12.03)
    assert r["update_call_ms_median"] == pytest.approx(1.0)
    assert r["failed_steps"] == 0


def test_window_traces_one_group_and_keeps_it_out_of_the_off_rate():
    clock = FakeClock()
    marks = []

    def sync():
        clock.now += 1.0
        return float("nan") if len(marks) == 1 else 0.5
    r = window.run_window(lambda: None, sync, 8, seconds=0.5, sync_every=2,
                          clock=clock, trace_group=1,
                          trace_start=lambda: marks.append("start"),
                          trace_stop=lambda: marks.append("stop"))
    assert marks == ["start", "stop"] and r["groups"] == 2
    assert r["items_per_s_profiler_off"] == pytest.approx(2 * 8 / 1.0)
    assert r["failed_steps"] == 2     # the group whose loss was not finite


# -------------------------------------------------- (g) no chip, no result
def test_run_py_refuses_the_cpu_and_prints_no_metric():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, os.path.join(BENCH, "run.py"), "--workload",
         "alexnet-resident", "--seed", "3000000001", "--seconds", "1",
         "--trace", "0"], cwd=ROOT, env=env, capture_output=True, text=True,
        timeout=300)
    assert p.returncode != 0
    assert p.stdout.strip() == ""
    assert "TPU" in p.stderr


def test_unknown_device_kind_is_refused(monkeypatch):
    import jax

    class Dev:
        platform = "tpu"
        device_kind = "TPU v9 imaginary"
    monkeypatch.setattr(jax, "devices", lambda *a: [Dev()])
    with pytest.raises(bench_run.Refused, match="peaks.json"):
        bench_run.check_device(1)
    with pytest.raises(bench_run.Refused, match="4 chips"):
        bench_run.check_device(4)


# ------------------------------------------------- comparison arithmetic
def test_worst_leaf_measures_the_gap_of_norms_against_the_median_floor():
    ref = {"a": 1.0, "b": 2.0, "c": 1e-9}
    got = {"a": 1.1, "b": 2.0, "c": 0.01}
    v, at = compare.worst_leaf(got, ref)
    assert at == "a" and v == pytest.approx(0.1)     # c is held to median
    assert compare.worst_leaf({"a": 1.0}, ref)[0] == float("inf")
    rows = compare.judge({"x": {"value": 0.2, "at": ""},
                          "y": {"value": 0.0, "at": ""},
                          "z": {"value": 9.0, "at": ""}},
                         {"x": 0.1, "z": None, "w": 1.0})
    # over its limit; named by no limit; no number; shown but not compared
    assert [(r["name"], r["ok"]) for r in rows] == [
        ("w", False), ("x", False), ("y", False), ("z", True)]


def test_unmoved_state_reads_one_and_tiny_gradients_are_left_out():
    ref = {"loss": [1.0, 0.9, 0.8],
           "grad_norm": {"a": 1.0, "b": 1.0, "k": 1e-9},
           "change_norm": {"a": 0.1, "b": 0.1, "k": 1e-7}}
    still = {"loss": [1.0, 1.0, 1.0], "grad_norm": dict(ref["grad_norm"]),
             "change_norm": {"a": 0.0, "b": 0.0, "k": 5e-7}}
    n = compare.numbers(still, ref)
    assert n["change_worst"]["value"] == pytest.approx(1.0)
    assert n["loss3"]["value"] == pytest.approx(0.25)
    sound = dict(ref, change_norm={"a": 0.1, "b": 0.1, "k": 9e-7})
    assert compare.numbers(sound, ref)["change_worst"]["value"] == 0.0


# ------------------------------ (e) the reference against Trainer.update
def _tiny(workload, hw, **cfg_over):
    """A real configuration cut to a size a test can hold: float32 compute,
    so that the two sides agree to rounding, dropout on."""
    spec = bench_run.resolve(workload)
    spec["cfg"] = dict(
        spec["cfg"], input_shape=[3, hw, hw], batch_per_chip=8, ref_block=4,
        extra_cfg="eval_train = 0\nhealth_monitor = 1\n",
        dropout_stream={"dtype": "float32"}, **cfg_over)
    # GoogLeNet's last pool covers the whole 7x7 map; at 64x64 that map is 2x2
    spec["conf_text"] = spec["conf_text"].replace(
        "kernel_size = 7\n  stride = 7", "kernel_size = 2\n  stride = 2")
    return spec


@pytest.mark.parametrize("workload,hw", [("alexnet-resident", 67),
                                         ("googlenet-resident", 64)])
def test_reference_agrees_with_trainer_update(workload, hw):
    spec = _tiny(workload, hw)
    cfg = spec["cfg"]
    seed = 2**31 + 77
    ref = reference.Reference.for_config(spec["conf_text"], cfg, 8)
    program = cxxnet_trainer.Program(spec["conf_text"], cfg, 1, seed,
                                     spec["traffic"])
    got = window.first_steps(program, ref.hyper, 3)
    nums = compare.numbers(got, ref.run(seed, 3))
    assert set(nums) == {"loss1", "loss2", "loss3", "grad_worst",
                         "change_worst"}
    # float32 on both sides, the same weights, rows and dropout masks: what
    # is left is the order of float32 sums (and w3 - w0 cancelling)
    assert max(nums[k]["value"] for k in ("loss1", "loss2", "loss3")) < 1e-5
    assert nums["grad_worst"]["value"] < 1e-4
    assert nums["change_worst"]["value"] < 5e-3
    # the program's batch is the reference's blocks, one after another
    import numpy as np
    data, label = ref._block(inputs.seed_key(seed), 1, 1)
    np.testing.assert_array_equal(np.asarray(program.batches[1].data[4:8]),
                                  np.asarray(data))
    np.testing.assert_array_equal(np.asarray(program.batches[1].label[4:8]),
                                  np.asarray(label))


# -------------------- the control and the faults come out as not correct
SMALL_CONF = """
netconfig=start
layer[0->1] = conv:c1
  kernel_size = 3
  stride = 2
  nchannel = 16
layer[1->2] = relu
layer[2->3] = max_pooling
  kernel_size = 3
  stride = 2
layer[3->4] = lrn
  local_size = 5
  alpha = 0.001
  beta = 0.75
  knorm = 1
layer[4->5] = conv:c2
  ngroup = 2
  kernel_size = 3
  pad = 1
  nchannel = 32
layer[5->6] = relu
layer[6->7] = flatten
layer[7->8] = fullc:f1
  nhidden = 64
layer[8->9] = relu
layer[9->9] = dropout
  threshold = 0.5
layer[9->10] = fullc:f2
  nhidden = 10
layer[10->10] = softmax
netconfig=end
momentum = 0.9
wmat:lr = 0.05
bias:lr = 0.1
wmat:wd = 0.0005
random_type = xavier
"""
SMALL_LIMITS = {"loss1": 1e-4, "loss2": 1e-4, "loss3": 1e-4,
                "grad_worst": 1e-3, "change_worst": 5e-3}


def _small_spec(chips=1):
    spec = bench_run.resolve("alexnet-resident")
    spec["cell"] = dict(spec["cell"], chips=chips)
    spec["conf_text"] = SMALL_CONF
    spec["cfg"] = dict(spec["cfg"], input_shape=[3, 24, 24], n_class=10,
                       batch_per_chip=32, ref_block=16,
                       extra_cfg="eval_train = 0\nhealth_monitor = 1\n",
                       dropout_stream={"dtype": "float32"})
    spec["traffic"] = dict(spec["traffic"], sync_every=2, warm_steps=1)
    if chips > 1:
        spec["traffic"]["batch_sharding"] = "data"
    spec["limits"] = dict(SMALL_LIMITS)
    return spec


class _StateUnchanged(cxxnet_trainer.Program):
    """A step that computes and then returns its state as it was."""

    def step(self):
        import jax
        import jax.numpy as jnp
        tr = self.trainer
        keep = jax.tree.map(jnp.copy, (tr.params, tr.opt_state))
        super().step()
        tr.params, tr.opt_state = keep


class _PartOfBatch(cxxnet_trainer.Program):
    """Only the first ``1 / part`` of the rows reach the step, repeated to
    fill the batch: the mean is taken over those rows alone. With ``part``
    = chips it is what one chip computes when the exchange is left out."""
    part = 2

    def __init__(self, *a, **k):
        import jax
        import jax.numpy as jnp
        super().__init__(*a, **k)
        n = self.batch // self.part
        for b in self.batches:
            sh = b.data.sharding
            b.data = jax.device_put(
                jnp.tile(b.data[:n], (self.part, 1, 1, 1)), sh)
            b.label = jax.device_put(
                jnp.tile(b.label[:n], (self.part, 1)), sh)


class _QuarterOfBatch(_PartOfBatch):
    part = 4


def _run_small(chips=1, factory=None):
    import io
    log = io.StringIO()
    r = bench_run.run_cell(_small_spec(chips), seed=2**31 + 5, seconds=0.2,
                           trace=False, require_tpu=False,
                           program_factory=factory, log=log,
                           compile_cache=False)
    assert r["compared"] and list(r)[-1] == "compared"
    assert all(name in log.getvalue() for name in r["compared"])
    return r


def test_a_sound_run_is_correct_and_its_line_has_the_contracts_keys():
    r = _run_small()
    assert r["correct"] is True, r["compared"]
    assert {"correct", "attempted", "failed", "metrics", "device"} <= set(r)
    assert set(r["metrics"]) == {"train_items_per_s_per_chip", "setup_s"}
    assert r["attempted"] > 0 and r["failed"] == 0
    assert r["compared"]["window_compiles"] == [0.0, 0.0]
    assert set(r["device"]) == {"platform", "kind", "count",
                                "memory_peak_bytes"}


@pytest.mark.parametrize("chips,factory,tripped", [
    (1, _StateUnchanged, "change_worst"),
    (1, _PartOfBatch, "grad_worst"),
    (4, _QuarterOfBatch, "grad_worst"),
])
def test_a_broken_timed_path_comes_out_not_correct(chips, factory, tripped):
    r = _run_small(chips, factory)
    assert r["correct"] is False
    value, limit = r["compared"][tripped]
    assert value > 10 * limit
    if factory is _StateUnchanged:
        assert value == pytest.approx(1.0, abs=1e-3)


def test_four_chips_sound_run_is_correct():
    r = _run_small(4)
    assert r["correct"] is True, r["compared"]


def test_a_mix_that_does_not_lay_its_batch_cannot_run_across_chips():
    spec = _small_spec(4)
    del spec["traffic"]["batch_sharding"]
    with pytest.raises(ValueError, match="no mesh|a mesh"):
        bench_run.run_cell(spec, seed=3, seconds=0.1, trace=False,
                           require_tpu=False, compile_cache=False)


@pytest.mark.parametrize("precision", ["bf16", "fp8"])
def test_the_control_in_lower_precision_is_not_correct(precision):
    """The reference, put in the program's place and computed in the
    precision below the configuration's (bf16 below this float32 test
    configuration; fp8 below the cells' bfloat16), fails a limit."""
    spec = _small_spec()
    cfg = spec["cfg"]
    seed = 991
    want = reference.Reference.for_config(SMALL_CONF, cfg, 32).run(seed)
    got = reference.Reference.for_config(SMALL_CONF, cfg, 32,
                                         precision=precision).run(seed)
    rows = compare.judge(compare.numbers(got, want), SMALL_LIMITS)
    assert not all(r["ok"] for r in rows)
    sound = compare.judge(compare.numbers(want, want), SMALL_LIMITS)
    assert all(r["ok"] for r in sound)
