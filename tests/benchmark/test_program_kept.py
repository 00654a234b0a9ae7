"""The per-layer metrics that read the program's kept spans
(``readers/program_kept.py``), and the data files PR 40 added over the
readers that were there: on the CPU, no chip, fed times only."""

import json
import os
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark import run as bench_run  # noqa: E402

BENCH = os.path.join(ROOT, "benchmark")
HOST = ["h2d_call_ms", "args_call_ms", "dispatch_call_ms",
        "host_share_of_step"]
BUILD = {"step_trace_s": "jit.build/jit.train_step/trace",
         "step_lower_s": "jit.build/jit.train_step/lower",
         "step_cache_load_s": "jit.build/jit.train_step/cache_load",
         "init_params_s": "init.params"}
SCOPES = ["moe_token_side_time_share", "emb_time_share",
          "cast_params_time_share"]
NEW = HOST + list(BUILD) + SCOPES          # in the manifest's order
FIVE = ["alexnet-resident", "googlenet-resident", "alexnet-dp4",
        "smallthinker-ep4-train-8k", "sdar-ep8-train-8k"]
PARTS = {"parent": "train.update",
         "parts": [["train.h2d", 0.5], ["train.args", 0.5],
                   ["train.dispatch", 0.25]]}


def _read():
    return bench_run.load_reader(BENCH, "program_kept")


def _manifest():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


@pytest.fixture
def telemetry(monkeypatch):
    """The program's telemetry with an empty kept account for the test:
    the account is the process's and outlives reset()."""
    from cxxnet_tpu.utils import telemetry
    monkeypatch.setattr(telemetry._REG, "kept_rings", {})
    return telemetry


def _feed(telemetry, name, spans):
    """Plant occurrences, as (t0, dur) in seconds: times are fed, no test
    here reads a clock."""
    from collections import deque
    telemetry._REG.kept_rings.setdefault(
        name, deque(maxlen=telemetry.KEPT_CAP)).extend(spans)


def _ctx(steps, rate=None, batch=100, chips=1):
    return {"window": {"steps": steps, "items_per_s_profiler_off": rate},
            "cfg": {"batch_per_chip": batch}, "chips": chips}


@pytest.mark.parametrize("module", [None, object()],
                         ids=["program_not_loaded", "program_has_no_account"])
def test_nothing_on_a_program_without_the_account(monkeypatch, module):
    # the parent commit: its telemetry has no kept(); or no program at all
    monkeypatch.setitem(sys.modules, "cxxnet_tpu.utils.telemetry", module)
    assert _read()(_ctx(10, 1000.0), name="train.h2d") is None
    assert _read()(_ctx(10, 1000.0), **PARTS) is None


@pytest.mark.parametrize("ctx", [{}, {"window": {}}, {"window": None},
                                 {"window": {"steps": 0}}],
                         ids=["no_ctx", "empty", "none", "no_steps"])
def test_nothing_without_a_window(telemetry, ctx):
    _feed(telemetry, "train.h2d", [(float(i), 0.001) for i in range(5)])
    assert _read()(_ctx(5), name="train.h2d") == pytest.approx(1.0)
    assert _read()(ctx, name="train.h2d") is None


def test_nothing_where_fewer_than_two_occurrences_stand(telemetry):
    read = _read()
    assert read(_ctx(10), name="train.h2d") is None       # no such name
    _feed(telemetry, "train.h2d", [(0.0, 0.001)])
    assert read(_ctx(10), name="train.h2d") is None       # one
    _feed(telemetry, "train.h2d", [(1.0, 0.003)])
    assert read(_ctx(10), name="train.h2d") == pytest.approx(2.0)
    # a window of one step takes one occurrence: nothing to read
    assert read(_ctx(1), name="train.h2d") is None


def test_the_windows_last_steps_entries_and_no_earlier_ones(telemetry):
    # set-up's calls (the first steps, the warm-up) stand before the
    # window's in the ring and are slow: they are not the window's
    _feed(telemetry, "train.dispatch",
          [(float(i), 5.0) for i in range(13)]
          + [(100.0 + i, 0.002) for i in range(20)])
    read = _read()
    assert read(_ctx(20), name="train.dispatch", q=0.5) == pytest.approx(2.0)
    assert read(_ctx(20), name="train.dispatch", q=1.0) == pytest.approx(2.0)
    assert read(_ctx(21), name="train.dispatch", q=1.0) == pytest.approx(5e3)


def test_the_median_and_the_lower_quartile(telemetry):
    # six calls in ten return at once, four block for a whole step
    durs = [0.003, 0.004, 0.0035, 0.0031, 0.100, 0.0032, 0.101, 0.099,
            0.0033, 0.102]
    _feed(telemetry, "train.dispatch",
          [(float(i), d) for i, d in enumerate(durs)])
    read = _read()
    ms = sorted(1e3 * d for d in durs)
    assert read(_ctx(10), name="train.dispatch", q=0.5) == pytest.approx(
        (ms[4] + ms[5]) / 2)
    # position 0.25 x 9 = 2.25: a quarter of the way from the third value
    assert read(_ctx(10), name="train.dispatch", q=0.25) == pytest.approx(
        ms[2] + 0.25 * (ms[3] - ms[2]))
    assert read(_ctx(10), name="train.dispatch", q=0.25) < 3.3
    assert read(_ctx(10), name="train.dispatch") == read(
        _ctx(10), name="train.dispatch", q=0.5)      # the median by default


def test_the_hosts_share_against_a_given_profiler_off_rate(telemetry):
    # ten calls: 0.1 ms h2d, 0.4 ms args, the dispatch 3 ms (six) or 90 ms
    # (four, blocked), 0.05 ms of the call's own
    t = 0.0
    for i in range(10):
        disp = 0.090 if i % 10 >= 6 else 0.003
        _feed(telemetry, "train.update", [(t, 0.00055 + disp)])
        _feed(telemetry, "train.h2d", [(t + 0.00002, 0.0001)])
        _feed(telemetry, "train.args", [(t + 0.00013, 0.0004)])
        _feed(telemetry, "train.dispatch", [(t + 0.00054, disp)])
        t += 0.1
    read = _read()
    # 2,048 items a step at 20,480 items/s: a period of 100 ms; the host
    # needs 0.05 + 0.1 + 0.4 + 3.0 = 3.55 ms of it
    got = read(_ctx(10, rate=20480.0, batch=2048), **PARTS)
    assert got == pytest.approx(3.55)
    # four chips: 4 x 2,048 items a step at the same rate, 400 ms
    assert read(_ctx(10, rate=20480.0, batch=2048, chips=4),
                **PARTS) == pytest.approx(3.55 / 4)
    # no profiler-off group (a window of the traced group alone): nothing
    assert read(_ctx(10, rate=None, batch=2048), **PARTS) is None
    # a part the program does not keep: nothing, not a smaller share
    telemetry._REG.kept_rings.pop("train.args")
    assert read(_ctx(10, rate=20480.0, batch=2048), **PARTS) is None


def test_the_manifest_lints_and_the_data_files_say_what_is_read():
    import importlib.util
    spec = importlib.util.spec_from_file_location(
        "bench_lint_rules", os.path.join(ROOT, "tests", "benchmark",
                                         "test_benchmark.py"))
    rules = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(rules)
    manifest = _manifest()
    assert rules.lint(manifest, ROOT) == []
    by_name = {m["name"]: m for m in manifest["per_layer"]}
    names = list(by_name)
    # in the issue's order, one behind the other
    first = names.index(NEW[0])
    assert names[first:first + len(NEW)] == NEW

    def desc(name):
        with open(os.path.join(BENCH, "metrics", name + ".json")) as f:
            return json.load(f)
    for name in HOST:
        m = by_name[name]
        assert (m["better"], m["source"], m["layer"], m["moves"]) == (
            "lower", "program_span", "train step, host side",
            "train_items_per_s_per_chip")
        assert m["workloads"] == FIVE and desc(name)["reader"] == \
            "program_kept"
    assert desc("h2d_call_ms")["args"] == {"name": "train.h2d", "q": 0.5}
    assert desc("args_call_ms")["args"] == {"name": "train.args", "q": 0.5}
    assert desc("dispatch_call_ms")["args"] == {"name": "train.dispatch",
                                                "q": 0.25}
    assert desc("host_share_of_step")["args"] == PARTS
    assert by_name["host_share_of_step"]["unit"] == "%"
    for name, phase in BUILD.items():
        m = by_name[name]
        assert (m["unit"], m["better"], m["source"], m["moves"]) == (
            "s", "lower", "program_span", "setup_s")
        assert m["layer"] == ("model build" if name == "init_params_s"
                              else "step build")
        assert m["workloads"] == FIVE
        assert desc(name) == dict(desc(name), reader="program_phase",
                                  args={"name": phase})
    for name in SCOPES:
        m = by_name[name]
        assert (m["unit"], m["better"], m["source"], m["layer"]) == (
            "%", "lower", "device_trace", "kernels")
        assert m["workloads"] == FIVE[3:]
        assert desc(name)["reader"] == "scope_time_share"
    assert all(desc(name)["what"] for name in NEW)


@pytest.mark.parametrize("cell", FIVE)
def test_a_cell_lists_the_new_names_after_every_name_that_stood(cell):
    with open(os.path.join(ROOT, "tests", "benchmark",
                           "accepted.json")) as f:
        accepted = json.load(f)
    listed = [m["name"] for m in bench_run.resolve(cell)["per_layer"]]
    want = [n for n in NEW if n not in SCOPES or cell in FIVE[3:]]
    assert listed[-len(want):] == want
    assert not set(listed[:-len(want)]) & set(NEW)
    # whatever stood before this PR comes first, in its order
    stood = [n for n in accepted["per_layer"] if n in listed]
    assert [n for n in listed if n in stood] == stood
    assert max(listed.index(n) for n in stood) < listed.index(want[0])


def test_the_pinned_cell_lists_none_of_them():
    # tests/benchmark/test_keye_dsa.py holds the exact set of metrics that
    # name keye-ep8-train-8k; only a benchmark PR may turn that pin
    listed = [m["name"] for m in
              bench_run.resolve("keye-ep8-train-8k")["per_layer"]]
    assert not set(listed) & set(NEW)


def test_a_traced_line_carries_the_host_metrics_once_update_has_run(
        telemetry):
    spec = bench_run.resolve("alexnet-resident")
    spec["per_layer"] = [m for m in spec["per_layer"] if m["name"] in HOST]
    ctx = _ctx(6, rate=2000.0, batch=4)
    assert bench_run.per_layer_metrics(spec, ctx) == {}   # left out, no raise
    from tests.test_trainer_update import _batch, _trainer
    tr, b = _trainer(), _batch(device=True)
    for _ in range(8):
        tr.update(b)
    got = bench_run.per_layer_metrics(spec, ctx)
    assert list(got) == HOST
    assert [got[n]["unit"] for n in HOST] == ["ms", "ms", "ms", "%"]
    assert all(v["value"] > 0 for v in got.values())
    # every call holds its three parts, so no part's quantile passes the
    # whole call's median (no clock is compared with another)
    call = _read()(ctx, name="train.update")
    assert all(got[n]["value"] <= call for n in HOST[:3])


def test_the_reader_imports_nothing_of_the_program():
    code = ("import sys; sys.path.insert(0, %r)\n"
            "from benchmark import run\n"
            "read = run.load_reader(%r, 'program_kept')\n"
            "assert read({'window': {'steps': 5}}, name='train.h2d') is None\n"
            "assert not [m for m in sys.modules if m.startswith('cxxnet')]\n"
            "assert 'jax' not in sys.modules\n" % (ROOT, BENCH))
    subprocess.run([sys.executable, "-c", code], check=True, timeout=60)
