"""The two per-layer metrics that read the program's own phase account
(``readers/program_phase.py``): on the CPU, no chip."""

import importlib.util
import json
import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark import run as bench_run  # noqa: E402

BENCH = os.path.join(ROOT, "benchmark")
NEW = {"init_model_s": "init.model",
       "step_build_s": "jit.build/jit.train_step"}


RAN = {"window": {"steps": 10}}       # a run was made: ctx holds its window


def _read():
    return bench_run.load_reader(BENCH, "program_phase")


@pytest.fixture
def telemetry(monkeypatch):
    """The program's telemetry with an empty phase account for the test:
    the account is the process's and outlives reset()."""
    from cxxnet_tpu.utils import telemetry
    monkeypatch.setattr(telemetry._REG, "phase_s", {})
    return telemetry


def _manifest():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def test_reader_gives_the_accounts_entry_and_nothing_for_a_missing_one(
        telemetry):
    read = _read()
    assert read(RAN, name="init.model") is None
    with telemetry.phase("init.model"):
        pass
    assert read(RAN, name="init.model") == telemetry.phases()["init.model"]
    assert read(RAN, name="jit.build/jit.train_step") is None
    # the account is the process's: with no window in ctx no run was made
    assert read({"window": {}}, name="init.model") is None
    # a later model in the process (the harness's reference, were it one of
    # the program's) is not added in: the metric is the first's
    first = read(RAN, name="init.model")
    with telemetry.phase("init.model"):
        pass
    assert read(RAN, name="init.model") == first


@pytest.mark.parametrize("module", [None, object()],
                         ids=["program_not_loaded", "program_has_no_account"])
def test_reader_gives_nothing_where_the_program_has_no_account(
        monkeypatch, module):
    # the parent commit: its telemetry has no phases(); or no program at all
    monkeypatch.setitem(sys.modules, "cxxnet_tpu.utils.telemetry", module)
    assert _read()(RAN, name="init.model") is None


def test_the_manifest_still_lints_with_the_two_new_metrics():
    spec = importlib.util.spec_from_file_location(
        "bench_lint_rules", os.path.join(ROOT, "tests", "benchmark",
                                         "test_benchmark.py"))
    rules = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(rules)
    manifest = _manifest()
    assert rules.lint(manifest, ROOT) == []
    cells = [w["name"] for w in manifest["workloads"]]
    by_name = {m["name"]: m for m in manifest["per_layer"]}
    # in this order; what stands after them is the append-only rule's
    names = list(by_name)
    assert names.index("init_model_s") + 1 == names.index("step_build_s")
    for name, phase in NEW.items():
        m = by_name[name]
        assert (m["unit"], m["better"], m["source"], m["moves"]) == (
            "s", "lower", "program_span", "setup_s")
        assert m["workloads"] == cells
        with open(os.path.join(BENCH, "metrics", name + ".json")) as f:
            desc = json.load(f)
        assert desc["reader"] == "program_phase"
        assert desc["args"] == {"name": phase}
    assert by_name["init_model_s"]["layer"] == "model build"
    assert by_name["step_build_s"]["layer"] == "step build"


def test_a_traced_line_carries_both_metrics_once_the_program_has_run(
        telemetry):
    spec = bench_run.resolve("alexnet-resident")
    spec["per_layer"] = [m for m in spec["per_layer"] if m["name"] in NEW]
    assert bench_run.per_layer_metrics(spec, RAN) == {}   # left out, no raise
    with telemetry.phase("init.model"):
        pass
    with telemetry.phase("jit.build/jit.train_step"):
        pass
    got = bench_run.per_layer_metrics(spec, RAN)
    assert set(got) == set(NEW) and all(v["unit"] == "s" and v["value"] >= 0
                                        for v in got.values())
