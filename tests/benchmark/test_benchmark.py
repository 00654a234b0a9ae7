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
ACCEPTED = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                        "accepted.json")
_NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
_UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")


def _manifest():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def _conf(name):
    with open(os.path.join(BENCH, "configs", name + ".conf")) as f:
        return f.read()


def _accepted():
    with open(ACCEPTED) as f:
        return json.load(f)


# --------------------------------------------------------------- (a) lint
def append_only(manifest, root, accepted):
    """The one rule on where things stand: what ``accepted.json`` records
    (a ``benchmark`` PR brings it up to date; no other PR touches it) is
    still there and still first, in its order. A later PR puts a
    configuration, a cell, a per-layer metric and a cell on a metric's
    ``workloads`` list at the END of the list it joins: the driver reads an
    entry put before an accepted one as a change to that one. Nothing is
    said about what stands last."""
    errs = []

    def prefix(what, was, now):
        if now[:len(was)] != was:
            errs.append("append-only: %s has %s where accepted.json records "
                        "%s first: add at the end, move and remove nothing"
                        % (what, now[:len(was)], was))
    cells = [w["name"] for w in manifest["workloads"]]
    metrics = {m["name"]: m for m in manifest["per_layer"]}
    prefix("configs", accepted["configs"],
           [c["name"] for c in manifest["configs"]])
    prefix("workloads", accepted["workloads"], cells)
    prefix("per_layer", accepted["per_layer"], list(metrics))
    for name, was in accepted["per_layer_workloads"].items():
        if name in metrics:
            prefix("per_layer %s: workloads" % name, was,
                   metrics[name].get("workloads", cells))
    for m in manifest["per_layer"]:
        listed = [c for c in m.get("workloads", cells) if c in cells]
        if listed != [c for c in cells if c in listed]:
            errs.append("append-only: per_layer %s lists its cells in "
                        "another order than workloads has them" % m["name"])
    for name in accepted["lists_every_cell"]:
        if name in metrics and metrics[name].get("workloads", cells) != cells:
            errs.append("append-only: per_layer %s lists every cell, a new "
                        "one too" % name)
    for directory, files in accepted["files"].items():
        for f in files:
            if not os.path.exists(os.path.join(root, "benchmark", directory,
                                               f)):
                errs.append("append-only: benchmark/%s/%s is recorded in "
                            "accepted.json and is not there"
                            % (directory, f))
    return errs


def lint(manifest, root, accepted=None):
    """The rules of the manifest that a file can be checked against."""
    errs = append_only(manifest, root, accepted or _accepted())
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


def limits_errors(limits_file):
    """A limits file of a training cell: the five numbers, each with a
    limit in (0, 1). A loss (never a norm) may hold ``null``, read and
    shown but not compared, only with its reason beside it under
    ``"not_compared": {"<name>": "<why>"}`` (PERF.md section 2 has the
    readings)."""
    limits = limits_file["limits"]
    why = limits_file.get("not_compared", {})
    errs = []
    if set(limits) != {"loss1", "loss2", "loss3", "grad_worst",
                       "change_worst"}:
        errs.append("names %s" % sorted(limits))
    for name, v in limits.items():
        if v is None:
            if not name.startswith("loss") or not why.get(name):
                errs.append("%s is not compared and not_compared does not "
                            "say why, or it is no loss" % name)
        elif not 0 < v < 1:
            errs.append("%s: limit %r" % (name, v))
    if set(why) - {k for k, v in limits.items() if v is None}:
        errs.append("not_compared names a number that has a limit")
    return errs


def test_every_metric_and_limit_file_is_there():
    m = _manifest()
    for metric in m["per_layer"]:
        desc = json.load(open(os.path.join(BENCH, "metrics",
                                           metric["name"] + ".json")))
        assert callable(bench_run.load_reader(BENCH, desc["reader"]))
    for w in m["workloads"]:
        spec = bench_run.resolve(w["name"])
        assert spec["limits"], w["name"]
        if spec["traffic"]["kind"] == "resident":      # a training cell
            with open(os.path.join(BENCH, "limits",
                                   w["name"] + ".json")) as f:
                assert limits_errors(json.load(f)) == [], w["name"]
    # what the cells hold: the two losses with no upper reading are shown,
    # each with its reason; the language-model cell's limits were set anew
    # (PR 34) from the cell as committed, one model under every seed
    held = {w["name"]: bench_run.resolve(w["name"])["limits"]
            for w in m["workloads"][:4]}
    assert [k for k, v in held["googlenet-resident"].items()
            if v is None] == ["loss2"]
    assert held["smallthinker-ep4-train-8k"] == {
        "loss1": None, "loss2": 6e-5, "loss3": 3e-4, "grad_worst": 0.06,
        "change_worst": 0.03}
    assert limits_errors({"limits": dict(held["alexnet-resident"],
                                         loss2=None)}) != []
    assert limits_errors({"limits": dict(held["alexnet-resident"],
                                         grad_worst=None),
                          "not_compared": {"grad_worst": "why"}}) != []


# ------------------------------------------- (b) the harness is driven by data
def _join_every_cell_lists(manifest, cell):
    """A new cell goes to the end of the lists that hold every cell."""
    for m in manifest["per_layer"]:
        if m["name"] in _accepted()["lists_every_cell"]:
            m["workloads"].append(cell)


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
    _join_every_cell_lists(m, "other-cell")
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
    assert [x["name"] for x in spec["per_layer"]] == [
        "init_model_s", "step_build_s", "steps_twice"]
    spec["per_layer"] = spec["per_layer"][2:]
    got = bench_run.per_layer_metrics(spec, {"window": {"steps": 21}})
    assert got == {"steps_twice": {"value": 42.0, "unit": "count"}}
    # an old cell does not report the new metric, and no file was edited
    old = bench_run.resolve("alexnet-resident", bench, manifest_path)
    assert "steps_twice" not in [x["name"] for x in old["per_layer"]]
    for rel, data in before.items():
        assert open(os.path.join(bench, rel), "rb").read() == data


def _next_model_config_pr(bench, where):
    """What the next ``model_config`` PR brings, as files in the copy
    ``bench`` and as entries of the manifest that comes back: one
    configuration, one one-chip cell on the mix ``resident``, two per-layer
    metrics (a kernel's roofline share as a data file over a
    ``kernel_work`` name of its own new reference, and a reader of its
    own), a program and a limits file. ``where``: ``"end"`` appends
    everything, as the rule asks; ``"middle"`` puts each entry before the
    last accepted one of its list."""
    def write(rel, text):
        with open(os.path.join(bench, rel), "w") as f:
            f.write(text)
    write("configs/next-lm.json", json.dumps(
        {"name": "next-lm", "program": "next_trainer",
         "reference": "next_lm", "seq_len": 64, "batch_per_chip": 128}))
    write("programs/next_trainer.py", "class Program:\n    pass\n")
    write("references/next_lm.py",
          "def kernel_work(conf_text, cfg, name, ctx):\n"
          "    if name == 'delta_rule':\n"
          "        return {'flops': 2e9 * cfg['batch_per_chip'],\n"
          "                'bytes': 1e6}\n")
    write("limits/next-cell.json", json.dumps(
        {"limits": {"loss1": 1e-4, "loss2": None, "loss3": 1e-4,
                    "grad_worst": 0.05, "change_worst": 0.05},
         "not_compared": {"loss2": "no upper reading"}}))
    write("metrics/delta_rule_roofline.json", json.dumps(
        {"reader": "scope_roofline_share",
         "args": {"scopes": ["*_gdn/core"], "work": "delta_rule"}}))
    write("metrics/gdn_state_rows.json", json.dumps(
        {"reader": "counted_sum", "args": {"key": "state_rows"}}))
    write("readers/counted_sum.py",
          "def read(ctx, key):\n"
          "    got = (ctx.get('want') or {}).get(key)\n"
          "    return sum(got.values()) if got else None\n")
    m = _manifest()
    at = (lambda seq: len(seq)) if where == "end" else (
        lambda seq: len(seq) - 1)
    m["configs"].insert(at(m["configs"]), {
        "name": "next-lm", "source": "a model card",
        "file": "benchmark/configs/next-lm.json",
        "reduced": ["num_hidden_layers"], "why": "a rehearsal"})
    m["workloads"].insert(at(m["workloads"]), {
        "name": "next-cell", "config": "next-lm", "traffic": "resident",
        "chips": 1, "why": "a rehearsal"})
    joined = [p for p in m["per_layer"]
              if "smallthinker-ep4-train-8k" in p["workloads"]][:7]
    for p in joined:
        p["workloads"].insert(at(p["workloads"]), "next-cell")
    for name, unit, source in (
            ("delta_rule_roofline", "%", "device_trace"),
            ("gdn_state_rows", "count", "program_counter")):
        m["per_layer"].insert(at(m["per_layer"]), {
            "name": name, "unit": unit, "better": "higher", "source": source,
            "layer": "kernels", "moves": "train_items_per_s_per_chip",
            "workloads": ["next-cell"]})
    return m, [p["name"] for p in joined]


def _removed(m):
    del m["per_layer"][4]
    return m


def _reordered(m):
    m["workloads"][0], m["workloads"][1] = m["workloads"][1], \
        m["workloads"][0]
    return m


def _cell_taken_off_a_list(m):
    m["per_layer"][0]["workloads"].remove("alexnet-dp4")
    return m


@pytest.mark.parametrize("where,then,fails_with", [
    ("end", None, None),
    ("middle", None, "add at the end, move and remove nothing"),
    ("end", _removed, "per_layer has"),
    ("end", _reordered, "workloads has"),
    ("end", _cell_taken_off_a_list,
     "per_layer compile_cache_misses: workloads has"),
], ids=["appended", "in_the_middle", "a_metric_removed", "cells_reordered",
        "a_cell_taken_off_a_list"])
def test_the_next_model_config_pr_appends_and_edits_nothing(
        tmp_path, where, then, fails_with):
    """The rehearsal of what ISSUE 34 makes room for. Appended, it lints
    clean, resolves, and its data-file metric reads the new reference's
    ``kernel_work``; the same put before what is accepted, a removal and a
    reorder each fail by the append-only rule's own message."""
    bench = str(tmp_path / "benchmark")
    shutil.copytree(BENCH, bench,
                    ignore=shutil.ignore_patterns("__pycache__"))
    before = {os.path.join(d, f): open(os.path.join(d, f), "rb").read()
              for d, _, files in os.walk(bench) for f in files}
    m, joined = _next_model_config_pr(bench, where)
    if then:
        m = then(m)
    errs = lint(m, str(tmp_path))
    if fails_with:
        assert errs and all(e.startswith("append-only: ") for e in errs)
        assert any(fails_with in e for e in errs), errs
        return
    assert errs == []
    assert len(joined) == 7
    manifest_path = str(tmp_path / "BENCHMARK.json")
    json.dump(m, open(manifest_path, "w"))
    spec = bench_run.resolve("next-cell", bench, manifest_path)
    assert [x["name"] for x in spec["per_layer"]] == joined + [
        "delta_rule_roofline", "gdn_state_rows"]
    assert spec["limits"]["loss2"] is None
    with open(os.path.join(bench, "limits", "next-cell.json")) as f:
        assert limits_errors(json.load(f)) == []
    for directory, name in (("programs", spec["cfg"]["program"]),
                            ("references", spec["cfg"]["reference"])):
        assert bench_run.load_part(bench, directory, name)
    # the two new metrics through run.py as it stands: 256 GFLOP a step in
    # 2.6 ms a step is half of the peak; the accepted cells do not gain them
    spec["per_layer"] = [x for x in spec["per_layer"] if x["name"] in (
        "delta_rule_roofline", "gdn_state_rows")]
    ctx = {"window": {"steps": 40},
           "want": {"state_rows": {"b0_gdn": 7.0, "b1_gdn": 5.0}},
           "trace": {"steps": 10, "busy_s": 1.0, "scope_s": {
               "forward": {"b0_gdn/core": 0.006, "b1_gdn/core": 0.004},
               "backward": {"b0_gdn/core": 0.009, "b1_gdn/core": 0.007,
                            "b0_gdn/conv": 0.5}}},
           "peak": {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9},
           "reference": bench_run.load_part(bench, "references", "next_lm"),
           "conf_text": None, "cfg": spec["cfg"], "said": {}}
    got = bench_run.per_layer_metrics(spec, ctx)
    assert got["gdn_state_rows"] == {"value": 12.0, "unit": "count"}
    assert got["delta_rule_roofline"]["value"] == pytest.approx(
        100 * (256e9 / 197e12) / 0.0026)
    assert ctx["said"]["roofline/delta_rule"]["bound"] == "compute"
    old = bench_run.resolve("smallthinker-ep4-train-8k", bench,
                            manifest_path)
    assert not {"delta_rule_roofline", "gdn_state_rows"} & {
        x["name"] for x in old["per_layer"]}
    for path, data in before.items():
        assert open(path, "rb").read() == data


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
        _join_every_cell_lists(m, name)
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


# ----------------------------------------------------- (c') scopes, readers
# tf_ops as the cells' own traces have them (my chip runs, PR 34: the
# language-model cell's and the conv cells' traced runs; PERF.md section 3
# writes the rules down)
_TF_OPS = [
    ("jit(step)/jvp(conv1)/conv_general_dilated:", ("forward", "conv1")),
    ("jit(step)/transpose(jvp(conv1))/conv_general_dilated:",
     ("backward", "conv1")),
    ("jit(step)/update/conv1/mul:", ("update", "conv1")),
    ("jit(step)/jvp(i3a_1x1+i3a_3x3r+i3a_5x5r)/conv_general_dilated:",
     ("forward", "i3a_1x1+i3a_3x3r+i3a_5x5r")),
    ("jit(step)/jvp(b0_att)/~core/pallas_call:", ("forward", "b0_att/core")),
    ("jit(step)/transpose(jvp(b0_att))/~qkv/dot_general:",
     ("backward", "b0_att/qkv")),
    ("jit(step)/transpose(jvp(b1_moe))/jvp(b1_moe)/checkpoint/"
     "rematted_computation/~experts/pallas_call:",
     ("backward", "b1_moe/experts")),
    ("jit(step)/jvp(b2_moe)/checkpoint/cond/branch_1_fun/~dispatch/gather:",
     ("forward", "b2_moe/dispatch")),
    ("jit(step)/transpose(jvp(b2_moe))/jvp(b2_moe)/checkpoint/cond/"
     "branch_1_fun/transpose(jvp(~experts))/pallas_call:",
     ("backward", "b2_moe/experts")),
    ("jit(step)/transpose(jvp(b2_moe))/jvp(b2_moe)/checkpoint/cond/"
     "branch_0_fun/jvp(~combine)/gather:", ("backward", "b2_moe/combine")),
    ("jit(step)/jvp(head)/dot_general:", ("forward", "head")),
    ("jit(step)/update/b3_moe/add:", ("update", "b3_moe")),
    ("jit(step)/health/reduce_sum:", ("other", "health")),
    ("jit(step)/cast_params/convert_element_type:",
     ("other", "cast_params")),
    ("jit(step)/jvp()/add:", ("forward", "-")),
    ("jit(step)/transpose(jvp())/convert_element_type:", ("backward", "-")),
    ("jit(step)/reduce_sum:", ("other", "-")),
    ("jit(step)/transpose(jvp())/mul;jit(step)/transpose(jvp())/"
     "broadcast_in_dim", ("backward", "-")),
    ("", ("other", "-")),
]


@pytest.mark.parametrize("tf_op,want", _TF_OPS,
                         ids=[t[-48:] or "no_tf_op" for t, _ in _TF_OPS])
def test_scope_of_puts_a_tf_op_to_its_phase_and_scope(tf_op, want):
    assert trace_reduce.scope_of(tf_op) == want
    # the program's own tool (tools/trace_layers.py) says the same; it
    # keeps a phase for `health` and two names for a row in no scope
    from cxxnet_tpu.utils import devtrace
    phase, layer = devtrace.scope_of(tf_op)
    if phase == "health":
        phase = "other"
    if layer in (devtrace.UNNAMED, devtrace.NO_TF_OP):
        layer = trace_reduce.NO_SCOPE
    assert (phase, layer) == want


def test_reduce_events_sums_self_time_by_phase_and_scope():
    def fusion(n, kind="kLoop"):
        return "%%fusion.%d = f32[8]{0} fusion(f32[8]{0} %%p), kind=%s, " \
            "calls=%%f" % (n, kind)
    core = "%_core.1 = bf16[8]{0} custom-call(bf16[8]{0} %q), " \
           "custom_call_target=\"tpu_custom_call\""
    dev = {"modules": [_ev("jit_step(1)", 0, 500), _ev("jit_step(1)", 500, 500)],
           "ops": [
               (core, 0.0, 100.0, "jit(step)/jvp(b0_att)/~core/pallas_call:"),
               (fusion(1), 100.0, 50.0, "jit(step)/jvp(b0_att)/~qkv/mul:"),
               # a fusion with an operation nested in it: self time
               (fusion(2, "kOutput"), 200.0, 200.0,
                "jit(step)/transpose(jvp(b0_att))/~core/dot_general:"),
               (fusion(3), 250.0, 50.0, ""),
               (core, 500.0, 100.0,
                "jit(step)/jvp(b0_att)/~core/pallas_call:"),
               (fusion(4), 600.0, 25.0, "jit(step)/update/b0_att/sub:"),
               _ev(fusion(5), 700, 10)]}           # a plain tuple: no tf_op
    r = trace_reduce.reduce_events({"/device:TPU:0": dev}, [], "jit_step")
    assert r["scope_s"] == {
        "forward": {"b0_att/core": pytest.approx(200e-9),
                    "b0_att/qkv": pytest.approx(50e-9)},
        "backward": {"b0_att/core": pytest.approx(150e-9)},
        "update": {"b0_att": pytest.approx(25e-9)},
        "other": {"-": pytest.approx(60e-9)}}
    total = sum(v for rows in r["scope_s"].values() for v in rows.values())
    assert total == pytest.approx(r["busy_s"]) == pytest.approx(
        sum(r["class_s"].values()))
    assert trace_reduce.scope_seconds(r["scope_s"], ["*_att/core"]) == \
        pytest.approx(350e-9)
    assert trace_reduce.scope_seconds(r["scope_s"], ["*_att/*"],
                                      ["forward"]) == pytest.approx(250e-9)
    assert trace_reduce.scope_seconds(r["scope_s"], ["*_moe/experts"]) is None


def test_the_scoped_trace_reduces_to_what_is_written_beside_it():
    path = os.path.join(FIXTURES, "scoped.xplane.pb")
    want = json.load(open(os.path.join(FIXTURES, "scoped.expected.json")))
    got = trace_reduce.reduce_trace(path, want["step_module"])
    assert got["steps"] == want["steps"] == 4
    assert set(got["scope_s"]) == {"forward", "backward", "update", "other"}
    for phase, rows in want["scope_s"].items():
        assert got["scope_s"][phase] == pytest.approx(rows, rel=1e-9), phase
    for key in ("window_s", "busy_s"):
        assert got[key] == pytest.approx(want[key], rel=1e-9)
    assert got["class_s"] == pytest.approx(want["class_s"], rel=1e-9)
    # every kind of scope the rules name is in it, with time under it
    s = got["scope_s"]
    assert s["forward"]["l0"] > 0 and s["backward"]["l0"] > 0
    assert s["forward"]["l1/core"] > 0 and s["backward"]["l1/core"] > 0
    assert s["backward"]["l1/gate"] > 0          # transpose(jvp(~gate))
    assert s["update"]["l0"] > 0 and s["update"]["l1"] > 0
    total = sum(v for rows in s.values() for v in rows.values())
    assert total == pytest.approx(got["busy_s"], rel=1e-9)


def test_the_benchmarks_tf_op_reader_agrees_with_the_programs():
    """``trace_reduce.tf_ops`` and ``cxxnet_tpu.utils.devtrace.read_xplane``
    decode the same file on their own: the same operations in the same
    order with the same ``tf_op``, on both fixtures."""
    from cxxnet_tpu.utils import devtrace
    for name in ("scoped", "tiny"):
        path = os.path.join(FIXTURES, name + ".xplane.pb")
        ours = trace_reduce.tf_ops(path)
        theirs, _ = devtrace.read_xplane(path)
        assert set(ours) == set(theirs) and ours
        for plane, ops in theirs.items():
            assert ours[plane] == [(o.name, o.tf_op) for o in ops["ops"]]
        devices, _ = trace_reduce.read_xplane(path)
        for plane, lines in devices.items():
            assert [(e[0], e[3]) for e in lines["ops"]] == ours[plane]
    assert any(tf for _, tf in ours[plane])


_SCOPED = {"steps": 10, "busy_s": 2.0, "window_s": 2.5, "scope_s": {
    "forward": {"b0_att/core": 0.04, "b1_att/core": 0.03,
                "b0_moe/experts": 0.02, "b0_att/qkv": 0.05},
    "backward": {"b0_att/core": 0.11, "b1_att/core": 0.09,
                 "b0_moe/experts": 0.06}}}


def test_scope_time_share_reads_the_matching_rows_and_nothing_without():
    read = bench_run.load_reader(BENCH, "scope_time_share")
    ctx = {"trace": _SCOPED}
    assert read(ctx, scopes=["*_att/core"]) == pytest.approx(13.5)
    assert read(ctx, scopes=["*_att/core"], phases=["forward"]) == \
        pytest.approx(3.5)
    assert read(ctx, scopes=["*_moe/experts", "b0_att/qkv"]) == \
        pytest.approx(6.5)
    # a program that opens no such scope, no trace, a reduction of before
    assert read(ctx, scopes=["*_gdn/core"]) is None
    assert read({"trace": None}, scopes=["*_att/core"]) is None
    assert read({"trace": {"busy_s": 2.0}}, scopes=["*_att/core"]) is None


def test_scope_roofline_share_asks_the_reference_and_says_what_bounds_it():
    import types
    read = bench_run.load_reader(BENCH, "scope_roofline_share")
    asked = []

    def kernel_work(conf_text, cfg, name, ctx):
        asked.append((conf_text, cfg, name))
        return {"flash": {"flops": 1.97e12, "bytes": 8.19e8},
                "stream": {"flops": 1.97e9, "bytes": 8.19e9}}.get(name)
    ctx = {"trace": _SCOPED, "peak": {"bf16_flops_per_s": 197e12,
                                      "hbm_bytes_per_s": 819e9},
           "reference": types.SimpleNamespace(kernel_work=kernel_work),
           "conf_text": "conf", "cfg": {"seq_len": 8}}
    # 0.27 s over 10 steps; 1.97 TFLOP need 10 ms of the peak
    assert read(ctx, scopes=["*_att/core"], work="flash") == \
        pytest.approx(100 * 0.010 / 0.027)
    assert asked == [("conf", {"seq_len": 8}, "flash")]
    assert ctx["said"]["roofline/flash"]["bound"] == "compute"
    assert ctx["said"]["roofline/flash"]["kernel_s_a_step"] == \
        pytest.approx(0.027)
    assert read(ctx, scopes=["*_moe/experts"], work="stream") == \
        pytest.approx(100 * 0.010 / 0.008)     # the reader clips nothing
    assert ctx["said"]["roofline/stream"]["bound"] == "memory"
    # nothing to read, never 0: no matching row (the parent's program, a
    # stale executable), a name the reference does not know, a reference
    # without kernel_work, no trace
    assert read(ctx, scopes=["*_gdn/core"], work="flash") is None
    assert read(ctx, scopes=["*_att/core"], work="unknown") is None
    assert read(dict(ctx, reference=types.SimpleNamespace()),
                scopes=["*_att/core"], work="flash") is None
    assert read(dict(ctx, reference=reference), scopes=["*_att/core"],
                work="flash") is None          # convnet has no kernel_work
    assert read(dict(ctx, trace=None), scopes=["*_att/core"],
                work="flash") is None


@pytest.mark.parametrize("counted,want", [
    ({"moe.sparse": 4, "attn.flash": 4}, 0),
    ({"moe.sparse": 3, "moe.dense": 1}, 1),
    ({"attn.flash": 4}, None),
    (None, None)], ids=["all_sparse", "one_dense", "no_moe_layer",
                        "no_account"])
def test_program_count_reads_the_path_account(monkeypatch, counted, want):
    import types
    module = None if counted is None else types.SimpleNamespace(
        paths=lambda: dict(counted))
    monkeypatch.setitem(sys.modules, "cxxnet_tpu.utils.telemetry", module)
    read = bench_run.load_reader(BENCH, "program_count")
    args = {"name": "moe.dense", "of": ["moe.sparse", "moe.dense"]}
    assert read({"window": {"steps": 10}}, **args) == want
    assert read({"window": {}}, **args) is None        # no run was made


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
