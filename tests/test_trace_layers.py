"""Layer names in the compiled step, program spans on the profiler's clock,
the phase account, and the reduction of a trace to layers (utils/devtrace).

All on the CPU. The recorded trace under tests/fixtures/ comes from the chip
(tests/fixtures/record_layers_trace.py); nothing here touches a TPU."""

import glob
import json
import os
import re
import subprocess
import sys
import time

import numpy as np
import pytest

import jax

from cxxnet_tpu.io.data import DataBatch
from cxxnet_tpu.nnet.trainer import Trainer
from cxxnet_tpu.utils import devtrace, telemetry
from cxxnet_tpu.utils.config import parse_config_string
from tests.test_fusion import MODULE_CONF

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
FIXTURES = os.path.join(ROOT, "tests", "fixtures")

SMALL_CONF = """
netconfig=start
layer[0->1] = conv:c1
  kernel_size = 3
  nchannel = 4
layer[1->2] = relu
layer[2->3] = max_pooling:p1
  kernel_size = 2
  stride = 2
layer[3->4] = flatten
layer[4->5] = fullc:fc1
  nhidden = 5
layer[5->5] = softmax
netconfig=end
input_shape = 3,8,8
batch_size = 4
dev = cpu
eta = 0.1
momentum = 0.9
"""


def _trainer(conf, extra=""):
    tr = Trainer()
    for k, v in parse_config_string(conf + extra):
        tr.set_param(k, v)
    tr.init_model()
    return tr


def _batch(n=4, shape=(3, 8, 8), n_class=5, seed=0):
    rs = np.random.RandomState(seed)
    b = DataBatch()
    b.data = rs.rand(n, *shape).astype(np.float32)
    b.label = rs.randint(0, n_class, (n, 1)).astype(np.float32)
    b.batch_size = n
    return b


def _lowered_names(tr):
    """(every op_name of the lowered train step, the op_names of its
    convolutions and dots, the module's name)."""
    txt = tr.lower_update(_batch()).as_text(debug_info=True)
    locs = dict(re.findall(r'^(#loc\d+) = loc\("([^"]*)"', txt, re.M))
    matmuls = [locs.get(m, "") for m in re.findall(
        r"stablehlo\.(?:convolution|dot_general).* loc\((#loc\d+)\)$", txt,
        re.M)]
    return set(locs.values()), matmuls, re.search(r"module @(\S+)",
                                                  txt).group(1)


def _weighted_scopes(tr):
    return [tr.net.layer_scope(i) for i, ups in enumerate(tr.updaters)
            if ups]


# ------------------------------------------------ (a), (b): the scopes
@pytest.mark.parametrize("conf, extra, scopes", [
    (SMALL_CONF, "", ["c1", "fc1"]),
    # remat: the checkpointed apply keeps its layer's name on both passes
    (SMALL_CONF, "remat = 1\n", ["c1", "fc1"]),
    # sibling fusion: one scope for the group, named by its members
    (MODULE_CONF, "", ["stem", "b1+b3r+c5r", "b3", "c5", "dproj", "head"]),
], ids=["plain", "remat", "fused_siblings"])
def test_lowered_step_names_every_layer_on_both_passes(conf, extra, scopes):
    tr = _trainer(conf, extra)
    names, matmuls, module = _lowered_names(tr)
    assert module == "jit_step"        # resident.json finds the stretch by it
    for scope in scopes:
        assert any(n.startswith("jit(step)/jvp(%s)/" % scope)
                   for n in names), scope
        assert any(n.startswith("jit(step)/transpose(jvp(%s))/" % scope)
                   for n in names), scope
    for layer in _weighted_scopes(tr):
        assert any(n.startswith("jit(step)/update/%s/" % layer)
                   for n in names), layer
    # no convolution or dot is left under an empty scope
    assert matmuls and all(devtrace.scope_of(n)[1] not in
                           (devtrace.UNNAMED, devtrace.NO_TF_OP)
                           for n in matmuls), matmuls
    if extra.startswith("remat"):
        assert any(n.startswith("jit(step)/transpose(jvp(fc1))/jvp(fc1)/"
                                "checkpoint/rematted_computation/")
                   for n in names)


def test_a_pipelined_steps_layers_are_found_behind_shard_map():
    """``forward_pipelined``'s stage bodies carry the layers' names, but
    behind ``jvp()/shard_map/while/...``: the compiled step's convolution
    and dot op_names (what a device operation's ``tf_op`` reads) still
    reduce to their layers, on both passes."""
    from tests.test_accumulation import CONF as PIPE_CONF
    tr = _trainer(PIPE_CONF, "dev = cpu:0-1\npipeline_parallel = 2\n"
                  "pipeline_micro = 2\nbatch_size = 16\n")
    txt = tr.lower_update(_batch(16, (3, 6, 6))).compile().as_text()
    found = {devtrace.scope_of(n) for n in re.findall(
        r'op_name="([^"]*/(?:conv_general_dilated|dot_general))"', txt)}
    assert found == {("forward", "c1"), ("forward", "head"),
                     ("backward", "c1"), ("backward", "head")}
    assert any(n.startswith("jit(step)/update/packed/")
               for n in re.findall(r'op_name="([^"]*)"', txt))


def test_attention_and_moe_rows_split_by_their_sub_scopes():
    """The compiled step of a small SmallThinker block names, on both
    passes, the seven sub-scopes ``trace_layers.py`` splits those layers'
    rows by (the moe layers run under remat, as in the benchmark's cell)."""
    from cxxnet_tpu import models
    from cxxnet_tpu.utils.config import parse_config_string
    conf = models.smallthinker_conf(
        seq=16, batch_size=2, vocab=40, dim=32, nhead=4, nkvhead=2,
        head_dim=8, nlayer=1, n_expert=4, top_k=2, expert_width=16,
        window=8, dev="cpu")
    tr = Trainer()
    for k, v in parse_config_string(conf):
        tr.set_param(k, v)
    tr.init_model()
    b = DataBatch()
    b.data = np.zeros((2, 1, 1, 16), np.float32)
    b.label = np.zeros((2, 16), np.float32)
    b.batch_size = 2
    txt = tr.lower_update(b).as_text(debug_info=True)
    found = {devtrace.scope_of(n + ":") for n in
             re.findall(r'loc\("(jit\(step\)/[^"]*)"', txt)}
    for phase in ("forward", "backward"):
        for row in ("b0_att/qkv", "b0_att/core", "b0_att/out",
                    "b0_moe/route", "b0_moe/dispatch", "b0_moe/experts",
                    "b0_moe/combine", "b0_rn1", "head"):
            if (phase, row) == ("backward", "b0_moe/route"):
                continue      # top-k and the sort carry no gradient
            assert (phase, row) in found, (phase, row)


def test_what_is_no_layer_has_a_scope_of_its_own():
    tr = _trainer(SMALL_CONF, "compute_dtype = bfloat16\nchannels_last = 1\n"
                  "input_divideby = 255\nhealth_monitor = 1\n"
                  "nonfinite_action = skip\nclip_global_norm = 1.0\n"
                  "update_period = 2\n")
    b = _batch()
    step = tr._get_step(True, True, True, False, True)
    acc = jax.tree.map(np.zeros_like, tr.params)
    txt = step.lower(tr.params, tr.opt_state, acc, None,
                     tr._shard_batch(b.data), tr._shard_batch(b.label),
                     np.int32(0), jax.random.PRNGKey(0)
                     ).as_text(debug_info=True)
    names = set(re.findall(r'loc\("(jit\(step\)/[^"]*)"', txt))
    for head in ("jvp(input)", "jvp(relayout)", "jvp(cast_params)",
                 "transpose(jvp(cast_params))", "health", "accum", "clip",
                 "guard", "update/c1"):
        assert any(n.startswith("jit(step)/%s/" % head) for n in names), head


def test_layer_scope_is_the_conf_name_made_safe_else_type_and_index():
    tr = _trainer(SMALL_CONF.replace("conv:c1", "conv:a/b(1)"))
    assert [tr.net.layer_scope(i) for i in range(6)] == [
        "a_b_1_", "relu_1", "p1", "flatten_3", "fc1", "softmax_5"]
    assert tr.net.group_scope([0, 4]) == "a_b_1_+fc1"


# ------------------------------------- (c): spans on the profiler's clock
def _host_spans(profile_dir):
    (path,) = glob.glob(os.path.join(profile_dir, "plugins", "profile", "*",
                                     "*.xplane.pb"))
    return [s for s in devtrace.read_xplane(path)[1]
            if s[0].startswith("train.")]


def test_train_spans_land_on_the_host_plane_with_telemetry_disabled(tmp_path):
    tr = _trainer(SMALL_CONF)
    b = _batch()
    tr.update(b)                              # the build stays outside
    telemetry.disable()
    telemetry.reset()
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    try:
        for _ in range(3):
            tr.update(b)
    finally:
        jax.profiler.stop_trace()
    assert telemetry.events() == []           # nothing went to the registry
    spans = sorted(_host_spans(str(tmp_path)), key=lambda s: s[1])
    assert [s[0] for s in spans] == [
        "train.update", "train.h2d", "train.step", "train.args",
        "train.dispatch"] * 3
    for upd, h2d, step, args, disp in zip(*(spans[i::5] for i in range(5))):
        # one thread's line
        assert upd[3] == h2d[3] == step[3] == args[3] == disp[3]
        assert upd[1] <= h2d[1] and h2d[1] + h2d[2] <= step[1]
        assert step[1] <= args[1] and args[1] + args[2] <= disp[1]
        assert disp[1] + disp[2] <= step[1] + step[2] <= upd[1] + upd[2]
    # the four kept ones also stand in the always-on account, the three
    # calls last: the same spans on the host's clock, nothing enabled
    kept = telemetry.kept()
    assert all(len(kept[n]) >= 3 for n in (
        "train.update", "train.h2d", "train.args", "train.dispatch"))
    assert "train.step" not in kept


def test_an_enabled_span_is_recorded_and_annotated(tmp_path):
    telemetry.enable()
    try:
        jax.profiler.start_trace(str(tmp_path))
        try:
            with telemetry.span("train.probe", k=1):
                pass
        finally:
            jax.profiler.stop_trace()
        (ev,) = [e for e in telemetry.events() if e.get("ev") == "span"]
        assert ev["name"] == "train.probe" and ev["k"] == 1
    finally:
        telemetry.disable()
        telemetry.reset()
    assert [s[0] for s in _host_spans(str(tmp_path))] == ["train.probe"]


def test_warm_update_asks_the_profiler_only_whether_it_records(monkeypatch):
    tr = _trainer(SMALL_CONF)
    b = _batch()
    tr.update(b)
    telemetry.disable()
    calls = {"is_enabled": 0, "made": 0}

    class Annotation:
        def __init__(self, *a, **kw):
            calls["made"] += 1

        @staticmethod
        def is_enabled():
            calls["is_enabled"] += 1
            return False
    monkeypatch.setattr(telemetry, "_TRACE_ANNOTATION", Annotation)
    assert telemetry.span("train.h2d") is telemetry.span("train.step")
    calls.update(is_enabled=0)
    tr.update(b)
    # train.update > train.h2d, train.step > train.args, train.dispatch
    assert calls == {"is_enabled": 5, "made": 0}


def test_telemetry_imports_and_spans_without_jax():
    code = ("import sys, importlib.util as u\n"
            "s = u.spec_from_file_location('cxxnet_tpu.utils.telemetry', %r)\n"
            "import types\n"
            "for p in ('cxxnet_tpu', 'cxxnet_tpu.utils'):\n"
            "    sys.modules[p] = types.ModuleType(p); "
            "sys.modules[p].__path__ = [%r]\n"
            "m = u.module_from_spec(s); sys.modules[s.name] = m\n"
            "s.loader.exec_module(m)\n"
            "a, b = m.span('x'), m.span('y')\n"
            "assert a is b\n"
            "with m.phase('init.x'): pass\n"
            "assert 'init.x' in m.phases() and 'jax' not in sys.modules\n"
            % (os.path.join(ROOT, "cxxnet_tpu", "utils", "telemetry.py"),
               os.path.join(ROOT, "cxxnet_tpu", "utils")))
    subprocess.run([sys.executable, "-c", code], check=True, timeout=60)


# ------------------------------------------------ (f): the phase account
@pytest.fixture
def fresh_account(monkeypatch):
    """The account is the process's and outlives reset(): a test that reads
    it starts from an empty one and hands the process's back."""
    monkeypatch.setattr(telemetry._REG, "phase_s", {})


def test_phase_account_holds_init_and_the_first_update_only(fresh_account):
    telemetry.disable()
    telemetry.reset()
    tr = _trainer(SMALL_CONF)
    got = telemetry.phases()
    parts = ("init.structure", "init.params", "init.opt", "init.pack")
    assert set(got) == set(parts) | {"init.model"}
    assert sum(got[p] for p in parts) <= got["init.model"] \
        <= sum(got[p] for p in parts) + 0.05
    b = _batch()
    tr.update(b)
    first = telemetry.phases()
    build = "jit.build/jit.train_step"
    assert first[build] > 0
    # jax's own split of the first call stands beside it
    assert {build + "/trace", build + "/lower", build + "/compile"} \
        <= set(first)
    assert first[build + "/trace"] + first[build + "/lower"] \
        + first[build + "/compile"] <= first[build]
    tr.update(b)
    assert telemetry.phases() == first        # a warm call adds nothing
    assert telemetry.events() == []
    # the first occurrence stands: a second model and its step's build in
    # the same process (the benchmark's reference after the window) are
    # not added in, and reset() / enable() do not lose what cannot recur
    _trainer(SMALL_CONF).update(b)
    telemetry.reset()
    telemetry.enable()
    telemetry.disable()
    telemetry.reset()
    assert telemetry.phases() == first


def test_phase_account_keeps_the_first_occurrence_of_a_name():
    reg = telemetry._Registry()
    for secs in (0.02, 0.0):
        with reg.phase("init.x"):
            time.sleep(secs)
    assert 0.02 <= reg.phases()["init.x"] < 1.0
    reg.reset()
    assert list(reg.phases()) == ["init.x"]


def test_build_parts_have_one_listener_a_process(monkeypatch):
    made = []
    monkeypatch.setattr(jax.monitoring,
                        "register_event_duration_secs_listener", made.append)
    monkeypatch.setattr(telemetry, "_BUILD_LISTENING", False)
    for _ in range(3):                        # three registries, each a build
        reg = telemetry._Registry()
        with reg.phase("jit.build/p", parts=True):
            telemetry._on_build_duration(
                "/jax/core/compile/jaxpr_trace_duration", 0.25)
            telemetry._on_build_duration("/jax/some/other_duration", 9.0)
        assert reg.phases()["jit.build/p/trace"] == 0.25
        assert set(reg.phases()) == {"jit.build/p", "jit.build/p/trace"}
    assert made == [telemetry._on_build_duration]
    # with no build open on the thread an event is dropped
    telemetry._on_build_duration("/jax/core/compile/jaxpr_trace_duration", 1.0)


def test_the_account_and_its_build_parts_reach_summary_metrics_and_report(
        fresh_account, tmp_path, capsys):
    """What reads the ``jit.build/<program>/{trace,lower,compile,
    cache_load}`` entries: ``summary()`` (so the JSONL's summary event and
    tools/telemetry_report.py), ``/metrics`` and ``/statusz``."""
    from cxxnet_tpu.utils import statusd
    sys.path.insert(0, os.path.join(ROOT, "tools"))
    import telemetry_report
    log = str(tmp_path / "t.jsonl")
    telemetry.enable(log)
    try:
        tr = _trainer(SMALL_CONF)
        tr.update(_batch())
        got = telemetry.phases()
        build = "jit.build/jit.train_step"
        want = {"init.model", build, build + "/trace", build + "/lower"}
        assert want <= set(got)
        assert want <= set(telemetry.summary()["phases"])
        text = statusd.prometheus_metrics(telemetry._REG.metrics_snapshot())
        for name in want:
            assert 'cxxnet_phase_seconds{process="0",phase="%s"} ' % name \
                in text
        telemetry.finish(close=True)
    finally:
        telemetry.disable()
        telemetry.reset()
    agg = telemetry_report.aggregate(telemetry_report.load_events(log))
    assert agg["setup_phases"][build + "/trace"] == pytest.approx(
        got[build + "/trace"], abs=1e-5)
    telemetry_report.print_report(agg)
    out = capsys.readouterr().out
    assert "== set-up phases" in out and build + "/lower" in out


# --------------------------------------------- (d): devtrace, plain tuples
def _op(name, start, dur, tf_op="", flops=0.0, nbytes=0.0):
    return (name, float(start), float(dur), tf_op, flops, nbytes)


@pytest.mark.parametrize("tf_op, want", [
    ("jit(step)/jvp(conv1)/conv_general_dilated:", ("forward", "conv1")),
    ("jit(step)/transpose(jvp(conv1))/conv_general_dilated:",
     ("backward", "conv1")),
    ("jit(step)/transpose(jvp(c1))/jvp(c1)/checkpoint/rematted_computation/"
     "tanh:", ("backward", "c1")),
    ("jit(step)/jvp(a_1x1+a_3x3r)/conv_general_dilated",
     ("forward", "a_1x1+a_3x3r")),
    # a pipelined step: the stage bodies stand behind shard_map's control
    # flow; what the schedule itself runs is shard_map's
    ("jit(step)/jvp()/shard_map/while/body/closed_call/cond/branch_0_fun/c1/"
     "conv_general_dilated:", ("forward", "c1")),
    ("jit(step)/transpose(jvp())/shard_map/while/body/closed_call/cond/"
     "branch_1_fun/checkpoint/head/dot_general:", ("backward", "head")),
    ("jit(step)/transpose(jvp())/shard_map/while/body/closed_call/cond/"
     "branch_0_fun/checkpoint/rematted_computation/slice:",
     ("backward", "shard_map")),
    ("jit(step)/jvp()/shard_map/while/body/closed_call/ppermute:",
     ("forward", "shard_map")),
    ("jit(step)/jvp()/shard_map/while/body/closed_call/jit(clip)/max:",
     ("forward", "shard_map")),
    ("jit(step)/jvp()/shard_map:", ("forward", "shard_map")),
    ("jit(step)/update/conv1/mul:", ("update", "conv1")),
    # a layer's own sub-scopes split its rows (attention, moe)
    ("jit(step)/jvp(b0_att)/~core/pallas_call:", ("forward", "b0_att/core")),
    ("jit(step)/transpose(jvp(b0_att))/~qkv/dot_general:",
     ("backward", "b0_att/qkv")),
    ("jit(step)/transpose(jvp(b1_moe))/jvp(b1_moe)/checkpoint/"
     "rematted_computation/~experts/pallas_call:",
     ("backward", "b1_moe/experts")),
    ("jit(step)/jvp(b1_moe)/checkpoint/~route/sort:",
     ("forward", "b1_moe/route")),
    # inside the branches of a bounded moe layer's cond, and in its
    # backward, which runs a vjp of its own (PR 33)
    ("jit(step)/jvp(b2_moe)/cond/branch_1_fun/~dispatch/gather:",
     ("forward", "b2_moe/dispatch")),
    ("jit(step)/transpose(jvp(b2_moe))/jvp(b2_moe)/checkpoint/cond/"
     "branch_1_fun/transpose(jvp(~experts))/pallas_call:",
     ("backward", "b2_moe/experts")),
    ("jit(step)/transpose(jvp(b2_moe))/jvp(b2_moe)/checkpoint/cond/"
     "branch_0_fun/jvp(~combine)/gather:", ("backward", "b2_moe/combine")),
    # only the mark makes a sub-scope: a layer named as attention's is
    # stays a layer, behind a pipeline's control flow too
    ("jit(step)/jvp(out)/dot_general:", ("forward", "out")),
    ("jit(step)/jvp()/shard_map/while/body/out/dot_general:",
     ("forward", "out")),
    ("jit(step)/update/packed/select_n:", ("update", "packed")),
    ("jit(step)/health/reduce_sum:", ("health", "health")),
    ("jit(step)/clip/mul:", ("other", "clip")),
    ("jit(step)/jvp()/add:", ("forward", devtrace.UNNAMED)),
    ("jit(step)/transpose(jvp())/convert_element_type:",
     ("backward", devtrace.UNNAMED)),
    ("jit(step)/reduce_sum:", ("other", devtrace.UNNAMED)),
    ("jit(fwd)/conv1/conv_general_dilated:", ("other", "conv1")),
    ("", ("other", devtrace.NO_TF_OP)),
])
def test_scope_of_reads_phase_and_layer_from_tf_op(tf_op, want):
    assert devtrace.scope_of(tf_op) == want


def _toy_trace():
    conv = "%fusion.7 = bf16[8] fusion(bf16[8] %p), kind=kOutput, calls=%c"

    def loop(n, reads="%p"):
        return ("%%fusion.%d = f32[8] fusion(f32[8] %s), kind=kLoop, "
                "calls=%%c" % (n, reads))
    ops = [
        _op(conv, 100, 40, "jit(step)/jvp(conv1)/conv_general_dilated:",
            1000.0, 64.0),
        # nested in the convolution: its time is not the convolution's own;
        # the compiler's, with no tf_op, and nothing named reads it
        _op("%copy.1 = f32[8] copy(f32[8] %x)", 110, 10),
        # the compiler's too (a packed mask), read by conv1's backward
        _op("%fusion.30 = u16[2] fusion(bf16[8] %fusion.7), kind=kLoop, "
            "calls=%c", 140, 5),
        _op(loop(9, "%g, u16[2] %fusion.30"), 150, 20,
            "jit(step)/transpose(jvp(conv1))/mul:"),
        _op(loop(10), 180, 10, "jit(step)/update/conv1/sub:"),
        _op("%reduce.2 = f32[] reduce(f32[8] %g)", 190, 5,
            "jit(step)/health/reduce_sum:"),
        # the second step, after an idle gap of 105 ns
        _op(conv, 300, 40, "jit(step)/jvp(conv1)/conv_general_dilated:",
            1000.0, 64.0),
        _op(loop(11), 350, 30, "jit(step)/jvp()/add:"),
        _op(loop(12), 2000, 5, "jit(step)/jvp(late)/add:"),  # past the end
    ]
    devices = {"/device:TPU:0": {
        "modules": [("jit_step(1)", 100, 95), ("jit_step(1)", 300, 100),
                    ("jit_other(2)", 500, 10)],
        "ops": ops}}
    spans = [("train.update", 90, 400, "t0"), ("train.h2d", 95, 20, "t0"),
             ("train.step", 200, 150, "t0"), ("bench.sync", 0, 9000, "t0"),
             ("io.decode", 240, 20, "t1")]
    return devices, spans


def test_reduce_ops_phases_rows_spans_and_gaps():
    devices, spans = _toy_trace()
    r = devtrace.reduce_ops(devices, spans, "jit_step")
    assert r["steps"] == 2 and r["window_s"] == pytest.approx(300e-9)
    assert r["busy_s"] == pytest.approx(150e-9)
    # the phases are shares of busy time and sum to the whole of it
    assert sum(r["phase_share"].values()) == pytest.approx(100.0)
    assert r["phase_s"] == pytest.approx({
        "forward": 100e-9, "backward": 25e-9, "update": 10e-9,
        "health": 5e-9, "other": 10e-9})
    rows = {(x["layer"], x["phase"]): x for x in r["layers"]}
    fwd = rows[("conv1", "forward")]
    assert r["layers"][0] is fwd and fwd["self_s"] == pytest.approx(70e-9)
    assert fwd["ops"] == [["fusion.7", pytest.approx(70e-9)]]
    assert fwd["flops"] == 2000.0 and fwd["bytes_accessed"] == 128.0
    # the mask has no tf_op: it goes where its reader stands
    bwd = rows[("conv1", "backward")]
    assert bwd["ops"] == [["fusion.9", pytest.approx(20e-9)],
                          ["fusion.30", pytest.approx(5e-9)]]
    assert "flops" not in bwd
    assert r["via_reader_share"] == pytest.approx(100.0 * 5 / 150)
    # an operation with no tf_op and no reader, and one under an empty scope
    assert rows[(devtrace.NO_TF_OP, "other")]["self_s"] == \
        pytest.approx(10e-9)
    assert rows[(devtrace.UNNAMED, "forward")]["self_s"] == \
        pytest.approx(30e-9)
    # named by a tf_op of its own: the mask's 5 ns stand beside it
    assert r["named_share"] == pytest.approx(100.0 * 105 / 150)
    assert r["warning"] is None               # no matmul is unnamed
    # host spans: the program's only, self time = less what they enclose
    by = {s["name"]: s for s in r["host_spans"]}
    assert set(by) == {"train.update", "train.h2d", "train.step",
                       "io.decode"}
    assert by["train.update"]["self_s"] == pytest.approx(230e-9)
    assert by["io.decode"]["self_s"] == pytest.approx(20e-9)   # own line
    # the gap between the steps lies in train.step and io.decode; the
    # innermost (shortest) span over its middle names it
    assert r["idle_gaps"][0] == ["io.decode", pytest.approx(105e-9)]
    assert r["idle_gaps"][1] == ["train.update", pytest.approx(20e-9)]
    assert r["idle_gap_s_by_span"]["io.decode"] == pytest.approx(105e-9)


def test_an_idle_gap_is_named_by_the_step_spans_parts():
    """``Trainer.update`` opens ``train.args`` and ``train.dispatch`` inside
    ``train.step``: a gap the device spends waiting on the jitted call reads
    ``train.dispatch``, no longer ``train.step``, with no change here."""
    devices, spans = _toy_trace()
    spans = [s for s in spans if s[0] != "io.decode"] + [
        ("train.args", 200, 15, "t0"), ("train.dispatch", 215, 135, "t0")]
    r = devtrace.reduce_ops(devices, spans, "jit_step")
    by = {s["name"]: s for s in r["host_spans"]}
    assert set(by) == {"train.update", "train.h2d", "train.step",
                       "train.args", "train.dispatch"}
    assert by["train.step"]["self_s"] == pytest.approx(0.0)
    assert by["train.dispatch"]["self_s"] == pytest.approx(135e-9)
    assert by["train.update"]["self_s"] == pytest.approx(230e-9)
    assert r["idle_gaps"][0] == ["train.dispatch", pytest.approx(105e-9)]
    assert "train.step" not in r["idle_gap_s_by_span"]
    assert "train.dispatch" in devtrace.format_report(r)


def test_reduce_ops_agrees_with_the_benchmarks_reduction_on_busy_time():
    sys.path.insert(0, ROOT)
    from benchmark import trace_reduce
    devices, spans = _toy_trace()
    plain = {d: {"modules": v["modules"],
                 "ops": [o[:3] for o in v["ops"]]}
             for d, v in devices.items()}
    theirs = trace_reduce.reduce_events(plain, [s[:3] for s in spans],
                                        "jit_step")
    ours = devtrace.reduce_ops(devices, spans, "jit_step")
    assert ours["busy_s"] == theirs["busy_s"]
    assert ours["window_s"] == theirs["window_s"]
    assert sum(ours["phase_s"].values()) == pytest.approx(
        sum(theirs["class_s"].values()))


def test_unnamed_matmuls_raise_the_stale_cache_warning_and_no_stretch():
    devices, spans = _toy_trace()
    ops = devices["/device:TPU:0"]["ops"]
    ops[0] = ops[0][:3] + ("jit(step)/jvp()/conv_general_dilated:", 0, 0)
    r = devtrace.reduce_ops(devices, spans, "jit_step")
    assert r["warning"] == devtrace.STALE_WARNING
    assert "compiled before the scopes existed" in devtrace.format_report(r)
    assert devtrace.reduce_ops(devices, spans, "jit_missing") is None


# ------------------------------------- (e): a trace recorded on the chip
def test_recorded_trace_reduces_to_what_is_written_beside_it():
    path = os.path.join(FIXTURES, "layers.xplane.pb")
    with open(os.path.join(FIXTURES, "layers.expected.json")) as f:
        want = json.load(f)
    got = json.loads(json.dumps(devtrace.reduce_trace(path, "jit_step")))
    assert _close(got, want)
    # recorded after the scopes: nothing stale, nearly all of it named
    assert got["warning"] is None and got["stale_share"] == 0.0
    assert sum(got["phase_share"].values()) == pytest.approx(100.0)
    # by tf_op alone; the compiler's masks and copies reach their layers
    # through their readers and are counted beside it
    assert got["named_share"] > 70.0
    assert got["named_share"] + got["via_reader_share"] > 77.0
    assert {s["name"] for s in got["host_spans"]} >= {
        "train.update", "train.h2d", "train.step"}
    layers = {row["layer"] for row in got["layers"]}
    assert {"conv1", "conv2", "fc6", "fc8"} <= layers
    assert any(row["phase"] == "update" for row in got["layers"])


def _close(a, b):
    if isinstance(a, dict):
        return a.keys() == b.keys() and all(_close(a[k], b[k]) for k in a)
    if isinstance(a, list):
        return len(a) == len(b) and all(_close(x, y) for x, y in zip(a, b))
    if isinstance(a, float) or isinstance(b, float):
        return a == pytest.approx(b, rel=1e-9, abs=1e-15)
    return a == b


def test_recorded_trace_busy_time_agrees_with_the_benchmarks_reader():
    sys.path.insert(0, ROOT)
    from benchmark import trace_reduce
    path = os.path.join(FIXTURES, "layers.xplane.pb")
    theirs = trace_reduce.reduce_trace(path, "jit_step")
    ours = devtrace.reduce_trace(path, "jit_step")
    # ProfileData hands out whole nanoseconds, the file holds picoseconds:
    # over some thousand operations of a tiny net that is 0.02%
    assert ours["busy_s"] == pytest.approx(theirs["busy_s"], rel=2e-3)
    assert ours["window_s"] == pytest.approx(theirs["window_s"], rel=1e-6)
    assert ours["steps"] == theirs["steps"]


def test_trace_layers_tool_prints_the_report_and_json():
    path = os.path.join(FIXTURES, "layers.xplane.pb")
    tool = os.path.join(ROOT, "tools", "trace_layers.py")
    out = subprocess.run([sys.executable, tool, path], check=True,
                         capture_output=True, text=True, timeout=120).stdout
    assert "device self time by phase" in out and "train.step" in out
    js = subprocess.run([sys.executable, tool, path, "--json"], check=True,
                        capture_output=True, text=True, timeout=120).stdout
    assert json.loads(js)["module"] == "jit_step"
    bad = subprocess.run([sys.executable, tool, path, "--module", "jit_x"],
                         capture_output=True, text=True, timeout=120)
    assert bad.returncode == 1 and "no device ran module" in bad.stderr


def test_old_trace_without_scopes_gets_the_warning():
    r = devtrace.reduce_trace(os.path.join(
        ROOT, "benchmark", "fixtures", "tiny.xplane.pb"), "jit_step")
    assert r["warning"] == devtrace.STALE_WARNING and r["stale_share"] > 10
