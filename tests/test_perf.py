"""The live program performance ledger (utils/perf.py): DeviceSpec
resolution, ProgramCard math from faked XLA analyses, MFU/headroom
joins, /programz + /metrics rendering, the /profilez capture guard,
and the report's program-ledger section — all jax-free except ONE cheap real-jit CPU test pinning
that a compiled train step actually produces a card."""

import json
import os
import sys
import time

import pytest

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "tools"))
sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

from cxxnet_tpu.utils import perf, statusd, telemetry  # noqa: E402


class FakeArr:
    def __init__(self, shape, dtype="float32"):
        self.shape, self.dtype = shape, dtype


def make_ledger(spec=None):
    reg = telemetry._Registry()
    reg.enable()
    lg = perf.Ledger(registry=reg,
                     spec=spec or perf.DeviceSpec(
                         "test", 100e12, 500e9, 8 * 2.0**30)).enable()
    return lg, reg


# ----------------------------------------------------------------------
# DeviceSpec
# ----------------------------------------------------------------------

def test_device_spec_is_keyed_by_device_kind_and_unknown_raises():
    """The table is keyed by the device_kind string the chip reports,
    every entry names the source of its figures, and a kind that is not
    in it is an error — never a silent v5e."""
    s = perf.device_spec("TPU v5 lite")
    assert s.peak_flops == 197.0e12 and s.hbm_bw == 819.0e9
    assert s.hbm_capacity == 16 * 2.0**30
    assert all(sp.source and sp.name == kind
               for kind, sp in perf.DEVICE_SPECS.items())
    assert perf.device_spec() is perf.DEVICE_SPECS[perf.TARGET_DEVICE_KIND]
    for kind in ("v5e", "cpu", "TPU v99"):
        with pytest.raises(KeyError, match="no peak figures"):
            perf.device_spec(kind)


def test_cpu_backend_has_no_spec_and_no_mfu():
    """On a CPU backend the ledger resolves NO spec: cards carry their
    XLA counts but no predicted time, MFU, roofline share, HBM capacity
    or headroom — a CPU number never appears under a device metric."""
    assert perf.current_device_spec() is None
    reg = telemetry._Registry()
    reg.enable()
    lg = perf.Ledger(registry=reg).enable()
    lg.on_compile("jit.train_step", "first", 0.5, args=(FakeArr((4, 8)),))
    sig = lg.cards()[0]["sig"]
    card = lg.complete_card(
        "jit.train_step", sig, cost={"flops": 1e9, "bytes accessed": 1e6},
        mem={"argument_size_in_bytes": 10, "temp_size_in_bytes": 20,
             "output_size_in_bytes": 30})
    assert card["flops"] == 1e9 and card["peak_bytes"] == 60
    assert card["predicted_s"] is None
    for _ in range(3):
        reg.hist("train.period", 0.01)
    snap = lg.snapshot()
    assert snap["spec"] is None
    c = snap["cards"][0]
    assert c["measured_p50_ms"] is not None
    assert c["mfu_pct"] is None and c["roofline_eff_pct"] is None
    assert snap["hbm"]["capacity_bytes"] is None
    assert snap["hbm"]["headroom_bytes"] is None
    assert snap["hbm"]["peak_bytes"] == 60
    assert lg.decode_pool_cap_bytes(0.5) is None
    assert "device spec: none" in statusd.programz_html(snap)
    lg.disable()


# ----------------------------------------------------------------------
# shapes signature + card math
# ----------------------------------------------------------------------

def test_shapes_signature_stable_and_truncated():
    disp, h = perf.shapes_signature((FakeArr((8, 128)),
                                     {"w": FakeArr((128, 64), "bfloat16")}))
    assert "f32[8,128]" in disp and "bf16[128,64]" in disp
    disp2, h2 = perf.shapes_signature((FakeArr((8, 128)),
                                       {"w": FakeArr((128, 64),
                                                     "bfloat16")}))
    assert h == h2
    _, h3 = perf.shapes_signature((FakeArr((9, 128)),))
    assert h3 != h
    # None leaves vanish; a big arg list truncates but keeps the hash
    disp4, h4 = perf.shapes_signature(([FakeArr((4, 4))] * 40, None))
    assert h4 in disp4 and len(disp4) < 80


def test_card_math_flops_vs_bandwidth_bound():
    lg, reg = make_ledger()   # 100 TFLOP/s, 500 GB/s
    try:
        # flops-bound: 2e12/100e12=20ms  >  1e9/500e9=2ms
        c = lg.complete_card("jit.train_step", "sig1",
                             cost={"flops": 2.0e12,
                                   "bytes accessed": 1.0e9},
                             mem={"argument_size_in_bytes": 100,
                                  "temp_size_in_bytes": 20,
                                  "output_size_in_bytes": 3})
        assert abs(c["predicted_s"] - 0.02) < 1e-12
        assert c["peak_bytes"] == 123
        # bandwidth-bound: 1e9/100e12=0.01ms < 5e9/500e9=10ms
        c2 = lg.complete_card("jit.decode_step", "sig2",
                              cost={"flops": 1.0e9,
                                    "bytes accessed": 5.0e9})
        assert abs(c2["predicted_s"] - 0.01) < 1e-12
        assert c2["peak_bytes"] is None      # no memory tier yet
        # error completion: card visible, analytic fields null
        bad = lg.complete_card("jit.predict", "sig3", error="kaboom")
        assert bad["status"] == "error" and bad["flops"] is None
        # every completion left a program_card event with the spec peaks
        evs = [e for e in reg.events() if e.get("ev") == "program_card"]
        assert len(evs) == 3
        assert evs[0]["spec_peak_flops"] == 100e12
    finally:
        lg.disable()
        reg.disable()


# six entries follow each other a dispatch apart, then four a step: what
# the runtime's limit of steps in flight makes of back-to-back calls
BIMODAL_PERIODS = ([0.004] * 6 + [0.100] * 4) * 3


def test_train_step_card_divides_by_the_mean_period_not_its_p50():
    lg, reg = make_ledger()
    try:
        lg.complete_card("jit.train_step", "s",
                         cost={"flops": 2.12e12, "bytes accessed": 1.0},
                         mem={})
        for d in BIMODAL_PERIODS:
            reg.hist("train.period", d)
        c = lg.snapshot()["cards"][0]
        assert c["measured_ms"] == pytest.approx(42.4)
        assert c["measured_p50_ms"] < 6.0          # the dispatch's mode
        # 2.12e12 / (0.0424 s x 100e12) = 50%; by the p50 it read > 350%
        assert c["mfu_pct"] == pytest.approx(50.0)
        assert c["roofline_eff_pct"] == pytest.approx(
            100 * c["predicted_s"] / 0.0424, rel=1e-3)
    finally:
        lg.disable()
        reg.disable()


@pytest.mark.parametrize("program,series", sorted(
    (p, s) for p, s in perf.MEASURED_SERIES.items()
    if s not in perf.MEAN_SERIES))
def test_every_other_card_still_divides_by_its_series_p50(program, series):
    lg, reg = make_ledger()
    try:
        lg.complete_card(program, "s",
                         cost={"flops": 1.0e12, "bytes accessed": 1.0},
                         mem={})
        for d in BIMODAL_PERIODS:
            reg.hist(series, d)
        c = lg.snapshot()["cards"][0]
        assert c["measured_series"] == series
        assert c["measured_ms"] == c["measured_p50_ms"] < 6.0
    finally:
        lg.disable()
        reg.disable()


def test_mfu_and_headroom_join_measured_hist():
    lg, reg = make_ledger()
    try:
        lg.complete_card("jit.train_step", "s",
                         cost={"flops": 1.0e12, "bytes accessed": 1.0},
                         mem={"argument_size_in_bytes": 2 * 2**30,
                              "temp_size_in_bytes": 2**30,
                              "output_size_in_bytes": 0})
        # no measurements yet: joins stay null, never fake zeros
        c = lg.snapshot()["cards"][0]
        assert c["mfu_pct"] is None and c["measured_p50_ms"] is None
        # the dispatch's own series is not the step's: nothing joins it
        for _ in range(8):
            reg.hist("train.step", 0.001)
            reg.hist("train.dispatch", 0.001)
        c = lg.snapshot()["cards"][0]
        assert c["mfu_pct"] is None and c["measured_ms"] is None
        # mean period 20ms -> mfu = 1e12/(0.02*100e12) = 50%
        for _ in range(8):
            reg.hist("train.period", 0.020)
        snap = lg.snapshot()
        c = snap["cards"][0]
        assert c["measured_series"] == "train.period"
        assert c["measured_n"] == 8
        assert c["measured_ms"] == pytest.approx(20.0)
        assert c["mfu_pct"] == pytest.approx(50.0)
        # predicted 10ms vs measured 20ms -> eff 50%
        assert c["roofline_eff_pct"] == pytest.approx(50.0)
        hbm = snap["hbm"]
        assert hbm["peak_bytes"] == 3 * 2**30
        assert hbm["headroom_bytes"] == 8 * 2.0**30 - 3 * 2**30
    finally:
        lg.disable()
        reg.disable()


def test_on_compile_accumulates_and_keys_cards():
    lg, reg = make_ledger()
    try:
        args = (FakeArr((2, 3)),)
        lg.on_compile("jit.train_step", "new_signature", 1.0, fn=None,
                      args=args, key=("train", True))
        lg.on_compile("jit.train_step", "rebuild_after_clear", 0.5,
                      fn=None, args=args, key=("train", True))
        cards = lg.cards()
        assert len(cards) == 1
        assert cards[0]["compiles"] == 2
        assert abs(cards[0]["compile_s"] - 1.5) < 1e-9
        assert cards[0]["key"] == str(("train", True))
        # a different signature gets its own card
        lg.on_compile("jit.train_step", "shape_change", 0.2, fn=None,
                      args=(FakeArr((4, 3)),))
        assert len(lg.cards()) == 2
    finally:
        lg.disable()
        reg.disable()


def test_jitwatch_calls_compile_hook_with_key():
    reg = telemetry._Registry()
    reg.enable()
    calls = []

    class FakeJit:
        def __init__(self):
            self.n = 0

        def _cache_size(self):
            return self.n

        def __call__(self, x):
            self.n = 1          # first call "compiles"
            return x

    reg.compile_hook = lambda *a, **kw: calls.append((a, kw))
    try:
        w = telemetry.JitWatch(FakeJit(), "jit.test", registry=reg,
                               key=("k", 1))
        w(41)
        w(42)                   # cache stable: no second hook call
        assert len(calls) == 1
        a, kw = calls[0]
        assert a[0] == "jit.test" and a[1] == "new_signature"
        assert kw["key"] == ("k", 1) and kw["args"] == (41,)
        # the compile event carries the key too
        ev = [e for e in reg.events() if e.get("ev") == "compile"]
        assert ev and ev[0]["key"] == str(("k", 1))
    finally:
        reg.compile_hook = None
        reg.disable()


def test_jitwatch_hook_fires_even_with_telemetry_disabled():
    """The ledger must card programs in runs that configured no JSONL
    log (bench rows, embedders) — the hook alone defeats the fast
    path."""
    reg = telemetry._Registry()     # never enabled
    calls = []

    class FakeJit:
        def __init__(self):
            self.n = 0

        def _cache_size(self):
            return self.n

        def __call__(self, x):
            self.n = 1
            return x

    reg.compile_hook = lambda *a, **kw: calls.append(1)
    w = telemetry.JitWatch(FakeJit(), "jit.test", registry=reg)
    w(1)
    assert calls == [1]


# ----------------------------------------------------------------------
# statusd surfaces
# ----------------------------------------------------------------------

def _scrape(url):
    from urllib.request import urlopen
    return urlopen(url, timeout=5)


def test_programz_and_metrics_render_the_ledger():
    from urllib.error import HTTPError
    lg, reg = make_ledger()
    srv = statusd.StatusServer(0, host="127.0.0.1", registry=reg).start()
    try:
        base = "http://127.0.0.1:%d" % srv.port
        # no ledger registered yet -> 404 with a hint
        try:
            _scrape(base + "/programz")
            raise AssertionError("programz without a ledger should 404")
        except HTTPError as e:
            assert e.code == 404
        srv.perf = lg
        lg.complete_card("jit.train_step", "sigA",
                         cost={"flops": 3.0e12, "bytes accessed": 2.0e9},
                         mem={"argument_size_in_bytes": 1 << 20,
                              "temp_size_in_bytes": 1 << 20,
                              "output_size_in_bytes": 0})
        for _ in range(4):
            reg.hist("train.period", 0.05)
        page = _scrape(base + "/programz").read().decode()
        assert "jit.train_step" in page and "MFU" in page
        assert "meas ms" in page and "50.00" in page
        assert "headroom" in page
        doc = json.loads(_scrape(base + "/programz?json=1").read())
        assert doc["cards"][0]["name"] == "jit.train_step"
        assert doc["hbm"]["peak_bytes"] == 2 << 20
        m = _scrape(base + "/metrics").read().decode()
        for line in m.splitlines():
            if line and not line.startswith("#"):
                assert statusd.PROM_LINE_RE.match(line), line
        assert 'cxxnet_program_flops{process="0",program="jit.train_step"' \
            in m
        assert "cxxnet_program_mfu_pct" in m
        assert "cxxnet_program_roofline_eff_pct" in m
        assert 'cxxnet_hbm_peak_bytes{process="0"} %d' % (2 << 20) in m
        assert "cxxnet_hbm_headroom_bytes" in m
        assert "cxxnet_program_cards" in m
        # /statusz carries the summary row
        page = _scrape(base + "/statusz").read().decode()
        assert "program ledger" in page
    finally:
        srv.stop()
        lg.disable()
        reg.disable()


def test_profilez_guard_and_404s(tmp_path):
    from urllib.error import HTTPError
    reg = telemetry._Registry()
    reg.enable()
    srv = statusd.StatusServer(0, host="127.0.0.1", registry=reg).start()
    started = []

    def fake_trace(secs, path):
        started.append(path)
        time.sleep(secs)

    try:
        base = "http://127.0.0.1:%d" % srv.port
        try:
            _scrape(base + "/profilez?secs=1")
            raise AssertionError("no profiler registered should 404")
        except HTTPError as e:
            assert e.code == 404
        prof = perf.ProfilerCapture(str(tmp_path), trace_fn=fake_trace)
        srv.profiler = prof
        r = _scrape(base + "/profilez?secs=0.4")
        assert r.status == 200
        body = r.read().decode()
        assert "capture_001" in body
        # concurrent second capture: refused, 409
        try:
            _scrape(base + "/profilez?secs=0.4")
            raise AssertionError("concurrent capture should 409")
        except HTTPError as e:
            assert e.code == 409
            assert "in progress" in e.read().decode()
        assert prof.wait(5.0)
        assert started == [os.path.join(str(tmp_path), "capture_001")]
        # guard released: next capture runs, numbered fresh
        ok, path = prof.start(0.01)
        assert ok and path.endswith("capture_002")
        assert prof.wait(5.0)
        # bad secs: 400, not a capture
        try:
            _scrape(base + "/profilez?secs=banana")
            raise AssertionError("bad secs should 400")
        except HTTPError as e:
            assert e.code == 400
        ok, detail = prof.start(-3)
        assert not ok and "secs" in detail
    finally:
        srv.stop()
        reg.disable()


def test_profilez_shutdown_cuts_capture_short(tmp_path):
    """shutdown() must stop an in-flight capture and join its thread
    (a daemon capture thread inside native profiler code at interpreter
    exit segfaults the process — the clean-drain rc 0 contract)."""
    prof = perf.ProfilerCapture(str(tmp_path))

    def fake_trace(secs, path):
        deadline = time.monotonic() + secs
        while time.monotonic() < deadline and not prof._stop.is_set():
            time.sleep(0.01)

    prof._trace_fn = fake_trace
    ok, _ = prof.start(30.0)              # would outlive any drain
    assert ok and prof.busy()
    t0 = time.monotonic()
    assert prof.shutdown(timeout=10.0)
    assert time.monotonic() - t0 < 5.0, "shutdown waited out the window"
    assert not prof.busy()
    # shutdown LATCHES: a /profilez request racing the drain must not
    # start a fresh capture thread into interpreter teardown
    ok, detail = prof.start(0.01)
    assert not ok and "shut down" in detail


def test_decode_bound_annotation_null_safe():
    """servd's flight-record annotation: null until a decode-step card
    is ready, then (ntok-1)/predicted_s."""
    assert perf.decode_bound_tokens_per_s(16) is None   # ledger off
    reg = telemetry._Registry()
    reg.enable()
    mod = perf.ledger()
    old_reg, old_spec = mod._registry, mod.spec
    mod._registry = reg
    try:
        perf.enable(spec=perf.DeviceSpec("t", 1e12, 1e9, 2.0**30))
        assert perf.decode_bound_tokens_per_s(16) is None  # no card yet
        mod.complete_card("jit.decode_step", "s",
                          cost={"flops": 1.0e6,
                                "bytes accessed": 1.0e8})  # 0.1s
        assert perf.decode_bound_tokens_per_s(2) == pytest.approx(10.0)
        assert perf.decode_bound_tokens_per_s(11) == pytest.approx(100.0)
        assert perf.decode_bound_tokens_per_s(1) is None   # no scan ran
    finally:
        perf.disable()
        mod.reset()
        mod._registry, mod.spec = old_reg, old_spec
        reg.disable()


# ----------------------------------------------------------------------
# report + tools satellites
# ----------------------------------------------------------------------

def test_report_program_ledger_section():
    import telemetry_report as tr
    h = telemetry.Histogram()
    for _ in range(6):
        h.observe(0.04)                      # mean period 40ms
    events = [
        {"ev": "meta", "pid": 1, "t0_wall": 100.0, "p": 0, "ts": 0.0},
        {"ev": "program_card", "p": 0, "ts": 1.0,
         "name": "jit.train_step", "shapes": "f32[8,16]", "sig": "aa",
         "key": None, "cause": "new_signature", "compiles": 1,
         "compile_s": 2.5, "flops": 2.0e12, "bytes_accessed": 1e9,
         "arg_bytes": 10, "temp_bytes": 5, "out_bytes": 1,
         "peak_bytes": 16, "predicted_s": 0.02, "status": "ready",
         "error": None, "spec": "test", "spec_peak_flops": 100e12,
         "spec_hbm_bw": 500e9},
        {"ev": "hists", "p": 0, "ts": 2.0,
         "hists": {"train.period": h.to_dict()}},
    ]
    agg = tr.aggregate(events)
    pg = agg["programs"]
    assert pg["count"] == 1
    row = pg["cards"][0]
    assert row["name"] == "jit.train_step"
    # mfu = 2e12 / (0.04 * 100e12) = 50%: the mean is exact, no bucket's
    assert row["measured_ms"] == pytest.approx(40.0)
    assert row["mfu_pct"] == pytest.approx(50.0)
    assert row["roofline_eff_pct"] == pytest.approx(50.0)
    # a log from before train.period has the dispatch's series alone:
    # the card reads no time off it (it gave an MFU 60 times too high)
    old = events[:2] + [dict(events[2], hists={"train.step": h.to_dict()})]
    row = tr.aggregate(old)["programs"]["cards"][0]
    assert row["measured_ms"] is None and row["mfu_pct"] is None
    assert pg["hbm_peak_bytes"] == 16
    assert pg["top_by_compile"] == ["jit.train_step"]
    assert pg["top_by_gap"] == ["jit.train_step"]
    # without cards the section stays absent (older logs)
    assert tr.aggregate(events[:1] + events[2:])["programs"] is None


# ----------------------------------------------------------------------
# the ONE real-jit CPU test (everything above is jax-free)
# ----------------------------------------------------------------------

TINY_CONF = """
netconfig = start
layer[+1] = fullc:fc1
  nhidden = 32
  init_sigma = 0.05
layer[+1] = relu
layer[+1] = fullc:fc2
  nhidden = 10
  init_sigma = 0.05
layer[+0] = softmax
netconfig = end
input_shape = 1,1,16
batch_size = 8
eta = 0.1
dev = cpu
eval_train = 0
"""


def test_real_train_step_produces_a_program_card():
    import numpy as np
    from cxxnet_tpu.io.data import DataBatch
    from cxxnet_tpu.nnet.trainer import Trainer
    from cxxnet_tpu.utils.config import parse_config_string
    telemetry.reset()
    telemetry.enable()
    perf.enable()
    try:
        tr = Trainer()
        for k, v in parse_config_string(TINY_CONF):
            tr.set_param(k, v)
        tr.init_model()
        rs = np.random.RandomState(0)
        b = DataBatch()
        b.data = rs.rand(8, 1, 1, 16).astype(np.float32)
        b.label = rs.randint(0, 10, (8, 1)).astype(np.float32)
        b.batch_size = 8
        for _ in range(4):
            tr.update(b)
        assert perf.drain(60.0), "carder thread never finished"
        card = perf.ledger().card("jit.train_step")
        assert card is not None and card["status"] == "ready", card
        assert card["flops"] and card["flops"] > 0
        assert card["peak_bytes"] and card["peak_bytes"] > 0
        # this is a CPU run: XLA's counts are real, but there is no
        # spec, so no predicted time, MFU or roofline share
        assert card["predicted_s"] is None
        assert card["compile_s"] > 0
        assert card["key"] is not None
        snap = perf.ledger().snapshot()
        c = [c for c in snap["cards"]
             if c["name"] == "jit.train_step"][0]
        # the measured join fired: the call that built the step starts no
        # period, the two calls that follow a plain call each end one
        assert c["measured_series"] == "train.period"
        assert c["measured_n"] == 2
        assert c["mfu_pct"] is None and c["roofline_eff_pct"] is None
    finally:
        perf.disable()
        perf.reset()
        telemetry.disable()
        telemetry.reset()


@pytest.mark.slow
def test_profilez_real_capture_writes_a_loadable_trace(tmp_path):
    """Real jax.profiler capture through the guard (slow: the first
    start_trace pays a ~10s lazy tensorflow import)."""
    import jax.numpy as jnp
    prof = perf.ProfilerCapture(str(tmp_path))
    ok, path = prof.start(1.0)
    assert ok
    deadline = time.monotonic() + 90
    while prof.busy() and time.monotonic() < deadline:
        jnp.ones((64, 64)).sum().block_until_ready()
        time.sleep(0.05)
    assert not prof.busy() and prof.last_error is None
    found = []
    for root, _, files in os.walk(path):
        found += files
    assert any(f.endswith(".xplane.pb") for f in found), found


def test_decode_pool_cap_bytes_sizes_from_live_account():
    """The paged decode KV pool's byte budget (ROADMAP item 2: "sized
    from the live HBM account"): frac x (capacity − peak program
    footprint), peak taken over the cards measured SO FAR; None when
    the ledger is off (the pool falls back to dense-equivalent
    sizing). The decode-KV hook is NOT charged — the pool replaces
    the dense caches that hook reports (charging them would
    double-count the bytes being sized)."""
    lg, reg = make_ledger()          # capacity 8 GiB
    try:
        # no cards yet: the whole capacity is headroom
        assert lg.decode_pool_cap_bytes(0.5) == int(0.5 * 8 * 2.0**30)
        lg.complete_card("jit.train_step", "s",
                         mem={"argument_size_in_bytes": 2 * 2**30,
                              "temp_size_in_bytes": 2**30,
                              "output_size_in_bytes": 0})
        assert lg.decode_pool_cap_bytes(0.5) == int(0.5 * 5 * 2.0**30)
        # a registered decode-KV hook must NOT shrink the budget
        lg.set_decode_kv(lambda: 10 * 2**30)
        assert lg.decode_pool_cap_bytes(0.5) == int(0.5 * 5 * 2.0**30)
        # frac clamps to [0, 1]
        assert lg.decode_pool_cap_bytes(2.0) == int(5 * 2.0**30)
        assert lg.decode_pool_cap_bytes(-1.0) == 0
    finally:
        lg.disable()
        reg.disable()
    assert lg.decode_pool_cap_bytes(0.5) is None    # ledger off
