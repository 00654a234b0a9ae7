"""``Trainer.update`` by its parts: the four kept spans it leaves in the
always-on account, the step's period, and what ``io.h2d_bytes`` counts.
No test here asserts on a wall clock: spans are compared with each other,
periods are counted, means are taken of fed times."""

import numpy as np
import pytest

from cxxnet_tpu.io.data import DataBatch
from cxxnet_tpu.nnet.trainer import Trainer
from cxxnet_tpu.utils import telemetry
from cxxnet_tpu.utils.config import parse_config_string

CONF = """
netconfig = start
layer[+1] = fullc:fc1
  nhidden = 8
  init_sigma = 0.01
layer[+0] = softmax
netconfig = end
input_shape = 1,1,16
batch_size = 4
dev = cpu
eta = 0.1
eval_train = 0
"""
KEPT = ("train.update", "train.h2d", "train.args", "train.dispatch")


@pytest.fixture(autouse=True)
def _isolated(monkeypatch):
    """Telemetry is the process's, the kept account even across reset()."""
    telemetry.disable()
    telemetry.reset()
    monkeypatch.setattr(telemetry._REG, "kept_rings", {})
    yield
    telemetry.disable()
    telemetry.reset()


def _trainer():
    tr = Trainer()
    for k, v in parse_config_string(CONF):
        tr.set_param(k, v)
    tr.init_model()
    return tr


def _batch(device=False):
    rs = np.random.RandomState(0)
    b = DataBatch()
    b.data = rs.rand(4, 1, 1, 16).astype(np.float32)
    b.label = np.zeros((4, 1), np.float32)
    b.batch_size = 4
    if device:
        import jax.numpy as jnp
        b.data, b.label = jnp.asarray(b.data), jnp.asarray(b.label)
    return b


def _periods():
    return telemetry.summary()["hists"].get("train.period", {"count": 0})


@pytest.mark.parametrize("enabled", [False, True],
                         ids=["telemetry_disabled", "telemetry_enabled"])
def test_ten_updates_leave_ten_of_each_kept_span_and_nine_periods(
        enabled, monkeypatch):
    tr, b = _trainer(), _batch()
    tr.update(b)                    # the call that builds the step
    monkeypatch.setattr(telemetry._REG, "kept_rings", {})
    if enabled:
        telemetry.enable()
    for _ in range(10):
        tr.update(b)
    kept = telemetry.kept()
    assert set(kept) == set(KEPT)               # train.step is not kept
    assert all(len(kept[n]) == 10 for n in KEPT)
    for (u0, ud), (h0, hd), (a0, ad), (d0, dd) in zip(
            *(kept[n] for n in KEPT)):
        # the children inside the parent's interval, in the call's order
        assert u0 <= h0 and h0 + hd <= a0 and a0 + ad <= d0
        assert d0 + dd <= u0 + ud
    if not enabled:
        assert telemetry.events() == [] and _periods()["count"] == 0
        return
    # the call after the build starts the chain: ten entries, nine periods
    per = _periods()
    assert per["count"] == 9
    entries = [t0 for t0, _ in kept["train.update"]]
    assert per["sum_s"] == pytest.approx(entries[-1] - entries[0], abs=1e-5)
    spans = telemetry.summary()["spans"]
    assert {n: spans[n]["count"] for n in spans if n.startswith("train.")} \
        == dict.fromkeys(KEPT + ("train.step",), 10)


class _NoEval:
    def before_first(self):
        pass

    def next(self):
        return False


@pytest.mark.parametrize("between", [
    "evaluate", "clear_jit_cache", "start_round", "save_model",
    "init_model"])
def test_a_call_after_a_break_records_no_period(between, tmp_path):
    tr, b = _trainer(), _batch()
    tr.update(b)
    telemetry.enable()
    tr.update(b)
    tr.update(b)
    assert _periods()["count"] == 1
    if between == "evaluate":
        tr.evaluate(_NoEval(), "test")
    elif between == "clear_jit_cache":
        tr._clear_jit_cache()
    elif between == "start_round":
        tr.start_round(1)
    elif between == "save_model":
        from cxxnet_tpu.utils import serializer
        tr.save_model(serializer.Writer())
    else:
        tr.init_model()
    tr.update(b)                        # first after the break: none
    assert _periods()["count"] == 1
    if between in ("clear_jit_cache", "init_model"):
        tr.update(b)                    # it built the step anew: none
        assert _periods()["count"] == 1
    tr.update(b)
    assert _periods()["count"] == 2


def test_the_mean_period_of_a_bimodal_series_is_its_sum_over_its_count():
    telemetry.enable()
    fed = ([0.004] * 6 + [0.100] * 4) * 7
    for d in fed:
        telemetry.hist("train.period", d)
    per = _periods()
    assert per["count"] == len(fed)
    assert per["sum_s"] == pytest.approx(sum(fed))
    assert per["mean_ms"] == pytest.approx(1e3 * sum(fed) / len(fed))
    assert telemetry.summary()["step_time_ms"] == pytest.approx(42.4)


@pytest.mark.parametrize("device", [True, False],
                         ids=["resident_batch", "host_batch"])
def test_h2d_bytes_counts_what_comes_from_the_host(device):
    tr, b = _trainer(), _batch(device)
    telemetry.enable()
    tr.update(b)
    tr.update(b)
    want = 0 if device else 2 * (b.data.nbytes + b.label.nbytes)
    assert telemetry.summary()["counters"].get("io.h2d_bytes", 0) == want
