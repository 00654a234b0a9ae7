"""The language-model cell's benchmark parts on the CPU at a small size:
``references/moe_lm.py``, ``programs/cxxnet_lm_trainer.py``, ``lm_flops.py``
and the cell's entries in the manifest. The
reference against ``Trainer.update`` leaf by leaf is
``tests/test_smallthinker.py``."""

import io
import json
import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark import compare, lm_flops, lm_inputs, netconf  # noqa: E402
from benchmark import run as bench_run  # noqa: E402
from benchmark.programs import cxxnet_lm_trainer  # noqa: E402
from benchmark.references import moe_lm  # noqa: E402

BENCH = os.path.join(ROOT, "benchmark")
CELL = "smallthinker-ep4-train-8k"
L = 32
# float32 on both sides here: the limits a sound run has to meet are those
# of rounding, and everything a run can do wrong reads far above them
LIMITS = {"loss1": 1e-5, "loss2": 1e-5, "loss3": 1e-5,
          "grad_worst": 1e-3, "change_worst": 5e-3}


def _small_conf():
    from cxxnet_tpu import models
    return models.smallthinker_netconfig(
        vocab=96, dim=64, nhead=4, nkvhead=2, head_dim=16, nlayer=4,
        n_expert=8, top_k=2, expert_width=32, window=8, n_held=4,
        expert_offset=2) + models.SMALLTHINKER_ADAMW


def _small_spec():
    spec = bench_run.resolve(CELL)
    spec["conf_text"] = _small_conf()
    spec["cfg"] = dict(spec["cfg"], seq_len=L, batch_per_chip=2 * L,
                       extra_cfg="eval_train = 0\nhealth_monitor = 1\n")
    spec["traffic"] = dict(spec["traffic"], sync_every=2, warm_steps=1)
    spec["limits"] = dict(LIMITS)
    return spec


def _run(factory=None):
    log = io.StringIO()
    return bench_run.run_cell(_small_spec(), seed=2**31 + 5, seconds=0.2,
                              trace=False, require_tpu=False,
                              program_factory=factory, log=log,
                              compile_cache=False)


class _HalfTheTokens(cxxnet_lm_trainer.Program):
    """Only the first half of each sequence reaches the step, repeated to
    fill the row: the mean is taken over those tokens alone (the language
    model's ``_PartOfBatch``)."""

    def __init__(self, *a, **k):
        import jax.numpy as jnp
        super().__init__(*a, **k)
        for b in self.batches:
            half = b.data.shape[-1] // 2
            b.data = jnp.concatenate([b.data[..., :half]] * 2, axis=-1)
            b.label = jnp.concatenate([b.label[:, :half]] * 2, axis=-1)


class _PairsOverMeanLoadDropped(cxxnet_lm_trainer.Program):
    """The mechanism's own fault: a capacity. Every expert takes at most
    the mean load (pairs held / experts held, as even routing would give)
    and the pairs beyond it are dropped, as a capacity-factor dispatch
    would. Planted under the layer, in the grouped product: a row whose
    rank within its group is past the capacity comes out as nought."""

    def __init__(self, *a, **k):
        import jax.numpy as jnp
        from cxxnet_tpu import ops
        super().__init__(*a, **k)
        self._ops, self._sound = ops, ops.grouped_matmul

        def capped(lhs, rhs, sizes):
            out = self._sound(lhs, rhs, sizes)
            ends = jnp.cumsum(sizes)
            row = jnp.arange(lhs.shape[0])
            group = jnp.minimum(jnp.searchsorted(ends, row, side="right"),
                                sizes.shape[0] - 1)
            rank = row - (ends - sizes)[group]
            cap = jnp.sum(sizes) // sizes.shape[0]
            return jnp.where((rank < cap)[:, None], out, 0)
        ops.grouped_matmul = capped

    def release(self):
        self._ops.grouped_matmul = self._sound
        super().release()


def test_a_sound_run_of_the_cell_is_correct():
    r = _run()
    assert r["correct"] is True, r["compared"]
    assert set(r["metrics"]) == {"train_items_per_s_per_chip", "setup_s"}
    assert r["attempted"] > 0 and r["failed"] == 0
    assert r["compared"]["window_compiles"] == [0.0, 0.0]


def test_half_the_tokens_is_not_correct():
    r = _run(_HalfTheTokens)
    assert r["correct"] is False
    assert r["compared"]["grad_worst"][0] > 10 * LIMITS["grad_worst"]


def test_pairs_dropped_over_an_experts_mean_load_are_not_correct():
    r = _run(_PairsOverMeanLoadDropped)
    assert r["correct"] is False
    assert r["compared"]["grad_worst"][0] > 10 * LIMITS["grad_worst"]


@pytest.mark.parametrize("how,tripped", [
    ({"precision": "fp8"}, "grad_worst"),
    ({"precision": "bf16"}, "grad_worst"),
    ({"rows_used": L}, "loss1")], ids=["fp8", "bf16", "half_the_tokens"])
def test_the_controls_are_not_correct(how, tripped):
    """The reference in the program's place, computed in a precision below
    this float32 test configuration's, or with half the tokens left out of
    the loss (calibrate.py's ``half_batch``), fails a limit."""
    spec = _small_spec()
    seed = 991
    want = moe_lm.for_config(spec["conf_text"], spec["cfg"], 2 * L).run(seed)
    got = moe_lm.for_config(spec["conf_text"], spec["cfg"], 2 * L,
                            **how).run(seed)
    rows = {r["name"]: r for r in compare.judge(compare.numbers(got, want),
                                                LIMITS)}
    assert not rows[tripped]["ok"], rows
    assert all(r["ok"] for r in compare.judge(compare.numbers(want, want),
                                              LIMITS))


def test_the_same_seed_gives_the_same_tokens_and_they_are_zipf():
    import jax
    import numpy as np
    from benchmark.inputs import seed_key
    key = seed_key(2**31 + 9)
    data, label = jax.jit(lambda k: lm_inputs.make_tokens(k, 0, 4, 4096,
                                                          37984))(key)
    again, _ = lm_inputs.make_tokens(key, 0, 4, 4096, 37984)
    np.testing.assert_array_equal(np.asarray(data), np.asarray(again))
    ids = np.asarray(data).reshape(4, 4096)
    np.testing.assert_array_equal(ids[:, 1:], np.asarray(label)[:, :-1])
    assert ids.min() >= 0 and ids.max() < 37984 and ids.max() > 10000
    # Zipf with exponent 1 over 37984 ids: id 0 has 1 / H(37984) = 9.0%
    assert 0.075 < (ids == 0).mean() < 0.105
    other, _ = lm_inputs.make_tokens(key, 1, 4, 4096, 37984)
    assert (np.asarray(other) != np.asarray(data)).mean() > 0.5


def test_lm_flops_agrees_with_a_hand_count():
    with open(os.path.join(BENCH, "configs",
                           "smallthinker-21b-ep4-l4.conf")) as f:
        conf = f.read()
    macs = lm_flops.forward_macs(conf, 8192)
    by_part = {}
    for _, part, m in macs:
        by_part[part] = by_part.get(part, 0) + m
    d, q, kv, f = 2560, 28 * 128, 4 * 128, 768
    assert by_part["qkv"] + by_part["out"] == 4 * (d * (q + 2 * kv) + q * d)
    glob = 2 * q * (8192 + 1) / 2
    win = 2 * q * (4096 * 4097 / 2 + 4096 * 4096) / 8192
    assert by_part["core"] == pytest.approx(glob + 3 * win)
    assert by_part["route"] == 4 * d * 64
    assert by_part["experts"] == 4 * (6 * 16 / 64) * 3 * d * f
    assert by_part["head"] == d * 37984
    flops = lm_flops.train_flops_per_item(conf, 8192)
    assert flops == 6 * sum(by_part.values())
    assert flops == pytest.approx(1.876e9, rel=2e-3)      # ISSUE 29: 1.88
    cfg = {"seq_len": 8192}
    assert moe_lm.train_flops_per_item(conf, cfg) == flops
    assert lm_flops.mean_keys(32, 32) == lm_flops.mean_keys(32) == 16.5
    e = lm_flops.expert_product(12288, d, f, 16)
    assert e["flops"] == 2 * 12288 * 3 * d * f
    assert e["bytes"] > 2 * 16 * 3 * d * f
    a = lm_flops.flash_attention(8192, 28, 4, 128, 4096)
    assert a["flops"] == pytest.approx(8192 * win * 2 * 128 / 128)


def test_the_configuration_keeps_the_published_widths():
    cfg = bench_run.resolve(CELL)["cfg"]
    row = None
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    if os.path.exists(catalog):
        with open(catalog) as f:
            row = next(r for r in map(json.loads, f)
                       if r["name"] == "SmallThinker-21BA3B-Instruct")
    published = row["config"] if row else dict(
        cfg, **cfg["published"])
    assert set(cfg["reduced"]) == {"num_hidden_layers",
                                   "moe_num_primary_experts", "vocab_size"}
    for key, val in published.items():
        if key in cfg["reduced"]:
            assert cfg["published"][key] == val and cfg[key] < val
        else:
            assert cfg[key] == val, key
    # and the conf text that is run says the same
    layers, glob = netconf.parse(bench_run.resolve(CELL)["conf_text"])
    att = [lay for lay in layers if lay.type == "attention"]
    moe = [lay for lay in layers if lay.type == "moe"]
    assert len(att) == len(moe) == cfg["num_hidden_layers"] == 4
    assert [a.geti("rope") for a in att] == cfg["rope_layout"][:4]
    assert [bool(a.geti("attn_window")) for a in att] == \
        [bool(v) for v in cfg["sliding_window_layout"][:4]]
    for a in att:
        assert (a.geti("nhead"), a.geti("nkvhead"), a.geti("head_dim")) == (
            cfg["num_attention_heads"], cfg["num_key_value_heads"],
            cfg["head_dim"])
        assert a.geti("attn_window") in (0, cfg["sliding_window_size"])
        assert a.getf("rope_base") == cfg["rope_theta"]
    for m in moe:
        assert (m.geti("nexpert"), m.geti("top_k"), m.geti("nhidden"),
                m.geti("nexpert_held")) == (
            cfg["published"]["moe_num_primary_experts"],
            cfg["moe_num_active_primary_experts"],
            cfg["moe_ffn_hidden_size"], cfg["moe_num_primary_experts"])
    shapes = lm_inputs.weight_shapes(layers)
    assert shapes["emb"]["wmat"] == (cfg["vocab_size"], cfg["hidden_size"])
    assert shapes["head"]["wmat"] == (cfg["vocab_size"], cfg["hidden_size"])
    import math
    n = sum(math.prod(s) for tags in shapes.values() for s in tags.values())
    assert 655e6 < n < 657e6                     # ISSUE 29: 656 M
    assert cfg["batch_per_chip"] % cfg["seq_len"] == 0


def test_the_conf_is_what_the_builder_writes():
    from cxxnet_tpu import models
    text = bench_run.resolve(CELL)["conf_text"]
    body = "\n".join(ln for ln in text.splitlines()
                     if not ln.startswith("#"))
    want = models.smallthinker_netconfig(nlayer=4, n_held=16, vocab=37984) \
        + models.SMALLTHINKER_ADAMW
    assert body.strip() == want.strip()


ACCEPTED = ["compile_cache_misses", "update_call_ms", "step_mfu_share",
            "matmul_time_share", "pool_bwd_time_share",
            "collective_time_share", "device_idle_share", "init_model_s",
            "step_build_s"]


def _manifest():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def test_the_cell_is_on_the_lists_the_issue_names():
    m = _manifest()
    listed = {p["name"] for p in m["per_layer"]
              if CELL in p.get("workloads", [])}
    assert listed == set(ACCEPTED) - {"pool_bwd_time_share",
                                      "collective_time_share"}
    cell = next(w for w in m["workloads"] if w["name"] == CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        "smallthinker-21b-ep4-l4", "resident", 1)


def test_the_cell_is_appended_and_no_metric_stands_before_an_accepted_one():
    # the driver reads an entry put before ``init_model_s`` as a change to
    # it, and ``test_program_phase.py`` holds ``init_model_s`` and
    # ``step_build_s`` to be the last two: so this PR adds no per-layer
    # entry at all (PERF.md section 7), only the cell at the end of lists
    m = _manifest()
    assert [p["name"] for p in m["per_layer"]] == ACCEPTED
    assert m["workloads"][-1]["name"] == CELL
    assert m["configs"][-1]["name"] == "smallthinker-21b-ep4-l4"
    for p in m["per_layer"]:
        if CELL in p["workloads"]:
            assert p["workloads"][-1] == CELL
            assert p["workloads"].count(CELL) == 1
    assert sorted(os.listdir(os.path.join(BENCH, "metrics"))) == sorted(
        name + ".json" for name in ACCEPTED)


def test_a_traced_line_of_the_cell_carries_the_accepted_metrics():
    spec = bench_run.resolve(CELL)
    assert [m["name"] for m in spec["per_layer"]] == [
        n for n in ACCEPTED
        if n not in ("pool_bwd_time_share", "collective_time_share")]
    trace = {"busy_s": 2.0, "window_s": 2.5,
             "class_s": {"other": 1.0, "loop": 0.5, "copy": 0.1,
                         "matmul": 0.4}}
    peak = {"bf16_flops_per_s": 197e12}
    flops = moe_lm.train_flops_per_item(spec["conf_text"], spec["cfg"])
    ctx = {"window": {"steps": 10, "items_per_s_profiler_off": 20000.0,
                      "update_call_ms_median": 3.5},
           "counters": {"compile_cache_misses": 0},
           "trace": trace, "chips": 1, "peak": peak, "flops_per_item": flops}
    got = {k: v["value"] for k, v in
           bench_run.per_layer_metrics(spec, ctx).items()}
    assert got["matmul_time_share"] == 20.0
    assert got["device_idle_share"] == pytest.approx(20.0)
    # 1.876 GFLOP a trained token x 20,000 tokens/s over 197 TFLOP/s
    assert got["step_mfu_share"] == pytest.approx(19.05, abs=0.05)
    assert got["update_call_ms"] == 3.5
    # no trace (a --trace 0 run): the shares of the trace are left out
    got = bench_run.per_layer_metrics(spec, dict(ctx, trace=None))
    assert "matmul_time_share" not in got and "device_idle_share" not in got
