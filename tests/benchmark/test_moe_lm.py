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


# A share's sorted side is bounded only from 512 rows up (the grouped
# product's row tile): two sequences of LONG tokens, top 2, make 2,048 pairs,
# and experts 2-3 of 8 get 3/2 * 2,048 * 2/8 = 768 rows, in whole tiles 1,024.
# Under base seed SKEWED, of 4,000 searched through the reference's forward
# pass the one whose fullest layer holds most, the last layer's two experts
# hold 1,105 and 1,088 of the two batches' pairs: more than its rows.
LONG, LONG_ROWS, SKEWED = 512, 1024, 3336
SEED = 2**31 + 5


def _small_conf(**over):
    from cxxnet_tpu import models
    return models.smallthinker_netconfig(**dict(dict(
        vocab=96, dim=64, nhead=4, nkvhead=2, head_dim=16, nlayer=4,
        n_expert=8, top_k=2, expert_width=32, window=8, n_held=4,
        expert_offset=2), **over)) + models.SMALLTHINKER_ADAMW


def _small_spec(seq=L, **over):
    spec = bench_run.resolve(CELL)
    spec["conf_text"] = _small_conf(**over)
    spec["cfg"] = dict(spec["cfg"], seq_len=seq, batch_per_chip=2 * seq,
                       extra_cfg="eval_train = 0\nhealth_monitor = 1\n")
    spec["traffic"] = dict(spec["traffic"], sync_every=2, warm_steps=1)
    spec["limits"] = dict(LIMITS)
    return spec


def _skewed_spec():
    spec = _small_spec(LONG, n_held=2)
    spec["cfg"]["weights_base_seed"] = SKEWED
    return spec


def _run(factory=None, spec=None):
    log = io.StringIO()
    return bench_run.run_cell(spec or _small_spec(), seed=SEED, seconds=0.2,
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


class _LimitsKept(cxxnet_lm_trainer.Program):
    """Keeps the rows each layer's sorted side was traced with."""
    rows = {}

    def release(self):
        type(self).rows = {name: rows for name, (_, rows)
                           in self.trainer.health_gauge_limits.items()}
        super().release()


@pytest.mark.parametrize("load", ["at_rest", "skewed"])
def test_a_sound_run_of_the_cell_is_correct(load):
    """``skewed``: the cell's accepted weights (``weights_base_seed``) route
    near evenly under every seed, so no run of the cell on the chip meets a
    layer that holds more pairs than its sorted side has rows. Here one
    does, in every step: a second base seed at a small size, whose last
    layer takes the whole-order branch, compared number by number with the
    reference as the cell's runs are."""
    if load == "at_rest":
        r = _run()
    else:
        r = _run(_LimitsKept, _skewed_spec())
        held = r["run"]["gauges"]["moe.pairs_held/b3_moe"]
        assert _LimitsKept.rows["moe.pairs_held/b3_moe"] == LONG_ROWS
        assert held > LONG_ROWS + 50
        assert all(r["run"]["gauges"]["moe.pairs_held/b%d_moe" % i]
                   < LONG_ROWS for i in range(3))
    assert r["correct"] is True, r["compared"]
    assert set(r["metrics"]) == {"train_items_per_s_per_chip", "setup_s"}
    assert r["attempted"] > 0 and r["failed"] == 0
    assert r["compared"]["window_compiles"] == [0.0, 0.0]


class _PairsPastTheRowsDropped(_LimitsKept):
    """The bound's own fault: the sorted side's first rows whatever the
    load, so that the pairs past them are dropped where a layer holds more
    than its rows (``_either_side`` without its second branch)."""

    def __init__(self, *a, **k):
        from cxxnet_tpu.layer import layers
        self._layers, self._sound = layers, layers._either_side
        layers._either_side = layers._sorted_side
        super().__init__(*a, **k)

    def release(self):
        self._layers._either_side = self._sound
        super().release()


def test_pairs_past_the_sorted_sides_rows_dropped_are_not_correct():
    r = _run(_PairsPastTheRowsDropped, _skewed_spec())
    assert _LimitsKept.rows["moe.pairs_held/b3_moe"] == LONG_ROWS
    assert r["run"]["gauges"]["moe.pairs_held/b3_moe"] > LONG_ROWS + 50
    assert r["correct"] is False
    assert r["compared"]["grad_worst"][0] > 10 * LIMITS["grad_worst"]


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


def test_a_base_seed_gives_every_seed_the_same_model_in_another_order():
    """``weights_base_seed``: the seed reorders the model's hidden units
    and draws the tokens, and nothing else: the same tokens meet the same
    experts and give the same logits under every seed (to rounding: the
    sums run in another order), so a step is the same work."""
    import jax
    import numpy as np
    from benchmark.inputs import seed_key
    layers, glob = netconf.parse(_small_conf())
    make = lm_inputs.params_from_seed(layers, glob,
                                      {"weights_base_seed": 77})
    a, b = make(seed_key(1)), make(seed_key(2**31 + 5))
    other = lm_inputs.params_from_seed(layers, glob,
                                       {"weights_base_seed": 78})(seed_key(1))
    ids = jax.random.randint(jax.random.PRNGKey(3), (L,), 0, 96)
    logits = [np.asarray(moe_lm.logits_of(layers, "highest", p, ids))
              for p in (a, b, other)]
    np.testing.assert_allclose(logits[0], logits[1], rtol=0, atol=2e-5)
    assert np.abs(logits[0] - logits[2]).max() > 1e-2
    # the arrays differ: the same entries along the model axis, reordered
    axes = lm_inputs.model_axes(layers)
    assert set(axes) == set(a)
    for name, tags in a.items():
        for tag, w in tags.items():
            w, v, ax = np.asarray(w), np.asarray(b[name][tag]), \
                axes[name][tag]
            assert w.shape[ax] == 64
            np.testing.assert_array_equal(np.sort(w, axis=ax),
                                          np.sort(v, axis=ax))
            if w.ndim > 1:
                assert (w != v).mean() > 0.9
    # the routers meet the same tokens with the same experts
    for lay in layers:
        if lay.type == "moe":
            x = np.asarray(a["emb"]["wmat"])[np.asarray(ids)]
            y = np.asarray(b["emb"]["wmat"])[np.asarray(ids)]
            ra = np.argsort(x @ np.asarray(a[lay.name]["gate"]).T)[:, -2:]
            rb = np.argsort(y @ np.asarray(b[lay.name]["gate"]).T)[:, -2:]
            assert (ra == rb).mean() > 0.98
    # a cfg that states no base seed (the program's own tests) runs the
    # model of seed 0: one path, no second draw
    none = lm_inputs.params_from_seed(layers, glob, {})(seed_key(1))
    zero = lm_inputs.params_from_seed(layers, glob,
                                      {"weights_base_seed": 0})(seed_key(1))
    np.testing.assert_array_equal(np.asarray(none["head"]["wmat"]),
                                  np.asarray(zero["head"]["wmat"]))


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


# the seven per-layer lists the cell was accepted on (PR 29) and the nine
# metrics of its own that PR 34 appended
ON_ACCEPTANCE = ["compile_cache_misses", "update_call_ms", "step_mfu_share",
                 "matmul_time_share", "device_idle_share", "init_model_s",
                 "step_build_s"]
ITS_OWN = ["flash_roofline", "expert_product_roofline", "other_time_share",
           "loop_time_share", "copy_time_share", "moe_dense_layers",
           "forward_time_share", "backward_time_share", "update_time_share"]


def _manifest():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def _rules():
    """``tests/benchmark/test_benchmark.py``: the manifest's lint and its
    append-only rule live there, once."""
    import importlib.util
    spec = importlib.util.spec_from_file_location(
        "bench_lint_rules", os.path.join(ROOT, "tests", "benchmark",
                                         "test_benchmark.py"))
    rules = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(rules)
    return rules


def test_the_cell_is_on_the_lists_the_issue_names():
    m = _manifest()
    listed = [p["name"] for p in m["per_layer"]
              if CELL in p.get("workloads", [])]
    # at least these, in this order; a later PR may append to them
    assert [n for n in listed if n in ON_ACCEPTANCE + ITS_OWN] == \
        ON_ACCEPTANCE + ITS_OWN
    for p in m["per_layer"]:
        if p["name"] in ITS_OWN:
            assert p["workloads"][:1] == [CELL]
            assert p["moves"] == "train_items_per_s_per_chip"
    cell = next(w for w in m["workloads"] if w["name"] == CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        "smallthinker-21b-ep4-l4", "resident", 1)


def test_the_cell_is_appended_and_no_metric_stands_before_an_accepted_one():
    # the driver reads an entry put before an accepted one as a change to
    # it (PR 29 was refused once for that): the append-only rule of
    # test_benchmark.py holds every list to start with what accepted.json
    # records, and says nothing about what stands last
    rules = _rules()
    m, accepted = _manifest(), rules._accepted()
    assert rules.append_only(m, ROOT, accepted) == []
    assert accepted["per_layer"][:9] + ITS_OWN == accepted["per_layer"][:18]
    assert CELL in accepted["workloads"]
    assert "smallthinker-21b-ep4-l4" in accepted["configs"]
    for p in m["per_layer"]:
        assert p["workloads"].count(CELL) <= 1
    # its nine metrics put before the two that list every cell, as PR 29
    # first had its four: refused by the rule's own message
    moved = dict(m, per_layer=[p for p in m["per_layer"]
                               if p["name"] in ITS_OWN]
                 + [p for p in m["per_layer"] if p["name"] not in ITS_OWN])
    errs = rules.append_only(moved, ROOT, accepted)
    assert errs and "add at the end" in errs[0]
    for name in ON_ACCEPTANCE + ITS_OWN:
        assert name + ".json" in accepted["files"]["metrics"]
        assert os.path.exists(os.path.join(BENCH, "metrics", name + ".json"))


def test_a_traced_line_of_the_cell_carries_the_accepted_metrics():
    spec = bench_run.resolve(CELL)
    names = [m["name"] for m in spec["per_layer"]]
    assert [n for n in names if n in ON_ACCEPTANCE + ITS_OWN] == \
        ON_ACCEPTANCE + ITS_OWN
    trace = {"busy_s": 2.0, "window_s": 2.5, "steps": 10,
             "class_s": {"other": 1.0, "loop": 0.5, "copy": 0.1,
                         "matmul": 0.4},
             "scope_s": {"forward": {"b0_att/core": 0.04,
                                     "b1_att/core": 0.1,
                                     "b0_moe/experts": 0.06},
                         "backward": {"b0_att/core": 0.11,
                                      "b1_att/core": 0.273,
                                      "b0_moe/experts": 0.204},
                         "update": {"emb": 0.03, "b0_moe": 0.01},
                         "other": {"-": 0.1, "health": 0.02}}}
    peak = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
    flops = moe_lm.train_flops_per_item(spec["conf_text"], spec["cfg"])
    ctx = {"window": {"steps": 10, "items_per_s_profiler_off": 20000.0,
                      "update_call_ms_median": 3.5},
           "counters": {"compile_cache_misses": 0},
           "trace": trace, "chips": 1, "peak": peak, "flops_per_item": flops,
           "reference": moe_lm, "conf_text": spec["conf_text"],
           "cfg": spec["cfg"], "said": {}}
    got = {k: v["value"] for k, v in
           bench_run.per_layer_metrics(spec, ctx).items()}
    assert got["matmul_time_share"] == 20.0
    assert (got["other_time_share"], got["loop_time_share"],
            got["copy_time_share"]) == (50.0, 25.0, 5.0)
    assert got["device_idle_share"] == pytest.approx(20.0)
    # the phases' rows over the 2 s the device was busy
    assert (got["forward_time_share"], got["backward_time_share"],
            got["update_time_share"]) == pytest.approx((10.0, 29.35, 2.0))
    # 1.876 GFLOP a trained token x 20,000 tokens/s over 197 TFLOP/s
    assert got["step_mfu_share"] == pytest.approx(19.05, abs=0.05)
    assert got["update_call_ms"] == 3.5
    # 4.69 TFLOP a step are 23.8 ms of the peak; the cores took 52.3 ms
    assert got["flash_roofline"] == pytest.approx(
        100 * (4.6905e12 / 197e12) / 0.0523, rel=1e-4)
    # even routing's 49,152 pairs x 11.8 MFLOP x 3 in 26.4 ms
    assert got["expert_product_roofline"] == pytest.approx(
        100 * (1.7395e12 / 197e12) / 0.0264, rel=1e-4)
    assert {v["bound"] for v in ctx["said"].values()} == {"compute"}
    assert ctx["said"]["roofline/expert_product"]["pairs_a_step"] == 49152
    # where the reference has run, the pairs it counted on the two batches
    counted = dict(ctx, said={}, want={"pairs_held": {
        "b%d_moe" % i: [12000, 12100, 11000] for i in range(4)}})
    got2 = bench_run.per_layer_metrics(spec, counted)
    assert got2["expert_product_roofline"]["value"] == pytest.approx(
        got["expert_product_roofline"] * 4 * 12050 / 49152, rel=1e-3)
    assert counted["said"]["roofline/expert_product"]["pairs_a_step"] \
        == 4 * 12050
    # a reduction that has no row of the kernels (the parent's program, an
    # executable from before the scopes were named): left out, never 0
    bare = dict(ctx, trace=dict(trace, scope_s={"other": {"-": 2.0}}))
    got = bench_run.per_layer_metrics(spec, bare)
    assert "flash_roofline" not in got
    assert "expert_product_roofline" not in got
    assert "forward_time_share" not in got
    assert "matmul_time_share" in got
    # no trace (a --trace 0 run): the shares of the trace are left out
    got = bench_run.per_layer_metrics(spec, dict(ctx, trace=None))
    assert not {"matmul_time_share", "device_idle_share", "flash_roofline",
                "other_time_share", "backward_time_share"} & set(got)


def test_kernel_work_agrees_with_a_hand_count_at_the_cells_sizes():
    spec = bench_run.resolve(CELL)
    conf, cfg = spec["conf_text"], spec["cfg"]
    q, dh, L, W = 28 * 128, 128, 8192, 4096
    # q k^T and p v over the keys the mask keeps: one global layer (the
    # causal triangle) and three window layers, forward; x 3 to train
    glob = 4 * q * L * (L + 1) / 2
    win = 4 * q * (W * (W + 1) / 2 + (L - W) * W)
    flash = moe_lm.kernel_work(conf, cfg, "flash_attention", {})
    assert flash["flops"] == 3 * (glob + 3 * win)
    assert flash["flops"] == pytest.approx(4.69e12, rel=2e-3)  # ISSUE 34
    assert glob == pytest.approx(0.481e12, rel=2e-3)
    assert win == pytest.approx(0.361e12, rel=2e-3)
    assert flash["bytes"] == 3 * 4 * 2 * L * dh * (2 * 28 + 2 * 4)
    # a pair's three products of 2560 x 768, forward: 11.8 MFLOP
    pair = 2 * 3 * 2560 * 768
    assert pair == pytest.approx(11.8e6, rel=1e-3)
    even = moe_lm.kernel_work(conf, cfg, "expert_product", {})
    assert even["flops"] == 3 * 4 * (8192 * 6 * 16 / 64) * pair
    # the pairs the reference counted in its own first steps, where it has
    # run: the mean of the two resident batches, the third step left out
    held = {"b%d_moe" % i: [n, n + 20, 7] for i, n in
            enumerate((12477, 10142, 11913, 11777))}
    said = moe_lm.kernel_work(conf, cfg, "expert_product",
                              {"want": {"pairs_held": held}})
    assert said["pairs_a_step"] == 12477 + 10142 + 11913 + 11777 + 40
    assert said["flops"] == 3 * said["pairs_a_step"] * pair
    assert said["flops"] == pytest.approx(1.64e12, rel=5e-3)   # ISSUE 34
    one = moe_lm.kernel_work(conf, cfg, "expert_product",
                             {"want": {"pairs_held": {"b0_moe": [12477]}}})
    assert one["flops"] == 3 * (12477 + 3 * 12288) * pair
    # what the program says of itself counts for nothing
    assert moe_lm.kernel_work(
        conf, cfg, "expert_product",
        {"gauges": {"moe.pairs_held/b0_moe": 1.0}}) == even
    assert even["pairs_a_step"] == 4 * 12288
    # every matrix held read once a product, forward: 16 x 3 x 2560 x 768
    assert even["bytes"] > 3 * 4 * 2 * 16 * 3 * 2560 * 768
    assert moe_lm.kernel_work(conf, cfg, "lrn", {}) is None


def test_the_program_reports_the_pairs_its_experts_held():
    r = _run()
    gauges = r["run"]["gauges"]
    held = {k: v for k, v in gauges.items()
            if k.startswith("moe.pairs_held/")}
    assert sorted(held) == ["moe.pairs_held/b%d_moe" % i for i in range(4)]
    # 2 x 32 tokens, top 2 of 8, 4 held: even routing holds 64 pairs
    assert all(0 < v < 128 and v == int(v) for v in held.values())
    assert 150 < sum(held.values()) < 350
    # and they are the pairs the reference counts by itself on the same
    # tokens (the window ends on the second batch; the routing is at rest),
    # which is what the roofline's work is counted from
    spec = _small_spec()
    want = moe_lm.for_config(spec["conf_text"], spec["cfg"], 2 * L).run(SEED)
    assert sorted(want["pairs_held"]) == ["b%d_moe" % i for i in range(4)]
    assert any(n[0] != n[1] for n in want["pairs_held"].values())
    for name, by_step in want["pairs_held"].items():
        assert len(by_step) == 3
        assert abs(held["moe.pairs_held/" + name] - by_step[1]) <= 1
    # a program whose adapter has no gauges() gives none: the conv cells
    assert not hasattr(__import__(
        "benchmark.programs.cxxnet_trainer",
        fromlist=["Program"]).Program, "gauges")
