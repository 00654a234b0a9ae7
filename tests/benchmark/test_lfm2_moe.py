"""The short-conv / sigmoid-routing cell's benchmark parts on the CPU at a
small size: ``references/lfm2_moe.py``, ``programs/cxxnet_lfm2_trainer.py``,
``lfm2_inputs.py`` and the cell's entries in the manifest. The whole model
through ``Trainer.update`` against the reference, by the cell's own
``run_cell``: sound, and with this model's three faults planted in the
program; the reference's controls and the same faults in the program's
place. The layers one by one against the reference's functions are
``tests/test_lfm2.py``."""

import io
import json
import os
import sys

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark import compare, lfm2_inputs, lm_inputs, netconf  # noqa: E402
from benchmark import run as bench_run  # noqa: E402
from benchmark.programs import cxxnet_lfm2_trainer  # noqa: E402
from benchmark.references import lfm2_moe  # noqa: E402

BENCH = os.path.join(ROOT, "benchmark")
CELL, CONFIG = "lfm2-ep8-train-8k", "lfm2-24b-a2b-ep8-l5"
L = 64
# float32 on both sides here: the limits a sound run has to meet are those
# of rounding, and everything a run can do wrong reads far above them
LIMITS = {"loss1": 1e-5, "loss2": 1e-5, "loss3": 1e-5,
          "grad_worst": 1e-3, "change_worst": 5e-3}
SEED = 2**31 + 7
SMALL = dict(vocab=96, dim=64, nhead=4, nkvhead=2, head_dim=16,
             layer_types=("conv", "full_attention", "conv", "conv", "conv"),
             n_dense=1, dense_width=96, n_expert=8, top_k=4, expert_width=32,
             n_held=4, expert_offset=2)
# a step size at which three steps move every leaf past float32's rounding
ADAMW = "0.001"


def _small_conf():
    from cxxnet_tpu import models
    return models.lfm2_moe_netconfig(**SMALL) \
        + models.LFM2_ADAMW.replace("0.0000003", ADAMW)


def _small_spec():
    spec = bench_run.resolve(CELL)
    spec["conf_text"] = _small_conf()
    spec["cfg"] = dict(spec["cfg"], seq_len=L, batch_per_chip=2 * L,
                       weights_base_seed=5,
                       extra_cfg="eval_train = 0\nhealth_monitor = 1\n")
    spec["traffic"] = dict(spec["traffic"], sync_every=2, warm_steps=1)
    spec["limits"] = dict(LIMITS)
    return spec


def _run(factory=None):
    return bench_run.run_cell(_small_spec(), seed=SEED, seconds=0.2,
                              trace=False, require_tpu=False,
                              program_factory=factory, log=io.StringIO(),
                              compile_cache=False)


def test_a_sound_run_of_the_cell_is_correct():
    r = _run()
    assert r["correct"] is True, r["compared"]
    assert set(r["metrics"]) == {"train_items_per_s_per_chip", "setup_s"}
    assert r["attempted"] > 0 and r["failed"] == 0
    assert r["compared"]["window_compiles"] == [0.0, 0.0]
    gauges = r["run"]["gauges"]
    for i in range(1, 5):
        # two sequences of 64, 4 choices a token, 4 of 8 experts held
        assert 0 < gauges["moe.pairs_held/b%d_moe" % i] < 2 * L * 4


class _Patched(cxxnet_lfm2_trainer.Program):
    """A fault planted in the program for the life of the program: the
    attribute ``name`` of ``owner`` replaced by ``fault(sound)``."""
    owner = name = None

    @staticmethod
    def fault(sound):
        raise NotImplementedError

    def __init__(self, *a, **k):
        self._sound = getattr(self.owner, self.name)
        setattr(self.owner, self.name, type(self).fault(self._sound))
        try:
            super().__init__(*a, **k)
        except Exception:
            setattr(self.owner, self.name, self._sound)
            raise

    def release(self):
        setattr(self.owner, self.name, self._sound)
        super().release()


def _layers():
    from cxxnet_tpu.layer import layers
    return layers


class _ConvAhead(_Patched):
    """The conv reads one position ahead: v_t from u_{t-1..t+1}, the next
    token leaking in (B and X moved one row earlier, C not)."""
    owner, name = _layers(), "short_conv"

    @staticmethod
    def fault(sound):
        def conv(bcx, w):
            import jax.numpy as jnp
            d = w.shape[0]
            early = jnp.pad(bcx[:, 1:], ((0, 0), (0, 1), (0, 0)))
            return sound(jnp.concatenate(
                [early[..., :d], bcx[..., d:2 * d], early[..., 2 * d:]],
                axis=-1), w)
        return conv


class _BiasLeftOut(_Patched):
    """The experts chosen by the sigmoid scores alone."""
    owner, name = _layers().MoELayer, "_top"

    @staticmethod
    def fault(sound):
        return lambda self, logits, bias=None: sound(self, logits, None)


class _BiasedWeights(_Patched):
    """The chosen experts weighted by their BIASED scores."""
    owner, name = _layers().MoELayer, "_top"

    @staticmethod
    def fault(sound):
        def top(self, logits, bias=None):
            import jax
            import jax.numpy as jnp
            idx, _ = sound(self, logits, bias)
            w = jnp.take_along_axis(jax.nn.sigmoid(logits) + bias, idx, -1)
            return idx, w / (jnp.sum(w, -1, keepdims=True) + 1e-6)
        return top


class _BiasStepped(cxxnet_lfm2_trainer.Program):
    """The expert bias handed a step too small to move a choice: 1e-6 added
    to one layer's in the window, after the first steps' change read it
    fixed."""

    def step(self):
        super().step()
        if self.steps_done == 5:
            leaves = self.trainer.params[
                self.trainer.net.cfg.get_layer_index("b1_moe")]
            leaves["expert_bias"] = leaves["expert_bias"] + np.float32(1e-6)


@pytest.mark.parametrize("factory,tripped", [
    (_ConvAhead, "grad_worst"), (_BiasLeftOut, "grad_worst"),
    (_BiasedWeights, "grad_worst"), (_BiasStepped, "change_worst")],
    ids=["conv_reads_ahead", "bias_left_out", "weights_from_biased_scores",
         "bias_moved_in_the_window"])
def test_each_fault_planted_in_the_program_is_not_correct(factory, tripped):
    r = _run(factory)
    assert r["correct"] is False
    assert r["compared"][tripped][0] > 10 * LIMITS[tripped], r["compared"]


@pytest.fixture(scope="module")
def sound():
    """The reference's own run of the small cell, which every control is
    read against."""
    spec = _small_spec()
    return spec, lfm2_moe.for_config(spec["conf_text"], spec["cfg"],
                                     2 * L).run(991)


@pytest.mark.parametrize("how,tripped", [
    ({"precision": "fp8"}, "grad_worst"),
    ({"precision": "bf16"}, "grad_worst"),
    ({"rows_used": L}, "loss1"),
    ({"conv_ahead": True}, "grad_worst"),
    ({"bias_choice": False}, "grad_worst"),
    ({"bias_weight": True}, "grad_worst")],
    ids=["fp8", "bf16", "half_the_tokens", "conv_reads_ahead",
         "bias_left_out", "weights_from_biased_scores"])
def test_the_controls_and_planted_faults_are_not_correct(sound, how, tripped):
    """The reference in the program's place, computed in a precision below
    this float32 test configuration's, with half the tokens left out of the
    cross-entropy (calibrate.py's ``half_batch``), or with one of this
    model's own faults planted, fails a limit; the bias has no gradient to
    compare, and its change reads 0."""
    spec, want = sound
    got = lfm2_moe.for_config(spec["conf_text"], spec["cfg"], 2 * L,
                              **how).run(991)
    rows = {r["name"]: r for r in compare.judge(compare.numbers(got, want),
                                                LIMITS)}
    assert not rows[tripped]["ok"], rows
    assert all(r["ok"] for r in compare.judge(compare.numbers(want, want),
                                              LIMITS))
    assert set(want["pairs_held"]) == set(want["bias_moved"]) == {
        "b%d_moe" % i for i in range(1, 5)}
    assert not any(n.endswith(":expert_bias") for n in want["grad_norm"])
    assert {n: v for n, v in want["change_norm"].items()
            if n.endswith(":expert_bias")} == {
        "b%d_moe:expert_bias" % i: 0.0 for i in range(1, 5)}
    # the bias moves some of the choices, and the fault that leaves it out
    # moves none
    assert all(0 < n < 2 * L * 4 for v in want["bias_moved"].values()
               for n in v)
    if how == {"bias_choice": False}:
        assert all(n == 0 for v in got["bias_moved"].values() for n in v)


def test_every_seed_gives_the_same_model_in_another_order():
    """``--seed`` reorders the model's hidden units and nothing else: the
    same ids give the same logits and the same routing (to rounding), and
    the leaves with no model axis (the heads' norms, the conv's taps, the
    expert bias) are not reordered."""
    import jax
    from benchmark.inputs import seed_key
    layers, glob = netconf.parse(_small_conf())
    cfg = {"weights_base_seed": 77, "expert_bias": {"mean": 0.2,
                                                    "sd": 0.05}}
    make = lfm2_inputs.params_from_seed(layers, glob, cfg)
    a, b = make(seed_key(1)), make(seed_key(2**31 + 5))
    ids = jax.random.randint(jax.random.PRNGKey(3), (L,), 0, 96)
    out = [lfm2_moe.forward(layers, "highest", p, ids) for p in (a, b)]
    assert out[0][0].shape == (L, 96)
    np.testing.assert_allclose(np.asarray(out[0][0]), np.asarray(out[1][0]),
                               rtol=0, atol=2e-5)
    assert out[0][1]["pairs_held"] == out[1][1]["pairs_held"]
    assert (np.asarray(a["b0_conv"]["w_in"]) != np.asarray(
        b["b0_conv"]["w_in"])).mean() > 0.9
    for name, tag in (("b0_conv", "w_conv"), ("b1_att", "qnorm"),
                      ("b2_moe", "expert_bias")):
        np.testing.assert_array_equal(np.asarray(a[name][tag]),
                                      np.asarray(b[name][tag]))
    bias = np.asarray(a["b2_moe"]["expert_bias"])
    assert bias.shape == (8,) and abs(bias.mean() - 0.2) < 0.1
    names = [t for _, n, t, _ in lfm2_inputs.leaves_of(layers)
             if n in ("b0_conv", "b0_ffn", "b1_moe")]
    assert names == ["w_in", "w_conv", "w_out", "wmat", "up", "down",
                     "wmat", "gate", "up", "down", "expert_bias"]


def _cell_conf():
    with open(os.path.join(BENCH, "configs", CONFIG + ".conf")) as f:
        return f.read()


def test_the_flops_and_each_kernels_work_agree_with_hand_counts():
    conf, cfg = _cell_conf(), bench_run.resolve(CELL)["cfg"]
    by_part = {}
    for _, part, m in lfm2_moe.forward_macs(conf, 8192):
        by_part[part] = by_part.get(part, 0) + m
    d, q, kv, rows = 2048, 32 * 64, 8 * 64, 8192
    tri = 8192 * 8193 // 2
    assert by_part["conv_in"] + by_part["conv_out"] == 4 * rows * 4 * d * d
    assert by_part["qkv"] + by_part["out"] == rows * (d * (q + 2 * kv)
                                                      + q * d)
    assert by_part["core"] == 2 * q * tri
    assert by_part["ffn"] == rows * 3 * d * 11776
    assert by_part["route"] == 4 * rows * d * 64
    assert by_part["experts"] == 4 * rows * (4 * 8 / 64) * 3 * d * 1536
    assert by_part["head"] == rows * d * 8192
    flops = lfm2_moe.train_flops_per_item(conf, cfg) * 16384
    assert flops == pytest.approx(2 * 6 * sum(by_part.values()), rel=1e-12)
    # ISSUE 42: the conv mixers ~33% of the products, the dense FFN ~36%
    total = sum(by_part.values())
    assert (by_part["conv_in"] + by_part["conv_out"]) / total \
        == pytest.approx(0.33, abs=0.01)
    assert by_part["ffn"] / total == pytest.approx(0.36, abs=0.01)
    assert flops == pytest.approx(20.0e12, rel=0.01)
    flash = lfm2_moe.kernel_work(conf, cfg, "flash_attention", {})
    assert flash["flops"] == 2 * 6 * by_part["core"]
    assert flash["bytes"] == 2 * 2 * 2 * rows * 64 * (2 * 32 + 2 * 8)
    conv = lfm2_moe.kernel_work(conf, cfg, "short_conv", {})
    tokens = 16384
    assert conv["flops"] == 4 * 3 * tokens * (3 * 2 * d + 2 * d)
    assert conv["bytes"] == 4 * (tokens * 2 * d * 11 + d * 3 * 8)
    even = lfm2_moe.kernel_work(conf, cfg, "expert_product", {})
    assert even["pairs_a_step"] == 4 * 8192
    assert even["flops"] == 2 * 6 * by_part["experts"]
    counted = {"want": {"pairs_held": {
        "b%d_moe" % i: [6000.0, 8000.0, 9000.0] for i in range(1, 5)}}}
    read = lfm2_moe.kernel_work(conf, cfg, "expert_product", counted)
    assert read["pairs_a_step"] == 4 * 7000       # the two batches' mean
    assert read["flops"] == pytest.approx(even["flops"] * 7000 / 8192)
    assert lfm2_moe.kernel_work(conf, cfg, "select", {}) is None
    # a small size, by hand: one conv mixer of d 4 and 3 taps, 10 tokens
    small = lfm2_moe.short_conv(4, 3, 10)
    assert small == {"flops": 3 * 10 * (24 + 8),
                     "bytes": 10 * 2 * 4 * 11 + 4 * 3 * 8}


def test_the_configuration_keeps_the_published_widths():
    spec = bench_run.resolve(CELL)
    cfg = spec["cfg"]
    row = None
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    if os.path.exists(catalog):
        with open(catalog) as f:
            row = next(r for r in map(json.loads, f)
                       if r["name"] == "LFM2-24B-A2B")
        assert cfg["source"] == row["source_url"]
    published = row["config"] if row else dict(cfg, **cfg["published"])
    assert set(cfg["reduced"]) == {"num_hidden_layers", "num_dense_layers",
                                   "layer_types", "num_experts",
                                   "vocab_size"}
    for key, val in published.items():
        if key in cfg["reduced"]:
            assert cfg["published"][key] == val, key
        else:
            assert cfg[key] == val, key
    assert cfg["layer_types"] == published["layer_types"][1:6]
    for key in ("scoring", "expert_bias", "head", "intermediate_size",
                "conv", "norms", "weights", "optimizer", "remat", "tokens"):
        assert cfg["assumed"][key]
    assert "8 chips" in cfg["deployment"]
    assert cfg["weights_base_seed"] > 0
    # and the conf text that is run says the same
    layers, glob = netconf.parse(spec["conf_text"])
    kinds = [lay.type for lay in layers
             if lay.type in ("shortconv", "attention")]
    assert kinds == [{"conv": "shortconv", "full_attention": "attention"}[t]
                     for t in cfg["layer_types"]]
    ffn = [lay for lay in layers if lay.type in ("ffn", "moe")]
    assert [lay.type for lay in ffn] == ["ffn"] + ["moe"] * 4
    assert ffn[0].geti("nhidden") == cfg["intermediate_size"] == 11776
    for m in ffn[1:]:
        assert (m.geti("nexpert"), m.geti("top_k"), m.geti("nhidden"),
                m.geti("nexpert_held")) == (
            cfg["published"]["num_experts"], cfg["num_experts_per_tok"],
            cfg["moe_intermediate_size"], cfg["num_experts"])
        assert m.params["score"] == "sigmoid" and m.geti("expert_bias")
        assert "route_scale" not in m.params    # the published scale is 1
    assert cfg["routed_scaling_factor"] == 1
    att = next(lay for lay in layers if lay.type == "attention")
    assert (att.geti("nhead"), att.geti("nkvhead"), att.geti("head_dim")) \
        == (cfg["num_attention_heads"], cfg["num_key_value_heads"],
            cfg["hidden_size"] // cfg["num_attention_heads"])
    assert att.getf("rope_base") == cfg["rope_parameters"]["rope_theta"]
    assert att.getf("qk_norm_eps") == cfg["norm_eps"]
    assert all(lay.geti("kernel") == cfg["conv_L_cache"]
               for lay in layers if lay.type == "shortconv")
    assert lm_inputs.vocab_of(layers) == cfg["vocab_size"] == 8192
    n = sum(int(np.prod(s)) for tags in
            lfm2_inputs.weight_shapes(layers).values() for s in tags.values())
    assert n == pytest.approx(486e6, rel=2e-3)        # ISSUE 42's count
    for tag in ("qnorm", "knorm", "gain"):
        assert glob[tag + ":wd"] == "0.0"


def test_the_conf_is_what_the_builder_writes():
    from cxxnet_tpu import models
    body = "\n".join(ln for ln in _cell_conf().splitlines()
                     if not ln.startswith("#"))
    assert body.strip() == (models.lfm2_moe_netconfig(
        layer_types=("conv", "full_attention", "conv", "conv", "conv"),
        n_dense=1, n_held=8, vocab=8192) + models.LFM2_ADAMW).strip()


OWN = {"shortconv_time_share": ("scope_time_share", {
           "scopes": ["*_conv/in", "*_conv/core", "*_conv/out"]}),
       "shortconv_roofline": ("scope_roofline_share", {
           "scopes": ["*_conv/core"], "work": "short_conv"})}
# the conv cells' own and the learned selection's; and PR 40's eleven, whose
# cell lists tests/benchmark/test_program_kept.py pins whole (PERF.md §7)
NOT_JOINED = {"pool_bwd_time_share", "collective_time_share",
              "index_scores_roofline", "select_roofline",
              "index_select_time_share", "h2d_call_ms", "args_call_ms",
              "dispatch_call_ms", "host_share_of_step", "step_trace_s",
              "step_lower_s", "step_cache_load_s", "init_params_s",
              "moe_token_side_time_share", "emb_time_share",
              "cast_params_time_share"}


def test_the_cell_stands_in_each_list_after_the_accepted_cells():
    """Membership and order, never "stands last": a later PR appends
    behind this one. The cell joins every list of the language-model
    cells but those ``NOT_JOINED`` names."""
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        manifest = json.load(f)
    with open(os.path.join(ROOT, "tests", "benchmark",
                           "accepted.json")) as f:
        accepted = json.load(f)
    configs = [c["name"] for c in manifest["configs"]]
    cells = [c["name"] for c in manifest["workloads"]]
    assert configs.index(CONFIG) > configs.index("keye-30b-a3b-ep8-l4")
    assert cells.index(CELL) > cells.index("keye-ep8-train-8k")
    assert manifest["workloads"][cells.index(CELL)] == dict(
        manifest["workloads"][cells.index(CELL)], config=CONFIG,
        traffic="resident", chips=1)
    names = [m["name"] for m in manifest["per_layer"]]
    for m in manifest["per_layer"]:
        if m["name"] in OWN:
            continue
        cells_of = m["workloads"]
        assert (CELL in cells_of) == (m["name"] not in NOT_JOINED), m["name"]
        if CELL in cells_of:
            before = cells_of[:cells_of.index(CELL)]
            assert "sdar-ep8-train-8k" in before or m["name"] in (
                "pool_bwd_time_share",), m["name"]
            was = accepted.get("per_layer_workloads", {}).get(m["name"])
            if was:
                assert before[:len(was)] == was, m["name"]
    for name, (reader, args) in OWN.items():
        assert names.index(name) > names.index("index_select_time_share")
        entry = manifest["per_layer"][names.index(name)]
        assert entry["workloads"] == [CELL] and entry["unit"] == "%"
        assert entry["layer"] == "kernels"
        assert entry["moves"] == "train_items_per_s_per_chip"
        with open(os.path.join(BENCH, "metrics", name + ".json")) as f:
            desc = json.load(f)
        assert desc["reader"] == reader and desc["args"] == args


def test_the_limits_file_has_both_readings_for_every_number():
    with open(os.path.join(BENCH, "limits", CELL + ".json")) as f:
        limits = json.load(f)
    assert set(limits["limits"]) == set(LIMITS)
    for name, value in limits["limits"].items():
        assert value is None or 0 < value < 1, name
    for word in ("fp8", "half the tokens", "conv reads one position ahead",
                 "bias left out of the selection",
                 "weights from the biased scores"):
        assert word in limits["set_from"], word
