"""The gated-head / shared-expert cell's benchmark parts on the CPU at a
small size: ``references/laguna_moe.py``, ``programs/cxxnet_laguna_trainer.py``,
``laguna_inputs.py`` and the cell's entries in the manifest. The whole
model through ``Trainer.update`` against the reference, by the cell's own
``run_cell``: sound, and with each of this model's four faults planted in
the program; the reference's controls and the same faults in the
program's place. The layers one by one against the reference's functions
are ``tests/test_laguna.py``."""

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

from benchmark import compare, laguna_inputs, lm_inputs, netconf  # noqa: E402
from benchmark import run as bench_run  # noqa: E402
from benchmark.programs import cxxnet_laguna_trainer  # noqa: E402
from benchmark.references import laguna_moe  # noqa: E402

BENCH = os.path.join(ROOT, "benchmark")
CELL, CONFIG = "laguna-ep8-train-8k", "laguna-xs2-ep8-l5"
L = 64
# float32 on both sides here: the limits a sound run has to meet are those
# of rounding, and everything a run can do wrong reads far above them
LIMITS = {"loss1": 1e-5, "loss2": 1e-5, "loss3": 1e-5,
          "grad_worst": 1e-3, "change_worst": 5e-3}
SEED = 2**31 + 7
SMALL_ROPE = dict(theta=1e4, rope_dim=8, factor=4.0, original_len=64,
                  beta_fast=4.0, beta_slow=1.0, attention_factor=0.0)
SMALL = dict(vocab=96, dim=64, full_heads=6, window_heads=8, nkvhead=2,
             head_dim=16, window=8, full_rope=SMALL_ROPE, dense_width=96,
             n_expert=16, top_k=4, expert_width=32, shared_width=24,
             n_held=4, expert_offset=2)
# a step size at which three steps move every leaf past float32's rounding
ADAMW = "0.001"


def _small_conf():
    """The cell's five layers at the small size; matrices at normal(0,
    0.1), at which a width of 64 still attends by position (at 0.02 every
    head is all but uniform, and the rotation and the window hardly
    show)."""
    from cxxnet_tpu import models
    return (models.laguna_moe_netconfig(
        layer_types=models.LAGUNA_LAYER_TYPES[:5], **SMALL)
        + models.LAGUNA_ADAMW.replace("0.0000003", ADAMW)).replace(
        "init_sigma = 0.02\n", "init_sigma = 0.1\n")


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
    for name in ("b1w_moe", "b2w_moe", "b3w_moe", "b4_moe"):
        # two sequences of 64, 4 choices a token, 4 of 16 experts held
        assert 0 < gauges["moe.pairs_held/" + name] < 2 * L * 4


class _Edited(cxxnet_laguna_trainer.Program):
    """A fault planted in the program through the conf text it is given
    (the reference reads the sound one): ``edit(conf_text)``."""

    @staticmethod
    def edit(text):
        raise NotImplementedError

    def __init__(self, conf_text, *a, **k):
        super().__init__(type(self).edit(conf_text), *a, **k)


class _Unwindowed(_Edited):
    """The window layers see every earlier key."""

    @staticmethod
    def edit(text):
        return text.replace("attn_window = 8\n", "attn_window = 0\n")


class _NoYarn(_Edited):
    """YaRN left out: plain frequencies at the full layers' theta over the
    turned half."""

    @staticmethod
    def edit(text):
        return "".join(ln for ln in text.splitlines(True)
                       if "yarn" not in ln)


class _ScaleOne(_Edited):
    """The routed sum taken at a scale of 1."""

    @staticmethod
    def edit(text):
        return text.replace("routed_scale = 2.5\n", "routed_scale = 1\n")


class _NoGate(cxxnet_laguna_trainer.Program):
    """The heads' gate left out: the core's output passed on as it is, the
    gate's leaf kept (and trained by nothing)."""

    def __init__(self, *a, **k):
        from cxxnet_tpu.layer import layers
        self._sound = layers.AttentionLayer._gate
        layers.AttentionLayer._gate = lambda self, seq, wgate, out: out
        try:
            super().__init__(*a, **k)
        except Exception:
            layers.AttentionLayer._gate = self._sound
            raise

    def release(self):
        from cxxnet_tpu.layer import layers
        layers.AttentionLayer._gate = self._sound
        super().release()


@pytest.mark.parametrize("factory", [_Unwindowed, _NoYarn, _NoGate,
                                     _ScaleOne],
                         ids=["unwindowed", "yarn_left_out", "gate_left_out",
                              "routed_scale_1"])
def test_each_fault_planted_in_the_program_is_not_correct(factory):
    r = _run(factory)
    assert r["correct"] is False
    assert max(r["compared"][n][0] / LIMITS[n] for n in LIMITS) > 10, \
        r["compared"]


@pytest.fixture(scope="module")
def sound():
    """The reference's own run of the small cell, which every control is
    read against."""
    spec = _small_spec()
    return spec, laguna_moe.for_config(spec["conf_text"], spec["cfg"],
                                       2 * L).run(991)


@pytest.mark.parametrize("how", [
    {"precision": "fp8"}, {"precision": "bf16"}, {"rows_used": L},
    {"unwindowed": True}, {"yarn": False}, {"gate": False},
    {"routed_scale": 1.0}],
    ids=["fp8", "bf16", "half_the_tokens", "unwindowed", "yarn_left_out",
         "gate_left_out", "routed_scale_1"])
def test_the_controls_and_planted_faults_are_not_correct(sound, how):
    """The reference in the program's place, computed in a precision below
    this float32 test configuration's, with half the tokens left out of the
    cross-entropy (calibrate.py's ``half_batch``), or with one of this
    model's own faults planted, fails a limit; the sound reference passes
    against itself."""
    spec, want = sound
    got = laguna_moe.for_config(spec["conf_text"], spec["cfg"], 2 * L,
                                **how).run(991)
    rows = compare.judge(compare.numbers(got, want), LIMITS)
    assert not all(r["ok"] for r in rows), rows
    assert all(r["ok"] for r in compare.judge(compare.numbers(want, want),
                                              LIMITS))
    assert set(want["pairs_held"]) == {"b1w_moe", "b2w_moe", "b3w_moe",
                                       "b4_moe"}


def test_every_seed_gives_the_same_model_in_another_order():
    """``--seed`` reorders the model's hidden units and nothing else: the
    same ids give the same logits and the same routing (to rounding); the
    new leaves are reordered on their model axis."""
    import jax
    from benchmark.inputs import seed_key
    layers, glob = netconf.parse(_small_conf())
    make = laguna_inputs.params_from_seed(layers, glob,
                                          {"weights_base_seed": 77})
    a, b = make(seed_key(1)), make(seed_key(2**31 + 5))
    ids = jax.random.randint(jax.random.PRNGKey(3), (L,), 0, 96)
    out = [laguna_moe.forward(layers, "highest", p, ids) for p in (a, b)]
    assert out[0][0].shape == (L, 96)
    np.testing.assert_allclose(np.asarray(out[0][0]), np.asarray(out[1][0]),
                               rtol=0, atol=5e-5)
    assert out[0][1]["pairs_held"] == out[1][1]["pairs_held"]
    for name, tag in (("b0_att", "wgate"), ("b1w_moe", "shared_down")):
        x, y = np.asarray(a[name][tag]), np.asarray(b[name][tag])
        assert (x != y).mean() > 0.9
        np.testing.assert_array_equal(np.sort(x, axis=None),
                                      np.sort(y, axis=None))
    names = [t for _, n, t, _ in laguna_inputs.leaves_of(layers)
             if n in ("b0_att", "b0_ffn", "b1w_moe")]
    assert names == ["wmat", "wo", "wgate", "wmat", "up", "down",
                     "wmat", "gate", "up", "down", "shared_wmat",
                     "shared_up", "shared_down"]


def _cell_conf():
    with open(os.path.join(BENCH, "configs", CONFIG + ".conf")) as f:
        return f.read()


def test_the_flops_and_each_kernels_work_agree_with_hand_counts():
    """The work by part, per token forward: projections 343.9 MFLOP,
    full-attention cores 201.3, window cores 48.8, dense FFN 100.7, routed
    experts held 25.2, shared 25.2, head 51.4; about 19.7 TFLOP a step."""
    conf, cfg = _cell_conf(), bench_run.resolve(CELL)["cfg"]
    by_part = {}
    seq = 8192
    for name, part, m in laguna_moe.forward_macs(conf, seq):
        key = part + ("_w" if part == "core" and name.endswith("w_att")
                      else "")
        by_part[key] = by_part.get(key, 0) + m
    d = 2048
    tri = seq * (seq + 1) / 2
    win = 512 * 513 / 2 + (seq - 512) * 512
    proj = 2 * seq * d * (48 * 128 + 16 * 128 + 48 + 48 * 128) \
        + 3 * seq * d * (64 * 128 + 16 * 128 + 64 + 64 * 128)
    assert by_part["qkv"] + by_part["gate"] + by_part["out"] == proj
    assert by_part["core"] == 2 * 2 * 48 * 128 * tri
    assert by_part["core_w"] == pytest.approx(3 * 2 * 64 * 128 * win)
    assert by_part["ffn"] == seq * 3 * d * 8192
    assert by_part["route"] == 4 * seq * d * 256
    assert by_part["experts"] == 4 * seq * (8 * 32 / 256) * 3 * d * 512
    assert by_part["shared"] == 4 * seq * 3 * d * 512
    assert by_part["head"] == seq * d * 12544
    per_token = {k: 2 * v / seq / 1e6 for k, v in by_part.items()}
    # the projections are q, k, v and o; the gates' and routers' products
    # add 5.4 MFLOP a token beside them
    assert per_token["qkv"] + per_token["out"] == pytest.approx(343.9,
                                                                abs=0.5)
    assert per_token["gate"] + per_token["route"] == pytest.approx(5.37,
                                                                   abs=0.01)
    assert per_token["core"] == pytest.approx(201.3, abs=0.5)
    assert per_token["core_w"] == pytest.approx(48.8, abs=0.5)
    assert per_token["ffn"] == pytest.approx(100.7, abs=0.1)
    assert per_token["experts"] == pytest.approx(25.2, abs=0.1)
    assert per_token["shared"] == pytest.approx(25.2, abs=0.1)
    assert per_token["head"] == pytest.approx(51.4, abs=0.1)
    flops = laguna_moe.train_flops_per_item(conf, cfg) * seq
    assert flops == pytest.approx(6 * sum(by_part.values()), rel=1e-12)
    assert flops == pytest.approx(19.7e12, rel=0.01)
    # the kernels' work: the forward's, x3
    flash = laguna_moe.kernel_work(conf, cfg, "flash_attention", {})
    window = laguna_moe.kernel_work(conf, cfg, "window_attention", {})
    assert flash["flops"] == pytest.approx(
        6 * (by_part["core"] + by_part["core_w"]))
    assert window["flops"] == pytest.approx(6 * by_part["core_w"])
    io_full = 2 * seq * 128 * (2 * 48 + 2 * 8)
    io_win = 2 * seq * 128 * (2 * 64 + 2 * 8)
    assert window["bytes"] == 2 * 3 * io_win
    assert flash["bytes"] == 2 * (2 * io_full + 3 * io_win)
    shared = laguna_moe.kernel_work(conf, cfg, "shared_expert", {})
    assert shared["flops"] == 6 * by_part["shared"]
    assert shared["bytes"] == 3 * 4 * 2 * 3 * (d * 512 + seq * (d + 512))
    even = laguna_moe.kernel_work(conf, cfg, "expert_product", {})
    assert even["pairs_a_step"] == 4 * 8192
    assert even["flops"] == 6 * by_part["experts"]
    counted = {"want": {"pairs_held": {
        n: [6000.0, 8000.0, 9000.0]
        for n in ("b1w_moe", "b2w_moe", "b3w_moe", "b4_moe")}}}
    read = laguna_moe.kernel_work(conf, cfg, "expert_product", counted)
    assert read["pairs_a_step"] == 4 * 7000       # the two batches' mean
    assert read["flops"] == pytest.approx(even["flops"] * 7000 / 8192)
    assert laguna_moe.kernel_work(conf, cfg, "short_conv", {}) is None


def test_the_configuration_keeps_the_published_widths():
    spec = bench_run.resolve(CELL)
    cfg = spec["cfg"]
    row = None
    catalog = os.environ.get("MODEL_CATALOG", "")   # architectures.jsonl
    if catalog and os.path.exists(catalog):
        with open(catalog) as f:
            row = next(r for r in map(json.loads, f)
                       if r["name"] == "Laguna-XS.2")
        assert cfg["source"] == row["source_url"]
    published = row["config"] if row else dict(cfg, **cfg["published"])
    assert set(cfg["reduced"]) == {
        "num_hidden_layers", "layer_types", "mlp_layer_types",
        "num_attention_heads_per_layer", "num_experts", "vocab_size"}
    for key, val in published.items():
        if key in cfg["reduced"]:
            assert cfg["published"][key] == val, key
        else:
            assert cfg[key] == val, key
    for key in ("layer_types", "mlp_layer_types",
                "num_attention_heads_per_layer"):
        assert cfg[key] == published[key][:5], key
    for key in ("gate", "scoring", "shared_expert", "qk_norm", "rope",
                "head", "weights", "optimizer", "remat", "tokens"):
        assert cfg["assumed"][key]
    assert "8 chips" in cfg["deployment"]
    assert cfg["weights_base_seed"] > 0
    # and the conf text that is run says the same
    layers, glob = netconf.parse(spec["conf_text"])
    att = [lay for lay in layers if lay.type == "attention"]
    full = cfg["rope_parameters"]["full_attention"]
    for a, kind, heads in zip(att, cfg["layer_types"],
                              cfg["num_attention_heads_per_layer"]):
        assert (a.geti("nhead"), a.geti("nkvhead"), a.geti("head_dim")) \
            == (heads, cfg["num_key_value_heads"], cfg["head_dim"])
        assert a.geti("head_gate") == 1
        if kind == "full_attention":
            assert not a.geti("attn_window")
            assert a.getf("rope_base") == full["rope_theta"]
            assert a.params["rope_type"] == full["rope_type"] == "yarn"
            assert a.geti("rope_dim") == cfg["head_dim"] * full[
                "partial_rotary_factor"]
            assert (a.getf("yarn_factor"), a.geti("yarn_original_len"),
                    a.getf("yarn_beta_fast"), a.getf("yarn_beta_slow"),
                    a.getf("yarn_attention_factor")) == (
                full["factor"], full["original_max_position_embeddings"],
                full["beta_fast"], full["beta_slow"],
                full["attention_factor"])
        else:
            assert a.geti("attn_window") == cfg["sliding_window"]
            assert a.getf("rope_base") == cfg["rope_parameters"][
                "sliding_attention"]["rope_theta"]
            assert not a.geti("rope_dim")
    ffn = [lay for lay in layers if lay.type in ("ffn", "moe")]
    assert [lay.type for lay in ffn] == [
        {"dense": "ffn", "sparse": "moe"}[t] for t in cfg["mlp_layer_types"]]
    assert ffn[0].geti("nhidden") == cfg["intermediate_size"] == 8192
    for m in ffn[1:]:
        assert (m.geti("nexpert"), m.geti("top_k"), m.geti("nhidden"),
                m.geti("nexpert_held"), m.geti("nshared_hidden")) == (
            cfg["published"]["num_experts"], cfg["num_experts_per_tok"],
            cfg["moe_intermediate_size"], cfg["num_experts"],
            cfg["shared_expert_intermediate_size"])
        assert m.params["score"] == "sigmoid"
        assert m.getf("routed_scale") == cfg["moe_routed_scaling_factor"]
    assert {lay.getf("eps") for lay in layers if lay.type == "rmsnorm"} \
        == {cfg["rms_norm_eps"]}
    assert lm_inputs.vocab_of(layers) == cfg["vocab_size"] == 12544
    n = sum(int(np.prod(s)) for tags in
            laguna_inputs.weight_shapes(layers).values()
            for s in tags.values())
    assert n == pytest.approx(691.6e6, rel=2e-3)   # 12.45 GB at 18 B each
    assert glob["gain:wd"] == "0.0"


def test_the_conf_is_what_the_builder_writes():
    from cxxnet_tpu import models
    body = "\n".join(ln for ln in _cell_conf().splitlines()
                     if not ln.startswith("#"))
    assert body.strip() == (models.laguna_moe_netconfig(
        layer_types=models.LAGUNA_LAYER_TYPES[:5], n_held=32, vocab=12544)
        + models.LAGUNA_ADAMW).strip()


# the lists lfm2-ep8-train-8k did not join, and its own two; the eleven
# host-side and set-up ones, whose cell lists
# tests/benchmark/test_program_kept.py pins whole
NOT_JOINED = {"pool_bwd_time_share", "collective_time_share",
              "index_scores_roofline", "select_roofline",
              "index_select_time_share", "h2d_call_ms", "args_call_ms",
              "dispatch_call_ms", "host_share_of_step", "step_trace_s",
              "step_lower_s", "step_cache_load_s", "init_params_s",
              "moe_token_side_time_share", "emb_time_share",
              "cast_params_time_share", "shortconv_time_share",
              "shortconv_roofline"}


def test_the_cell_stands_in_each_list_after_the_accepted_cells():
    """Membership and order, never "stands last": a later PR appends
    behind this one. The cell joins every list ``lfm2-ep8-train-8k``
    joined but its conv mixers' two, each after that cell, and adds no
    metric of its own (PERF.md section 7: a metric that does not name
    ``lfm2-ep8-train-8k`` fails that cell's own test of the lists)."""
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        manifest = json.load(f)
    configs = [c["name"] for c in manifest["configs"]]
    cells = [c["name"] for c in manifest["workloads"]]
    assert configs.index(CONFIG) > configs.index("lfm2-24b-a2b-ep8-l5")
    assert cells.index(CELL) > cells.index("lfm2-ep8-train-8k")
    assert manifest["workloads"][cells.index(CELL)] == dict(
        manifest["workloads"][cells.index(CELL)], config=CONFIG,
        traffic="resident", chips=1)
    for m in manifest["per_layer"]:
        cells_of = m.get("workloads", cells)
        assert (CELL in cells_of) == (m["name"] not in NOT_JOINED), m["name"]
        if CELL in cells_of:
            assert cells_of.index(CELL) > cells_of.index(
                "lfm2-ep8-train-8k"), m["name"]


def test_the_window_glob_picks_the_window_cores_alone():
    """The layers' names let one glob, ``*w_att/core``, name the three
    window layers' core rows and no other (what a roofline of the window
    cores would read, PERF.md section 7); ``flash_roofline``'s and the
    gate's glob name all five."""
    import fnmatch
    layers, _ = netconf.parse(_cell_conf())
    att = [lay.name for lay in layers if lay.type == "attention"]
    assert att == ["b0_att", "b1w_att", "b2w_att", "b3w_att", "b4_att"]
    rows = ["%s/%s" % (n, s) for n in att for s in ("qkv", "core", "gate",
                                                    "out")]
    pick = lambda g: [r for r in rows if fnmatch.fnmatchcase(r, g)]  # noqa
    assert pick("*w_att/core") == ["b1w_att/core", "b2w_att/core",
                                   "b3w_att/core"]
    assert len(pick("*_att/core")) == len(pick("*_att/gate")) == 5


def test_the_limits_file_has_both_readings_for_every_number():
    with open(os.path.join(BENCH, "limits", CELL + ".json")) as f:
        limits = json.load(f)
    assert set(limits["limits"]) == set(LIMITS)
    for name, value in limits["limits"].items():
        assert value is None or 0 < value < 1, name
    for word in ("fp8", "half the tokens", "unwindowed", "YaRN left out",
                 "gate left out", "routed_scale 1"):
        assert word in limits["set_from"], word
