"""The learned-sparse-attention cell's benchmark parts on the CPU at a small
size: ``references/keye_dsa.py``, ``programs/cxxnet_dsa_trainer.py``,
``dsa_inputs.py`` and the cell's entries in the manifest. The whole model
through ``Trainer.update`` against the reference, by the cell's own
``run_cell``: sound, and with the faults a step under a learned selection
can have planted in the program; the reference's controls and its own
planted faults in the program's place. The layers one by one against the
reference's functions are ``tests/test_dsa.py``."""

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

from benchmark import compare, dsa_inputs, lm_inputs, netconf  # noqa: E402
from benchmark import run as bench_run  # noqa: E402
from benchmark.programs import cxxnet_dsa_trainer  # noqa: E402
from benchmark.references import keye_dsa  # noqa: E402

BENCH = os.path.join(ROOT, "benchmark")
CELL, CONFIG = "keye-ep8-train-8k", "keye-30b-a3b-ep8-l4"
L = 64
# float32 on both sides here: the limits a sound run has to meet are those
# of rounding, and everything a run can do wrong reads far above them
LIMITS = {"loss1": 1e-5, "loss2": 1e-5, "loss3": 1e-5,
          "grad_worst": 1e-3, "change_worst": 5e-3}
SEED = 2**31 + 7
SMALL = dict(vocab=96, dim=64, nhead=4, nkvhead=2, head_dim=16, nlayer=2,
             n_expert=8, top_k=2, expert_width=32, n_held=4,
             expert_offset=2, index_heads=2, index_dim=8, index_topk=8)
# a step size at which three steps move every leaf past float32's rounding
ADAMW = "0.001"


def _small_conf(**over):
    from cxxnet_tpu import models
    return models.keye_dsa_netconfig(**dict(SMALL, **over)) \
        + models.KEYE_DSA_ADAMW.replace("0.0000003", ADAMW)


def _small_spec(**over):
    spec = bench_run.resolve(CELL)
    spec["conf_text"] = _small_conf(**over)
    spec["cfg"] = dict(spec["cfg"], seq_len=L, batch_per_chip=2 * L,
                       weights_base_seed=5,
                       extra_cfg="eval_train = 0\nhealth_monitor = 1\n")
    spec["traffic"] = dict(spec["traffic"], sync_every=2, warm_steps=1)
    spec["limits"] = dict(LIMITS)
    return spec


def _run(factory=None, spec=None):
    log = io.StringIO()
    return bench_run.run_cell(spec or _small_spec(), seed=SEED, seconds=0.2,
                              trace=False, require_tpu=False,
                              program_factory=factory, log=log,
                              compile_cache=False)


def test_a_sound_run_of_the_cell_is_correct():
    r = _run()
    assert r["correct"] is True, r["compared"]
    assert set(r["metrics"]) == {"train_items_per_s_per_chip", "setup_s"}
    assert r["attempted"] > 0 and r["failed"] == 0
    assert r["compared"]["window_compiles"] == [0.0, 0.0]
    gauges = r["run"]["gauges"]
    for i in range(2):
        # a sequence's selected pairs, the static count, in the last step
        assert gauges["dsa.selected/b%d_att" % i] == keye_dsa.kept_scores(
            L, 8) == 8 * 9 / 2 + (L - 8) * 8
        assert 0.0 < gauges["dsa.index_loss/b%d_att" % i] < 3.0
        assert 0 < gauges["moe.pairs_held/b%d_moe" % i] < 2 * L * 2


class _Patched(cxxnet_dsa_trainer.Program):
    """A fault planted under the layer for the life of the program."""
    module = name = None

    @staticmethod
    def fault(sound):
        raise NotImplementedError

    def __init__(self, *a, **k):
        import importlib
        self._mod = importlib.import_module(self.module)
        self._sound = getattr(self._mod, self.name)
        setattr(self._mod, self.name, type(self).fault(self._sound))
        try:
            super().__init__(*a, **k)
        except Exception:
            setattr(self._mod, self.name, self._sound)
            raise

    def release(self):
        setattr(self._mod, self.name, self._sound)
        super().release()


class _SelectionLeftOut(_Patched):
    """Every query keeps every key at or before it: plain causal."""
    module, name = "cxxnet_tpu.ops.dsa", "select"

    @staticmethod
    def fault(sound):
        return lambda scores, topk: sound(scores, scores.shape[-1])


class _HalfTheKeys(_Patched):
    """The selection at half its published number of keys."""
    module, name = "cxxnet_tpu.ops.dsa", "select"

    @staticmethod
    def fault(sound):
        return lambda scores, topk: sound(scores, topk // 2)


class _IndexLossLeftOut(_Patched):
    """The indexer's loss reads nought and teaches nothing."""
    module, name = "cxxnet_tpu.ops.dsa", "index_loss"

    @staticmethod
    def fault(sound):
        return lambda scores, sel, p: 0.0 * sound(scores, sel, p)


class _TiesToTheHigherIndex(_Patched):
    """A threshold lowering's own fault: a selection that keeps every key
    tied with the last place (more keys than ``topk`` a query)."""
    module, name = "cxxnet_tpu.ops.dsa", "select"

    @staticmethod
    def fault(sound):
        def select(scores, topk):
            import jax.numpy as jnp
            from jax import lax
            rows = scores.shape[-1]
            causal = jnp.tril(jnp.ones((rows, rows), bool))
            # scores cut to a few levels, so that ties are many
            coarse = jnp.round(scores * 4.0) / 4.0
            masked = jnp.where(causal, coarse, -jnp.inf)
            thr = lax.top_k(masked, min(topk, rows))[0][..., -1:]
            return (causal & (masked >= thr)).astype(jnp.int8)
        return select


@pytest.mark.parametrize("factory,tripped", [
    (_SelectionLeftOut, "grad_worst"), (_HalfTheKeys, "grad_worst"),
    (_IndexLossLeftOut, "loss1"), (_TiesToTheHigherIndex, "grad_worst")],
    ids=["selection_left_out", "half_the_keys", "index_loss_left_out",
         "ties_kept"])
def test_each_fault_planted_in_the_program_is_not_correct(factory, tripped):
    r = _run(factory)
    assert r["correct"] is False
    assert r["compared"][tripped][0] > 10 * LIMITS[tripped], r["compared"]


@pytest.fixture(scope="module")
def sound():
    """The reference's own run of the small cell, which every control is
    read against."""
    spec = _small_spec()
    return spec, keye_dsa.for_config(spec["conf_text"], spec["cfg"],
                                     2 * L).run(991)


@pytest.mark.parametrize("how,tripped", [
    ({"precision": "fp8"}, "grad_worst"),
    ({"precision": "bf16"}, "grad_worst"),
    ({"rows_used": L}, "loss1"),
    ({"topk": 0}, "grad_worst"),
    ({"topk": 4}, "grad_worst"),
    ({"index_loss": False}, "loss1"),
    ({"detach": False}, "grad_worst")],
    ids=["fp8", "bf16", "half_the_tokens", "selection_left_out", "half_topk",
         "index_loss_left_out", "indexer_not_detached"])
def test_the_controls_and_planted_faults_are_not_correct(sound, how, tripped):
    """The reference in the program's place, computed in a precision below
    this float32 test configuration's, with half the tokens left out of the
    cross-entropy (calibrate.py's ``half_batch``), or with one of this
    model's own faults planted (the selection left out, half the keys a
    query, L_idx left out, the indexer reading the stream attached),
    fails a limit."""
    spec, want = sound
    got = keye_dsa.for_config(spec["conf_text"], spec["cfg"], 2 * L,
                              **how).run(991)
    rows = {r["name"]: r for r in compare.judge(compare.numbers(got, want),
                                                LIMITS)}
    assert not rows[tripped]["ok"], rows
    assert all(r["ok"] for r in compare.judge(compare.numbers(want, want),
                                              LIMITS))
    assert set(want["pairs_held"]) == {"b0_moe", "b1_moe"}
    assert set(want["selected"]) == set(want["index_loss"]) == {"b0_att",
                                                                "b1_att"}
    assert all(n == 2 * keye_dsa.kept_scores(L, 8)
               for v in want["selected"].values() for n in v)
    if how == {"detach": False}:
        # the same forward pass: only where L_idx's gradient goes differs
        assert rows["loss1"]["value"] < 1e-6


def test_every_seed_gives_the_same_model_in_another_order():
    """``--seed`` reorders the model's hidden units and nothing else: the
    same ids give the same logits and the same selections (to rounding),
    and the leaves with no model axis (the heads' norms, the indexer's
    LayerNorm) are not reordered."""
    import jax
    from benchmark.inputs import seed_key
    layers, glob = netconf.parse(_small_conf())
    make = dsa_inputs.params_from_seed(layers, glob,
                                       {"weights_base_seed": 77})
    a, b = make(seed_key(1)), make(seed_key(2**31 + 5))
    ids = jax.random.randint(jax.random.PRNGKey(3), (L,), 0, 96)
    out = [keye_dsa.forward(layers, "highest", p, ids) for p in (a, b)]
    assert out[0][0].shape == (L, 96)
    np.testing.assert_allclose(np.asarray(out[0][0]), np.asarray(out[1][0]),
                               rtol=0, atol=2e-5)
    for name in ("b0_att", "b1_att"):
        np.testing.assert_allclose(out[0][1]["index_loss"][name],
                                   out[1][1]["index_loss"][name], rtol=1e-4)
    assert (np.asarray(a["emb"]["wmat"]) != np.asarray(
        b["emb"]["wmat"])).mean() > 0.9
    assert (np.asarray(a["b0_att"]["widx_q"]) != np.asarray(
        b["b0_att"]["widx_q"])).mean() > 0.9
    names = [t for _, n, t, _ in dsa_inputs.leaves_of(layers)
             if n == "b0_att"]
    assert names == ["wmat", "wo", "qnorm", "knorm", "widx_q", "widx_k",
                     "widx_w", "idx_gain", "idx_bias"]
    for tag in dsa_inputs.NO_MODEL_AXIS:
        np.testing.assert_array_equal(np.asarray(a["b0_att"][tag]),
                                      np.asarray(b["b0_att"][tag]))
    assert not np.asarray(a["b0_att"]["idx_bias"]).any()


def _cell_conf():
    with open(os.path.join(BENCH, "configs", CONFIG + ".conf")) as f:
        return f.read()


def test_the_flops_and_each_kernels_work_agree_with_hand_counts():
    conf, cfg = _cell_conf(), bench_run.resolve(CELL)["cfg"]
    by_part = {}
    for _, part, m in keye_dsa.forward_macs(conf, 8192):
        by_part[part] = by_part.get(part, 0) + m
    d, q, kv, f, rows = 2048, 32 * 128, 4 * 128, 768, 8192
    kept = 2048 * 2049 // 2 + 6144 * 2048
    tri = 8192 * 8193 // 2
    assert keye_dsa.kept_scores(8192, 2048) == kept == 14681088
    assert tri == 33558528
    assert by_part["qkv"] + by_part["out"] == 4 * rows * (
        d * (q + 2 * kv) + q * d)
    assert by_part["core"] == 4 * 2 * q * kept
    assert by_part["index_proj"] == 4 * rows * d * (1024 + 64 + 16)
    assert by_part["index_scores"] == 4 * 16 * 64 * tri
    assert by_part["route"] == 4 * rows * d * 128
    assert by_part["experts"] == 4 * rows * (8 * 16 / 128) * 3 * d * f
    assert by_part["head"] == rows * d * 18992
    flops = keye_dsa.train_flops_per_item(conf, cfg) * 8192
    model = 6 * (sum(by_part.values()) - by_part["index_proj"]
                 - by_part["index_scores"])
    indexer = 2 * (2 * by_part["index_proj"] + by_part["index_scores"]
                   + 4 * 2 * 16 * 64 * kept)
    assert flops == pytest.approx(model + indexer, rel=1e-12)
    # ISSUE 38: a step's model work is about 10 TFLOP, the core 2.9 of it
    assert flops == pytest.approx(10.3e12, rel=0.01)
    assert 6 * by_part["core"] == pytest.approx(2.9e12, rel=0.01)
    flash = keye_dsa.kernel_work(conf, cfg, "flash_attention", {})
    assert flash["flops"] == 6 * by_part["core"]
    assert flash["bytes"] == 4 * 2 * 2 * rows * 128 * (2 * 32 + 2 * 4)
    index = keye_dsa.kernel_work(conf, cfg, "index_scores", {})
    assert index["flops"] == indexer
    assert index["bytes"] == 4 * 2 * rows * (d + 2 * (1024 + 64 + 16))
    select = keye_dsa.kernel_work(conf, cfg, "select", {})
    assert select == {"flops": 0.0, "bytes": 4 * tri * (4 + 1 / 8)}
    even = keye_dsa.kernel_work(conf, cfg, "expert_product", {})
    assert even["pairs_a_step"] == 4 * 8192
    assert even["flops"] == 6 * by_part["experts"]
    counted = {"want": {"pairs_held": {
        "b%d_moe" % i: [5000.0, 7000.0, 9000.0] for i in range(4)}}}
    read = keye_dsa.kernel_work(conf, cfg, "expert_product", counted)
    assert read["pairs_a_step"] == 4 * 6000       # the two batches' mean
    assert read["flops"] == pytest.approx(even["flops"] * 6000 / 8192)
    assert keye_dsa.kernel_work(conf, cfg, "conv", {}) is None


def test_the_configuration_keeps_the_published_widths():
    spec = bench_run.resolve(CELL)
    cfg = spec["cfg"]
    row = None
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    if os.path.exists(catalog):
        with open(catalog) as f:
            row = next(r for r in map(json.loads, f)
                       if r["name"] == "Keye-VL-2.0-30B-A3B")
        assert cfg["source"] == row["source_url"]
    published = row["config"] if row else dict(cfg, **cfg["published"])
    assert set(cfg["reduced"]) == {"num_hidden_layers", "num_experts",
                                   "num_local_experts", "vocab_size"}
    for key, val in published.items():
        if key in cfg["reduced"]:
            assert cfg["published"][key] == val and cfg[key] < val
        else:
            assert cfg[key] == val, key
    for key in ("training_stage", "index_scores", "qk_norm", "indexer_query",
                "indexer_key", "indexer_weights", "hadamard", "chunk_sizes",
                "index_loss_weight", "mrope_section", "vision_tower",
                "weights", "optimizer", "remat"):
        assert cfg["assumed"][key]
    assert ("If Keye's own code selects by chunk and not by token this is "
            "a departure") in cfg["assumed"]["chunk_sizes"]
    assert "8 chips" in cfg["deployment"]
    # and the conf text that is run says the same
    layers, glob = netconf.parse(spec["conf_text"])
    att = [lay for lay in layers if lay.type == "attention"]
    moe = [lay for lay in layers if lay.type == "moe"]
    assert len(att) == len(moe) == cfg["num_hidden_layers"] == 4
    sa = cfg["sa_config"]
    for a in att:
        assert (a.geti("nhead"), a.geti("nkvhead"), a.geti("head_dim")) == (
            cfg["num_attention_heads"], cfg["num_key_value_heads"],
            cfg["head_dim"])
        assert a.getf("rope_base") == cfg["rope_theta"] == 1e7
        assert a.geti("qk_norm") == 1 and cfg["rms_norm_eps"] == 1e-6
        assert a.params["attn_mask"] == "dsa" and a.geti("causal") == 1
        assert (a.geti("index_heads"), a.geti("index_dim"),
                a.geti("index_topk")) == (
            sa["indexer_num_heads"], sa["indexer_head_dim"], sa["topk"])
        assert sa["indexer_num_kv_heads"] == 1
    for m in moe:
        assert (m.geti("nexpert"), m.geti("top_k"), m.geti("nhidden"),
                m.geti("nexpert_held")) == (
            cfg["published"]["num_experts"], cfg["num_experts_per_tok"],
            cfg["moe_intermediate_size"], cfg["num_experts"])
    assert lm_inputs.vocab_of(layers) == cfg["vocab_size"] == 18992
    n = sum(int(np.prod(s)) for tags in
            dsa_inputs.weight_shapes(layers).values() for s in tags.values())
    assert n == pytest.approx(465.4e6, rel=2e-3)          # ISSUE 38's count
    for tag in ("idx_gain", "idx_bias", "qnorm", "knorm", "gain"):
        assert glob[tag + ":wd"] == "0.0"


def test_the_conf_is_what_the_builder_writes():
    from cxxnet_tpu import models
    body = "\n".join(ln for ln in _cell_conf().splitlines()
                     if not ln.startswith("#"))
    assert body.strip() == (models.keye_dsa_netconfig(
        nlayer=4, n_held=16, vocab=18992) + models.KEYE_DSA_ADAMW).strip()


JOINED = {
    "compile_cache_misses", "update_call_ms", "step_mfu_share",
    "matmul_time_share", "device_idle_share", "init_model_s",
    "step_build_s", "flash_roofline", "expert_product_roofline",
    "other_time_share", "loop_time_share", "copy_time_share",
    "moe_dense_layers", "forward_time_share", "backward_time_share",
    "update_time_share", "attn_dense_layers", "attn_qkv_time_share"}
OWN = {"index_scores_roofline": ("scope_roofline_share",
                                 {"scopes": ["*_att/index"],
                                  "work": "index_scores"}),
       "select_roofline": ("scope_roofline_share",
                           {"scopes": ["*_att/select"], "work": "select"}),
       "index_select_time_share": ("scope_time_share", {
           "scopes": ["*_att/index", "*_att/select", "*_att/index_loss"]})}


def test_the_cell_stands_in_each_list_after_the_accepted_cells():
    """Membership and order, never "stands last": a later PR appends
    behind this one."""
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        manifest = json.load(f)
    with open(os.path.join(ROOT, "tests", "benchmark",
                           "accepted.json")) as f:
        accepted = json.load(f)
    configs = [c["name"] for c in manifest["configs"]]
    cells = [c["name"] for c in manifest["workloads"]]
    assert configs.index(CONFIG) > configs.index("sdar-30b-a3b-ep8-l4")
    assert cells.index(CELL) > cells.index("sdar-ep8-train-8k")
    assert manifest["workloads"][cells.index(CELL)] == dict(
        manifest["workloads"][cells.index(CELL)], config=CONFIG,
        traffic="resident", chips=1)
    by_name = {m["name"]: m for m in manifest["per_layer"]}
    assert {n for n, m in by_name.items()
            if CELL in m["workloads"]} == JOINED | set(OWN)
    for name in JOINED:
        cells_of = by_name[name]["workloads"]
        before = cells_of[:cells_of.index(CELL)]
        assert before[-1] == "sdar-ep8-train-8k", name
        # whatever the accepted benchmark lists for the metric comes first
        was = accepted.get("per_layer_workloads", {}).get(name)
        if was:
            assert before[:len(was)] == was, name
    names = [m["name"] for m in manifest["per_layer"]]
    for name, (reader, args) in OWN.items():
        assert names.index(name) > names.index("attn_qkv_time_share")
        entry = by_name[name]
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
        assert value is not None and 0 < value < 1, name
    for word in ("fp8", "selection left out", "L_idx left out", "topk 1024",
                 "not detached", "half the tokens"):
        assert word in limits["set_from"], word
