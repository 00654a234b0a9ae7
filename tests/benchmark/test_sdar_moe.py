"""The block-diffusion cell's benchmark parts on the CPU at a small size:
``references/sdar_moe.py``, ``programs/cxxnet_bdlm_trainer.py``,
``bd_inputs.py`` and the cell's entries in the manifest. The whole model
through ``Trainer.update`` against the reference, by the cell's own
``run_cell``: sound, and with each fault a block-diffusion step can have
planted in the program. The layers one by one against the reference's
functions are ``tests/test_blockdiff.py``."""

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

from benchmark import bd_inputs, compare, lm_inputs, netconf  # noqa: E402
from benchmark import run as bench_run  # noqa: E402
from benchmark.programs import cxxnet_bdlm_trainer  # noqa: E402
from benchmark.references import sdar_moe  # noqa: E402

BENCH = os.path.join(ROOT, "benchmark")
CELL = "sdar-ep8-train-8k"
L = 64
# float32 on both sides here: the limits a sound run has to meet are those
# of rounding, and everything a run can do wrong reads far above them
LIMITS = {"loss1": 1e-5, "loss2": 1e-5, "loss3": 1e-5,
          "grad_worst": 1e-3, "change_worst": 5e-3}
SEED = 2**31 + 5
SMALL = dict(vocab=96, dim=64, nhead=4, nkvhead=2, head_dim=16, nlayer=2,
             n_expert=16, top_k=4, expert_width=32, n_held=4,
             expert_offset=2, seq=L, block_len=4)


def _small_conf(**over):
    from cxxnet_tpu import models
    return models.sdar_moe_netconfig(**dict(SMALL, **over)) \
        + models.SDAR_MOE_ADAMW


def _small_spec(**over):
    spec = bench_run.resolve(CELL)
    spec["conf_text"] = _small_conf(**over)
    spec["cfg"] = dict(spec["cfg"], seq_len=L, batch_per_chip=2 * L,
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
    # 2 sequences x 128 rows x 4 choices, 4 of 16 experts held
    held = [r["run"]["gauges"]["moe.pairs_held/b%d_moe" % i]
            for i in range(2)]
    assert all(0 < n < 2 * 2 * L * 4 for n in held)


class _WeightFieldDropped(cxxnet_bdlm_trainer.Program):
    """The loss's own fault: every position of the noised copy weighs one,
    masked or not (a masked-token loss without its weights)."""

    def __init__(self, *a, **k):
        import jax.numpy as jnp
        super().__init__(*a, **k)
        for b in self.batches:
            half = b.label.shape[1] // 2
            b.label = jnp.concatenate(
                [b.label[:, :half], jnp.ones_like(b.label[:, half:])], 1)


class _Patched(cxxnet_bdlm_trainer.Program):
    """A fault planted under the layer for the life of the program."""
    module = name = None

    @staticmethod
    def fault(sound):
        raise NotImplementedError

    def __init__(self, *a, **k):
        import importlib
        self._mod = importlib.import_module(self.module)
        owner, _, attr = self.name.rpartition(".")
        self._owner = getattr(self._mod, owner) if owner else self._mod
        self._attr, self._sound = attr, getattr(self._owner, attr)
        setattr(self._owner, attr, type(self).fault(self._sound))
        try:
            super().__init__(*a, **k)
        except Exception:
            setattr(self._owner, attr, self._sound)
            raise

    def release(self):
        setattr(self._owner, self._attr, self._sound)
        super().release()


class _CausalMask(_Patched):
    """The mask's own fault: plain causal over the 2 L rows in place of the
    block-diffusion mask (the rotation still wraps)."""
    module, name = "cxxnet_tpu.parallel.ring", "block_diffusion_keep"

    @staticmethod
    def fault(sound):
        import jax.numpy as jnp
        return lambda rows, block_len: jnp.tril(jnp.ones((rows, rows), bool))


class _PositionsDoNotWrap(_Patched):
    """The rows' own fault: row r rotated at position r, so that the clean
    copy stands L positions after the noised one."""
    module, name = "cxxnet_tpu.layer.layers", "AttentionLayer._apply_rope"

    @staticmethod
    def fault(sound):
        def rope(self, x, offset=0):
            mask, self.attn_mask = self.attn_mask, "causal"
            try:
                return sound(self, x, offset)
            finally:
                self.attn_mask = mask
        return rope


@pytest.mark.parametrize("factory,tripped", [
    (_WeightFieldDropped, "loss1"), (_CausalMask, "grad_worst"),
    (_PositionsDoNotWrap, "grad_worst")],
    ids=["weight_field_dropped", "causal_mask", "positions_do_not_wrap"])
def test_each_planted_fault_is_not_correct(factory, tripped):
    r = _run(factory)
    assert r["correct"] is False
    assert r["compared"][tripped][0] > 10 * LIMITS[tripped], r["compared"]


@pytest.fixture(scope="module")
def sound():
    """The reference's own run of the small cell, which every control is
    read against."""
    spec = _small_spec()
    return spec, sdar_moe.for_config(spec["conf_text"], spec["cfg"],
                                     2 * L).run(991)


@pytest.mark.parametrize("how,tripped", [
    ({"precision": "fp8"}, "grad_worst"),
    ({"precision": "bf16"}, "grad_worst"),
    ({"rows_used": L}, "loss1"),
    ({"mask": "causal"}, "grad_worst"),
    ({"wrap": False}, "grad_worst")],
    ids=["fp8", "bf16", "half_the_tokens", "causal_mask", "no_wrap"])
def test_the_controls_are_not_correct(sound, how, tripped):
    """The reference in the program's place, computed in a precision below
    this float32 test configuration's, with half the masked tokens left out
    of the loss (calibrate.py's ``half_batch``), under a causal mask, or
    with positions that do not wrap, fails a limit."""
    spec, want = sound
    got = sdar_moe.for_config(spec["conf_text"], spec["cfg"], 2 * L,
                              **how).run(991)
    rows = {r["name"]: r for r in compare.judge(compare.numbers(got, want),
                                                LIMITS)}
    assert not rows[tripped]["ok"], rows
    assert all(r["ok"] for r in compare.judge(compare.numbers(want, want),
                                              LIMITS))
    assert set(want["pairs_held"]) == {"b0_moe", "b1_moe"}
    assert all(len(v) == 3 for v in want["pairs_held"].values())


def test_the_same_seed_gives_the_same_batch_zipf_and_70_percent_masked():
    import jax
    from benchmark.inputs import seed_key
    cfg = bench_run.resolve(CELL)["cfg"]
    key = seed_key(2**31 + 9)
    make = jax.jit(lambda k: bd_inputs.make_batch(k, 0, 2, 4096, 18992, cfg))
    data, label = make(key)
    again, _ = bd_inputs.make_batch(key, 0, 2, 4096, 18992, cfg)
    np.testing.assert_array_equal(np.asarray(data), np.asarray(again))
    ids = np.asarray(data).reshape(2, 8192)
    xt, x0 = ids[:, :4096], ids[:, 4096:]
    weight = np.asarray(label)[:, 4096:]
    np.testing.assert_array_equal(np.asarray(label)[:, :4096], x0)
    # x_0 never holds the mask's id; Zipf (s = 1) over 18,991 ids: id 0 has
    # 1 / H(18991) = 9.6%
    assert x0.min() >= 0 and x0.max() < 18991 and x0.max() > 5000
    assert 0.08 < (x0 == 0).mean() < 0.112
    masked = xt == 18991
    np.testing.assert_array_equal(masked, weight > 0)
    np.testing.assert_array_equal(xt[~masked], x0[~masked])
    assert 0.67 < masked.mean() < 0.73        # the mean of t is 0.7
    assert 1 / 0.95 - 1e-6 <= weight[masked].min() \
        and weight[masked].max() <= 1 / 0.45 + 1e-6
    other, _ = bd_inputs.make_batch(key, 1, 2, 4096, 18992, cfg)
    assert (np.asarray(other) != np.asarray(data)).mean() > 0.3


def test_every_seed_gives_the_same_model_in_another_order():
    """``--seed`` reorders the model's hidden units and nothing else: the
    same ids give the same logits (to rounding), and the heads' norm gains,
    whose axis is the head's, are not reordered."""
    import jax
    from benchmark.inputs import seed_key
    layers, glob = netconf.parse(_small_conf())
    make = bd_inputs.params_from_seed(layers, glob,
                                      {"weights_base_seed": 77})
    a, b = make(seed_key(1)), make(seed_key(2**31 + 5))
    ids = jax.random.randint(jax.random.PRNGKey(3), (2 * L,), 0, 96)
    logits = [np.asarray(sdar_moe.forward(layers, "highest", p, ids)[0])
              for p in (a, b)]
    assert logits[0].shape == (L, 96)         # the noised copy's rows alone
    np.testing.assert_allclose(logits[0], logits[1], rtol=0, atol=2e-5)
    assert (np.asarray(a["emb"]["wmat"]) != np.asarray(
        b["emb"]["wmat"])).mean() > 0.9
    names = [(n, t) for _, n, t, _ in bd_inputs.leaves_of(layers)]
    assert ("b0_att", "qnorm") in names and ("b1_att", "knorm") in names
    assert [t for n, t in names if n == "b0_att"] == ["wmat", "wo", "qnorm",
                                                      "knorm"]
    for tag in bd_inputs.HEAD_NORMS:
        assert a["b0_att"][tag].shape == (16,)
        np.testing.assert_array_equal(np.asarray(a["b0_att"][tag]),
                                      np.asarray(b["b0_att"][tag]))


def _cell_conf():
    with open(os.path.join(BENCH, "configs", "sdar-30b-a3b-ep8-l4.conf")) as f:
        return f.read()


def test_the_flops_agree_with_a_hand_count():
    conf, cfg = _cell_conf(), bench_run.resolve(CELL)["cfg"]
    by_part = {}
    for _, part, m in sdar_moe.forward_macs(conf, 8192):
        by_part[part] = by_part.get(part, 0) + m
    d, q, kv, f, rows = 2048, 32 * 128, 4 * 128, 768, 16384
    assert by_part["qkv"] + by_part["out"] == 4 * rows * (
        d * (q + 2 * kv) + q * d)
    kept = 8192 * 8192 + 8192 * 4
    assert sdar_moe.kept_scores(8192, 4) == kept
    assert by_part["core"] == 4 * 2 * q * kept
    assert by_part["route"] == 4 * rows * d * 128
    assert by_part["experts"] == 4 * rows * (8 * 16 / 128) * 3 * d * f
    assert by_part["head"] == 8192 * d * 18992
    flops = sdar_moe.train_flops_per_item(conf, cfg)
    assert flops * 8192 == 6 * sum(by_part.values())
    # ISSUE 36: a step's model work is ~24.5 TFLOP, attention ~54% of it
    assert flops * 8192 == pytest.approx(24.5e12, rel=0.01)
    assert 6 * by_part["core"] / (flops * 8192) == pytest.approx(0.54,
                                                                 abs=0.01)
    flash = sdar_moe.kernel_work(conf, cfg, "flash_attention", {})
    assert flash["flops"] == 6 * by_part["core"]
    assert flash["bytes"] == 4 * 2 * 2 * rows * 128 * (2 * 32 + 2 * 4)
    even = sdar_moe.kernel_work(conf, cfg, "expert_product", {})
    assert even["pairs_a_step"] == 4 * 16384
    assert even["flops"] == 6 * by_part["experts"]
    counted = {"want": {"pairs_held": {
        "b%d_moe" % i: [10000, 20000, 30000] for i in range(4)}}}
    read = sdar_moe.kernel_work(conf, cfg, "expert_product", counted)
    assert read["pairs_a_step"] == 4 * 15000      # the two batches' mean
    assert read["flops"] == pytest.approx(even["flops"] * 15000 / 16384)
    assert sdar_moe.kernel_work(conf, cfg, "conv", {}) is None


def test_the_configuration_keeps_the_published_widths():
    spec = bench_run.resolve(CELL)
    cfg = spec["cfg"]
    row = None
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    if os.path.exists(catalog):
        with open(catalog) as f:
            row = next(r for r in map(json.loads, f)
                       if r["name"] == "SDAR-30B-A3B-Chat")
        assert cfg["source"] == row["source_url"]
    published = row["config"] if row else dict(cfg, **cfg["published"])
    assert set(cfg["reduced"]) == {"num_hidden_layers", "num_experts",
                                   "vocab_size"}
    for key, val in published.items():
        if key in cfg["reduced"]:
            assert cfg["published"][key] == val and cfg[key] < val
        else:
            assert cfg[key] == val, key
    for key in ("block_len", "noise", "mask", "qk_norm", "weights",
                "optimizer", "mask_id", "remat"):
        assert cfg["assumed"][key]
    assert "8 chips" in cfg["deployment"]
    # and the conf text that is run says the same
    layers, glob = netconf.parse(spec["conf_text"])
    att = [lay for lay in layers if lay.type == "attention"]
    moe = [lay for lay in layers if lay.type == "moe"]
    assert len(att) == len(moe) == cfg["num_hidden_layers"] == 4
    for a in att:
        assert (a.geti("nhead"), a.geti("nkvhead"), a.geti("head_dim")) == (
            cfg["num_attention_heads"], cfg["num_key_value_heads"],
            cfg["head_dim"])
        assert a.getf("rope_base") == cfg["rope_theta"]
        assert a.geti("qk_norm") == 1 and cfg["rms_norm_eps"] == 1e-6
        assert a.geti("block_len") == cfg["block_len"] == 4
    for m in moe:
        assert (m.geti("nexpert"), m.geti("top_k"), m.geti("nhidden"),
                m.geti("nexpert_held")) == (
            cfg["published"]["num_experts"], cfg["num_experts_per_tok"],
            cfg["moe_intermediate_size"], cfg["num_experts"])
    assert lm_inputs.vocab_of(layers) == cfg["vocab_size"] == 18992
    n = sum(int(np.prod(s)) for tags in
            bd_inputs.weight_shapes(layers).values() for s in tags.values())
    assert n == pytest.approx(456.3e6, rel=2e-3)          # ISSUE 36's count
    assert next(lay for lay in layers
                if lay.name == "norm_f").geti("seq_rows") == cfg["seq_len"]


def test_the_conf_is_what_the_builder_writes():
    from cxxnet_tpu import models
    body = "\n".join(ln for ln in _cell_conf().splitlines()
                     if not ln.startswith("#"))
    assert body.strip() == (models.sdar_moe_netconfig(
        nlayer=4, n_held=16, vocab=18992, seq=8192, block_len=4)
        + models.SDAR_MOE_ADAMW).strip()


def test_the_cell_joins_the_lists_at_their_ends():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        manifest = json.load(f)
    assert manifest["configs"][-1]["name"] == "sdar-30b-a3b-ep8-l4"
    assert manifest["workloads"][-1] == dict(
        manifest["workloads"][-1], name=CELL, config="sdar-30b-a3b-ep8-l4",
        traffic="resident", chips=1)
    by_name = {m["name"]: m for m in manifest["per_layer"]}
    joined = [n for n, m in by_name.items() if CELL in m["workloads"]]
    assert all(by_name[n]["workloads"][-1] == CELL for n in joined)
    assert set(joined) == {
        "compile_cache_misses", "update_call_ms", "step_mfu_share",
        "matmul_time_share", "device_idle_share", "init_model_s",
        "step_build_s", "flash_roofline", "expert_product_roofline",
        "other_time_share", "loop_time_share", "copy_time_share",
        "moe_dense_layers", "forward_time_share", "backward_time_share",
        "update_time_share", "attn_dense_layers"}
    assert manifest["per_layer"][-1]["name"] == "attn_dense_layers"
    with open(os.path.join(BENCH, "metrics", "attn_dense_layers.json")) as f:
        desc = json.load(f)
    assert desc["reader"] == "program_count" and desc["args"] == {
        "name": "attn.dense", "of": ["attn.flash", "attn.dense"]}
    with open(os.path.join(BENCH, "limits", CELL + ".json")) as f:
        limits = json.load(f)
    assert set(limits["limits"]) == set(LIMITS)
    for name, value in limits["limits"].items():
        assert value is not None, name       # every number has both readings
