"""SmallThinker's block through the normal path (conf text ->
``Trainer.update``), at a small size on the CPU: d 64, 4 query / 2 key-value
heads of 16, 8 experts top-2 of width 32, vocabulary 96, L 32, window 8,
pattern [0, 1, 1, 1]. Against the benchmark's plain reference
(``benchmark/references/moe_lm.py``) on seeded weights, float32. A share of
the experts bounds its sorted side only from 512 rows up (the grouped
product's row tile), so what reads the bound runs at L 512 (``LONG``)."""

import os
import sys
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark import compare, lm_inputs, netconf  # noqa: E402
from benchmark.inputs import seed_key  # noqa: E402
from benchmark.programs import cxxnet_lm_trainer  # noqa: E402
from benchmark.references import moe_lm  # noqa: E402
from benchmark.windows import resident as window  # noqa: E402
from cxxnet_tpu import models, ops  # noqa: E402
from cxxnet_tpu.layer.base import ApplyContext  # noqa: E402
from cxxnet_tpu.layer.layers import AttentionLayer, MoELayer  # noqa: E402
from cxxnet_tpu.utils import telemetry  # noqa: E402

D, L, VOCAB, NEXP, WIDTH = 64, 32, 96, 8, 32
SMALL = dict(vocab=VOCAB, dim=D, nhead=4, nkvhead=2, head_dim=16, nlayer=4,
             n_expert=NEXP, top_k=2, expert_width=WIDTH, window=8)
# the model of base seed 0, stated: ``lm_inputs.params_from_seed`` gives a
# ``cfg`` without the key the same one (one path for the weights)
CFG = {"seq_len": L, "batch_per_chip": 2 * L, "weights_base_seed": 0,
       "extra_cfg": "eval_train = 0\nhealth_monitor = 1\n"}
SEED = 2**31 + 77
# two sequences of LONG tokens, top 2: 2,048 pairs; a share of 2 of the 8
# experts gets 3/2 * 2,048 * 2/8 = 768 rows, in whole tiles of 512: 1,024
LONG, LONG_PAIRS, LONG_ROWS = 512, 2048, 1024


def _conf(**over):
    return models.smallthinker_netconfig(**dict(SMALL, **over)) \
        + models.SMALLTHINKER_ADAMW


def _paths_since(before):
    return {k: n - before.get(k, 0) for k, n in telemetry.paths().items()
            if n != before.get(k, 0)}


@pytest.fixture(scope="module")
def trained():
    """The uncut small model, three steps of the program and of the
    reference from the same seed."""
    conf = _conf()
    ref = moe_lm.for_config(conf, CFG, 2 * L)
    # the path account is the process's: count from what tests before left
    before = telemetry.paths()
    program = cxxnet_lm_trainer.Program(conf, CFG, 1, SEED, {})
    probs = _probabilities(program)
    got = window.first_steps(program, ref.hyper, 3)
    return types.SimpleNamespace(
        conf=conf, ref=ref, program=program, probs=probs, got=got,
        want=ref.run(SEED, 3), paths=_paths_since(before), gauges={})


@pytest.fixture(scope="module")
def share():
    """One chip's share of the same model (experts 2-3 of 8 in each layer)
    at L ``LONG``, where its sorted side is bounded; one step, traced with
    telemetry on so that the gauges written at trace time are kept."""
    conf = _conf(n_held=2, expert_offset=2)
    cfg = dict(CFG, seq_len=LONG, batch_per_chip=2 * LONG)
    before = telemetry.paths()
    telemetry.enable()
    try:
        program = cxxnet_lm_trainer.Program(conf, cfg, 1, SEED, {})
        program.step()
        program.sync()
        gauges = telemetry.summary()["gauges"]
    finally:
        telemetry.disable()
        telemetry.reset()
    return types.SimpleNamespace(program=program, gauges=gauges,
                                 paths=_paths_since(before))


def _probabilities(program):
    tr = program.trainer
    values, _ = tr.net.forward(tr.params, program.batches[0].data)
    return np.asarray(values[-1])            # (rows, vocab, 1, L)


def test_logits_agree_with_the_reference(trained):
    conf, ref, program, before = (trained.conf, trained.ref,
                                  trained.program, trained.probs)
    layers, _ = netconf.parse(conf)
    params = jax.jit(ref._weights)(seed_key(SEED))
    ids = program.batches[0].data.reshape(2, L).astype(jnp.int32)
    want = jax.vmap(lambda row: moe_lm.logits_of(layers, "highest", params,
                                                 row))(ids)
    want = jax.nn.softmax(want, axis=-1)     # the loss node holds softmax
    # float32 on both sides, the same weights and tokens: what is left is
    # the order of float32 sums, on probabilities of about 1 / 96
    np.testing.assert_allclose(before[:, :, 0, :].transpose(0, 2, 1), want,
                               rtol=2e-5, atol=1e-8)


def test_loss_gradients_and_three_adamw_steps_agree(trained):
    got, want = trained.got, trained.want
    nums = compare.numbers(got, want)
    # the mean of 64 float32 cross-entropies, summed in another order
    assert max(nums[k]["value"] for k in ("loss1", "loss2", "loss3")) < 1e-6
    # every leaf's first gradient, read out of AdamW's m1 = (1 - beta1) g:
    # float32 sums in another order, and the division by (1 - beta1)
    assert set(got["grad_norm"]) == set(want["grad_norm"])
    for leaf, w in want["grad_norm"].items():
        assert got["grad_norm"][leaf] == pytest.approx(w, rel=2e-5), leaf
    # three steps of AdamW: m / sqrt(v) is about +-1 at the first step
    # whatever the gradient's size, so a gradient element of 1e-9 whose
    # sign float32 rounding flips moves by 2 eta; norms over a leaf of
    # thousands of elements agree far closer than any one element
    for leaf, w in want["change_norm"].items():
        assert got["change_norm"][leaf] == pytest.approx(w, rel=1e-4), leaf
    assert nums["grad_worst"]["value"] < 2e-5
    assert nums["change_worst"]["value"] < 1e-4


@pytest.mark.parametrize("which", ["trained", "share"])
def test_the_step_counts_its_paths_and_returns_the_routing(request, which):
    fx = request.getfixturevalue(which)
    program, paths, gauges = fx.program, fx.paths, fx.gauges
    assert paths.get("moe.sparse", 0) >= 4 and not paths.get("moe.dense")
    assert paths.get("attn.dense", 0) >= 4       # no flash kernel on a CPU
    tr = program.trainer
    health = np.asarray(tr.last_health)
    names = tr.health_gauge_names
    assert names == [n + "/b%d_moe" % i for i in range(4)
                     for n in ("moe.pairs_held", "moe.load_max")]
    extra = dict(zip(names, health[4:]))
    held = [extra["moe.pairs_held/b%d_moe" % i] for i in range(4)]
    if which == "trained":
        # every expert is held: all 2 * 64 pairs, some expert above the mean,
        # the sorted side whole and no second branch
        pairs = 2 * 2 * L
        assert held == [pairs] * 4 and "moe.bounded" not in paths
        assert all(pairs / NEXP <= extra["moe.load_max/b%d_moe" % i]
                   <= 2 * L for i in range(4))
    else:
        # 2 of 8 experts: about a quarter of the pairs, on a sorted side of
        # half the rows in each of the four layers, traced once each
        pairs = LONG_PAIRS
        assert paths["moe.bounded"] == paths["moe.sparse"] == 4
        assert gauges["moe.rows"] == LONG_ROWS
        assert gauges["moe.rows_full"] == pairs
        assert all(pairs / 8 < n < LONG_ROWS for n in held), held
    assert tr.health_gauge_limits == {
        "moe.pairs_held/b%d_moe" % i: ("moe.overflow/b%d_moe" % i,
                                       min(pairs, LONG_ROWS))
        for i in range(4)}


@pytest.mark.parametrize("which", ["trained", "share"])
def test_the_health_monitor_keeps_the_routing_as_gauges(request, which):
    from cxxnet_tpu.utils import health
    tr = request.getfixturevalue(which).program.trainer

    def kept(vectors):
        telemetry.enable()
        try:
            mon = health.HealthMonitor(
                gauge_names=lambda: tr.health_gauge_names,
                gauge_limits=lambda: tr.health_gauge_limits)
            # the check runs one step late: the vector before is judged
            # when the next arrives
            for i, vec in enumerate(vectors):
                assert mon.observe(0, i, vec) is None
            return telemetry.summary()["gauges"]
        finally:
            telemetry.disable()
            telemetry.reset()
    last = np.asarray(tr.last_health)
    gauges = kept([last, last])
    assert gauges["moe.pairs_held/b0_moe"] == last[4]
    assert gauges["moe.load_max/b3_moe"] == float(last[-1])
    # at rest the pairs held fit the rows the sorted side has
    assert [gauges["moe.overflow/b%d_moe" % i] for i in range(4)] == [0.0] * 4
    # a step whose layer 1 held one pair more than its rows says so (the
    # layer itself then takes all rows: the tests of the layer below)
    full = last.copy()
    full[4 + 2 * 1] = tr.health_gauge_limits["moe.pairs_held/b1_moe"][1] + 1
    gauges = kept([full, last])
    assert [gauges["moe.overflow/b%d_moe" % i] for i in range(4)] \
        == [0.0, 1.0, 0.0, 0.0]


def test_with_every_expert_held_the_step_lowers_as_before_the_bound(share):
    """The uncut model at ``LONG``: the sparse lowering has all its rows,
    no ``cond``, and the step's lowered text is the one this tree had before
    a share's sorted side was bounded (PR 33; the digest is of the parent
    commit's text, by ``tests/fixtures/lowered_step_text.stripped``). Who
    changes what the uncut layer lowers to on purpose records a new one."""
    import hashlib
    import importlib.util
    spec = importlib.util.spec_from_file_location(
        "lowered_step_text",
        os.path.join(ROOT, "tests", "fixtures", "lowered_step_text.py"))
    tool = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(tool)
    cfg = dict(CFG, seq_len=LONG, batch_per_chip=2 * LONG)
    program = cxxnet_lm_trainer.Program(_conf(), cfg, 1, SEED, {})
    text = tool.stripped(program.trainer.lower_update(program.batches[0]))
    assert "stablehlo.case" not in text
    assert hashlib.sha256(text.encode()).hexdigest()[:16] \
        == "f4ba3e2cc645d5bf"
    # and a share of it does branch: once forward, once backward, a layer
    program = share.program
    text = tool.stripped(program.trainer.lower_update(program.batches[0]))
    assert text.count("stablehlo.case") == 2 * 4


# ------------------------------------------------ the moe layer by itself
def _moe_layer(held=NEXP, offset=0, seq=L, **keys):
    lay = MoELayer()
    for k, v in dict({"nexpert": NEXP, "top_k": 2, "nhidden": WIDTH,
                      "expert_act": "reglu",
                      "nexpert_held": held, "expert_offset": offset},
                     **keys).items():
        lay.set_param(k, str(v))
    lay.infer_shape([(2, D, 1, seq)] * 2)
    return lay


def _moe_weights(seed=0):
    rng = np.random.RandomState(seed)
    return {"gate": rng.normal(0, 0.5, (NEXP, D)).astype(np.float32),
            "experts": rng.normal(0, 0.2, (NEXP, D, WIDTH)).astype(np.float32),
            "up": rng.normal(0, 0.2, (NEXP, D, WIDTH)).astype(np.float32),
            "down": rng.normal(0, 0.2, (NEXP, WIDTH, D)).astype(np.float32)}


def _share(w, lo, n):
    return dict(w, **{k: w[k][lo:lo + n] for k in ("experts", "up", "down")})


def _apply_moe(lay, w, u, x):
    ctx = ApplyContext(train=True)
    ctx.conn_index = 0
    y, = lay.apply(w, [jnp.asarray(u), jnp.asarray(x)], ctx)
    return np.asarray(y), np.asarray(ctx.layer_stats[0])


def _reference_moe(w, u, x, held=NEXP, offset=0):
    lay = netconf.Layer("moe", "m", ["u", "x"], ["y"], {
        "nexpert": str(NEXP), "top_k": "2", "expert_act": "reglu",
        "expert_offset": str(offset)})
    ww = _share(w, offset, held)
    ww = {"wmat": ww["experts"], "gate": ww["gate"], "up": ww["up"],
          "down": ww["down"]}
    seq = u.shape[-1]
    rows = [moe_lm._moe(lay, "highest", ww, u[i].reshape(D, seq).T,
                        x[i].reshape(D, seq).T) for i in range(u.shape[0])]
    return jnp.stack([r.T.reshape(D, 1, seq) for r in rows])


@pytest.mark.parametrize("seq, bounded", [(L, 0), (LONG, 4)])
def test_the_four_shares_add_up_to_the_uncut_layer(seq, bounded):
    """What ties one chip's share to the model: experts 0-1, 2-3, 4-5, 6-7
    each give their part of y, and the parts add up to the layer's y; at
    ``LONG`` each share does so from a sorted side of half the rows."""
    rng = np.random.RandomState(1)
    u, x = rng.randn(2, 2, D, 1, seq).astype(np.float32)
    w = _moe_weights()
    before = telemetry.paths()
    whole, stats = _apply_moe(_moe_layer(seq=seq), w, u, x)
    np.testing.assert_allclose(whole, _reference_moe(w, u, x), rtol=1e-4,
                               atol=1e-5)
    parts, pairs = [], 0
    for lo in range(0, NEXP, 2):
        y, st = _apply_moe(_moe_layer(held=2, offset=lo, seq=seq),
                           _share(w, lo, 2), u, x)
        np.testing.assert_allclose(
            y, _reference_moe(w, u, x, held=2, offset=lo), rtol=1e-4,
            atol=1e-5)
        parts.append(y)
        pairs += st[0]
    assert _paths_since(before).get("moe.bounded", 0) == bounded
    # float32 sums in another order
    np.testing.assert_allclose(sum(parts), whole, rtol=1e-4, atol=1e-5)
    assert pairs == stats[0] == 2 * 2 * seq      # every pair is held once


def _value_and_gradients(lay, w, u, x):
    """sum(sin(y)), y, the layer's two readings, and the gradients to the
    router, the three expert matrices and both inputs."""
    def f(w, u, x):
        ctx = ApplyContext(train=True)
        ctx.conn_index = 0
        y, = lay.apply(w, [u, x], ctx)
        return jnp.sum(jnp.sin(y)), (y, ctx.layer_stats[0])
    return jax.value_and_grad(f, argnums=(0, 1, 2), has_aux=True)(
        w, jnp.asarray(u), jnp.asarray(x))


@pytest.mark.parametrize("kernel", [False, True], ids=["ragged_dot", "gmm"])
@pytest.mark.parametrize("load", ["at_rest", "all_pairs_here"])
def test_a_share_under_its_bound_is_its_whole_sorted_side(load, kernel):
    """Experts 2-3 of 8 at ``LONG``: the sorted side has 1,024 of the 2,048
    rows. At rest about a quarter of the pairs are held and the first 1,024
    rows give what all rows give; with every token routed to experts 2 and 3
    all 2,048 pairs are held, twice the bound, and the layer takes all rows:
    none dropped, clipped or re-weighted, forward and every gradient."""
    rng = np.random.RandomState(5)
    u = rng.randn(2, D, 1, LONG).astype(np.float32)
    x = rng.randn(2, D, 1, LONG).astype(np.float32)
    w = _moe_weights()
    if load == "all_pairs_here":
        x = np.abs(x) + 1.0
        w["gate"] = np.zeros((NEXP, D), np.float32)
        w["gate"][2], w["gate"][3] = 0.2, 0.1
    mine = _share(w, 2, 2)
    bounded, whole = (_moe_layer(held=2, offset=2, seq=LONG)
                      for _ in range(2))
    whole._sorted_rows = lambda pairs: pairs     # the lowering of before
    before = telemetry.paths()
    ops.set_use_pallas(True if kernel else None)
    try:
        got = _value_and_gradients(bounded, mine, u, x)
        assert _paths_since(before) == {"moe.sparse": 1, "moe.bounded": 1}
        want = _value_and_gradients(whole, mine, u, x)
    finally:
        ops.set_use_pallas(None)
    assert _paths_since(before) == {"moe.sparse": 2, "moe.bounded": 1}
    stats = np.asarray(got[0][1][1]).tolist()
    if load == "at_rest":
        assert LONG_PAIRS / 8 < stats[0] < LONG_ROWS
    else:
        assert stats == [LONG_PAIRS, LONG_PAIRS // 2] and stats[0] > LONG_ROWS
    # the same rows through the same products: float32 sums in another
    # order at most (lax.ragged_dot's weight gradients on 1,024 and on
    # 2,048 rows)
    for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        np.testing.assert_allclose(a, b, rtol=2e-5, atol=2e-6)

    def reference(w, u, x):
        return jnp.sum(jnp.sin(_reference_moe(w, u, x, held=2, offset=2)))
    ref = jax.grad(reference, argnums=(0, 1, 2))(w, jnp.asarray(u),
                                                 jnp.asarray(x))
    np.testing.assert_allclose(got[0][1][0], _reference_moe(
        w, u, x, held=2, offset=2), rtol=1e-4, atol=1e-5)
    for key in ("gate", "experts", "up", "down"):
        r = ref[0][key] if key == "gate" else ref[0][key][2:4]
        np.testing.assert_allclose(got[1][0][key], r, rtol=1e-4, atol=1e-4)
    for a, b in zip(got[1][1:], ref[1:]):
        np.testing.assert_allclose(a, b, rtol=1e-4, atol=1e-4)


def test_all_tokens_to_one_pair_of_experts_and_none_is_dropped():
    rng = np.random.RandomState(2)
    u = rng.randn(2, D, 1, L).astype(np.float32)
    x = np.abs(rng.randn(2, D, 1, L)).astype(np.float32) + 1.0
    w = _moe_weights()
    w["gate"] = np.zeros((NEXP, D), np.float32)
    w["gate"][3], w["gate"][5] = 0.2, 0.1        # every token: experts 3, 5
    y, stats = _apply_moe(_moe_layer(), w, u, x)
    assert stats.tolist() == [2 * 2 * L, 2 * L]  # all pairs; one expert: all
    np.testing.assert_allclose(y, _reference_moe(w, u, x), rtol=1e-4,
                               atol=1e-5)
    # the share that holds neither expert adds nothing, and says so
    y0, stats0 = _apply_moe(_moe_layer(held=2, offset=0), _share(w, 0, 2),
                            u, x)
    assert stats0.tolist() == [0, 0] and not y0.any()


def test_the_kernel_path_agrees_with_the_plain_one():
    """The grouped product through the megablox kernel (interpreted here)
    against lax.ragged_dot, forward and both gradients, with rows left
    over that no group takes."""
    rng = np.random.RandomState(3)
    lhs = jnp.asarray(rng.randn(200, D), jnp.float32)
    rhs = jnp.asarray(rng.randn(NEXP, D, WIDTH), jnp.float32)
    sizes = jnp.array([5, 0, 70, 3, 0, 0, 10, 2], jnp.int32)

    def both(a, b):
        out = ops.grouped_matmul(a, b, sizes)
        return jnp.sum(jnp.sin(out)), out
    plain = jax.value_and_grad(both, argnums=(0, 1), has_aux=True)(lhs, rhs)
    ops.set_use_pallas(True)
    try:
        kernel = jax.value_and_grad(both, argnums=(0, 1), has_aux=True)(
            lhs, rhs)
    finally:
        ops.set_use_pallas(None)
    assert not np.asarray(kernel[0][1][90:]).any()      # exact zeros
    for a, b in zip(jax.tree.leaves(plain), jax.tree.leaves(kernel)):
        np.testing.assert_allclose(a, b, rtol=1e-4, atol=1e-4)


def test_sequence_input_on_an_expert_mesh_raises():
    import jax.sharding as shd
    lay = _moe_layer()
    mesh = shd.Mesh(np.array(jax.devices()[:2]), ("ep",))
    ctx = ApplyContext(train=True, mesh=mesh)
    with pytest.raises(ValueError, match="token exchange"):
        lay.apply(_moe_weights(), [jnp.zeros((2, D, 1, L))] * 2, ctx)


# ------------------------------------------ the attention layer by itself
def _attention(**keys):
    lay = AttentionLayer()
    for k, v in dict({"nhead": 4, "nkvhead": 2, "head_dim": 16,
                      "causal": 1}, **keys).items():
        lay.set_param(k, str(v))
    lay.infer_shape([(2, D, 1, L)])
    return lay


def _attention_weights(lay, seed=4):
    return {k: jnp.asarray(v) * 10
            for k, v in lay.init_params(np.random.RandomState(seed)).items()}


def _apply_attention(lay, w, x):
    y, = lay.apply(w, [jnp.asarray(x)], ApplyContext(train=True))
    return np.asarray(y)


def _reference_attention(w, x, **keys):
    lay = netconf.Layer("attention", "a", ["x"], ["y"], {
        k: str(v) for k, v in dict({"nhead": 4, "nkvhead": 2,
                                    "head_dim": 16, "causal": 1},
                                   **keys).items()})
    ww = {"wmat": w["wqkv"], "wo": w["wo"]}
    return np.stack([np.asarray(moe_lm._attention(
        lay, "highest", ww, x[i].reshape(D, L).T)).T.reshape(D, 1, L)
        for i in range(x.shape[0])])


@pytest.mark.parametrize("keys", [
    {}, {"rope": 1, "rope_base": 1500000}, {"attn_window": 8},
    {"rope": 1, "rope_base": 1500000, "attn_window": 8}],
    ids=["global_nope", "global_rope", "window_nope", "window_rope"])
def test_attention_with_a_head_size_of_its_own_agrees(keys):
    x = np.random.RandomState(5).randn(2, D, 1, L).astype(np.float32)
    lay = _attention(**keys)
    w = _attention_weights(lay)
    assert w["wqkv"].shape == (D, (4 + 2 * 2) * 16) and \
        w["wo"].shape == (4 * 16, D)
    np.testing.assert_allclose(_apply_attention(lay, w, x),
                               _reference_attention(w, x, **keys),
                               rtol=1e-4, atol=1e-5)


def test_a_window_of_the_whole_sequence_is_global_attention():
    x = np.random.RandomState(6).randn(2, D, 1, L).astype(np.float32)
    lay = _attention()
    w = _attention_weights(lay)
    want = _apply_attention(lay, w, x)
    for win in (L, 4 * L):
        np.testing.assert_array_equal(
            _apply_attention(_attention(attn_window=win), w, x), want)
    assert np.abs(_apply_attention(_attention(attn_window=8), w, x)
                  - want).max() > 1e-3


def test_rope_off_is_no_rotation_and_rope_on_is_one():
    x = np.random.RandomState(7).randn(2, D, 1, L).astype(np.float32)
    lay = _attention(rope=0)
    w = _attention_weights(lay)
    plain = _apply_attention(lay, w, x)
    # rope = 0 is the layer with its rotation taken out
    rot = _attention(rope=1)
    rot._apply_rope = lambda t, offset=0: t
    np.testing.assert_array_equal(_apply_attention(rot, w, x), plain)
    assert np.abs(_apply_attention(_attention(rope=1), w, x)
                  - plain).max() > 1e-3


def test_query_head_j_reads_key_value_head_j_over_group():
    """4 query heads on 2 key-value heads equal 4 on 4 whose key and value
    columns are those of head j // 2."""
    x = np.random.RandomState(8).randn(2, D, 1, L).astype(np.float32)
    lay = _attention()
    w = _attention_weights(lay)
    q, k, v = np.split(np.asarray(w["wqkv"]), [64, 96], axis=1)
    wide = lambda m: np.repeat(m.reshape(D, 2, 16), 2, axis=1) \
        .reshape(D, 64)                                     # noqa: E731
    mha = _attention(nkvhead=4)
    w4 = {"wqkv": jnp.asarray(np.concatenate([q, wide(k), wide(v)], 1)),
          "wo": w["wo"]}
    np.testing.assert_allclose(_apply_attention(mha, w4, x),
                               _apply_attention(lay, w, x), rtol=1e-5,
                               atol=1e-6)


def test_head_dim_is_checked_with_a_message_that_names_the_key():
    with pytest.raises(ValueError, match="head_dim"):
        _attention(head_dim=12)
    with pytest.raises(ValueError, match="head_dim"):
        lay = AttentionLayer()
        lay.set_param("nhead", "4")
        lay.set_param("rope", "1")
        lay.infer_shape([(2, 12, 1, L)])        # head size 3: odd


@pytest.mark.parametrize("window", [0, 160])
def test_the_flash_path_counts_its_tile_schedule_once_a_layer(window):
    """Forced through the interpreter at L 256: ``attn.flash`` once, the
    forward tile as gauges, one head's tiles by kind in the path account
    (a window of 160 takes the band walk, ``attn.flash.band``, and its
    tiles are the walk's sub-tiles); the dense path leaves all of it
    alone."""
    L2 = 256
    lay = AttentionLayer()
    for k, v in {"nhead": 4, "nkvhead": 2, "head_dim": 16, "causal": 1,
                 "attn_window": window}.items():
        lay.set_param(k, str(v))
    lay.infer_shape([(1, D, 1, L2)])
    w = _attention_weights(lay)
    x = jnp.asarray(np.random.RandomState(6).randn(1, D, 1, L2), jnp.float32)

    def delta(force):
        before = telemetry.paths()
        telemetry.enable()
        ops.set_use_pallas(force)
        try:
            y, = lay.apply(w, [x], ApplyContext(train=True))
            gauges = telemetry.summary()["gauges"]
        finally:
            ops.set_use_pallas(None)
            telemetry.disable()
            telemetry.reset()
        return np.asarray(y), gauges, {
            k: n - before.get(k, 0) for k, n in telemetry.paths().items()
            if n != before.get(k, 0)}

    y_flash, gauges, paths = delta(True)
    sched = ops.flash_schedule(jnp.zeros((1, 4, L2, 16)),
                               jnp.zeros((1, 2, L2, 16)), True, window)
    bq, bk = sched["block_q"], sched["block_k"]
    assert L2 % bq == 0 and L2 % bk == 0
    assert gauges["flash.block_q"] == bq and gauges["flash.block_k"] == bk
    kinds = {k: sched[k] for k in ("full", "edge", "skipped")}
    assert sum(kinds.values()) == (L2 // bq) * (L2 // bk)
    assert kinds["edge"] >= L2 // max(bq, bk)      # the diagonal's tiles
    # heads of 16: the plain lines between the qkv dot and the core
    assert sched["band"] == (window == 160)
    assert paths == dict({"attn.flash": 1, "attn.prep.xla": 1,
                          "attn.bwd.fused": 1}, **{
        "flash.tiles." + k: n for k, n in kinds.items() if n}, **(
            {"attn.flash.band": 1} if sched["band"] else {}))
    y_dense, gauges, paths = delta(False)
    assert paths == {"attn.dense": 1, "attn.prep.xla": 1}
    assert not any(k.startswith("flash.") for k in gauges)
    np.testing.assert_allclose(y_flash, y_dense, rtol=2e-4, atol=2e-5)


def test_the_step_on_the_forced_flash_path_fuses_the_rotation():
    """``Trainer.update`` with the kernels forced on (the interpreter),
    heads of the published 128 at L 256: the three layers with a rotation
    take the fused pass between the qkv dot and the core, the global layer
    (no rotation, no norm) keeps the plain lines, all four the flash
    kernels; and the step gives the plain path's loss."""
    conf = _conf(nhead=2, nkvhead=1, head_dim=128, window=64)
    cfg = dict(CFG, seq_len=256, batch_per_chip=256)

    def run(force):
        before = telemetry.paths()
        ops.set_use_pallas(force)
        try:
            program = cxxnet_lm_trainer.Program(conf, cfg, 1, SEED, {})
            program.step()
            program.sync()
        finally:
            ops.set_use_pallas(None)
        return float(program.trainer.last_health[0]), {
            k: n for k, n in _paths_since(before).items()
            if k.startswith("attn.")}
    loss, paths = run(True)
    assert paths == {"attn.flash": 4, "attn.bwd.fused": 4,
                     "attn.prep.fused": 3,
                     "attn.prep.xla": 1}
    plain, paths = run(False)
    assert paths == {"attn.dense": 4, "attn.prep.xla": 4}
    assert np.isfinite(loss) and loss == pytest.approx(plain, rel=1e-5)


# ------------------------------------------------------------- the recipe
def test_the_builder_writes_the_published_model():
    layers, glob = netconf.parse(models.smallthinker_conf())
    kinds = [lay.type for lay in layers]
    assert kinds.count("attention") == kinds.count("moe") == 52
    att = [lay for lay in layers if lay.type == "attention"]
    assert [a.geti("rope") for a in att[:8]] == [0, 1, 1, 1, 0, 1, 1, 1]
    assert [a.geti("attn_window") for a in att[:4]] == [0, 4096, 4096, 4096]
    assert (att[0].geti("nhead"), att[0].geti("nkvhead"),
            att[0].geti("head_dim")) == (28, 4, 128)
    moe = next(lay for lay in layers if lay.type == "moe")
    assert (moe.geti("nexpert"), moe.geti("top_k"), moe.geti("nhidden"),
            moe.geti("nexpert_held")) == (64, 6, 768, 64)
    assert moe.ins[1] == "emb"            # the router reads the block's input
    n = sum(int(np.prod(s)) for tags in
            lm_inputs.weight_shapes(layers).values() for s in tags.values())
    assert 21.4e9 < n < 21.6e9            # "21B"
    assert glob["updater"] == "adamw" and "metric" not in glob


@pytest.mark.parametrize("path, kw", [
    ("smallthinker_21b.conf", {}),
    ("smallthinker_21b_ep4_l4.conf",
     dict(nlayer=4, n_held=16, vocab=37984,
          extra_cfg="compute_dtype = bfloat16\n"))])
def test_the_example_confs_are_what_the_builder_writes(path, kw):
    """The recipe lives in the builder; ``example/`` carries its text (and
    ``benchmark/configs/`` the measured copy, held by
    ``tests/benchmark/test_moe_lm.py``)."""
    here = os.path.dirname(os.path.abspath(__file__))
    with open(os.path.join(here, "..", "example", "transformer", path)) as f:
        body = "\n".join(ln for ln in f.read().splitlines()
                         if not ln.startswith("#"))
    assert body.strip() == models.smallthinker_conf(**kw).strip()

