"""Property fuzz over random netconfig DAGs: for seeded random nets
built from the layer vocabulary, (a) inferred node shapes match the
actual forward values, (b) a train step leaves every parameter finite,
(c) the model checkpoint round-trips bitwise through a fresh trainer.
This is the generative counterpart of the per-layer unit tests — it
exercises layer COMPOSITIONS (conv stacks onto pools onto norms onto
branches) no hand-written case covers."""

import numpy as np
import jax
import pytest

from cxxnet_tpu.io.data import DataBatch
from cxxnet_tpu.nnet.trainer import Trainer
from cxxnet_tpu.utils import serializer
from cxxnet_tpu.utils.config import parse_config_string

N_CLASS = 5


def _random_conf(rs):
    """A random conv/pool/act/norm trunk, optionally with
    inception-style split/concat blocks, ending flatten -> fullc ->
    softmax. Nodes are explicit integers so branches wire exactly."""
    lines = ["netconfig = start"]
    node = 0      # current output node id
    nxt = 1       # next unused node id
    c, h = 3, 16  # channels, spatial (square)

    def emit(src, dst, layer, *keys):
        lines.append("layer[%s->%s] = %s" % (src, dst, layer))
        lines.extend("  " + k for k in keys)

    for b in range(rs.randint(2, 6)):
        kind = rs.choice(["conv", "pool", "act", "norm", "branch"])
        if kind == "conv":
            k = int(rs.choice([1, 3])) if h >= 3 else 1
            ch = int(rs.choice([4, 8]))
            g = 2 if (k == 1 and c % 2 == 0 and rs.rand() < 0.3) else 1
            emit(node, nxt, "conv:c%d" % b, "kernel_size = %d" % k,
                 "pad = %d" % (k // 2), "nchannel = %d" % ch,
                 "ngroup = %d" % g, "random_type = xavier")
            node, nxt, c = nxt, nxt + 1, ch
        elif kind == "pool":
            if h < 4:
                continue
            emit(node, nxt, str(rs.choice(["max_pooling", "avg_pooling"])),
                 "kernel_size = 2", "stride = 2")
            node, nxt, h = nxt, nxt + 1, (h + 1) // 2
        elif kind == "act":
            emit(node, nxt, str(rs.choice(
                ["relu", "sigmoid", "tanh", "softplus", "prelu"])))
            node, nxt = nxt, nxt + 1
        elif kind == "norm":
            name = str(rs.choice(["batch_norm", "lrn"]))
            if name == "lrn":
                emit(node, nxt, name, "local_size = 3")
            else:
                emit(node, nxt, "batch_norm:bn%d" % b)
            node, nxt = nxt, nxt + 1
        elif kind == "branch" and h >= 3:
            a_in, b_in = nxt, nxt + 1
            emit(node, "%d,%d" % (a_in, b_in), "split")
            ca, cb = int(rs.choice([4, 8])), int(rs.choice([4, 8]))
            emit(a_in, nxt + 2, "conv:b%da" % b, "kernel_size = 1",
                 "nchannel = %d" % ca, "random_type = xavier")
            emit(b_in, nxt + 3, "conv:b%db" % b, "kernel_size = 3",
                 "pad = 1", "nchannel = %d" % cb, "random_type = xavier")
            emit("%d,%d" % (nxt + 2, nxt + 3), nxt + 4, "ch_concat")
            node, nxt, c = nxt + 4, nxt + 5, ca + cb
    emit(node, nxt, "flatten")
    node, nxt = nxt, nxt + 1
    emit(node, nxt, "fullc:head", "nhidden = %d" % N_CLASS,
         "init_sigma = 0.05")
    node = nxt
    lines.append("layer[%d->%d] = softmax" % (node, node))
    lines += ["netconfig = end", "input_shape = 3,16,16",
              "batch_size = 4", "eta = 0.05", "dev = cpu"]
    return "\n".join(lines) + "\n"


@pytest.mark.parametrize("seed", range(20))
def test_random_dag_shapes_grads_checkpoint(seed):
    rs = np.random.RandomState(100 + seed)
    conf = _random_conf(rs)
    # every generated config is valid by construction (the generator
    # tracks shape/channel/group constraints), so ANY init failure here
    # is a framework regression — no except-and-skip
    tr = Trainer()
    for k, v in parse_config_string(conf):
        tr.set_param(k, v)
    tr.init_model()
    net = tr.net

    # (a) inferred shapes match actual forward values on every node
    x = rs.rand(4, 3, 16, 16).astype(np.float32)
    values, _ = net.forward(tr.params, x, train=False,
                            rng=jax.random.PRNGKey(0))
    for n, v in enumerate(values):
        if v is None:
            continue
        want = tuple(net.node_shapes[n][1:])
        got = tuple(np.shape(v)[1:])
        assert got == want, "node %d: inferred %s actual %s\n%s" % (
            n, want, got, conf)

    # (b) one update step: finite params after
    b = DataBatch()
    b.data = x
    b.label = rs.randint(0, N_CLASS, (4, 1)).astype(np.float32)
    b.batch_size = 4
    tr.update(b)
    for p in tr.params:
        for key, w in p.items():
            assert np.isfinite(np.asarray(w)).all(), (key, conf)

    # (c) checkpoint round-trip is bitwise through a fresh trainer
    w1 = serializer.Writer()
    tr.save_model(w1)
    tr2 = Trainer()
    for k, v in parse_config_string(conf):
        tr2.set_param(k, v)
    tr2.init_model()
    tr2.load_model(serializer.Reader(w1.getvalue()))
    w2 = serializer.Writer()
    tr2.save_model(w2)
    assert w1.getvalue() == w2.getvalue(), conf


# --- serving fuzz: decode == full recompute across the attention grid --

ATT_GRID = [
    # (embed_extra, attn_extra) random-ish corners beyond the
    # hand-picked cases in test_decode.py
    ("pos_embed = 1", "  nkvhead = 2\n"),
    ("pos_embed = 0", "  rope = 1\n"),
    ("pos_embed = 0", "  rope = 1\n  attn_window = 5\n"),
    ("pos_embed = 1", "  nkvhead = 1\n  attn_window = 9\n"),
    ("pos_embed = 0", "  rope = 1\n  nkvhead = 4\n"),
    ("pos_embed = 1", "  attn_window = 16\n"),
    # flash-decode (decode_chunk while-loop) corners: chunk dividing and
    # equal to the cache length, composed with GQA/rope/window
    ("pos_embed = 1", "  decode_chunk = 8\n  nkvhead = 2\n"),
    ("pos_embed = 0",
     "  rope = 1\n  attn_window = 5\n  decode_chunk = 8\n"),
    ("pos_embed = 1", "  decode_chunk = 24\n"),
    ("pos_embed = 0",
     "  rope = 1\n  nkvhead = 4\n  decode_chunk = 12\n"),
]


# tier-1 budget: three representative corners ride tier-1 — plain GQA
# (0), rope+window (2), and the flash-decode chunk composed with
# rope+window (7); the full grid still runs in the slow tier
@pytest.mark.parametrize(
    "case",
    [c if c in (0, 2, 7) else pytest.param(c, marks=pytest.mark.slow)
     for c in range(len(ATT_GRID))])
def test_decode_grid_matches_recompute(case):
    """KV-cached decode must be token-exact vs full-prefix recompute for
    every (positions, rope, GQA-width, window) corner — including ragged
    prompts — not just the hand-picked combinations."""
    from tests.test_decode import _trained, _check
    embed_extra, attn_extra = ATT_GRID[case]
    tr = _trained(embed_extra=embed_extra, attn_extra=attn_extra,
                  steps=8)
    _check(tr, n_new=6)
    # beam=1 IS greedy, for every attention-config corner
    rsb = np.random.RandomState(90 + case)
    bp = rsb.randint(0, 12, (4, 6))
    np.testing.assert_array_equal(tr.beam_generate(bp, 5, beam=1),
                                  tr.generate(bp, 5))
    # ragged variant on the same trainer
    rs = np.random.RandomState(50 + case)
    prompts = rs.randint(0, 12, (4, 8))
    lens = np.array([4, 8, 6, 5])
    got = tr.generate(prompts, 4, prompt_lens=lens)
    for r in range(4):
        want = tr.generate(prompts[r:r + 1, :lens[r]], 4)
        np.testing.assert_array_equal(got[r:r + 1], want,
                                      err_msg="row %d" % r)


# --- parallelism fuzz: random DAG x (dp, dp x tp) exactness ------------


@pytest.mark.parametrize("seed", range(6))
def test_random_dag_parallel_matches_single_device(seed):
    """Seeded random DAGs must train IDENTICALLY (tight tolerance)
    under data parallelism and composed dp x tp vs the single-device
    net — the generative version of test_compose's hand-built cases."""
    rs = np.random.RandomState(300 + seed)
    conf = _random_conf(rs)
    # batch 8 so every data-parallel degree divides it
    variants = {
        "1dev": "dev = cpu\nbatch_size = 8\n",
        "dp8": "dev = cpu:0-7\nbatch_size = 8\n",
        "dp4xtp2": "dev = cpu:0-7\nbatch_size = 8\n"
                   "model_parallel = 2\n",
    }
    from tests.test_compose import _trainer, _assert_params_match
    trainers = {name: _trainer(conf, extra)
                for name, extra in variants.items()}
    xs = rs.rand(3, 8, 3, 16, 16).astype(np.float32)
    ys = rs.randint(0, N_CLASS, (3, 8, 1)).astype(np.float32)
    for x, y in zip(xs, ys):
        for tr in trainers.values():
            b = DataBatch()
            b.data = x
            b.label = y
            b.batch_size = 8
            tr.update(b)
    ref = trainers["1dev"]
    for name in ("dp8", "dp4xtp2"):
        # same helper + 2e-4 tolerance every sibling dp/tp exactness
        # comparison uses (all-reduce ordering drift allowance)
        _assert_params_match(trainers[name], ref)


@pytest.mark.parametrize("seed", range(8))
def test_random_dag_pipeline_matches_single_device(seed):
    """Random DAGs under pipeline parallelism track the single-device
    net. Two documented semantic boundaries shape the comparison
    (doc/multichip.md): batch_norm statistics are per-MICROBATCH under
    GPipe (exact only at pipeline_micro = 1) and per-data-SHARD under a
    composed dp axis (exact only at dp = 1) — so BN nets run pp2-only
    with one microbatch, everything else runs pp2 x dp4 with the
    default microbatch count."""
    rs = np.random.RandomState(300 + seed)
    conf = _random_conf(rs)
    from tests.test_compose import _trainer, _assert_params_match
    if "batch_norm" in conf:
        extra = ("dev = cpu:0-1\nbatch_size = 8\n"
                 "pipeline_parallel = 2\npipeline_micro = 1\n")
    else:
        extra = ("dev = cpu:0-7\nbatch_size = 8\n"
                 "pipeline_parallel = 2\n")
    tr = _trainer(conf, extra)
    ref = _trainer(conf, "dev = cpu\nbatch_size = 8\n")
    assert tr._pp_entries is not None
    xs = rs.rand(2, 8, 3, 16, 16).astype(np.float32)
    ys = rs.randint(0, N_CLASS, (2, 8, 1)).astype(np.float32)
    for x, y in zip(xs, ys):
        for t in (tr, ref):
            b = DataBatch()
            b.data = x
            b.label = y
            b.batch_size = 8
            t.update(b)
    _assert_params_match(tr, ref)


SP_ATT_CONF = """
netconfig = start
layer[+1:att] = attention:att
  nhead = 4
  causal = 1
  init_sigma = 0.1
%s
layer[+1] = flatten
layer[+1:fc1] = fullc:fc1
  nhidden = 8
  init_sigma = 0.1
layer[+0] = softmax
netconfig = end
input_shape = 8,1,16
batch_size = 8
eta = 0.1
"""

SP_GRID = [
    "  nkvhead = 2\n",
    "  rope = 1\n",
    "  rope = 1\n  attn_window = 8\n",
    "  attn_window = 16\n",
    "  rope = 1\n  nkvhead = 4\n",
]


@pytest.mark.parametrize("case", range(len(SP_GRID)))
def test_attention_grid_seq_parallel_matches(case):
    """Ring attention under seq_parallel = 2 trains identically to the
    single-device net across the (GQA-width, rope, window) grid — the
    sp counterpart of the decode grid above (window tile-skipping and
    GQA-sized ring hops are the risky corners)."""
    from tests.test_compose import _trainer, _assert_params_match
    conf = SP_ATT_CONF % SP_GRID[case]
    tr = _trainer(conf, "dev = cpu:0-7\nseq_parallel = 2\n")
    ref = _trainer(conf, "dev = cpu\n")
    assert "sp" in tr.mesh.axis_names
    rs = np.random.RandomState(case)
    for _ in range(3):
        b = DataBatch()
        b.data = rs.rand(8, 8, 1, 16).astype(np.float32)
        b.label = rs.randint(0, 8, (8, 1)).astype(np.float32)
        b.batch_size = 8
        tr.update(b)
        ref.update(b)
    _assert_params_match(tr, ref)


EP_CONF = """
netconfig = start
layer[+1:m1] = moe:m1
  nexpert = %d
  nhidden = 8
%s
  init_sigma = 0.1
layer[+1] = relu
layer[+1:fc] = fullc:fc
  nhidden = 4
  init_sigma = 0.1
layer[+0] = softmax
netconfig = end
input_shape = 1,1,6
batch_size = 8
eta = 0.1
"""

EP_GRID = [(4, ""), (8, ""), (4, "  top_k = 2\n"), (8, "  top_k = 1\n")]


@pytest.mark.parametrize("case", range(len(EP_GRID)))
def test_moe_grid_expert_parallel_matches(case):
    """Expert parallelism across the (nexpert, top_k) grid: sharded
    experts + gate-weighted psum combine must train identically to the
    single-device dense dispatch."""
    from tests.test_compose import _trainer, _assert_params_match
    nexpert, extra_keys = EP_GRID[case]
    conf = EP_CONF % (nexpert, extra_keys)
    tr = _trainer(conf, "dev = cpu:0-7\nexpert_parallel = 2\n")
    ref = _trainer(conf, "dev = cpu\n")
    assert "ep" in tr.mesh.axis_names
    rs = np.random.RandomState(40 + case)
    for _ in range(3):
        b = DataBatch()
        b.data = rs.rand(8, 1, 1, 6).astype(np.float32)
        b.label = rs.randint(0, 4, (8, 1)).astype(np.float32)
        b.batch_size = 8
        tr.update(b)
        ref.update(b)
    _assert_params_match(tr, ref)


@pytest.mark.parametrize("seed", range(4))
def test_random_dag_zero_sharding_matches(seed):
    """ZeRO tiers on random DAGs: update_on_server (opt-state sharding)
    and fsdp (ZeRO-3 full param sharding) must not change numerics vs
    plain data parallelism."""
    rs = np.random.RandomState(500 + seed)
    conf = _random_conf(rs)
    from tests.test_compose import _trainer, _assert_params_match
    variants = {
        "1dev": "dev = cpu\nbatch_size = 8\n",
        "zero1": "dev = cpu:0-7\nbatch_size = 8\nupdate_on_server = 1\n",
        "fsdp": "dev = cpu:0-7\nbatch_size = 8\nfsdp = 1\n",
    }
    trainers = {name: _trainer(conf, extra)
                for name, extra in variants.items()}
    xs = rs.rand(3, 8, 3, 16, 16).astype(np.float32)
    ys = rs.randint(0, N_CLASS, (3, 8, 1)).astype(np.float32)
    for x, y in zip(xs, ys):
        for tr in trainers.values():
            b = DataBatch()
            b.data = x
            b.label = y
            b.batch_size = 8
            tr.update(b)
    for name in ("zero1", "fsdp"):
        _assert_params_match(trainers[name], trainers["1dev"])


@pytest.mark.parametrize("seed", range(15))
def test_mutated_config_fails_controlled(seed):
    """Corrupted configs must fail with a framework error (ValueError /
    ConfigError / AssertionError with a message), never an uncontrolled
    crash — the reference's utils::Check discipline (src/utils/utils.h)
    applied generatively: take a valid random config and break it."""
    rs = np.random.RandomState(700 + seed)
    conf = _random_conf(rs)
    lines = conf.splitlines()
    mutation = rs.choice(["drop", "scramble_node", "bad_value", "dup"])
    idx = [i for i, l in enumerate(lines) if l.startswith("layer[")]
    i = int(rs.choice(idx))
    if mutation == "drop":
        del lines[i]                       # dangling node references
    elif mutation == "scramble_node":
        lines[i] = lines[i].replace("[", "[9", 1)   # undefined source
    elif mutation == "bad_value":
        lines.insert(i + 1, "  kernel_size = -3")
    elif mutation == "dup":
        lines.insert(i, lines[i])          # node written twice
    broken = "\n".join(lines) + "\n"
    tr = Trainer()
    try:
        for k, v in parse_config_string(broken):
            tr.set_param(k, v)
        tr.init_model()
        # some mutations still yield a valid net (e.g. a dup split
        # branch that type-checks) — then it must actually train
        b = DataBatch()
        b.data = rs.rand(4, 3, 16, 16).astype(np.float32)
        b.label = rs.randint(0, N_CLASS, (4, 1)).astype(np.float32)
        b.batch_size = 4
        tr.update(b)
    except (ValueError, AssertionError) as e:
        # 40-seed census: every failure is a messaged ValueError
        # (ConfigError subclasses it); KeyError/IndexError would be an
        # uncontrolled-crash regression
        assert str(e), "error must carry a message"
