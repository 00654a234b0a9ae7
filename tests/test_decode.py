"""KV-cached autoregressive decoding (Trainer.generate): one decode step
per token against per-layer k/v caches must reproduce, token for token,
the naive full-prefix-recompute generation — incl. learned positions,
RoPE offsets, GQA caches, and sliding-window masking.
"""

import numpy as np
import pytest

from cxxnet_tpu.io.data import DataBatch
from cxxnet_tpu.nnet.trainer import Trainer
from cxxnet_tpu.utils.config import parse_config_string

VOCAB, SEQ = 12, 24

LM = """
netconfig = start
layer[0->1] = embed:emb
  vocab_size = %(vocab)d
  nhidden = 16
  %(embed_extra)s
  init_sigma = 0.05
layer[1->2,3] = split
layer[2->4] = attention:att1
  nhead = 4
  causal = 1
  init_sigma = 0.05
%(attn_extra)s
layer[3,4->5] = add
layer[5->6] = conv:head
  kernel_size = 1
  nchannel = %(vocab)d
  random_type = kaiming
layer[6->6] = softmax
  seq = 1
netconfig = end
input_shape = 1,1,%(seq)d
batch_size = 8
label_width = %(seq)d
label_vec[0,%(seq)d) = label
updater = adam
eta = 0.01
dev = cpu
"""


def _trained(embed_extra="pos_embed = 1", attn_extra="", steps=30,
             extra_params=()):
    conf = LM % {"vocab": VOCAB, "seq": SEQ, "embed_extra": embed_extra,
                 "attn_extra": attn_extra}
    tr = Trainer()
    for k, v in parse_config_string(conf):
        tr.set_param(k, v)
    for k, v in extra_params:
        tr.set_param(k, v)
    tr.init_model()
    rs = np.random.RandomState(0)
    for _ in range(steps):
        phase = rs.randint(0, VOCAB, (8, 1))
        t = np.arange(SEQ + 1)[None, :]
        toks = (phase + t) % VOCAB
        b = DataBatch()
        b.data = toks[:, :SEQ].reshape(8, 1, 1, SEQ).astype(np.float32)
        b.label = toks[:, 1:].astype(np.float32)
        b.batch_size = 8
        tr.update(b)
    return tr


def _full_recompute_generate(tr, prompts, n_new):
    """Reference: greedy continuation recomputing the whole prefix per
    token through the ordinary padded forward (causal masking makes the
    zero tail inert)."""
    b, plen = prompts.shape
    toks = np.zeros((b, SEQ), np.int64)
    toks[:, :plen] = prompts
    for t in range(plen, plen + n_new):
        db = DataBatch()
        db.data = toks.reshape(b, 1, 1, SEQ).astype(np.float32)
        db.label = np.zeros((b, SEQ), np.float32)
        db.batch_size = b
        probs = tr.extract_feature(db, "top[-1]")
        toks[:, t] = probs.reshape(b, VOCAB, SEQ)[:, :, t - 1].argmax(1)
    return toks[:, plen:plen + n_new]


def _check(tr, n_new=8):
    rs = np.random.RandomState(7)
    prompts = rs.randint(0, VOCAB, (8, 6))
    want = _full_recompute_generate(tr, prompts, n_new)
    got = tr.generate(prompts, n_new)
    np.testing.assert_array_equal(got, want)


def test_decode_matches_full_recompute_learned_pos():
    _check(_trained())


def test_decode_matches_rope_gqa_window():
    """RoPE decode offsets, grouped-query caches (nkv < nh), and the
    sliding-window mask over the cache."""
    tr = _trained(embed_extra="pos_embed = 0",
                  attn_extra="  rope = 1\n  nkvhead = 2\n"
                             "  attn_window = 8\n")
    _check(tr)


def test_decode_ragged_prompt_lens():
    """A ragged batch (per-row prompt lengths) generates, row for row,
    exactly what each row's uniform-length generation produces."""
    tr = _trained()
    rs = np.random.RandomState(11)
    prompts = rs.randint(0, VOCAB, (8, 9))
    lens = np.array([4, 9, 6, 4, 9, 6, 5, 7])
    got = tr.generate(prompts, 6, prompt_lens=lens)
    for r in range(8):
        want = tr.generate(prompts[r:r + 1, :lens[r]], 6)
        np.testing.assert_array_equal(got[r:r + 1], want, err_msg="row %d" % r)


def test_decode_ragged_with_sampling():
    """Sampling composed with ragged lengths: seeds reproduce, prompts
    are never overwritten (each row's output continues ITS prompt), and
    tokens stay in-vocab."""
    tr = _trained()
    rs = np.random.RandomState(12)
    prompts = rs.randint(0, VOCAB, (8, 9))
    lens = np.array([4, 9, 6, 4, 9, 6, 5, 7])
    s1 = tr.generate(prompts, 6, temperature=1.0, top_k=4,
                     seed=3, prompt_lens=lens)
    s2 = tr.generate(prompts, 6, temperature=1.0, top_k=4,
                     seed=3, prompt_lens=lens)
    np.testing.assert_array_equal(s1, s2)
    assert s1.shape == (8, 6) and s1.min() >= 0 and s1.max() < VOCAB


def _seq_logprob(tr, prompts, cont):
    """Sum of model log-probs of `cont` given `prompts` (full forward)."""
    b, plen = prompts.shape
    n = cont.shape[1]
    toks = np.zeros((b, SEQ), np.int64)
    toks[:, :plen] = prompts
    toks[:, plen:plen + n] = cont
    db = DataBatch()
    db.data = toks.reshape(b, 1, 1, SEQ).astype(np.float32)
    db.label = np.zeros((b, SEQ), np.float32)
    db.batch_size = b
    probs = tr.extract_feature(db, "top[-1]").reshape(b, VOCAB, SEQ)
    lp = np.zeros(b)
    for t in range(plen, plen + n):
        lp += np.log(np.maximum(
            probs[np.arange(b), toks[:, t], t - 1], 1e-30))
    return lp


def test_beam_search():
    """beam=1 IS greedy (called FIRST — no prior generate() warms the
    decode state); beam=4 is deterministic, in-vocab, and in practice
    scores at least as well as greedy on this model (informative, not a
    theorem — beam search may prune the greedy path; only logged)."""
    tr = _trained(steps=12)   # partially trained: beams can disagree
    rs = np.random.RandomState(21)
    prompts = rs.randint(0, VOCAB, (8, 6))
    b1 = tr.beam_generate(prompts, 8, beam=1)
    greedy = tr.generate(prompts, 8)
    np.testing.assert_array_equal(b1, greedy)
    b4 = tr.beam_generate(prompts, 8, beam=4)
    b4_again = tr.beam_generate(prompts, 8, beam=4)
    np.testing.assert_array_equal(b4, b4_again)
    assert b4.shape == (8, 8) and b4.min() >= 0 and b4.max() < VOCAB
    lp_greedy = _seq_logprob(tr, prompts, greedy)
    lp_beam = _seq_logprob(tr, prompts, b4)
    print("beam4 vs greedy mean log-prob: %.3f vs %.3f"
          % (lp_beam.mean(), lp_greedy.mean()))


def test_decode_sampling():
    """temperature > 0 samples valid tokens reproducibly per seed; a tiny
    temperature concentrates the categorical on the argmax (= greedy)."""
    tr = _trained()
    rs = np.random.RandomState(7)
    prompts = rs.randint(0, VOCAB, (8, 6))
    greedy = tr.generate(prompts, 8)
    cold = tr.generate(prompts, 8, temperature=1e-4)
    np.testing.assert_array_equal(cold, greedy)
    s1 = tr.generate(prompts, 8, temperature=1.0, top_k=4, seed=1)
    s2 = tr.generate(prompts, 8, temperature=1.0, top_k=4, seed=1)
    s3 = tr.generate(prompts, 8, temperature=1.0, top_k=4, seed=2)
    np.testing.assert_array_equal(s1, s2)
    assert (s1 != s3).any(), "different seeds produced identical samples"
    assert s1.min() >= 0 and s1.max() < VOCAB


def test_export_decode_artifacts_match(tmp_path):
    """The exported prefill/step StableHLO pair, driven by the jax-only
    reference loop, reproduces Trainer.generate token for token."""
    from cxxnet_tpu import api
    tr = _trained()
    rs = np.random.RandomState(9)
    prompts = rs.randint(0, VOCAB, (4, 6))
    pre_b, step_b = tr.export_decode(batch_size=4, prompt_len=6)
    p1, p2 = str(tmp_path / "pre.hlo"), str(tmp_path / "step.hlo")
    open(p1, "wb").write(pre_b)
    open(p2, "wb").write(step_b)
    gen = api.load_decode(p1, p2)
    got = gen(prompts, 8)
    want = tr.generate(prompts, 8)
    np.testing.assert_array_equal(got, want)


def test_cli_generate_task(tmp_path):
    """task = generate through the CLI: train -> save -> generate ragged
    prompt lines to a file; outputs match Trainer.generate."""
    from cxxnet_tpu import learn_task
    from cxxnet_tpu.utils import serializer
    tr = _trained()
    model = str(tmp_path / "0001.model")
    with open(model, "wb") as f:
        w = serializer.Writer(f)
        w.write_int32(0)
        tr.save_model(w)
    rs = np.random.RandomState(5)
    lines = [rs.randint(0, VOCAB, n).tolist() for n in (4, 7, 5, 7)]
    pf = str(tmp_path / "prompts.txt")
    with open(pf, "w") as f:
        for row in lines:
            f.write(" ".join(map(str, row)) + "\n")
    gout = str(tmp_path / "gen.txt")
    conf = LM % {"vocab": VOCAB, "seq": SEQ,
                 "embed_extra": "pos_embed = 1", "attn_extra": ""}
    cf = str(tmp_path / "gen.conf")
    with open(cf, "w") as f:
        f.write(conf + "task = generate\nmodel_in = %s\n"
                "prompt_in = %s\ngen_out = %s\ngen_new = 5\n"
                % (model, pf, gout))
    assert learn_task.main([cf]) == 0
    got = [list(map(int, line.split())) for line in open(gout)]
    prompts = np.zeros((4, 7), np.int64)
    lens = np.array([len(r) for r in lines])
    for i, r in enumerate(lines):
        prompts[i, :len(r)] = r
    want = tr.generate(prompts, 5, prompt_lens=lens)
    np.testing.assert_array_equal(np.asarray(got), want)


def test_decode_bounds_checked():
    import pytest
    tr = _trained(steps=1)
    with pytest.raises(Exception, match="exceeds"):
        tr.generate(np.zeros((8, 20), np.int64), 10)
    # non-causal attention cannot decode or export artifacts
    conf = (LM % {"vocab": VOCAB, "seq": SEQ, "embed_extra": "pos_embed = 1",
                  "attn_extra": ""}).replace("causal = 1", "causal = 0")
    nc = Trainer()
    for k, v in parse_config_string(conf):
        nc.set_param(k, v)
    nc.init_model()
    with pytest.raises(Exception, match="not causal"):
        nc.generate(np.zeros((8, 4), np.int64), 2)
    with pytest.raises(Exception, match="not causal"):
        nc.export_decode(batch_size=2, prompt_len=4)


def test_export_decode_artifact_bounds(tmp_path):
    from cxxnet_tpu import api
    import pytest
    tr = _trained(steps=1)
    pre_b, step_b = tr.export_decode(batch_size=2, prompt_len=4)
    p1, p2 = str(tmp_path / "p.hlo"), str(tmp_path / "s.hlo")
    open(p1, "wb").write(pre_b)
    open(p2, "wb").write(step_b)
    gen = api.load_decode(p1, p2)
    with pytest.raises(ValueError, match="exceeds"):
        gen(np.zeros((2, 4), np.int64), SEQ)
    assert gen(np.zeros((2, 4), np.int64), 0).shape == (2, 0)


def test_decode_bf16_compute():
    """A bf16-trained model decodes in bf16 (the decode nets inherit
    compute_dtype) and still matches ITS OWN bf16 full recompute."""
    conf = (LM % {"vocab": VOCAB, "seq": SEQ,
                  "embed_extra": "pos_embed = 1", "attn_extra": ""}
            ) + "compute_dtype = bfloat16\n"
    tr = Trainer()
    for k, v in parse_config_string(conf):
        tr.set_param(k, v)
    tr.init_model()
    rs = np.random.RandomState(0)
    for _ in range(20):
        phase = rs.randint(0, VOCAB, (8, 1))
        t = np.arange(SEQ + 1)[None, :]
        toks = (phase + t) % VOCAB
        b = DataBatch()
        b.data = toks[:, :SEQ].reshape(8, 1, 1, SEQ).astype(np.float32)
        b.label = toks[:, 1:].astype(np.float32)
        b.batch_size = 8
        tr.update(b)
    assert tr._seq_net(8, 1).compute_dtype is not None
    _check(tr)


def test_decode_with_remat_attention():
    """remat=1 attention (the long-context training config): decode skips
    the checkpoint wrapper (no backward at inference) and still matches
    the full recompute."""
    _check(_trained(attn_extra="  remat = 1\n"))


WEIGHT_TIED = """
netconfig = start
layer[0->1] = embed:emb
  vocab_size = %(vocab)d
  nhidden = 16
  pos_embed = 1
  init_sigma = 0.05
layer[1->2,3] = split
layer[2->4] = attention:att1
  nhead = 4
  causal = 1
  init_sigma = 0.05
layer[3,4->5] = add
layer[5->6,7] = split
layer[6->8] = share[att1]
layer[7,8->9] = add
layer[9->10] = conv:head
  kernel_size = 1
  nchannel = %(vocab)d
  random_type = kaiming
layer[10->10] = softmax
  seq = 1
netconfig = end
input_shape = 1,1,%(seq)d
batch_size = 8
label_width = %(seq)d
label_vec[0,%(seq)d) = label
updater = adam
eta = 0.01
dev = cpu
"""


def test_decode_weight_tied_attention_has_separate_caches():
    """share[att1] reuses the WEIGHTS at a second depth; each application
    must keep its own KV cache (keyed by connection index, not params
    slot) — decode matches the full recompute."""
    conf = WEIGHT_TIED % {"vocab": VOCAB, "seq": SEQ}
    tr = Trainer()
    for k, v in parse_config_string(conf):
        tr.set_param(k, v)
    tr.init_model()
    rs = np.random.RandomState(0)
    for _ in range(20):
        phase = rs.randint(0, VOCAB, (8, 1))
        t = np.arange(SEQ + 1)[None, :]
        toks = (phase + t) % VOCAB
        b = DataBatch()
        b.data = toks[:, :SEQ].reshape(8, 1, 1, SEQ).astype(np.float32)
        b.label = toks[:, 1:].astype(np.float32)
        b.batch_size = 8
        tr.update(b)
    _check(tr)


def test_generate_sees_set_weight():
    """The decode param cache must invalidate on SetWeight: net.set_weight
    mutates the params list in place, so identity-keyed caching would
    silently generate with stale weights (ADVICE r4)."""
    tr = _trained(steps=10)
    prompts = np.random.RandomState(3).randint(0, VOCAB, (4, 6))
    before = tr.generate(prompts, 4)          # warm the decode cache
    w, _ = tr.get_weight("head", "wmat")
    tr.set_weight(np.zeros_like(w), "head", "wmat")
    bias, _ = tr.get_weight("head", "bias")
    tr.set_weight(np.zeros_like(bias), "head", "bias")
    got = tr.generate(prompts, 4)
    # zero head => uniform logits => greedy argmax picks token 0
    np.testing.assert_array_equal(got, np.zeros_like(got))
    assert not np.array_equal(before, np.zeros_like(before))


def test_generate_tensor_parallel_token_exact():
    """Serving under tensor parallelism (VERDICT r4 #3): generate() on a
    model_parallel=2 trainer decodes with the FFN/head weights sharded
    over the model axis (same Megatron specs as training) and must be
    token-exact vs the single-device decode of the same weights —
    column/output-channel splits introduce no reduction reordering."""
    from cxxnet_tpu.utils import serializer
    tr = _trained(steps=15)
    w = serializer.Writer()
    tr.save_model(w)

    conf = LM % {"vocab": VOCAB, "seq": SEQ,
                 "embed_extra": "pos_embed = 1", "attn_extra": ""}
    tr_tp = Trainer()
    for k, v in parse_config_string(conf):
        tr_tp.set_param(k, v)
    tr_tp.set_param("dev", "cpu:0-7")
    tr_tp.set_param("model_parallel", "2")
    tr_tp.init_model()
    tr_tp.load_model(serializer.Reader(w.getvalue()))
    assert tr_tp._decode_mesh() is not None

    rs = np.random.RandomState(11)
    prompts = rs.randint(0, VOCAB, (4, 6))
    want = tr.generate(prompts, 8)
    got = tr_tp.generate(prompts, 8)
    np.testing.assert_array_equal(got, want)
    # the sharded decode really holds the head weight split over the
    # model axis (not gathered to one device)
    params = tr_tp._decode_params_current()
    idx = tr_tp.net_cfg.get_layer_index("head")
    sh = params[idx]["wmat"].sharding
    assert "model" in getattr(sh, "spec", ()) or any(
        "model" in str(p) for p in sh.spec), sh.spec
    # beam search rides the same sharded decode params
    bw = tr.beam_generate(prompts, 6, beam=2)
    bt = tr_tp.beam_generate(prompts, 6, beam=2)
    np.testing.assert_array_equal(bt, bw)


def test_cli_generate_task_tensor_parallel(tmp_path):
    """task = generate with model_parallel = 2 through the CLI: the
    serving mesh decodes with sharded weights and the output matches the
    single-device CLI run token for token."""
    from cxxnet_tpu import learn_task
    from cxxnet_tpu.utils import serializer
    tr = _trained(steps=10)
    model = str(tmp_path / "0001.model")
    with open(model, "wb") as f:
        w = serializer.Writer(f)
        w.write_int32(0)
        tr.save_model(w)
    rs = np.random.RandomState(8)
    prompts = rs.randint(0, VOCAB, (4, 6))
    pf = str(tmp_path / "prompts.txt")
    with open(pf, "w") as f:
        for row in prompts:
            f.write(" ".join(map(str, row)) + "\n")
    conf = LM % {"vocab": VOCAB, "seq": SEQ,
                 "embed_extra": "pos_embed = 1", "attn_extra": ""}
    outs = {}
    for name, extra in (("1dev", ""),
                        ("tp2", "dev = cpu:0-7\nmodel_parallel = 2\n")):
        gout = str(tmp_path / ("gen_%s.txt" % name))
        cf = str(tmp_path / ("gen_%s.conf" % name))
        with open(cf, "w") as f:
            f.write(conf + extra +
                    "task = generate\nmodel_in = %s\n"
                    "prompt_in = %s\ngen_out = %s\ngen_new = 6\n"
                    % (model, pf, gout))
        assert learn_task.main([cf]) == 0
        outs[name] = [list(map(int, line.split())) for line in open(gout)]
    np.testing.assert_array_equal(np.asarray(outs["tp2"]),
                                  np.asarray(outs["1dev"]))


def test_generate_after_pipeline_training():
    """A model TRAINED under pipeline (+tensor) parallelism serves
    through the same generate() surface: packed stage params gather
    canonical, then decode (re-sharded by tp when model_parallel is
    set). Token-exact vs the full-recompute reference."""
    tr = _trained(steps=10, extra_params=(
        ("dev", "cpu:0-7"), ("pipeline_parallel", "2"),
        ("model_parallel", "2")))
    assert tr._pp_entries is not None
    _check(tr, n_new=6)


def test_cli_serve_task(tmp_path):
    """task = serve: the stdin/stdout loop (now the servd frontend
    engine, utils/servd.py) answers each prompt line with its
    continuation, matching Trainer.generate (seed advances per request
    so sampling streams differ per line; greedy here, so rows match
    generate exactly) — and SURVIVES request-level failures: an empty
    line is answered ``ERR empty`` (not silently swallowed), a malformed
    line ``ERR parse``, and a backend exception (a prompt too long for
    the net's sequence length fails inside generate) is answered
    ``ERR backend`` with the loop continuing to serve."""
    import os
    import subprocess
    import sys as _sys
    from cxxnet_tpu.utils import serializer
    REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    tr = _trained(steps=10)
    model = str(tmp_path / "0001.model")
    with open(model, "wb") as f:
        w = serializer.Writer(f)
        w.write_int32(0)
        tr.save_model(w)
    conf = LM % {"vocab": VOCAB, "seq": SEQ,
                 "embed_extra": "pos_embed = 1", "attn_extra": ""}
    cf = str(tmp_path / "serve.conf")
    with open(cf, "w") as f:
        f.write(conf + "task = serve\nmodel_in = %s\ngen_new = 5\n"
                % model)
    rs = np.random.RandomState(13)
    lines = [rs.randint(0, VOCAB, n).tolist() for n in (4, 6, 4)]
    bad = ["",                                # -> ERR empty
           "3 not-a-token 5",                 # -> ERR parse
           " ".join(["1"] * (SEQ + 1))]       # in-vocab but longer than
    #                                           the decode cache: the
    #                                           backend raises mid-loop
    stdin = "\n".join(bad
                      + [" ".join(map(str, r)) for r in lines]) + "\n"
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [_sys.executable, os.path.join(REPO, "bin", "cxxnet"), cf],
        input=stdin, capture_output=True, text=True, timeout=600,
        env=env)
    assert p.returncode == 0, (p.stdout[-1000:], p.stderr[-1000:])
    out_lines = [l for l in p.stdout.splitlines() if l.strip()]
    assert "served 3 prompts (3 request errors)" in p.stderr
    # one ERR line per failed request, in request order, loop alive after
    errs = [l for l in out_lines if l.startswith("ERR")]
    assert [e.split()[1] for e in errs] == ["empty", "parse", "backend"]
    got = [list(map(int, l.split())) for l in out_lines[-3:]]
    for i, r in enumerate(lines):
        want = tr.generate(np.asarray([r]), 5)
        np.testing.assert_array_equal(np.asarray([got[i]]), want,
                                      err_msg="line %d" % i)


def test_decode_chunked_attention_unit():
    """decode_attention_chunked == attention_reference for a one-row
    query at every position class (first chunk, chunk boundary, interior,
    last row), with and without GQA grouping and a sliding window."""
    import jax.numpy as jnp
    from cxxnet_tpu.parallel.ring import (attention_reference,
                                          decode_attention_chunked)
    rs = np.random.RandomState(3)
    b, nh, nkv, L, d = 2, 4, 2, 32, 8
    k = jnp.asarray(rs.randn(b, nkv, L, d).astype(np.float32))
    v = jnp.asarray(rs.randn(b, nkv, L, d).astype(np.float32))
    for window in (0, 5):
        for pos in (0, 3, 7, 8, 15, 31):
            q = jnp.asarray(rs.randn(b, nh, 1, d).astype(np.float32))
            want = attention_reference(q, k, v, causal=True,
                                       window=window, q_offset=pos)
            got = decode_attention_chunked(q, k, v, pos=pos,
                                           window=window, chunk=8)
            np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                       rtol=2e-5, atol=2e-5)


def test_decode_chunked_token_exact():
    """generate() with decode_chunk (flash-decode while-loop) reproduces
    the full-recompute reference token for token."""
    _check(_trained(attn_extra="  decode_chunk = 8\n"))


def test_decode_chunked_rope_gqa_window_token_exact():
    """The chunked path under the long-context serving recipe: RoPE +
    GQA caches + sliding window."""
    _check(_trained(embed_extra="pos_embed = 0",
                    attn_extra="  rope = 1\n  nkvhead = 2\n"
                               "  attn_window = 8\n  decode_chunk = 8\n"))


def test_decode_chunked_export_artifacts_match(tmp_path):
    """export_decode with decode_chunk: the while-loop step program
    exports through jax.export and the artifact loop reproduces the
    (chunk-enabled) generate token for token."""
    from cxxnet_tpu import api
    tr = _trained(attn_extra="  decode_chunk = 8\n")
    rs = np.random.RandomState(9)
    prompts = rs.randint(0, VOCAB, (4, 6))
    pre_b, step_b = tr.export_decode(batch_size=4, prompt_len=6)
    p1, p2 = str(tmp_path / "pre.hlo"), str(tmp_path / "step.hlo")
    open(p1, "wb").write(pre_b)
    open(p2, "wb").write(step_b)
    gen = api.load_decode(p1, p2)
    got = gen(prompts, 8)
    want = tr.generate(prompts, 8)
    np.testing.assert_array_equal(got, want)


def test_decode_chunked_beam1_equals_greedy():
    """Beam search rides the same decode step: with decode_chunk on,
    beam=1 stays pinned to greedy."""
    tr = _trained(attn_extra="  decode_chunk = 8\n")
    rs = np.random.RandomState(11)
    prompts = rs.randint(0, VOCAB, (4, 6))
    np.testing.assert_array_equal(tr.beam_generate(prompts, 6, beam=1),
                                  tr.generate(prompts, 6))


def test_generate_stable_across_predict_calls():
    """predict() swaps the params list identity (donate-and-return,
    _swap_params); interleaved generate() calls must neither go stale
    nor lose their decode-param cache to the identity change."""
    tr = _trained()
    rs = np.random.RandomState(13)
    prompts = rs.randint(0, VOCAB, (4, 6))
    first = tr.generate(prompts, 5)
    db = DataBatch()
    db.data = np.zeros((4, 1, 1, SEQ), np.float32)
    db.label = np.zeros((4, SEQ), np.float32)
    db.batch_size = 4
    tr.predict(db)
    # a regather would re-run canonical_params — count it
    calls = []
    orig = tr.canonical_params
    tr.canonical_params = lambda: (calls.append(1), orig())[1]
    again = tr.generate(prompts, 5)
    tr.canonical_params = orig
    np.testing.assert_array_equal(first, again)
    assert not calls, "decode copy was regathered after predict()"


def test_generate_failure_evicts_decode_programs():
    """A generate() that fails after caching its decode programs must
    evict them: the programs may never have compiled, and a retry that
    believes they did would dispatch the decode scan before the
    first-token block — charging its synchronous compile to
    prefill/TTFT, the exact misattribution the two-program split
    prevents (trainer except-path contract)."""
    from cxxnet_tpu.utils import telemetry
    tr = _trained(steps=0)
    rs = np.random.RandomState(17)
    prompts = rs.randint(0, VOCAB, (2, 4))
    orig = telemetry.mark

    def boom(name, **kw):
        if name == "first_token":
            raise RuntimeError("injected first-token failure")
        return orig(name, **kw)

    telemetry.mark = boom
    try:
        with np.testing.assert_raises(RuntimeError):
            tr.generate(prompts, 5)
    finally:
        telemetry.mark = orig
    assert not tr._decode_fns, "failed call left decode programs cached"
    assert tr._decode_params is None
    # the retry takes the fresh path end-to-end and still serves
    out = tr.generate(prompts, 5)
    assert out.shape == (2, 5)
    # a WARMED signature keeps its programs through a transient
    # failure: they are known-compiled, and evicting would charge the
    # retry a recompile cliff for every backend hiccup
    warmed = dict(tr._decode_fns)
    assert warmed
    telemetry.mark = boom
    try:
        with np.testing.assert_raises(RuntimeError):
            tr.generate(prompts, 5)
    finally:
        telemetry.mark = orig
    assert tr._decode_fns == warmed, "transient failure evicted warmed " \
        "decode programs"
    np.testing.assert_array_equal(tr.generate(prompts, 5), out)


# ----------------------------------------------------------------------
# continuous batching: DecodeSession (iteration-granularity bucketed
# decode — doc/serving.md "Continuous batching") must be token-exact vs
# solo dispatch of every request, with zero recompiles on a warm bucket.


def _solo_continuations(tr, prompts, n_new, temp, top_k, seed0):
    return [list(tr.generate(np.asarray([p]), n_new, temperature=temp,
                             top_k=top_k, seed=seed0 + i)[0])
            for i, p in enumerate(prompts)]


def _drive_session(sess, prompts, seed0, stagger=True):
    """Schedule `prompts` through the session like the servd dispatcher:
    admit into free slots, step, retire on done. ``stagger`` admits at
    most one request per iteration, so later requests join while
    earlier ones are MID-DECODE — the composition the token-exactness
    claim is about."""
    got, live, nxt = {}, {}, 0
    while nxt < len(prompts) or live:
        free = sess.free_slots()
        admit_n = min(len(free), len(prompts) - nxt)
        if stagger:
            admit_n = min(admit_n, 1)
        for s in free[:admit_n]:
            i, nxt = nxt, nxt + 1
            tok, done = sess.prefill(s, prompts[i], seed0 + i)
            live[s] = (i, [tok])
            if done:
                got[i] = live.pop(s)[1]
                sess.retire(s)
        for s, tok, done in sess.step():
            live[s][1].append(tok)
            if done:
                i, toks = live.pop(s)
                got[i] = toks
                sess.retire(s)
    return [got[i] for i in range(len(prompts))]


def test_decode_session_token_exact_and_warm_bucket_no_recompile():
    """Batched == solo, token for token, greedy AND sampled, with
    staggered admissions (every later request joins mid-decode); then
    a request re-served through the WARM bucket records ZERO compiles
    on the recompile detector — the arXiv:1802.04799 cliff pin."""
    from cxxnet_tpu.utils import telemetry
    tr = _trained()
    rs = np.random.RandomState(5)
    # two prompt lengths only (tier-1 compile budget; the full ragged
    # grid is the slow test below)
    prompts = [rs.randint(0, VOCAB, (4, 6)[i % 2]).tolist()
               for i in range(5)]
    n_new = 5
    for temp, top_k in ((0.0, 0), (0.8, 3)):
        solo = _solo_continuations(tr, prompts, n_new, temp, top_k, 50)
        sess = tr.decode_session(3, n_new, temperature=temp, top_k=top_k)
        got = _drive_session(sess, prompts, 50)
        assert got == solo, "batched != solo at temp=%s top_k=%s" \
            % (temp, top_k)
        # warm-bucket join: the recompile detector (trace-context
        # compile attribution — works with telemetry disabled) must
        # record NOTHING for a request joining the warm bucket
        tc = telemetry.trace_context("warm-join")
        with tc:
            got2 = _drive_session(sess, prompts[:1], 50)
        assert got2[0] == solo[0]
        assert tc.compiles == [], tc.compiles
        sess.close()


def test_decode_session_stale_after_params_change():
    """A session serves the params it was created under: swapping the
    trainer's params (model reload) makes every call raise AND latches
    ``closed`` — the slot caches hold old-weight K/V, and the
    dispatcher keys warm-pool eviction (and breaker accounting) on the
    closed flag, so a stale session must never be re-offered."""
    tr = _trained(steps=2)
    sess = tr.decode_session(2, 3)
    sess.prefill(0, [1, 2, 3], 7)
    tr.params = list(tr.params)        # the reload signature: new list
    with pytest.raises(ValueError):
        sess.step()
    assert sess.closed
    with pytest.raises(ValueError):
        sess.prefill(1, [1, 2], 7)


def test_decode_session_kv_account_pins_cache_nbytes():
    """The live KV/HBM occupancy account against REAL device arrays:
    kv_bytes is exactly the slot-major cache arrays' nbytes, the live
    share tracks prompt + generated extents through prefill/step/
    retire, a closed session accounts 0 — and the value survives to
    the cxxnet_decode_kv_bytes /metrics row through a batching
    frontend's snapshot (the acceptance pin)."""
    from cxxnet_tpu.utils import servd, statusd
    tr = _trained(steps=2)
    sess = tr.decode_session(2, 3)
    nbytes = sum(int(a.nbytes) for a in sess._caches.values())
    assert nbytes > 0
    acct = sess.kv_account()
    assert acct["kv_bytes"] == nbytes
    assert acct["bucket"] == 2 and acct["l_max"] == tr.net_cfg.param.input_shape[2]
    assert acct["active"] == 0 and acct["kv_live_bytes"] == 0
    sess.prefill(0, [1, 2, 3], 7)
    acct = sess.kv_account()
    assert acct["active"] == 1 and acct["live_tokens"] == 3
    sess.step()
    acct = sess.kv_account()
    assert acct["live_tokens"] == 4      # one more cache row written
    assert acct["kv_live_bytes"] == int(
        round(nbytes * 4.0 / acct["alloc_tokens"]))
    sess.retire(0)
    assert sess.kv_account()["live_tokens"] == 0
    sess.close()
    assert sess.kv_account()["kv_bytes"] == 0
    # the frontend snapshot -> /metrics pin: a warm session's real
    # nbytes is what cxxnet_decode_kv_bytes{bucket=} reports
    made = []

    class _SlotBackend:
        buckets = [2]

        def session(self, nslots):
            s = tr.decode_session(nslots, 3)
            made.append(s)
            return s

    fe = servd.ServeFrontend(None, slot_backend=_SlotBackend(),
                             batch_max=2, drain_ms=8000.0)
    fe.start()
    port = fe.listen(0)
    try:
        assert servd._ask(port, "1 2 3", timeout=120.0)
        warm_bytes = sum(int(a.nbytes)
                         for a in made[0]._caches.values())
        snap = fe.batch_snapshot()
        assert snap["kv_bytes"] == warm_bytes
        assert snap["buckets"]["2"]["kv_bytes"] == warm_bytes
        assert fe.decode_kv_bytes() == warm_bytes
        text = statusd.prometheus_metrics(
            {"process": 0, "uptime_s": 1.0, "counters": {},
             "gauges": {}, "hists": {}, "compiles": 0,
             "compile_s": 0.0}, batch=snap)
        assert 'cxxnet_decode_kv_bytes{process="0",bucket="2"} %d' \
            % warm_bytes in text
    finally:
        fe.drain()


def test_serve_frontend_continuous_batching_token_exact():
    """The real datapath end-to-end: servd's batching dispatcher over
    Trainer.decode_session serves a concurrent flood with responses
    IDENTICAL to solo generate, coalesces (occupancy > 1), and a
    request admitted into the warm bucket carries zero recompiles in
    its flight record."""
    import threading

    from cxxnet_tpu.utils import servd
    tr = _trained(steps=5)
    n_new = 4

    class _SlotBackend:
        buckets = [2]

        def session(self, nslots):
            # the dispatcher's seq ordinal is the seed (greedy: unused)
            return tr.decode_session(nslots, n_new)

    fe = servd.ServeFrontend(None, slot_backend=_SlotBackend(),
                             batch_max=2, batch_window_ms=60.0,
                             drain_ms=8000.0)
    fe.start()
    port = fe.listen(0)
    try:
        prompts = [[1, 2, 3, 4], [5, 6, 7, 8], [9, 10, 11, 1]]
        solo = [" ".join(str(t) for t in
                         tr.generate(np.asarray([p]), n_new)[0])
                for p in prompts]
        out = [None] * len(prompts)

        def ask(i):
            out[i] = servd._ask(port, " ".join(map(str, prompts[i])),
                                timeout=120.0)

        ts = [threading.Thread(target=ask, args=(i,))
              for i in range(len(prompts))]
        for t in ts:
            t.start()
        for t in ts:
            t.join()
        assert out == solo, (out, solo)
        assert fe.mean_occupancy() > 1.0
        # warm-bucket request (seen prompt length): its flight record's
        # recompile attribution must be EMPTY
        warm = servd._ask(port, " ".join(map(str, prompts[0])),
                          timeout=60.0)
        assert warm == solo[0]
        rec = fe.flight.list()[0]
        assert rec["outcome"] == "served"
        assert rec["recompiles"] == [], rec["recompiles"]
        assert rec.get("occupancy_at_dispatch") == 1
    finally:
        stats = fe.drain()
    assert stats["accepted"] == stats["served"] == 4


@pytest.mark.slow
def test_decode_session_grid_token_exact():
    """The full acceptance grid: batched == solo across greedy /
    sampled / top_k sampling x ragged prompt lengths x the
    learned-pos AND rope+GQA+window model variants, all with
    staggered mid-decode joins."""
    variants = (
        {},
        dict(embed_extra="pos_embed = 0",
             attn_extra="  rope = 1\n  nkvhead = 2\n"
                        "  attn_window = 8\n"),
    )
    for kwargs in variants:
        tr = _trained(**kwargs)
        rs = np.random.RandomState(9)
        prompts = [rs.randint(0, VOCAB, rs.randint(3, 9)).tolist()
                   for _ in range(7)]
        for temp, top_k in ((0.0, 0), (1.0, 0), (0.7, 4)):
            solo = _solo_continuations(tr, prompts, 6, temp, top_k, 30)
            sess = tr.decode_session(4, 6, temperature=temp,
                                     top_k=top_k)
            got = _drive_session(sess, prompts, 30)
            assert got == solo, (kwargs, temp, top_k)
            sess.close()


# ----------------------------------------------------------------------
# paged KV cache (doc/performance.md "Decode KV cache"): block-table
# sessions over the trainer-wide free-list pool must be token-exact vs
# the dense session AND solo dispatch — shared-prefix reuse and
# copy-on-write included — with zero recompiles on a warm bucket, and
# exhaustion must be a deterministic deferral, never a device fault.


def test_decode_session_paged_token_exact_and_prefix_reuse():
    """Paged == solo, token for token, greedy AND sampled, staggered
    mid-decode admissions, over prompts that SHARE full-block prefixes
    (prefill-once reuse) including an identical twin (the
    copy-on-write demotion case); then a warm re-serve records ZERO
    compiles — paging must not reintroduce the arXiv:1802.04799
    per-request compile cliff."""
    from cxxnet_tpu.utils import telemetry
    tr = _trained()
    base = [1, 2, 3, 4]                       # one full block (bs=4)
    prompts = [base + [5, 6], base + [5, 6],  # identical twin: CoW
               base + [7], [2, 3, 4, 5, 6, 7], base]
    n_new = 5
    pool = tr.decode_kv_pool(4, pool_tokens=3 * SEQ)
    for temp, top_k in ((0.0, 0), (0.8, 3)):
        solo = _solo_continuations(tr, prompts, n_new, temp, top_k, 50)
        sess = tr.decode_session(3, n_new, temperature=temp,
                                 top_k=top_k, kv_pool=pool)
        got = _drive_session(sess, prompts, 50)
        assert got == solo, "paged != solo at temp=%s top_k=%s" \
            % (temp, top_k)
        # every retirement returned its blocks — to the RETAINED pool
        # (PR 18: refcount-0 conversations stay trie-resident as
        # evictable headroom), so the books reconcile at zero live,
        # full availability, not a drained trie
        assert pool.alloc.live_blocks == 0
        assert pool.alloc.available_blocks == pool.alloc.usable
        pool.alloc.check()
        # warm-bucket join through the PAGED programs: nothing compiles
        tc = telemetry.trace_context("warm-paged-join")
        with tc:
            got2 = _drive_session(sess, prompts[:1], 50)
        assert got2[0] == solo[0]
        assert tc.compiles == [], tc.compiles
        sess.close()
    # the prompt family DID share (prefill-once) and the twin DID
    # copy-on-write — the reuse the token-exactness claim covers
    assert pool.alloc.prefix_hits > 0
    assert pool.alloc.cow_copies > 0
    tr.release_kv_pool()


def test_decode_session_paged_exhaustion_defers_and_retire_reclaims():
    """Pool exhaustion at admission raises KVPoolExhausted BEFORE any
    device work with the session left OPEN (servd turns this into a
    deterministic queue-wait), and a retired slot returns its blocks
    to the free list MID-DECODE — the reclaim the paged design exists
    for."""
    from cxxnet_tpu.nnet.trainer import KVPoolExhausted
    tr = _trained(steps=2)
    # the smallest legal pool: one max-length sequence (6 blocks of 4)
    pool = tr.decode_kv_pool(4, pool_tokens=SEQ, prefix_reuse=False)
    assert pool.alloc.usable == SEQ // 4
    sess = tr.decode_session(4, 3, kv_pool=pool)
    # plen 6 + n_new 3 -> 8 rows -> 2 blocks per sequence
    for s in range(3):
        sess.prefill(s, [s + 1, s + 2, s + 3, s + 4, s + 5, s + 6], 7)
    assert pool.alloc.free_blocks == 0
    assert not pool.reservable(6, 3)
    with pytest.raises(KVPoolExhausted):
        sess.prefill(3, [9, 10, 11, 12, 13, 14], 7)
    assert not sess.closed            # no device work ran: still open
    sess.step()                       # ...and decoding continues
    acct = sess.kv_account()
    assert acct["paged"] == 1 and acct["blocks_held"] == 6
    assert acct["kv_bytes"] == 6 * pool.block_bytes
    sess.retire(0)                    # mid-decode reclaim
    assert pool.alloc.free_blocks == 2
    first, _ = sess.prefill(3, [9, 10, 11, 12, 13, 14], 7)
    # the deferred-then-admitted request decodes exactly like a solo
    # dispatch (deferral must not perturb the stream)
    want = tr.generate(np.asarray([[9, 10, 11, 12, 13, 14]]), 3,
                       seed=7)[0]
    assert first == want[0]
    sess.close()
    assert pool.alloc.free_blocks == pool.alloc.usable
    pool.alloc.check()
    tr.release_kv_pool()
    assert pool.closed and pool.nbytes == 0


def test_decode_session_paged_kv_account_pins_pool_nbytes():
    """The block-exact decode KV account (the PR 13
    conservative-by-one-session caveat fix): through a batching
    frontend over the PAGED backend, ``cxxnet_decode_kv_bytes`` (the
    perf ledger hook) equals the pool arrays' REAL nbytes at all
    times — free blocks included, because they are allocated HBM —
    and the cxxnet_decode_kv_block_* series ride the /metrics text."""
    from cxxnet_tpu.utils import servd, statusd
    tr = _trained(steps=2)

    class _PagedBackend:
        buckets = [2]

        def _pool(self):
            return tr.decode_kv_pool(4)

        def session(self, nslots):
            return tr.decode_session(nslots, 3, kv_pool=self._pool())

        def kv_pool_account(self):
            p = getattr(tr, "_kv_pool", None)
            return p.account() if p is not None and not p.closed \
                else None

        def kv_free_blocks(self):
            p = getattr(tr, "_kv_pool", None)
            return p.alloc.free_blocks \
                if p is not None and not p.closed else None

        def kv_fresh_blocks(self, toks):
            p = getattr(tr, "_kv_pool", None)
            if p is None or p.closed:
                return None
            return p.alloc.fresh_need(len(toks), 3, toks)

    fe = servd.ServeFrontend(None, slot_backend=_PagedBackend(),
                             batch_max=2, drain_ms=8000.0)
    fe.start()
    port = fe.listen(0)
    try:
        assert servd._ask(port, "1 2 3", timeout=120.0)
        pool = tr._kv_pool
        real = sum(int(a.nbytes) for a in pool.pools.values())
        assert real > 0 and pool.nbytes == real
        snap = fe.batch_snapshot()
        assert snap["pool"]["pool_bytes"] == real
        # THE pin: the HBM-account hook reads the pool's real nbytes —
        # not a per-session sum, not conservative, EQUAL
        assert fe.decode_kv_bytes() == real
        text = statusd.prometheus_metrics(
            {"process": 0, "uptime_s": 1.0, "counters": {},
             "gauges": {}, "hists": {}, "compiles": 0,
             "compile_s": 0.0}, batch=snap)
        assert ("cxxnet_decode_kv_pool_bytes{process=\"0\"} %d"
                % real) in text
        assert "cxxnet_decode_kv_block_total" in text
        assert "cxxnet_decode_prefix_queries_total" in text
    finally:
        fe.drain()
    tr.release_kv_pool()
    # released: the account must read 0 the moment the datapath lets go
    assert tr._kv_pool is None and pool.nbytes == 0


@pytest.mark.slow
def test_decode_session_paged_grid_token_exact():
    """The paged acceptance grid (the ISSUE pin): paged == solo across
    greedy / sampled / top_k x ragged shared-family prompt lengths x
    the learned-pos AND rope+GQA+window AND flash-decode-chunked model
    variants, all with staggered mid-decode admissions through the
    shared block pool."""
    variants = (
        {},
        dict(embed_extra="pos_embed = 0",
             attn_extra="  rope = 1\n  nkvhead = 2\n"
                        "  attn_window = 8\n"),
        dict(attn_extra="  decode_chunk = 8\n"),
    )
    for kwargs in variants:
        tr = _trained(**kwargs)
        rs = np.random.RandomState(9)
        fam = rs.randint(0, VOCAB, 12).tolist()
        prompts = [fam[:rs.randint(3, 12)] for _ in range(5)] \
            + [fam[:8], fam[:8]]              # twins: the CoW case
        pool = tr.decode_kv_pool(4, pool_tokens=4 * SEQ)
        for temp, top_k in ((0.0, 0), (1.0, 0), (0.7, 4)):
            solo = _solo_continuations(tr, prompts, 6, temp, top_k, 30)
            sess = tr.decode_session(4, 6, temperature=temp,
                                     top_k=top_k, kv_pool=pool)
            got = _drive_session(sess, prompts, 30)
            assert got == solo, (kwargs, temp, top_k)
            sess.close()
            pool.alloc.check()
        assert pool.alloc.prefix_hits > 0
        tr.release_kv_pool()
