#!/usr/bin/env python
"""Train the lm.conf transformer on a synthetic character grammar.

The corpus is deterministic-but-nontrivial: each sequence is a cyclic
alphabet walk with a random phase and stride, so the next character is
exactly predictable from the prefix — a trained causal LM must reach
~100% next-token accuracy, an untrained one sits near 1/vocab.

Usage: python train_lm.py [steps] [conf]   (~400 adam steps reach 100%)

``conf`` defaults to lm.conf; pass lm_pipeline.conf to train the deeper
trunk on the composed pipeline x tensor x data mesh (8 devices — on a
machine without them, prefix
XLA_FLAGS=--xla_force_host_platform_device_count=8 JAX_PLATFORMS=cpu).
"""

import os
import sys

sys.path.insert(0, os.path.join(
    os.path.dirname(os.path.abspath(__file__)), "..", ".."))

import numpy as np

from cxxnet_tpu.io.data import DataBatch
from cxxnet_tpu.nnet.trainer import Trainer
from cxxnet_tpu.utils.config import ConfigIterator

VOCAB = 28
SEQ = 64


def make_batch(rs, batch=16, seq=SEQ):
    """Cyclic walks: tok[t] = (phase + stride * t) % VOCAB. The alphabet
    stays VOCAB however wide the model's own vocabulary is."""
    phase = rs.randint(0, VOCAB, (batch, 1))
    stride = rs.randint(1, 5, (batch, 1))
    t = np.arange(seq + 1)[None, :]
    toks = (phase + stride * t) % VOCAB          # (b, seq+1)
    b = DataBatch()
    b.data = toks[:, :seq].reshape(batch, 1, 1, seq).astype(np.float32)
    b.label = toks[:, 1:].astype(np.float32)     # next-token targets (b, seq)
    b.batch_size = batch
    return b


def next_token_accuracy(tr, batch):
    probs = tr.extract_feature(batch, "top[-1]")   # (b, vocab, 1, seq)
    pred = probs.reshape(probs.shape[0], probs.shape[1], -1).argmax(axis=1)
    # score the second half: the prefix there always determines the walk
    half = pred.shape[1] // 2
    return float((pred[:, half:] == batch.label[:, half:]).mean())


def generate(tr, prompts, n_new):
    """Greedy autoregressive continuation of a (batch, prefix_len) prompt
    matrix via the KV-cached decode scan (Trainer.generate — one O(L*d)
    step per token; tests/test_decode.py pins it against the naive
    full-prefix recompute)."""
    return tr.generate(prompts, min(n_new, SEQ - prompts.shape[1]))


def main(steps=400, dev=None, seed=None, conf_name="lm.conf"):
    conf = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                        conf_name)
    overrides = []
    if dev:
        overrides.append("dev=%s" % dev)
    if seed is not None:
        overrides.append("seed=%d" % seed)
    tr = Trainer()
    for k, v in ConfigIterator(conf, overrides):
        tr.set_param(k, v)
    tr.init_model()
    rs = np.random.RandomState(0)
    eval_b = make_batch(np.random.RandomState(999))
    print("accuracy before: %.3f" % next_token_accuracy(tr, eval_b))
    for i in range(steps):
        tr.update(make_batch(rs))
        if (i + 1) % 50 == 0:
            print("step %d: accuracy %.3f"
                  % (i + 1, next_token_accuracy(tr, eval_b)))
    acc = next_token_accuracy(tr, eval_b)
    print("final next-token accuracy: %.3f" % acc)
    # greedy generation demo: continue the eval walks from their first half
    half = SEQ // 2
    prompts = np.asarray(eval_b.data).reshape(-1, SEQ)[:, :half].astype(np.int64)
    cont = generate(tr, prompts, half)
    truth = np.concatenate(
        [np.asarray(eval_b.data).reshape(-1, SEQ)[:, half:],
         np.asarray(eval_b.label)[:, -1:]], axis=1)[:, :half]
    gen_acc = float((cont == truth).mean())
    print("greedy generation accuracy over %d tokens: %.3f" % (half, gen_acc))
    return acc


if __name__ == "__main__":
    main(int(sys.argv[1]) if len(sys.argv) > 1 else 400,
         conf_name=sys.argv[2] if len(sys.argv) > 2 else "lm.conf")
