"""The example/transformer LM: DSL-built causal transformer learns a
deterministic grammar (exercises embed/attention/add/conv-FFN/seq-softmax
end to end, incl. the softmax seq=1 loss)."""

import os
import pytest
import sys

import numpy as np

sys.path.insert(0, os.path.join(os.path.dirname(__file__),
                                "..", "example", "transformer"))

import train_lm  # noqa: E402


def test_lm_learns_grammar():
    from cxxnet_tpu.nnet.trainer import Trainer
    from cxxnet_tpu.utils.config import ConfigIterator
    conf = os.path.join(os.path.dirname(__file__), "..",
                        "example", "transformer", "lm.conf")
    tr = Trainer()
    for k, v in ConfigIterator(conf, ["dev=cpu"]):
        tr.set_param(k, v)
    tr.init_model()
    rs = np.random.RandomState(0)
    eval_b = train_lm.make_batch(np.random.RandomState(999))
    before = train_lm.next_token_accuracy(tr, eval_b)
    assert before < 0.2, "untrained accuracy should be near chance"
    for _ in range(120):
        tr.update(train_lm.make_batch(rs))
    after = train_lm.next_token_accuracy(tr, eval_b)
    assert after > 0.7, "LM failed to learn the grammar: %.3f" % after


@pytest.mark.slow
def test_lm_pipeline_conf_learns_grammar():
    """lm_pipeline.conf: the composed pp x tp x dp + ZeRO-1 example
    trains the same grammar through the example driver."""
    acc = train_lm.main(steps=120, dev="cpu:0-7",
                        conf_name="lm_pipeline.conf")
    assert acc > 0.7, "composed-mesh LM accuracy %.3f" % acc


@pytest.mark.slow
def test_serve_lm_demo_agrees_across_surfaces():
    """example/transformer/serve_lm.py: in-process generate, the
    exported prefill/step artifact loop, and tensor-parallel serving
    produce identical tokens (run short — agreement holds at any
    training step). Slow tier (tier-1 budget): the per-surface
    token-exactness is pinned in tier-1 by test_decode/test_export;
    this adds the cross-surface demo agreement."""
    import subprocess
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable,
         os.path.join(os.path.dirname(__file__), "..", "example",
                      "transformer", "serve_lm.py"), "25", "cpu"],
        capture_output=True, text=True, timeout=900, env=env,
        cwd=os.path.join(os.path.dirname(__file__), "..", "example",
                         "transformer"))
    assert p.returncode == 0, (p.stdout[-1500:], p.stderr[-1500:])
    assert "SERVING DEMO PASSED" in p.stdout
    assert "artifact decode loop: MATCH" in p.stdout
    assert "tensor-parallel serving (mp=2): MATCH" in p.stdout
