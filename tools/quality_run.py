#!/usr/bin/env python
"""Convergence/quality evidence runner -> QUALITY.md.

Runs the example recipes (MNIST.conf, MNIST_CONV.conf, a bowl-shaped conv
recipe) to their full round counts and records final train/test error per
seed. The reference's quality claim is ~2% error on real MNIST after the
15-round MLP recipe (reference example/MNIST/README.md); this sandbox has
zero egress, so the corpora here are generated (tests/synth_mnist.py,
bit-identical idx format):

* easy  — the test-suite corpus (noise 20): every recipe must reach 0 error
  (capacity/sanity: the net memorizes a separable task through the full
  io -> augment -> trainer path).
* hard  — 10k/2k glyph images (make_glyph_dataset): each class is a
  distinct shape drawn at a jittered position over sigma-60 pixel noise.
  Like real MNIST, test error lands in the low percents for the conv
  recipe and conv beats the mlp by a wide margin (translation jitter is
  exactly what convolution's inductive bias buys); both must be stable
  across seeds.

Usage: python tools/quality_run.py <dev> [out.md]   (from the repo root;
``dev`` is ``cpu`` or ``tpu`` and is what every recipe runs on — a recipe
that cannot get it fails). The CLI recipes run as children one at a time;
this process touches jax only after the last of them has exited.
"""

import os
import re
import subprocess
import sys
import tempfile
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)
sys.path.insert(0, os.path.join(REPO, "tests"))

_RECIPE_CONF = """
data = train
iter = mnist
    path_img = "{dir}/train-images-idx3-ubyte.gz"
    path_label = "{dir}/train-labels-idx1-ubyte.gz"
    input_flat = 0
    shuffle = 1
iter = end
eval = test
iter = mnist
    input_flat = 0
    path_img = "{dir}/t10k-images-idx3-ubyte.gz"
    path_label = "{dir}/t10k-labels-idx1-ubyte.gz"
iter = end
{netconfig}
input_shape = 1,28,28
batch_size = 100
dev = {dev}
save_model = 0
{train_params}metric[label] = error
"""

_BOWL_NET = """netconfig=start
layer[0->1] = conv:c1
  kernel_size = 5
  nchannel = 16
  random_type = xavier
layer[1->2] = relu
layer[2->3] = max_pooling
  kernel_size = 3
  stride = 2
layer[3->4] = flatten
layer[4->5] = fullc:f1
  nhidden = 128
  random_type = xavier
layer[5->6] = relu
layer[6->7] = fullc:f2
  nhidden = 10
  random_type = xavier
layer[7->7] = softmax
netconfig=end"""

_BOWL_PARAMS = "max_round = 12\nnum_round = 12\neta = 0.05\n" \
    "momentum = 0.9\nwd = 0.0001\n"
_VIT_PARAMS = "max_round = 15\nnum_round = 15\nupdater = adamw\n" \
    "eta = 0.001\nwd = 0.01\n"



def run_cli(conf_path, overrides, cwd):
    cmd = [sys.executable, os.path.join(REPO, "bin", "cxxnet"),
           conf_path] + overrides
    t0 = time.time()
    p = subprocess.run(cmd, cwd=cwd, capture_output=True, text=True,
                       timeout=3600)
    assert p.returncode == 0, p.stdout + p.stderr
    text = p.stdout + p.stderr   # metric lines go to stderr (reference)
    rounds = re.findall(
        r"\[(\d+)\]\s+train-error:([\d.]+)\s+test-error:([\d.]+)", text)
    assert rounds, "no metric lines in output:\n" + text[-2000:]
    last = rounds[-1]
    return {"rounds": int(last[0]) + 1, "train_err": float(last[1]),
            "test_err": float(last[2]), "wall_s": round(time.time() - t0, 1)}


def main():
    if len(sys.argv) < 2 or sys.argv[1] not in ("cpu", "tpu"):
        sys.exit(__doc__)
    dev = sys.argv[1]
    out_path = sys.argv[2] if len(sys.argv) > 2 else \
        os.path.join(REPO, "QUALITY.md")
    from synth_mnist import make_dataset, make_glyph_dataset

    results = []

    with tempfile.TemporaryDirectory() as td:
        for corpus, maker, kw in (
                ("easy", make_dataset,
                 dict(n_train=600, n_test=200, noise=20.0)),
                ("hard", make_glyph_dataset,
                 dict(n_train=10000, n_test=2000))):
            for seed in (0, 1, 2):
                droot = os.path.join(td, "%s_s%d" % (corpus, seed))
                os.makedirs(os.path.join(droot, "data"))
                os.makedirs(os.path.join(droot, "models"), exist_ok=True)
                maker(os.path.join(droot, "data"), seed=seed, **kw)
                for name, conf, extra in (
                        ("mnist_mlp",
                         os.path.join(REPO, "example/MNIST/MNIST.conf"),
                         ["dev=%s" % dev, "seed=%d" % seed,
                          "save_model=0"]),
                        ("mnist_conv",
                         os.path.join(REPO, "example/MNIST/MNIST_CONV.conf"),
                         ["dev=%s" % dev, "seed=%d" % seed,
                          "save_model=0"]),
                ):
                    r = run_cli(conf, extra, droot)
                    r.update(recipe=name, corpus=corpus, seed=seed)
                    results.append(r)
                    print(r, flush=True)
                # bowl-shaped conv recipe (kaggle_bowl-like trunk)
                bowl = os.path.join(droot, "bowl_like.conf")
                with open(bowl, "w") as f:
                    f.write(_RECIPE_CONF.format(
                        dir=os.path.join(droot, "data"), dev=dev,
                        netconfig=_BOWL_NET, train_params=_BOWL_PARAMS))
                r = run_cli(bowl, ["seed=%d" % seed], droot)
                r.update(recipe="bowl_like_conv", corpus=corpus, seed=seed)
                results.append(r)
                print(r, flush=True)
                # ViT recipe (patch-embed conv -> im2seq -> attention):
                # the DSL-composed vision-transformer family end to end
                from cxxnet_tpu.models import vit_netconfig
                vit = os.path.join(droot, "vit_like.conf")
                with open(vit, "w") as f:
                    f.write(_RECIPE_CONF.format(
                        dir=os.path.join(droot, "data"), dev=dev,
                        netconfig=vit_netconfig(
                            10, image_hw=28, patch=4, dim=48,
                            nhead=4, nlayer=2),
                        train_params=_VIT_PARAMS))
                r = run_cli(vit, ["seed=%d" % seed], droot)
                r.update(recipe="vit_like", corpus=corpus, seed=seed)
                results.append(r)
                print(r, flush=True)

    # transformer-LM recipe (the long-context family, beyond the
    # reference): cyclic-walk corpus, 400 adam steps, next-token accuracy
    # (in this process: every CLI child above has exited, so the
    # device is free to take)
    from cxxnet_tpu.utils import enable_compile_cache
    enable_compile_cache()
    sys.path.insert(0, os.path.join(REPO, "example", "transformer"))
    import train_lm
    lm_rows = []
    for seed in (0, 1, 2):
        t0 = time.time()
        acc = train_lm.main(steps=400, dev=dev, seed=seed)
        lm_rows.append(dict(seed=seed, steps=400, acc=acc,
                            wall_s=time.time() - t0))
        print(lm_rows[-1], flush=True)

    lines = [
        "# QUALITY — convergence evidence",
        "",
        "Recipes run end-to-end through the CLI (`bin/cxxnet <conf>`) on "
        "backend **%s**; corpora generated by tests/synth_mnist.py (real "
        "MNIST is unreachable: zero-egress sandbox — the reference's ~2%% "
        "claim on real MNIST is reproduced in *structure*: low, "
        "seed-stable error on the hard corpus, 0 on the easy one, "
        "conv <= mlp)." % dev,
        "",
        "| recipe | corpus | seed | rounds | train err | test err | wall s |",
        "|---|---|---|---|---|---|---|",
    ]
    for r in results:
        lines.append("| %s | %s | %d | %d | %.4f | %.4f | %.1f |" % (
            r["recipe"], r["corpus"], r["seed"], r["rounds"],
            r["train_err"], r["test_err"], r["wall_s"]))

    lines.append("")
    lines.append("Transformer LM (example/transformer, cyclic-walk corpus, "
                 "400 adam steps):")
    lines.append("")
    lines.append("| recipe | seed | steps | next-token acc | wall s |")
    lines.append("|---|---|---|---|---|")
    for r in lm_rows:
        lines.append("| transformer_lm | %d | %d | %.4f | %.1f |" % (
            r["seed"], r["steps"], r["acc"], r["wall_s"]))

    # aggregate check lines
    import statistics as st
    lines.append("")
    for recipe in ("mnist_mlp", "mnist_conv", "bowl_like_conv",
                   "vit_like"):
        hard = [r["test_err"] for r in results
                if r["recipe"] == recipe and r["corpus"] == "hard"]
        easy = [r["test_err"] for r in results
                if r["recipe"] == recipe and r["corpus"] == "easy"]
        lines.append(
            "- **%s**: easy test err %s; hard test err mean %.4f "
            "(spread %.4f over 3 seeds)" % (
                recipe, easy, st.mean(hard),
                max(hard) - min(hard)))
    with open(out_path, "w") as f:
        f.write("\n".join(lines) + "\n")
    print("wrote", out_path)

    # acceptance criteria — regressions must FAIL the run, not just be
    # recorded (verify skill step 7 relies on the exit code)
    bad = []
    for r in results:
        if r["corpus"] == "easy" and r["test_err"] > 0.0:
            bad.append("easy-corpus error %.4f on %s seed %d"
                       % (r["test_err"], r["recipe"], r["seed"]))
    hards = {rec: [r["test_err"] for r in results
                   if r["recipe"] == rec and r["corpus"] == "hard"]
             for rec in ("mnist_mlp", "mnist_conv", "bowl_like_conv",
                         "vit_like")}
    if st.mean(hards["mnist_conv"]) > st.mean(hards["mnist_mlp"]):
        bad.append("conv does not beat mlp on the hard corpus")
    if st.mean(hards["mnist_conv"]) > 0.15:
        bad.append("conv hard error %.3f implausibly high"
                   % st.mean(hards["mnist_conv"]))
    for rec, errs in hards.items():
        if max(errs) - min(errs) > 0.1:
            bad.append("%s hard error unstable across seeds: %s"
                       % (rec, errs))
    lm_accs = [r["acc"] for r in lm_rows]
    if min(lm_accs) < 0.90:
        bad.append("transformer_lm next-token acc below 0.90: %s" % lm_accs)
    if st.mean(lm_accs) < 0.93:
        bad.append("transformer_lm mean acc %.3f below 0.93"
                   % st.mean(lm_accs))
    if bad:
        print("QUALITY REGRESSION:\n  " + "\n  ".join(bad))
        sys.exit(1)
    print("quality criteria met")


if __name__ == "__main__":
    main()
