"""chip_smoke.py off the chip: it refuses any platform but a TPU, and its
phases' own commands — native build, corpus + im2bin, AlexNet through
bin/cxxnet from the imgbin pipeline, LM training + checkpoint, both serve
configurations over TCP — run end to end at a tiny size on the CPU, so
chip time is spent on the chip's problems only."""

import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

import chip_smoke  # noqa: E402

TINY = dict(
    chip_smoke.FULL, img_hw=72, crop=67, batch=8, n_train=40, n_test=8,
    n_class=4, vocab=64, dim=32, nhead=2, nlayer=1, seq=128, lm_batch=4,
    dtype="float32", lm_max_steps=41, lm_eval_every=20, lm_min_acc=0.0,
    prompt_lens=[8, 12], gen_new=6, n_prompts=4, kv_block=32)


def test_refuses_a_cpu_and_names_it():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run([sys.executable, os.path.join(REPO, "chip_smoke.py")],
                       capture_output=True, text=True, timeout=300, env=env)
    assert p.returncode != 0
    assert "platform 'cpu'" in p.stderr and "not a TPU" in p.stderr
    assert p.stdout.strip() == "", "a refused run must print no result"


def test_refuses_a_directory_that_is_not_the_repo(tmp_path):
    import shutil
    shutil.copy(os.path.join(REPO, "chip_smoke.py"), tmp_path)
    p = subprocess.run([sys.executable, str(tmp_path / "chip_smoke.py")],
                       capture_output=True, text=True, timeout=60)
    assert p.returncode != 0 and "not the root of the repo" in p.stderr
    assert p.stdout.strip() == ""


def test_phases_run_end_to_end_at_tiny_size_on_cpu(monkeypatch, tmp_path):
    if not chip_smoke.shutil.which("make"):
        pytest.skip("native toolchain unavailable")
    monkeypatch.setattr(chip_smoke, "WORK", str(tmp_path / "work"))
    monkeypatch.setattr(chip_smoke, "LOGS", str(tmp_path / "logs"))
    monkeypatch.setenv("JAX_PLATFORMS", "cpu")
    monkeypatch.delenv("XLA_FLAGS", raising=False)   # one device, as there
    ok, summary = chip_smoke.run_smoke(TINY, require_tpu=False)
    phases = summary["phases"]
    assert ok, {n: r.get("error") for n, r in phases.items()}
    for name in ("alexnet", "lm", "serve_solo", "serve_paged"):
        assert phases[name]["outcome"] == "passed", phases[name]
    # one device: the four-chip phases are reported as not run, never passed
    for name in ("alexnet_dp4", "alexnet_dp4_zero"):
        assert phases[name]["outcome"] == "not run: 1 device"
    assert summary["device"] == {"platform": "cpu", "kind": "cpu",
                                 "count": 1}
    # the last stdout line: exactly ok + device, whatever else the result has
    assert chip_smoke.json.loads(chip_smoke.verdict_line(summary)) == {
        "ok": True,
        "device": {"platform": "cpu", "kind": "cpu", "count": 1}}
    assert phases["alexnet"]["steps"] == 5
    assert summary["switches"] == {
        "use_pallas": False, "pallas_interpret": True,
        "channels_last": False, "flash_mosaic": False}
    assert summary["compile_cache"]["dir"]
