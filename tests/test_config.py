"""Config tokenizer tests — semantics of the reference config format
(src/utils/config.h)."""

import numpy as np
import pytest

from cxxnet_tpu.utils.config import ConfigError, parse_config_string


def test_basic_pairs():
    cfg = parse_config_string("a = 1\nb=2\n  c   =    hello\n")
    assert cfg == [("a", "1"), ("b", "2"), ("c", "hello")]


def test_comments_and_blank_lines():
    cfg = parse_config_string("# comment\na = 1 # trailing\n\n#x=9\nb = 2\n")
    assert cfg == [("a", "1"), ("b", "2")]


def test_quoted_strings():
    cfg = parse_config_string('path = "./data/my file.bin"\n')
    assert cfg == [("path", "./data/my file.bin")]


def test_escaped_quote():
    cfg = parse_config_string(r'path = "a\"b"')
    assert cfg == [("path", 'a"b')]


def test_multiline_single_quote():
    cfg = parse_config_string("doc = 'line1\nline2'\n")
    assert cfg == [("doc", "line1\nline2")]


def test_repeat_keys_keep_order():
    cfg = parse_config_string("iter = mnist\nshuffle = 1\niter = end\n")
    assert cfg == [("iter", "mnist"), ("shuffle", "1"), ("iter", "end")]


def test_no_space_around_equals():
    cfg = parse_config_string("layer[0->1]=conv:cv1\n")
    assert cfg == [("layer[0->1]", "conv:cv1")]


def test_unterminated_string_raises():
    with pytest.raises(ConfigError):
        parse_config_string('a = "unterminated\n')


def test_netconfig_section_tokens():
    text = """
netconfig=start
layer[+1:fc1] = fullc:fc1
  nhidden = 100
layer[+0] = softmax
netconfig=end
"""
    cfg = parse_config_string(text)
    assert cfg[0] == ("netconfig", "start")
    assert cfg[1] == ("layer[+1:fc1]", "fullc:fc1")
    assert cfg[2] == ("nhidden", "100")
    assert cfg[3] == ("layer[+0]", "softmax")
    assert cfg[4] == ("netconfig", "end")


def test_metric_recall_topn():
    """rec@n: fraction of true labels inside the top-n predictions
    (reference utils/metric.h MetricRecall)."""
    from cxxnet_tpu.utils.metric import create_metric

    m = create_metric("rec@2")
    pred = np.array([[0.1, 0.5, 0.4],     # top-2 = {1, 2}
                     [0.7, 0.2, 0.1],     # top-2 = {0, 1}
                     [0.3, 0.3, 0.4]])    # top-2 includes 2
    labels = np.array([[1.0], [2.0], [2.0]])
    m.add_eval(pred, labels)
    assert m.get() == pytest.approx(2.0 / 3.0)

    with pytest.raises(ValueError):
        create_metric("rec@5").add_eval(np.zeros((2, 3)), np.zeros((2, 1)))


def test_dist_worker_corpus_sharding(tmp_path):
    """dist_num_worker/dist_worker_rank split a multi-part corpus into
    disjoint contiguous slices covering everything
    (reference iter_thread_imbin-inl.hpp:189-220)."""
    from cxxnet_tpu.io.iter_image import ImagePageIterator

    # 4 parts, one record name per part
    for i in range(4):
        (tmp_path / ("part_%d.lst" % i)).write_text("%d 0 img%d.jpg\n" % (i, i))
        (tmp_path / ("part_%d.bin" % i)).write_bytes(b"")
    seen = []
    for rank in range(2):
        it = ImagePageIterator()
        it.set_param("image_conf_prefix", str(tmp_path / "part_%d"))
        it.set_param("image_conf_ids", "0-3")
        it.set_param("dist_num_worker", "2")
        it.set_param("dist_worker_rank", str(rank))
        it._parse_image_conf()
        seen.append([p.split("part_")[-1] for p in it.path_imgbin])
    assert seen[0] == ["0.bin", "1.bin"]
    assert seen[1] == ["2.bin", "3.bin"]

    # too many workers for the part list must fail fast
    it = ImagePageIterator()
    it.set_param("image_conf_prefix", str(tmp_path / "part_%d"))
    it.set_param("image_conf_ids", "0-1")
    it.set_param("dist_num_worker", "5")
    it.set_param("dist_worker_rank", "4")
    with pytest.raises(AssertionError):
        it._parse_image_conf()


def test_no_environment_variable_chooses_a_lowering():
    """The package reads five ``CXXNET_*`` variables, all operational
    (lock-order checks, the multi-host launch, the native library): which
    lowering an op takes is decided from the platform, the layout and the
    shape, and an A/B is two checkouts, never a switch."""
    import glob
    import os
    import re
    allowed = {"CXXNET_LOCKRANK", "CXXNET_NUM_WORKER", "CXXNET_WORKER_RANK",
               "CXXNET_TPU_NATIVE", "CXXNET_TPU_NATIVE_LIB"}
    root = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "cxxnet_tpu")
    found = {}
    for path in glob.glob(os.path.join(root, "**", "*.py"), recursive=True):
        with open(path, encoding="utf-8") as f:
            for name in re.findall(r"\bCXXNET_[A-Z0-9_]+\b", f.read()):
                found.setdefault(name, os.path.relpath(path, root))
    assert set(found) == allowed, {k: v for k, v in found.items()
                                   if k not in allowed}
