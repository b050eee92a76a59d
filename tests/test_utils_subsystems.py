"""Tests for visualization, env config layer, and the im2rec tool.

Parity models: python/mxnet/visualization.py, docs/faq/env_var.md,
tools/im2rec.py.
"""
import glob
import os
import re
import subprocess
import sys

import numpy as np
import pytest

import incubator_mxnet_tpu as mx
from incubator_mxnet_tpu import config, visualization


def _mlp():
    data = mx.sym.var("data")
    fc1 = mx.sym.FullyConnected(data, num_hidden=16, name="fc1")
    act = mx.sym.Activation(fc1, act_type="relu", name="relu1")
    fc2 = mx.sym.FullyConnected(act, num_hidden=4, name="fc2")
    return mx.sym.softmax(fc2, name="sm")


def test_print_summary(capsys):
    out = visualization.print_summary(_mlp(), shape={"data": (2, 8)})
    # params: fc1 = 8*16+16 = 144, fc2 = 16*4+4 = 68 → 212
    assert "Total params: 212" in out
    assert "fc1(FullyConnected)" in out
    assert "relu1(Activation)" in out


def test_plot_network_dot():
    res = visualization.plot_network(_mlp(), title="net")
    src = res if isinstance(res, str) else res.source
    assert "digraph" in src
    assert '"fc1" -> "relu1"' in src and '"relu1" -> "fc2"' in src
    assert '"data"' in src          # data var shown
    assert '"fc1_weight"' not in src  # weights hidden by default


def test_config_env_layer(monkeypatch):
    assert config.get("ENGINE_TYPE") == "AsyncEngine"
    monkeypatch.setenv("MXTPU_ENGINE_TYPE", "NaiveEngine")
    assert config.naive_engine()
    monkeypatch.delenv("MXTPU_ENGINE_TYPE")
    monkeypatch.setenv("MXNET_ENGINE_TYPE", "NaiveEngine")  # fallback prefix
    assert config.naive_engine()
    monkeypatch.setenv("MXTPU_SEED", "123")
    assert config.get_int("SEED") == 123
    monkeypatch.setenv("MXTPU_PROFILER_AUTOSTART", "true")
    assert config.get_bool("PROFILER_AUTOSTART")
    doc = config.document()
    assert "MXTPU_ENGINE_TYPE" in doc and "NaiveEngine" in doc
    # generated doc is committed
    here = os.path.join(os.path.dirname(__file__), "..", "docs", "env_var.md")
    assert os.path.exists(here)


_REPO = os.path.join(os.path.dirname(os.path.abspath(__file__)), "..")


def _switches_read():
    """Every ``GRAFT_*`` name the package looks up: the quoted names in
    its source (a docstring's mention is not a lookup)."""
    names = set()
    for path in glob.glob(os.path.join(_REPO, "incubator_mxnet_tpu", "**",
                                       "*.py"), recursive=True):
        with open(path) as f:
            names.update(re.findall(r'''["'](GRAFT_[A-Z0-9_]+)["']''',
                                    f.read()))
    return sorted(names)


def _switches_documented():
    """The ``GRAFT_*`` names that head a section of docs/env_var.md."""
    with open(os.path.join(_REPO, "docs", "env_var.md")) as f:
        return {name for line in f if line.startswith("## ")
                for name in re.findall(r"GRAFT_[A-Z0-9_]+", line)}


@pytest.mark.parametrize("switch", _switches_read())
def test_switch_is_documented(switch):
    assert switch in _switches_documented()


def test_no_documented_switch_is_dead():
    assert _switches_documented() <= set(_switches_read())


def test_im2rec_list_and_pack(tmp_path):
    cv2 = pytest.importorskip("cv2")
    root = tmp_path / "images"
    for cls in ("cat", "dog"):
        d = root / cls
        d.mkdir(parents=True)
        for i in range(3):
            img = (np.random.RandomState(i).rand(8, 8, 3) * 255).astype("uint8")
            cv2.imwrite(str(d / ("%s_%d.jpg" % (cls, i))), img)
    prefix = str(tmp_path / "set")
    tool = os.path.join(os.path.dirname(__file__), "..", "tools", "im2rec.py")
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    r = subprocess.run([sys.executable, tool, prefix, str(root),
                        "--list", "--recursive"], env=env,
                       capture_output=True, text=True)
    assert r.returncode == 0, r.stderr
    lines = open(prefix + ".lst").read().strip().splitlines()
    assert len(lines) == 6
    labels = {float(ln.split("\t")[1]) for ln in lines}
    assert labels == {0.0, 1.0}

    r = subprocess.run([sys.executable, tool, prefix, str(root),
                        "--resize", "8"], env=env,
                       capture_output=True, text=True)
    assert r.returncode == 0, r.stderr
    assert os.path.exists(prefix + ".rec") and os.path.exists(prefix + ".idx")

    from incubator_mxnet_tpu import recordio
    rec = recordio.MXIndexedRecordIO(prefix + ".idx", prefix + ".rec", "r")
    keys = sorted(rec.keys)
    assert len(keys) == 6
    hdr, img = recordio.unpack_img(rec.read_idx(keys[0]))
    assert img.shape[2] == 3 and hdr.label in (0.0, 1.0)


def test_rec2idx_roundtrip(tmp_path):
    """tools/rec2idx.py: an index built from a bare .rec enables read_idx
    random access identical to the write-time index."""
    import importlib.util
    import os
    import numpy as np
    from incubator_mxnet_tpu import recordio

    rec = str(tmp_path / "t.rec")
    idx = str(tmp_path / "t.idx")
    w = recordio.MXRecordIO(rec, "w")
    payloads = [b"rec-%d-" % i + bytes(np.arange(i % 7, dtype=np.uint8))
                for i in range(9)]
    for pl in payloads:
        w.write(pl)
    w.close()

    spec = importlib.util.spec_from_file_location(
        "rec2idx", os.path.join(os.path.dirname(__file__), "..",
                                "tools", "rec2idx.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    assert mod.create_index(rec, idx) == len(payloads)

    r = recordio.MXIndexedRecordIO(idx, rec, "r")
    for i in (0, 4, 8, 2):
        assert r.read_idx(i) == payloads[i]
    r.close()


def test_parse_log_markdown(tmp_path):
    import importlib.util
    import os
    spec = importlib.util.spec_from_file_location(
        "parse_log", os.path.join(os.path.dirname(__file__), "..",
                                  "tools", "parse_log.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    lines = [
        "INFO:root:Epoch[0] Train-accuracy=0.5",
        "INFO:root:Epoch[0] Time cost=12.5",
        "INFO:root:Epoch[0] Validation-accuracy=0.55",
        "INFO:root:Epoch[1] Train-accuracy=0.75",
        "INFO:root:Epoch[1] Time cost=11.0",
    ]
    data, cols = mod.parse(lines)
    assert data[0]["train-accuracy"] == 0.5
    assert data[0]["val-accuracy"] == 0.55
    assert data[1]["time"] == 11.0
    md = mod.to_markdown(data, cols)
    assert md.startswith("| epoch |") and "| 1 | 0.75" in md
    # scientific notation + regex-special metric names (round-4 advisor)
    data2, _ = mod.parse(["INFO:root:Epoch[2] Train-loss=1e-05"], ("loss",))
    assert data2[2]["train-loss"] == 1e-05
    data3, _ = mod.parse(
        ["INFO:root:Epoch[0] Train-top_k_accuracy_5=0.9"],
        ("top_k_accuracy_5",))
    assert data3[0]["train-top_k_accuracy_5"] == 0.9
