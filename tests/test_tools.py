"""Tooling tier (§2.6): bandwidth, flakiness_checker, gen_api_docs, and
the convert_model CLI all run end-to-end in-suite."""
import importlib.util
import json
import os
import subprocess
import sys

import numpy as np

import incubator_mxnet_tpu as mx

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


from capi_utils import subprocess_env as _cpu_env   # shared CPU-pinned env


def _load_tool(name):
    spec = importlib.util.spec_from_file_location(
        f"tool_{name}", os.path.join(REPO, "tools", f"{name}.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_bandwidth_measures_collectives():
    bw = _load_tool("bandwidth")
    rows = bw.measure([0.5], reps=2)
    assert len(rows) == 1
    row = rows[0]
    assert row["h2d_gbps"] > 0 and row["d2h_gbps"] > 0
    # the suite runs on the forced 8-device mesh: collective rows present
    if row["devices"] > 1:
        for k in ("allreduce_gbps", "allgather_gbps",
                  "reduce_scatter_gbps"):
            assert row[k] > 0, (k, row)


def test_bandwidth_calib_writes_where_told_and_is_what_roofline_reads(
        tmp_path, monkeypatch):
    """`--calib` writes the file it is told to (by default `calib.json` in
    the working directory, never a path inside the checkout), in the
    format `inspect.roofline.load_calibration` takes."""
    from incubator_mxnet_tpu.inspect import roofline
    bw = _load_tool("bandwidth")
    monkeypatch.chdir(tmp_path)
    assert not os.path.isabs(bw.DEFAULT_CALIB_PATH)
    cal = bw.write_calibration(size_mb=1, reps=1)
    with open(tmp_path / bw.DEFAULT_CALIB_PATH) as f:
        written = json.load(f)
    assert written == cal and written["format_version"] == 1
    assert written["platform"] and "device_kind" in written
    assert written["probes"]["membw"]["triad_gbps"] > 0
    named = str(tmp_path / "sub.json")
    bw.write_calibration(named, peak_tflops=2.0, size_mb=1, reps=1)
    got = roofline.load_calibration(path=named)
    assert got["peak_flops"] == 2.0e12 and got["source"] \
        == "tools/bandwidth.py --calib"
    assert got["ridge_flop_per_byte"] == \
        got["peak_flops"] / got["peak_bytes_per_sec"]


def test_flakiness_checker_normalize():
    fc = _load_tool("flakiness_checker")
    assert fc.normalize("tests/test_gluon.py::test_x") \
        == "tests/test_gluon.py::test_x"
    assert fc.normalize("test_gluon.test_x") \
        == os.path.join("tests", "test_gluon.py") + "::test_x"


def test_gen_api_docs_emits_pages(tmp_path):
    r = subprocess.run(
        [sys.executable, os.path.join(REPO, "tools", "gen_api_docs.py"),
         "--out", str(tmp_path)],
        capture_output=True, text=True, timeout=600, cwd=REPO)
    assert r.returncode == 0, r.stderr[-2000:]
    assert "SKIP" not in r.stdout, r.stdout  # every module must render
    pages = os.listdir(tmp_path)
    assert "README.md" in pages and len(pages) > 25
    nn_page = (tmp_path / "gluon_nn.md").read_text()
    assert "Conv2D" in nn_page and "BatchNorm" in nn_page


def test_convert_model_cli_auto_map(tmp_path):
    from incubator_mxnet_tpu.gluon.model_zoo import vision, model_store
    mx.seed(9)
    net = vision.alexnet()
    net.initialize()
    x = mx.np.zeros((1, 3, 224, 224))
    net(x)
    foreign = {f"zoo_p{i}": p.data().asnumpy()
               for i, (_, p) in enumerate(net.collect_params().items())}
    pfile = str(tmp_path / "zoo.params")
    model_store.save_params_file(pfile, foreign)
    out = str(tmp_path / "alexnet.npz")
    r = subprocess.run(
        [sys.executable, os.path.join(REPO, "tools", "convert_model.py"),
         pfile, out, "--auto-map", "alexnet"],
        capture_output=True, text=True, timeout=600, cwd=REPO)
    assert r.returncode == 0, r.stderr[-2000:]
    assert "auto-map" in r.stdout
    with np.load(out) as f:
        assert len(f.files) == len(foreign)


def test_parse_log_extracts_metrics(tmp_path):
    """≙ reference tools/parse_log.py: epoch metrics + speed out of mixed
    log styles."""
    import runpy
    mod = runpy.run_path(os.path.join(REPO, "tools", "parse_log.py"))
    assert mod["_self_test"]()
    f = tmp_path / "t.log"
    f.write_text("Epoch[0] Speed: 100.0 samples/sec accuracy=0.25\n"
                 "Epoch[1] Speed: 120.0 samples/sec accuracy=0.75\n")
    out = subprocess.run(
        [sys.executable, os.path.join(REPO, "tools", "parse_log.py"),
         str(f), "--format", "csv"],
        capture_output=True, text=True, env=_cpu_env(), timeout=120)
    assert out.returncode == 0
    assert "0,0.25,100" in out.stdout.replace(" ", "")


def test_diagnose_runs(tmp_path):
    """tools/diagnose.py prints env + package + device sections without
    crashing, even when the accelerator is unreachable."""
    env = _cpu_env()
    env["DIAGNOSE_FORCE_CPU"] = "1"   # keep the probe off the real chip
    r = subprocess.run(
        [sys.executable, os.path.join(REPO, "tools", "diagnose.py")],
        capture_output=True, text=True, env=env, timeout=300)
    assert r.returncode == 0, r.stderr
    for section in ("Python Info", "Package Versions", "Framework",
                    "Devices"):
        assert section in r.stdout


def test_name_and_attr_scopes():
    """mx.name.Prefix / NameManager and mx.attribute.AttrScope drive
    symbol naming + attributes (≙ name.py / attribute.py)."""
    import incubator_mxnet_tpu as mx
    with mx.name.Prefix("enc_"):
        s = mx.sym.FullyConnected(mx.sym.Variable("data"), num_hidden=4)
        assert s.name.startswith("enc_fullyconnected")
    with mx.name.NameManager():
        a = mx.sym.Activation(mx.sym.Variable("x"), act_type="relu")
        b = mx.sym.Activation(mx.sym.Variable("y"), act_type="relu")
        assert a.name == "activation0" and b.name == "activation1"
    # reference Prefix semantics: the prefix applies to EXPLICIT names too
    with mx.name.Prefix("zzz_"):
        s = mx.sym.Activation(mx.sym.Variable("x"), act_type="relu",
                              name="mine")
        assert s.name == "zzz_mine"
    with mx.attribute.AttrScope(__group__="backbone"):
        with mx.attribute.AttrScope(lr_mult="0.1"):
            s = mx.sym.Activation(mx.sym.Variable("x"), act_type="relu")
    attrs = s.list_attr()
    assert attrs.get("__group__") == "backbone"
    assert attrs.get("lr_mult") == "0.1"
    # scope attrs reach Variables and auto-created param slots, and a
    # scope key colliding with an op PARAM stays metadata (no_bias must
    # not drop the bias slot)
    with mx.attribute.AttrScope(lr_mult="0.5", no_bias="True"):
        v = mx.sym.Variable("w")
        fc = mx.sym.FullyConnected(mx.sym.Variable("d"), num_hidden=4)
    assert v.list_attr().get("lr_mult") == "0.5"
    assert any(n.endswith("_bias") for n in fc.list_arguments()), \
        fc.list_arguments()
    import pytest as _pytest
    with _pytest.raises(mx.MXNetError):
        mx.attribute.AttrScope(bad=3)
