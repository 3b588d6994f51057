"""The harness finds every configuration, mix and metric by name, and
refuses to run without a TPU."""
import json
import os
import shutil
import subprocess
import sys

import pytest

from bench import harness, peaks

BENCH = harness.benchmark()


def test_every_cell_finds_its_files():
    for cell in BENCH["workloads"]:
        cfg = harness.config(cell["config"])
        mx = harness.mix(cell["traffic"])
        assert cfg["name"] == cell["config"]
        assert harness.reference(cfg).logits is not None
        assert mx["loop"] in ("open", "closed")
        assert cell["chips"] in (1, 4)
    files = {c["name"]: c["file"] for c in BENCH["configs"]}
    for name, path in files.items():
        assert os.path.isfile(harness.ROOT / path)
        assert json.load(open(harness.ROOT / path))["name"] == name


def test_every_per_layer_metric_has_a_reader():
    for m in BENCH["per_layer"]:
        assert callable(harness.reader(m["name"]))


def test_config_files_keep_published_widths():
    g = harness.config("granite3-2b.sd4")
    assert (g["hidden_size"], g["num_hidden_layers"], g["head_dim"],
            g["intermediate_size"], g["vocab_size"]) == (2048, 40, 64, 8192,
                                                         49155)
    d = harness.config("dscoder33b-l8.sd1")
    assert (d["hidden_size"], d["num_attention_heads"], d["head_dim"],
            d["intermediate_size"], d["vocab_size"]) == (7168, 56, 128,
                                                         19200, 32256)
    assert d["reduced"] == ["num_hidden_layers"]


def test_unknown_device_kind_is_an_error():
    assert peaks.peaks_for("TPU v5 lite")["bf16_flops_per_s"] == 197e12
    with pytest.raises(KeyError):
        peaks.peaks_for("cpu")


def _run(cwd, env_extra=None):
    env = dict(os.environ, JAX_PLATFORMS="cpu", **(env_extra or {}))
    env.pop("PYTHONPATH", None)
    return subprocess.run(
        [sys.executable, "bench/run.py", "--workload",
         BENCH["workloads"][0]["name"], "--seed", "1", "--seconds", "1",
         "--trace", "0"], cwd=cwd, env=env, capture_output=True, text=True,
        timeout=300)


def test_run_exits_nonzero_without_a_tpu():
    p = _run(harness.ROOT)
    assert p.returncode != 0
    assert "no TPU" in p.stderr
    assert p.stdout.strip() == ""


def test_run_exits_nonzero_with_only_the_benchmark(tmp_path):
    shutil.copy(harness.ROOT / "BENCHMARK.json", tmp_path)
    shutil.copytree(harness.BENCH, tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    p = _run(tmp_path)
    assert p.returncode != 0
    assert p.stdout.strip() == ""
