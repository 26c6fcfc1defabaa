"""BENCHMARK.json against the rules of its format, and the loader
finding a configuration, a traffic mix and a metric from files alone."""
import json
import os
import re
import subprocess
import sys

import pytest

from bench_tiny import ROOT
from bench import manifest

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
BM = json.loads((ROOT / "BENCHMARK.json").read_text())


def test_manifest_keys_and_names():
    assert set(BM) == {"command", "paths", "run_seconds", "configs",
                       "workloads", "end_to_end", "per_layer"}
    assert 1 <= BM["run_seconds"] <= 51
    for entry in BM["configs"] + BM["workloads"] + BM["end_to_end"] + \
            BM["per_layer"]:
        assert NAME.match(entry["name"]), entry["name"]
    for c in BM["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert (ROOT / c["file"]).is_file()
        assert c["file"].split("/")[0] in BM["paths"]
    for w in BM["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert w["chips"] in (1, 4) and len(w["why"]) <= 200
    names = {w["name"] for w in BM["workloads"]}
    for m in BM["end_to_end"] + BM["per_layer"]:
        assert UNIT.match(m["unit"]) and m["better"] in ("lower", "higher")
    for m in BM["per_layer"]:
        assert m["workloads"] and set(m["workloads"]) <= names
    for m in BM["end_to_end"]:
        assert "workloads" not in m
        assert 0.01 <= m["bound"] <= 0.25 and m["source"] in (
            "host_clock", "device_trace")
    assert any(m["name"] == "setup_s" for m in BM["end_to_end"])
    e2e = {m["name"] for m in BM["end_to_end"]}
    for m in BM["per_layer"]:
        assert m["moves"] in e2e and "\n" not in m["layer"]


@pytest.mark.parametrize("cell", [w["name"] for w in BM["workloads"]])
def test_every_cell_loads_with_its_readers(cell):
    c = manifest.load_cell(cell)
    assert c.config["vocab_size"] > 0 and c.traffic["rate_rps"] > 0
    for m in c.end_to_end + c.per_layer:
        assert callable(manifest.reader(m["name"]))
    assert any(m["name"] == "setup_s" for m in c.end_to_end)
    assert len(c.end_to_end) >= 2 and c.per_layer
    manifest.generator(c.traffic["kind"])


def test_new_cell_mix_and_metric_from_files_alone(tmp_path):
    """A later change adds a cell by new files and entries only."""
    (tmp_path / "bench" / "configs").mkdir(parents=True)
    (tmp_path / "bench" / "traffic").mkdir()
    (tmp_path / "bench" / "metrics").mkdir()
    (tmp_path / "bench" / "models").mkdir()
    conf = json.loads((ROOT / "bench" / "configs" /
                       "h2o-danube-1.8b.json").read_text())
    conf["model"] = "windowed"
    (tmp_path / "bench" / "configs" / "danube-x4.json").write_text(
        json.dumps(conf))
    (tmp_path / "bench" / "models" / "windowed.py").write_text(
        "LEAF_IDS = {'w_window': 99}\n")
    (tmp_path / "bench" / "traffic" / "chat.json").write_text(
        (ROOT / "bench" / "traffic" / "chat.json").read_text())
    (tmp_path / "bench" / "traffic" / "chat-x4.json").write_text(
        json.dumps({"base": "chat", "rate_rps": 9.5}))
    (tmp_path / "bench" / "metrics" / "router.tier_load_ratio.py"
     ).write_text("def read(rec):\n    return 1.25\n")
    bm = {"configs": [{"name": "danube-x4",
                       "file": "bench/configs/danube-x4.json"}],
          "workloads": [{"name": "danube.chat-pool4", "config": "danube-x4",
                         "traffic": "chat-x4", "chips": 4}],
          "end_to_end": [{"name": "setup_s"}, {"name": "ttft_p90_ms"}],
          "per_layer": [{"name": "router.tier_load_ratio",
                         "moves": "ttft_p90_ms",
                         "workloads": ["danube.chat-pool4"]},
                        {"name": "paged_attn_roofline",
                         "moves": "ttft_p90_ms", "workloads": ["other"]}]}
    c = manifest.load_cell("danube.chat-pool4", bm, checkout=tmp_path)
    assert c.chips == 4 and "replicas" not in c.config
    assert manifest.model(c.config, tmp_path / "bench").LEAF_IDS == {
        "w_window": 99}
    assert c.traffic["rate_rps"] == 9.5 and c.traffic["kind"] == "open_loop"
    assert [m["name"] for m in c.end_to_end] == ["setup_s", "ttft_p90_ms"]
    assert [m["name"] for m in c.per_layer] == ["router.tier_load_ratio"]
    assert manifest.reader("router.tier_load_ratio",
                           tmp_path / "bench")(None) == 1.25


def test_unknown_cell_is_an_error():
    with pytest.raises(KeyError):
        manifest.load_cell("no-such-cell")


def test_run_without_a_tpu_prints_no_result():
    r = subprocess.run([sys.executable, "bench/run.py", "--workload",
                        BM["workloads"][0]["name"], "--seed", "5",
                        "--seconds", "1", "--trace", "0"],
                       cwd=ROOT, capture_output=True, text=True, timeout=300,
                       env=dict(os.environ, JAX_PLATFORMS="cpu"))
    assert r.returncode != 0
    assert r.stdout.strip() == "", r.stdout
    assert "no TPU" in r.stderr
