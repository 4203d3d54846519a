"""BENCHMARK.json keeps the contract, and every name resolves to its files.

Also shows that a new cell is new files plus new entries: a copy of the
benchmark with one added configuration, traffic mix and per-layer metric
resolves and runs with no file of the copy edited.
"""
import json
import os
import re
import shutil
import subprocess
import types

import pytest

from bench import harness, layers as L, spec
from bench.reference.forward import CONTROLS

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
BENCH = spec.Bench()
DOC = BENCH.doc


def test_top_level_keys():
    assert set(DOC) == {"command", "paths", "run_seconds", "configs",
                        "workloads", "end_to_end", "per_layer"}
    assert DOC["paths"] == ["bench"]
    assert DOC["command"] == ["python3", "bench/run.py"]
    assert 1 <= DOC["run_seconds"] <= 51
    assert os.path.getsize(os.path.join(spec.ROOT, "BENCHMARK.json")) < 65536


def test_run_seconds_fit_a_full_check_of_24_cells():
    per_cell = 14 * (DOC["run_seconds"] + 60) + 2 * 90
    assert 2 * (DOC["run_seconds"] + 60) + 24 * per_cell + 1200 <= 43200


@pytest.mark.parametrize("entry", DOC["configs"], ids=lambda c: c["name"])
def test_config_resolves(entry):
    assert set(entry) == {"name", "source", "file", "reduced", "why"}
    assert NAME.match(entry["name"])
    assert entry["file"].startswith("bench/")
    cfg = BENCH.config(entry["name"])
    assert cfg["name"] == entry["name"]
    assert cfg["reduced"] == entry["reduced"] == []
    assert cfg["source"] == entry["source"]
    layers = BENCH.layers(cfg)
    assert layers[0]["op"] == "input_quant"
    assert cfg["check"]["logit_gap_limit"] > 0
    assert cfg["check"]["control"] in CONTROLS[1:]


@pytest.mark.parametrize("cell", DOC["workloads"], ids=lambda w: w["name"])
def test_workload_resolves(cell):
    assert set(cell) == {"name", "config", "traffic", "chips", "why"}
    assert NAME.match(cell["name"]) and NAME.match(cell["traffic"])
    assert cell["chips"] in (1, 4)
    assert 1 <= len(cell["why"]) <= 200 and "\n" not in cell["why"]
    BENCH.config(cell["config"])
    traffic = BENCH.traffic(cell["traffic"])
    assert hasattr(BENCH.driver(traffic), "Driver")
    e2e = [m["name"] for m in BENCH.metrics_for(cell["name"], "end_to_end")]
    assert "setup_s" in e2e and len(e2e) >= 2
    per_layer = BENCH.metrics_for(cell["name"], "per_layer")
    assert per_layer
    for m in per_layer:
        assert m["moves"] in e2e, (m["name"], cell["name"])


def test_at_most_half_the_cells_take_four_chips():
    four = sum(w["chips"] == 4 for w in DOC["workloads"])
    assert four <= max(1, len(DOC["workloads"]) // 2)


@pytest.mark.parametrize("m", DOC["end_to_end"] + DOC["per_layer"],
                         ids=lambda m: m["name"])
def test_metric_entries(m):
    assert NAME.match(m["name"]) and UNIT.match(m["unit"])
    assert m["better"] in ("lower", "higher")
    if m in DOC["end_to_end"]:
        assert set(m) <= {"name", "unit", "better", "bound", "source",
                          "workloads"}
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.25
    else:
        assert set(m) <= {"name", "unit", "better", "source", "layer",
                          "moves", "workloads"}
        assert m["source"] in ("device_trace", "program_span",
                               "program_counter", "host_clock")
        assert hasattr(BENCH.metric_reader(m["name"]), "read")
    for w in m.get("workloads", ()):
        BENCH.workload(w)


def test_setup_s_is_reported_by_every_cell():
    (setup,) = [m for m in DOC["end_to_end"] if m["name"] == "setup_s"]
    assert "workloads" not in setup and setup["bound"] <= 0.25


def test_layers_share_one_name_per_layer():
    layers = {m["layer"] for m in DOC["per_layer"]}
    assert layers == {"plan", "kernels", "device"}


def test_unknown_device_kind_is_an_error():
    assert BENCH.peaks("TPU v5 lite")["int8_ops_per_s"] == 393e12
    with pytest.raises(spec.UnknownDevice):
        BENCH.peaks("TPU v9 imaginary")


def _copy_bench(tmp_path):
    root = tmp_path / "checkout"
    shutil.copytree(os.path.join(spec.ROOT, "bench"), root / "bench",
                    ignore=shutil.ignore_patterns("tests", "__pycache__"))
    shutil.copy(os.path.join(spec.ROOT, "BENCHMARK.json"), root)
    return root


def test_a_new_cell_needs_only_new_files_and_entries(tmp_path):
    """A CNV-w2a2 configuration, an offline mix of slot 8 and a per-layer
    metric, added as files plus entries: resolved and run on the CPU."""
    root = _copy_bench(tmp_path)
    before = {p: p.read_bytes() for p in (root / "bench").rglob("*")
              if p.is_file()}
    cfg = json.loads((root / "bench/configs/cnv-w1a1.json").read_text())
    cfg.update(name="cnv-w2a2", weight_bits=2, act_bits=2, act_relu=True,
               act_signed=False, act_scale_log2=-1,
               weight_scale_log2=[-6, -8, -8, -8, -8, -8, -8, -8, -6])
    (root / "bench/configs/cnv-w2a2.json").write_text(json.dumps(cfg))
    (root / "bench/traffic/offline-b8.json").write_text(json.dumps(
        {"driver": "offline", "max_batch": 8, "call_batch": 16,
         "pool_calls": 2}))
    (root / "bench/metrics/images_in_window.py").write_text(
        "def read(record):\n    return float(record['images'])\n")
    doc = json.loads((root / "BENCHMARK.json").read_text())
    doc["configs"].append({"name": "cnv-w2a2", "source": "test",
                           "file": "bench/configs/cnv-w2a2.json",
                           "reduced": [], "why": "test"})
    doc["workloads"].append({"name": "cnv-w2a2.offline-b8",
                             "config": "cnv-w2a2", "traffic": "offline-b8",
                             "chips": 1, "why": "test"})
    for m in doc["end_to_end"]:
        if m["name"] == "images_per_s":
            m["workloads"].append("cnv-w2a2.offline-b8")
    doc["per_layer"].append({"name": "images_in_window", "unit": "images",
                             "better": "higher", "source": "host_clock",
                             "layer": "plan", "moves": "images_per_s",
                             "workloads": ["cnv-w2a2.offline-b8"]})
    (root / "BENCHMARK.json").write_text(json.dumps(doc))

    bench = spec.Bench(str(root))
    assert [m["name"] for m in bench.metrics_for("cnv-w2a2.offline-b8",
                                                 "per_layer")] == \
        ["images_in_window"]
    args = types.SimpleNamespace(workload="cnv-w2a2.offline-b8", seed=3,
                                 seconds=0.5, trace=0)
    result = harness.run(args, require_tpu=False, root=str(root))
    assert result["correct"] and result["attempted"] >= 1
    assert set(result["metrics"]) == {"setup_s", "images_per_s"}
    assert bench.metric_reader("images_in_window").read(
        {"images": 16}) == 16.0
    after = {p: p.read_bytes() for p in before}
    assert after == before                  # no file that was there changed


def test_table3_counts_of_the_layer_tables():
    cnv = BENCH.config("cnv-w1a1")
    c = L.count(BENCH.layers(cnv), cnv["input_shape"])
    assert (c["macs_beyond_first"], c["weights"]) == (57_906_176, 1_542_848)
    mn = BENCH.config("mobilenet-w4a4-224")
    c = L.count(BENCH.layers(mn), mn["input_shape"])
    assert c["weights_beyond_first"] == 4_208_224
    # the repository's counting-convention gap to Table III's MACs
    # (tests/test_zoo.py allows the same 2e-3)
    assert abs(c["macs_beyond_first"] - 557_381_408) / 557_381_408 < 2e-3


def _command(cwd, *extra):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("PYTHONPATH", None)
    return subprocess.run(
        DOC["command"] + ["--workload", "cnv-w1a1.offline", "--seed",
                          "2147483659", "--seconds", "1", "--trace", "0",
                          *extra],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=300)


def test_command_refuses_a_host_without_a_tpu():
    proc = _command(spec.ROOT)
    assert proc.returncode != 0
    assert "platform=cpu" in proc.stdout          # device line comes first
    assert not any(ln.lstrip().startswith("{")
                   for ln in proc.stdout.splitlines())
    assert "needs a TPU" in proc.stderr


def test_command_fails_with_the_benchmark_files_alone(tmp_path):
    root = _copy_bench(tmp_path)
    proc = _command(str(root))
    assert proc.returncode != 0
    assert "No module named 'repro'" in proc.stderr
    assert not any(ln.lstrip().startswith("{")
                   for ln in proc.stdout.splitlines())
