"""What the harness promises: the manifest, the peak table, and refusing
to report anywhere but on the chip."""

import json
import os
import re
import shutil
import subprocess
import sys

import pytest

import cell as cell_mod
import run
from tiny import BENCH, ROOT

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
MANIFEST = json.loads((ROOT / "BENCHMARK.json").read_text())


def test_unknown_device_kind_is_an_error():
    with pytest.raises(SystemExit, match="no peaks"):
        run.load_peaks("TPU v99 imaginary")


def test_v5e_peaks_and_source():
    table = json.loads(run.PEAKS.read_text())
    assert "TPU v5e" in table["source"]
    v5e = run.load_peaks("TPU v5 lite")
    assert v5e["bf16_flops_per_s"] == 197e12
    assert v5e["hbm_bytes_per_s"] == 819e9
    assert v5e["hbm_bytes"] == 16e9


def _run(cwd, env_extra=None):
    env = {k: v for k, v in os.environ.items()
           if k not in ("XLA_FLAGS", "PYTHONPATH")}
    env["JAX_PLATFORMS"] = "cpu"
    env.update(env_extra or {})
    return subprocess.run(
        [sys.executable, "bench/run.py", "--workload",
         "tmgcn-epinions-1chip", "--seed", str(2**31 + 3), "--seconds",
         "1", "--trace", "0"], cwd=cwd, env=env, capture_output=True,
        text=True, timeout=300)


def _no_result(proc):
    return not any(line.lstrip().startswith("{")
                   for line in proc.stdout.splitlines())


def test_refuses_to_report_without_a_tpu():
    proc = _run(ROOT)
    assert proc.returncode != 0
    assert "no TPU" in proc.stderr
    assert _no_result(proc)


def test_refuses_in_a_checkout_of_the_benchmark_alone(tmp_path):
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    shutil.copytree(BENCH, tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    proc = _run(tmp_path)
    assert proc.returncode != 0
    assert _no_result(proc)


def test_manifest_names_units_and_keys():
    assert set(MANIFEST) == {"command", "paths", "run_seconds", "configs",
                             "workloads", "end_to_end", "per_layer"}
    assert MANIFEST["paths"] == ["bench"]
    assert MANIFEST["command"][1] == "bench/run.py"
    assert 1 <= MANIFEST["run_seconds"] <= 51
    names = set()
    for c in MANIFEST["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert NAME.match(c["name"]) and c["file"].startswith("bench/")
        assert json.loads((ROOT / c["file"]).read_text())["name"] == c["name"]
        assert all(NAME.match(k) for k in c["reduced"])
        names.add(c["name"])
    cells = {}
    for w in MANIFEST["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert NAME.match(w["name"]) and NAME.match(w["traffic"])
        assert w["config"] in names and w["chips"] in (1, 4)
        assert (BENCH / "traffic" / f"{w['traffic']}.json").exists()
        assert 1 <= len(w["why"]) <= 200
        cells[w["name"]] = w
    assert len(cells) == len(MANIFEST["workloads"])
    metrics = set()
    for m in MANIFEST["end_to_end"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "bound",
                                          "source"}
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.25
        metrics.add(m["name"])
    assert "setup_s" in metrics
    layers_of = {}
    for m in MANIFEST["per_layer"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "source",
                                          "layer", "moves"}
        assert m["moves"] in metrics
        assert set(m.get("workloads", cells)) <= set(cells)
        assert (BENCH / "metrics" / f"{m['name']}.py").exists()
        layers_of.setdefault(m["layer"], set()).add(m["name"])
    for m in MANIFEST["end_to_end"] + MANIFEST["per_layer"]:
        assert NAME.match(m["name"]) and UNIT.match(m["unit"])
        assert m["better"] in ("lower", "higher")
        assert m["name"] not in names - metrics


def test_every_cell_reports_setup_another_metric_and_a_layer():
    for w in MANIFEST["workloads"]:
        cell = cell_mod.load_cell(w["name"])
        e2e = {m["name"] for m in cell.end_to_end}
        assert "setup_s" in e2e and len(e2e) >= 2
        assert cell.per_layer
        assert cell.chips == w["chips"]
        # a metric that names its cells appears in those cells alone
        for m in MANIFEST["per_layer"]:
            mine = w["name"] in m.get("workloads", [w["name"]])
            assert mine == (m in cell.per_layer)


def test_unknown_workload_is_refused():
    with pytest.raises(SystemExit, match="unknown workload"):
        cell_mod.load_cell("no-such-cell")


def _tree(tmp_path, model="tmgcn", reader=None, traffic=None):
    """A manifest of the one-chip cell whose configuration names
    ``model``, which declares the per-layer metric ``reader`` too, and
    which runs ``traffic`` where given."""
    manifest = json.loads(json.dumps(MANIFEST))
    if traffic:
        manifest["workloads"][0]["traffic"] = traffic
    cfg = json.loads((ROOT / "bench/configs/tmgcn-epinions.json").read_text())
    (tmp_path / "cfg.json").write_text(json.dumps({**cfg, "model": model}))
    manifest["configs"] = [{**manifest["configs"][0], "file": "cfg.json"}]
    if reader:
        manifest["per_layer"].append({**manifest["per_layer"][0],
                                      "name": reader})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(manifest))
    return tmp_path


@pytest.mark.parametrize("model,reader,traffic,missing", [
    ("nosuchmodel", None, None, "bench/models/nosuchmodel.py"),
    ("tmgcn", "train.nosuch_ms", None, "bench/metrics/train.nosuch_ms.py"),
    ("tmgcn", None, "nosuch-mix", "bench/traffic/nosuch-mix.json")])
def test_a_cell_this_tree_cannot_run_exits_before_the_device(
        tmp_path, monkeypatch, model, reader, traffic, missing):
    import jax

    def no_device(*_a, **_k):
        raise AssertionError("load_cell asked JAX for a device")

    monkeypatch.setattr(jax, "devices", no_device)
    workload = MANIFEST["workloads"][0]["name"]
    root = _tree(tmp_path, model, reader, traffic)
    with pytest.raises(SystemExit, match=re.escape(missing)) as exc:
        cell_mod.load_cell(workload, root)
    assert "\n" not in str(exc.value)
    assert cell_mod.load_cell(workload, _tree(tmp_path)).config["model"] \
        == "tmgcn"
