"""A copy of the benchmark's manifest whose cells run the configurations
in ``bench/configs`` cut to a size the CPU holds (256 vertices, 700
smoothed edges per snapshot unless told otherwise).  It holds every
workload of ``BENCHMARK.json`` and, for each configuration file that no
workload runs yet, a cell named after the file on the chips of its plan
under the first workload's traffic."""

import json
import time
from pathlib import Path

import cell as cell_mod

BENCH = Path(__file__).resolve().parents[1]
ROOT = BENCH.parent


def _cells() -> dict[str, dict]:
    """Workload name -> {config file, traffic, chips}."""
    manifest = json.loads((ROOT / "BENCHMARK.json").read_text())
    files = {c["name"]: ROOT / c["file"] for c in manifest["configs"]}
    cells = {w["name"]: {"file": files[w["config"]], "traffic": w["traffic"],
                         "chips": w["chips"]}
             for w in manifest["workloads"]}
    used = {c["file"] for c in cells.values()}
    for path in sorted((BENCH / "configs").glob("*.json")):
        if path not in used:
            chips = json.loads(path.read_text())["plan"]["shards"]
            cells[path.stem] = {"file": path, "chips": chips,
                                "traffic": manifest["workloads"][0]["traffic"]}
    return cells


CELLS = _cells()


def make_root(tmp_path, num_nodes=256, smoothed_edges=700):
    manifest = json.loads((ROOT / "BENCHMARK.json").read_text())
    (tmp_path / "cfg").mkdir()
    manifest["configs"], manifest["workloads"] = [], []
    for name, c in CELLS.items():
        cfg = json.loads(c["file"].read_text())
        cfg.update(num_nodes=num_nodes,
                   smoothed_edges_per_snapshot=smoothed_edges)
        (tmp_path / "cfg" / f"{name}.json").write_text(json.dumps(cfg))
        manifest["configs"].append({"name": name,
                                    "file": f"cfg/{name}.json"})
        manifest["workloads"].append({
            "name": name, "config": name, "traffic": c["traffic"],
            "chips": c["chips"]})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(manifest))
    return tmp_path


def run_cell(root, workload, seed, traced=False, trace_dir=None):
    import jax
    cell = cell_mod.load_cell(workload, root)
    return cell_mod.run(cell, seed, 0.5, traced, t_start=time.perf_counter(),
                        peaks={"bf16_flops_per_s": 197e12,
                               "hbm_bytes_per_s": 819e9},
                        devices=jax.devices(), trace_dir=trace_dir,
                        log=lambda _m: None)
