"""A copy of the benchmark's manifest whose cells run the configurations
in ``bench/configs`` cut to a size the CPU holds (256 vertices, 700
smoothed edges per snapshot unless told otherwise).  It holds a cell for
every configuration file, the four-chip one included, whether or not the
manifest measures it yet."""

import json
import time

import cell as cell_mod
from conftest import BENCH, ROOT

CELLS = {"tmgcn-epinions-1chip": "tmgcn-epinions",
         "tmgcn-epinions-4chip": "tmgcn-epinions-p4"}


def make_root(tmp_path, num_nodes=256, smoothed_edges=700):
    manifest = json.loads((ROOT / "BENCHMARK.json").read_text())
    (tmp_path / "cfg").mkdir()
    manifest["configs"], manifest["workloads"] = [], []
    for name, config in CELLS.items():
        cfg = json.loads((BENCH / "configs" / f"{config}.json").read_text())
        cfg.update(num_nodes=num_nodes,
                   smoothed_edges_per_snapshot=smoothed_edges)
        (tmp_path / "cfg" / f"{config}.json").write_text(json.dumps(cfg))
        manifest["configs"].append({"name": config,
                                    "file": f"cfg/{config}.json"})
        manifest["workloads"].append({
            "name": name, "config": config, "traffic": "evolving-churn10",
            "chips": cfg["plan"]["shards"]})
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
