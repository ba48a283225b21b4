"""The paper's own dynamic-GNN configs (TM-GCN / CD-GCN / EvolveGCN on
epinions/flickr/youtube/AMLSim-scale DTDGs) as selectable archs.

Full configs mirror Table 1 scales (vertex/timestep counts); smoke configs
run on CPU.  Shapes: one `dtdg_train` cell per dataset scale.
"""

from repro.configs.registry import ArchSpec, ShapeSpec, register
from repro.core.models import DynGNNConfig

FULL_SHAPE = "dtdg_epinions"    # what --full-config trains
CHURN = 0.1                     # synthetic traces: edges resampled per step
# host-side smoothing (§5.4) each model trains on
SMOOTHING = {"tmgcn": "mproduct", "evolvegcn": "edgelife", "cdgcn": "none"}

_DATASETS = {
    # name: (N, T, smoothed edges per snapshot).  N and T are rounded from
    # Table 1 to multiples of 32 resp. 128 so the production meshes divide
    # the vertex and timestep axes evenly (noted in DESIGN.md).
    "epinions": (755_200, 512, 2_097_152),
    "flickr": (2_300_000, 128, 7_340_032),
    "youtube": (3_200_000, 256, 3_342_336),
    "amlsim": (1_000_000, 256, 4_194_304),
    "weak_scale": (1_048_576, 256, 3_145_728),   # weak-scaling generator
}


def _shapes():
    return {
        f"dtdg_{k}": ShapeSpec(
            f"dtdg_{k}", "dtdg_train",
            {"n_nodes": n, "n_steps": t, "edges_per_snap": e})
        for k, (n, t, e) in _DATASETS.items()
    }


def synthetic_trace(cfg: DynGNNConfig, num_steps: int | None = None):
    """The ``FULL_SHAPE`` trace for ``cfg``'s model, seeded: the shape's N
    and smoothed edges per snapshot, and its T unless ``num_steps`` cuts
    it.  ``evolving_dynamic_graph`` keeps 1 - CHURN of each snapshot, so
    a union of ``window`` consecutive snapshots (M-transform, edge-life)
    holds about 1 + (window - 1) * CHURN raw snapshots' worth of edges."""
    from repro.run.data import SyntheticTrace
    d = _shapes()[FULL_SHAPE].dims
    smooth = SMOOTHING[cfg.model]
    union = 1.0 if smooth == "none" else 1 + (cfg.window - 1) * CHURN
    return SyntheticTrace(
        num_nodes=d["n_nodes"], num_steps=num_steps or d["n_steps"],
        density=d["edges_per_snap"] / (d["n_nodes"] * union), churn=CHURN,
        smoothing_mode=smooth, window=cfg.window)


def _mk(model: str):
    def make_config():
        full = _shapes()[FULL_SHAPE].dims
        return DynGNNConfig(model=model, num_nodes=full["n_nodes"],
                            num_steps=full["n_steps"], feat_in=2, hidden=6,
                            out_dim=6, num_layers=2, window=5, num_classes=2,
                            checkpoint_blocks=4)

    def make_smoke_config():
        return DynGNNConfig(model=model, num_nodes=64, num_steps=16,
                            feat_in=2, hidden=6, out_dim=6, num_layers=2,
                            window=3, num_classes=2, checkpoint_blocks=2)

    return make_config, make_smoke_config


for _model in ("tmgcn", "cdgcn", "evolvegcn"):
    _mc, _ms = _mk(_model)
    register(ArchSpec(arch_id=_model, family="dyngnn", make_config=_mc,
                      make_smoke_config=_ms, shapes=_shapes()))

# canonical alias for the paper's workload (the CI end-to-end job and the
# README drive `--arch paper_dyngnn`); TM-GCN is the paper's headline model
_mc, _ms = _mk("tmgcn")
register(ArchSpec(arch_id="paper_dyngnn", family="dyngnn", make_config=_mc,
                  make_smoke_config=_ms, shapes=_shapes(),
                  notes="alias of tmgcn (paper headline config)"))
