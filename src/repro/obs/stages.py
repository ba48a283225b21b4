"""The training step's stages, as ``jax.named_scope`` names.

Each stage is wrapped in ``jax.named_scope(<stage>)`` where its work is
written, so every device op carries its stage in its HLO ``op_name``
(``jit(step)/jvp(spatial)/spmm/...``).  Under ``value_and_grad`` the
backward ops of a stage sit under ``transpose(jvp(<stage>))``, which
tells forward from backward.  A profiler trace of the step can then be
split by stage without a second tracing system; scopes change HLO
metadata only, never the ops or the jits.

``SPMM`` nests inside ``SPATIAL`` (the aggregation ``A_tilde @ X``);
every other stage is disjoint from the rest.
"""

from __future__ import annotations

DELTA_APPLY = "delta_apply"     # graphdiff.apply_delta, decode + apply
EDGE_WEIGHTS = "edge_weights"   # self-loops, degree counts, GCN weights
SPATIAL = "spatial"             # models.spatial_stage, all three models
SPMM = "spmm"                   # gcn.spatial_aggregate, inside SPATIAL
TEMPORAL = "temporal"           # models.temporal_stage
A2A = "a2a"                     # the mesh's all-to-alls
LOSS = "loss"                   # classifier, NLL and its mean / psum
OPTIMIZER = "optimizer"         # adamw.apply_updates

STAGES = (DELTA_APPLY, EDGE_WEIGHTS, SPATIAL, SPMM, TEMPORAL, A2A, LOSS,
          OPTIMIZER)
