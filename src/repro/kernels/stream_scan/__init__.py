"""Fused streaming megakernels (scoring scan, Alg. 1 fold, Alg. 3 place).

kernel.py — the blocked-grid Pallas megakernels (one dispatch per chunk,
insert and retract via a ``sign`` operand); ops.py — the fused → tiled →
oracle degradation ladder plus the engine-facing carries; ref.py — the
seed ``lax.scan`` oracles (bit-identical contract).
"""

from .kernel import (  # noqa: F401
    CLUSTER_ARRAYS,
    DEFAULT_BLOCK,
    LANES,
    assign_scan,
    cluster_leaf_shapes,
    cluster_scan,
    dispatch_count,
    reset_dispatch_count,
    scoring_scan,
    stream_scan_tpu,
    table_width,
)
from .ops import (  # noqa: F401
    CLUSTER_SMEM_ORDER,
    DEFAULT_VMEM_BUDGET,
    SMEM_BYTES,
    VMEM_BUDGET_ENV,
    GreedyCarry,
    GridCarry,
    HdrfCarry,
    assign_state_bytes,
    cluster_state_bytes,
    cluster_vmem_arrays,
    kernel_fits,
    make_chunk_fn,
    paths_taken,
    reset_path_log,
    scoring_state_bytes,
    select_path,
    vmem_budget,
)
from .ref import (  # noqa: F401
    assign_chunk_oracle,
    cluster_chunk_oracle,
    greedy_chunk,
    greedy_init,
    greedy_retract_chunk,
    grid_chunk,
    grid_init,
    grid_retract_chunk,
    hdrf_chunk,
    hdrf_init,
    hdrf_retract_chunk,
)
