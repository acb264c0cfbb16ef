"""GEMM lowering subsystem: contraction trees → executable kernel schedules.

The paper's Sec. V pipeline on Sunway is  *contraction → fused GEMM →
adaptive path refiner → kernel schedule*; this package is the TPU/Pallas
port of that bridge between the planner and the kernels:

  gemm_form — normalize each pairwise contraction into
              transpose→reshape→GEMM→reshape form (batch/M/N/K index
              classification; open sampling indices ride as batch axes,
              sliced indices are fixed before lowering)
  layout    — lane-dense storage: every buffer flat in a static index
              order, permutations as transposes whose minor group is at
              least one TPU lane tile wide, GEMM orientation per step
  refiner   — the Sec. V-B adaptive refiner for TPU: per-node backend
              choice (Pallas tiled_matmul / jnp.dot / jnp.einsum),
              MXU-128-snapped block shapes priced by grid steps and
              operand re-reads, and the per-node cost model fed back
              into PlanReport
  cache     — compiled-plan LRU keyed by a canonical network
              fingerprint (structure + dtype + open indices + planner
              params), so repeated requests for the same circuit family
              skip planning and retracing; plus the hoisted-prologue LRU
              keyed by leaf-array fingerprint
  partition — lifetime-based two-phase split (Sec. III interpretation):
              slice-invariant prologue vs slice-dependent epilogue, the
              hoisted buffer frontier between them, and the executed-FLOPs
              accounting that turns Eq. 4 into a runtime win
  memory    — lifetime-based buffer planner: linear-scan slot assignment
              over step lifetimes, exact live-set peaks per execution
              segment (naive / prologue / epilogue), deterministic free
              schedules and donation hints; feeds PlanReport and the
              peak-aware slicer mode
  precision — mixed-precision planner: per-node bf16-input/fp32-
              accumulate demotion under a forward amplitude-error model
              certified against a Linear-XEB fidelity tolerance
              (REPRO_PRECISION / fidelity_tol), plus the per-node
              storage-itemsize maps that make the memory planner and
              peak-aware slicer dtype-true

Sunway→TPU mapping of the refiner, for the record: SWTT 8×8 fused-GEMM
kernel quantization → MXU 128×128 tile quantization; LDM residency →
VMEM residency budget; DMA-bandwidth roofline → HBM roofline;
fp16-compute/fp32-accumulate → bf16/fp32 ``preferred_element_type``;
the permute-or-pad index rewrite → per-node block choice by grid steps,
with no padding past the MXU tile.
"""

from .cache import (  # noqa: F401
    PLAN_CACHE,
    HoistCache,
    PlanCache,
    PlanEntry,
    leaf_fingerprint,
    leaf_key,
    network_fingerprint,
)
from .gemm_form import GemmForm, apply, contract_flat, lower_step  # noqa: F401
from .layout import DenseStep, dense_step, permute_flat  # noqa: F401
from .memory import (  # noqa: F401
    MemoryPlan,
    SegmentPlan,
    node_nbytes,
    peak_bytes,
    plan_memory,
)
from .partition import TreePartition, partition_tree  # noqa: F401
from .precision import (  # noqa: F401
    DEFAULT_FIDELITY_TOL,
    PRECISION_MODES,
    assign_precision,
    default_precision,
    node_amp_error,
    storage_itemsizes,
    tree_storage_itemsizes,
)
from .refiner import (  # noqa: F401
    GemmSpec,
    LoweredSchedule,
    modeled_step_time,
    operand_transpose_bytes,
    refine_schedule,
    refine_step,
    refine_tree_schedule,
)
