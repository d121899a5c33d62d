"""Hand-written Pallas TPU kernels for the hot ops.

The reference hand-writes CUDA for its hot paths (paddle/cuda/src/hl_*.cu,
operators/math/*.cu); here XLA fusion covers most of that ground, and Pallas
covers what fusion cannot: the attention inner loop (flash attention — the
reference has no attention kernel at all, SURVEY.md §5.7) where materializing
the [q, k] score matrix in HBM is the bandwidth bottleneck.
Paged-attention decode (in-kernel block-table reads and fused dequant),
a lightning indexer's scores over its paged index keys, the expert
layer's grouped matmul (each expert's matrices meet its own rows only)
and a gated delta rule's recurrence (a head's matrix state once in and
once out, in place) are the serving path's kernels.  Each kernel's
module decides from the
shapes, dtypes and platform it is called with whether the Pallas kernel
or the XLA composition runs (docs/performance.md "Kernel selection").
"""
from .delta_rule import delta_rule_supports, select_delta_rule  # noqa: F401
from .flash_attention import flash_attention, flash_attention_reference  # noqa: F401
from .grouped_matmul import grouped_matmul_supports, select_grouped_matmul  # noqa: F401
from .paged_attention import paged_attention_supports, select_paged_attention  # noqa: F401
from .paged_index_scores import select_index_scores  # noqa: F401
