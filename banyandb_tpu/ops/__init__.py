"""Device kernel substrate: the TPU-native columnar execution primitives.

This layer replaces the reference's per-row Go scan loop
(banyand/measure/query.go:594, pkg/query/vectorized/) with dense, statically
shaped JAX computations that XLA fuses onto the TPU's VPU/MXU.
"""

from banyandb_tpu.ops.blocks import ColumnBatch, pad_rows_bucket
from banyandb_tpu.ops.decode import (
    decode_chunk,
    delta_decode,
    dict_gather,
    dict_remap,
    dod_decode,
    ints_to_f32,
    widen_codes,
)
from banyandb_tpu.ops.filter import (
    mask_and,
    mask_or,
    mask_not,
    cmp_mask,
    in_set_mask,
    time_range_mask,
)
from banyandb_tpu.ops.groupby import (
    mixed_radix_key,
    group_reduce,
    GroupReduceResult,
)
from banyandb_tpu.ops.topk import topk_groups
from banyandb_tpu.ops.percentile import (
    HistogramRanks,
    group_histogram,
    group_percentile_histogram,
    invert_histogram,
)
from banyandb_tpu.ops.dedup import latest_by_version
