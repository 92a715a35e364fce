"""DDSketch device kernels: Pallas TPU implementations + pure-XLA oracles.

Hot spots the paper optimizes (Algorithm 1's insert loop) plus the
UDDSketch uniform-collapse fold, TPU-native:

* ``ddsketch_seg_hist`` — the one insert kernel: a row-sparse segmented
  histogram over a bank's stacked sign stores (a single sketch is a bank
  of one),
* ``bank_quantiles``    — fused cumsum + searchsorted bank query,
* ``bank_range_merge``  — fused slice-range merge for windowed quantiles
  (fold each slice row to the range's max collapse level, reduce slices),
* ``fold_pairs``        — uniform-collapse resolution fold (gamma -> gamma^2),
* ``ref``               — pure-jnp semantic oracles / XLA fallback,
* ``ops``               — backend dispatch (``force=`` pins a path).
"""

from repro.kernels.ops import (  # noqa: F401
    BucketSpec,
    bank_histograms,
    bank_quantiles,
    bank_range_merge,
    dispatch_stats,
    fold_pairs,
    reset_dispatch_stats,
    segment_histogram,
)
from repro.kernels.ref import (  # noqa: F401
    MAX_COLLAPSE_LEVEL,
    bank_quantiles_ref,
    bank_range_merge_ref,
    fold_pairs_ref,
    histogram_ref,
    segment_histogram_ref,
)
