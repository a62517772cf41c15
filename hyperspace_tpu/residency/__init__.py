"""Oversubscribed residency: the tier ladder that keeps tables larger
than the HBM budget on the device fast path.

PR 5's join regions and the base resident caches share one failure mode
at scale: once a table's raw int32 planes exceed the HBM budget, the
caches refuse it outright and every query pays the host path — the
admit/deny cliff. PystachIO and Theseus (PAPERS.md) both reach
the same conclusion: storage->device movement must be a first-class
pipeline, not a boolean. This package supplies the ladder

    resident -> compressed -> streaming -> host

with two compounding levers:

* ``tiers``     — the ONE tier-planning procedure both caches call: given
  raw plane bytes, per-column pack plans and the budget, pick the
  cheapest tier that fits (and explain refusals).
* ``streaming`` — the block-window tier: pinned-host packed planes staged
  through a fixed pair of HBM slabs, upload of window k+1 overlapped
  with the mask of window k, per-window count partials the only D2H.
* ``knobs``     — the ``hyperspace.residency.*`` config family (constants
  registry, HS013) with HYPERSPACE_TPU_RESIDENCY_* env overrides.

Compression/decode codecs live in ``ops.bitpack`` (device code is ops/
territory); the caches integrate the ladder in exec/hbm_cache and
exec/mesh_cache (the mesh supports resident + compressed; streaming is
single-chip — a mesh table that large should shard wider instead, and
the decline is counted).
"""

from .knobs import (  # noqa: F401
    adopt_conf,
    compression_mode,
    for_delta_enabled,
    streaming_enabled,
    streaming_window_rows,
)
from .tiers import TierPlan, plan_tier  # noqa: F401
