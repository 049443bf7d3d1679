"""Cross-layout resharding: striped reads between mismatched shard layouts.

TensorHub's ROS abstraction promises "fetch any version on demand", but a
bare shard-to-shard pipe only serves reads between *identical* layouts.
Real RL fleets reshard on every transfer — training TP x PP rarely matches
inference TP — so this package turns the single-source pipeline into a
layout-translating data plane: a destination replica with an arbitrary
shard layout replicates from a source published under a different one,
striping byte-interval reads across *all* source shards.

Layout descriptor format
========================

Layout metadata rides on the existing control-plane types — no side
channel, no extra RPCs:

* ``repro.core.meta.TensorMeta`` carries two optional fields:

  - ``global_shape`` — the logical (unsharded) shape of the tensor.
  - ``offset`` — per-dim start of this shard's local block in global
    coordinates; the shard holds ``[offset[d], offset[d] + shape[d])``
    along every dim ``d`` (a dense hyper-rectangle, C-order contiguous
    in local memory).

  ``global_shape is None`` means "no layout metadata": the tensor is
  treated as layout-invariant (replicated), convertible only when the
  peer holds an identically-shaped block.

* A replica's *layout* is the collection of its per-shard manifests:
  :class:`ReplicaLayout` (``layout.py``) is built from
  ``{shard_idx: ShardManifest}`` and records, per tensor, the global
  shape, dtype, and every shard's slice plus the transfer unit that
  carries it (for pipeline-replication progress gating).

Two layouts are *convertible* when they agree on tensor names, dtypes and
global shapes, and the source slices jointly cover every destination
slice. Overlap (replication) is allowed and exploited for load balancing.

Planning
========

:func:`plan_reshard` intersects each destination shard's slice of each
tensor against every source shard's slice and emits a
:class:`ReshardPlan`: per destination shard, an ordered list of
:class:`ReadInterval` — ``(source_shard, src byte range) -> (dest tensor,
dst byte range)`` — that exactly tiles every destination tensor (no gaps,
no overlaps; validated). Regions available from several source shards
(replicated tensors, overlapping slices) are assigned greedily to the
least-loaded source shard, so bytes-per-source stays balanced and a
single hot shard never serializes the transfer.

Execution
=========

:class:`ReshardExecutor` (``executor.py``) drives a shard's plan:
intervals are pulled into a contiguous staging buffer (the RDMA-landing
analogue), and once a destination transfer unit's intervals are all in,
a *repack* step scatters staging bytes into the registered weight
buffers — either the NumPy reference path or the device gather in
``repro.kernels.repack``. Progress is counted in completed destination
units, so a resharded replica serves its prefix to downstream readers
exactly like a same-layout one (4.3.3 pipeline replication), and source
failure mid-plan re-plans against the replacement source (4.5).
"""

from repro.resharding.layout import (
    ReplicaLayout,
    TensorLayout,
    layout_from_manifests,
    tp_shard,
)
from repro.resharding.planner import (
    ReadInterval,
    ReshardPlan,
    ShardPlan,
    plan_reshard,
    plan_shard,
)
from repro.resharding.executor import ReshardExecutor

__all__ = [
    "ReadInterval",
    "ReplicaLayout",
    "ReshardExecutor",
    "ReshardPlan",
    "ShardPlan",
    "TensorLayout",
    "layout_from_manifests",
    "plan_reshard",
    "plan_shard",
    "tp_shard",
]
