"""Multi-process cluster runtime: the feature table PARTITIONED by Morton
key range across process boundaries (ISSUE 15 / ROADMAP open item 1).

PR 7 made the fleet horizontal by REPLICATION — every node holds a full
copy, so the corpus is bounded by one host's HBM. This package adds the
missing axis: a jax.distributed runtime in which each process owns a
contiguous key-range shard of the sorted columnar table, assembled into
one global jax.Array with ``make_array_from_process_local_data`` +
``NamedSharding`` over a named ``rows`` axis (the SNIPPETS partitioner
pattern). Counts/density run as psum-reduced global kernels (every
process returns the exact global answer); selects stream per-process
local matches through a host-side ordered merge (rank order == key
order, so concatenation IS the global sort order).

Modules:
  runtime   jax.distributed bring-up (GEOMESA_TPU_CLUSTER_* knobs),
            mesh topology as first-class config (flat process-contiguous
            rows mesh / hybrid ICI x DCN), host exchange, federation
            auto-registration, /cluster state.
  table     ClusterShardedTable — global-array construction from
            process-local shards; cross-process ownership boundaries.
  exec      ClusterScan — psum'd count/density, ordered-merge select.
  build     cross-process splitter exchange: distributed partition of
            unsorted rows into per-process contiguous key ranges, so
            distributed index builds land sorted-by-construction.
  dryrun    spawned N-process CPU-backend dryrun + single-process
            oracle comparison (the CI acceptance surface).
  cells     shard cells (cluster v2): each Morton key-range shard as a
            replicated primary+follower group with its own fencing
            epoch — the ownership map the shard-aware router routes
            writes by, the per-cell admit matrix, graceful ownership
            handoff, and the node-local ingest ownership gate.
"""

from geomesa_tpu.cluster.runtime import (ClusterRuntime, runtime,
                                         cluster_active)
from geomesa_tpu.cluster.cells import (CellInfo, NotOwnedError,
                                       ShardCells)

__all__ = ["ClusterRuntime", "runtime", "cluster_active",
           "CellInfo", "NotOwnedError", "ShardCells"]
