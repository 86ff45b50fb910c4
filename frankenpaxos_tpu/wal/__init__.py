"""paxlog: drain-granular durability for protocol roles.

An append-only, CRC-framed, segment-rotating write-ahead log with GROUP
COMMIT at the actor runtime's ``on_drain`` boundary: every record
appended while a drain's messages are being handled is made durable by
ONE ``fsync`` when the drain ends, so the per-message durability cost
amortizes across the drain exactly like the run pipeline's device
dispatches ("Paxos in the Cloud" finds durable logging dominates Paxos
latency unless writes are batched -- PAPERS.md).

The reference keeps no persistence layer at all;
this package is the production-scale answer: acceptors recover
promises/votes/run records and replicas recover an SM snapshot + the
executed watermark after ``kill -9``, then rejoin the cluster.

  * ``wal.records`` -- the typed record set + fixed-layout codecs
    (wire tags 84-89, registered with the runtime codec registry so
    the corrupt-frame containment fuzz covers them).
  * ``wal.log`` -- ``Wal`` (framing, group commit, segment rotation,
    snapshot/compaction, torn-tail recovery) over ``FileStorage``
    (real files + fsync) or ``MemStorage`` (the sim's crash-surviving
    stand-in: synced bytes survive ``crash_restart``, the unsynced
    group-commit buffer dies with the actor).
  * ``wal.faults`` -- deterministic fsync-stall fault injection for
    the paxworld scenario matrix (a wrapping storage: off by default,
    zero cost on the unwrapped hot path).
"""

from frankenpaxos_tpu.wal.faults import FsyncStallStorage  # noqa: F401
from frankenpaxos_tpu.wal.log import FileStorage, MemStorage, Wal, WalMetrics  # noqa: F401
from frankenpaxos_tpu.wal.records import (  # noqa: F401
    WalChosenRun,
    WalEpoch,
    WalGeoEpoch,
    WalGeoPromise,
    WalGeoVote,
    WalNoopRange,
    WalPromise,
    WalSnapshot,
    WalVote,
    WalVoteRun,
)
from frankenpaxos_tpu.wal.role import DurableRole  # noqa: F401
