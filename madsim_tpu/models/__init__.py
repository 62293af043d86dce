"""Protocol models for the TPU engine.

`echo` — 2-node request/response (the tonic-example-class workload,
reference: tonic-example/tests/test.rs:22-120).
`raft` — MadRaft-class leader election + log replication, the flagship
benchmark workload (BASELINE.json configs).
`kv` — versioned KV store + retrying clients, session-monotonicity
invariant (the etcd-class kill/restart workload).
`mq` — idempotent-producer message queue, per-producer gapless ordering
invariant (the rdkafka-class workload).
`etcd` — leased-KV leader election (grant/campaign/keepalive over an
MVCC server), lease-safety invariant (the madsim-etcd-client service-
class workload, batched).
`twopc` — two-phase commit with durable write-ahead logs, transaction-
atomicity invariant (the atomic-commitment workload class).
`kafka_group` — consumer-group coordinator with generations, session
timeouts and fenced commits; at-least-once + no-commit-regression
invariants (the rdkafka consumer-group workload, batched).
`paxos` — single-decree Paxos with durable acceptors and dueling
proposers; agreement invariant via a ghost chosen-register.
`multipaxos` — multi-decree Paxos: a log of synod slots driven by
dueling proposers with LEARN propagation; per-slot agreement + learned-
log-consistency invariants (the second consensus family at MadRaft
depth).
`etcd_mvcc` — MVCC etcd server (revisions, txns, leases with ghost
expiry) + retrying clients; revision-accounting, txn-atomicity,
lease-expiry-safety and exactly-once invariants.
`raft_compact` — `raft` with snapshots and log compaction (a torn
snapshot file is its demo bug).
`gossip` — 33-node epidemic broadcast with quorum commit, the lane of
more than 30 nodes.
`s3` — object store: multipart uploads, versions, expiry, retried
writes; five demo bugs, one an invariant.
`kafka` — madsim-rdkafka's whole pipeline: `mq`'s produce path over
three live partition logs and `kafka_group`'s rebalancing group, the
broker's state held once a lane (the `kafka_pc5` configuration).
`kvraft` — MadRaft's lab 3: a key/value service layered on `raft`'s
handlers (commands in log entries, apply at commit, a session table),
its clerks as nodes of the same lane, reads checked against what was
acknowledged (the `kvraft5` configuration, under `--churn kv3a`).
"""

from . import (
    echo, etcd, etcd_mvcc, gossip, kafka, kafka_group, kv, kvraft, mq,
    multipaxos, paxos, raft, raft_compact, s3, twopc,
)

__all__ = [
    "echo", "etcd", "etcd_mvcc", "gossip", "kafka", "kafka_group", "kv",
    "kvraft", "mq", "multipaxos", "paxos", "raft", "raft_compact", "s3",
    "twopc",
]
