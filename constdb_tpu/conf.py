"""Configuration: TOML file + command-line overrides.

Capability parity with the reference's config system (reference
src/conf.rs:10-88 `OriginConfig`→`Config` with defaults, src/server.yml clap
args): a TOML file selected by `--config` plus flag overrides, frozen into a
`Config` dataclass at boot.  Fields keep the reference's names where the
concept carries over; TPU-specific fields are new.

Unlike the reference, `replica_heartbeat_frequency` is actually WIRED to the
pusher heartbeat (the reference parses-but-ignores it — conf.rs:81-82,
SURVEY.md §"Known reference defects").
"""

from __future__ import annotations

import argparse
import dataclasses
import os
from dataclasses import dataclass

import tomllib


# ------------------------------------------------------------ env registry
# The ONE place a CONSTDB_* tuning knob is declared.  Reads anywhere in
# the package go through the env_* helpers below (which raise on
# unregistered names), the ENV-REGISTRY lint rule rejects direct
# os.environ reads, and tests/test_analysis.py pins every registered
# name into the README "Tuning" table — so a knob cannot ship
# undeclared or undocumented.  Tools OUTSIDE the package (bench.py,
# opbench.py, tests) may still read their own CONSTDB_BENCH_*/test-only
# vars directly; the registry covers the operational surface.

@dataclass(frozen=True)
class EnvVar:
    name: str
    default: str   # rendered default, for docs/errors (not parsed)
    doc: str       # one-line effect, mirrored by the README table


ENV_REGISTRY: dict[str, EnvVar] = {v.name: v for v in (
    EnvVar("CONSTDB_SHARDS", "auto",
           "hash-shard count for the process-parallel merge; 1 = the "
           "exact single-keyspace path"),
    EnvVar("CONSTDB_PIPELINE", "1",
           "stage/dispatch overlap inside merge_many; 0 = serial path"),
    EnvVar("CONSTDB_STAGE_WORKERS", "min(4, cores-1)",
           "threads in the engine's staging pool"),
    EnvVar("CONSTDB_POOL_FLUSH_MB", "1536",
           "win-value pool cap (MB) before a streamed catch-up "
           "auto-flushes"),
    EnvVar("CONSTDB_NO_NATIVE", "",
           "any value forces the pure-Python table/RESP tiers (floor "
           "measurement)"),
    EnvVar("CONSTDB_APPLY_BATCH", "512",
           "max replicate frames coalesced into one merge on the "
           "steady-state pull path; 1 = the exact per-frame path"),
    EnvVar("CONSTDB_APPLY_LATENCY_MS", "5",
           "max ms a coalesced replicate frame may wait before its "
           "batch is force-flushed (idle streams flush immediately)"),
    EnvVar("CONSTDB_WIRE_BATCH", "512",
           "max repl-log ops group-encoded into one REPLBATCH wire "
           "frame on the push path; 1 = the byte-exact per-frame "
           "stream (and the capability is not advertised)"),
    EnvVar("CONSTDB_WIRE_LATENCY_MS", "5",
           "max ms a drained op may sit in the push loop's aggregated "
           "wire buffer before a socket flush (idle cycles flush "
           "immediately, so a lone write is never delayed)"),
    EnvVar("CONSTDB_WIRE_COMPRESS", "1",
           "negotiated replication compression (CAP_COMPRESS): REPLBATCH "
           "payloads above the floor, FULLSYNC/DELTASYNC windows, and "
           "the compressed snapshot container all gate on it; 0 = every "
           "peer gets the byte-exact plain stream and dumps stay plain"),
    EnvVar("CONSTDB_WIRE_COMPRESS_MIN", "512",
           "min REPLBATCH payload bytes before the negotiated stream "
           "compression engages (smaller payloads ship plain — framing "
           "overhead would beat the savings)"),
    EnvVar("CONSTDB_ENCODE_CACHE_MB", "16",
           "encode-once run cache cap (MB): finished wire encodings "
           "published by the first push loop to drain a run and reused "
           "by every other peer at the same cursor and caps-class; "
           "0 disables (every peer re-encodes, the pre-broadcast path)"),
    EnvVar("CONSTDB_READ_CACHE_MB", "16",
           "versioned hot-key reply cache cap (MB): finished RESP reply "
           "bytes served by the coalescer's read planner while a key's "
           "envelope version is unchanged, invalidated at every "
           "mutation intake; 0 disables (every read recomputes)"),
    EnvVar("CONSTDB_SERVE_BATCH", "512",
           "max pipelined client commands the serve path plans into one "
           "columnar merge; 1 = the exact per-command path"),
    EnvVar("CONSTDB_NATIVE_INTAKE", "1",
           "native intake stage: one C call splits a coalescing "
           "connection's pipelined chunk and classifies the plannable "
           "commands into opcodes + pre-flattened payloads; 0 = the "
           "pure drain()+run_chunk path (byte-identical output)"),
    EnvVar("CONSTDB_SERVE_LAT_SAMPLE", "32",
           "sample every Nth coalesced client command into the INFO "
           "reply-latency ring (serve_lat_p50/p99_ms); 0 = off"),
    EnvVar("CONSTDB_SERVE_SHARDS", "1",
           "serve worker processes, each owning a keyspace shard + "
           "engine + repl-log segment; 1 = the exact single-loop path"),
    EnvVar("CONSTDB_DELTA_SYNC", "1",
           "digest-driven partial resync on the replication push path; "
           "0 = always ship full snapshots"),
    EnvVar("CONSTDB_DELTA_MAX_DIVERGENCE", "0.5",
           "digest bucket-mismatch fraction past which a delta resync "
           "demotes to a full snapshot"),
    EnvVar("CONSTDB_DELTA_BUCKET_KEYS", "8",
           "target keys per digest leaf bucket (finer buckets localize "
           "divergence; 8 bytes of digest per bucket)"),
    EnvVar("CONSTDB_DELTA_STAMP_MIN", "4096",
           "min keys in the divergent buckets before the per-key stamp "
           "refinement round runs (below it, whole buckets stream)"),
    EnvVar("CONSTDB_RESIDENT", "auto",
           "steady-state device residency: a resident engine merges "
           "op-stream micro-batches in place against the resident "
           "planes; auto = only over a real (non-CPU) backend, "
           "1 = force on, 0 = always the host micro strategy"),
    EnvVar("CONSTDB_RESIDENT_WARMUP", "2",
           "consecutive micro rounds a plane's host version must stay "
           "stable before its device mirror uploads (cold planes merge "
           "on host meanwhile)"),
    EnvVar("CONSTDB_TENSOR_POOL_MB", "512",
           "resident tensor payload pool cap (MB of device bytes) "
           "before the engine flushes and releases the pools"),
    EnvVar("CONSTDB_TENSOR_MAX_ELEMS", "4194304",
           "max elements per tensor value a TENSOR.SET may create "
           "(guards one client frame from allocating GBs)"),
    EnvVar("CONSTDB_TENSOR_STRATEGY", "lww",
           "merge strategy TENSOR.SET uses when the strategy argument "
           "is '-' (lww, sum, avg, maxmag, trimmed-mean)"),
    EnvVar("CONSTDB_RECONNECT_BASE_MS", "5000",
           "replica-link reconnect backoff base delay (first retry "
           "after a drop; doubles per consecutive failure)"),
    EnvVar("CONSTDB_RECONNECT_FACTOR", "2.0",
           "replica-link reconnect backoff multiplier per consecutive "
           "dial failure"),
    EnvVar("CONSTDB_RECONNECT_MAX_MS", "60000",
           "replica-link reconnect backoff ceiling — a long partition "
           "retries at this cadence, never slower"),
    EnvVar("CONSTDB_RECONNECT_JITTER", "0.2",
           "replica-link reconnect jitter fraction, derived "
           "DETERMINISTICALLY from (node_id, peer, attempt) so chaos "
           "runs replay exactly from their seed"),
    EnvVar("CONSTDB_UNDO_WINDOW", "4096",
           "locally-originated counter ops kept undoable (CNTUNDO "
           "looks its target up here; older ops report 'evicted')"),
    EnvVar("CONSTDB_MAXMEMORY", "0",
           "governed memory ceiling in bytes (store + repl log + device "
           "pools + applier buffers); 0 = unlimited.  Past the soft "
           "watermark client DATA writes shed with an -OOM error; "
           "reads, deletes, admin, and ALL replication intake stay "
           "admitted (the convergence-soundness asymmetry, "
           "docs/INVARIANTS.md)"),
    EnvVar("CONSTDB_MAXMEMORY_SOFT_PCT", "85",
           "soft watermark as a percent of CONSTDB_MAXMEMORY: shedding "
           "starts here; at 100% of the cap the node additionally "
           "flushes device state, drops warm caches, and forces GC"),
    EnvVar("CONSTDB_CLIENT_OUTBUF_MAX", "134217728",
           "per-connection reply-buffer cap in bytes: a client that "
           "stops reading past it is disconnected loudly "
           "(client_outbuf_disconnects) instead of pinning unbounded "
           "reply memory; 0 = uncapped"),
    EnvVar("CONSTDB_REPL_WINDOW", "16777216",
           "max unacked replication-stream bytes in flight per peer: "
           "the push loop pauses draining the ring for a stalled peer "
           "at this window and resumes on REPLACK — a long stall "
           "degrades to ring eviction + delta resync; 0 = unbounded"),
    EnvVar("CONSTDB_PROTO_MAX_BULK", "536870912",
           "max declared RESP bulk-string length accepted at parse "
           "time (Redis-style 512MB default): a $-header past it is a "
           "protocol error before any buffering, in both parsers"),
    EnvVar("CONSTDB_SNAPSHOT_FSYNC", "1",
           "fsync background/shutdown snapshot dumps — file AND parent "
           "directory after the atomic rename — so a crash right after "
           "the dump cannot lose it; 0 trades that for dump latency"),
    EnvVar("CONSTDB_AOF", "0",
           "durable op log (persist/oplog.py): every repl-log append "
           "mirrors into crc-framed append-only segments in "
           "<work_dir>/aof and boot replays snapshot + oplog tail "
           "through the real merge path; 0 = in-memory only (a crash "
           "between snapshot dumps loses acknowledged writes)"),
    EnvVar("CONSTDB_AOF_FSYNC", "everysec",
           "group-commit policy: always = a serve chunk is acked only "
           "after its covering fsync lands (one fsync per pipelined "
           "chunk); everysec = background fsync every second; no = the "
           "OS decides (records still written through)"),
    EnvVar("CONSTDB_AOF_REWRITE_PCT", "100",
           "log-rewrite compaction trigger: when the oplog grows this "
           "percent past its post-rewrite base size, the node rewrites "
           "it as base snapshot + fresh segments (atomic rename + "
           "parent fsync); 0 disables auto-rewrite"),
    EnvVar("CONSTDB_AOF_REWRITE_MIN_MB", "16",
           "oplog size floor (MB) below which the rewrite trigger "
           "never fires — tiny logs are cheaper to replay than to "
           "compact"),
    EnvVar("CONSTDB_RECOVER_BULK", "1",
           "bulk-merge boot replay (persist/oplog.py): decoded AOF "
           "records accumulate into merge rounds sized like snapshot "
           "ingest chunks and land through one engine merge_many call "
           "per round; 0 pins the per-record reference path (each "
           "record merges individually — the serial replay the bench "
           "oracle compares against)"),
    EnvVar("CONSTDB_RECOVER_SHARDS", "0",
           "concurrent per-segment AOF replay on a sharded node "
           "(persist/oplog.py recover_into_plane): per-shard segments "
           "decode and route to their serve workers concurrently "
           "(cross-segment records commute — the parallel recovery "
           "law); 0 = auto (one replay task per segment), 1 = the "
           "serial merged-stream path, N caps the concurrency"),
    EnvVar("CONSTDB_CHECKPOINT_SECS", "0",
           "incremental checkpoint cadence (seconds): past it the cron "
           "cuts a consistent base snapshot + fresh AOF generation (the "
           "rewrite machinery, time-triggered), so a restart replays "
           "only the post-checkpoint tail; 0 disables (growth-triggered "
           "rewrites via CONSTDB_AOF_REWRITE_PCT still run)"),
    EnvVar("CONSTDB_CHECKPOINT_MIN_MB", "1",
           "minimum MB of post-checkpoint log tail before a time-due "
           "checkpoint actually cuts — an idle node never churns "
           "snapshots just because the clock advanced"),
    EnvVar("CONSTDB_CLUSTER", "0",
           "cluster mode (constdb_tpu/cluster): partition the 16384 "
           "hash slots (crc32(key) mod 16384 — the digest plane's own "
           "partition) across replication groups; non-owned keys get "
           "MOVED/ASK redirects and slots migrate live over the "
           "digest->delta path; 0 (default) = the exact pre-cluster "
           "single-group node, byte for byte"),
    EnvVar("CONSTDB_SLOT_GROUPS", "1",
           "bootstrap slot-table shape under CONSTDB_CLUSTER=1: the "
           "16384 slots split into this many contiguous group ranges "
           "at epoch 1 (each node's group id is supplied by the "
           "harness/operator); live migration + gossip rebalance from "
           "there"),
    EnvVar("CONSTDB_MIGRATE_BATCH_MB", "8",
           "slot-migration wire chunk (MB): a migrating slot's "
           "ColumnarBatch export streams as CLUSTER IMPORT frames of "
           "at most this size, so one big slot cannot wedge the "
           "target's loop behind a single giant frame"),
    EnvVar("CONSTDB_MIGRATE_STALL_S", "120",
           "import-window staleness timeout (seconds): a migration "
           "target whose source goes silent after SETSLOT IMPORTING — "
           "no IMPORT chunk, no STABLE, no FINALIZE — for this long "
           "drops the import window and releases its tombstone-GC pin "
           "instead of serving the slot's partial copy (and pinning "
           "GC) forever; a retried migration re-opens the window "
           "cleanly"),
    EnvVar("CONSTDB_TRACKING_BATCH", "128",
           "max invalidation keys coalesced into one RESP3 push frame "
           "per tracked connection before an immediate flush "
           "(server/tracking.py; the batch half of the dual bound)"),
    EnvVar("CONSTDB_TRACKING_LATENCY_MS", "2",
           "max milliseconds a pending invalidation key waits in a "
           "tracked connection's coalescing buffer before its push "
           "frame flushes (the latency half of the dual bound); 0 = "
           "flush on the next loop tick"),
    EnvVar("CONSTDB_TRACKING_MAX_KEYS", "65536",
           "per-connection cap on keys the default-mode tracking "
           "registry records for one client; past it the server sends "
           "a flush-all invalidation and starts over (bounded memory, "
           "never silently stale)"),
)}


def _env_read(name: str) -> str | None:
    if name not in ENV_REGISTRY:
        raise KeyError(
            f"{name} is not declared in conf.ENV_REGISTRY — register it "
            "(name, default, doc) and add a README Tuning row")
    return os.environ.get(name)


def env_str(name: str, default: str = "") -> str:
    v = _env_read(name)
    return default if v is None else v


def env_int(name: str, default: int) -> int:
    v = _env_read(name)
    return default if v is None or v == "" else int(v)


def env_float(name: str, default: float) -> float:
    v = _env_read(name)
    return default if v is None or v == "" else float(v)


def env_flag(name: str, default: bool) -> bool:
    """'0' (and only '0') is false when the variable is set — matching
    every pre-registry call site's `!= "0"` convention."""
    v = _env_read(name)
    return default if v is None or v == "" else v != "0"


@dataclass
class Config:
    # reference fields (src/conf.rs:63-88)
    daemon: bool = False          # detach (double-fork), write a pid file,
    #                               and log to a rolling file (bin/server.py;
    #                               reference src/lib.rs:89-136)
    node_id: int = 0
    node_alias: str = ""
    ip: str = "127.0.0.1"
    port: int = 9001
    threads: int = 1              # parsed for config-file compatibility with
    #                               the reference's N-IO-thread design
    #                               (src/lib.rs:138-142); this build's IO is
    #                               one asyncio loop (the loop IS the single
    #                               exec thread, so there is no parse-thread
    #                               pool to size) — values > 1 are ignored
    log: str = "console"          # "console" | path to a log file
    work_dir: str = "./"
    tcp_backlog: int = 1024       # wired to the listen backlog (server/io.py;
    #                               reference src/server.rs:96-101)
    replica_heartbeat_frequency: int = 4   # seconds (wired, unlike reference)
    replica_gossip_frequency: int = 15     # seconds between reconnect dials
    # new (TPU build)
    addr: str = ""                # advertised address, default ip:port
    engine: str = "auto"          # "auto" | "tpu" | "cpu" (build_engine):
    #                               "tpu" fails the boot unless JAX's
    #                               default backend is an accelerator;
    #                               "auto" takes the chip if one is
    #                               there, else the pure-CPU engine
    snapshot_path: str = ""       # load on boot + background dump target
    snapshot_interval: int = 0    # seconds between background dumps (0 = off)
    snapshot_chunk_keys: int = 1 << 16
    snapshot_compress_level: int = 1  # zlib level for snapshot sections —
    #                               on disk AND on the wire (full sync
    #                               streams the same file; reference
    #                               src/conn/writer.rs:92-112 streams raw).
    #                               0 = store/send raw; 1 (default) = fast;
    #                               up to 9 = smallest
    repl_log_cap: int = 1_024_000  # reference src/server.rs:81
    log_level: str = "info"
    pid_file: str = ""            # default: <work_dir>/constdb.pid (daemon)
    log_max_bytes: int = 64 << 20  # rolling-log size cap per file
    log_backups: int = 4           # rolled files kept
    ingest_shards: int = 0  # process-parallel snapshot ingest: hash-shard
    #                         a large downloaded snapshot across this many
    #                         worker processes (store/sharded_keyspace.py).
    #                         0 = auto (CONSTDB_SHARDS env / core count;
    #                         stays 1 on <= 2 cores), 1 = off.
    ingest_shard_min_bytes: int = 64 << 20  # snapshots below this take the
    #                         plain single-keyspace path (worker spawn
    #                         costs more than it saves on small syncs)
    serve_shards: int = 0  # shard-per-core serving (server/serve_shards.py):
    #                        N worker processes each owning a keyspace shard
    #                        + engine + repl-log segment, the event loop
    #                        routing by key hash.  0 = the CONSTDB_SERVE_SHARDS
    #                        env default (1); 1 = the exact single-loop path.
    aof: bool = False      # durable op log (persist/oplog.py): mirror
    #                        every repl-log append into crc-framed
    #                        append-only segments under aof_dir and
    #                        replay snapshot + oplog tail on boot.
    #                        False = the CONSTDB_AOF env default decides.
    aof_fsync: str = ""    # "always" | "everysec" | "no"; "" = the
    #                        CONSTDB_AOF_FSYNC env default (everysec)
    aof_rewrite_pct: int = -1  # log-rewrite growth trigger (percent over
    #                        the post-rewrite base; 0 = off); -1 = the
    #                        CONSTDB_AOF_REWRITE_PCT env default (100)
    aof_dir: str = ""      # segment directory; "" = <work_dir>/aof
    cluster_group: int = 0  # this node's replication-group id under
    #                        CONSTDB_CLUSTER=1 (which slot range of the
    #                        CONSTDB_SLOT_GROUPS bootstrap split it
    #                        owns); every member of a group shares one
    #                        id.  Deliberately a flag, not an env: two
    #                        nodes of one cluster differ ONLY here.
    restore_to: int = 0    # point-in-time restore: boot replays the AOF
    #                        only up to this uuid (record-boundary
    #                        granularity), then re-bases the log on the
    #                        restored state.  Run it against a COPY of
    #                        the data dir — the skipped suffix is
    #                        discarded by the re-basing checkpoint.
    #                        0 = full recovery (the normal boot).
    # a peer silent for longer than this stops pinning the GC tombstone
    # horizon.  0 (default) = never exclude — the reference's behavior,
    # where one dead peer pins tombstone collection mesh-wide forever
    # (reference replica/replica.rs:87-89).  When enabled, an excluded
    # peer whose tombstones were collected AND whose resume point fell
    # off the repl_log is forced through a STATE-CLEARING full resync on
    # return (link.py fullsync reset flag): its local keyspace and
    # repl_log are wiped before the snapshot merge, so stale keys cannot
    # resurrect mesh-wide — at the cost of discarding any writes the
    # excluded peer made while partitioned.  While the repl_log still
    # covers its resume point, partial replay stays lossless.
    gc_peer_retention: int = 0  # seconds (0 = off)


def load_config(argv: list[str] | None = None) -> Config:
    """`constdb-tpu-server [config.toml] [-h HOST] [-p PORT] ...`
    (reference bin/server.rs + server.yml arg spec)."""
    ap = argparse.ArgumentParser(prog="constdb-tpu-server",
                                 description="constdb-tpu node")
    ap.add_argument("config", nargs="?", help="TOML config file")
    ap.add_argument("--host", "-H", dest="ip")
    ap.add_argument("--port", "-p", type=int)
    ap.add_argument("--node-id", type=int, dest="node_id")
    ap.add_argument("--alias", dest="node_alias")
    ap.add_argument("--addr", help="advertised address (host:port)")
    ap.add_argument("--work-dir", dest="work_dir")
    ap.add_argument("--engine", choices=["auto", "tpu", "cpu"])
    ap.add_argument("--snapshot", dest="snapshot_path")
    ap.add_argument("--snapshot-interval", type=int, dest="snapshot_interval")
    ap.add_argument("--aof", action="store_const", const=True, dest="aof",
                    default=None, help="enable the durable op log")
    ap.add_argument("--aof-fsync", dest="aof_fsync",
                    choices=["always", "everysec", "no"])
    ap.add_argument("--restore-to", type=int, dest="restore_to",
                    metavar="UUID",
                    help="point-in-time restore: replay the AOF only up "
                         "to this uuid, then re-base the log (run "
                         "against a copy of the data dir)")
    ap.add_argument("--cluster-group", type=int, dest="cluster_group",
                    metavar="GID",
                    help="this node's replication-group id under "
                         "CONSTDB_CLUSTER=1 (default 0; see "
                         "CONSTDB_SLOT_GROUPS)")
    ap.add_argument("--log-level", dest="log_level")
    ns = ap.parse_args(argv)

    cfg = Config()
    if ns.config:
        with open(ns.config, "rb") as f:
            data = tomllib.load(f)
        for field in dataclasses.fields(Config):
            if field.name in data:
                setattr(cfg, field.name, data[field.name])
    for field in dataclasses.fields(Config):
        v = getattr(ns, field.name, None)
        if v is not None:
            setattr(cfg, field.name, v)
    return cfg


# The persistent XLA compile cache when JAX_COMPILATION_CACHE_DIR is not
# set: ONE fixed directory inside the checkout (gitignored).  The path
# is part of the cache key's environment — a directory that moves with
# a pid, a time or a tmpdir never hits.
COMPILE_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__))), ".jax_cache")


# this process's persistent-cache counters (JAX's cache is process-wide,
# so are these): INFO prints them beside the directory, and a boot that
# re-compiles what an earlier boot cached shows as misses, not hits
COMPILE_CACHE = {"dir": "", "hits": 0, "misses": 0}


def _count_cache_event(event: str, **_kw) -> None:
    if event == "/jax/compilation_cache/cache_hits":
        COMPILE_CACHE["hits"] += 1
    elif event == "/jax/compilation_cache/cache_misses":
        COMPILE_CACHE["misses"] += 1


def enable_compile_cache() -> str:
    """Turn on JAX's persistent compile cache before the first compile
    and return its directory.  Every process that compiles for a device
    (the server, bench.py, ladder.py) calls this one helper.  Where
    JAX_COMPILATION_CACHE_DIR is set, JAX already reads it and no other
    directory is ever set in code.  Every compile is kept, however
    short: a boot re-traces each pow2 shape bucket, and on a chip even
    the small ones cost more to compile than to load."""
    if COMPILE_CACHE["dir"]:
        return COMPILE_CACHE["dir"]
    import jax
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = COMPILE_CACHE_DIR
        jax.config.update("jax_compilation_cache_dir", path)
    if "JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS" not in os.environ:
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.monitoring.register_event_listener(_count_cache_event)
    COMPILE_CACHE["dir"] = path
    return path


def build_engine(kind: str):
    """The node's merge engine.  JAX initializes HERE, in the server
    process, once — the chip belongs to one process, so nothing probes
    it from a second one.

    'cpu': the pure-CPU per-row engine; JAX is never imported.
    'tpu': the batched device engine, and a boot FAILURE unless JAX's
    default backend is a real accelerator — a missing chip is a
    misconfiguration to page on, never a slower engine under the same
    name.  'auto': the portable default — the device engine when an
    accelerator is there, else the pure-CPU engine.  INFO reports what
    was built (`engine`, `jax_backend`, `device_kind`,
    `device_count`)."""
    if kind not in ("auto", "tpu", "cpu"):
        raise ValueError(f"unknown engine {kind!r} (auto | tpu | cpu)")
    if kind != "cpu":
        import jax
        platform = jax.default_backend()
        if platform != "cpu":
            enable_compile_cache()
            from .engine.tpu import TpuMergeEngine
            # resident: per-family device state persists across merge
            # rounds — op-stream micro-batches merge in place per
            # CONSTDB_RESIDENT, and bulk catch-up pays row uploads only,
            # never a state round-trip per chunk; Node.ensure_flushed
            # syncs before every host read
            return TpuMergeEngine(resident=True)
        if kind == "tpu":
            raise RuntimeError(
                "engine='tpu' requires an accelerator, but JAX's default "
                f"backend is {platform!r} (JAX_PLATFORMS="
                f"{os.environ.get('JAX_PLATFORMS', '')!r})")
    from .engine.cpu import CpuMergeEngine
    return CpuMergeEngine()
