"""constdb-tpu-server: run one node.

Capability parity with the reference server binary (reference bin/server.rs
→ lib.rs `run_server`): config, logging, bind, cron, serve until signalled.
Background snapshot dumps replace the reference's fork()-COW scheme with the
capture-on-loop / encode-on-thread pipeline (persist/snapshot.py), and the
snapshot is reloaded on boot — the reference restarts empty (SURVEY.md §5.4).

Usage: python -m constdb_tpu.bin.server [config.toml] [--port N] ...
"""

from __future__ import annotations

import asyncio
import logging
import signal
import sys
from functools import partial

from ..conf import Config, build_engine, load_config
from ..persist.snapshot import NodeMeta, dump_keyspace
from ..server.io import ServerApp, start_node
from ..server.node import Node
from ..utils.stagetime import TimedSelector

log = logging.getLogger("constdb_tpu.server")


def setup_logging(cfg: Config) -> None:
    level = getattr(logging, cfg.log_level.upper(), logging.INFO)
    fmt = "%(asctime)s %(levelname)s %(filename)s:%(lineno)d - %(message)s"
    if cfg.log and cfg.log != "console":
        # size-capped rolling file (reference src/lib.rs:109-136 rolls its
        # log by size too)
        from logging.handlers import RotatingFileHandler
        handler = RotatingFileHandler(cfg.log, maxBytes=cfg.log_max_bytes,
                                      backupCount=cfg.log_backups)
        handler.setFormatter(logging.Formatter(fmt))
        logging.basicConfig(level=level, handlers=[handler])
    else:
        logging.basicConfig(level=level, format=fmt)


def daemonize(cfg: Config) -> str:
    """Detach (double fork + setsid), point stdio at /dev/null, and write
    the pid file (reference src/lib.rs:89-108).  Returns the pid path."""
    import os

    if os.fork() > 0:
        os._exit(0)
    os.setsid()
    if os.fork() > 0:
        os._exit(0)
    devnull = os.open(os.devnull, os.O_RDWR)
    for fd in (0, 1, 2):
        os.dup2(devnull, fd)
    os.close(devnull)
    pid_path = cfg.pid_file or os.path.join(cfg.work_dir, "constdb.pid")
    os.makedirs(cfg.work_dir, exist_ok=True)
    with open(pid_path, "w") as f:
        f.write(str(os.getpid()))
    return pid_path


def _snapshot_fsync() -> bool:
    """Durable dumps by default (file data + parent directory entry —
    persist/snapshot.py): CONSTDB_SNAPSHOT_FSYNC=0 trades the crash
    guarantee for dump latency."""
    from ..conf import env_flag
    return env_flag("CONSTDB_SNAPSHOT_FSYNC", True)


def _dump_container_level(app: ServerApp) -> int:
    """Background/shutdown dumps ride the compressed snapshot container
    (persist/snapshot.py; boot restore sniffs the magic, pre-PR files
    stay loadable).  Gates on the same per-app/env compression master
    switch as every wire decision (CONSTDB_WIRE_COMPRESS=0 or
    ServerApp(wire_compress=False) keeps dumps in the plain pre-PR
    format)."""
    from ..replica.link import wire_compress_of
    return 6 if wire_compress_of(app) else 0


async def snapshot_cron(app: ServerApp, cfg: Config) -> None:
    """Periodic background dump (fork-free; see persist/snapshot.py)."""
    from ..engine.base import batch_from_keyspace
    from ..persist.snapshot import write_snapshot_file

    while True:
        await asyncio.sleep(cfg.snapshot_interval)
        node = app.node
        # RuntimeError: a sharded node's dump awaits serve-pool worker
        # exports, and a failed worker surfaces as one — it must not
        # kill the cron (the node would silently never snapshot again)
        try:
            if node.serve_plane is not None:
                # shard-per-core node: the workers hold the state —
                # dump their consolidated exports (landed watermark: a
                # dump may not claim coverage of minted-but-in-flight
                # writes)
                await _dump_plane_snapshot(app, cfg)
            else:
                node.ensure_flushed()  # device-resident merge → host
                capture = batch_from_keyspace(node.ks)  # on the loop
                meta = NodeMeta(node_id=node.node_id, alias=node.alias,
                                addr=app.advertised_addr,
                                repl_last_uuid=node.repl_log.last_uuid)
                records = node.replicas.records()
                await asyncio.to_thread(
                    write_snapshot_file, cfg.snapshot_path, meta,
                    records, [capture],
                    chunk_keys=cfg.snapshot_chunk_keys,
                    compress_level=cfg.snapshot_compress_level,
                    fsync=_snapshot_fsync(),
                    container_level=_dump_container_level(app))
            log.info("background snapshot written to %s",
                     cfg.snapshot_path)
        except (OSError, RuntimeError) as e:
            log.error("background snapshot failed: %s", e)


def loop_factory(selector: TimedSelector):
    """asyncio.run's `loop_factory`: the default selector loop, polling
    through `selector` (its wait in epoll is the stage `loop_poll`)."""
    return partial(asyncio.SelectorEventLoop, selector)


async def amain(cfg: Config, selector: TimedSelector) -> None:
    node = Node(node_id=cfg.node_id, alias=cfg.node_alias,
                engine=build_engine(cfg.engine),
                repl_log_cap=cfg.repl_log_cap)
    # the loop's poll, its thread and every collection, on the node's
    # clock (INFO span_loop_poll_*, loop_*, span_gc_*)
    selector.watch(node.stages)
    app = await start_node(
        node, host=cfg.ip, port=cfg.port,
        advertised_addr=cfg.addr, work_dir=cfg.work_dir,
        heartbeat=float(cfg.replica_heartbeat_frequency),
        reconnect_delay=float(cfg.replica_gossip_frequency) / 3.0,
        snapshot_chunk_keys=cfg.snapshot_chunk_keys,
        snapshot_compress_level=cfg.snapshot_compress_level,
        snapshot_path=cfg.snapshot_path,
        tcp_backlog=cfg.tcp_backlog,
        gc_peer_retention=float(cfg.gc_peer_retention),
        ingest_shards=cfg.ingest_shards,
        ingest_shard_min_bytes=cfg.ingest_shard_min_bytes,
        serve_shards=cfg.serve_shards or None,
        aof=cfg.aof or None,
        aof_fsync=cfg.aof_fsync or None,
        aof_rewrite_pct=cfg.aof_rewrite_pct
        if cfg.aof_rewrite_pct >= 0 else None,
        aof_dir=cfg.aof_dir,
        cluster_group=cfg.cluster_group,
        restore_to=cfg.restore_to)
    log.info("constdb-tpu node %d (engine=%s) serving on %s",
             node.node_id, node.engine.name, app.advertised_addr)

    stop = asyncio.Event()
    loop = asyncio.get_running_loop()
    for sig in (signal.SIGINT, signal.SIGTERM):
        loop.add_signal_handler(sig, stop.set)
    crons = []
    if cfg.snapshot_interval > 0 and cfg.snapshot_path:
        crons.append(asyncio.create_task(snapshot_cron(app, cfg)))
    await stop.wait()
    for t in crons:
        t.cancel()
    if cfg.snapshot_path:
        # final synchronous dump so a clean restart resumes warm
        if node.serve_plane is not None:
            # shard-per-core node: consolidate the worker shards — the
            # parent keyspace is empty by design (server/serve_shards.py)
            await _dump_plane_snapshot(app, cfg)
        else:
            node.ensure_flushed()  # device-resident merge state → host
            dump_keyspace(cfg.snapshot_path, node.ks,
                          NodeMeta(node_id=node.node_id, alias=node.alias,
                                   addr=app.advertised_addr,
                                   repl_last_uuid=node.repl_log.last_uuid),
                          node.replicas.records(),
                          chunk_keys=cfg.snapshot_chunk_keys,
                          compress_level=cfg.snapshot_compress_level,
                          fsync=_snapshot_fsync(),
                          container_level=_dump_container_level(app))
        log.info("final snapshot written to %s", cfg.snapshot_path)
    await app.close()


async def _dump_plane_snapshot(app: ServerApp, cfg: Config) -> None:
    """Whole-state dump of a sharded serving node: worker exports,
    landed watermark (the same rules as snapshot_cron / share.py)."""
    from ..persist.snapshot import write_snapshot_file

    node = app.node
    # watermarks (own repl_last AND the per-peer records) are captured
    # BEFORE the worker exports: frames landing mid-export end up in the
    # state but above every recorded watermark (harmless redelivery).
    # Captured after, a record would claim pull coverage the exported
    # state lacks, and a boot restore adopting it would skip those
    # frames' redelivery forever (persist/share.py has the long form).
    repl_last = node.repl_log.landed_last_uuid
    records = node.replicas.records()
    captures = await node.serve_plane.export_batches()
    meta = NodeMeta(node_id=node.node_id, alias=node.alias,
                    addr=app.advertised_addr, repl_last_uuid=repl_last)
    await asyncio.to_thread(
        write_snapshot_file, cfg.snapshot_path, meta,
        records, captures,
        chunk_keys=cfg.snapshot_chunk_keys,
        compress_level=cfg.snapshot_compress_level,
        fsync=_snapshot_fsync(),
        container_level=_dump_container_level(app))


def main(argv=None) -> None:
    import os

    cfg = load_config(argv)
    pid_path = ""
    if cfg.daemon:
        if not cfg.log or cfg.log == "console":
            # stdio points at /dev/null after detaching — console logging
            # would be silently discarded, so force a file
            cfg.log = os.path.join(cfg.work_dir, "constdb.log")
        pid_path = daemonize(cfg)
    setup_logging(cfg)
    selector = TimedSelector()
    try:
        asyncio.run(amain(cfg, selector),
                    loop_factory=loop_factory(selector))
    except KeyboardInterrupt:
        pass
    finally:
        if pid_path:
            import os
            try:
                os.unlink(pid_path)
            except OSError:
                pass


if __name__ == "__main__":
    main(sys.argv[1:])
