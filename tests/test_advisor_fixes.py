"""Regression tests for the round-2 advisor findings (ADVICE.md).

Each test pins one fixed behavior: engine='auto' boots on any host,
snapshot-dump invalidation on bulk ingest, redis LPUSH order,
the RESP fast-path bulk cap, and the structured FORGOTTEN error code.
"""

import asyncio

import numpy as np
import pytest

from constdb_tpu.errors import InvalidRequestMsg
from constdb_tpu.resp.codec import RespParser
from constdb_tpu.resp.message import Arr, Bulk, Err
from constdb_tpu.server.node import Node


def _cmd(node, *parts):
    return node.execute([Bulk(p if isinstance(p, bytes) else str(p).encode())
                         for p in parts])


# --------------------------------------------------------------- 1: engine


def test_engine_auto_is_the_portable_default():
    """engine='auto' on a host with no accelerator builds the pure-CPU
    engine — a node boots and serves on any host, under its own name."""
    import constdb_tpu.conf as conf

    eng = conf.build_engine("auto")
    assert eng.name == "cpu" and hasattr(eng, "merge")


# ----------------------------------------------------- 2: dump invalidation


def test_bulk_ingest_invalidates_shared_dump(tmp_path):
    """State merged OUTSIDE the repl_log (snapshot ingest) must force a
    fresh full-sync dump: the old dump + log tail would silently omit it."""
    import sys
    sys.path.insert(0, ".")
    from bench import make_workload
    from constdb_tpu.server.io import ServerApp

    async def main():
        node = Node(node_id=1)
        app = ServerApp(node, work_dir=str(tmp_path))
        _cmd(node, b"set", b"seed", b"1")
        d1 = await app.shared_dump.acquire()
        assert app.shared_dump.dumps_taken == 1
        # reuse while nothing bypassed the log
        assert (await app.shared_dump.acquire()) is d1
        # bulk ingest (not in the repl_log) must invalidate
        node.merge_batch(make_workload(50, 1, seed=3)[0])
        d2 = await app.shared_dump.acquire()
        assert app.shared_dump.dumps_taken == 2
        assert d2 is not d1
    asyncio.run(main())


# ------------------------------------------------------------ 3: lpush order


def test_lpush_multi_value_order_matches_redis():
    node = Node(node_id=1)
    _cmd(node, b"rpush", b"l", b"x")
    _cmd(node, b"lpush", b"l", b"a", b"b", b"c")
    got = _cmd(node, b"lrange", b"l", b"0", b"-1")
    assert isinstance(got, Arr)
    assert [b.val for b in got.items] == [b"c", b"b", b"a", b"x"]


# --------------------------------------------------------- 4: RESP bulk cap


def test_fast_path_rejects_oversized_bulk():
    p = RespParser()
    # flat array fast path: declared 600MB bulk must fail fast, without
    # ever buffering the body
    p.feed(b"*2\r\n$3\r\nset\r\n$629145600\r\n")
    with pytest.raises(InvalidRequestMsg):
        p.next_msg()


def test_general_path_still_rejects_oversized_bulk():
    p = RespParser()
    p.feed(b"$629145600\r\n")
    with pytest.raises(InvalidRequestMsg):
        p.next_msg()


# ------------------------------------------------------ 5: FORGOTTEN prefix


def test_forgotten_requires_structured_code(tmp_path):
    from constdb_tpu.errors import CstError
    from constdb_tpu.replica.link import ReplicaLink
    from constdb_tpu.replica.manager import ReplicaMeta
    from constdb_tpu.server.io import ServerApp

    async def main():
        node = Node(node_id=1)
        app = ServerApp(node, work_dir=str(tmp_path))
        meta = ReplicaMeta("127.0.0.1:1", add_t=1)
        link = ReplicaLink(app, meta)
        # an unrelated error that merely mentions the word must NOT suspend
        with pytest.raises(CstError):
            link._check_sync_reply(Err(b"db loading, forgotten keys pending"))
        assert meta.dial_suspended is False
        # the structured code DOES suspend
        with pytest.raises(CstError):
            link._check_sync_reply(Err(b"FORGOTTEN removed from this mesh"))
        assert meta.dial_suspended is True
    asyncio.run(main())
