"""Lists: edge-aware positions, the ordered list index, planned pushes, and
a resident plane grown under load.

Pinned here:
  * a run of 200,000 head pushes and 200,000 tail pushes keeps every
    position at <= 8 levels (80 bytes) and in list order, in
    `pos_between_bytes` and in `Sequence` alike (crdt/sequence.py);
  * after seeded interleavings of pushes, inserts, replicated `lins` /
    `lremat`, state merges, GC and compaction, a list's index equals its
    sorted element rows, its live count their live ones, and LRANGE its
    live values in order (store/keyspace.py ListIndex), on the CPU engine
    and on the device engine over JAX-CPU;
  * planned LPUSH / RPUSH give the same replies, the same canonical store
    and the same replication log as the per-command path, in passes that
    mix them with LRANGE / LLEN (read from the run overlay), SET / GET,
    LREM and SADD / SPOP barriers (server/serve.py, commands._plan_push);
  * a device engine's planes grown under micro rounds jump to the floor
    (`TpuMergeEngine.GROW_FLOOR`) at their first grow, keep it through a
    rebuild, double past it across three capacities, equal the host
    columns and count their grows apart from their rebuilds; a boot
    restore's bulk rounds take no floor.
"""

import random
from collections import deque

import numpy as np
import pytest

from constdb_tpu.crdt.sequence import (Sequence, Sorted, pos_between_bytes,
                                       pos_from_bytes)
from constdb_tpu.engine.base import batch_from_keyspace
from constdb_tpu.engine.hostbatch import HOST_MICRO_MAX
from constdb_tpu.resp.codec import encode_msg
from constdb_tpu.resp.message import Arr, Int
from constdb_tpu.server.node import Node
from constdb_tpu.server.serve import ServeCoalescer

from test_serve_coalesce import cmd, stepping_clock

PUSHES = 200_000
MAX_LEVELS = 8


def _device_engine(warmup: int = 0):
    pytest.importorskip("jax")
    from constdb_tpu.engine.tpu import TpuMergeEngine
    return TpuMergeEngine(resident=True, steady=True, warmup=warmup)


def _node(engine: str, node_id: int = 1, clock=None) -> Node:
    eng = _device_engine() if engine == "device" else None
    return Node(node_id=node_id, engine=eng, clock=clock)


# ------------------------------------------------------------- positions


def test_sorted_keeps_order_through_splits_and_removals():
    rng = random.Random(5)
    s, ref = Sorted(), set()
    for _ in range(6000):
        k = rng.randrange(4000)
        if rng.random() < 0.7:
            assert s.insert(k, -k) == (k not in ref)
            ref.add(k)
        else:
            assert s.remove(k) == (k in ref)
            ref.discard(k)
    keys = sorted(ref)
    assert [k for k, _ in s.items()] == keys and len(s) == len(keys)
    assert all(s.get(k) == -k for k in keys)
    assert s.first() == keys[0] and s.last() == keys[-1]
    assert s.before(keys[10]) == keys[9] and s.before(keys[0]) is None


def test_bytes_positions_of_head_and_tail_runs_stay_short_and_ordered():
    order = deque()
    head = tail = None
    for i in range(PUSHES):
        h = pos_between_bytes(None, head, 1)
        order.appendleft(h)
        head = h
        t = pos_between_bytes(tail, None, 2)
        order.append(t)
        tail = t
        if i == 0:
            # the first tail push goes after the first head push
            tail = t = pos_between_bytes(head, None, 2)
            order[-1] = t
    got = list(order)
    assert max(map(len, got)) <= MAX_LEVELS * 10
    assert got == sorted(got) and len(set(got)) == len(got)


def test_sequence_head_and_tail_runs_stay_short_and_ordered():
    s = Sequence()
    want = deque()
    for i in range(PUSHES):
        v = b"%d" % i
        s.insert(0, b"h" + v, node=1, uuid=2 * i + 1)
        want.appendleft(b"h" + v)
        s.insert(s.n_live, b"t" + v, node=2, uuid=2 * i + 2)
        want.append(b"t" + v)
    assert s.read() == list(want)
    assert max(len(pos) for pos, _ in s.items.items()) <= MAX_LEVELS
    # an interior run, before a fixed element, costs a level, then packs
    for i in range(1000):
        s.insert(7, b"i%d" % i, node=3, uuid=10 * PUSHES + i)
    assert max(len(pos) for pos, _ in s.items.items()) <= MAX_LEVELS + 1
    assert s.n_live == len(s.read()) == 2 * PUSHES + 1000


# ------------------------------------------------------ the index's writers


def _index_matches(node: Node, key: bytes) -> None:
    node.ensure_flushed()
    ks = node.ks
    kid = ks.lookup(key)
    if kid < 0:
        return
    li = ks.list_index(kid)
    ks._sync_el_lists()
    rows = [r for r in ks.el_rows_by_kid.get(kid, ())
            if int(ks.el.kid[r]) == kid]
    want = sorted((ks.el_member[r], r) for r in rows)
    assert list(li.rows.items()) == want
    live = [r for _m, r in want
            if int(ks.el.add_t[r]) >= int(ks.el.del_t[r])]
    assert li.n_live == len(live)
    got = node.execute(cmd(b"lrange", key, 0, -1))
    assert [b.val for b in got.items] == [ks.el_val[r] or b"" for r in live]


@pytest.mark.parametrize("engine", ["cpu", "device"])
@pytest.mark.parametrize("seed", range(3))
def test_index_equals_sorted_rows_through_every_writer(engine, seed):
    rng = random.Random(seed)
    a = _node(engine, 1)
    peer = Node(node_id=2)
    key = b"l"
    uuid = [peer.hlc.tick(True)]
    known: list = []        # positions a peer wrote

    def next_uuid() -> int:
        uuid[0] += 1 << 22
        return uuid[0]

    for step in range(70):
        r = rng.random()
        if r < 0.25:
            a.execute(cmd(rng.choice([b"lpush", b"rpush"]), key,
                          b"v%d" % step))
        elif r < 0.32:
            a.execute(cmd(b"linsert", key, rng.randrange(-1, 6),
                          b"i%d" % step))
        elif r < 0.45:
            lo = rng.choice(known) if known and rng.random() < 0.5 else None
            pos = pos_between_bytes(lo, None, 2)
            known.append(pos)
            a.apply_replicated(b"lins", [key, pos, b"p%d" % step], 2,
                               next_uuid())
        elif r < 0.55 and known:
            a.apply_replicated(b"lremat", [key, rng.choice(known)], 2,
                               next_uuid())
        elif r < 0.62:
            a.execute(cmd(b"lrem", key, rng.randrange(0, 4)))
        elif r < 0.74:
            peer.execute(cmd(rng.choice([b"lpush", b"rpush"]), key,
                             b"q%d" % step))
            if rng.random() < 0.3:
                peer.execute(cmd(b"lrem", key, 0))
            a.merge_batch(batch_from_keyspace(peer.ks))
        elif r < 0.84:
            a.ensure_flushed()
            a.ks.gc(1 << 62)
        elif r < 0.9:
            a.ensure_flushed()
            a.ks.gc(1 << 62)
            if a.ks.el_dead:
                a.ks._compact_elements()
        _index_matches(a, key)
        if rng.random() < 0.2:
            a.execute(cmd(b"llen", key))


# --------------------------------------------------------- planned pushes


def _log(node: Node) -> list:
    return [(e.uuid, e.name, tuple((type(a).__name__, a.val) for a in e.args))
            for e in node.repl_log.entries()]


def _chunk(rng, step: int) -> list:
    out = []
    for _ in range(rng.randrange(1, 25)):
        r = rng.random()
        step += 1
        key = rng.choice([b"l", b"m"])
        if r < 0.25:
            out.append(cmd(b"lpush", key, b"v%d" % step,
                           *([b"w%d" % step] if rng.random() < 0.2 else [])))
        elif r < 0.4:
            out.append(cmd(b"rpush", key, b"v%d" % step))
        elif r < 0.62:
            out.append(cmd(b"lrange", key, rng.randrange(-6, 4),
                           rng.randrange(-6, 8)))
        elif r < 0.7:
            out.append(cmd(b"llen", key))
        elif r < 0.78:
            out.append(cmd(b"set", b"s", b"x%d" % step))
        elif r < 0.83:
            out.append(cmd(b"get", b"s"))
        elif r < 0.88:
            out.append(cmd(b"lrem", key, rng.randrange(0, 4)))
        elif r < 0.94:
            out.append(cmd(b"sadd", b"z", b"m"))
        else:
            out.append(cmd(b"spop", b"z"))
    return out


@pytest.mark.parametrize("engine", ["cpu", "device"])
@pytest.mark.parametrize("seed", range(6))
def test_planned_pushes_equal_the_per_command_path(engine, seed):
    rng = random.Random(seed)
    planned = _node(engine, 1, stepping_clock())
    plain = Node(node_id=1, clock=stepping_clock())
    co = ServeCoalescer(planned)
    for step in range(0, 240, 40):
        chunk = _chunk(rng, step)
        out = bytearray()
        # every other pass as depth-1 connections gather it (each command
        # alone on its connection, so a lone push rides the run too)
        co.run_chunk(chunk, out,
                     solo=b"\x01" * len(chunk) if step % 80 else None)
        want = b"".join(encode_msg(plain.execute(m)) for m in chunk)
        assert bytes(out) == want
    planned.ensure_flushed()
    assert planned.ks.canonical() == plain.ks.canonical()
    assert _log(planned) == _log(plain)
    assert planned.stats.list_inserts == plain.stats.list_inserts > 0
    assert planned.stats.list_pos_bytes_sum == plain.stats.list_pos_bytes_sum


def test_a_pass_of_pushes_and_ranges_lands_once():
    """Pushes and LRANGEs of one list in one pass: the ranges read the run
    overlay, so the pass lands its run once, at its end."""
    node = Node(node_id=1, clock=stepping_clock())
    co = ServeCoalescer(node)
    chunk = []
    for i in range(20):
        chunk.append(cmd(b"lpush" if i % 3 else b"rpush", b"l", b"v%d" % i))
        chunk.append(cmd(b"lrange", b"l", 0, 4))
    out = bytearray()
    co.run_chunk(chunk, out, solo=b"\x01" * len(chunk))
    assert node.stats.serve_flushes == 1
    assert node.stats.serve_read_flushes == 0
    got = node.execute(cmd(b"lrange", b"l", 0, 2))
    assert got == Arr([cmd(b"v19").items[0], cmd(b"v17").items[0],
                       cmd(b"v16").items[0]])
    assert node.execute(cmd(b"llen", b"l")) == Int(20)


# ------------------------------------------------------------ plane grows


def test_grown_element_plane_equals_host_columns():
    """A list's pushes grow the element planes under micro rounds: the
    first build, then one grow to the floor, equal to the host columns."""
    eng = _device_engine()
    node = Node(node_id=1, engine=eng, clock=stepping_clock())
    co = ServeCoalescer(node)
    for p in range(40):
        chunk = [cmd(b"rpush" if i % 2 else b"lpush", b"l", b"%d.%d" % (p, i))
                 for i in range(60)]
        co.run_chunk(chunk, bytearray(), solo=b"\x01" * len(chunk))
    assert eng.merge_rows_dev["el"] == 2400 and eng.merge_rows_host["el"] == 0
    assert eng.mirror_grows["el"] == 1                 # 64 -> the floor
    assert sum(eng.mirror_rebuilds.values()) <= 1     # the first build
    node.ensure_flushed()
    res = eng._res["el"]
    assert res["cap"] == eng.GROW_FLOOR
    n = node.ks.el.n
    for col in ("add_t", "add_node", "del_t"):
        assert np.array_equal(eng._plane_get(res["cols"][col], n),
                              node.ks.el.col(col)[:n])
    assert node.execute(cmd(b"llen", b"l")) == Int(2400)


def test_plane_grown_across_three_capacities_past_the_floor():
    """Micro rounds of 32,768 new keys each: the register planes jump to
    the floor at their first grow, then double past it three times, and
    equal the host columns."""
    from test_pallas_dense import _reg_batch
    eng = _device_engine()
    node = Node(node_id=1, engine=eng, clock=stepping_clock())
    step = HOST_MICRO_MAX                  # the most a micro round takes
    caps = []
    while node.ks.keys.n <= 4 * eng.GROW_FLOOR:
        j = node.ks.keys.n // step
        eng.merge(node.ks, _reg_batch(
            [b"k%d" % i for i in range(j * step, (j + 1) * step)],
            1 + j * step))
        caps.append(eng._res["reg"]["cap"])
    f = eng.GROW_FLOOR
    assert eng.dev_rounds_resident == len(caps)        # every one a micro
    assert sorted(set(caps))[1:] == [f, 2 * f, 4 * f, 8 * f]
    assert eng.mirror_grows["reg"] == 4
    assert eng.mirror_rebuilds["reg"] == 0             # nothing stale
    node.ensure_flushed()
    res, n = eng._res["reg"], node.ks.keys.n
    for col in ("rv_t", "rv_node"):
        assert np.array_equal(eng._plane_get(res["cols"][col], n),
                              node.ks.keys.col(col)[:n])


def test_a_table_grown_under_load_keeps_the_floor():
    """The first grow a micro round takes jumps the planes to GROW_FLOOR
    rows, and a rebuild of the stale mirror keeps them there, equal to the
    host columns."""
    eng = _device_engine()
    node = Node(node_id=1, engine=eng, clock=stepping_clock())
    co = ServeCoalescer(node)
    for p in range(6):
        chunk = [cmd(b"lpush", b"l", b"%d.%d" % (p, i)) for i in range(60)]
        co.run_chunk(chunk, bytearray(), solo=b"\x01" * len(chunk))
        if p == 3:     # an op write, collected: the journal is whole
            node.execute(cmd(b"lrem", b"l", 0))
            node.ensure_flushed()
            assert node.ks.gc((1 << 63) - 1) == 1
    node.ensure_flushed()
    res = eng._res["el"]
    assert "el" in eng._growing and res["cap"] == eng.GROW_FLOOR
    assert eng.mirror_rebuilds["el"] >= 1
    n = node.ks.el.n
    for col in ("add_t", "add_node", "del_t"):
        assert np.array_equal(eng._plane_get(res["cols"][col], n),
                              node.ks.el.col(col)[:n])
    assert node.execute(cmd(b"llen", b"l")) == Int(359)


def test_bulk_grown_plane_is_not_warmed_ahead():
    """A boot restore's bulk rounds grow a plane to the size its table
    keeps; only a grow a micro round takes marks a table as growing under
    load, so a restored table keeps pow2(rows): no floor, nothing sized
    ahead of need."""
    from test_pallas_dense import _reg_batch
    eng = _device_engine()
    node = Node(node_id=1, engine=eng, clock=stepping_clock())
    chunk = 1000                       # unique rows: each takes the bulk path
    for j in range(3):                 # as persist/snapshot.py loads chunks
        b = _reg_batch([b"k%d" % i for i in range(j * chunk,
                                                  (j + 1) * chunk)],
                       1 + j * chunk)
        b.rows_unique_per_slot = True
        eng.merge(node.ks, b)
    node.ensure_flushed()
    assert eng.mirror_grows["reg"] >= 1 and eng.dev_rounds_resident == 0
    assert "reg" not in eng._growing
    assert eng._res["reg"]["cap"] == 4096          # pow2(3,000)
    assert node.execute(cmd(b"get", b"k%d" % (3 * chunk - 1))) is not None
