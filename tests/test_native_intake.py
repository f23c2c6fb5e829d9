"""Native intake plane (native/intake.cpp + server/serve.py
run_native_chunk + server/io.py): the C scanner's opcode table, the
serve-level byte-identity oracle against the pure planner path, chunk
split invariance, the SYNC/ upgrade stop, the ABI build stamp gate, the
REPLBATCH blob-column fast path, and the end-to-end INFO gauges."""

import asyncio
import random

import pytest

from constdb_tpu.resp.codec import encode_msg, make_parser
from constdb_tpu.resp.message import Arr, Bulk, Err, Int, NIL, Simple
from constdb_tpu.server import serve as SV
from constdb_tpu.server.node import Node
from constdb_tpu.server.serve import ServeCoalescer
from constdb_tpu.utils import native_tables as NT
from constdb_tpu.utils.hlc import SEQ_BITS

ext = NT.load_ext()
pytestmark = pytest.mark.skipif(
    ext is None or not hasattr(ext, "intake_scan"),
    reason="native extension with intake_scan not built")

MS0 = 1_700_000_000_000


def stepping_clock():
    ms = [MS0]

    def clock():
        ms[0] += 1
        return ms[0]
    return clock


def cmd(*parts) -> Arr:
    return Arr([p if isinstance(p, (Bulk, Int)) else
                Bulk(p if isinstance(p, bytes) else str(p).encode())
                for p in parts])


def scan(raw: bytes, pos: int = 0):
    return ext.intake_scan(raw, pos, Arr, Bulk, Int, Simple, Err, NIL)


def mixed_chunks(seed: int, rounds: int = 30):
    """Random pipelined chunks covering every native opcode, OTHER
    demotes (uppercase, barriers, arity errors), and planner demotes
    (non-int counter args)."""
    rng = random.Random(seed)
    keys = [b"k%d" % i for i in range(8)]
    chunks = []
    for _ in range(rounds):
        msgs = []
        for _ in range(rng.randint(1, 12)):
            k = rng.choice(keys)
            c = rng.randint(0, 17)
            if c == 0:
                msgs.append(cmd(b"set", k, b"v%d" % rng.randint(0, 99)))
            elif c == 1:
                msgs.append(cmd(b"incr", k))
            elif c == 2:
                msgs.append(cmd(b"incr", k, rng.randint(-5, 50)))
            elif c == 3:
                msgs.append(cmd(b"decr", k))
            elif c == 4:
                msgs.append(cmd(b"decr", k, rng.randint(0, 9)))
            elif c == 5:
                msgs.append(cmd(b"sadd", k, b"a", b"b%d" % rng.randint(0, 3)))
            elif c == 6:
                msgs.append(cmd(b"srem", k, b"a"))
            elif c == 7:
                msgs.append(cmd(b"hset", k, b"f1", b"x", b"f2",
                                b"y%d" % rng.randint(0, 3)))
            elif c == 8:
                msgs.append(cmd(b"hdel", k, b"f1"))
            elif c == 9:
                msgs.append(cmd(b"get", k))
            elif c == 10:
                msgs.append(cmd(b"scnt", k))
            elif c == 11:
                msgs.append(cmd(b"sismember", k, b"a"))
            elif c == 12:
                msgs.append(cmd(b"smembers", k))
            elif c == 13:
                msgs.append(cmd(b"hget", k, b"f1"))
            elif c == 14:
                msgs.append(cmd(b"hgetall", k))
            elif c == 15:
                msgs.append(cmd(b"llen", k))
            elif c == 16:
                msgs.append(cmd(b"del", k))          # barrier -> OTHER
            else:
                msgs.append(cmd(b"SET", k, b"up"))   # uppercase -> OTHER
        if rng.random() < 0.3:  # planner demote: non-int counter arg
            msgs.append(cmd(b"incr", rng.choice(keys), b"notanint"))
        if rng.random() < 0.2:  # classify demote: set arity
            msgs.append(cmd(b"set", rng.choice(keys), b"v", b"extra"))
        chunks.append(msgs)
    return chunks


def logview(node):
    return [(e.uuid, e.prev_uuid, e.name, e.size,
             tuple((type(a).__name__, a.val) for a in e.args))
            for e in node.repl_log.entries()]


# ------------------------------------------------------------ the scanner

def test_opcode_table_and_payload_shapes():
    """The frozen opcode ABI: exact lowercase names + arity gates; write
    payloads carry (bulks, raws) views over the SAME bytes objects;
    anything else is OTHER with a fully-parsed Msg."""
    pipeline = [
        (cmd(b"set", b"k", b"v"), 1),
        (cmd(b"incr", b"k"), 2),
        (cmd(b"incr", b"k", b"5"), 3),
        (cmd(b"decr", b"k"), 4),
        (cmd(b"decr", b"k", b"2"), 5),
        (cmd(b"sadd", b"s", b"a"), 6),
        (cmd(b"srem", b"s", b"a"), 7),
        (cmd(b"hset", b"h", b"f", b"v"), 8),
        (cmd(b"hdel", b"h", b"f"), 9),
        (cmd(b"get", b"k"), 10),
        (cmd(b"scnt", b"s"), 11),
        (cmd(b"sismember", b"s", b"a"), 12),
        (cmd(b"smembers", b"s"), 13),
        (cmd(b"hget", b"h", b"f"), 14),
        (cmd(b"hgetall", b"h"), 15),
        (cmd(b"llen", b"l"), 16),
        (cmd(b"SET", b"k", b"v"), 0),          # uppercase: exact-name only
        (cmd(b"set", b"k", b"v", b"x"), 0),    # arity demote
        (cmd(b"hset", b"h", b"f"), 0),         # hset needs pairs
        (cmd(b"del", b"k"), 0),                # barrier
    ]
    raw = b"".join(encode_msg(m) for m, _ in pipeline)
    ops, payloads, pos = scan(raw)
    assert pos == len(raw)
    assert list(ops) == [op for _, op in pipeline]
    for (msg, op), pl in zip(pipeline, payloads):
        if op == 0:
            assert pl == msg                       # full parsed Msg
        elif op < SV._FIRST_READ_OP:
            bulks, raws = pl
            assert [b.val for b in bulks] == list(raws)
            assert all(b.val is r for b, r in zip(bulks, raws))
            assert Arr([SV._OP_HEAD[op]] + bulks) == msg
        else:
            assert Arr([SV._OP_HEAD[op]] + [Bulk(x) for x in pl]) == msg


def test_scan_stops_at_upgrade_and_partials():
    """The scanner never consumes a SYNC/FULLSYNC frame or a partial
    frame — those bytes stay for the pure parser (server/io.py owns the
    upgrade hand-off)."""
    head = encode_msg(cmd(b"set", b"k", b"v"))
    sync = encode_msg(cmd(b"sync", b"0"))
    tail = encode_msg(cmd(b"incr", b"k"))
    raw = head + sync + tail
    ops, _payloads, pos = scan(raw)
    assert list(ops) == [1] and pos == len(head)
    for cut in range(len(raw)):        # every-prefix truncation
        ops, _p, pos = scan(raw[:cut])
        assert pos <= cut
        boundaries = (0, len(head), len(head) + len(sync))
        assert pos in boundaries       # never lands mid-frame


def test_native_drain_vs_pure_parser_split():
    """Parser-level differential: native_drain's (ops, payloads) recover
    the exact message sequence the pure parser sees, across random feed
    boundaries."""
    rng = random.Random(31)
    msgs = [m for ch in mixed_chunks(31, rounds=10) for m in ch]
    wire = b"".join(encode_msg(m) for m in msgs)
    parser = make_parser()
    got = []
    pos = 0
    while pos < len(wire) or len(got) < len(msgs):
        step = rng.randrange(1, 48)
        parser.feed(wire[pos:pos + step])
        pos += step
        while (nat := parser.native_drain()) is not None:
            for op, pl in zip(nat[0], nat[1]):
                got.append(SV._nat_msg(op, pl))
        got.extend(parser.drain())
    assert got == msgs


# ------------------------------------------------------- the serve oracle

def run_pure(chunks, setup=None):
    node = Node(node_id=1, alias="n1", clock=stepping_clock())
    if setup is not None:
        setup(node)
    coal = ServeCoalescer(node, max_run=64)
    out = bytearray()
    for msgs in chunks:
        coal.run_chunk(list(msgs), out)
    return node, bytes(out)


def run_native(chunks, setup=None):
    node = Node(node_id=1, alias="n1", clock=stepping_clock())
    if setup is not None:
        setup(node)
    coal = ServeCoalescer(node, max_run=64)
    out = bytearray()
    for msgs in chunks:
        raw = b"".join(encode_msg(m) for m in msgs)
        ops, payloads, pos = scan(raw)
        assert pos == len(raw)
        coal.run_native_chunk(ops, payloads, out)
    return node, bytes(out)


@pytest.mark.parametrize("seed", range(8))
def test_native_plan_byte_identity(seed):
    """THE oracle: the native-opcode plan path and the pure planner path
    produce byte-identical reply streams, canonical exports, and
    repl_log entry sequences for the same pipelined workload."""
    chunks = mixed_chunks(seed)
    na, ra = run_pure(chunks)
    nb, rb = run_native(chunks)
    assert ra == rb
    assert na.canonical() == nb.canonical()
    assert logview(na) == logview(nb)


def test_chunk_split_invariance():
    """Splitting the same byte stream at arbitrary boundaries into many
    native chunks (partial frames resuming across feeds) changes nothing:
    same replies, same state as the one-chunk pure run."""
    chunks = mixed_chunks(404, rounds=12)
    msgs = [m for ch in chunks for m in ch]
    na, ra = run_pure([msgs])

    rng = random.Random(7)
    node = Node(node_id=1, alias="n1", clock=stepping_clock())
    coal = ServeCoalescer(node, max_run=64)
    parser = make_parser()
    out = bytearray()
    wire = b"".join(encode_msg(m) for m in msgs)
    pos = 0
    while pos < len(wire):
        step = rng.randrange(1, 96)
        parser.feed(wire[pos:pos + step])
        pos += step
        while (nat := parser.native_drain()) is not None:
            coal.run_native_chunk(nat[0], nat[1], out)
        rest = parser.drain()
        if rest:
            coal.run_chunk(rest, out)
    assert bytes(out) == ra
    assert node.canonical() == na.canonical()
    assert logview(node) == logview(na)


def test_oom_shed_parity():
    """The OOM write-shed decision covers native opcodes exactly like
    pure planner entries (CMD_DENYOOM parity via _OOM_OPS)."""
    chunks = [[cmd(b"set", b"k%d" % i, b"v" * 64) for i in range(8)] +
              [cmd(b"incr", b"c"), cmd(b"srem", b"s", b"a"),
               cmd(b"get", b"k0")]]

    def shed_everything(node):  # tiny cap: every data write sheds
        node.governor.configure(maxmemory=1, soft_pct=0.0)

    na, ra = run_pure(chunks, setup=shed_everything)
    nb, rb = run_native(chunks, setup=shed_everything)
    assert ra == rb
    assert b"OOM" in ra or na.stats.oom_shed_writes > 0
    assert na.stats.oom_shed_writes == nb.stats.oom_shed_writes
    assert na.canonical() == nb.canonical()


# ----------------------------------------------- cluster redirect parity

def _install_cluster(node):
    """Group 0 of a 2-group even split, with the first owned workload
    key's slot mid-handoff — so the differential stream carries local
    serves, MOVED redirects, and ASK redirects at once."""
    from constdb_tpu.cluster import ClusterState, even_split, slot_of
    cl = ClusterState(0, even_split(
        2, addrs=["127.0.0.1:7100", "127.0.0.1:7101"]))
    for i in range(8):
        s = slot_of(b"k%d" % i)
        if cl.owns(s):
            cl.migrating[s] = "127.0.0.1:7101"
            break
    node.cluster = cl


@pytest.mark.parametrize("seed", range(8))
def test_native_redirect_byte_parity(seed):
    """Cluster routing differential: with half the keyspace foreign and
    one owned slot in its ASK window, the native-opcode path and the
    pure planner path emit byte-identical MOVED/ASK redirect streams,
    identical surviving state, and the identical redirects_sent count
    (the serve-plan demotion probe is counter-free; only execute()
    counts)."""
    chunks = mixed_chunks(seed)
    na, ra = run_pure(chunks, setup=_install_cluster)
    nb, rb = run_native(chunks, setup=_install_cluster)
    assert b"MOVED " in ra and b"ASK " in ra
    assert ra == rb
    assert na.canonical() == nb.canonical()
    assert logview(na) == logview(nb)
    assert na.cluster.redirects_sent == nb.cluster.redirects_sent > 0


# ------------------------------------------------------------- abi stamp

def test_abi_stamp_matches_sources():
    assert ext.abi_stamp() == NT.expected_abi_stamp()


def test_stale_extension_refused(monkeypatch):
    """A .so whose compiled-in stamp disagrees with the sources on disk
    must not load (frozen-row-layout law, docs/INVARIANTS.md)."""
    monkeypatch.setattr(NT, "expected_abi_stamp", lambda: "0" * 64)
    monkeypatch.setattr(NT, "_ext", None)
    assert NT.load_ext() is None
    monkeypatch.undo()
    assert NT.reload_tiers()


# ------------------------------------------------------- wire blob columns

def test_wire_blob_pack_unpack_differential():
    """native/wire.cpp vs the pure packers: byte-identical columns and
    round-trips across random shapes, including None sentinels and the
    width-4 boundary."""
    from constdb_tpu.replica import wire as W
    rng = random.Random(11)
    trials = []
    for _ in range(200):
        n = rng.randrange(0, 24)
        trials.append([None if rng.random() < 0.2 else
                       bytes(rng.randrange(256)
                             for _ in range(rng.choice((0, 1, 7, 300))))
                       for _ in range(n)])
    trials.append([b"x" * 0x10000, None, b""])  # forces width 4
    for items in trials:
        nat, pure = bytearray(), bytearray()
        W._pack_blobs(nat, items)               # native engaged
        try:
            W._WIRE_NATIVE_CACHE[:] = [None]    # pin pure
            W._pack_blobs(pure, items)
            assert bytes(nat) == bytes(pure)
            rd = W._Reader(memoryview(bytes(pure)))
            got_pure = rd.blobs(len(items))
            pure_pos = rd.pos
        finally:
            W._WIRE_NATIVE_CACHE.clear()
        rd = W._Reader(memoryview(bytes(nat)))
        got_nat = rd.blobs(len(items))
        assert got_nat == got_pure == items
        assert rd.pos == pure_pos


def test_wire_blob_malformed_errors_unchanged():
    """C decline paths fall through to the pure reader's reference
    errors: bad width byte and truncated payloads raise
    WireFormatError either way."""
    from constdb_tpu.replica import wire as W
    rd = W._Reader(memoryview(b"\x03\x01"))
    with pytest.raises(W.WireFormatError):
        rd.blobs(1)
    good = bytearray()
    W._pack_blobs(good, [b"abcdef"])
    rd = W._Reader(memoryview(bytes(good[:-2])))
    with pytest.raises(W.WireFormatError):
        rd.blobs(1)


# ------------------------------------------------------------- end to end

def test_e2e_gauges_and_pinned_leg(tmp_path, monkeypatch):
    """Over a real socket: the native leg counts native_intake_chunks /
    native_intake_msgs; CONSTDB_NATIVE_INTAKE=0 pins them to zero; both
    legs reply byte-identically."""
    import sys
    sys.path.insert(0, str(tmp_path))  # no-op, keeps flake quiet
    from cluster_util import FAST, Client
    from constdb_tpu.server.io import start_node

    chunk = [cmd(b"set", b"k", b"v"), cmd(b"incr", b"c"),
             cmd(b"sadd", b"s", b"a", b"b"), cmd(b"get", b"k"),
             cmd(b"scnt", b"s"), cmd(b"del", b"k"), cmd(b"get", b"k")]

    async def leg(work_dir, native):
        monkeypatch.setenv("CONSTDB_NATIVE_INTAKE", "1" if native else "0")
        node = Node(node_id=1, alias="n1")
        app = await start_node(node, host="127.0.0.1", port=0,
                               work_dir=str(work_dir), **FAST)
        c = await Client().connect(app.advertised_addr)
        c.writer.write(b"".join(encode_msg(m) for m in chunk))
        await c.writer.drain()
        replies = []
        while len(replies) < len(chunk):
            m = c.parser.next_msg()
            if m is not None:
                replies.append(m)
                continue
            c.parser.feed(await asyncio.wait_for(
                c.reader.read(1 << 16), 5.0))
        await c.close()
        gauges = (node.stats.native_intake_chunks,
                  node.stats.native_intake_msgs)
        await app.close()
        return replies, gauges

    async def main():
        d1 = tmp_path / "on"
        d2 = tmp_path / "off"
        d1.mkdir()
        d2.mkdir()
        r_on, g_on = await leg(d1, True)
        r_off, g_off = await leg(d2, False)
        assert g_on[0] > 0 and g_on[1] >= len(chunk)
        assert g_off == (0, 0)
        assert [encode_msg(m) for m in r_on] == \
            [encode_msg(m) for m in r_off]

    asyncio.run(main())
