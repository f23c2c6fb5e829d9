"""Planned read misses write their reply bytes straight from the gathers
(resp/codec.py encode_rows_into / bulk_reply / int_reply; the native pass
is native/resp.cpp resp_encode_rows) — no Arr/Bulk/Int tree — and a
missed SMEMBERS / HGETALL gathers nothing: one native pass goes from the
key's row list to the reply bytes (codec.scan_replier; native/resp.cpp
resp_scan_reply).

Pinned here (docs/INVARIANTS.md "Read coalescing laws"):
  * byte identity: for every planned read kind and every edge of the
    reply's shape, the planner's reply — and the payload it put into the
    reply cache — equals `encode_msg` of the Msg tree the per-command
    handler returns, once through the extension's pass and once through
    the pure twin;
  * the two tiers decline and fail alike on shapes neither encodes;
  * the fused scan pass is the byte twin of `elem_live_rows_batch` + the
    row encoder over every shape of a row list, declines what it will not
    take with nothing appended, and `serve_read_scans_native` counts
    exactly the members / pairs misses it answered;
  * `serve_read_replies_direct` counts exactly the planned misses (never
    a cache hit, never a demotion), is an INFO field, and rides shard
    worker acks.
"""

import asyncio
import types

import numpy as np
import pytest

from constdb_tpu.resp import codec
from constdb_tpu.resp.codec import encode_msg
from constdb_tpu.resp.message import NIL, Arr, Err
from constdb_tpu.server.io import start_node
from constdb_tpu.server.node import Node
from constdb_tpu.server.serve import ServeCoalescer
from constdb_tpu.store.keyspace import BlobList

from cluster_util import FAST, Client
from test_read_path import _pipeline, drive_node
from test_serve_coalesce import cmd, stepping_clock, u
from test_stagetime import info_of

MB = bytes(range(256)) * 4096


def run(node, *cmds) -> None:
    for c in cmds:
        reply = node.execute(c)
        assert type(reply) is not Err, (c, reply)


def kill_value(node, key: bytes, member: bytes) -> None:
    """A live element whose value blob is None (a set member read as a
    hash field has none): the handlers answer the empty bulk."""
    ks = node.ks
    row = ks.el_row(ks.key_index.lookup(key), member)
    assert row >= 0
    ks.el_val[row] = None


# ---- the states: name -> commands (or a function of the node) that build it

def hash_of(n: int):
    return [cmd(b"hset", b"k", b"f%04d" % i, b"v%d" % i) for i in range(n)]


def set_of(n: int):
    return [cmd(b"sadd", b"k", b"m%04d" % i) for i in range(n)]


def list_of(n: int):
    return [cmd(b"rpush", b"k", *(b"x%04d" % i for i in range(n)))]


def none_value(base, member):
    def build(node):
        run(node, *base)
        kill_value(node, b"k", member)
    return build


def list_none_value(node):
    run(node, *list_of(4))
    ks = node.ks
    kid = ks.key_index.lookup(b"k")
    kill_value(node, b"k", sorted(m for m, _v, _t in ks.elem_live(kid))[1])


STATES = {
    "absent": [],
    "hash": hash_of(10),
    "hash-no-live-rows": hash_of(3) + [cmd(b"hdel", b"k", b"f%04d" % i)
                                       for i in range(3)],
    "hash-none-value": none_value(hash_of(3), b"f0001"),
    "hash-empty-member": hash_of(2) + [cmd(b"hset", b"k", b"", b"e")],
    "hash-empty-value": hash_of(2) + [cmd(b"hset", b"k", b"f0001", b"")],
    "hash-1mb-value": hash_of(2) + [cmd(b"hset", b"k", b"big", MB)],
    "hash-1100-rows": hash_of(1100),
    "hash-tombstones": hash_of(9) + [cmd(b"hdel", b"k", b"f%04d" % i)
                                     for i in (0, 3, 4, 8)],
    "set": set_of(7),
    "set-no-live-rows": set_of(3) + [cmd(b"srem", b"k", b"m%04d" % i)
                                     for i in range(3)],
    "set-empty-member": set_of(2) + [cmd(b"sadd", b"k", b"")],
    "set-1mb-member": set_of(2) + [cmd(b"sadd", b"k", MB)],
    "set-1100-rows": set_of(1100),
    "set-tombstones": set_of(9) + [cmd(b"srem", b"k", b"m%04d" % i)
                                   for i in (1, 2, 5, 8)],
    "list": list_of(6),
    "list-no-live-rows": list_of(2) + [cmd(b"lrem", b"k", 0),
                                       cmd(b"lrem", b"k", 0)],
    "list-none-value": list_none_value,
    "list-1mb-value": list_of(2) + [cmd(b"lpush", b"k", MB)],
    "list-1100-rows": list_of(1100),
    "list-tombstones": list_of(8) + [cmd(b"lrem", b"k", i)
                                     for i in (0, 3, 5)],
    "reg": [cmd(b"set", b"k", b"value")],
    "reg-empty": [cmd(b"set", b"k", b"")],
    "reg-1mb": [cmd(b"set", b"k", MB)],
    "cnt-5": [cmd(b"incr", b"k", 5)],
    "cnt-1023": [cmd(b"incr", b"k", 1023)],
    "cnt-1024": [cmd(b"incr", b"k", 1024)],
    "cnt-negative": [cmd(b"decr", b"k", 7)],
    "cnt-large": [cmd(b"incr", b"k", 1 << 40)],
}

# ---- the cases: (kind, state, read); row kinds run through both tiers

ROW_CASES = [
    ("pairs", s, cmd(b"hgetall", b"k")) for s in STATES
    if s.startswith("hash")
] + [
    ("members", s, cmd(b"smembers", b"k")) for s in STATES
    if s.startswith("set")
] + [
    ("lrange", s, cmd(b"lrange", b"k", 0, -1)) for s in STATES
    if s.startswith("list")
] + [
    ("lrange", "list", cmd(b"lrange", b"k", lo, hi))
    for lo, hi in ((1, 3), (0, 0), (2, 100), (-3, -2), (-100, 1), (-1, -1),
                   (4, 2), (-1, -4), (6, 9), (7, -1))
] + [
    ("lrange", "list-1100-rows", cmd(b"lrange", b"k", 1000, -50)),
    ("lrange", "list-tombstones", cmd(b"lrange", b"k", -3, 3)),
]

VALUE_CASES = [
    ("elemget", "hash", cmd(b"hget", b"k", b"f0003")),
    ("elemget", "hash", cmd(b"hget", b"k", b"nofield")),
    ("elemget", "hash-tombstones", cmd(b"hget", b"k", b"f0003")),
    ("elemget", "hash-no-live-rows", cmd(b"hget", b"k", b"f0001")),
    ("elemget", "hash-none-value", cmd(b"hget", b"k", b"f0001")),
    ("elemget", "hash-empty-value", cmd(b"hget", b"k", b"f0001")),
    ("elemget", "hash-empty-member", cmd(b"hget", b"k", b"")),
    ("elemget", "hash-1mb-value", cmd(b"hget", b"k", b"big")),
    ("elemget", "absent", cmd(b"hget", b"k", b"f")),
    ("reg", "reg", cmd(b"get", b"k")),
    ("reg", "reg-empty", cmd(b"get", b"k")),
    ("reg", "reg-1mb", cmd(b"get", b"k")),
    ("reg", "absent", cmd(b"get", b"k")),
    ("ismember", "set", cmd(b"sismember", b"k", b"m0002")),
    ("ismember", "set", cmd(b"sismember", b"k", b"nomember")),
    ("ismember", "set-tombstones", cmd(b"sismember", b"k", b"m0002")),
    ("ismember", "set-empty-member", cmd(b"sismember", b"k", b"")),
    ("ismember", "absent", cmd(b"sismember", b"k", b"m")),
] + [
    ("cnt", s, cmd(b"get", b"k")) for s in STATES if s.startswith("cnt")
] + [
    ("card", s, cmd(b"scnt", b"k")) for s in
    ("set", "set-no-live-rows", "set-1100-rows", "set-tombstones", "absent")
] + [
    ("card", s, cmd(b"hlen", b"k")) for s in
    ("hash", "hash-no-live-rows", "hash-1100-rows", "hash-tombstones")
] + [
    ("llen", s, cmd(b"llen", b"k")) for s in
    ("list", "list-no-live-rows", "list-1100-rows", "list-tombstones",
     "absent")
] + [
    ("pairs", "absent", cmd(b"hgetall", b"k")),
    ("members", "absent", cmd(b"smembers", b"k")),
    ("lrange", "absent", cmd(b"lrange", b"k", 0, -1)),
]


def _case_id(case) -> str:
    kind, state, read = case
    args = b" ".join(a.val if len(a.val) < 16 else b"..."
                     for a in read.items[2:])
    return f"{kind}:{state}:{read.items[0].val.decode()}" + \
        (f"[{args.decode()}]" if args else "")


@pytest.fixture(params=["native", "pure"])
def tier(request, monkeypatch):
    """Which of the direct encoders' two tiers a test runs through."""
    if request.param == "pure":
        monkeypatch.setattr(codec, "_enc_rows", lambda: None)
        monkeypatch.setattr(codec, "_scan_reply", lambda: None)
    elif codec._enc_rows() is None or codec._scan_reply() is None:
        pytest.skip("native extension not built")
    return request.param


def check_identical(_kind: str, state: str, read) -> None:
    node = Node(node_id=1, clock=stepping_clock())
    build = STATES[state]
    if callable(build):
        build(node)
    else:
        run(node, *build)
    st = node.stats
    rc = node.read_cache
    assert rc.enabled
    coal = ServeCoalescer(node)
    # two reads: a one-message chunk takes the per-command path
    got = bytearray()
    coal.run_chunk([read, read], got)
    assert st.serve_read_replies_direct == 2 == rc.misses  # no demotion
    want = encode_msg(node.execute(read))
    assert bytes(got) == want + want
    # the reply cache took the same bytes: the repeat is all hits
    again = bytearray()
    coal.run_chunk([read, read], again)
    assert rc.hits == 2 and st.serve_read_replies_direct == 2
    assert bytes(again) == want + want


@pytest.mark.parametrize("case", ROW_CASES, ids=_case_id)
def test_row_reply_bytes_equal_the_handlers(case, tier):
    check_identical(*case)


@pytest.mark.parametrize("case", VALUE_CASES, ids=_case_id)
def test_value_reply_bytes_equal_the_handlers(case):
    check_identical(*case)


# ------------------------------------------------------ the encoder alone

MEMBERS = [b"a", b"", b"ccc", None, b"e"]
VALUES = [b"1", None, b"", b"4", 5]


@pytest.mark.parametrize("kind,rows,want", [
    ("members", [], b"*0\r\n"),
    ("members", [2, 0], b"*2\r\n$3\r\nccc\r\n$1\r\na\r\n"),
    ("members", [1], b"*1\r\n$0\r\n\r\n"),
    ("pairs", [0, 1, 2],
     b"*3\r\n*2\r\n$1\r\na\r\n$1\r\n1\r\n*2\r\n$0\r\n\r\n$0\r\n\r\n"
     b"*2\r\n$3\r\nccc\r\n$0\r\n\r\n"),
    ("pairs", [], b"*0\r\n"),
    ("lrange", [2, 0, 1], b"*3\r\n$0\r\n\r\n$1\r\n1\r\n$0\r\n\r\n"),
    ("lrange", [], b"*0\r\n"),
])
def test_encoder_appends_and_returns_the_payload(kind, rows, want, tier,
                                                 monkeypatch):
    if tier == "native":   # the C pass takes these itself: no fallback
        monkeypatch.setattr(codec, "_py_encode_rows_into", None)
    for planes in ((MEMBERS, VALUES),
                   (BlobList(Node(node_id=1).ks, MEMBERS), VALUES)):
        out = bytearray(b"head")
        payload = codec.encode_rows_into(out, kind, rows, *planes)
        assert type(payload) is bytes and payload == want
        assert bytes(out) == b"head" + want


@pytest.mark.parametrize("kind,rows,error", [
    ("members", [3], TypeError),       # a dead row's member is None
    ("pairs", [4], TypeError),         # a value that is not bytes
    ("members", [5], IndexError),      # past the plane
    ("pairs", [1 << 70], IndexError),
    ("nokind", [0], KeyError),
])
def test_tiers_fail_alike_and_append_nothing(kind, rows, error, tier):
    out = bytearray(b"head")
    with pytest.raises(error):
        codec.encode_rows_into(out, kind, rows, MEMBERS, VALUES)
    assert out == b"head"


# ------------------------------------------------------ the fused scan pass

SCAN_KINDS = {"members": b"sadd", "pairs": b"hset"}


def scan_state(kind: str, shape: str):
    """-> (ks, kid) of key `k` in the shape's state; `other` is a second
    key whose rows a compaction-stale list still names."""
    node = Node(node_id=1, clock=stepping_clock())
    add = SCAN_KINDS[kind]

    def elems(key: bytes, n: int) -> list:
        return [cmd(add, key, b"m%04d" % i, *([b"v%d" % i] * (kind == "pairs")))
                for i in range(n)]

    n = {"many-rows": 100, "many-rows-tombstoned": 100}.get(shape, 10)
    run(node, *elems(b"k", n), *elems(b"other", 4))
    ks = node.ks
    kid = ks.key_index.lookup(b"k")
    ks._sync_el_lists()
    rows = ks.el_rows_by_kid[kid]
    if shape in ("tombstoned", "many-rows-tombstoned"):
        rem = b"srem" if kind == "members" else b"hdel"
        run(node, *[cmd(rem, b"k", b"m%04d" % i) for i in range(1, n, 3)])
    elif shape == "stale-rows":
        # what _compact_elements leaves until the lists rebuild: rows that
        # now belong to another key, between the key's own
        theirs = ks.el_rows_by_kid[ks.key_index.lookup(b"other")]
        rows[3:3] = theirs[:2]
        rows.append(theirs[3])
    elif shape == "none-value":
        ks.el_val[rows[4]] = None
    elif shape == "empty-list":
        del rows[:]
    elif shape == "no-list":
        kid = 1 << 40
    elif shape == "row-past-columns":
        rows.insert(2, ks.el.n + 5)
    elif shape == "non-bytes-blob":
        ks.el_member[rows[2]] = "str"
    elif shape == "non-int-row":
        rows[1] = float(rows[1])
    else:
        assert shape in ("all-live", "many-rows"), shape
    return ks, kid


SCAN_SHAPES = ["all-live", "tombstoned", "stale-rows", "none-value",
               "empty-list", "no-list", "many-rows", "many-rows-tombstoned"]
# shape -> the error the pure twin raises once the C pass has declined
SCAN_DECLINES = {"row-past-columns": IndexError,
                 "non-bytes-blob": TypeError,
                 "non-int-row": IndexError}


@pytest.mark.parametrize("shape", SCAN_SHAPES)
@pytest.mark.parametrize("kind", list(SCAN_KINDS))
def test_fused_scan_is_the_byte_twin_of_gather_plus_encode(kind, shape):
    scan = codec._scan_reply()
    if scan is None:
        pytest.skip("native extension not built")
    ks, kid = scan_state(kind, shape)
    want = bytearray(b"head")
    twin = codec._py_scan_reply_into(want, kind, kid, ks)
    # the twin is the documented composition, through the row encoder
    rows = ks.elem_live_rows_batch([kid])[0].tolist()
    assert twin == codec.encode_rows_into(bytearray(), kind, rows,
                                          ks.el_member, ks.el_val)
    if shape.startswith("many-rows"):
        assert len(ks.el_rows_by_kid[kid]) >= 64   # the vectorized leg
        assert len(rows) == (100 if shape == "many-rows" else 67)
    elif shape in ("tombstoned", "stale-rows"):
        assert len(rows) == (7 if shape == "tombstoned" else 10)
        assert len(ks.el_rows_by_kid[kid]) > len(rows)
    out = bytearray(b"head")
    payload, native = codec.scan_replier(ks)(out, kind, kid)
    assert native is True
    assert type(payload) is bytes and payload == twin
    assert out == want and bytes(out) == b"head" + twin


@pytest.mark.parametrize("shape", list(SCAN_DECLINES))
@pytest.mark.parametrize("kind", list(SCAN_KINDS))
def test_fused_scan_declines_with_nothing_appended(kind, shape):
    scan = codec._scan_reply()
    if scan is None:
        pytest.skip("native extension not built")
    ks, kid = scan_state(kind, shape)
    el = ks.el
    out = bytearray(b"head")
    assert scan(out, codec._ROW_KINDS[kind], kid, ks.el_rows_by_kid[kid],
                el.kid, el.add_t, el.del_t, ks.el_member,
                ks.el_val) is None
    assert out == b"head"
    # through the replier the pure twin then raises its own error
    with pytest.raises(SCAN_DECLINES[shape]):
        codec.scan_replier(ks)(out, kind, kid)
    assert out == b"head"


@pytest.mark.parametrize("column", ["int32", "strided", "not-a-buffer",
                                    "rows-not-a-list", "values-kind"])
def test_fused_scan_declines_columns_it_cannot_read(column):
    scan = codec._scan_reply()
    if scan is None:
        pytest.skip("native extension not built")
    ks, kid = scan_state("pairs", "all-live")
    el = ks.el
    rows = ks.el_rows_by_kid[kid]
    cols = [el.kid, el.add_t, el.del_t]
    code = 1
    if column == "int32":
        cols[1] = el.add_t.astype(np.int32)
    elif column == "strided":
        cols[2] = np.repeat(el.del_t, 2)[::2]
    elif column == "not-a-buffer":
        cols[0] = el.kid.tolist()
    elif column == "rows-not-a-list":
        rows = tuple(rows)
    else:
        code = codec._ROW_KINDS["values"]
    out = bytearray(b"head")
    assert scan(out, code, kid, rows, *cols, ks.el_member,
                ks.el_val) is None
    assert out == b"head"


@pytest.mark.parametrize("kind", list(SCAN_KINDS))
def test_extension_without_the_entry_point_degrades_to_the_twin(
        kind, monkeypatch):
    """A cst_ext.so from before resp_scan_reply existed: `_scan_reply`
    resolves to None once and the pure twin answers, flagged not native."""
    from constdb_tpu.utils import native_tables
    ext = native_tables.load_ext()
    if ext is None:
        pytest.skip("native extension not built")
    older = types.SimpleNamespace(**{
        name: getattr(ext, name) for name in dir(ext)
        if name != "resp_scan_reply" and not name.startswith("__")})
    monkeypatch.setattr(codec, "_SCAN_REPLY_CACHE", [])
    monkeypatch.setattr(native_tables, "load_ext", lambda: older)
    ks, kid = scan_state(kind, "tombstoned")
    want = bytearray()
    twin = codec._py_scan_reply_into(want, kind, kid, ks)
    out = bytearray()
    payload, native = codec.scan_replier(ks)(out, kind, kid)
    assert native is False and payload == twin and out == want
    assert codec._SCAN_REPLY_CACHE == [None]


def scan_pipeline() -> list:
    """HGETALL / SMEMBERS hits and misses with an HSET / HDEL between
    them; every chunk long enough to take the planner."""
    reads = [cmd(b"hgetall", b"h%d" % i) for i in range(4)] + \
        [cmd(b"smembers", b"s%d" % i) for i in range(3)]
    return [
        [cmd(b"hset", b"h%d" % (i % 4), b"f%d" % i, b"v%d" % i)
         for i in range(24)] +
        [cmd(b"sadd", b"s%d" % (i % 3), b"m%d" % i) for i in range(12)],
        reads,                                    # 7 misses
        reads + [cmd(b"hgetall", b"nokey")],      # 7 hits, 1 absent key
        [cmd(b"hgetall", b"h0"), cmd(b"hset", b"h1", b"f1", b"new"),
         cmd(b"hgetall", b"h1"), cmd(b"hdel", b"h2", b"f2"),
         cmd(b"smembers", b"s0"), cmd(b"hgetall", b"h2"),
         cmd(b"hlen", b"h1"), cmd(b"hgetall", b"h1"),
         cmd(b"srem", b"s1", b"m1"), cmd(b"smembers", b"s1"),
         cmd(b"scnt", b"s1"), cmd(b"hgetall", b"h3")],
    ]


def test_pipelined_scans_reply_as_the_per_command_path_and_are_counted(
        tmp_path):
    """Through ServeCoalescer: replies byte-identical to the per-command
    path, in order, and `serve_read_scans_native` = the members / pairs
    misses of existing keys (never a hit, an absent key's constant, a
    count or a write).  The shard workers' fold of the counter is held
    by test_counter_rides_shard_worker_acks."""
    if codec._scan_reply() is None:
        pytest.skip("native extension not built")
    work = [scan_pipeline()]

    async def main():
        ref = await drive_node(tmp_path / "ref", 1, work)
        one = await drive_node(tmp_path / "one", 64, work)
        return ref, one

    (ref_raw, _c, _r, ref_node), (one_raw, _c1, _r1, one_node) = \
        asyncio.run(main())
    assert one_raw == ref_raw
    assert ref_node.stats.serve_read_scans_native == 0   # never planned
    # chunk 2: 7 misses; chunk 3: hits + an absent key; chunk 4: h1, h2
    # and s1 were written since their fill, h1's second read sits in the
    # same run as its first (the fill comes at the run's end), and HLEN /
    # SCNT count but do not scan
    assert one_node.stats.serve_read_scans_native == 7 + 4
    assert info_of(one_node)["serve_read_scans_native"] == 11
    assert one_node.stats.serve_read_replies_direct == 7 + 1 + 4 + 2


def test_single_value_replies():
    assert codec.bulk_reply(None) == b"$-1\r\n"
    assert codec.bulk_reply(b"") == b"$0\r\n\r\n"
    assert codec.bulk_reply(b"ab") == b"$2\r\nab\r\n"
    for v in (0, 1, 1023, 1024, -1, 1 << 62, -(1 << 62), 1 << 70):
        assert codec.int_reply(v) == b":%d\r\n" % v


# ------------------------------------------------------------ the counter

@pytest.mark.parametrize("cache_mb", ["0", "16"])
def test_counter_is_the_planned_misses_and_in_info(tmp_path, monkeypatch,
                                                   cache_mb):
    """HGETALL-only pipelines: cache off, every read answered is a direct
    reply; cache on and cold, exactly the read-cache misses are — the
    repeats are hits and count nothing.  Demotions (an expiry-armed key,
    a type conflict) never count."""
    monkeypatch.setenv("CONSTDB_READ_CACHE_MB", cache_mb)

    async def main():
        node = Node(node_id=1, clock=stepping_clock())
        app = await start_node(node, host="127.0.0.1", port=0,
                               work_dir=str(tmp_path), serve_batch=512,
                               **FAST)
        app._cron_task.cancel()
        c = await Client().connect(app.advertised_addr)
        try:
            await _pipeline(c, [cmd(b"hset", b"h%d" % (i % 6), b"f%d" % i,
                                    b"v%d" % i) for i in range(30)]
                            + [cmd(b"set", b"reg", b"v"),
                               cmd(b"hset", b"armed", b"f", b"v"),
                               cmd(b"expireat", b"armed", u(1 << 30))])
            before = info_of(node)
            reads = [cmd(b"hgetall", b"h%d" % i) for i in range(6)] + \
                [cmd(b"hgetall", b"nokey")]
            first = await _pipeline(c, reads)
            again = await _pipeline(c, reads)
            mid = info_of(node)
            demoted = await _pipeline(c, [cmd(b"hgetall", b"armed"),
                                          cmd(b"hgetall", b"reg"),
                                          cmd(b"hgetall", b"armed")])
            text = (await c.cmd("info")).val.decode()
            return before, mid, info_of(node), first, again, demoted, text
        finally:
            await c.close()
            await app.close()

    before, mid, after, first, again, demoted, text = asyncio.run(main())

    def delta(a, b, field):
        return b[field] - a[field]

    assert first == again and first[6] == NIL
    assert sum(len(m.items) for m in first[:6]) == 30
    assert delta(before, mid, "serve_reads_coalesced") == 14
    if cache_mb == "0":
        assert delta(before, mid, "serve_read_replies_direct") == 14
        assert delta(before, mid, "read_cache_misses") == 0
    else:
        assert delta(before, mid, "serve_read_replies_direct") == 7
        assert delta(before, mid, "read_cache_misses") == 7
        assert delta(before, mid, "read_cache_hits") == 7
    # the demoted reads answered (a hash, an error, a hash) and counted
    # as misses of the cache's probe, not as direct replies
    assert [type(m) for m in demoted] == [Arr, Err, Arr]
    assert delta(mid, after, "serve_read_replies_direct") == 0
    assert f"serve_read_replies_direct:{after['serve_read_replies_direct']}" \
        in text


def test_counter_rides_shard_worker_acks(tmp_path):
    """serve_shards=2: the workers' planners write the replies, and the
    parent's INFO totals (direct replies, native scans) are the fold of
    their acks — not silently zero."""
    work = [[[cmd(b"hset", b"h%d" % i, b"f", b"v%d" % i) for i in range(8)],
             [cmd(b"hgetall", b"h%d" % i) for i in range(8)],
             [cmd(b"hgetall", b"h%d" % i) for i in range(8)]]]

    async def main():
        g = await drive_node(tmp_path / "a", 64, work, serve_shards=2)
        w = await drive_node(tmp_path / "b", 64, work, serve_shards=1)
        return g, w

    (g_raw, _gc, _gr, g_node), (w_raw, _wc, _wr, w_node) = asyncio.run(main())
    assert g_raw == w_raw
    assert w_node.stats.serve_read_replies_direct == 8
    assert g_node.stats.serve_read_replies_direct == 8
    if codec._scan_reply() is not None:   # all eight are HGETALL misses
        assert w_node.stats.serve_read_scans_native == 8
        assert g_node.stats.serve_read_scans_native == 8
    assert g_node.read_cache.misses == 8 and g_node.read_cache.hits == 8
