"""Command-layer tests: dispatch, per-type semantics, del rewrites, and
two-node convergence through the replication stream (the op-path analogue of
the reference's bin/test.rs oracle harness)."""

import pytest

from constdb_tpu.resp.message import Arr, Bulk, Err, Int, Msg, NIL, Nil, NoReply, Simple, mkcmd
from constdb_tpu.server.node import Node
from constdb_tpu.server.repl_log import ReplLog


class FakeClock:
    def __init__(self, start=1000):
        self.ms = start

    def __call__(self):
        return self.ms

    def advance(self, d=1):
        self.ms += d


def mknode(node_id=1, start_ms=1000):
    clk = FakeClock(start_ms)
    n = Node(node_id=node_id, alias=f"n{node_id}", clock=clk)
    n.clock = clk
    return n


def run(n, *parts):
    reply = n.execute(mkcmd(*parts))
    n.clock.advance()
    return reply


def replay(src: Node, dst: Node):
    """Feed src's repl_log to dst the way a Puller would."""
    for e in list(src.repl_log.entries()):
        dst.apply_replicated(e.name, e.args, src.node_id, e.uuid)


def converged(a: Node, b: Node) -> bool:
    return a.ks.canonical() == b.ks.canonical()


# ---------------------------------------------------------------- basics

def test_set_get_roundtrip():
    n = mknode()
    assert run(n, "set", "k", "v") == Simple(b"OK")
    assert run(n, "get", "k") == Bulk(b"v")
    assert run(n, "get", "missing") == NIL


def test_incr_decr_and_get():
    n = mknode()
    assert run(n, "incr", "c") == Int(1)
    assert run(n, "incr", "c") == Int(2)
    assert run(n, "decr", "c") == Int(1)
    assert run(n, "get", "c") == Int(1)


def test_wrongtype_errors():
    n = mknode()
    run(n, "incr", "c")
    r = run(n, "set", "c", "x")
    assert isinstance(r, Err) and b"WRONGTYPE" in r.val
    r = run(n, "sadd", "c", "m")
    assert isinstance(r, Err)


def test_unknown_and_arity():
    n = mknode()
    assert isinstance(run(n, "nope"), Err)
    assert isinstance(run(n, "get"), Err)


def test_set_ops():
    n = mknode()
    assert run(n, "sadd", "s", "a", "b") == Int(2)
    assert run(n, "sadd", "s", "b") == Int(0)
    r = run(n, "smembers", "s")
    assert sorted(m.val for m in r.items) == [b"a", b"b"]
    assert run(n, "srem", "s", "a") == Int(1)
    assert run(n, "srem", "s", "zz") == Int(0)
    r = run(n, "smembers", "s")
    assert [m.val for m in r.items] == [b"b"]


def test_hash_ops():
    n = mknode()
    assert run(n, "hset", "h", "f1", "v1", "f2", "v2") == Int(2)
    assert run(n, "hget", "h", "f1") == Bulk(b"v1")
    assert run(n, "hget", "h", "zz") == NIL
    r = run(n, "hgetall", "h")
    got = sorted((p.items[0].val, p.items[1].val) for p in r.items)
    assert got == [(b"f1", b"v1"), (b"f2", b"v2")]
    assert run(n, "hdel", "h", "f1") == Int(1)
    assert run(n, "hget", "h", "f1") == NIL


def test_hset_overwrites_value():
    n = mknode()
    run(n, "hset", "h", "f", "v1")
    assert run(n, "hset", "h", "f", "v2") == Int(0)  # not newly-visible
    assert run(n, "hget", "h", "f") == Bulk(b"v2")


# ---------------------------------------------------------------- del

def test_del_bytes_tombstones_and_rewrites():
    n = mknode()
    run(n, "set", "k", "v")
    assert run(n, "del", "k") == Int(1)
    assert run(n, "get", "k") == NIL
    names = [e.name for e in n.repl_log.entries()]
    assert names == [b"set", b"delbytes"]


def test_del_counter_tombstones_and_rewrites():
    n = mknode()
    run(n, "incr", "c")
    run(n, "incr", "c")
    assert run(n, "del", "c") == Int(1)
    assert run(n, "get", "c") == NIL
    e = [e for e in n.repl_log.entries() if e.name == b"delcnt"]
    assert len(e) == 1 and e[0].args[0].val == b"c"
    # resurrect: a later incr counts from 0 (dt gated out the old slots)
    assert run(n, "incr", "c") == Int(1)
    assert run(n, "get", "c") == Int(1)


def test_counter_delete_converges_despite_interleaving():
    """The divergence that killed the reference's delta-based delcnt: a
    deleting node and a lagging node apply {incr, del} in different orders."""
    a, b, c = mknode(1, 1000), mknode(2, 2000), mknode(3, 3000)
    run(a, "incr", "c")
    replay(a, b)                       # b saw a's incr, c did NOT yet
    run(b, "del", "c")                 # b deletes knowing only a's 1 incr
    run(c, "incr", "c")                # c's own concurrent incr (t < b's del)
    # now everything reaches everyone, in different orders
    replay(b, c); replay(a, c)
    replay(c, a); replay(b, a)
    replay(c, b)
    assert converged(a, b) and converged(b, c)
    # c's incr is NEWER than b's delete, so it revives the counter from zero
    assert run(a, "get", "c") == run(b, "get", "c") == run(c, "get", "c") == Int(1)


def test_del_set_and_resurrect():
    n = mknode()
    run(n, "sadd", "s", "a", "b")
    assert run(n, "del", "s") == Int(1)
    assert run(n, "smembers", "s") == Arr([])
    run(n, "sadd", "s", "c")
    r = run(n, "smembers", "s")
    assert [m.val for m in r.items] == [b"c"]


def test_del_missing_key():
    n = mknode()
    assert run(n, "del", "zz") == Int(0)


def test_repl_only_rejected_from_client():
    n = mknode()
    r = run(n, "delset", "s")
    assert isinstance(r, Err) and b"replicas" in r.val


def test_client_only_rejected_from_repl():
    n = mknode()
    from constdb_tpu.errors import InvalidRequestMsg
    with pytest.raises(InvalidRequestMsg):
        n.apply_replicated(b"del", [Bulk(b"k")], 9, 1 << 30)


# ------------------------------------------------------------ replication

def test_two_node_convergence_basic():
    a, b = mknode(1, 1000), mknode(2, 2000)
    run(a, "set", "k", "va")
    run(a, "incr", "c")
    run(a, "sadd", "s", "x", "y")
    run(a, "hset", "h", "f", "v")
    replay(a, b)
    assert run(b, "get", "k") == Bulk(b"va")
    assert run(b, "get", "c") == Int(1)
    assert converged(a, b)


def test_concurrent_set_lww_converges():
    # b's clock is ahead, so b's write wins on both nodes
    a, b = mknode(1, 1000), mknode(2, 50_000)
    run(a, "set", "k", "va")
    run(b, "set", "k", "vb")
    replay(a, b)
    replay(b, a)
    assert run(a, "get", "k") == Bulk(b"vb")
    assert run(b, "get", "k") == Bulk(b"vb")
    assert converged(a, b)


def test_concurrent_counter_adds_sum():
    a, b = mknode(1, 1000), mknode(2, 2000)
    run(a, "incr", "c")
    run(a, "incr", "c")
    run(b, "decr", "c")
    replay(a, b)
    replay(b, a)
    assert run(a, "get", "c") == Int(1)
    assert run(b, "get", "c") == Int(1)
    assert converged(a, b)


def test_sadd_vs_remote_key_delete():
    # a deletes the whole set at a LATER time than b's concurrent sadd:
    # the delete wins for b's members once streams cross
    a, b = mknode(1, 10_000), mknode(2, 1000)
    run(a, "sadd", "s", "m1")
    replay(a, b)
    run(b, "sadd", "s", "m2")       # t ~ 1001 < a's del time
    run(a, "del", "s")              # t ~ 10001
    replay(a, b)                     # b sees delset AFTER its own sadd
    replay(b, a)                     # a sees b's sadd AFTER its delset
    assert run(a, "smembers", "s") == Arr([])
    assert run(b, "smembers", "s") == Arr([])
    assert converged(a, b)


def test_hset_vs_remote_key_delete():
    a, b = mknode(1, 10_000), mknode(2, 1000)
    run(a, "hset", "h", "f1", "v1")
    replay(a, b)
    run(b, "hset", "h", "f2", "v2")
    run(a, "del", "h")
    replay(a, b)
    replay(b, a)
    assert run(a, "hgetall", "h") == Arr([])
    assert run(b, "hgetall", "h") == Arr([])
    assert converged(a, b)


def test_spop_replicates_deterministic_srem():
    a, b = mknode(1, 1000), mknode(2, 2000)
    run(a, "sadd", "s", "a", "b", "c")
    popped = run(a, "spop", "s")
    assert isinstance(popped, Bulk)
    replay(a, b)
    ra = sorted(m.val for m in run(a, "smembers", "s").items)
    rb = sorted(m.val for m in run(b, "smembers", "s").items)
    assert ra == rb and len(ra) == 2 and popped.val not in ra
    names = [e.name for e in a.repl_log.entries()]
    assert b"spop" not in names and names.count(b"srem") == 1


def test_replicated_uuid_advances_local_clock():
    a, b = mknode(1, 50_000), mknode(2, 1000)
    run(a, "set", "k", "va")
    replay(a, b)
    run(b, "set", "k", "vb")  # must win: b's HLC observed a's larger uuid
    replay(b, a)
    assert run(a, "get", "k") == Bulk(b"vb")
    assert run(b, "get", "k") == Bulk(b"vb")


# ------------------------------------------------------------------ expiry

def test_expire_ttl_and_lazy_delete():
    n = mknode()
    run(n, "set", "k", "v")
    assert run(n, "expire", "k", 10) == Int(1)
    ttl = run(n, "ttl", "k")
    assert isinstance(ttl, Int) and 0 <= ttl.val <= 10
    assert run(n, "ttl", "missing") == Int(-2)
    run(n, "set", "k2", "v")
    assert run(n, "ttl", "k2") == Int(-1)


def test_expire_fires_via_hlc():
    clk = FakeClock(1000)
    import constdb_tpu.server.commands as C
    n = Node(node_id=1, clock=clk)
    n.clock = clk
    run(n, "set", "k", "v")
    # bypass wall clock: expire at an absolute uuid just past now
    kid = n.ks.lookup(b"k")
    exp_uuid = (clk.ms + 5) << 22
    n.ks.expire_at(b"k", exp_uuid)
    assert run(n, "get", "k") == Bulk(b"v")
    clk.advance(100)
    assert run(n, "get", "k") == NIL  # lazily tombstoned
    assert not n.ks.alive(kid)


def test_expiry_replicates_absolute_deadline():
    n = mknode()
    run(n, "set", "k", "v")
    run(n, "expire", "k", 10)
    names = [e.name for e in n.repl_log.entries()]
    assert names == [b"set", b"expireat"]


# ------------------------------------------------------------------ misc

def test_node_command():
    n = mknode(7)
    assert run(n, "node", "id") == Int(7)
    assert run(n, "node", "id", "9") == Simple(b"OK")
    assert n.node_id == 9
    assert run(n, "node", "alias") == Bulk(b"n7")
    assert run(n, "node", "alias", "bob") == Simple(b"OK")
    assert run(n, "node", "alias") == Bulk(b"bob")


def test_desc_and_repllog():
    n = mknode()
    run(n, "set", "k", "v")
    d = run(n, "desc", "k")
    assert isinstance(d, Arr)
    uuids = run(n, "repllog", "uuids")
    assert len(uuids.items) == 1
    at = run(n, "repllog", "at", uuids.items[0].val)
    assert isinstance(at, Arr) and at.items[0].val == b"set"
    assert run(n, "repllog", "at", 42) == NIL


def test_readonly_commands_do_not_replicate():
    n = mknode()
    run(n, "get", "k")
    run(n, "smembers", "s")
    assert len(n.repl_log) == 0


# ---------------------------------------------------------------- repl_log

def test_repl_log_ring_eviction_and_resume():
    rl = ReplLog(cap_bytes=100)
    for i in range(1, 50):
        rl.push(i, b"set", [Bulk(b"k" * 10), Bulk(b"v" * 10)])
    assert rl.total_bytes <= 100 + 23
    assert rl.evicted_up_to > 0
    assert not rl.can_resume_from(0)
    assert rl.can_resume_from(rl.evicted_up_to)
    assert rl.first_uuid == rl.evicted_up_to + 1
    e = rl.next_after(rl.evicted_up_to)
    assert e is not None and e.uuid == rl.first_uuid
    assert rl.next_after(49) is None
    assert rl.at(49).uuid == 49


def test_repl_log_rejects_regressing_uuid():
    rl = ReplLog()
    rl.push(10, b"set", [])
    with pytest.raises(ValueError):
        rl.push(10, b"set", [])


def test_gc_frees_acked_tombstones():
    n = mknode()
    run(n, "sadd", "s", "a", "b")
    run(n, "srem", "s", "a")
    kid = n.ks.lookup(b"s")
    assert len(list(n.ks.elem_all(kid))) == 2
    freed = n.gc()  # standalone: horizon = own clock
    assert freed >= 1
    assert len(list(n.ks.elem_all(kid))) == 1


def test_incr_decr_optional_amount():
    """INCR/DECR take an optional amount (Redis INCRBY/DECRBY folded in;
    the reference steps by exactly 1 — type_counter.rs:169-189).  The
    wire stays the absolute cntset total either way, so replaying the
    log on a peer converges."""
    node = mknode()
    assert run(node, "incr", "c") == Int(1)
    assert run(node, "incr", "c", "41") == Int(42)
    assert run(node, "decr", "c", "40") == Int(2)
    assert run(node, "decr", "c") == Int(1)
    peer = mknode(node_id=9, start_ms=5000)
    replay(node, peer)
    assert run(peer, "get", "c") == Int(1)
