"""Coalesced client serving: pipelined RESP chunks ride the merge engine.

The connection loop (server/io.py) used to execute every client command
one at a time through the full dispatch stack — the same per-message
Python shape PR 4 eliminated on the replication intake.  Under pipelined
load a single read chunk carries dozens of commands; this module plans
the chunk instead: contiguous runs of group-encodable write commands
(`server/commands.py SERVE_PLANNERS`) are translated into their
replication rewrites, group-encoded into ONE ColumnarBatch by the same
COLUMNAR_ENCODERS the replication coalescer uses, and landed through
`node.merge_serve_batch` (vectorized host micro-merge,
engine/hostbatch.py).  The run's repl_log entries append in one pass
(`ReplLog.push_many`).

Ordering discipline (docs/INVARIANTS.md "Client-serving coalescing"):

  * replies are produced strictly in request order.  Planned replies are
    computed at plan time from the landed store overlaid with the
    pending run's tracked per-key deltas — byte-identical to what the
    per-command path would have replied, because the whole chunk runs
    synchronously on the single-writer loop (nothing can interleave) and
    every command that could OBSERVE pending rows is a barrier.
  * runs of plannable key-scoped READS (commands.SERVE_READS —
    get/scnt/sismember/smembers/hget/hgetall/lrange/llen) become ONE
    planned read batch instead of N barriers: keys resolve via one
    batched native index call, the device flush narrows to exactly the
    families the run observes (READ_FLUSH_FAMILIES → ensure_flushed_for
    — a clean resident plane serves the batch with zero downloads),
    values gather vectorized per family (store/keyspace.py
    register_get_batch / counter_sum_batch / elem_probe_batch, and
    elem_live_rows_batch for the scans that need a count: scnt/hlen;
    lrange/llen read the list's ordered index, store/keyspace.py
    ListIndex) — except a missed smembers/hgetall, which
    gathers nothing: ONE native pass goes from the key's row list to its
    reply bytes (resp/codec.py scan_replier; INFO
    serve_read_scans_native counts them) — and finished reply bytes
    are served from — and fill — the versioned hot-key reply cache
    (server/read_cache.py, CONSTDB_READ_CACHE_MB).  A run stays open
    across interleaved commands that provably commute with it —
    KEY-CONFINED data commands
    whose first-arg key the run does not read (their replies buffer and
    splice back in exact request order; their HLC ticks and state
    effects happen at their exact positions, as do the reads' own
    ticks, minted at append time) — so a 90:10 pipeline plans
    chunk-sized read batches instead of write-fragmented slivers.
    Read-your-writes is structural: any command touching a run key
    closes the run first, a read batch lands the pending write run
    first iff one of its keys has pending rows (serve_read_flushes),
    and anything unusual (expiry-armed key, type conflict, odd arity)
    demotes to the exact per-command path at its exact position in the
    run.
  * other reads, non-plannable writes, and admin commands are ordered
    BARRIERS: the pending run flushes (lands + logs) first, then the
    command executes on the exact per-command path.  Read-your-writes
    within a pipeline is therefore free, and the reply socket write
    already sits at end-of-chunk, after the covering flush.  Two
    refinements keep barriers from fragmenting runs: a key-scoped READ
    of a key with no pending rows commutes with the whole run and
    executes WITHOUT flushing it (SERVE_KEY_SCOPED_READS), and a
    barrier invalidates only the cached state it could actually have
    changed — the key in its first argument (_invalidate_after) — so
    the chunk's bulk-seeded probe caches (_preprobe) survive.
  * a CHUNK is what one pass of the event loop gathered (server/io.py
    _PassGather): the commands every connection delivered in that pass,
    in the order the loop read them, each connection's own commands in
    their own order — one connection's pipeline, or two hundred
    connections' lone commands.  One coalescer per node plans it; the
    gather cuts the replies at the connections' boundaries (`spans`).
    It is a serial execution of the commands in the gathered order,
    which is a legal history for connections that each have at most
    one chunk outstanding.
  * a chunk that yields a single message takes the per-command path
    untouched — a lone command in a pass of its own pays no micro-merge
    overhead.  `CONSTDB_SERVE_BATCH=1` pins every connection to the
    exact per-command path (server/io.py never constructs a coalescer).
  * a plannable write opens a run only when it has company: an open
    run or a plannable successor.  A command that arrived ALONE on its
    connection (`solo`: a depth-1 client, which can never bring a
    successor of its own) always rides: its pass is its pipeline.
    Reads of other keys between them commute with the run, so the
    depth-1 writes of a pass's connections land as one micro-batch —
    and none of them takes execute(), whose version bump would stale
    the device mirror and send the next rounds to the host twin (the
    placement would flip with every pass that held one write).
  * the run NEVER outlives the chunk: replies must reach the sockets at
    the end of the pass, so the chunk epilogue always flushes.  Between
    chunks the loop runs (peer streams, the next pass), so all
    per-chunk state caches reset at chunk entry.

Exactness notes (why planned == per-command, byte for byte):
  * every plannable command's local apply equals applying its own
    replication rewrite — and PR 4 established that the rewrites'
    columnar GROUP encoding through the merge engine is byte-identical
    to the per-key op path (replica/coalesce.py module docstring).
  * replies: `set` wins its LWW against any landed state (the HLC has
    observed every landed write, so a fresh client uuid is strictly
    newer) and against earlier pending writes (smaller uuids) — the
    planner still runs the exact comparison.  Counter replies derive
    from one landed-state probe per key per run plus tracked deltas;
    element replies from one landed-row probe per (key, member) plus
    tracked visibility flips.
  * uuid parity: planners mint one HLC write-tick per planned command,
    demotions mint none — the uuid sequence is identical to the
    per-command path's, which makes a coalesced node's canonical export
    byte-identical to a CONSTDB_SERVE_BATCH=1 node's under the same
    deterministic workload (tests/test_serve_coalesce.py).
"""

from __future__ import annotations

import time

import numpy as np

from ..errors import CstError
from ..resp.codec import (bulk_reply, encode_into, encode_rows_into,
                          int_reply, scan_replier)
from ..resp.message import (Arr, Bulk, Int, NIL, NoReply, OK, as_bytes,
                            as_int)
from ..replica.coalesce import BatchBuilder
from ..crdt import semantics as S
from ..store.keyspace import KeySpace
from .commands import (CMD_CTRL, CMD_READONLY, COMMANDS, SERVE_ENCODERS,
                       SERVE_KEY_SCOPED_READS, SERVE_PLANNERS,
                       SERVE_READS, list_range)
from .events import EVENT_REPLICATED

_I64 = np.int64


def _enc1(msg) -> bytes:
    b = bytearray()
    encode_into(b, msg)
    return bytes(b)


# pre-encoded constant replies the read planner emits without building
# message objects (absent keys / empty ranges)
_NIL_BYTES = _enc1(NIL)
_INT0_BYTES = _enc1(Int(0))
_EMPTY_ARR_BYTES = _enc1(Arr([]))
# the reply cache's stamp-verify reads host env columns only
_ENV_FAMS = ("env",)

# pre-probe extraction tables (_preprobe): which argument positions of a
# plannable command name state the planners will ask for
_PP_REG = frozenset((b"set",))
_PP_CNT = frozenset((b"incr", b"decr"))
_PP_EL = {b"sadd": (S.ENC_SET, 1), b"srem": (S.ENC_SET, 1),
          b"hset": (S.ENC_DICT, 2), b"hdel": (S.ENC_DICT, 1)}
_PP_ANY = _PP_REG | _PP_CNT | frozenset(_PP_EL)
# below this many plannable commands the batch calls cost more than the
# per-command probes they replace
_PREPROBE_MIN = 16

# demotion sentinel returned by ServeCoalescer.resolve_key on a type
# conflict: the command re-executes per-command and raises the exact
# op-path error (planners compare with `is`)
CONFLICT = object()

# ---------------------------------------------------------- native intake
# Opcode numbering emitted by native/intake.cpp intake_scan — part of the
# extension ABI (the NATIVE-INTAKE-TABLE marker block there names the
# commands; analysis/rules.py NATIVE-CONTRACT pins it against the
# SERVE_PLANNERS / SERVE_READS registries).  run_native_chunk consumes
# these without ever constructing message objects for the plannable set.
_OP_SET, _OP_INCR1, _OP_INCR, _OP_DECR1, _OP_DECR = 1, 2, 3, 4, 5
_OP_SADD, _OP_SREM, _OP_HSET, _OP_HDEL = 6, 7, 8, 9
_OP_GET, _OP_SCNT, _OP_SISMEMBER, _OP_SMEMBERS = 10, 11, 12, 13
_OP_HGET, _OP_HGETALL, _OP_LLEN, _OP_HLEN = 14, 15, 16, 17
_FIRST_READ_OP = _OP_GET

_OP_NAME = {_OP_SET: b"set", _OP_INCR1: b"incr", _OP_INCR: b"incr",
            _OP_DECR1: b"decr", _OP_DECR: b"decr", _OP_SADD: b"sadd",
            _OP_SREM: b"srem", _OP_HSET: b"hset", _OP_HDEL: b"hdel",
            _OP_GET: b"get", _OP_SCNT: b"scnt",
            _OP_SISMEMBER: b"sismember", _OP_SMEMBERS: b"smembers",
            _OP_HGET: b"hget", _OP_HGETALL: b"hgetall", _OP_LLEN: b"llen",
            _OP_HLEN: b"hlen"}
# shared command-head Bulks for demote-time message materialization
# (handlers only ever read them)
_OP_HEAD = {op: Bulk(nm) for op, nm in _OP_NAME.items()}
# CMD_DENYOOM members of the native write set (the maxmemory shed gate;
# srem/hdel free memory and keep riding the run, like the pure path)
_OOM_OPS = frozenset((_OP_SET, _OP_INCR1, _OP_INCR, _OP_DECR1, _OP_DECR,
                      _OP_SADD, _OP_HSET))
# read opcode -> (SERVE_READS spec, canonical lowercase name): the same
# (spec, name) pair _planner_of resolves per message
_NOP_READ = {op: (SERVE_READS[_OP_NAME[op]], _OP_NAME[op])
             for op in range(_FIRST_READ_OP, _OP_HLEN + 1)}
# element-family write opcodes that share one planner body
_NOP_ELEM = {_OP_SADD: (b"sadd", S.ENC_SET, True),
             _OP_SREM: (b"srem", S.ENC_SET, False),
             _OP_HDEL: (b"hdel", S.ENC_DICT, False)}
# pre-encoded planned replies (reply bytes are emitted directly — the
# pure planners' OK/_INT0/Int(n) objects encode to exactly these)
_OK_BYTES = _enc1(OK)
_INT_BYTES = [b":%d\r\n" % i for i in range(1024)]


def _nat_msg(op: int, pl):
    """Materialize the full message for a natively-scanned command —
    only ever on the cold paths (lone command, demotion, OOM shed,
    barrier) where the pure path would hold a parsed message."""
    if op == 0:
        return pl
    if op < _FIRST_READ_OP:
        return Arr([_OP_HEAD[op]] + pl[0])
    return Arr([_OP_HEAD[op]] + [Bulk(x) for x in pl])


def _materialize_msg(m):
    """A read-run slot holds either a parsed message (pure intake) or a
    native `(op, raws)` marker — the message is built only if the read
    demotes to the per-command path."""
    if type(m) is not tuple:
        return m
    op, raw = m
    return Arr([_OP_HEAD[op]] + [Bulk(x) for x in raw])


class ServeCoalescer:
    """The node's planner driving gathered client chunks into the node
    (see module docstring for the discipline).  server/io.py builds ONE
    per node; `client` is set by whoever runs a chunk — None for a
    gathered chunk, the connection's ClientConn for a chunk that keeps
    its own path because its connection has HELLO / CLIENT TRACKING
    state."""

    CONFLICT = CONFLICT

    __slots__ = ("node", "max_run", "nodeid", "ks", "regs", "cnts", "els",
                 "tns", "lists", "_keys", "_pending_keys", "_buf", "_log",
                 "_pending", "_planned", "_lat_pending", "_sample_every",
                 "_now", "_cur_uuid", "client", "_stage")

    def __init__(self, node, max_run: int = 512,
                 sample_every: int | None = None,
                 now=time.monotonic, client=None) -> None:
        from ..conf import env_int
        self.node = node
        # the connection's ClientConn (server/tracking.py): demoted
        # per-command executions carry it into ExecCtx, and planned
        # reads feed note_read for default-mode tracking subscribers
        self.client = client
        self.max_run = max_run
        self.nodeid = node.node_id
        self.ks = node.ks
        # the node's stage clock (utils/stagetime.py): plan / read_batch /
        # read_miss / exec are per-chunk counters, serve_flush also a
        # trace span; none is held across an await (this class has none)
        self._stage = node.stages.stage
        # per-chunk overlay caches: landed-state probes (seeded in bulk
        # by _preprobe) overlaid with the pending run's own writes.
        # Reset at chunk entry; a mid-chunk barrier invalidates only the
        # key it touched (_invalidate_after) — everything else it could
        # not have changed stays warm.
        self._keys: dict = {}   # key -> (kid, enc); kid -1 = run-created
        self.regs: dict = {}    # key -> (rv_t, rv_node)
        self.cnts: dict = {}    # key -> [visible_sum, my_slot_total]
        self.els: dict = {}     # key -> {member -> visible?}
        self.tns: dict = {}     # key -> packed cfg of run-created tensors
        self.lists: dict = {}   # key -> [first member, last member, live
        #                         length, values pushed at the head and at
        #                         the tail not landed] (list_overlay)
        # the pending run
        self._pending_keys: dict = {}  # key with un-landed rows -> the
        #                                 rewrite name that put them there
        self._buf: dict = {}    # rewrite name -> encoder recs
        self._log: list = []    # (uuid, name, args) for push_many
        self._pending = 0
        self._planned = 0
        self._lat_pending: list = []
        self._sample_every = env_int("CONSTDB_SERVE_LAT_SAMPLE", 32) \
            if sample_every is None else sample_every
        self._now = now
        # pre-minted HLC uuid for the command currently being planned
        # (shard-per-core serving: the parent process is the clock
        # authority and mints at route time — see run_chunk `uuids`).
        # None = mint locally via node.hlc (the shards=1 path).
        self._cur_uuid = None

    # -------------------------------------------------------------- chunk

    def run_chunk(self, msgs: list, out: bytearray, uuids: list = None,
                  spans: list = None, solo: bytes = None) -> None:
        """Plan and execute one gathered chunk of client messages,
        appending every reply to `out` in request order.  The pending
        run always lands before this returns.

        `uuids`: pre-minted HLC uuids, one per message, assigned by the
        shard-routing parent (server/serve_shards.py) with the exact
        tick(is_write) discipline the local paths apply — planners and
        demoted per-command executions consume the message's assigned
        uuid instead of ticking.  `spans`: when given, receives
        `len(out)` after each message — the parent slices per-command
        replies out for in-order reassembly across shards, the gather
        (server/io.py) cuts them at the connections' boundaries.
        `solo`: one byte per message, non-zero where the message arrived
        alone on its connection (the module docstring's company rule),
        or None where none did."""
        self._plan_counted(self._plan_chunk, msgs, out, uuids, spans, solo)

    def _plan_counted(self, plan, *args) -> None:
        """One chunk's plan under its `plan` stage; the keys it created,
        by its runs' landings and its per-command executions alike, count
        in INFO serve_keys_created."""
        keys = self.node.ks.keys
        n0 = keys.n
        with self._stage("plan"):
            plan(*args)
        if self.node.ks.keys is keys:
            self.node.stats.serve_keys_created += keys.n - n0

    def _plan_chunk(self, msgs: list, out: bytearray, uuids: list,
                    spans: list, solo: bytes = None) -> None:
        """run_chunk's body, under its `plan` stage (whose self time
        excludes the read batches, per-command executions and flushes
        nested in it)."""
        self.reset_caches()
        if len(msgs) == 1:
            # lone command: the exact per-command path, zero overhead
            # (no invalidation needed — the next chunk resets anyway)
            if uuids is not None:
                self._cur_uuid = uuids[0]
            self._exec(msgs[0], out, count_barrier=False,
                       invalidate=False)
            self._cur_uuid = None
            if spans is not None:
                spans.append(len(out))
            return
        plan = [self._planner_of(m) for m in msgs]
        gov = self.node.governor
        if gov.maxmemory and gov.shed_writes(weight=len(msgs)):
            # maxmemory shed: data-growing writes must NOT be planned —
            # they fall through to _exec, where execute() returns the
            # exact -OOM error without applying, logging, or
            # replicating anything.  Exempt planners (srem/hdel free
            # memory) keep riding the run; reads (tuple plans) are
            # never shed.
            plan = [None if callable(fn) and self._oom_gated(m) else fn
                    for fn, m in zip(plan, msgs)]
        cl = self.node.cluster
        if cl is not None:
            # slot routing (cluster/slots.py): a planned command on a
            # slot this group does not serve must NOT ride the run —
            # demote it to the exact per-command path, where execute()
            # returns (and counts) the byte-exact MOVED/ASK redirect.
            # Keys come from the same first-arg confinement the
            # planners ride (KEY-CONFINED).
            for i, fn in enumerate(plan):
                if fn is None:
                    continue
                if type(fn) is tuple:
                    key, wr = fn[2], False  # read plans serve through
                    #                         an ASK window (write law)
                else:
                    it = msgs[i].items
                    key = it[1].val if len(it) > 1 and \
                        type(it[1]) is Bulk else None
                    wr = True  # callable planners are all write planners
                if key is not None and cl.needs_redirect(key, wr):
                    plan[i] = None
        n = len(msgs)
        n_plannable = sum(callable(f) for f in plan)
        if n_plannable >= _PREPROBE_MIN:
            self._preprobe(msgs, plan)
        max_run = self.max_run
        tick = self.node.hlc.tick
        read_run: list = []
        run_keys: set = set()   # keys the open read run observes
        deferred: list = []     # (msg_index, reply_bytes) executed while
        #                         the run stayed open (disjoint keys)
        for i, msg in enumerate(msgs):
            fn = plan[i]
            if type(fn) is tuple:
                # runs of plannable key-scoped reads become ONE planned
                # read batch (batched key resolution + vectorized family
                # gathers + the versioned reply cache) instead of N
                # per-command barriers.  The run's HLC tick is minted
                # HERE — at the read's exact stream position — so the
                # uuid stream is the per-command path's even though the
                # gathers run later.
                pre = uuids[i] if uuids is not None else tick(False)
                read_run.append((i, msg) + fn + (pre,))
                run_keys.add(fn[2])
                continue
            if read_run:
                # a read run stays open across interleaved commands that
                # provably commute with every read in it: a registered
                # data command confined to a first-arg key OUTSIDE the
                # run's key set (KEY-CONFINED — the same convention the
                # planners and the reply cache ride).  Anything else —
                # a write/read of a run key, CTRL, membership, unknown —
                # closes the run first, so each read still gathers the
                # state of its exact stream position.
                key = self._confined_key(msg)
                if key is None or key in run_keys:
                    self._run_read_batch(read_run, out, spans, deferred)
                    read_run = []
                    run_keys = set()
                    deferred = []
            if uuids is not None:
                self._cur_uuid = uuids[i]
            sink = out
            if read_run:
                # reply bytes buffer until the run closes (replies are
                # emitted strictly in request order); state effects
                # happen NOW, at this command's exact position
                sink = bytearray()
            isolated = False
            handled = False
            # a plannable command opens a run only when it has company
            # (an open run, or a plannable successor) — an isolated
            # write between barriers is cheaper per-command than as a
            # one-row micro-merge
            if fn is not None:
                if self._pending or \
                        (i + 1 < n and callable(plan[i + 1])) or \
                        (solo is not None and solo[i]):
                    reply = fn(self, msg.items)
                    if reply is not None:
                        encode_into(sink, reply)
                        handled = True
                    # else: demoted — a real barrier (exact op error)
                else:
                    isolated = True  # per-command by CHOICE, not a barrier
            if not handled:
                if self._pending and not self._scoped_read_commutes(msg):
                    self.flush()
                self._exec(msg, sink, count_barrier=not isolated)
            if sink is out:
                if spans is not None:
                    spans.append(len(out))
            else:
                deferred.append((i, bytes(sink)))
            if handled and self._pending >= max_run:
                self.flush()
        if read_run:
            self._run_read_batch(read_run, out, spans, deferred)
        self._cur_uuid = None
        if self._pending:
            self.flush()

    def run_native_chunk(self, ops: bytes, payloads: list,
                         out: bytearray, spans: list = None,
                         solo: bytes = None) -> None:
        """Plan and execute one natively-scanned chunk (`ops`/`payloads`
        from native/intake.cpp intake_scan, via resp/codec.py
        native_drain; the gather joins the connections' scans and hands
        a pure message over as opcode 0).  Control flow mirrors
        run_chunk exactly; native opcodes skip message construction,
        classification, and planner dispatch, but share every stateful
        primitive (tick / resolve_key / count_elem_flips / add / flush /
        _exec), so replies, uuid streams, planes, and repl_log entries
        stay byte-identical to the pure path (tests/test_resp_fuzz.py
        pins the differential).  `spans` and `solo` as run_chunk's.
        Never used on the sharded plane — io.py builds a coalescer only
        when no plane is active — so there are no pre-minted uuids."""
        self._plan_counted(self._plan_native_chunk, ops, payloads, out,
                           spans, solo)

    def _plan_native_chunk(self, ops: bytes, payloads: list,
                           out: bytearray, spans: list = None,
                           solo: bytes = None) -> None:
        """run_native_chunk's body, under its `plan` stage."""
        self.reset_caches()
        n = len(ops)
        if n == 1:
            # lone command: the exact per-command path, zero overhead
            self._exec(_nat_msg(ops[0], payloads[0]), out,
                       count_barrier=False, invalidate=False)
            if spans is not None:
                spans.append(len(out))
            return
        # plan[i]: a native opcode int, or _planner_of's result for an
        # OP_OTHER message (callable / read-spec tuple / None)
        plan = [op if op else self._planner_of(payloads[i])
                for i, op in enumerate(ops)]
        gov = self.node.governor
        if gov.maxmemory and gov.shed_writes(weight=n):
            plan = [None if (type(fn) is int and fn in _OOM_OPS) or
                    (callable(fn) and self._oom_gated(pl)) else fn
                    for fn, pl in zip(plan, payloads)]
        cl = self.node.cluster
        if cl is not None:
            # slot routing, native intake: same demotion as run_chunk —
            # a native opcode IS a registered key-confined data command
            # (write key = first raw arg, read key = pl[0]), so demoting
            # it to _exec lands on the SAME execute() redirect and the
            # reply bytes stay byte-identical to the pure drain
            # (tests/test_native_intake.py redirect differential)
            for i, fn in enumerate(plan):
                if fn is None:
                    continue
                if type(fn) is int:
                    pl = payloads[i]
                    if fn < _FIRST_READ_OP:
                        key, wr = pl[1][0], True
                    else:
                        key, wr = pl[0], False
                elif type(fn) is tuple:
                    key, wr = fn[2], False
                else:
                    it = payloads[i].items
                    key = it[1].val if len(it) > 1 and \
                        type(it[1]) is Bulk else None
                    wr = True  # callable planners are all write planners
                if key is not None and cl.needs_redirect(key, wr):
                    plan[i] = None
        n_plannable = sum(1 for fn in plan if callable(fn) or
                          (type(fn) is int and fn < _FIRST_READ_OP))
        if n_plannable >= _PREPROBE_MIN:
            reg_keys: list = []
            cnt_keys: list = []
            el_cmds: list = []
            for fn, pl in zip(plan, payloads):
                if type(fn) is int:
                    if fn >= _FIRST_READ_OP:
                        continue
                    raw = pl[1]
                    if fn == _OP_SET:
                        reg_keys.append(raw[0])
                    elif fn <= _OP_DECR:
                        cnt_keys.append(raw[0])
                    elif fn == _OP_HSET:
                        el_cmds.append((raw[0], S.ENC_DICT, None,
                                        raw[1::2]))
                    else:  # sadd / srem / hdel
                        ent = _NOP_ELEM[fn]
                        el_cmds.append((raw[0], ent[1], None, raw[1:]))
                elif callable(fn):
                    self._pp_classify(pl.items, reg_keys, cnt_keys,
                                      el_cmds)
            self._preprobe_core(reg_keys, cnt_keys, el_cmds)
        max_run = self.max_run
        tick = self.node.hlc.tick
        read_run: list = []
        run_keys: set = set()
        deferred: list = []
        for i in range(n):
            fn = plan[i]
            pl = payloads[i]
            if type(fn) is int and fn >= _FIRST_READ_OP:
                # native plannable read: the (spec, name, key, extra,
                # parsed) tuple comes from constant tables; a message is
                # built only if the batch executor demotes it
                spec_name = _NOP_READ[fn]
                if len(pl) > 1:  # sismember / hget carry a member arg
                    extra = parsed = pl[1]
                else:
                    extra, parsed = b"", None
                pre = tick(False)
                read_run.append((i, (fn, pl), spec_name[0], spec_name[1],
                                 pl[0], extra, parsed, pre))
                run_keys.add(pl[0])
                continue
            if type(fn) is tuple:
                pre = tick(False)
                read_run.append((i, pl) + fn + (pre,))
                run_keys.add(fn[2])
                continue
            op = ops[i]
            if read_run:
                # same commutes-with-the-run gate as run_chunk: a native
                # opcode IS a registered key-confined data command, so
                # its confined key is its first payload byte-string
                # (write: first raw arg; read: pl[0] — a slot-demoted
                # native read reaches here with fn=None but op set)
                if op:
                    key = pl[1][0] if op < _FIRST_READ_OP else pl[0]
                else:
                    key = self._confined_key(pl)
                if key is None or key in run_keys:
                    self._run_read_batch(read_run, out, spans, deferred)
                    read_run = []
                    run_keys = set()
                    deferred = []
            sink = out
            if read_run:
                sink = bytearray()
            isolated = False
            handled = False
            if fn is not None:
                nxt = plan[i + 1] if i + 1 < n else None
                if self._pending or callable(nxt) or \
                        (type(nxt) is int and nxt < _FIRST_READ_OP) or \
                        (solo is not None and solo[i]):
                    if type(fn) is int:
                        handled = self._nplan_native(fn, pl, sink)
                    else:
                        reply = fn(self, pl.items)
                        if reply is not None:
                            encode_into(sink, reply)
                            handled = True
                else:
                    isolated = True
            if not handled:
                msg = _nat_msg(op, pl)
                if self._pending and not self._scoped_read_commutes(msg):
                    self.flush()
                self._exec(msg, sink, count_barrier=not isolated)
            if sink is not out:
                deferred.append((i, bytes(sink)))
            elif spans is not None:
                spans.append(len(out))
            if handled and self._pending >= max_run:
                self.flush()
        if read_run:
            self._run_read_batch(read_run, out, spans, deferred)
        self._cur_uuid = None
        if self._pending:
            self.flush()

    def _nplan_native(self, op: int, pl: tuple, sink: bytearray) -> bool:
        """Plan one native write opcode from its raw payload — each
        branch is the exact planner body (commands.py _plan_set /
        _plan_counter_step / _plan_elem_update / _plan_hset) minus the
        message objects, emitting pre-encoded reply bytes.  Returns
        False to demote: the caller re-executes per-command, identical
        to a pure planner returning None."""
        bulks, raw = pl
        key = raw[0]
        if op == _OP_SET:
            kid = self.resolve_key(key, S.ENC_BYTES)
            if kid is CONFLICT:
                return False
            uuid = self.tick()
            st = self.regs.get(key)
            if st is None:
                st = (int(self.ks.keys.rv_t[kid]),
                      int(self.ks.keys.rv_node[kid])) if kid >= 0 \
                    else (0, 0)
            won = not S.lww_wins(st[0], st[1], uuid, self.nodeid)
            if won:
                self.regs[key] = (uuid, self.nodeid)
            self.add(b"set", (key, uuid, raw[1]), bulks)
            sink += _OK_BYTES if won else _INT0_BYTES
            return True
        if op <= _OP_DECR:  # the incr/decr family
            if op == _OP_INCR1:
                delta = 1
            elif op == _OP_DECR1:
                delta = -1
            else:
                try:
                    delta = as_int(bulks[1])
                except CstError:
                    return False  # non-integer delta: exact op error
                if op == _OP_DECR:
                    delta = -delta
            kid = self.resolve_key(key, S.ENC_COUNTER)
            if kid is CONFLICT:
                return False
            uuid = self.tick()
            st = self.cnts.get(key)
            if st is None:
                ks = self.ks
                st = [ks.counter_sum(kid),
                      ks.counter_slot_total(kid, self.nodeid)] \
                    if kid >= 0 else [0, 0]
                self.cnts[key] = st
            st[0] += delta
            st[1] += delta
            self.node.undo.record(uuid, key, delta)
            self.add(b"cntset", (key, uuid, st[1]),
                     [bulks[0], Int(st[1])])
            v = st[0]
            sink += _INT_BYTES[v] if 0 <= v < 1024 else b":%d\r\n" % v
            return True
        if op == _OP_HSET:
            fields = list(raw[1::2])
            kid = self.resolve_key(key, S.ENC_DICT)
            if kid is CONFLICT:
                return False
            uuid = self.tick()
            cnt = self.count_elem_flips(key, kid, fields, True)
            self.add(b"hset", (key, uuid, fields, list(raw[2::2])), bulks)
            sink += _INT_BYTES[cnt] if cnt < 1024 else b":%d\r\n" % cnt
            return True
        name, enc, add = _NOP_ELEM[op]  # sadd / srem / hdel
        members = list(raw[1:])
        kid = self.resolve_key(key, enc)
        if kid is CONFLICT:
            return False
        uuid = self.tick()
        cnt = self.count_elem_flips(key, kid, members, add)
        self.add(name, (key, uuid, members), bulks)
        sink += _INT_BYTES[cnt] if cnt < 1024 else b":%d\r\n" % cnt
        return True

    @staticmethod
    def _oom_gated(msg) -> bool:
        """Is this (already known-plannable) command a data-growing
        write the maxmemory soft watermark sheds (CMD_DENYOOM)?"""
        from .commands import CMD_DENYOOM
        name = msg.items[0].val
        cmd = COMMANDS.get(name) or COMMANDS.get(name.lower())
        return cmd is not None and bool(cmd.flags & CMD_DENYOOM)

    @staticmethod
    def _planner_of(msg):
        """One classification pass per message: a SERVE_PLANNERS
        callable (plannable write), a read spec TUPLE `(spec, name,
        key, extra, parsed)` for an exact-arity key-scoped read the
        batch executor can serve (commands.SERVE_READS), or None for
        everything else — which falls back to the scoped-read / barrier
        machinery, raising the exact arity/coercion error on the
        per-command path."""
        if type(msg) is not Arr or not msg.items:
            return None
        items = msg.items
        head = items[0]
        if type(head) is not Bulk:
            return None
        name = head.val
        fn = SERVE_PLANNERS.get(name)
        if fn is not None:
            return fn
        spec = SERVE_READS.get(name)
        if spec is None:
            if name in COMMANDS:
                return None
            # mirror the dispatch table's lazy lowercase fallback
            name = name.lower()
            fn = SERVE_PLANNERS.get(name)
            if fn is not None:
                return fn
            spec = SERVE_READS.get(name)
            if spec is None:
                return None
        if len(items) != spec.arity or type(items[1]) is not Bulk:
            return None
        kind = spec.kind
        if kind in ("elemget", "ismember"):
            try:
                extra = as_bytes(items[2])
            except CstError:
                return None
            return (spec, name, items[1].val, extra, extra)
        if kind == "lrange":
            try:
                rng = (as_int(items[2]), as_int(items[3]))
            except CstError:
                return None
            return (spec, name, items[1].val, b"%d:%d" % rng, rng)
        return (spec, name, items[1].val, b"", None)

    def _preprobe(self, msgs: list, plan: list) -> None:
        """Seed the run caches for a whole chunk with BATCHED index
        probes: one native key lookup for every plannable command's key,
        one counter-slot batch, one member-interner batch, one element
        combo batch — replacing the per-command (and per-member) hash
        probes the planners would otherwise pay.  Seeds are exactly the
        values the first per-command probe would read (the store cannot
        change between here and the plans — the chunk runs synchronously
        and everything mutation-capable resets the caches), so planner
        behavior is byte-identical with or without this pass.  Commands
        whose arguments do not parse are simply not seeded — their
        planner demotes them as usual."""
        reg_keys: list = []
        cnt_keys: list = []
        el_cmds: list = []   # (key, want_enc, member item step, items)
        for i, fn in enumerate(plan):
            if not callable(fn):
                continue  # None, or a read-spec tuple (reads resolve
                #           through their own batched path)
            self._pp_classify(msgs[i].items, reg_keys, cnt_keys, el_cmds)
        self._preprobe_core(reg_keys, cnt_keys, el_cmds)

    @staticmethod
    def _pp_classify(items: list, reg_keys: list, cnt_keys: list,
                     el_cmds: list) -> None:
        """Sort one plannable command's probe-able arguments into the
        pre-probe buckets (the message-based extraction half of
        _preprobe; run_native_chunk feeds _preprobe_core directly from
        raw payloads instead)."""
        if len(items) < 2:
            return
        k = items[1]
        if type(k) is not Bulk:
            return
        nm = items[0].val
        if nm not in _PP_ANY:
            nm = nm.lower()
        if nm in _PP_REG:
            reg_keys.append(k.val)
        elif nm in _PP_CNT:
            cnt_keys.append(k.val)
        else:
            ent = _PP_EL.get(nm)
            if ent is None:
                return
            # member extraction is deferred until the key batch shows
            # the key exists with the right encoding — new keys (and
            # demotion-bound conflicts) never pay it
            el_cmds.append((k.val, ent[0], ent[1], items))

    def _preprobe_core(self, reg_keys: list, cnt_keys: list,
                       el_cmds: list) -> None:
        """The batched index probes behind _preprobe.  `el_cmds` rows
        are `(key, want_enc, step, seq)`: step > 0 slices member items
        out of a message item list (`seq[2::step]`, Bulk-gated); step
        None means `seq` already holds raw member byte-strings (the
        native intake path pre-slices its payloads)."""
        node = self.node
        # narrow barrier: the probes below read the key/reg/cnt/el
        # planes only — resident TENSOR payload pools stay put (their
        # stamps are host-authoritative and nothing here reads payloads)
        node.ensure_flushed_for(("env", "reg", "cnt", "el"))
        ks = self.ks
        all_keys = reg_keys + cnt_keys + [e[0] for e in el_cmds]
        if not all_keys:
            return
        kids = ks.key_index.lookup_batch(all_keys).tolist()
        enc_col = ks.keys.enc
        keys_cache = self._keys
        pos = 0
        if reg_keys:
            regs = self.regs
            rv_t, rv_n = ks.keys.rv_t, ks.keys.rv_node
            for key in reg_keys:
                kid = kids[pos]
                pos += 1
                if kid >= 0 and key not in keys_cache:
                    e = int(enc_col[kid])
                    keys_cache[key] = (kid, e)
                    if e == S.ENC_BYTES:
                        regs[key] = (int(rv_t[kid]), int(rv_n[kid]))
        if cnt_keys:
            cnts = self.cnts
            probe: list = []
            for key in cnt_keys:
                kid = kids[pos]
                pos += 1
                if kid >= 0 and key not in keys_cache:
                    e = int(enc_col[kid])
                    keys_cache[key] = (kid, e)
                    if e == S.ENC_COUNTER and key not in cnts:
                        probe.append((key, kid))
            if probe:
                kid_arr = np.fromiter((p[1] for p in probe), dtype=_I64,
                                      count=len(probe))
                rows = ks.cnt_rows_lookup(ks.rank_of(self.nodeid), kid_arr)
                vals = np.where(rows >= 0, ks.cnt.val[rows], 0).tolist()
                sums = ks.keys.cnt_sum[kid_arr].tolist()
                for (key, _kid), sm, tot in zip(probe, sums, vals):
                    cnts[key] = [sm, tot]
        if el_cmds:
            els = self.els
            flat_kids: list = []
            flat_members: list = []
            seed: list = []  # per-key member dict aligned w/ flat_members
            for key, want, step, seq in el_cmds:
                kid = kids[pos]
                pos += 1
                if kid < 0:
                    continue
                if key not in keys_cache:
                    keys_cache[key] = (kid, int(enc_col[kid]))
                if keys_cache[key][1] != want:
                    continue  # the planner demotes this command
                d = els.get(key)
                if d is None:
                    d = els[key] = {}
                if step is None:  # native payload: members are raw bytes
                    for mv in seq:
                        flat_kids.append(kid)
                        flat_members.append(mv)
                        seed.append(d)
                    continue
                for m in seq[2::step]:
                    if type(m) is Bulk:
                        flat_kids.append(kid)
                        flat_members.append(m.val)
                        seed.append(d)
            if flat_members:
                mids = ks.member_index.lookup_batch(flat_members)
                combos = (np.fromiter(flat_kids, dtype=_I64,
                                      count=len(flat_kids))
                          << KeySpace.MEMBER_BITS) | mids
                rows = ks.el_index.lookup_batch(combos)
                rows[mids < 0] = -1
                hit = rows >= 0
                alive = np.zeros(len(rows), dtype=bool)
                if hit.any():
                    hr = rows[hit]
                    alive[hit] = ks.el.add_t[hr] >= ks.el.del_t[hr]
                for d, m, a in zip(seed, flat_members, alive.tolist()):
                    if m not in d:
                        d[m] = a

    def reset_caches(self) -> None:
        self._keys.clear()
        self.regs.clear()
        self.cnts.clear()
        self.els.clear()
        self.tns.clear()
        self.lists.clear()
        self.ks = self.node.ks
        self.nodeid = self.node.node_id

    def _scoped_read_commutes(self, msg) -> bool:
        """True iff `msg` is a key-scoped read whose key has no pending
        rows (see commands.SERVE_KEY_SCOPED_READS) — it then commutes
        with the whole pending run and executes without flushing it."""
        if type(msg) is not Arr or len(msg.items) < 2:
            return False
        head = msg.items[0]
        if type(head) is not Bulk:
            return False
        name = head.val
        if name not in SERVE_KEY_SCOPED_READS and \
                name.lower() not in SERVE_KEY_SCOPED_READS:
            return False
        key = msg.items[1]
        return type(key) is Bulk and key.val not in self._pending_keys

    # ------------------------------------------------------ read planning

    def _confined_key(self, msg):
        """The first-arg key a registered DATA command's effects are
        confined to (the KEY-CONFINED convention the planners, the reply
        cache, and the shard router already rely on), or None for
        anything whose effects cannot be scoped to one key — CTRL
        (subcommands, not keys), membership (cluster state), unknown
        commands, non-Bulk keys.  None tells run_chunk a deferred read
        run cannot stay open across this command."""
        if type(msg) is not Arr:
            return None
        items = msg.items
        if len(items) < 2 or type(items[0]) is not Bulk or \
                type(items[1]) is not Bulk:
            return None
        name = items[0].val
        cmd = COMMANDS.get(name)
        if cmd is None:
            cmd = COMMANDS.get(name.lower())
            if cmd is None:
                return None
        if cmd.flags & CMD_CTRL:
            return None
        if not cmd.families and not (cmd.flags & CMD_READONLY):
            return None  # membership: meet/forget touch cluster state
        return items[1].val

    def _run_read_batch(self, specs: list, out: bytearray, spans,
                        extras=None) -> None:
        """Serve one planned read run as a batch — replies
        byte-identical to the per-command path, emitted strictly in
        request order (see the module docstring's read plane section).
        `specs`: `(msg_index, msg, spec, name, key, extra, parsed,
        uuid)` tuples from run_chunk (`uuid` pre-minted at the read's
        stream position).  `extras`: reply bytes of commands executed
        while the run stayed open — `(msg_index, payload)`, spliced
        back at their exact positions."""
        with self._stage("read_batch"):
            self._read_batch(specs, out, spans, extras)

    def _read_batch(self, specs: list, out: bytearray, spans,
                    extras) -> None:
        """_run_read_batch's body under its `read_batch` stage: the
        reply-cache probe and the splice of hits; what the cache cannot
        answer goes on to _read_misses."""
        node = self.node
        st = node.stats
        cl = self.client
        if cl is not None and cl.tracking == 1:
            # default-mode client tracking (server/tracking.py): every
            # read in the batch is a key this connection observes — the
            # tap covers cache hits, planned gathers, AND demotions
            # (the demoted re-execute records again; note_read is
            # idempotent per key)
            trk = node.tracking
            for sp in specs:
                trk.note_read(cl, sp[4])
        # read-your-writes: the run must land first iff a read observes
        # a key with pending rows; reads of un-pending keys commute
        # with the whole pending run (the batched twin of
        # SERVE_KEY_SCOPED_READS)
        # — except an LRANGE / LLEN of a list whose pending rows are the
        # run's pushes: it reads them from the run overlay (list_overlay)
        # beside the landed index, and never from the reply cache
        over = frozenset()
        if self._pending:
            pend = self._pending_keys
            mine = [j for j, sp in enumerate(specs) if sp[4] in pend]
            if mine:
                if all(sp[2].kind in ("lrange", "llen")
                       and pend[sp[4]] == b"lins" and sp[4] in self.lists
                       for sp in map(specs.__getitem__, mine)):
                    over = frozenset(mine)
                else:
                    # a key no landed row holds yet was created by the run
                    lookup = self.ks.key_index.lookup
                    if any(lookup(specs[j][4]) < 0 for j in mine):
                        st.serve_read_flushes_created += 1
                    self.flush()
                    st.serve_read_flushes += 1
        ks = self.ks
        rc = node.read_cache
        use_cache = rc.enabled
        n = len(specs)
        if use_cache and len(rc):
            # probe BEFORE any key resolution: a hit needs nothing but
            # its stamp verify (the entry carries its kid), so hot-key
            # batches skip the resolution/envelope machinery entirely.
            # env must be host-fresh for the verify; probing is pure,
            # so running it before the ticks cannot affect uuid parity.
            node.ensure_flushed_for(_ENV_FAMS)
            if over:
                probe = [j for j in range(n) if j not in over]
                hits = [None] * n
                for j, h in zip(probe, rc.get_batch(
                        [(specs[j][3], specs[j][4], specs[j][5])
                         for j in probe], ks)):
                    hits[j] = h
            else:
                hits = rc.get_batch([(sp[3], sp[4], sp[5]) for sp in specs],
                                    ks)
        else:
            if use_cache:
                rc.misses += n
            hits = [None] * n
        miss = [j for j in range(n) if hits[j] is None]
        if not miss:
            # the hot steady state: every reply spliced from the cache
            # (ticks were minted at append time), stats batched
            st.cmds_processed += n
            st.serve_reads_coalesced += n
            if extras:
                self._emit_merged(specs, hits, extras, out, spans)
                return
            for payload in hits:
                out += payload
                if spans is not None:
                    spans.append(len(out))
            return
        with self._stage("read_miss"):
            self._read_misses(specs, hits, miss, out, spans, extras, over)

    def _read_misses(self, specs: list, hits: list, miss: list,
                     out: bytearray, spans, extras, over=frozenset()) -> None:
        """The miss branch of a planned read run, under its `read_miss`
        stage: key resolution, family gathers, reply build, cache fill —
        and the in-order emit of hits and misses alike.  `over`: the
        specs that read a list through the run overlay."""
        node = self.node
        st = node.stats
        ks = self.ks
        rc = node.read_cache
        use_cache = rc.enabled
        n = len(specs)
        resolved: dict = {}
        env: dict = {}
        if miss:
            # narrow device flush: only the families the MISSES observe
            # (a clean resident plane serves the batch with zero flush
            # downloads)
            fams: set = set()
            for j in miss:
                fams.update(specs[j][2].families)
            node.ensure_flushed_for(tuple(fams))
            keys_cache = self._keys
            # batched key resolution: one native index call for every
            # missing key not already probed this chunk.  Entries
            # created by the pending run (kid == -1) re-resolve — a
            # flush above (or earlier in the chunk) may have landed
            # them.
            fresh: list = []
            seen: set = set()
            for j in miss:
                key = specs[j][4]
                ent = keys_cache.get(key)
                if (ent is None or ent[0] < 0) and key not in seen:
                    seen.add(key)
                    fresh.append(key)
            if fresh:
                kids = ks.key_index.lookup_batch(fresh).tolist()
                enc_col = ks.keys.enc
                for key, kid in zip(fresh, kids):
                    if kid >= 0:
                        keys_cache[key] = (kid, int(enc_col[kid]))
            # one envelope gather over the misses (alive / expiry-
            # demote decisions) — scalar below the vectorization floor
            for j in miss:
                resolved[j] = keys_cache.get(specs[j][4], (-1, -1))
            keys_t = ks.keys
            if not keys_t.n:  # empty keyspace: every read is absent
                for j in miss:
                    env[j] = (0, 0, 0)
            elif len(miss) < 16:
                ct_c, dt_c, exp_c = keys_t.ct, keys_t.dt, keys_t.expire
                for j in miss:
                    kid = resolved[j][0]
                    env[j] = (int(ct_c[kid]), int(dt_c[kid]),
                              int(exp_c[kid])) if kid >= 0 else (0, 0, 0)
            else:
                kid_arr = np.fromiter((resolved[j][0] for j in miss),
                                      dtype=_I64, count=len(miss))
                safe = np.maximum(kid_arr, 0)
                ct_l = keys_t.ct[safe].tolist()
                dt_l = keys_t.dt[safe].tolist()
                exp_l = keys_t.expire[safe].tolist()
                for x, j in enumerate(miss):
                    env[j] = (ct_l[x], dt_l[x], exp_l[x])
        # the ordered walk: demotions, hit emits, and miss bucketing
        # happen in request order (ticks were already minted at append
        # time, so the HLC stream is exactly the per-command path's)
        slots: list = [None] * n
        cacheable: list = [False] * n
        miss_scan: list = []   # el-family full scans (card)
        miss_list: list = []   # lrange / llen: (kid, range or None)
        miss_over: list = []   # the same through the run overlay: (key,
        #                        kid, range or None)
        miss_probe: list = []  # el-family combo probes (hget/sismember)
        miss_cnt: list = []    # counter totals (one cnt_sum gather)
        miss_reg: list = []    # register blobs
        planned = 0  # stats batched after the walk (the walk is hot)
        for j, sp in enumerate(specs):
            payload = hits[j]
            if payload is not None:
                planned += 1
                slots[j] = payload
                continue
            i, msg, spec, name, key, extra, parsed, pre = sp
            kid, enc = resolved[j]
            ct_j, dt_j, exp_j = env[j]
            alive = kid >= 0 and ct_j >= dt_j
            kind = spec.kind
            if j in over:
                if not (kid >= 0 and exp_j):
                    # a list with pushes pending: the overlay around the
                    # landed index
                    planned += 1
                    slots[j] = ("over", len(miss_over))
                    miss_over.append((key, kid, parsed))
                    continue
                # expiry-armed: it demotes below, after the run lands
                self.flush()
                st.serve_read_flushes += 1
            if kid >= 0 and exp_j:
                demote = True  # expiry-armed: time-dependent visibility
            elif kind == "get":
                demote = alive and enc not in (S.ENC_BYTES, S.ENC_COUNTER)
            elif kind in ("lrange", "llen"):
                demote = alive and enc != spec.enc
            else:
                demote = kid >= 0 and enc != spec.enc
            if demote:
                # the exact per-command path raises the exact op error
                # (InvalidType) / applies the exact lazy expiry; only
                # ever its OWN key's state, so the batched gathers
                # below stay coherent (expiry-armed keys never gather).
                # The pre-minted uuid keeps tick parity: execute() skips
                # its own tick and sees the exact per-command uuid.
                self._cur_uuid = pre
                buf = bytearray()
                self._exec(_materialize_msg(msg), buf)
                self._cur_uuid = None
                slots[j] = bytes(buf)
                continue
            # planned: the reply comes from the batched gathers (the
            # read's tick already happened at its stream position)
            planned += 1
            const = None
            if kind == "get":
                if not alive:
                    const = _NIL_BYTES
                elif enc == S.ENC_COUNTER:
                    slots[j] = ("cnt", len(miss_cnt))
                    miss_cnt.append(kid)
                else:
                    slots[j] = ("reg", len(miss_reg))
                    miss_reg.append(kid)
            elif kind in ("elemget", "ismember"):
                if kid < 0:
                    const = _NIL_BYTES if kind == "elemget" \
                        else _INT0_BYTES
                else:
                    slots[j] = ("probe", len(miss_probe))
                    miss_probe.append((j, kid, extra))
            else:  # members / pairs / card / lrange / llen scans
                if kid < 0:
                    const = {"members": _NIL_BYTES,
                             "pairs": _NIL_BYTES,
                             "card": _INT0_BYTES,
                             "lrange": _EMPTY_ARR_BYTES,
                             "llen": _INT0_BYTES}[kind]
                elif kind in ("lrange", "llen") and not alive:
                    const = _EMPTY_ARR_BYTES if kind == "lrange" \
                        else _INT0_BYTES
                elif kind == "members" or kind == "pairs":
                    # no gather: the stitch loop's fused pass goes from
                    # the key's row list to the reply bytes
                    slots[j] = ("fused", kid)
                elif kind in ("lrange", "llen"):
                    # the list's ordered index: its range, or its length
                    slots[j] = ("list", len(miss_list))
                    miss_list.append((kid, parsed))
                else:
                    slots[j] = ("scan", len(miss_scan))
                    miss_scan.append((j, kid))
            if const is not None:
                # fixed reply (absent or dead key): cacheable like any
                # other — absence/deadness is part of the stamp
                slots[j] = const
                if use_cache:
                    rc.put(name, key, extra, kid, ks, const,
                           env=(ct_j, dt_j))
            elif use_cache:
                cacheable[j] = True
        st.cmds_processed += planned
        st.serve_reads_coalesced += planned
        st.serve_read_replies_direct += planned - (n - len(miss))
        # ---- vectorized family gathers for the misses (card scans need a
        # count; members / pairs take the fused pass below, lrange / llen
        # the list's index)
        list_got: list = []
        over_got: list = []
        if miss_list or miss_over:
            with self._stage("list_index"):
                list_got = [ks.list_index(kid).n_live if rng is None
                            else list_range(ks, kid, *rng)
                            for kid, rng in miss_list]
                over_got = [self._overlay_read(*m) for m in miss_over]
        scan_rows: list = []
        if miss_scan:
            scan_rows = ks.elem_live_rows_batch([m[1] for m in miss_scan])
        probe_rows = probe_alive = None
        if miss_probe:
            probe_rows, probe_alive = ks.elem_probe_batch(
                np.fromiter((m[1] for m in miss_probe), dtype=_I64,
                            count=len(miss_probe)),
                [m[2] for m in miss_probe])
        cnt_vals: list = []
        if miss_cnt:
            cnt_vals = ks.counter_sum_batch(
                np.fromiter(miss_cnt, dtype=_I64, count=len(miss_cnt)))
        reg_vals: list = []
        if miss_reg:
            reg_vals = ks.register_get_batch(miss_reg)
        # ---- stitch: write miss replies as wire bytes straight from the
        # gathers (resp/codec.py's direct encoders: no Msg tree), emit
        # everything in order (splicing deferred non-read replies back at
        # their exact positions), fill the cache from the bytes written
        el_member, el_val = ks.el_member, ks.el_val
        scan_reply = None  # made at the run's first members/pairs miss
        native_scans = 0
        ei, ne = 0, len(extras) if extras else 0
        for j, sp in enumerate(specs):
            while ei < ne and extras[ei][0] < sp[0]:
                out += extras[ei][1]
                if spans is not None:
                    spans.append(len(out))
                ei += 1
            slot = slots[j]
            if type(slot) is tuple:
                kind, ref = slot
                k2 = sp[2].kind
                if kind == "fused":  # members / pairs
                    if scan_reply is None:
                        ks._sync_el_lists()  # once: it reads the row lists
                        scan_reply = scan_replier(ks)
                    payload, native = scan_reply(out, k2, ref)
                    native_scans += native
                elif kind == "over":
                    got = over_got[ref]
                    payload = got if k2 == "lrange" else int_reply(got)
                    out += payload
                elif kind == "list":
                    got = list_got[ref]
                    if k2 == "lrange":
                        payload = encode_rows_into(out, k2, got, el_member,
                                                   el_val)
                    else:  # llen
                        payload = int_reply(got)
                        out += payload
                elif kind == "scan":  # card
                    payload = int_reply(len(scan_rows[ref]))
                    out += payload
                else:
                    if kind == "cnt":
                        payload = int_reply(cnt_vals[ref])
                    elif kind == "reg":
                        payload = bulk_reply(reg_vals[ref] or b"")
                    else:  # probe
                        row = int(probe_rows[ref])
                        ok = row >= 0 and bool(probe_alive[ref])
                        if k2 == "ismember":
                            payload = int_reply(1 if ok else 0)
                        else:
                            payload = bulk_reply(el_val[row] if ok else None)
                    out += payload
                if cacheable[j]:
                    e = env[j]
                    rc.put(sp[3], sp[4], sp[5], resolved[j][0], ks,
                           payload, env=(e[0], e[1]))
            else:
                out += slot
            if spans is not None:
                spans.append(len(out))
        while ei < ne:
            out += extras[ei][1]
            if spans is not None:
                spans.append(len(out))
            ei += 1
        st.serve_read_scans_native += native_scans

    def _emit_merged(self, specs: list, hits: list, extras: list,
                     out: bytearray, spans) -> None:
        """All-hit emission with deferred replies spliced back in
        request order (the fast-path twin of the stitch loop's merge)."""
        ei, ne = 0, len(extras)
        for sp, payload in zip(specs, hits):
            while ei < ne and extras[ei][0] < sp[0]:
                out += extras[ei][1]
                if spans is not None:
                    spans.append(len(out))
                ei += 1
            out += payload
            if spans is not None:
                spans.append(len(out))
        while ei < ne:
            out += extras[ei][1]
            if spans is not None:
                spans.append(len(out))
            ei += 1

    def _exec(self, msg, out: bytearray, count_barrier: bool = True,
              invalidate: bool = True) -> None:
        """Exact per-command execution inside a chunk.  `count_barrier`
        keeps the INFO stat to its documented meaning (reads,
        non-plannable writes, demotions, admin) — an isolated plannable
        write executed per-command by CHOICE is not a barrier, but its
        mutation still invalidates its key's cached probes."""
        node = self.node
        with self._stage("exec"):
            reply = node.execute(msg, client=self.client,
                                 uuid=self._cur_uuid)
            if not isinstance(reply, NoReply):
                encode_into(out, reply)
            if count_barrier:
                node.stats.serve_barriers += 1
            if invalidate:
                self._invalidate_after(msg)

    def _invalidate_after(self, msg) -> None:
        """Drop exactly the cached state a just-executed barrier could
        have changed.  Every registered command's keyspace effects are
        confined to the key in its FIRST argument (data commands; the
        differential suite would catch a violation) — commands with
        empty `families` (membership) and READONLY commands touch no
        cached state at all (a read's lazy-expiry dt bump affects none
        of the cached planes).  Anything unclassifiable drops the whole
        cache."""
        node = self.node
        self.nodeid = node.node_id
        self.ks = node.ks
        items = msg.items if type(msg) is Arr else None
        if not items:
            return
        head = items[0]
        name = head.val if type(head) is Bulk else None
        cmd = COMMANDS.get(name) if name is not None else None
        if cmd is None and name is not None:
            cmd = COMMANDS.get(name.lower())
        if cmd is None:
            return  # unknown command: Err reply, nothing executed
        if cmd.flags & CMD_CTRL:
            # control commands take subcommands, not keys (NODE ID even
            # changes the identity the counter overlays are tracked
            # under) — drop everything rather than mis-scope
            self.reset_caches()
            return
        if cmd.flags & CMD_READONLY or not cmd.families:
            return
        if len(items) > 1 and type(items[1]) is Bulk:
            key = items[1].val
            self._keys.pop(key, None)
            self.regs.pop(key, None)
            self.cnts.pop(key, None)
            self.els.pop(key, None)
            self.tns.pop(key, None)
            self.lists.pop(key, None)
            return
        self.reset_caches()

    # ------------------------------------------------------ planner surface

    def tick(self) -> int:
        if self._cur_uuid is not None:
            return self._cur_uuid
        return self.node.hlc.tick(True)

    def resolve_key(self, key: bytes, enc: int):
        """kid for an existing key, -1 for a key this run (or this batch)
        creates, CONFLICT on an encoding mismatch (the planner demotes —
        the per-command path raises the exact InvalidType)."""
        ent = self._keys.get(key)
        if ent is not None:
            kid, e = ent
            return kid if e == enc else CONFLICT
        node = self.node
        # narrow barrier (see _preprobe): key resolution reads the key
        # table only — tensor payload pools stay resident
        node.ensure_flushed_for(("env", "reg", "cnt", "el"))
        ks = self.ks
        kid = ks.lookup(key)
        if kid >= 0:
            e = ks.enc_of(kid)
            self._keys[key] = (kid, e)
            return kid if e == enc else CONFLICT
        self._keys[key] = (-1, enc)
        return -1

    def list_overlay(self, key: bytes, kid: int) -> list:
        """The run overlay of list `key` (kid -1: created by this run):
        [first member, last member, live length, values pushed at the head
        and not landed (list order), values pushed at the tail and not
        landed] — from the key's index the first time a chunk asks."""
        st = self.lists.get(key)
        if st is None:
            st = [None, None, 0, [], []]
            if kid >= 0:
                with self._stage("list_index"):
                    li = self.ks.list_index(kid)
                    st[:3] = li.rows.first(), li.rows.last(), li.n_live
            self.lists[key] = st
        return st

    def _overlay_read(self, key: bytes, kid: int, rng):
        """LRANGE (`rng` = (start, stop)) or LLEN (`rng` None) of list
        `key` as the per-command path would answer it once the run had
        landed: the pending head values, the landed index, the pending
        tail values.  -> reply bytes, or the length."""
        st = self.lists[key]
        n = st[2]
        if rng is None:
            return n
        head, tail = st[3], st[4]
        start, stop = rng
        if start < 0:
            start += n
        if stop < 0:
            stop += n
        start, stop = max(0, start), min(stop, n - 1) + 1
        if stop <= start:
            return b"*0\r\n"
        n_head = len(head)
        n_landed = n - n_head - len(tail)
        vals = head[start:stop]
        a, b = max(start - n_head, 0), min(stop - n_head, n_landed)
        landed = b""
        if b > a and kid >= 0:
            ks = self.ks
            # the landed rows through the row encoder (native pass), less
            # its own array header
            landed = encode_rows_into(
                bytearray(), "lrange", ks.list_index(kid).live_rows(ks.el, a,
                                                                    b),
                ks.el_member, ks.el_val)
            landed = landed[landed.index(b"\n") + 1:]
        after = tail[max(start - n_head - n_landed, 0):
                     max(stop - n_head - n_landed, 0)]
        return b"".join([b"*%d\r\n" % (stop - start)]
                        + [b"$%d\r\n%b\r\n" % (len(v), v) for v in vals]
                        + [landed]
                        + [b"$%d\r\n%b\r\n" % (len(v), v) for v in after])

    def count_elem_flips(self, key: bytes, kid: int, members: list,
                         add: bool) -> int:
        """How many of `members` flip visibility under this add/remove —
        the sadd/srem/hset/hdel reply — against landed rows overlaid
        with the run's pending flips."""
        d = self.els.get(key)
        if d is None:
            d = self.els[key] = {}
        ks = self.ks
        el = ks.el
        cnt = 0
        for m in members:
            alive = d.get(m)
            if alive is None:
                if kid >= 0:
                    row = ks.el_row(kid, m)
                    alive = row >= 0 and S.elem_alive(
                        int(el.add_t[row]), int(el.del_t[row]))
                else:
                    alive = False
            if alive != add:
                cnt += 1
            d[m] = add
        return cnt

    def add(self, name: bytes, rec: tuple, args: list) -> None:
        """Commit one planned command: buffer its pre-parsed record
        (`rec[0]` = key, `rec[1]` = uuid — see commands.SERVE_ENCODERS
        for the per-command tails) for the flush-time group encoders,
        queue its repl_log entry, account it."""
        key = rec[0]
        if self._pending_keys.setdefault(key, name) != name:
            # an add and a remove of one key in one run (sadd + srem,
            # hset + hdel): the encoders group records by name, which
            # would land their new rows out of command order — and a
            # scan's reply lists rows in row order.  Land the run first.
            self.flush()
            self._pending_keys[key] = name
        buf = self._buf
        recs = buf.get(name)
        if recs is None:
            recs = buf[name] = []
        recs.append(rec)
        self._log.append((rec[1], name, args))
        self._pending += 1
        self.node.stats.cmds_processed += 1
        samp = self._sample_every
        if samp and self._planned % samp == 0:
            self._lat_pending.append(self._now())
        self._planned += 1

    # ---------------------------------------------------------------- land

    def flush(self) -> None:
        """Land the pending run: group-encode into one ColumnarBatch,
        merge through the engine seam, append the run to the repl_log in
        one pass, wake the pushers once."""
        buf, self._buf = self._buf, {}
        n, self._pending = self._pending, 0
        if not n:
            return
        self._pending_keys.clear()
        for st in self.lists.values():
            st[3].clear()   # the pushed values land with the run
            st[4].clear()
        log, self._log = self._log, []
        with self._stage("serve_flush"):
            self._land(buf, n, log)

    def _land(self, buf: dict, n: int, log: list) -> None:
        """flush()'s body under its `serve_flush` stage (self time: the
        group encode, the repl_log append, the event trigger — the
        engine's stages nest inside and are excluded)."""
        node = self.node
        bb = BatchBuilder(node.ks)
        nodeid = self.nodeid
        for name, recs in buf.items():
            # planner-built records are pre-parsed and well-formed by
            # construction (demotion happens at plan time) — encoding is
            # pure list comprehension and cannot reject
            SERVE_ENCODERS[name](bb, recs, nodeid)
        prev_uuid = node.repl_log.last_uuid  # the run's chain base
        node.merge_serve_batch(bb, n)
        node.repl_log.push_many(log)
        if node.oplog is not None:
            # mirror the run as ONE columnar batch record whose payload
            # is the exact REPLBATCH wire encoding — serialized straight
            # from this flush's builder, no re-encode — and publish the
            # finished frame into the encode-once cache so the peer
            # fan-out splices these very bytes (persist/oplog.py)
            node.oplog.append_local_run(log, prev_uuid, builder=bb)
        node.events.trigger(EVENT_REPLICATED, log[-1][0])
        lat = self._lat_pending
        if lat:
            now = self._now()
            ring = node.stats.serve_lat
            ring.extend(now - t for t in lat)
            lat.clear()
