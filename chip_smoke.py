#!/usr/bin/env python3
"""chip_smoke.py — the quickest proof that the served path still starts on
the chip.

Drives the system's main path once, through the entry points a user
calls, at a size a user would call real:

  1. builds native/ (the .so files are gitignored) and fails unless the
     extension loads;
  2. generates, from --seed, two replica snapshots A and B of ONE keyspace
     (default 1,000,000 keys: a quarter each of PN-Counter, LWW-Register
     with 32 B values, ORSet with 4 members, LWW-Hash with 10 fields x
     100 B — the YCSB record shape), with conflicting stamps on three
     quarters of the rows, each file over 64 MiB;
  3. boots the chip node C (`--engine tpu --snapshot A`) and the CPU peer
     P (`--engine cpu --snapshot B`), one process each, MEETs them and
     waits for the full sync both ways;
  4. drives C over a socket with > 20,000 ops in pipelines of 64 (SET,
     INCR/DECR, SADD/SREM, HSET/HDEL, DEL on existing and new keys,
     interleaved with GET, SMEMBERS, HGETALL and counter reads) and holds
     every reply to a model the parent keeps: every acknowledged write is
     read back from C within its pipeline, and from P after quiesce;
  5. compares C and P (the per-row CpuMergeEngine on the same data — the
     plain reference) on a seeded sample of pre-existing keys plus every
     key the traffic touched, and both against the merged state the
     parent derived from the seed;
  6. reads C's INFO: the backend platform must be `tpu`, bytes must have
     gone up to the device, micro rounds must have merged in place there;
  7. SIGTERMs C, reboots it from its final dump, re-reads the sample, and
     reports the compile cache after each boot.

Any exception, timeout, mismatch or non-TPU backend in any phase exits
non-zero, and the result line is printed only when every phase passed.
The parent process never imports JAX: the chip belongs to C alone.

`--engine cpu` (with a small `--keys`) runs the same logic off the chip
for debugging; it proves nothing about the device and says so.  Phase
wall times are smoke timings, not metrics.
"""

from __future__ import annotations

import argparse
import json
import os
import shutil
import signal
import socket
import subprocess
import sys
import time

import numpy as np

ROOT = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, ROOT)

FULL_KEYS = 1_000_000
MIN_KEYS = 100_000            # a cut never goes below this on the chip
SHARD_MIN_BYTES = 64 << 20    # Config.ingest_shard_min_bytes default
FAMILIES = ("cnt", "reg", "set", "hsh")
SET_MEMBERS = 4
HASH_FIELDS = 10
REG_BYTES = 32
FIELD_BYTES = 100
PIPELINE = 64
SEQ_BITS = 22
BASE_MS = 1_700_000_000_000   # every generated stamp is long past
WALL_BUDGET_S = 1150          # the contract allows 1200, compiles included


class SmokeFailure(Exception):
    pass


def check(cond, msg: str) -> None:
    if not cond:
        raise SmokeFailure(msg)


# ------------------------------------------------------------------ data


def _stamps(rng, n: int, lo_ms: int, hi_ms: int) -> np.ndarray:
    ms = rng.integers(lo_ms, hi_ms, n, dtype=np.int64)
    return ((BASE_MS + ms) << SEQ_BITS) | rng.integers(0, 8, n, dtype=np.int64)


def _blobs(rng, n: int, width: int) -> list:
    """n random printable values of `width` bytes (the YCSB value shape),
    made in bulk."""
    buf = rng.integers(97, 123, n * width, dtype=np.uint8).tobytes()
    return [buf[i:i + width] for i in range(0, n * width, width)]


class World:
    """The keyspace both replicas hold, in two VERSIONS of every row.

    Every row (a register, a counter slot, a set member, a hash field)
    has an `old` and a `new` write; `new` beats `old` under the CRDT
    rules (a later stamp, or — on a twentieth of the rows — the same
    stamp with the higher writer node or value, so the tie-breaks
    decide too).  Replica A holds `new` on a random 5/8 of the rows and
    `old` elsewhere, B the reverse 5/8: on 3/4 of the rows the replicas
    conflict and the merge must pick a side, and the merged state of
    every row is its `new` version — which is what `visible()` answers,
    with no merge code involved."""

    def __init__(self, n_keys: int, seed: int):
        rng = np.random.default_rng(seed)
        self.n = n = n_keys // 4
        self.n_keys = 4 * n
        # ---- counters: one slot per node (1 = C, 2 = P), in both files
        self.cnt_t_old = _stamps(rng, 2 * n, 0, 300_000)
        self.cnt_t_new = self.cnt_t_old + \
            (rng.integers(1, 300_000, 2 * n, dtype=np.int64) << SEQ_BITS)
        self.cnt_v_old = rng.integers(-1000, 1000, 2 * n, dtype=np.int64)
        self.cnt_v_new = self.cnt_v_old + rng.integers(1, 50, 2 * n)
        tie = rng.random(2 * n) < 0.05    # equal uuid: max value wins
        self.cnt_t_new[tie] = self.cnt_t_old[tie]
        # ---- registers
        self.reg_t_old = _stamps(rng, n, 0, 300_000)
        self.reg_t_new = self.reg_t_old + \
            (rng.integers(1, 300_000, n, dtype=np.int64) << SEQ_BITS)
        self.reg_n_old = rng.integers(1, 3, n, dtype=np.int64)
        self.reg_n_new = rng.integers(1, 3, n, dtype=np.int64)
        tie = rng.random(n) < 0.05        # equal stamp: higher node wins
        self.reg_t_new[tie] = self.reg_t_old[tie]
        self.reg_n_old[tie], self.reg_n_new[tie] = 1, 2
        self.reg_v_old = _blobs(rng, n, REG_BYTES)
        self.reg_v_new = _blobs(rng, n, REG_BYTES)
        # ---- elements: set members then hash fields, key-major
        self.n_set_rows = n * SET_MEMBERS
        rows = self.n_set_rows + n * HASH_FIELDS
        m0 = rng.integers(0, 50_000, n, dtype=np.int64)
        member_ids = (m0[:, None] + 7919 * np.arange(SET_MEMBERS)) % 100_000
        self.set_members = [b"m%05d" % i for i in member_ids.ravel().tolist()]
        self.fields = [b"field%d" % j for j in range(HASH_FIELDS)]
        a0 = _stamps(rng, rows, 0, 200_000)
        step = rng.integers(1, 100_000, rows, dtype=np.int64) << SEQ_BITS
        kind = rng.choice(4, rows, p=[0.70, 0.15, 0.10, 0.05])
        # 0 rewritten later | 1 deleted since | 2 dead then re-added |
        # 3 same stamp, higher writer node
        self.el_a_old = a0
        self.el_d_old = np.where(kind == 2, a0 + step, 0)
        self.el_a_new = np.where(kind == 0, a0 + step,
                                 np.where(kind == 2, a0 + 2 * step, a0))
        self.el_d_new = np.where(kind == 1, a0 + step, self.el_d_old)
        self.el_n_old = np.where(kind == 3, 1,
                                 rng.integers(1, 3, rows)).astype(np.int64)
        self.el_n_new = np.where(kind == 3, 2,
                                 np.where(kind == 1, self.el_n_old,
                                          rng.integers(1, 3, rows))
                                 ).astype(np.int64)
        nh = n * HASH_FIELDS
        self.hv_old = _blobs(rng, nh, FIELD_BYTES)
        hv_new = _blobs(rng, nh, FIELD_BYTES)
        same = np.flatnonzero(kind[self.n_set_rows:] == 1)  # same write
        for i in same.tolist():
            hv_new[i] = self.hv_old[i]
        self.hv_new = hv_new
        # ---- which replica holds which version
        self.pick = {name: rng.random(size) for name, size in
                     (("cnt", 2 * n), ("reg", n), ("el", rows))}
        self.max_stamp = int(max(self.cnt_t_new.max(), self.reg_t_new.max(),
                                 self.el_a_new.max(), self.el_d_new.max()))

    # key names -----------------------------------------------------------

    def key(self, fam: str, i: int) -> bytes:
        return b"%s:%09d" % (fam.encode(), i)

    def keys(self) -> list:
        return [b"%s:%09d" % (f.encode(), i)
                for f in FAMILIES for i in range(self.n)]

    # one replica's snapshot ------------------------------------------------

    def replica_batch(self, which: str):
        """Replica A's or B's whole state as one ColumnarBatch."""
        from constdb_tpu.crdt import semantics as S
        from constdb_tpu.engine.base import ColumnarBatch
        n = self.n

        def has_new(name):
            u = self.pick[name]
            return u < 0.625 if which == "A" else u >= 0.375

        b = ColumnarBatch()
        b.rows_unique_per_slot = True
        b.keys = self.keys()
        b.key_enc = np.repeat(np.array(
            [S.ENC_COUNTER, S.ENC_BYTES, S.ENC_SET, S.ENC_DICT],
            dtype=np.int8), n)
        # counters
        nw = has_new("cnt")
        b.cnt_ki = np.repeat(np.arange(n, dtype=np.int64), 2)
        b.cnt_node = np.tile(np.array([1, 2], dtype=np.int64), n)
        b.cnt_val = np.where(nw, self.cnt_v_new, self.cnt_v_old)
        b.cnt_uuid = np.where(nw, self.cnt_t_new, self.cnt_t_old)
        b.cnt_base = np.zeros(2 * n, dtype=np.int64)
        b.cnt_base_t = np.full(2 * n, S.NEUTRAL_T, dtype=np.int64)
        # registers
        nw = has_new("reg")
        b.reg_t = np.zeros(4 * n, dtype=np.int64)
        b.reg_node = np.zeros(4 * n, dtype=np.int64)
        b.reg_t[n:2 * n] = np.where(nw, self.reg_t_new, self.reg_t_old)
        b.reg_node[n:2 * n] = np.where(nw, self.reg_n_new, self.reg_n_old)
        vals = [new if w else old for w, new, old in
                zip(nw.tolist(), self.reg_v_new, self.reg_v_old)]
        b.reg_val = [None] * n + vals + [None] * (2 * n)
        # elements
        nw = has_new("el")
        b.el_ki = np.concatenate([
            np.repeat(np.arange(2 * n, 3 * n, dtype=np.int64), SET_MEMBERS),
            np.repeat(np.arange(3 * n, 4 * n, dtype=np.int64), HASH_FIELDS)])
        b.el_member = self.set_members + self.fields * n
        b.el_add_t = np.where(nw, self.el_a_new, self.el_a_old)
        b.el_add_node = np.where(nw, self.el_n_new, self.el_n_old)
        b.el_del_t = np.where(nw, self.el_d_new, self.el_d_old)
        hv = [new if w else old for w, new, old in
              zip(nw[self.n_set_rows:].tolist(), self.hv_new, self.hv_old)]
        b.el_val = [None] * self.n_set_rows + hv
        # envelopes: ct = mt = the key's newest data stamp in THIS
        # replica's view (KeySpace.updated_at), never deleted
        last = np.maximum(b.el_add_t, b.el_del_t)
        ct = np.concatenate([
            b.cnt_uuid.reshape(n, 2).max(axis=1),
            b.reg_t[n:2 * n],
            last[:self.n_set_rows].reshape(n, SET_MEMBERS).max(axis=1),
            last[self.n_set_rows:].reshape(n, HASH_FIELDS).max(axis=1)])
        b.key_ct = ct
        b.key_mt = ct.copy()
        b.key_dt = np.zeros(4 * n, dtype=np.int64)
        b.key_expire = np.zeros(4 * n, dtype=np.int64)
        return b

    # the merged state, derived with no merge code ---------------------------

    def visible(self, fam: str, i: int):
        """What a read of pre-existing key (fam, i) answers once A and B
        have merged: int | bytes | set | dict."""
        if fam == "cnt":
            return int(self.cnt_v_new[2 * i] + self.cnt_v_new[2 * i + 1])
        if fam == "reg":
            return self.reg_v_new[i]
        if fam == "set":
            r0 = i * SET_MEMBERS
            return {self.set_members[r] for r in range(r0, r0 + SET_MEMBERS)
                    if self.el_a_new[r] >= self.el_d_new[r]}
        h0 = i * HASH_FIELDS
        r0 = self.n_set_rows + h0
        return {self.fields[j]: self.hv_new[h0 + j]
                for j in range(HASH_FIELDS)
                if self.el_a_new[r0 + j] >= self.el_d_new[r0 + j]}


def write_snapshots(world: World, work: str, addrs: dict) -> dict:
    """Replica files A and B through the server's own snapshot writer
    (persist/snapshot.py write_snapshot_file, the one every dump site
    uses).  -> {"A": path, "B": path, "A_size": n, "B_size": n}.

    Each file lists the other replica as a member (`addrs`: which ->
    host:port), pull watermark 0 — two replicas of one keyspace that
    diverged while apart.  Membership is what pins tombstone GC: a node
    that knows no peer collects every tombstone it holds at its first
    cron tick, and the peer's older adds then resurrect what those
    tombstones had deleted."""
    from constdb_tpu.persist.snapshot import (NodeMeta, ReplicaRecord,
                                              write_snapshot_file)
    out = {}
    for which, node_id, peer in (("A", 1, "B"), ("B", 2, "A")):
        path = os.path.join(work, f"replica_{which}.snapshot")
        # repl_last_uuid > 0: a restored node fences its repl log there,
        # so a peer that never synced gets the snapshot, not an empty
        # partial replay
        meta = NodeMeta(node_id=node_id, alias=f"smoke-{which}",
                        addr=addrs[which], repl_last_uuid=world.max_stamp)
        member = ReplicaRecord(addr=addrs[peer], node_id=3 - node_id,
                               alias=f"smoke-{peer}",
                               add_t=BASE_MS << SEQ_BITS)
        size = write_snapshot_file(path, meta, [member],
                                   [world.replica_batch(which)])
        print(f"[smoke] snapshot {which}: {size:,} bytes "
              f"({size / (1 << 20):.1f} MiB)", flush=True)
        out[which], out[which + "_size"] = path, size
    return out


# ------------------------------------------------------------------ RESP


class Conn:
    """A minimal RESP2 client of the smoke's own: pipelines of commands
    out, parsed replies back."""

    def __init__(self, port: int, timeout: float = 120.0):
        self.sock = socket.create_connection(("127.0.0.1", port),
                                             timeout=timeout)
        self.buf = bytearray()
        self.pos = 0

    def close(self) -> None:
        self.sock.close()

    @staticmethod
    def encode(cmd) -> bytes:
        parts = [p if isinstance(p, bytes) else str(p).encode() for p in cmd]
        return b"*%d\r\n" % len(parts) + b"".join(
            b"$%d\r\n%s\r\n" % (len(p), p) for p in parts)

    def _fill(self) -> None:
        data = self.sock.recv(1 << 20)
        if not data:
            raise SmokeFailure("server closed the connection")
        if self.pos:
            del self.buf[:self.pos]
            self.pos = 0
        self.buf += data

    def _line(self) -> bytes:
        while True:
            end = self.buf.find(b"\r\n", self.pos)
            if end >= 0:
                line = bytes(self.buf[self.pos:end])
                self.pos = end + 2
                return line
            self._fill()

    def _reply(self):
        line = self._line()
        t, rest = line[:1], line[1:]
        if t == b"+":
            return rest.decode()
        if t == b"-":
            raise SmokeFailure(f"server error reply: {rest.decode()}")
        if t == b":":
            return int(rest)
        if t == b"$":
            n = int(rest)
            if n < 0:
                return None
            while len(self.buf) - self.pos < n + 2:
                self._fill()
            out = bytes(self.buf[self.pos:self.pos + n])
            self.pos += n + 2
            return out
        if t == b"*":
            n = int(rest)
            return None if n < 0 else [self._reply() for _ in range(n)]
        raise SmokeFailure(f"unparsable reply line {line!r}")

    def pipeline(self, cmds: list) -> list:
        """Send `cmds` in pipelines of PIPELINE, return their replies in
        order.  An error reply fails the smoke."""
        out = []
        for i in range(0, len(cmds), PIPELINE):
            chunk = cmds[i:i + PIPELINE]
            self.sock.sendall(b"".join(self.encode(c) for c in chunk))
            for c in chunk:
                try:
                    out.append(self._reply())
                except SmokeFailure as e:
                    raise SmokeFailure(f"{c[0]} {c[1:2]!r}: {e}") from None
        return out

    def cmd(self, *parts):
        return self.pipeline([parts])[0]

    def info(self) -> dict:
        text = self.cmd("info").decode()
        return dict(line.split(":", 1) for line in text.splitlines()
                    if ":" in line and not line.startswith("#"))


READ_CMD = {"cnt": "get", "reg": "get", "set": "smembers", "hsh": "hgetall"}


def normalize(fam: str, reply):
    """A read reply in the model's terms: int | bytes | set | dict | None."""
    if reply is None or fam in ("cnt", "reg"):
        return reply
    if fam == "set":
        return set(reply)
    return {f: v for f, v in reply}


# --------------------------------------------------------------- traffic


class Model:
    """What every touched key must read as, kept by the parent: seeded
    from World.visible for pre-existing keys, advanced by each write the
    traffic sends.  Independent of the code under test."""

    def __init__(self, world: World):
        self.world = world
        self.state = {}     # key bytes -> (fam, value)

    def get(self, fam: str, key: bytes, idx):
        if key not in self.state:
            self.state[key] = (fam, self.world.visible(fam, idx)
                               if idx is not None else None)
        return self.state[key][1]

    def put(self, fam: str, key: bytes, value) -> None:
        self.state[key] = (fam, value)


GROUP = 21      # writes per group: reads, writes, reads = 63 ops, a pipeline


def build_traffic(world: World, model: Model, n_ops: int, seed: int):
    """-> (cmds, expects): `expects[i]` is (fam, value) for a read whose
    reply must equal `value`, or None for a write (any non-error reply
    acknowledges it).  Ops come in groups over GROUP distinct keys: a
    read of each key, one write to each, a read of each again — so the
    writes of a group reach the server back to back, the shape its
    serve coalescer plans into one columnar micro-batch, and every
    acknowledged write is read back at once.  The last twentieth of the
    groups are DELs (per-command barriers on the serve path)."""
    rng = np.random.default_rng(seed + 1)
    cmds, expects = [], []
    new_serial = [0]

    def target(fam):
        if rng.random() < 0.25:      # a key neither snapshot holds
            if rng.random() < 0.5 and new_serial[0]:
                j = int(rng.integers(0, new_serial[0]))
            else:
                j = new_serial[0]
                new_serial[0] += 1
            return b"new:%s:%06d" % (fam.encode(), j), None
        i = int(rng.integers(0, world.n))
        return world.key(fam, i), i

    def write_for(fam, key, cur, deleting):
        """-> (command, the key's value once it lands)."""
        if deleting:
            # registers and counters read nil once deleted; a deleted
            # collection keeps its key and reads empty; deleting a key
            # that never existed creates nothing
            return ("del", key), (None if cur is None or fam in
                                  ("cnt", "reg") else type(cur)())
        if fam == "reg":
            val = bytes(rng.integers(65, 91, REG_BYTES, dtype=np.uint8))
            return ("set", key, val), val
        if fam == "cnt":
            d = int(rng.integers(1, 100))
            if rng.random() < 0.6:
                return ("incr", key, d), (cur or 0) + d
            return ("decr", key, d), (cur or 0) - d
        if fam == "set":
            cur = set() if cur is None else set(cur)
            if cur and rng.random() < 0.4:
                m = sorted(cur)[int(rng.integers(0, len(cur)))]
                return ("srem", key, m), cur - {m}
            m = b"t%05d" % int(rng.integers(0, 100_000))
            return ("sadd", key, m), cur | {m}
        cur = {} if cur is None else dict(cur)
        if cur and rng.random() < 0.25:
            f = sorted(cur)[int(rng.integers(0, len(cur)))]
            del cur[f]
            return ("hdel", key, f), cur
        # the YCSB update: one field of the record
        f = world.fields[int(rng.integers(0, HASH_FIELDS))]
        val = bytes(rng.integers(65, 91, FIELD_BYTES, dtype=np.uint8))
        cur[f] = val
        return ("hset", key, f, val), cur

    n_groups = -(-n_ops // (3 * GROUP))
    n_del = max(n_groups // 20, 1)
    for g in range(n_groups):
        group = {}
        while len(group) < GROUP:
            fam = FAMILIES[int(rng.integers(0, 4))]
            key, idx = target(fam)
            group.setdefault(key, (fam, idx))
        before, writes, after = [], [], []
        for key, (fam, idx) in group.items():
            cur = model.get(fam, key, idx)
            cmd, nxt = write_for(fam, key, cur, g >= n_groups - n_del)
            model.put(fam, key, nxt)
            before.append(((READ_CMD[fam], key), (fam, cur)))
            writes.append((cmd, None))
            after.append(((READ_CMD[fam], key), (fam, nxt)))
        for c, e in before + writes + after:
            cmds.append(c)
            expects.append(e)
    return cmds, expects


def compare_replies(where: str, cmds, expects, replies) -> int:
    """Hold read replies to the model; -> number of reads checked."""
    checked = 0
    for c, e, r in zip(cmds, expects, replies):
        if e is None:
            continue
        fam, want = e
        got = normalize(fam, r)
        if got != want:
            raise SmokeFailure(
                f"{where}: {c[0]} {c[1]!r} answered {got!r}, "
                f"expected {want!r}")
        checked += 1
    return checked


# --------------------------------------------------------------- servers


class Servers:
    """The child processes the smoke starts, and their end."""

    def __init__(self, work: str):
        self.work = work
        self.procs = {}

    def boot(self, name: str, engine: str, port: int, node_id: int,
             snapshot: str, config: str = "") -> None:
        env = dict(os.environ)
        # the program keeps its compile cache where
        # JAX_COMPILATION_CACHE_DIR says, else in the checkout
        # (conf.enable_compile_cache); nothing is set here
        if engine == "cpu":
            env["JAX_PLATFORMS"] = "cpu"
        log = open(os.path.join(self.work, f"{name}.log"), "ab")
        argv = [sys.executable, "-m", "constdb_tpu.bin.server"]
        if config:
            argv.append(config)
        argv += ["--port", str(port), "--node-id", str(node_id),
                 "--alias", name, "--engine", engine,
                 "--work-dir", os.path.join(self.work, name),
                 "--snapshot", snapshot, "--log-level", "info"]
        self.procs[name] = subprocess.Popen(
            argv, cwd=ROOT, env=env, stdout=log, stderr=subprocess.STDOUT)
        log.close()

    def wait_listening(self, name: str, port: int, timeout: float) -> Conn:
        deadline = time.monotonic() + timeout
        while True:
            rc = self.procs[name].poll()
            check(rc is None, f"server {name} exited rc={rc} before it "
                              f"listened:\n{self.log_tail(name)}")
            try:
                return Conn(port)
            except OSError:
                check(time.monotonic() < deadline,
                      f"server {name} not listening after {timeout:.0f}s")
                time.sleep(0.25)

    def terminate(self, name: str, timeout: float = 300.0) -> None:
        """SIGTERM (the server writes its final dump), and wait."""
        p = self.procs.pop(name)
        p.send_signal(signal.SIGTERM)
        try:
            rc = p.wait(timeout)
        except subprocess.TimeoutExpired:
            p.kill()
            p.wait()
            raise SmokeFailure(f"server {name} ignored SIGTERM for "
                               f"{timeout:.0f}s")
        check(rc == 0, f"server {name} exited rc={rc} on SIGTERM:\n"
                       f"{self.log_tail(name)}")

    def kill_all(self) -> None:
        for p in self.procs.values():
            if p.poll() is None:
                p.kill()
        for p in self.procs.values():
            p.wait()
        self.procs.clear()

    def log_tail(self, name: str, n: int = 4000) -> str:
        try:
            with open(os.path.join(self.work, f"{name}.log"), "rb") as f:
                f.seek(0, os.SEEK_END)
                f.seek(max(0, f.tell() - n))
                return f.read().decode(errors="replace")
        except OSError:
            return "<no log>"


def free_port() -> int:
    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    port = s.getsockname()[1]
    s.close()
    return port


def replica_field(info: dict, field: str) -> int:
    """`field` of the (single) peer's INFO replication row."""
    row = info.get("replica0", "")
    for part in row.split(","):
        k, _, v = part.partition("=")
        if k == field:
            return int(v)
    return 0


def wait_for(what: str, cond, timeout: float, poll: float = 0.5) -> None:
    deadline = time.monotonic() + timeout
    while not cond():
        check(time.monotonic() < deadline,
              f"timed out after {timeout:.0f}s waiting for {what}")
        time.sleep(poll)


def cache_entries(path: str) -> int:
    try:
        return sum(1 for n in os.listdir(path) if n.endswith("-cache"))
    except OSError:
        return 0


# ------------------------------------------------------------------ main


def run(args) -> dict:
    t_start = time.monotonic()
    marks = []

    def phase(name):
        now = time.monotonic()
        marks.append((name, now))
        print(f"[smoke] +{now - t_start:6.1f}s  {name}", flush=True)

    on_chip = args.engine == "tpu"
    if not on_chip:
        print("[smoke] --engine cpu: a debugging run OFF the chip — it "
              "proves nothing about the device", flush=True)
    if args.keys != FULL_KEYS:
        print(f"[smoke] CUT: {args.keys:,} keys instead of {FULL_KEYS:,} "
              "(record shapes unchanged)", flush=True)
    check(not on_chip or args.keys >= MIN_KEYS,
          f"--keys below {MIN_KEYS:,} is not a size a user would call real")

    work = os.path.join(ROOT, ".chip_smoke")
    shutil.rmtree(work, ignore_errors=True)
    os.makedirs(work)
    servers = Servers(work)
    try:
        return _run(args, servers, work, phase, on_chip)
    finally:
        servers.kill_all()
        # what is too long for the end of the output goes where the chip
        # tool brings it back from
        out = os.path.join(ROOT, "chiprun_out", "chip_smoke")
        os.makedirs(out, exist_ok=True)
        for name in ("C", "P"):
            with open(os.path.join(out, f"{name}.log"), "w") as f:
                f.write(servers.log_tail(name, 200_000))
        if not args.keep:
            shutil.rmtree(work, ignore_errors=True)
        total = time.monotonic() - t_start
        spans = [f"{a[0]} {b[1] - a[1]:.1f}s"
                 for a, b in zip(marks, marks[1:] + [("", time.monotonic())])]
        print(f"[smoke] smoke timings (not metrics): {'; '.join(spans)}; "
              f"total {total:.1f}s", flush=True)


def _run(args, servers: Servers, work: str, phase, on_chip: bool) -> dict:
    phase("build native")
    from constdb_tpu.utils import native_tables
    native_tables.build_native()      # raises unless cst_ext.so loads
    check(native_tables.load_ext() is not None, "native extension absent")

    phase("generate snapshots")
    port_c, port_p = free_port(), free_port()
    world = World(args.keys, args.seed)
    snaps = write_snapshots(world, work, {"A": f"127.0.0.1:{port_c}",
                                          "B": f"127.0.0.1:{port_p}"})
    config = ""
    if min(snaps["A_size"], snaps["B_size"]) < SHARD_MIN_BYTES:
        # a cut keyspace: lower the threshold so the sharded-ingest
        # decision is still taken on both nodes
        check(not on_chip or args.keys < FULL_KEYS,
              "full-size snapshots fell under 64 MiB")
        floor = min(snaps["A_size"], snaps["B_size"]) // 4
        config = os.path.join(work, "node.toml")
        with open(config, "w") as f:
            f.write(f"ingest_shard_min_bytes = {floor}\n")
        print(f"[smoke] CUT: files under 64 MiB, ingest_shard_min_bytes "
              f"lowered to {floor:,} in the nodes' config", flush=True)
    else:
        floor = SHARD_MIN_BYTES

    phase("boot C and P")
    servers.boot("C", args.engine, port_c, 1, snaps["A"], config)
    servers.boot("P", "cpu", port_p, 2, snaps["B"], config)
    c = servers.wait_listening("C", port_c, args.boot_timeout)
    p = servers.wait_listening("P", port_p, args.boot_timeout)
    for name, conn in (("C", c), ("P", p)):
        info = conn.info()
        check(int(info["keys"]) == world.n_keys,
              f"{name} restored {info['keys']} keys of {world.n_keys}")
        check("boot_snapshot_quarantined" not in info,
              f"{name} quarantined its boot snapshot")
    info_c = c.info()
    device = {"platform": info_c["jax_backend"],
              "kind": info_c["device_kind"],
              "count": int(info_c["device_count"])}
    print(f"[smoke] C: engine={info_c['engine']} device={device}",
          flush=True)
    if on_chip:
        check(info_c["engine"] == "tpu" and device["platform"] == "tpu",
              f"C does not run on a TPU: engine={info_c['engine']} "
              f"device={device}")

    phase("MEET + full sync both ways")
    check(c.cmd("meet", f"127.0.0.1:{port_p}") == "OK", "MEET refused")

    def synced():
        ic, ip = c.info(), p.info()
        return all(int(i.get("repl_full_syncs", 0)) >= 1
                   and replica_field(i, "he_sent") >= world.max_stamp
                   for i in (ic, ip))
    wait_for("full sync both ways", synced, args.sync_timeout)
    info_c, info_p = c.info(), p.info()
    for name, info in (("C", info_c), ("P", info_p)):
        got = int(info["repl_net_input_bytes"])
        check(got >= floor, f"{name} received {got:,} sync bytes, under "
                            f"the sharded-ingest threshold {floor:,}")
        print(f"[smoke] {name}: full sync in {got:,} bytes, out "
              f"{int(info["repl_net_output_bytes"]):,} bytes; sharded_ingests="
              f"{info.get('sharded_ingests', 0)} workers="
              f"{info.get('sharded_ingest_workers', 0)}", flush=True)
    if on_chip:
        # one process per chip: the decision was taken (the sync is over
        # the threshold) and C ingested in-process
        check("sharded_ingests" not in info_c,
              "C fanned its sync out to shard workers while holding "
              "the chip")

    phase("traffic on C")
    model = Model(world)
    cmds, expects = build_traffic(world, model, args.ops, args.seed)
    if args.corrupt_expectation:
        # the smoke's own self-test: one wrong expectation must fail it
        i = next(i for i, e in enumerate(expects) if e is not None)
        expects[i] = (expects[i][0], b"<deliberately wrong>")
    replies = c.pipeline(cmds)
    n_reads = compare_replies("C during traffic", cmds, expects, replies)
    n_writes = len(cmds) - n_reads
    print(f"[smoke] {len(cmds):,} ops in pipelines of {PIPELINE}: "
          f"{n_writes:,} writes acknowledged, {n_reads:,} reads held to "
          f"the model, {len(model.state):,} keys touched", flush=True)

    phase("quiesce + read back from P")
    last = int(c.info()["repl_log_last_uuid"])
    wait_for("P to receive C's stream",
             lambda: replica_field(p.info(), "he_sent") >= last,
             args.sync_timeout)
    touched = [(fam, key) for key, (fam, _v) in model.state.items()]
    touched_cmds = [(READ_CMD[fam], key) for fam, key in touched]
    touched_want = [(fam, model.state[key][1]) for fam, key in touched]

    deadline = time.monotonic() + 60.0
    while True:     # P lands the tail of the stream a coalescer tick later
        try:
            compare_replies("P after quiesce", touched_cmds, touched_want,
                            p.pipeline(touched_cmds))
            break
        except SmokeFailure:
            if time.monotonic() > deadline:
                raise
            time.sleep(0.5)
    compare_replies("C after traffic", touched_cmds, touched_want,
                    c.pipeline(touched_cmds))

    phase("compare C with P and the seed")
    rng = np.random.default_rng(args.seed + 2)
    per_fam = -(-args.sample // 4)
    sample = [(fam, int(i)) for fam in FAMILIES
              for i in rng.choice(world.n, min(per_fam, world.n),
                                  replace=False)]
    sample_cmds = [(READ_CMD[fam], world.key(fam, i)) for fam, i in sample]
    all_cmds = sample_cmds + touched_cmds
    fams = [fam for fam, _ in sample] + [fam for fam, _ in touched]
    ans_c = [normalize(f, r) for f, r in zip(fams, c.pipeline(all_cmds))]
    ans_p = [normalize(f, r) for f, r in zip(fams, p.pipeline(all_cmds))]
    for cmd, a, b in zip(all_cmds, ans_c, ans_p):
        check(a == b, f"C and P disagree on {cmd[0]} {cmd[1]!r}: "
                      f"{a!r} vs {b!r}")
    decided = 0
    for (fam, i), cmd, a in zip(sample, sample_cmds, ans_c):
        key = cmd[1]
        want = model.state[key][1] if key in model.state \
            else world.visible(fam, i)
        check(a == want, f"{cmd[0]} {key!r} answered {a!r}, the seed says "
                         f"{want!r}")
        decided += 1
    print(f"[smoke] C == P on {len(all_cmds):,} keys ({len(sample):,} "
          f"sampled pre-existing + {len(touched):,} touched); "
          f"{decided:,} sampled answers equal the merged state derived "
          "from the seed", flush=True)

    phase("INFO on C")
    info_c = c.info()
    gauges = {k: info_c.get(k) for k in (
        "engine", "jax_backend", "device_kind", "device_count",
        "dev_upload_bytes", "dev_download_bytes", "merge_rows",
        "merge_folds", "dev_rounds_resident", "host_micro_rounds",
        "serve_msgs_coalesced", "serve_flushes", "compile_cache_dir",
        "compile_cache_hits", "compile_cache_misses")}
    print(f"[smoke] C INFO: {json.dumps(gauges)}", flush=True)
    bad = [k for k in info_c if "degraded" in k or "fallback" in k]
    check(not bad, f"C reports a degraded/fallback marker: {bad}")
    if on_chip:
        for k in ("dev_upload_bytes", "merge_rows", "dev_rounds_resident"):
            check(int(info_c.get(k) or 0) > 0, f"C INFO {k} is not > 0: "
                                               f"{info_c.get(k)!r}")
    cache_dir = info_c.get("compile_cache_dir", "")
    entries1 = cache_entries(cache_dir)
    misses1 = int(info_c.get("compile_cache_misses", 0))
    print(f"[smoke] compile cache after boot 1: {cache_dir or '<none>'} "
          f"holds {entries1} entries (C: {info_c.get('compile_cache_hits')}"
          f" hits, {misses1} misses)", flush=True)

    phase("SIGTERM C, reboot from its final dump")
    c.close()
    servers.terminate("C")
    servers.boot("C", args.engine, port_c, 1, snaps["A"], config)
    c = servers.wait_listening("C", port_c, args.boot_timeout)
    info_c = c.info()
    check("boot_snapshot_quarantined" not in info_c,
          "C quarantined its own final dump")
    ans_c2 = [normalize(f, r) for f, r in zip(fams, c.pipeline(all_cmds))]
    for cmd, a, b in zip(all_cmds, ans_c, ans_c2):
        check(a == b, f"{cmd[0]} {cmd[1]!r} changed across the reboot: "
                      f"{a!r} then {b!r}")
    info_c = c.info()
    entries2 = cache_entries(cache_dir)
    hits2 = int(info_c.get("compile_cache_hits", 0))
    misses2 = int(info_c.get("compile_cache_misses", 0))
    print(f"[smoke] reboot read back {len(all_cmds):,} keys unchanged; "
          f"compile cache after boot 2: {entries2} entries "
          f"(+{entries2 - entries1}; C: {hits2} hits, {misses2} misses)",
          flush=True)
    if on_chip:
        # a hit proves boot 2 found what boot 1 wrote (a cache whose key
        # or directory moved never hits); every entry boot 2 added is a
        # shape boot 1 never compiled (a miss), never a second copy
        check(cache_dir and entries1 > 0, "C kept no compile cache")
        check(hits2 > 0, "boot 2 hit nothing in the compile cache")
        check(entries2 - entries1 <= misses2,
              f"boot 2 added {entries2 - entries1} cache entries for "
              f"{misses2} misses")
        check(device == {"platform": info_c["jax_backend"],
                         "kind": info_c["device_kind"],
                         "count": int(info_c["device_count"])},
              "C came back on a different device")

    c.close()
    p.close()
    return device      # run()'s finally stops both servers


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=20260926)
    ap.add_argument("--keys", type=int, default=FULL_KEYS,
                    help="keyspace size (a cut; never record shapes)")
    ap.add_argument("--ops", type=int, default=21_000)
    ap.add_argument("--sample", type=int, default=10_000)
    ap.add_argument("--engine", choices=["tpu", "cpu"], default="tpu",
                    help="C's engine; cpu = a debugging run off the chip")
    ap.add_argument("--boot-timeout", type=float, default=500.0)
    ap.add_argument("--sync-timeout", type=float, default=500.0)
    ap.add_argument("--keep", action="store_true",
                    help="keep the work directory (.chip_smoke/)")
    ap.add_argument("--corrupt-expectation", action="store_true",
                    help=argparse.SUPPRESS)
    args = ap.parse_args(argv)

    def on_alarm(signum, frame):
        raise SmokeFailure(f"wall budget of {WALL_BUDGET_S}s exhausted")
    signal.signal(signal.SIGALRM, on_alarm)
    signal.alarm(WALL_BUDGET_S)
    try:
        device = run(args)
    except SmokeFailure as e:
        print(f"[smoke] FAILED: {e}", file=sys.stderr, flush=True)
        return 1
    finally:
        signal.alarm(0)
    out = {"ok": True, "device": device}
    if args.engine != "tpu":
        out["proved_nothing_about_the_device"] = True
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
