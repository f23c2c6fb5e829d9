#!/usr/bin/env python
"""Headline benchmark: batched CRDT snapshot-merge throughput.

Scenario (BASELINE.json north-star): a node catches up by merging R replica
snapshots of an N-key mixed keyspace (PN-counters, LWW registers, ORSets)
into an empty local store, STREAMED in chunks exactly the way the replica
link applies a downloaded snapshot (persist/snapshot.py chunk sections →
one engine merge per chunk) — the bulk path the reference walks one key at
a time via `DB::merge_entry` → `Object::merge` (reference src/db.rs:31-43,
src/object.rs:63-83).  The TPU engine runs device-RESIDENT: chunk merges
keep state in HBM and the timed span includes the final flush back to the
host keyspace, so both engines end fully host-queryable.

Prints ONE JSON line:
  {"metric": "snapshot_merge_keys_per_sec", "value": <TPU-engine keys/sec>,
   "unit": "keys/sec", "vs_baseline": <speedup over the CPU MergeEngine>}

Sizing knobs (env): CONSTDB_BENCH_KEYS (default 1_000_000),
CONSTDB_BENCH_REPLICAS (default 8), CONSTDB_BENCH_CPU_KEYS (defaults to
CONSTDB_BENCH_KEYS so the baseline rate is same-scale; set lower to cap the
pure-Python run), CONSTDB_BENCH_CHUNK (keys per chunk, default 131072).
"""

from __future__ import annotations

import asyncio
import json
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

from constdb_tpu.crdt import semantics as S
from constdb_tpu.engine.base import ColumnarBatch
from constdb_tpu.engine.cpu import CpuMergeEngine
from constdb_tpu.store.keyspace import KeySpace
from constdb_tpu.utils.hlc import SEQ_BITS

_I64 = np.int64
MS0 = 1_700_000_000_000  # fixed epoch so uuids look like real HLC values


def ensure_native(timeout: float = 600.0) -> None:
    """Build the native extension (native/ C++ tables + RESP codec) when
    its artifacts are missing.  The toolchain is baked into the image and
    the build is one `make` call; without it every interning/index batch
    call falls back to pure-Python tiers — the single largest host
    dispatch cost measured in the BENCH_r05 profile.  CONSTDB_AUTO_NATIVE=0
    skips; failures degrade to the pure tiers, never abort the bench."""
    if os.environ.get("CONSTDB_AUTO_NATIVE", "1") == "0":
        return
    if os.environ.get("CONSTDB_NO_NATIVE"):
        return  # pure-tier floor measurement: building would be wasted
    from constdb_tpu.utils import native_tables as NT

    t0 = time.perf_counter()
    try:
        NT.build_native(timeout)
    except RuntimeError as e:
        print(f"[bench] native build skipped: {e}", file=sys.stderr)
        return
    print(f"[bench] native extension ready in "
          f"{time.perf_counter() - t0:.1f}s", file=sys.stderr)


def host_fingerprint() -> dict:
    """Box identity stamped into every bench JSON line: cross-box
    comparisons (the r05/r06 host_note confusion) become a field check
    instead of prose archaeology."""
    import platform

    model = ""
    try:
        with open("/proc/cpuinfo") as f:
            for line in f:
                if line.lower().startswith(("model name", "hardware")):
                    model = line.split(":", 1)[1].strip()
                    break
    except OSError:
        pass
    return {
        "cpu_model": model or platform.processor() or platform.machine(),
        "cores": os.cpu_count(),
        "jax_platforms": os.environ.get("JAX_PLATFORMS", ""),
        "platform": platform.platform(),
    }


def engine_counters(engine) -> dict:
    """Device-transfer gauges stamped into every JSON line that has an
    engine in reach (the residency metrics BENCH_r12 and the v5e
    follow-up round read; zeros for host-only engines)."""
    return {
        "dev_upload_bytes": getattr(engine, "bytes_h2d", 0),
        "dev_download_bytes": getattr(engine, "bytes_d2h", 0),
        "dev_rounds_resident": getattr(engine, "dev_rounds_resident", 0),
        "host_micro_rounds": getattr(engine, "host_micro_rounds", 0),
        "flush_rows_downloaded": getattr(engine, "flush_rows_downloaded", 0),
        "flush_rows_full_equiv": getattr(engine, "flush_rows_full_equiv", 0),
    }


def require_device(fold: str = "auto"):
    """-> (jax, device stamp) for a DEVICE leg, with the persistent
    compile cache on (conf.enable_compile_cache).  A device leg that
    finds no accelerator FAILS here: it never reports XLA-on-CPU numbers
    under a device metric's name.  The one exemption is a forced Pallas
    INTERPRET fold — the interpreter exists for CPU tests, so such a run
    is by construction a kernel-correctness smoke (scripts/ci.sh), and
    its JSON says platform "cpu".  Every device leg stamps the returned
    dict into its JSON line as "device"."""
    import jax
    from constdb_tpu.conf import enable_compile_cache

    enable_compile_cache()
    dev = jax.devices()
    stamp = {"platform": dev[0].platform, "kind": dev[0].device_kind,
             "count": len(dev)}
    if stamp["platform"] == "cpu" and "interpret" not in fold:
        sys.exit("[bench] device leg found no accelerator (JAX's default "
                 "backend is cpu): refusing to run it on the CPU")
    print(f"[bench] device: {stamp}", file=sys.stderr)
    return jax, stamp


def _uuids(rng, n, span_ms=600_000):
    # float-scaled draws: ~5x faster than bounded-integer rejection
    # sampling at the 10M scale (this is workload GENERATION — outside the
    # timed span, but inside the driver's wall clock)
    ms = (rng.random(n) * span_ms).astype(_I64)
    seq = (rng.random(n) * (1 << 10)).astype(_I64)
    return ((MS0 + ms) << SEQ_BITS) | seq


def make_workload(n_keys: int, n_replicas: int, seed: int = 7,
                  members_per_set: int = 4, hlc_order: bool = False):
    """R snapshot batches over one mixed N-key keyspace.

    40% counters / 30% registers / 30% sets.  Immutable columns (key bytes,
    enc, member bytes) are built once and shared across batches — replica
    snapshots of the same keyspace really do share this data.

    `hlc_order`: sort every uuid draw so columns are near-monotone in
    key order — the shape a REAL node's dump has (keys created over
    time, dumped in creation order; HLC uuids are arrival-ordered).
    The default uniform-random draw is the adversarial shape for the
    compressed-container bytes leg (uuid columns become incompressible
    noise no real store produces).
    """
    rng = np.random.default_rng(seed)

    def draw(n):
        u = _uuids(rng, n)
        if hlc_order:
            u.sort()
        return u
    keys = [b"k%010d" % i for i in range(n_keys)]
    enc = np.empty(n_keys, dtype=np.int8)
    n_cnt = int(n_keys * 0.4)
    n_reg = int(n_keys * 0.3)
    n_set = n_keys - n_cnt - n_reg
    enc[:n_cnt] = S.ENC_COUNTER
    enc[n_cnt:n_cnt + n_reg] = S.ENC_BYTES
    enc[n_cnt + n_reg:] = S.ENC_SET

    reg_pool = [b"v%06d" % i for i in range(1024)]
    reg_idx = rng.integers(0, len(reg_pool), n_reg)
    member_pool = [b"m%04d" % i for i in range(4096)]

    set_ki = np.repeat(np.arange(n_cnt + n_reg, n_keys, dtype=_I64),
                       members_per_set)
    member_idx = rng.integers(0, len(member_pool), len(set_ki))
    # batches declare rows_unique_per_slot: drop duplicate (key, member)
    # draws so the claim actually holds (a collision would make the
    # unique-indices scatter order-dependent)
    combo = (set_ki << 32) | member_idx
    _, first = np.unique(combo, return_index=True)
    first.sort()
    set_ki = set_ki[first]
    member_idx = member_idx[first]
    el_member = [member_pool[i] for i in member_idx]
    el_val = [None] * len(set_ki)

    batches = []
    for r in range(n_replicas):
        b = ColumnarBatch()
        b.rows_unique_per_slot = True
        b.keys = keys
        b.key_enc = enc
        b.key_ct = draw(n_keys)
        b.key_mt = b.key_ct + (rng.integers(0, 1000, n_keys) << SEQ_BITS)
        # ~2% of keys tombstoned later than their create time
        dt = np.where(rng.random(n_keys) < 0.02,
                      b.key_mt + (1 << SEQ_BITS), 0)
        b.key_dt = dt.astype(_I64)
        b.key_expire = np.zeros(n_keys, dtype=_I64)

        b.reg_val = [None] * n_cnt + [reg_pool[i] for i in reg_idx] + \
                    [None] * n_set
        b.reg_t = np.zeros(n_keys, dtype=_I64)
        b.reg_t[n_cnt:n_cnt + n_reg] = draw(n_reg)
        b.reg_node = np.zeros(n_keys, dtype=_I64)
        b.reg_node[n_cnt:n_cnt + n_reg] = r + 1

        # each replica snapshot carries that replica's own counter slot
        b.cnt_ki = np.arange(n_cnt, dtype=_I64)
        b.cnt_node = np.full(n_cnt, r + 1, dtype=_I64)
        b.cnt_val = rng.integers(-1000, 1000, n_cnt).astype(_I64)
        b.cnt_uuid = draw(n_cnt)
        b.cnt_base = np.zeros(n_cnt, dtype=_I64)
        b.cnt_base_t = np.full(n_cnt, S.NEUTRAL_T, dtype=_I64)

        b.el_ki = set_ki
        b.el_member = el_member
        b.el_val = el_val
        b.el_add_t = draw(len(set_ki))
        b.el_add_node = np.full(len(set_ki), r + 1, dtype=_I64)
        b.el_del_t = np.where(rng.random(len(set_ki)) < 0.1,
                              draw(len(set_ki)), 0).astype(_I64)
        batches.append(b)
    return batches


def subsample_keys(keys, n_keys: int, target: int = 100_000) -> list:
    """Key bytes of the verification subsample — the ONE home for the
    every-`step`-th-key formula (subsample_workload derives from it, and
    the bench parent uses it while the oracle replay runs in a worker)."""
    step = max(1, n_keys // target)
    return [keys[i] for i in range(0, n_keys, step)]


def subsample_workload(batches, n_keys: int, target: int = 100_000):
    """Deterministic per-key filter of a workload: every `step`-th key,
    with counter/element rows remapped.  Per-key merges are independent,
    so a CPU replay of the FILTERED batches is an exact oracle for those
    keys in the full device-merged store (bench verification)."""
    step = max(1, n_keys // target)
    keep = np.arange(0, n_keys, step)
    sub_keys = subsample_keys(batches[0].keys, n_keys, target)
    out = []
    for b in batches:
        fb = ColumnarBatch()
        fb.rows_unique_per_slot = b.rows_unique_per_slot
        fb.keys = sub_keys
        fb.key_enc = b.key_enc[keep]
        fb.key_ct = b.key_ct[keep]
        fb.key_mt = b.key_mt[keep]
        fb.key_dt = b.key_dt[keep]
        fb.key_expire = b.key_expire[keep]
        fb.reg_val = [b.reg_val[i] for i in keep.tolist()]
        fb.reg_t = b.reg_t[keep]
        fb.reg_node = b.reg_node[keep]
        cm = (b.cnt_ki % step) == 0
        fb.cnt_ki = b.cnt_ki[cm] // step
        for col in ("cnt_node", "cnt_val", "cnt_uuid", "cnt_base",
                    "cnt_base_t"):
            setattr(fb, col, getattr(b, col)[cm])
        em = (b.el_ki % step) == 0
        rows = np.nonzero(em)[0].tolist()
        fb.el_ki = b.el_ki[em] // step
        fb.el_member = [b.el_member[i] for i in rows]
        fb.el_val = [b.el_val[i] for i in rows]
        for col in ("el_add_t", "el_add_node", "el_del_t"):
            setattr(fb, col, getattr(b, col)[em])
        out.append(fb)
    return out, sub_keys


def oracle_canonical(batches, n_keys: int, target: int = 100_000) -> dict:
    """CPU-replay a deterministic ~`target`-key subsample of the workload
    and return its canonical state (the verification oracle)."""
    sub, _sub_keys = subsample_workload(batches, n_keys, target)
    oracle = KeySpace()
    cpu = CpuMergeEngine()
    for b in sub:
        cpu.merge(oracle, b)
    return oracle.canonical()


def compare_canonical(got: dict, want: dict) -> int:
    """Diff count between device and oracle canonical states (prints the
    first few mismatches)."""
    if got == want:
        return 0
    diff = [k for k in want if got.get(k) != want[k]]
    diff += [k for k in got if k not in want]
    for k in diff[:5]:
        print(f"[bench] VERIFY MISMATCH {k!r}:\n  device={got.get(k)!r}"
              f"\n  oracle={want.get(k)!r}", file=sys.stderr)
    return len(diff)


def verify_store(store, batches, n_keys: int, target: int = 100_000):
    """Oracle check of the device-merged store: CPU-replay a deterministic
    ~`target`-key subsample of the same workload and canonical()-compare.
    Returns (ok, n_checked, n_diff)."""
    sub_keys = subsample_keys(batches[0].keys, n_keys, target)
    want = oracle_canonical(batches, n_keys, target)
    n_diff = compare_canonical(store.canonical(keys=sub_keys), want)
    return n_diff == 0, len(sub_keys), n_diff


def _oracle_worker(conn, batches, n_keys: int, target: int) -> None:
    """Forked verify worker: sleeps on the pipe until the parent's "go"
    (sent after the timed merges, so the replay never competes with the
    measured run), then replays the subsample on the CPU engine and ships
    the oracle canonical state back."""
    try:
        conn.recv()  # block until the timed runs complete
        conn.send(oracle_canonical(batches, n_keys, target))
    except BaseException as e:  # surfaced (and re-raised) by the parent
        conn.send(e)
    finally:
        conn.close()


def start_oracle(batches, n_keys: int, target: int = 100_000):
    """Fork the oracle replay worker (copy-on-write: the workload is NOT
    re-pickled).  MUST be called before any in-process jax init — forking
    a JAX-threaded process can deadlock the child — which is why main()
    generates the workload and forks ahead of the backend import; the
    worker idles until go() anyway.  -> (process, conn), or None if fork
    is unavailable (the caller then falls back to the serial verify)."""
    import multiprocessing as mp

    try:
        ctx = mp.get_context("fork")
    except ValueError:  # pragma: no cover - non-POSIX
        return None
    parent, child = ctx.Pipe()
    p = ctx.Process(target=_oracle_worker,
                    args=(child, batches, n_keys, target), daemon=True)
    p.start()
    child.close()
    return p, parent


def probe_link(jax, mb: int = 64, repeats: int = 3):
    """Measured host<->device bandwidth (bytes/s up, down): device_put /
    device_get of a `mb`-MB buffer, best of `repeats` — the wall-clock
    ceiling for whatever share of the merge is transfer-bound."""
    dev = jax.devices()[0]
    buf = np.random.default_rng(0).integers(  # incompressible
        0, 1 << 62, (mb << 20) // 8, dtype=np.int64)
    jax.device_put(np.zeros(1024, dtype=np.int64), dev).block_until_ready()
    up = down = 0.0
    for _ in range(repeats):
        t0 = time.perf_counter()
        x = jax.device_put(buf, dev)
        x.block_until_ready()
        up = max(up, buf.nbytes / (time.perf_counter() - t0))
        t0 = time.perf_counter()
        back = np.asarray(x)
        down = max(down, back.nbytes / (time.perf_counter() - t0))
        del x, back
    return up, down


def chunk_batches(batches, chunk_keys: int):
    """Interleave replicas' snapshot chunks (the arrival order during a
    real multi-peer catch-up)."""
    from constdb_tpu.persist.snapshot import batch_chunks

    per_replica = [list(batch_chunks(b, chunk_keys)) for b in batches]
    out = []
    for i in range(max(len(p) for p in per_replica)):
        for p in per_replica:
            if i < len(p):
                out.append(p[i])
    return out


def time_engine(make_engine, chunks, repeats: int = 2,
                group: int = 1):
    """Best wall-time over `repeats` streamed catch-ups into a fresh store
    (includes the final flush for resident engines).  `group` > 1 feeds
    that many consecutive chunks per engine call (merge_many) — with the
    interleaved arrival order, groups of n_replicas are slot-ALIGNED and
    take the engine's fused dense-fold path (one scatter per group).
    Returns (best_seconds, last_run_store) — the store feeds the oracle
    verification."""
    best = float("inf")
    store = None
    for _ in range(repeats):
        engine = make_engine()
        store = KeySpace()
        t0 = time.perf_counter()
        if group > 1 and hasattr(engine, "merge_many"):
            for i in range(0, len(chunks), group):
                engine.merge_many(store, chunks[i:i + group])
        else:
            for c in chunks:
                engine.merge(store, c)
        if getattr(engine, "needs_flush", False):
            engine.flush(store)
        best = min(best, time.perf_counter() - t0)
    return best, store


# --------------------------------------------------------------------------
# --mode stream: steady-state replication apply (the coalescing pull path)


def make_frame_log(n_frames: int, n_keys: int, seed: int = 11) -> list:
    """Deterministic replicate-frame log over a mixed keyspace — the
    shape one peer's steady-state stream has on the wire (REPLICATE
    frames with monotone HLC uuids from one origin), including the DEL
    rewrites that act as coalescer barriers."""
    import random

    from constdb_tpu.resp.message import Bulk, Int

    rng = random.Random(seed)
    frames = []
    prev = 0
    for i in range(1, n_frames + 1):
        uuid = (MS0 + i) << SEQ_BITS
        k = b"%06d" % rng.randrange(n_keys)
        r = rng.random()
        if r < 0.30:
            body = (b"set", b"r" + k, b"v%08d" % i)
        elif r < 0.52:
            body = (b"cntset", b"c" + k, rng.randrange(-10_000, 10_000))
        elif r < 0.72:
            # multi-member set writes (tag/follower-list shape)
            body = (b"sadd", b"s" + k,
                    *(b"m%03d" % rng.randrange(64) for _ in range(4)))
        elif r < 0.80:
            body = (b"srem", b"s" + k, b"m%03d" % rng.randrange(64))
        elif r < 0.90:
            # multi-field record writes (YCSB's canonical user-record
            # workload writes 10 fields per op; 5 here is conservative)
            fv = []
            for f in range(5):
                fv += [b"f%02d" % rng.randrange(16), b"v%07d%d" % (i, f)]
            body = (b"hset", b"h" + k, *fv)
        elif r < 0.995:
            body = (b"hdel", b"h" + k, b"f%02d" % rng.randrange(16))
        elif r < 0.998:
            body = (b"delbytes", b"r" + k)   # scalar DEL: coalesces
        else:
            body = (b"delset", b"s" + k)     # collection DEL: barrier
        # DELs are ~0.5% of the stream: ConstDB's serving workload is
        # write-once constant data (PAPER.md), so deletes are
        # administrative, not steady-state — but they must be PRESENT so
        # the bench exercises the barrier flush machinery for real
        frames.append([Bulk(b"replicate"), Int(99), Int(prev), Int(uuid),
                       Bulk(body[0]),
                       *[Int(a) if isinstance(a, int) else Bulk(a)
                         for a in body[1:]]])
        prev = uuid
    return frames


def save_frame_log(path: str, frames: list) -> None:
    from constdb_tpu.resp.codec import encode_msg
    from constdb_tpu.resp.message import Arr

    with open(path, "wb") as f:
        for items in frames:
            f.write(encode_msg(Arr(items)))


def load_frame_log(path: str) -> list:
    from constdb_tpu.resp.codec import make_parser

    parser = make_parser()
    frames = []
    with open(path, "rb") as f:
        while True:
            data = f.read(1 << 20)
            if not data:
                break
            parser.feed(data)
            while (msg := parser.next_msg()) is not None:
                frames.append(msg.items)
    return frames


def replay_stream(frames, make_engine, apply_batch: int,
                  latency_s: float):
    """Replay a frame log through the coalescing applier exactly the way
    the pull loop drives it.  Returns (node, wall_seconds,
    per-frame visibility latencies) — visibility = intake→landed."""
    from constdb_tpu.replica.coalesce import CoalescingApplier
    from constdb_tpu.replica.manager import ReplicaMeta
    from constdb_tpu.server.node import Node

    node = Node(node_id=1, engine=make_engine())
    applier = CoalescingApplier(node, ReplicaMeta("bench-peer:0"),
                                max_frames=apply_batch,
                                max_latency=latency_s,
                                now=time.perf_counter)
    # visibility latency is SAMPLED (every 64th frame): per-frame clock
    # reads would tax the measured path itself, and ~1.5% of a frame log
    # is ample for a p99.  Sampled frames drain into `lat` when the
    # batch covering them actually LANDS (merge_stream_batch hook) — the
    # definition of visibility the coalescer's watermark rule uses.
    lat: list[float] = []
    pending_ts: list[float] = []
    clock = time.perf_counter
    real_land = node.merge_stream_batch

    def landing(bb, n):
        real_land(bb, n)
        now = clock()
        lat.extend(now - t for t in pending_ts)
        pending_ts.clear()

    node.merge_stream_batch = landing
    t0 = clock()
    for i, items in enumerate(frames):
        applier.apply(items)
        if not i & 63:
            if not applier.pending:  # landed immediately (barrier /
                lat.append(0.0)      # per-frame path)
            else:
                pending_ts.append(clock())
    applier.flush()
    node.ensure_flushed()
    end = clock()
    lat.extend(end - t for t in pending_ts)
    node.merge_stream_batch = real_land
    return node, end - t0, lat


def stream_resident_legs(args, frames, n_keys, apply_batch, latency_s,
                         device) -> None:
    """`--resident 0,1` stream legs: interleaved best-of-3 replays of the
    SAME frame log through a device-resident engine (steady in-place
    micro merges) vs the host-path engine (resident=0 routes micro
    batches to engine/hostbatch), each oracle-verified against the
    per-frame CPU replay, with per-leg transfer counters (BENCH_r12)."""
    from constdb_tpu.engine.tpu import TpuMergeEngine

    legs = [int(x) for x in str(args.resident).split(",")]
    # CONSTDB_BENCH_FOLD carries the kernel-backend forcing into the leg
    # engines (ci.sh runs the resident smoke under pallas-interpret)
    fold = os.environ.get("CONSTDB_BENCH_FOLD", "auto")
    best = {r: (float("inf"), None) for r in legs}
    base_wall, base_node = float("inf"), None
    for _ in range(3):
        for r in legs:
            n_, w_, _ = replay_stream(
                frames,
                # steady FORCED per leg: the auto default only engages
                # over a real accelerator, and the interpret smoke
                # drives this very path on the CPU
                lambda: TpuMergeEngine(resident=bool(r), steady=bool(r),
                                       dense_fold=fold),
                apply_batch=apply_batch, latency_s=latency_s)
            if w_ < best[r][0]:
                best[r] = (w_, n_)
        bn_, bw_, _ = replay_stream(frames, CpuMergeEngine,
                                    apply_batch=1, latency_s=1.0)
        if bw_ < base_wall:
            base_node, base_wall = bn_, bw_
    want = base_node.canonical()
    curve = []
    verified = True
    for r in legs:
        w_, n_ = best[r]
        diffs = compare_canonical(n_.canonical(), want)
        verified = verified and diffs == 0
        leg = {"resident": r, "wall_s": round(w_, 3),
               "fps": round(len(frames) / w_, 1),
               "coalesce_flushes": n_.stats.repl_coalesce_flushes,
               "apply_barriers": n_.stats.repl_apply_barriers,
               "diffs": diffs}
        leg.update(engine_counters(n_.engine))
        curve.append(leg)
        print(f"[bench] resident={r}: {w_:.3f}s = {leg['fps']:,.0f} "
              f"frames/s; dev rounds {leg['dev_rounds_resident']}, host "
              f"rounds {leg['host_micro_rounds']}, flush rows "
              f"{leg['flush_rows_downloaded']}/"
              f"{leg['flush_rows_full_equiv']}, h2d "
              f"{leg['dev_upload_bytes']:,} d2h "
              f"{leg['dev_download_bytes']:,} "
              f"({'OK' if diffs == 0 else 'MISMATCH'})", file=sys.stderr)
        if hasattr(n_.engine, "close"):
            n_.engine.close()
    base_fps = len(frames) / base_wall
    out = {
        "metric": "stream_apply_frames_per_sec",
        "value": curve[-1]["fps"],
        "unit": "frames/sec",
        "mode": "stream",
        "frames": len(frames),
        "stream_keys": n_keys,
        "apply_batch": apply_batch,
        "per_frame_baseline_fps": round(base_fps, 1),
        "resident_curve": curve,
        "device": device,
        "verified": verified,
        "host": host_fingerprint(),
    }
    print(json.dumps(out))
    if not verified:
        sys.exit(1)


def stream_main(args) -> None:
    """`bench.py --mode stream`: coalesced steady-state apply vs the
    exact per-frame path (CONSTDB_APPLY_BATCH=1 degenerate), replaying
    one recorded frame log through both and oracle-comparing the final
    stores.  Emits ONE JSON line with frames/s + p99 visibility."""
    n_frames = int(os.environ.get("CONSTDB_BENCH_FRAMES", 200_000))
    n_keys = int(os.environ.get("CONSTDB_BENCH_STREAM_KEYS", 20_000))
    apply_batch = int(os.environ.get("CONSTDB_BENCH_APPLY_BATCH", 4096))
    latency_s = float(os.environ.get("CONSTDB_BENCH_APPLY_LATENCY_MS",
                                     1000.0)) / 1000.0
    engine_kind = os.environ.get("CONSTDB_BENCH_STREAM_ENGINE", "xla")

    ensure_native()
    if args.frame_log and os.path.exists(args.frame_log):
        frames = load_frame_log(args.frame_log)
        print(f"[bench] replaying recorded frame log {args.frame_log}: "
              f"{len(frames)} frames", file=sys.stderr)
    else:
        t0 = time.perf_counter()
        frames = make_frame_log(n_frames, n_keys)
        print(f"[bench] frame log gen: {len(frames)} frames over "
              f"~{n_keys} keys in {time.perf_counter() - t0:.1f}s",
              file=sys.stderr)
        if args.frame_log:
            save_frame_log(args.frame_log, frames)
            print(f"[bench] recorded to {args.frame_log}", file=sys.stderr)

    device = None
    if engine_kind == "cpu":
        make_engine = CpuMergeEngine
    else:
        _, device = require_device(os.environ.get("CONSTDB_BENCH_FOLD",
                                                  "auto"))
        from constdb_tpu.engine.tpu import TpuMergeEngine

        make_engine = TpuMergeEngine

    if args.resident is not None:
        if device is None:
            sys.exit("[bench] --resident legs are device legs "
                     "(CONSTDB_BENCH_STREAM_ENGINE=cpu has none)")
        stream_resident_legs(args, frames, n_keys, apply_batch, latency_s,
                             device)
        return

    # both paths replay the SAME log, interleaved, best-of-3 (the same
    # convention the snapshot bench uses — one unlucky run on a shared
    # box must not be the round's number).  The per-frame leg
    # (apply_batch=1 routes every frame through node.apply_replicated —
    # the pre-coalescing hot loop) doubles as the verification oracle.
    wall = base_wall = float("inf")
    node = base_node = lat = None
    for _ in range(3):
        n_, w_, l_ = replay_stream(frames, make_engine,
                                   apply_batch=apply_batch,
                                   latency_s=latency_s)
        if w_ < wall:
            node, wall, lat = n_, w_, l_
        bn_, bw_, _ = replay_stream(frames, CpuMergeEngine,
                                    apply_batch=1, latency_s=1.0)
        if bw_ < base_wall:
            base_node, base_wall = bn_, bw_
    base_fps = len(frames) / base_wall
    print(f"[bench] per-frame path: {base_wall:.3f}s = "
          f"{base_fps:,.0f} frames/s", file=sys.stderr)
    fps = len(frames) / wall
    lat_ms = np.asarray(lat) * 1000.0
    p50, p99 = (float(np.percentile(lat_ms, q)) for q in (50, 99))
    print(f"[bench] coalesced (batch={apply_batch}, engine={engine_kind}/"
          f"{device['platform'] if device else 'none'}): "
          f"{wall:.3f}s = {fps:,.0f} frames/s "
          f"({fps / base_fps:.2f}x); visibility p50 {p50:.2f}ms "
          f"p99 {p99:.2f}ms; {node.stats.repl_coalesce_flushes} flushes, "
          f"{node.stats.repl_apply_barriers} barriers", file=sys.stderr)

    got, want = node.canonical(), base_node.canonical()
    n_diff = compare_canonical(got, want)
    verified = n_diff == 0
    print(f"[bench] verify: {'OK' if verified else 'MISMATCH'} on "
          f"{len(want)} keys ({n_diff} diffs)", file=sys.stderr)

    out = {
        "metric": "stream_apply_frames_per_sec",
        "value": round(fps, 1),
        "unit": "frames/sec",
        "mode": "stream",
        "frames": len(frames),
        "stream_keys": n_keys,
        "wall_s": round(wall, 3),
        "per_frame_baseline_fps": round(base_fps, 1),
        "vs_per_frame": round(fps / base_fps, 2),
        "visibility_p50_ms": round(p50, 3),
        "visibility_p99_ms": round(p99, 3),
        "apply_batch": apply_batch,
        "coalesce_flushes": node.stats.repl_coalesce_flushes,
        "apply_barriers": node.stats.repl_apply_barriers,
        "engine": engine_kind,
        "device": device,
        "verified": verified,
        "host": host_fingerprint(),
    }
    out.update(engine_counters(node.engine))
    eng = getattr(node, "engine", None)
    if hasattr(eng, "close"):
        eng.close()
    print(json.dumps(out))
    if not verified:
        sys.exit(1)


# --------------------------------------------------------------------------
# --mode stream --wire: the replication WIRE itself, socket to socket.
# The stream mode above replays frames straight into the applier — it
# measures the apply path with the transport already paid.  The wire
# legs run the REAL push loop against a real socket pair and a receiver
# driving the real intake, interleaved: batch wire (REPLBATCH columnar
# runs, replica/wire.py) vs per-frame wire (the pre-PR byte stream) vs
# the intra-node apply baseline, every leg oracle-verified against the
# per-frame CPU replay, with wire bytes + encode/decode split per leg —
# and a 3-node mesh differential (batch-wire nodes + one per-frame
# node) that must converge byte-identically under mixed traffic.


def frames_to_entries(frames) -> list:
    """Recorded REPLICATE frames -> (uuid, name, args) repl-log rows."""
    from constdb_tpu.resp.message import as_bytes, as_int

    return [(as_int(items[3]), as_bytes(items[4]), list(items[5:]))
            for items in frames]


def _timed_wire_codec():
    """Wrap the wire codec entry points with perf counters (bench-only:
    the product pays no timing overhead).  Returns (acc, restore)."""
    import constdb_tpu.replica.wire as wire_mod

    enc0, dec0 = wire_mod.build_wire_batch, wire_mod.decode_wire_batch
    acc = {"enc": 0.0, "dec": 0.0}

    def enc(*a, **k):
        t = time.perf_counter()
        r = enc0(*a, **k)
        acc["enc"] += time.perf_counter() - t
        return r

    def dec(*a, **k):
        t = time.perf_counter()
        r = dec0(*a, **k)
        acc["dec"] += time.perf_counter() - t
        return r

    wire_mod.build_wire_batch = enc
    wire_mod.decode_wire_batch = dec

    def restore():
        wire_mod.build_wire_batch = enc0
        wire_mod.decode_wire_batch = dec0

    return acc, restore


async def _wire_replay(entries, batching: bool, wire_batch: int,
                       apply_batch: int, latency_s: float):
    """One socket-to-socket leg: the real `_push_loop` streams a filled
    repl_log over a socketpair; the receiver drives the real intake
    (per-frame coalescer + REPLBATCH apply).  Returns the receiver
    node, wall seconds (push start -> watermark covers the last op),
    the pusher node (wire counters), and the REPLACK count."""
    import socket
    import types

    from constdb_tpu.replica.coalesce import CoalescingApplier
    from constdb_tpu.replica.link import (CAP_BATCH_STREAM, PARTSYNC,
                                          REPLACK, REPLBATCH, REPLICATE,
                                          ReplicaLink)
    from constdb_tpu.replica.manager import ReplicaMeta
    from constdb_tpu.resp.codec import make_parser
    from constdb_tpu.resp.message import as_bytes, as_int
    from constdb_tpu.server.node import Node

    loop = asyncio.get_running_loop()
    pusher = Node(node_id=99, repl_log_cap=1 << 40)
    for uuid, name, args in entries:
        pusher.repl_log.push(uuid, name, args)
    last = entries[-1][0]
    app = types.SimpleNamespace(node=pusher, heartbeat=0.2,
                                reconnect_delay=1.0, handshake_timeout=5.0,
                                work_dir=".", wire_batch=wire_batch,
                                wire_latency=0.005)
    meta = ReplicaMeta(addr="bench-wire:1")
    link = ReplicaLink(app, meta)
    link._peer_caps = CAP_BATCH_STREAM if batching else 0
    s_push, s_pull = socket.socketpair()
    push_reader, push_writer = await asyncio.open_connection(sock=s_push)
    pull_reader, pull_writer = await asyncio.open_connection(sock=s_pull)
    recv = Node(node_id=1)
    rmeta = ReplicaMeta("bench-wire:0")
    applier = CoalescingApplier(recv, rmeta, max_frames=apply_batch,
                                max_latency=latency_s, now=loop.time)
    acks = 0

    async def receiver() -> None:
        nonlocal acks
        parser = make_parser()
        while rmeta.uuid_he_sent < last:
            msg = parser.next_msg()
            if msg is None:
                if applier.pending:
                    applier.flush()  # stream idle: land now
                    continue  # re-check the watermark BEFORE blocking —
                    # a tail landed by this flush must end the leg now,
                    # not a pusher heartbeat later (which would charge
                    # an asymmetric ~0.2s penalty to the per-frame leg)
                data = await pull_reader.read(1 << 16)
                if not data:
                    raise ConnectionError("wire leg: EOF")
                parser.feed(data)
                continue
            items = msg.items
            kind = as_bytes(items[0]).lower()
            if kind == REPLICATE:
                applier.apply(items)
            elif kind == REPLBATCH:
                applier.apply_wire_batch(items)
            elif kind == REPLACK:
                acks += 1
                if len(items) > 3:
                    applier.observe_beacon(as_int(items[3]))
            elif kind != PARTSYNC:
                raise AssertionError(f"unexpected wire frame {kind!r}")

    t0 = loop.time()
    push_task = asyncio.create_task(link._push_loop(push_writer,
                                                    peer_resume=0))
    try:
        await asyncio.wait_for(receiver(), timeout=600)
        wall = loop.time() - t0
    finally:
        push_task.cancel()
        for w in (push_writer, pull_writer):
            try:
                w.close()
            except (ConnectionError, OSError):
                pass
    recv.ensure_flushed()
    return recv, wall, pusher, acks


async def _wire_mesh_differential(work_dir: str) -> dict:
    """3-node mesh, one node pinned to the per-frame wire: mixed
    write/DEL/membership traffic from every node must converge all
    three to the identical canonical export (the deterministic twin
    lives in tests/test_repl_capabilities.py)."""
    import random as _random

    from constdb_tpu.resp.codec import RespParser, encode_msg as _enc
    from constdb_tpu.resp.message import Arr, Bulk
    from constdb_tpu.server.io import start_node
    from constdb_tpu.server.node import Node

    class _Cli:
        def __init__(self):
            self.parser = RespParser()

        async def connect(self, addr):
            host, port = addr.rsplit(":", 1)
            self.reader, self.writer = await asyncio.open_connection(
                host, int(port))
            return self

        async def cmd(self, *parts):
            self.writer.write(_enc(Arr([
                Bulk(p if isinstance(p, bytes) else str(p).encode())
                for p in parts])))
            await self.writer.drain()
            while True:
                msg = self.parser.next_msg()
                if msg is not None:
                    return msg
                data = await asyncio.wait_for(self.reader.read(1 << 16), 10)
                if not data:
                    raise ConnectionError("EOF")
                self.parser.feed(data)

        async def close(self):
            self.writer.close()

    apps = []
    for i in range(3):
        node = Node(node_id=i + 1, alias=f"w{i + 1}")
        apps.append(await start_node(node, host="127.0.0.1", port=0,
                                     work_dir=work_dir, heartbeat=0.15,
                                     reconnect_delay=0.25, gc_interval=0.2))
    apps[2].wire_batch = 1  # the per-frame node, pinned pre-handshake
    out = {"converged": False, "batches": 0, "perframe_node_batches": 0}
    try:
        clients = [await _Cli().connect(a.advertised_addr) for a in apps]
        await clients[0].cmd("meet", apps[1].advertised_addr)
        await clients[0].cmd("meet", apps[2].advertised_addr)
        rng = _random.Random(31)
        for i in range(300):
            c = clients[i % 3]
            r = rng.random()
            k = f"k{rng.randrange(50)}"
            if r < 0.35:
                await c.cmd("set", "r" + k, f"v{i}")
            elif r < 0.55:
                await c.cmd("incrby", "c" + k, rng.randrange(1, 9))
            elif r < 0.75:
                await c.cmd("sadd", "s" + k, f"m{rng.randrange(12)}")
            elif r < 0.88:
                await c.cmd("hset", "h" + k, "f1", f"v{i}")
            else:
                await c.cmd("del", "r" + k)
        # pipelined burst so runs form on the capable pair
        c0 = clients[0]
        for i in range(300):
            c0.writer.write(_enc(Arr([Bulk(b"set"),
                                      Bulk(b"burst%d" % i),
                                      Bulk(b"v" * 12)])))
        await c0.writer.drain()
        got = 0
        while got < 300:
            if c0.parser.next_msg() is not None:
                got += 1
                continue
            data = await asyncio.wait_for(c0.reader.read(1 << 16), 10)
            if not data:
                raise ConnectionError("EOF")
            c0.parser.feed(data)
        deadline = asyncio.get_running_loop().time() + 60
        while asyncio.get_running_loop().time() < deadline:
            canons = [a.node.canonical() for a in apps]
            if all(c == canons[0] for c in canons[1:]):
                out["converged"] = True
                break
            await asyncio.sleep(0.05)
        out["batches"] = sum(a.node.stats.repl_wire_batches_out
                             for a in apps[:2])
        out["perframe_node_batches"] = \
            apps[2].node.stats.repl_wire_batches_out + \
            apps[2].node.stats.repl_wire_batches_in
        for c in clients:
            await c.close()
    finally:
        for a in apps:
            await a.close()
    return out


def wire_main(args) -> None:
    """`bench.py --mode stream --wire`: the batch wire protocol end to
    end over real sockets.  Emits ONE JSON line (BENCH_r14)."""
    import tempfile

    n_frames = int(os.environ.get("CONSTDB_BENCH_FRAMES", 100_000))
    n_keys = int(os.environ.get("CONSTDB_BENCH_STREAM_KEYS", 20_000))
    apply_batch = int(os.environ.get("CONSTDB_BENCH_APPLY_BATCH", 4096))
    latency_s = float(os.environ.get("CONSTDB_BENCH_APPLY_LATENCY_MS",
                                     1000.0)) / 1000.0
    wire_batch = int(os.environ.get("CONSTDB_BENCH_WIRE_BATCH", 512))
    reps = int(os.environ.get("CONSTDB_BENCH_WIRE_REPS", 3))

    ensure_native()
    if args.frame_log and os.path.exists(args.frame_log):
        frames = load_frame_log(args.frame_log)
    else:
        frames = make_frame_log(n_frames, n_keys)
        if args.frame_log:
            save_frame_log(args.frame_log, frames)
    entries = frames_to_entries(frames)
    per_frame_wire_bytes = sum(
        len(encode_msg_frame(items)) for items in frames)
    print(f"[bench] wire legs: {len(frames)} frames, per-frame wire "
          f"{per_frame_wire_bytes:,} B "
          f"({per_frame_wire_bytes / len(frames):.1f} B/op)",
          file=sys.stderr)

    # oracle: the per-frame CPU replay of the same log
    base_node, _, _ = replay_stream(frames, CpuMergeEngine,
                                    apply_batch=1, latency_s=1.0)
    want = base_node.canonical()

    # intra-node baseline: the coalesced apply path with no socket
    intra_wall = float("inf")
    for _ in range(reps):
        _, w_, _ = replay_stream(frames, CpuMergeEngine,
                                 apply_batch=apply_batch,
                                 latency_s=latency_s)
        intra_wall = min(intra_wall, w_)

    best = {True: None, False: None}
    for _ in range(reps):
        for batching in (True, False):
            acc, restore = _timed_wire_codec()
            try:
                recv, wall, pusher, acks = asyncio.run(_wire_replay(
                    entries, batching, wire_batch, apply_batch, latency_s))
            finally:
                restore()
            leg = {
                "leg": "batch-wire" if batching else "per-frame-wire",
                "wall_s": round(wall, 3),
                "fps": round(len(frames) / wall, 1),
                "wire_bytes": pusher.stats.repl_wire_bytes_out,
                "bytes_per_op": round(
                    pusher.stats.repl_wire_bytes_out / len(frames), 1),
                "batches": pusher.stats.repl_wire_batches_out,
                "batch_frames": pusher.stats.repl_wire_batch_frames_out,
                "encode_s": round(acc["enc"], 3),
                "decode_s": round(acc["dec"], 3),
                "replacks": acks,
                "coalesce_flushes": recv.stats.repl_coalesce_flushes,
                "apply_barriers": recv.stats.repl_apply_barriers,
                "wire_demotions": recv.stats.repl_wire_demotions,
                "diffs": compare_canonical(recv.canonical(), want),
            }
            prev = best[batching]
            if leg["diffs"]:
                best[batching] = leg  # a diverging rep always surfaces
            elif prev is None or (prev["diffs"] == 0
                                  and wall < prev["wall_s"]):
                best[batching] = leg
            print(f"[bench] {leg['leg']}: {leg['wall_s']}s = "
                  f"{leg['fps']:,.0f} frames/s, "
                  f"{leg['wire_bytes']:,} wire B "
                  f"({leg['bytes_per_op']} B/op), {leg['batches']} "
                  f"batches, enc {leg['encode_s']}s dec "
                  f"{leg['decode_s']}s, {leg['replacks']} acks "
                  f"({'OK' if leg['diffs'] == 0 else 'MISMATCH'})",
                  file=sys.stderr)

    batch_leg, frame_leg = best[True], best[False]
    with tempfile.TemporaryDirectory(prefix="constdb-wire-mesh") as td:
        mesh = asyncio.run(_wire_mesh_differential(td))
    print(f"[bench] mesh differential: converged={mesh['converged']}, "
          f"{mesh['batches']} batches on the capable pair, "
          f"{mesh['perframe_node_batches']} on the per-frame node",
          file=sys.stderr)

    verified = batch_leg["diffs"] == 0 and frame_leg["diffs"] == 0 and \
        mesh["converged"] and mesh["perframe_node_batches"] == 0
    out = {
        "metric": "wire_stream_apply_frames_per_sec",
        "value": batch_leg["fps"],
        "unit": "frames/sec",
        "mode": "stream-wire",
        "frames": len(frames),
        "stream_keys": n_keys,
        "wire_batch": wire_batch,
        "apply_batch": apply_batch,
        "legs": [batch_leg, frame_leg],
        "speedup_vs_per_frame_wire": round(
            batch_leg["fps"] / frame_leg["fps"], 2),
        "wire_bytes_ratio": round(
            frame_leg["wire_bytes"] / batch_leg["wire_bytes"], 2),
        "intra_node_fps": round(len(frames) / intra_wall, 1),
        "mesh_differential": mesh,
        "engine": "cpu-hostbatch",
        "backend": "none",
        "verified": verified,
        "host": host_fingerprint(),
    }
    print(json.dumps(out))
    if not verified:
        sys.exit(1)


def encode_msg_frame(items) -> bytes:
    from constdb_tpu.resp.codec import encode_msg
    from constdb_tpu.resp.message import Arr

    return encode_msg(Arr(items))


# ---------------------------------------------------------------- fan-out


async def _fanout_replay(entries, n_peers: int, cache_mb: int,
                         wire_batch: int, apply_batch: int,
                         latency_s: float, compress: bool = False):
    """One fan-out leg: ONE pusher node drives N real `_push_loop`s over
    N socketpairs into N independent receiver nodes (the broadcast
    plane's steady-state shape).  `cache_mb` sizes the encode-once run
    cache (0 = the pre-broadcast every-peer-re-encodes path).  Returns
    (recv_nodes, wall_s, pusher, per_link_rows)."""
    import socket
    import types

    from constdb_tpu.replica.coalesce import CoalescingApplier
    from constdb_tpu.replica.link import (CAP_BATCH_STREAM, CAP_COMPRESS,
                                          PARTSYNC, REPLACK, REPLBATCH,
                                          REPLICATE, ReplicaLink)
    from constdb_tpu.replica.manager import ReplicaMeta
    from constdb_tpu.resp.codec import make_parser
    from constdb_tpu.resp.message import as_bytes, as_int
    from constdb_tpu.server.node import Node

    loop = asyncio.get_running_loop()
    pusher = Node(node_id=99, repl_log_cap=1 << 40)
    pusher.wire_cache.configure(cache_mb << 20)
    for uuid, name, args in entries:
        pusher.repl_log.push(uuid, name, args)
    last = entries[-1][0]
    # repl_window=0: these receivers never REPLACK, so any finite
    # window would park the drain forever once a leg's stream bytes
    # pass it (flow control is not what this leg measures)
    app = types.SimpleNamespace(node=pusher, heartbeat=0.2,
                                reconnect_delay=1.0, handshake_timeout=5.0,
                                work_dir=".", wire_batch=wire_batch,
                                wire_latency=0.005, repl_window=0)
    caps = CAP_BATCH_STREAM | (CAP_COMPRESS if compress else 0)

    async def receiver(pull_reader, stash) -> None:
        # a real mesh's peers apply on OTHER machines: during the timed
        # window this 2-core box only pays the pusher's fan-out plus
        # minimal frame parsing (coverage detection); each captured
        # stream is applied and oracle-verified AFTER the wall stops
        parser = make_parser()
        covered = 0
        while covered < last:
            msg = parser.next_msg()
            if msg is None:
                data = await pull_reader.read(1 << 16)
                if not data:
                    raise ConnectionError("fanout leg: EOF")
                parser.feed(data)
                continue
            items = msg.items
            kind = as_bytes(items[0]).lower()
            if kind in (REPLICATE, REPLBATCH):
                covered = as_int(items[3])
                stash.append((kind, items))
            elif kind not in (REPLACK, PARTSYNC):
                raise AssertionError(f"unexpected wire frame {kind!r}")

    links, writers, recv_coros, stashes = [], [], [], []
    for i in range(n_peers):
        meta = ReplicaMeta(addr=f"bench-fan:{i}")
        pusher.replicas.peers[meta.addr] = meta
        link = ReplicaLink(app, meta)
        link._peer_caps = caps
        s_push, s_pull = socket.socketpair()
        _pr, push_writer = await asyncio.open_connection(sock=s_push)
        pull_reader, _pw = await asyncio.open_connection(sock=s_pull)
        stash: list = []
        links.append(link)
        writers.append((push_writer, _pw))
        stashes.append(stash)
        recv_coros.append(receiver(pull_reader, stash))

    t0 = loop.time()
    push_tasks = [asyncio.create_task(lk._push_loop(w[0], peer_resume=0))
                  for lk, w in zip(links, writers)]
    try:
        await asyncio.wait_for(asyncio.gather(*recv_coros), timeout=600)
        wall = loop.time() - t0
    finally:
        for t in push_tasks:
            t.cancel()
        for pw, qw in writers:
            for w in (pw, qw):
                try:
                    w.close()
                except (ConnectionError, OSError):
                    pass
    # post-wall: land every captured stream through the real intake
    recvs = []
    for i, stash in enumerate(stashes):
        recv = Node(node_id=i + 1)
        applier = CoalescingApplier(recv, ReplicaMeta(f"bench-fan-src:{i}"),
                                    max_frames=apply_batch,
                                    max_latency=latency_s, now=loop.time)
        for kind, items in stash:
            if kind == REPLICATE:
                applier.apply(items)
            else:
                applier.apply_wire_batch(items)
        applier.flush()
        recv.ensure_flushed()
        recvs.append(recv)
    per_link = [{"bytes_out": lk.bytes_out, "cache_hits": lk.cache_hits,
                 "cache_misses": lk.cache_misses,
                 "comp_raw": lk.comp_raw_bytes,
                 "comp_wire": lk.comp_wire_bytes} for lk in links]
    return recvs, wall, pusher, per_link


def _fullsync_bytes_leg(n_keys: int, n_replicas: int, engine_kind: str,
                        work_dir: str) -> dict:
    """Compressed-vs-plain bulk sync bytes: the SAME keyspace dumped as
    the plain full-sync stream (per-section zlib, the pre-CAP_COMPRESS
    wire) and as the compressed container, both loaded back into fresh
    stores and canonical()-compared byte-identically.  The workload is
    HLC-ordered (make_workload hlc_order): a real node's dump iterates
    keys in creation order, so its uuid columns are near-monotone —
    the shape the container's transposition filter exploits."""
    from constdb_tpu.persist.snapshot import (NodeMeta, batch_chunks,
                                              load_snapshot,
                                              write_snapshot_file)
    from constdb_tpu.engine.base import batch_from_keyspace

    batches = make_workload(n_keys, n_replicas, hlc_order=True)
    if engine_kind == "cpu":
        engine = CpuMergeEngine()
    else:
        from constdb_tpu.engine.tpu import TpuMergeEngine
        engine = TpuMergeEngine()
    ks = KeySpace()
    for b in batches:
        for chunk in batch_chunks(b, 1 << 16):
            engine.merge(ks, chunk)
    if getattr(engine, "needs_flush", False):
        engine.flush(ks)
    capture = batch_from_keyspace(ks)
    meta = NodeMeta(node_id=1, alias="bench")
    p_plain = os.path.join(work_dir, "fsync.plain.snapshot")
    p_comp = os.path.join(work_dir, "fsync.z.snapshot")
    # the acceptance denominator: the UNCOMPRESSED stream (level 0 —
    # what the bytes are before any compression; the pre-PR wire
    # additionally had the per-section zlib, reported as plain_bytes)
    raw_bytes = write_snapshot_file(p_plain, meta, [], [capture],
                                    compress_level=0)
    t0 = time.perf_counter()
    plain_bytes = write_snapshot_file(p_plain, meta, [], [capture],
                                      compress_level=1)
    t_plain = time.perf_counter() - t0
    t0 = time.perf_counter()
    comp_bytes = write_snapshot_file(p_comp, meta, [], [capture],
                                     container_level=6)
    t_comp = time.perf_counter() - t0
    # both variants must land IDENTICAL state (the verify half of the
    # bulk-bytes acceptance: byte-identical post-apply canonical export)
    sub_keys = subsample_keys(batches[0].keys, n_keys)
    want = ks.canonical(keys=sub_keys)
    canons = []
    for p in (p_plain, p_comp):
        ks2 = KeySpace()
        load_snapshot(p, ks2, engine=CpuMergeEngine())
        canons.append(ks2.canonical(keys=sub_keys))
    verified = canons[0] == want and canons[1] == want
    for p in (p_plain, p_comp):
        try:
            os.unlink(p)
        except OSError:
            pass
    return {
        "keys": n_keys, "replicas": n_replicas,
        "uncompressed_bytes": raw_bytes,
        "plain_bytes": plain_bytes, "compressed_bytes": comp_bytes,
        "bytes_ratio_vs_uncompressed": round(comp_bytes / raw_bytes, 4),
        "bytes_ratio_vs_plain_wire": round(comp_bytes / plain_bytes, 4),
        "plain_dump_s": round(t_plain, 3),
        "compressed_dump_s": round(t_comp, 3),
        "verified": verified,
    }


def fanout_main(args) -> None:
    """`bench.py --mode stream --peers N`: the broadcast replication
    plane — encode-once fan-out scaling (1/2/4 peers, cache-on vs
    cache-off interleaved, every peer oracle-verified) plus the
    compressed-vs-plain bulk-sync bytes leg.  Emits ONE JSON line
    (BENCH_r16)."""
    import tempfile

    n_frames = int(os.environ.get("CONSTDB_BENCH_FRAMES", 60_000))
    n_keys = int(os.environ.get("CONSTDB_BENCH_STREAM_KEYS", 20_000))
    apply_batch = int(os.environ.get("CONSTDB_BENCH_APPLY_BATCH", 4096))
    latency_s = float(os.environ.get("CONSTDB_BENCH_APPLY_LATENCY_MS",
                                     1000.0)) / 1000.0
    wire_batch = int(os.environ.get("CONSTDB_BENCH_WIRE_BATCH", 512))
    reps = int(os.environ.get("CONSTDB_BENCH_FANOUT_REPS", 2))
    cache_mb = int(os.environ.get("CONSTDB_BENCH_ENCODE_CACHE_MB", 64))
    peer_counts = [int(p) for p in os.environ.get(
        "CONSTDB_BENCH_FANOUT_PEERS", "1,2,4").split(",")]
    max_peers = args.peers
    fs_keys = int(os.environ.get("CONSTDB_BENCH_FSYNC_KEYS", 200_000))
    fs_replicas = int(os.environ.get("CONSTDB_BENCH_FSYNC_REPLICAS", 8))
    fs_engine = os.environ.get("CONSTDB_BENCH_FSYNC_ENGINE", "cpu")

    ensure_native()
    frames = make_frame_log(n_frames, n_keys)
    entries = frames_to_entries(frames)

    # oracle: the per-frame CPU replay of the same log
    base_node, _, _ = replay_stream(frames, CpuMergeEngine,
                                    apply_batch=1, latency_s=1.0)
    want = base_node.canonical()

    curve = []
    verified = True
    for peers in peer_counts:
        if peers > max_peers:
            continue
        best = {True: None, False: None}
        for _ in range(reps):
            # interleaved cache-on / cache-off so drift hits both legs
            for cache_on in (True, False):
                recvs, wall, pusher, per_link = asyncio.run(
                    _fanout_replay(entries, peers,
                                   cache_mb if cache_on else 0,
                                   wire_batch, apply_batch, latency_s))
                diffs = sum(compare_canonical(r.canonical(), want)
                            for r in recvs)
                st = pusher.stats
                hits, misses = (st.repl_encode_cache_hits,
                                st.repl_encode_cache_misses)
                leg = {
                    "peers": peers,
                    "cache": "on" if cache_on else "off",
                    "wall_s": round(wall, 3),
                    "fps_per_peer": round(n_frames / wall, 1),
                    "agg_fps": round(n_frames * peers / wall, 1),
                    "cache_hits": hits,
                    "cache_misses": misses,
                    "cache_hit_rate": round(hits / (hits + misses), 3)
                    if hits + misses else 0.0,
                    "wire_bytes": st.repl_wire_bytes_out,
                    "per_link": per_link,
                    "diffs": diffs,
                }
                prev = best[cache_on]
                if diffs:
                    best[cache_on] = leg
                elif prev is None or (prev["diffs"] == 0
                                      and wall < prev["wall_s"]):
                    best[cache_on] = leg
                print(f"[bench] fanout peers={peers} cache="
                      f"{leg['cache']}: {leg['wall_s']}s = "
                      f"{leg['agg_fps']:,.0f} agg frames/s, hit rate "
                      f"{leg['cache_hit_rate']}, "
                      f"{'OK' if diffs == 0 else 'MISMATCH'}",
                      file=sys.stderr)
        on, off = best[True], best[False]
        verified &= on["diffs"] == 0 and off["diffs"] == 0
        curve.append({"peers": peers, "cache_on": on, "cache_off": off,
                      "speedup_vs_cache_off": round(
                          on["agg_fps"] / off["agg_fps"], 2)})

    print(f"[bench] fullsync bytes leg: {fs_keys} keys x {fs_replicas} "
          f"replicas ({fs_engine})", file=sys.stderr)
    with tempfile.TemporaryDirectory(prefix="constdb-fanout") as td:
        fullsync = _fullsync_bytes_leg(fs_keys, fs_replicas, fs_engine, td)
    verified &= fullsync["verified"]
    print(f"[bench] fullsync bytes: uncompressed "
          f"{fullsync['uncompressed_bytes']:,} / plain wire "
          f"{fullsync['plain_bytes']:,} -> compressed "
          f"{fullsync['compressed_bytes']:,} "
          f"({fullsync['bytes_ratio_vs_uncompressed']:.3f}x of "
          f"uncompressed, {fullsync['bytes_ratio_vs_plain_wire']:.3f}x "
          f"of the plain wire), verified={fullsync['verified']}",
          file=sys.stderr)

    top = curve[-1]
    out = {
        "metric": "fanout_aggregate_frames_per_sec",
        "value": top["cache_on"]["agg_fps"],
        "unit": "frames/sec",
        "mode": "stream-fanout",
        "frames": n_frames,
        "stream_keys": n_keys,
        "wire_batch": wire_batch,
        "apply_batch": apply_batch,
        "encode_cache_mb": cache_mb,
        "curve": curve,
        "fanout_speedup_at_max_peers": top["speedup_vs_cache_off"],
        "cache_hit_rate_at_max_peers": top["cache_on"]["cache_hit_rate"],
        "fullsync": fullsync,
        "engine": "cpu-hostbatch",
        "backend": "none",
        "verified": verified,
        "host": host_fingerprint(),
    }
    print(json.dumps(out))
    if not verified:
        sys.exit(1)


# --------------------------------------------------------------------------
# --mode tensor: tensor-valued registers — the first family designed
# device-first (crdt/tensor.py).  A stream of contribution micro-batches
# (the coalescer flush shape: a few hundred rows, rows_unique=False)
# merges into a store, and EVERY round the full key set is read back
# (the aggregation product — distributed model/embedding serving).  The
# device leg keeps payloads resident (engine/tpu.py pools: merges
# scatter in place, reads gather+reduce on device, only [G, K] results
# download); the host leg is the per-row reference
# (KeySpace.tensor_merge_row + tensor_read).  Both legs are
# oracle-verified bit-identical — the canonical-order law makes that a
# hard equality even for float reductions.


def make_tensor_workload(n_rounds: int, batch_rows: int, n_keys: int,
                         n_nodes: int, elems: int, strat: str,
                         seed: int = 17) -> list:
    """Deterministic per-round ColumnarBatches of tensor contributions
    (every (key, node) slot seeded in round 0 so reads always see
    n_nodes contributors — the model-merge shape)."""
    from constdb_tpu.crdt import semantics as S
    from constdb_tpu.crdt import tensor as T
    from constdb_tpu.engine.base import ColumnarBatch

    rng = np.random.default_rng(seed)
    meta = T.TensorMeta(T.STRATEGY_IDS[strat], 0, (elems,))
    cfg = T.pack_config(meta)
    u = 1
    out = []
    for r in range(n_rounds):
        if r == 0:
            pairs = [(k, nd) for k in range(n_keys)
                     for nd in range(1, n_nodes + 1)]
        else:
            pairs = [(int(rng.integers(n_keys)),
                      int(rng.integers(1, n_nodes + 1)))
                     for _ in range(batch_rows)]
        n = len(pairs)
        b = ColumnarBatch()
        b.keys = [b"t%06d" % k for k, _ in pairs]
        uuids = np.empty(n, dtype=_I64)
        for i in range(n):
            u += 1
            uuids[i] = (MS0 + u) << SEQ_BITS
        b.key_enc = np.full(n, S.ENC_TENSOR, np.int8)
        b.key_ct = uuids.copy()
        b.key_mt = uuids.copy()
        b.key_dt = np.zeros(n, dtype=_I64)
        b.key_expire = np.zeros(n, dtype=_I64)
        b.reg_val = [None] * n
        b.reg_t = np.zeros(n, dtype=_I64)
        b.reg_node = np.zeros(n, dtype=_I64)
        b.tns_ki = np.arange(n, dtype=_I64)
        b.tns_node = np.fromiter((nd for _, nd in pairs), dtype=_I64,
                                 count=n)
        b.tns_uuid = uuids
        b.tns_cnt = rng.integers(1, 8, size=n).astype(_I64)
        b.tns_cfg = [cfg] * n
        payloads = (rng.standard_normal((n, elems)) * 4).astype(np.float32)
        b.tns_payload = [payloads[i].tobytes() for i in range(n)]
        b.rows_unique_per_slot = False
        out.append(b)
    return out


def _tensor_leg(batches, n_keys: int, make_engine, device_reads: bool):
    """One leg: merge every round's batch, then read ALL keys (device
    path via engine.tensor_read_many when available).  Returns (store,
    engine, wall_s, final reads dict key->bytes)."""
    from constdb_tpu.store.keyspace import KeySpace

    store = KeySpace()
    engine = make_engine()
    reads = None
    t0 = time.perf_counter()
    for b in batches:
        engine.merge_many(store, [b])
        kids = range(n_keys)
        if device_reads:
            reads = engine.tensor_read_many(store, kids)
        else:
            reads = {kid: store.tensor_read(kid) for kid in kids}
    if getattr(engine, "needs_flush", False):
        engine.flush(store)
    wall = time.perf_counter() - t0
    final = {store.key_bytes[kid]: (None if arr is None else arr.tobytes())
             for kid, arr in reads.items()}
    return store, engine, wall, final


def tensor_main(args) -> None:
    """`bench.py --mode tensor`: the resident device tensor path vs the
    host reference on coalescer-sized micro-batches, interleaved
    best-of-3 per strategy, both legs oracle-verified bit-identical
    (final reads AND canonical export).  Emits ONE JSON line
    (BENCH_r13)."""
    from constdb_tpu.engine.tpu import TpuMergeEngine

    n_keys = int(os.environ.get("CONSTDB_BENCH_TNS_KEYS", 128))
    elems = int(os.environ.get("CONSTDB_BENCH_TNS_ELEMS", 4096))
    n_nodes = int(os.environ.get("CONSTDB_BENCH_TNS_NODES", 8))
    n_rounds = int(os.environ.get("CONSTDB_BENCH_TNS_ROUNDS", 24))
    batch_rows = int(os.environ.get("CONSTDB_BENCH_TNS_BATCH", 128))
    strats = os.environ.get("CONSTDB_BENCH_TNS_STRATS",
                            "avg,maxmag,trimmed-mean,sum,lww").split(",")
    reps = int(os.environ.get("CONSTDB_BENCH_TNS_REPS", 3))
    fold = os.environ.get("CONSTDB_BENCH_FOLD", "auto")

    _, device = require_device(fold)

    curve = []
    verified = True
    for strat in strats:
        batches = make_tensor_workload(n_rounds, batch_rows, n_keys,
                                       n_nodes, elems, strat)
        rows_total = sum(len(b.tns_ki) for b in batches)
        best_dev = (float("inf"), None, None, None)
        best_host = (float("inf"), None, None)
        for _ in range(reps):
            st_d, eng_d, w_d, reads_d = _tensor_leg(
                batches, n_keys,
                # steady FORCED: this leg measures the resident path
                # itself; 'auto' keeps CPU-only production boxes on the
                # host strategy (the host leg below IS that path)
                lambda: TpuMergeEngine(resident=True, steady=True,
                                       warmup=0, dense_fold=fold),
                device_reads=True)
            if w_d < best_dev[0]:
                if best_dev[2] is not None:
                    best_dev[2].close()  # displaced best: free its pools
                best_dev = (w_d, st_d, eng_d, reads_d)
            elif hasattr(eng_d, "close"):
                eng_d.close()
            st_h, _eng_h, w_h, reads_h = _tensor_leg(
                batches, n_keys, CpuMergeEngine, device_reads=False)
            if w_h < best_host[0]:
                best_host = (w_h, st_h, reads_h)
        w_d, st_d, eng_d, reads_d = best_dev
        w_h, st_h, reads_h = best_host
        ok = reads_d == reads_h and \
            st_d.canonical() == st_h.canonical()
        verified = verified and ok
        leg = {
            "strategy": strat,
            "dev_wall_s": round(w_d, 3),
            "host_wall_s": round(w_h, 3),
            "dev_rows_per_sec": round(rows_total / w_d, 1),
            "host_rows_per_sec": round(rows_total / w_h, 1),
            "speedup": round(w_h / w_d, 2),
            "rows": rows_total,
            "reads": n_rounds * n_keys,
            "verified": ok,
        }
        leg.update(engine_counters(eng_d))
        leg["tns_dev_rows"] = getattr(eng_d, "tns_dev_rows", 0)
        leg["tns_host_rows"] = getattr(eng_d, "tns_host_rows", 0)
        curve.append(leg)
        print(f"[bench] tensor {strat}: device {w_d:.3f}s vs host "
              f"{w_h:.3f}s = {leg['speedup']:.2f}x "
              f"({rows_total} rows, {leg['reads']} reads, "
              f"{eng_d.tns_dev_rows} dev rows, "
              f"{leg['dev_rounds_resident']} resident rounds) "
              f"({'OK' if ok else 'MISMATCH'})", file=sys.stderr)
        if hasattr(eng_d, "close"):
            eng_d.close()
    ratios = [leg["speedup"] for leg in curve]
    out = {
        "metric": "tensor_merge_speedup_vs_host",
        "value": round(min(ratios), 2),
        "unit": "x (worst strategy)",
        "mode": "tensor",
        "keys": n_keys,
        "elems": elems,
        "payload_bytes": elems * 4,
        "contributors": n_nodes,
        "rounds": n_rounds,
        "batch_rows": batch_rows,
        "curve": curve,
        "device": device,
        "fold": fold,
        "verified": verified,
        "host": host_fingerprint(),
    }
    print(json.dumps(out))
    if not verified:
        sys.exit(1)


# --------------------------------------------------------------------------
# --mode serve: pipelined client serving over real sockets (the serve
# coalescer, server/serve.py) vs the CONSTDB_SERVE_BATCH=1 per-command
# baseline — the serving-throughput headline the r05-r08 trajectory
# (ingest, shards, stream) was still missing.


def serve_workload(conn_id: int, n_ops: int, n_keys: int, pipeline: int,
                   seed: int = 13) -> list:
    """Pre-encoded pipelined chunks for one connection: a write-heavy
    mixed command stream (sets, counters, set/hash members) with reads
    and DELs sprinkled in as serve-path barriers.  Keys carry the
    connection id, so each key has a single writer and both reply
    streams and final per-key values are interleave-invariant — the
    cross-leg oracle needs that, because two legs schedule the
    connections differently."""
    import random

    from constdb_tpu.resp.codec import encode_into
    from constdb_tpu.resp.message import Arr, Bulk

    rng = random.Random(seed * 1000 + conn_id)
    pfx = b"c%d:" % conn_id
    chunks = []
    cur = bytearray()
    n = 0
    for i in range(n_ops):
        r = rng.random()
        k = pfx + b"%05d" % rng.randrange(n_keys)
        if r < 0.25:
            body = (b"set", b"r" + k, b"v%08d" % i)
        elif r < 0.50:
            body = (b"incr", b"c" + k, b"%d" % rng.randrange(1, 100))
        elif r < 0.75:
            # tag/follower-list writes (multi-member, the set shape the
            # stream bench uses)
            body = (b"sadd", b"s" + k,
                    *(b"m%03d" % rng.randrange(256) for _ in range(8)))
        elif r < 0.95:
            # YCSB's canonical user-record workload writes 10 fields/op
            fv = []
            for f in range(10):
                fv += [b"f%02d" % rng.randrange(32), b"v%07d%d" % (i, f)]
            body = (b"hset", b"h" + k, *fv)
        elif r < 0.97:
            body = (b"get", b"r" + k)        # read barrier
        elif r < 0.995:
            body = (b"srem", b"s" + k, b"m%03d" % rng.randrange(256))
        else:
            # DELs ~0.5%, the r08 stream-bench convention: ConstDB's
            # serving workload is write-once constant data (PAPER.md) —
            # deletes are administrative, but must be PRESENT so the
            # bench exercises the flushing-barrier machinery for real
            body = (b"del", b"r" + k)        # read-modify barrier
        encode_into(cur, Arr([Bulk(b) for b in body]))
        n += 1
        if n >= pipeline:
            chunks.append((bytes(cur), n))
            cur = bytearray()
            n = 0
    if n:
        chunks.append((bytes(cur), n))
    return chunks


def _serve_bench_server(pipe, serve_batch: int, engine_kind: str,
                        serve_shards: int = 1, aof_policy=None,
                        aof_dir: str = "", read_cache_mb=None) -> None:
    """Forked server worker: one real ServerApp on a fresh port.  Sends
    the port up, serves until the parent says stop, then ships back the
    canonical export + serve stats.  `serve_shards > 1` runs the
    shard-per-core plane (server/serve_shards.py) — the canonical
    export then consolidates the worker shards."""
    import asyncio
    import gc

    from constdb_tpu.server.io import start_node
    from constdb_tpu.server.node import Node

    # redis-style serving GC posture, identical for BOTH legs: the boot
    # object graph is frozen out of collection and the gen0 threshold
    # raised so steady-state allocation churn (parsed frames, replies,
    # repl entries) is not swept every ~700 allocations
    gc.collect()
    gc.freeze()
    gc.set_threshold(100_000, 50, 50)

    if read_cache_mb is not None:
        # before Node construction — the cache cap is read from the
        # registry at init (cache-on/cache-off sub-legs)
        os.environ["CONSTDB_READ_CACHE_MB"] = str(read_cache_mb)

    def make_engine():
        if engine_kind == "cpu":
            from constdb_tpu.engine.cpu import CpuMergeEngine
            return CpuMergeEngine()
        from constdb_tpu.conf import build_engine
        return build_engine(engine_kind)

    async def main():
        node = Node(node_id=1, alias="bench", engine=make_engine())
        kw = {}
        if aof_policy is not None:
            kw = dict(aof=True, aof_fsync=aof_policy, aof_dir=aof_dir)
        app = await start_node(node, host="127.0.0.1", port=0,
                               work_dir="/tmp", serve_batch=serve_batch,
                               serve_shards=serve_shards, **kw)
        pipe.send(app.port)
        loop = asyncio.get_running_loop()
        await loop.run_in_executor(None, pipe.recv)  # block until "stop"
        node.ensure_flushed()
        if node.serve_plane is not None:
            canon = await node.serve_plane.canonical()
        else:
            canon = node.canonical()
        st = node.stats
        x = st.extra
        pipe.send((canon, {
            "serve_msgs_coalesced": st.serve_msgs_coalesced,
            "serve_flushes": st.serve_flushes,
            "serve_barriers": st.serve_barriers,
            "serve_reads_coalesced": st.serve_reads_coalesced,
            "serve_read_flushes": st.serve_read_flushes,
            "read_cache_hits": node.read_cache.hits,
            "read_cache_misses": node.read_cache.misses,
            "read_cache_bytes": node.read_cache.bytes,
            "read_cache_invalidations": node.read_cache.invalidations,
            "cmds_processed": st.cmds_processed,
            "native_intake_chunks": st.native_intake_chunks,
            "native_intake_msgs": st.native_intake_msgs,
            "oom_shed_writes": st.oom_shed_writes,
            "oom_hard_reclaims": st.oom_hard_reclaims,
            "used_memory": node.governor.used_memory(),
            "overload_state": node.governor.state_name,
            "serve_shards": serve_shards,
            "serve_xshard_barriers": x.get("serve_xshard_barriers", 0),
            "per_shard": {
                s: {"msgs": x.get(f"serve_shard{s}_msgs", 0),
                    "flushes": x.get(f"serve_shard{s}_flushes", 0),
                    "barriers": x.get(f"serve_shard{s}_barriers", 0),
                    "keys": x.get(f"serve_shard{s}_keys", 0)}
                for s in range(serve_shards)} if serve_shards > 1 else {},
            "aof_size_bytes": node.oplog.size_bytes()
            if node.oplog is not None else 0,
            "aof_fsyncs": node.oplog.fsyncs
            if node.oplog is not None else 0,
            "aof_encoded_batches": node.oplog.encoded_batches
            if node.oplog is not None else 0,
        }))
        await app.close()

    try:
        asyncio.run(main())
    except BaseException as e:  # parent surfaces the failure
        try:
            pipe.send(e)
        except OSError:
            pass
    finally:
        pipe.close()


def strip_canonical_times(canon: dict) -> dict:
    """Visible-value projection of a canonical export.  Two serve-bench
    legs schedule connections differently, so HLC timestamps (and
    therefore the raw canonical bytes) legitimately differ — but with
    single-writer keys every VISIBLE value is interleave-invariant, so
    this projection must match exactly."""
    from constdb_tpu.crdt import semantics as S

    out = {}
    for key, (enc, ct, mt, dt, expire, content) in canon.items():
        alive = ct >= dt
        if enc == S.ENC_COUNTER:
            val = sum(t - b for _n, t, _u, b, _bt in content)
        elif enc == S.ENC_BYTES:
            val = content[0]
        else:
            val = frozenset((m, v) for m, at, _an, dlt, v in content
                            if at >= dlt)
        out[key] = (enc, alive, val)
    return out


async def _serve_drive(port: int, per_conn: list, rtts: list,
                       hashes: list) -> None:
    """Drive every connection FULLY PIPELINED: a writer task streams the
    pre-encoded windows continuously (bounded only by socket
    backpressure — the server reads as deep a chunk as TCP delivers,
    which is what lets its planner build long runs), while a reader task
    concurrently counts replies and hashes the reply byte stream.
    Reply latency is sampled per window: send time vs the time the
    window's last reply is parsed (includes pipeline queueing — the
    latency a streaming client actually observes)."""
    import asyncio
    import hashlib
    from collections import deque

    from constdb_tpu.resp.codec import make_parser

    inflight_cap = int(os.environ.get("CONSTDB_BENCH_SERVE_INFLIGHT", 2048))

    async def one(chunks, sink, digest):
        reader, writer = await asyncio.open_connection("127.0.0.1", port)
        parser = make_parser()
        clock = time.perf_counter
        marks: deque = deque()  # (cumulative reply count, send ts)
        total = sum(n for _, n in chunks)
        got = 0
        progressed = asyncio.Event()

        async def pump():
            sent = 0
            for data, n in chunks:
                # bounded in-flight window: keeps the pipeline deep
                # enough to saturate the server without the unbounded
                # queueing that would turn reply latency into a pure
                # benchmark artifact
                while sent - got > inflight_cap:
                    progressed.clear()
                    await progressed.wait()
                sent += n
                marks.append((sent, clock()))
                writer.write(data)
                await writer.drain()

        ptask = asyncio.ensure_future(pump())
        try:
            while got < total:
                b = await reader.read(1 << 16)
                if not b:
                    raise ConnectionError("server EOF")
                digest.update(b)
                parser.feed(b)
                while parser.next_msg() is not None:
                    got += 1
                progressed.set()
                now = clock()
                while marks and marks[0][0] <= got:
                    sink.append(now - marks.popleft()[1])
            await ptask
        finally:
            ptask.cancel()
            writer.close()

    digests = [hashlib.sha256() for _ in per_conn]
    sinks = [[] for _ in per_conn]
    await asyncio.gather(*(one(c, s, d) for c, s, d
                           in zip(per_conn, sinks, digests)))
    for s in sinks:
        rtts.extend(s)
    hashes.extend(d.hexdigest() for d in digests)


def _serve_leg(serve_batch: int, engine_kind: str, per_conn: list,
               serve_shards: int = 1, aof_policy=None, aof_dir: str = "",
               read_cache_mb=None):
    """One full serve-bench leg: fork a server, drive the workload,
    collect (wall_s, rtts, reply_hashes, canonical, server_stats)."""
    import asyncio
    import multiprocessing as mp

    ctx = mp.get_context("fork")
    parent, child = ctx.Pipe()
    # a shard-serving leg spawns its own worker children, which a
    # daemonic process may not — those legs run non-daemonic with an
    # explicit terminate guard instead
    p = ctx.Process(target=_serve_bench_server,
                    args=(child, serve_batch, engine_kind, serve_shards,
                          aof_policy, aof_dir, read_cache_mb),
                    daemon=serve_shards <= 1)
    p.start()
    child.close()
    try:
        port = parent.recv()
        if isinstance(port, BaseException):
            raise port
        rtts: list = []
        hashes: list = []
        t0 = time.perf_counter()
        asyncio.run(_serve_drive(port, per_conn, rtts, hashes))
        wall = time.perf_counter() - t0
        parent.send("stop")
        result = parent.recv()
        p.join()
        parent.close()
        if isinstance(result, BaseException):
            raise result
    except BaseException:
        if p.is_alive():
            p.terminate()
            p.join(timeout=5)
        raise
    canon, stats = result
    return wall, rtts, hashes, canon, stats


def serve_main(args) -> None:
    """`bench.py --mode serve`: coalesced pipelined client serving vs the
    exact per-command path (CONSTDB_SERVE_BATCH=1), same deterministic
    workload over real sockets, interleaved best-of-N, oracle-compared
    (reply streams per connection + visible-value export).  Emits ONE
    JSON line with requests/s and p50/p99 pipeline-window reply
    latency."""
    n_ops = int(os.environ.get("CONSTDB_BENCH_SERVE_OPS", 200_000))
    n_conns = int(os.environ.get("CONSTDB_BENCH_SERVE_CONNS", 4))
    pipeline = int(os.environ.get("CONSTDB_BENCH_SERVE_PIPELINE", 64))
    n_keys = int(os.environ.get("CONSTDB_BENCH_SERVE_KEYS", 2000))
    serve_batch = int(os.environ.get("CONSTDB_BENCH_SERVE_BATCH", 512))
    engine_kind = os.environ.get("CONSTDB_BENCH_SERVE_ENGINE", "cpu")
    reps = int(os.environ.get("CONSTDB_BENCH_SERVE_REPS", 2))

    ensure_native()
    per_ops = n_ops // n_conns
    t0 = time.perf_counter()
    per_conn = [serve_workload(ci, per_ops, n_keys, pipeline)
                for ci in range(n_conns)]
    total = per_ops * n_conns
    print(f"[bench] serve workload: {total} ops over {n_conns} conns x "
          f"{pipeline}-deep pipelines ({time.perf_counter() - t0:.1f}s gen)",
          file=sys.stderr)

    best = {True: None, False: None}  # coalesced? -> leg result
    for rep in range(reps):
        for coalesced in (True, False):
            leg = _serve_leg(serve_batch if coalesced else 1,
                             engine_kind, per_conn)
            tag = f"serve_batch={serve_batch if coalesced else 1}"
            print(f"[bench] rep {rep + 1} {tag}: {leg[0]:.3f}s = "
                  f"{total / leg[0]:,.0f} req/s", file=sys.stderr)
            if best[coalesced] is None or leg[0] < best[coalesced][0]:
                best[coalesced] = leg
    wall, rtts, hashes, canon, stats = best[True]
    bwall, _brtts, bhashes, bcanon, bstats = best[False]
    rps = total / wall
    base_rps = total / bwall
    lat_ms = np.asarray(rtts) * 1000.0
    p50, p99 = (float(np.percentile(lat_ms, q)) for q in (50, 99))

    replies_ok = hashes == bhashes
    export_ok = strip_canonical_times(canon) == strip_canonical_times(bcanon)
    verified = replies_ok and export_ok
    print(f"[bench] coalesced: {rps:,.0f} req/s vs per-command "
          f"{base_rps:,.0f} req/s = {rps / base_rps:.2f}x; reply-window "
          f"p50 {p50:.2f}ms p99 {p99:.2f}ms; "
          f"{stats['serve_msgs_coalesced']} coalesced / "
          f"{stats['serve_flushes']} flushes / "
          f"{stats['serve_barriers']} barriers", file=sys.stderr)
    print(f"[bench] verify: replies {'OK' if replies_ok else 'MISMATCH'} "
          f"({len(hashes)} conns), export "
          f"{'OK' if export_ok else 'MISMATCH'} ({len(canon)} keys)",
          file=sys.stderr)

    out = {
        "metric": "serve_requests_per_sec",
        "value": round(rps, 1),
        "unit": "requests/sec",
        "mode": "serve",
        "ops": total,
        "conns": n_conns,
        "pipeline": pipeline,
        "wall_s": round(wall, 3),
        "per_command_baseline_rps": round(base_rps, 1),
        "vs_per_command": round(rps / base_rps, 2),
        "reply_p50_ms": round(p50, 3),
        "reply_p99_ms": round(p99, 3),
        "serve_batch": serve_batch,
        "serve_msgs_coalesced": stats["serve_msgs_coalesced"],
        "serve_flushes": stats["serve_flushes"],
        "serve_barriers": stats["serve_barriers"],
        "engine": engine_kind,
        "verified": verified,
        "host": host_fingerprint(),
    }
    print(json.dumps(out))
    if not verified:
        sys.exit(1)


def serve_read_workload(conn_id: int, n_ops: int, n_keys: int,
                        pipeline: int, read_pct: int,
                        seed: int = 17) -> list:
    """Pre-encoded pipelined chunks for one connection at a given
    read percentage: reads hit a HOT subset of this connection's own
    single-writer keys (the canonical cache-serving shape — and what
    keeps both reply streams and final per-key values
    interleave-invariant for the cross-leg oracle), spread across every
    planned read kind; writes keep the serve-workload mix so
    invalidation is exercised for real."""
    import random

    from constdb_tpu.resp.codec import encode_into
    from constdb_tpu.resp.message import Arr, Bulk

    rng = random.Random(seed * 1000 + conn_id)
    pfx = b"c%d:" % conn_id
    rfrac = read_pct / 100.0
    # every key is seeded (4 ops each), so clamp the universe to keep
    # the seeding preamble under ~25% of the op budget (smoke-sized
    # runs shrink the keyspace instead of starving the steady state)
    n_keys = max(8, min(n_keys, n_ops // 16))
    hot = max(8, n_keys // 50)
    chunks = []
    cur = bytearray()
    n = 0
    ops = []
    # seeding preamble: populate EVERY key's families first, so the
    # read-heavy steady state reads DATA, not absence — a cache serving
    # millions of users reads keys that exist, on the cold tail too
    # (cold sets/hashes get a smaller footprint than the hot ones)
    for kid in range(n_keys):
        k = pfx + b"%05d" % kid
        step = 3 if kid < hot else 13
        ops.append((b"set", b"r" + k, b"v%08d" % kid))
        ops.append((b"sadd", b"s" + k,
                    *(b"m%03d" % m for m in range(0, 64, step))))
        fv = []
        for f in range(10 if kid < hot else 3):
            fv += [b"f%02d" % f, b"v%06d" % (kid * 10 + f)]
        ops.append((b"hset", b"h" + k, *fv))
        ops.append((b"incr", b"c" + k, b"%d" % (kid + 1)))
    for body in ops:
        encode_into(cur, Arr([Bulk(b) for b in body]))
        n += 1
        if n >= pipeline:
            chunks.append((bytes(cur), n))
            cur = bytearray()
            n = 0
    for i in range(max(0, n_ops - len(ops))):
        kid = rng.randrange(hot) if rng.random() < 0.85 \
            else rng.randrange(n_keys)
        k = pfx + b"%05d" % kid
        if rng.random() < rfrac:
            q = rng.random()
            if q < 0.40:
                body = (b"get", b"r" + k)
            elif q < 0.55:
                body = (b"smembers", b"s" + k)
            elif q < 0.65:
                body = (b"scnt", b"s" + k)
            elif q < 0.75:
                body = (b"sismember", b"s" + k,
                        b"m%03d" % rng.randrange(64))
            elif q < 0.85:
                body = (b"hget", b"h" + k, b"f%02d" % rng.randrange(10))
            elif q < 0.93:
                body = (b"hgetall", b"h" + k)
            else:
                body = (b"get", b"c" + k)   # counter read
        else:
            q = rng.random()
            if q < 0.35:
                body = (b"set", b"r" + k, b"v%08d" % i)
            elif q < 0.55:
                body = (b"incr", b"c" + k, b"%d" % rng.randrange(1, 100))
            elif q < 0.80:
                body = (b"sadd", b"s" + k,
                        *(b"m%03d" % rng.randrange(64) for _ in range(4)))
            else:
                fv = []
                for f in range(4):
                    fv += [b"f%02d" % rng.randrange(10),
                           b"v%06d%d" % (i, f)]
                body = (b"hset", b"h" + k, *fv)
        encode_into(cur, Arr([Bulk(b) for b in body]))
        n += 1
        if n >= pipeline:
            chunks.append((bytes(cur), n))
            cur = bytearray()
            n = 0
    if n:
        chunks.append((bytes(cur), n))
    return chunks


def serve_read_main(args) -> None:
    """`bench.py --mode serve --read-pct 90[,50]`: the read-heavy
    serving legs (round 18).  For each read percentage, three
    interleaved best-of-N legs on the same deterministic workload over
    real sockets — coalesced+cache, coalesced with the cache disabled,
    and the CONSTDB_SERVE_BATCH=1 per-command baseline — with the
    reply-hash + timestamp-stripped-export oracle across ALL legs (a
    stale cached reply is an oracle mismatch, not a slowdown).  Emits
    one JSON line (BENCH_r18.json) with the per-pct curve and host
    fingerprint."""
    n_ops = int(os.environ.get("CONSTDB_BENCH_SERVE_OPS", 200_000))
    n_conns = int(os.environ.get("CONSTDB_BENCH_SERVE_CONNS", 4))
    pipeline = int(os.environ.get("CONSTDB_BENCH_SERVE_PIPELINE", 64))
    # smaller default universe than the write-heavy mode: every key is
    # seeded (the cold tail reads DATA, not absence), so the universe
    # bounds the seeding preamble's share of the measured ops
    n_keys = int(os.environ.get("CONSTDB_BENCH_SERVE_KEYS", 1000))
    serve_batch = int(os.environ.get("CONSTDB_BENCH_SERVE_BATCH", 512))
    engine_kind = os.environ.get("CONSTDB_BENCH_SERVE_ENGINE", "cpu")
    reps = int(os.environ.get("CONSTDB_BENCH_SERVE_REPS", 2))
    cache_mb = int(os.environ.get("CONSTDB_BENCH_READ_CACHE_MB", 16))
    pcts = [int(p) for p in str(args.read_pct).split(",")]

    ensure_native()
    per_ops = n_ops // n_conns
    total = per_ops * n_conns
    curve = []
    verified = True
    for pct in pcts:
        per_conn = [serve_read_workload(ci, per_ops, n_keys, pipeline,
                                        pct) for ci in range(n_conns)]
        print(f"[bench] read-pct {pct}: {total} ops over {n_conns} "
              f"conns x {pipeline}-deep pipelines", file=sys.stderr)
        # leg key -> (serve_batch, read_cache_mb)
        legs = {"cache": (serve_batch, cache_mb),
                "nocache": (serve_batch, 0),
                "percmd": (1, 0)}
        best: dict = {k: None for k in legs}
        for rep in range(reps):
            for name, (sb, mb) in legs.items():
                leg = _serve_leg(sb, engine_kind, per_conn,
                                 read_cache_mb=mb)
                print(f"[bench] rep {rep + 1} {pct}r {name}: "
                      f"{leg[0]:.3f}s = {total / leg[0]:,.0f} req/s",
                      file=sys.stderr)
                if best[name] is None or leg[0] < best[name][0]:
                    best[name] = leg
        ref = best["percmd"]
        ref_strip = strip_canonical_times(ref[3])
        entry = {"read_pct": pct}
        ok_all = True
        for name in legs:
            wall, rtts, hashes, canon, stats = best[name]
            ok = hashes == ref[2] and \
                strip_canonical_times(canon) == ref_strip
            ok_all = ok_all and ok
            lat_ms = np.asarray(rtts) * 1000.0
            entry[name] = {
                "rps": round(total / wall, 1),
                "wall_s": round(wall, 3),
                "reply_p50_ms": round(float(np.percentile(lat_ms, 50)), 3),
                "reply_p99_ms": round(float(np.percentile(lat_ms, 99)), 3),
                "serve_reads_coalesced": stats["serve_reads_coalesced"],
                "serve_read_flushes": stats["serve_read_flushes"],
                "read_cache_hits": stats["read_cache_hits"],
                "read_cache_misses": stats["read_cache_misses"],
                "read_cache_bytes": stats["read_cache_bytes"],
                "read_cache_invalidations":
                    stats["read_cache_invalidations"],
                "replies_ok": hashes == ref[2],
            }
        entry["speedup_vs_percmd"] = round(
            entry["cache"]["rps"] / entry["percmd"]["rps"], 2)
        entry["speedup_nocache_vs_percmd"] = round(
            entry["nocache"]["rps"] / entry["percmd"]["rps"], 2)
        hits = entry["cache"]["read_cache_hits"]
        probes = hits + entry["cache"]["read_cache_misses"]
        entry["cache_hit_rate"] = round(hits / probes, 3) if probes else 0.0
        entry["verified"] = ok_all
        verified = verified and ok_all
        print(f"[bench] read-pct {pct}: cache {entry['cache']['rps']:,.0f}"
              f" / nocache {entry['nocache']['rps']:,.0f} / per-command "
              f"{entry['percmd']['rps']:,.0f} req/s = "
              f"{entry['speedup_vs_percmd']}x (hit rate "
              f"{entry['cache_hit_rate']}); oracle "
              f"{'OK' if ok_all else 'MISMATCH'}", file=sys.stderr)
        curve.append(entry)

    out = {
        "metric": "serve_read_requests_per_sec",
        "value": curve[0]["cache"]["rps"],
        "unit": "requests/sec",
        "mode": "serve-read",
        "host_note": "burstable 1-core box: client and server share the "
                     "core, so CPU-credit state swings the 90:10 ratio "
                     "1.76-2.11x across invocations of this exact "
                     "interleaved best-of-N leg (all oracle-verified); "
                     "a box with dedicated cores isolates the server-side "
                     "win from the shared client cost",
        "ops": total,
        "conns": n_conns,
        "pipeline": pipeline,
        "serve_batch": serve_batch,
        "read_cache_mb": cache_mb,
        "curve": curve,
        "engine": engine_kind,
        "verified": verified,
        "host": host_fingerprint(),
    }
    print(json.dumps(out))
    if not verified:
        sys.exit(1)


def tracked_workload(ci: int, n_clients: int, per_ops: int, n_keys: int,
                     hot: int, seed: int = 0xC0FFEE) -> list:
    """Deterministic per-client schedule for the tracked-caching legs
    (round 22): 10% writes / 90% reads, with 90% of reads hammering the
    `hot` head of the universe (the skew that makes a near-cache earn
    its keep) and the tail uniform.  Writes are SINGLE-WRITER: client
    `ci` only ever sets keys where `idx % n_clients == ci`, each with a
    per-key serial — so the final visible value of every key is
    schedule-determined and the stripped canonical export must match
    exactly across legs (same oracle as the serve modes)."""
    import random

    rng = random.Random((seed << 8) | ci)
    owned = [i for i in range(n_keys) if i % n_clients == ci]
    serial: dict = {}
    sched = []
    for _ in range(per_ops):
        r = rng.random()
        if r < 0.10:
            idx = owned[rng.randrange(len(owned))]
            serial[idx] = serial.get(idx, 0) + 1
            sched.append((b"set", b"trk:%d" % idx,
                          b"c%d:%d" % (ci, serial[idx])))
        elif r < 0.91:
            sched.append((b"get", b"trk:%d" % rng.randrange(hot), None))
        else:
            sched.append((b"get", b"trk:%d" % rng.randrange(n_keys), None))
    return sched


async def _tracked_leg(tracked: bool, schedules: list, n_keys: int,
                       work_dir: str) -> tuple:
    """One in-process leg: a fresh single node on a real socket, K
    concurrent request-reply clients driving their schedules — plain
    RESP2 clients (every GET is a server round-trip) or tracked RESP3
    `NearCacheClient`s (a quiet-key GET never leaves the process).
    In-process (unlike `_serve_leg`'s fork) because the headline metric
    is the SERVER-side read-op count, read straight off the node's
    `cmds_processed` gauge (bumped once per client command on both the
    per-command and planned paths): the storm's delta minus its write
    count IS the reads that reached the server.  Returns
    (wall, counters, canonical-export)."""
    from constdb_tpu.chaos.cluster import Client
    from constdb_tpu.client import NearCacheClient
    from constdb_tpu.resp.message import Err
    from constdb_tpu.server.io import start_node
    from constdb_tpu.server.node import Node

    node = Node(node_id=1)
    app = await start_node(node, host="127.0.0.1", port=0,
                           work_dir=work_dir)
    addr = app.advertised_addr
    direct = await Client().connect(addr)
    try:
        # seed every key: the cold tail reads DATA, and both legs start
        # from the same per-key write history (seed, then owner serials)
        for i in range(n_keys):
            await direct.cmd(b"set", b"trk:%d" % i, b"seed:%d" % i)
        if tracked:
            clients = [await NearCacheClient(addr).connect()
                       for _ in schedules]
        else:
            clients = [await Client().connect(addr) for _ in schedules]
        n_writes = sum(1 for s in schedules for op, _k, _v in s
                       if op == b"set")
        cmds0 = node.stats.cmds_processed

        async def drive(c, sched):
            for op, k, v in sched:
                if op == b"set":
                    r = await (c.set(k, v) if tracked
                               else c.cmd(b"set", k, v))
                else:
                    r = await (c.get(k) if tracked else c.cmd(b"get", k))
                if isinstance(r, Err):
                    raise AssertionError(f"leg reply error: {r.val!r}")

        t0 = time.perf_counter()
        await asyncio.gather(*(drive(c, s)
                               for c, s in zip(clients, schedules)))
        wall = time.perf_counter() - t0
        # snapshot BEFORE the zero-stale oracle's direct reads below —
        # those are measurement traffic, not workload
        server_read_ops = node.stats.cmds_processed - cmds0 - n_writes
        stale = 0
        if tracked:
            # quiesce past the coalescing window, then the zero-stale
            # oracle: every entry still resident in every near-cache
            # must equal a direct read from the server
            await asyncio.sleep(0.3)
            for c in clients:
                await asyncio.sleep(0)
                for k, v in list(c.cache.items()):
                    if await direct.cmd(b"get", k) != v:
                        stale += 1
        st = node.stats
        counters = {
            "server_read_ops": server_read_ops,
            "stale_entries": stale,
            "tracking_invalidations_sent": st.tracking_invalidations_sent,
            "tracking_pushes": st.tracking_pushes,
            "tracking_demotions": st.tracking_demotions,
            "near_cache_hits": sum(getattr(c, "hits", 0)
                                   for c in clients),
            "near_cache_misses": sum(getattr(c, "misses", 0)
                                     for c in clients),
            "near_cache_invalidations": sum(
                getattr(c, "invalidations", 0) for c in clients),
            "near_cache_flushes": sum(getattr(c, "flushes", 0)
                                      for c in clients),
        }
        canon = app.node.canonical()
        for c in clients:
            await c.close()
        return wall, counters, canon
    finally:
        await direct.close()
        await app.close()


def tracked_main(args) -> None:
    """`bench.py --mode tracked`: the client-assisted-caching legs
    (round 22).  K tracked RESP3 near-cache clients vs K plain clients
    on the SAME deterministic hot-key 90:10 storm; the claim is
    server-side — the tracked leg's reads that actually reach the
    server must be >= 5x fewer — certified by the zero-stale oracle
    (every resident near-cache entry equals a direct read at quiesce)
    and the timestamp-stripped canonical export matching across legs.
    Emits one JSON line (BENCH_r22.json) with the host fingerprint."""
    import tempfile

    n_ops = int(os.environ.get("CONSTDB_BENCH_TRACKED_OPS", 40_000))
    n_clients = int(os.environ.get("CONSTDB_BENCH_TRACKED_CLIENTS", 4))
    n_keys = int(os.environ.get("CONSTDB_BENCH_TRACKED_KEYS", 512))
    hot = int(os.environ.get("CONSTDB_BENCH_TRACKED_HOT", 16))
    reps = int(os.environ.get("CONSTDB_BENCH_TRACKED_REPS", 2))
    floor = float(os.environ.get("CONSTDB_BENCH_TRACKED_FLOOR", 5.0))

    ensure_native()
    per_ops = n_ops // n_clients
    total = per_ops * n_clients
    schedules = [tracked_workload(ci, n_clients, per_ops, n_keys, hot)
                 for ci in range(n_clients)]
    n_reads = sum(1 for s in schedules for op, _k, _v in s
                  if op == b"get")
    print(f"[bench] tracked: {total} ops ({n_reads} reads) over "
          f"{n_clients} clients, {n_keys} keys (hot {hot})",
          file=sys.stderr)

    best: dict = {"tracked": None, "plain": None}
    for rep in range(reps):
        for name, is_tracked in (("plain", False), ("tracked", True)):
            with tempfile.TemporaryDirectory() as td:
                leg = asyncio.run(_tracked_leg(is_tracked, schedules,
                                               n_keys, td))
            print(f"[bench] rep {rep + 1} {name}: {leg[0]:.3f}s = "
                  f"{total / leg[0]:,.0f} op/s, "
                  f"{leg[1]['server_read_ops']} server reads",
                  file=sys.stderr)
            if best[name] is None or leg[0] < best[name][0]:
                best[name] = leg

    plain, tracked = best["plain"], best["tracked"]
    reduction = plain[1]["server_read_ops"] / \
        max(1, tracked[1]["server_read_ops"])
    hits = tracked[1]["near_cache_hits"]
    hit_rate = hits / max(1, hits + tracked[1]["near_cache_misses"])
    export_ok = strip_canonical_times(plain[2]) == \
        strip_canonical_times(tracked[2])
    verified = (export_ok
                and tracked[1]["stale_entries"] == 0
                and tracked[1]["tracking_invalidations_sent"] > 0
                and tracked[1]["tracking_demotions"] == 0
                and reduction >= floor)
    print(f"[bench] tracked: {plain[1]['server_read_ops']} -> "
          f"{tracked[1]['server_read_ops']} server reads = "
          f"{reduction:.1f}x reduction (floor {floor}x), hit rate "
          f"{hit_rate:.3f}; export {'OK' if export_ok else 'MISMATCH'}, "
          f"{tracked[1]['stale_entries']} stale", file=sys.stderr)

    out = {
        "metric": "tracked_server_read_reduction",
        "value": round(reduction, 2),
        "unit": "x fewer server-side reads",
        "mode": "tracked",
        "host_note": "in-process legs (client+server share the box): "
                     "the op-count reduction is load-independent, the "
                     "op/s walls are not",
        "ops": total,
        "reads": n_reads,
        "clients": n_clients,
        "keys": n_keys,
        "hot_keys": hot,
        "plain": {"op_per_s": round(total / plain[0], 1),
                  "wall_s": round(plain[0], 3),
                  **plain[1]},
        "tracked": {"op_per_s": round(total / tracked[0], 1),
                    "wall_s": round(tracked[0], 3),
                    "near_cache_hit_rate": round(hit_rate, 3),
                    **tracked[1]},
        "export_ok": export_ok,
        "verified": verified,
        "host": host_fingerprint(),
    }
    print(json.dumps(out))
    if not verified:
        sys.exit(1)


def serve_aof_main(args) -> None:
    """`bench.py --mode serve --aof`: the durability legs — the SAME
    pipelined serve workload against AOF-off / everysec / always
    servers, interleaved best-of-N, visible-value exports verified
    identical across legs, so the fsync tax is measured, not guessed.
    The `always` leg's produced log is then REPLAYED through the real
    recovery path (persist/oplog.py) with the replayed export verified
    against the leg's, yielding recovery seconds per GB of log."""
    import shutil
    import tempfile

    n_ops = int(os.environ.get("CONSTDB_BENCH_AOF_OPS", 60_000))
    n_conns = int(os.environ.get("CONSTDB_BENCH_SERVE_CONNS", 4))
    pipeline = int(os.environ.get("CONSTDB_BENCH_SERVE_PIPELINE", 64))
    n_keys = int(os.environ.get("CONSTDB_BENCH_SERVE_KEYS", 2000))
    serve_batch = int(os.environ.get("CONSTDB_BENCH_SERVE_BATCH", 512))
    engine_kind = os.environ.get("CONSTDB_BENCH_SERVE_ENGINE", "cpu")
    reps = int(os.environ.get("CONSTDB_BENCH_AOF_REPS", 2))

    ensure_native()
    per_ops = n_ops // n_conns
    per_conn = [serve_workload(ci, per_ops, n_keys, pipeline)
                for ci in range(n_conns)]
    total = per_ops * n_conns
    print(f"[bench] aof workload: {total} ops over {n_conns} conns x "
          f"{pipeline}-deep pipelines", file=sys.stderr)

    policies = (None, "everysec", "always")
    best: dict = {p: None for p in policies}
    best_dir: dict = {p: "" for p in policies}
    root = tempfile.mkdtemp(prefix="constdb-aofbench-")
    try:
        for rep in range(reps):
            for pol in policies:
                aof_dir = os.path.join(root, f"{pol}-{rep}") if pol \
                    else ""
                leg = _serve_leg(serve_batch, engine_kind, per_conn,
                                 aof_policy=pol, aof_dir=aof_dir)
                tag = pol or "off"
                print(f"[bench] rep {rep + 1} aof={tag}: {leg[0]:.3f}s "
                      f"= {total / leg[0]:,.0f} req/s "
                      f"({leg[4]['aof_size_bytes']} log bytes, "
                      f"{leg[4]['aof_fsyncs']} fsyncs)", file=sys.stderr)
                if best[pol] is None or leg[0] < best[pol][0]:
                    best[pol] = leg
                    best_dir[pol] = aof_dir

        off = best[None]
        stripped_off = strip_canonical_times(off[3])
        legs_out = []
        verified = True
        for pol in policies:
            wall, rtts, hashes, canon, stats = best[pol]
            ok = hashes == off[2] and \
                strip_canonical_times(canon) == stripped_off
            verified = verified and ok
            lat_ms = np.asarray(rtts) * 1000.0
            legs_out.append({
                "aof": pol or "off",
                "rps": round(total / wall, 1),
                "wall_s": round(wall, 3),
                "vs_off": round(off[0] / wall, 3),
                "reply_p50_ms": round(float(np.percentile(lat_ms, 50)), 3),
                "reply_p99_ms": round(float(np.percentile(lat_ms, 99)), 3),
                "aof_size_bytes": stats["aof_size_bytes"],
                "aof_fsyncs": stats["aof_fsyncs"],
                "aof_encoded_batches": stats["aof_encoded_batches"],
                "replies_ok": hashes == off[2],
            })

        # recovery replay of the `always` leg's log, timed (the real
        # boot path: persist/oplog.py recover through the merge engine)
        from constdb_tpu.persist import oplog as OL
        from constdb_tpu.server.node import Node as _Node
        rec_dir = best_dir["always"]
        log_bytes = sum(
            os.path.getsize(os.path.join(rec_dir, f))
            for f in os.listdir(rec_dir) if f.endswith(".log"))
        t0 = time.perf_counter()
        rnode = _Node(node_id=1, alias="recover")
        info = OL.recover(rnode, rec_dir)
        rec_wall = time.perf_counter() - t0
        # GC-invariant oracle: replayed visible values == the leg's
        for _ in range(64):
            rnode.gc()
            if not rnode.ks.garbage:
                break
        recov_ok = {k: v for k, v in
                    strip_canonical_times(rnode.canonical()).items()
                    if v[1]} == \
            {k: v for k, v in
             strip_canonical_times(best["always"][3]).items() if v[1]}
        verified = verified and recov_ok
        rec_per_gb = rec_wall / max(log_bytes / 1e9, 1e-9)
        print(f"[bench] recovery: {info.frames + info.batch_frames} ops "
              f"from {log_bytes} log bytes in {rec_wall:.3f}s = "
              f"{rec_per_gb:,.1f} s/GB; replay "
              f"{'OK' if recov_ok else 'MISMATCH'}", file=sys.stderr)

        out = {
            "metric": "serve_aof_everysec_vs_off",
            "value": legs_out[1]["vs_off"],
            "unit": "ratio",
            "mode": "serve-aof",
            "ops": total,
            "conns": n_conns,
            "pipeline": pipeline,
            "legs": legs_out,
            "recovery_wall_s": round(rec_wall, 3),
            "recovery_log_bytes": log_bytes,
            "recovery_s_per_gb": round(rec_per_gb, 2),
            "recovery_ops": info.frames + info.batch_frames,
            "recovery_verified": recov_ok,
            "engine": engine_kind,
            "verified": verified,
            "host": host_fingerprint(),
        }
        print(json.dumps(out))
        if not verified:
            sys.exit(1)
    finally:
        shutil.rmtree(root, ignore_errors=True)


# ---------------------------------------------------------------------------
# --mode recover: fast restart (BENCH_r20).  Recovery s/GB legs over the
# SAME always-fsync log — serial per-record reference vs bulk merge rounds
# vs concurrent per-shard segment replay vs a checkpointed tail — with the
# never-crashed leg's visible values as the oracle and byte-identity
# (canonical + full-state digest) required between serial and bulk.
# ---------------------------------------------------------------------------


def _recover_leg(aof_dir: str, bulk: bool, reps: int):
    """Timed in-process boot replays of one log dir (the real
    persist/oplog.py recover path); returns the best-of-reps
    (wall, node, info) with GC drained for the visible-value oracle.

    The timed region runs with the pre-existing heap FROZEN out of the
    cyclic collector: a real boot replays into a near-empty process,
    but by the time this leg runs the bench process retains every
    earlier leg's oracle state, and collector passes triggered inside
    the replay would scan that unrelated heap — inflating whichever
    leg happens to allocate more and drowning the s/GB signal."""
    import gc

    from constdb_tpu.persist import oplog as OL
    from constdb_tpu.server.node import Node as _Node

    best = None
    for _ in range(reps):
        node = _Node(node_id=1, alias="recover")
        gc.collect()
        gc.freeze()
        try:
            t0 = time.perf_counter()
            info = OL.recover(node, aof_dir, bulk=bulk)
            wall = time.perf_counter() - t0
        finally:
            gc.unfreeze()
        if best is None or wall < best[0]:
            best = (wall, node, info)
    wall, node, info = best
    _gc_drain(node)
    return wall, node, info


def _recover_pair(aof_dir: str, reps: int):
    """Serial and bulk legs with INTERLEAVED reps (serial, bulk, serial,
    bulk, ...): burstable builder hosts throttle over a run, so timing
    all serial reps before all bulk reps hands whichever leg goes first
    the faster CPU state and skews the ratio.  Returns the two
    best-of-reps (wall, node, info) triples."""
    s = b = None
    for _ in range(reps):
        sw = _recover_leg(aof_dir, False, 1)
        bw = _recover_leg(aof_dir, True, 1)
        if s is None or sw[0] < s[0]:
            s = sw
        if b is None or bw[0] < b[0]:
            b = bw
    return s, b


def _alive_values(canon: dict) -> dict:
    """The GC-invariant recovery oracle projection (see serve_aof_main):
    visible values of live keys only."""
    return {k: v for k, v in strip_canonical_times(canon).items() if v[1]}


def _gc_drain(node) -> None:
    for _ in range(64):
        node.gc()
        if not node.ks.garbage:
            break


def _frame_log_build(aof_dir: str, n_ops: int, n_keys: int):
    """Drive the exact single-loop command path with an armed op log:
    every write mirrors per-frame (Node.replicate_cmd ->
    OpLog.append_local), the REC_FRAME-heavy log shape that
    interactive shallow-pipeline traffic produces — the log where the
    serial replay reference is genuinely one apply per record.
    Returns the live node (GC-drained) as the never-crashed
    reference."""
    import random

    from constdb_tpu.persist import oplog as OL
    from constdb_tpu.resp.message import Arr, Bulk
    from constdb_tpu.server.node import Node as _Node

    rng = random.Random(1307)
    node = _Node(node_id=1, alias="framelog")
    lg = OL.OpLog(aof_dir, fsync_policy="no", node=node)
    node.oplog = lg
    for i in range(n_ops):
        r = rng.random()
        k = b"%05d" % rng.randrange(n_keys)
        if r < 0.25:
            body = (b"set", b"r" + k, b"v%08d" % i)
        elif r < 0.50:
            body = (b"incr", b"c" + k, b"%d" % rng.randrange(1, 100))
        elif r < 0.75:
            body = (b"sadd", b"s" + k,
                    *(b"m%03d" % rng.randrange(256) for _ in range(8)))
        elif r < 0.97:
            fv = []
            for f in range(10):
                fv += [b"f%02d" % rng.randrange(32), b"v%07d%d" % (i, f)]
            body = (b"hset", b"h" + k, *fv)
        elif r < 0.995:
            body = (b"srem", b"s" + k, b"m%03d" % rng.randrange(256))
        else:
            body = (b"del", b"r" + k)   # -> delbytes, columnar-encodable
        node.execute(Arr([Bulk(b) for b in body]))
    lg.close()
    node.oplog = None
    _gc_drain(node)
    return node


def _sharded_restart(aof_dir: str, recover_shards: int):
    """One in-process sharded restart over an existing per-shard log:
    boots the 2-shard plane with CONSTDB_RECOVER_SHARDS pinned, reads
    the recovery gauges, exports the consolidated canonical, closes.
    Returns (recovery_wall_s, gauges, alive-values projection)."""
    import asyncio

    from constdb_tpu.server.io import start_node
    from constdb_tpu.server.node import Node as _Node

    async def main():
        node = _Node(node_id=1, alias="rec")
        app = await start_node(node, host="127.0.0.1", port=0,
                               work_dir=os.path.dirname(aof_dir),
                               serve_shards=2, aof=True, aof_fsync="no",
                               aof_dir=aof_dir)
        try:
            x = node.stats.extra
            wall = x["recovery_wall_s"]
            gauges = {"recovery_mode": x["recovery_mode"],
                      "recovery_shards": x["recovery_shards"]}
            canon = await node.serve_plane.canonical()
        finally:
            await app.close()
        return wall, gauges, _alive_values(canon)

    os.environ["CONSTDB_RECOVER_SHARDS"] = str(recover_shards)
    try:
        return asyncio.run(main())
    finally:
        os.environ.pop("CONSTDB_RECOVER_SHARDS", None)


def _checkpoint_cut(src_dir: str, dst_dir: str, tail_ops: int) -> int:
    """Copy a log dir, run ONE incremental-checkpoint cut on the copy
    (the rewrite machinery recover_main's checkpointed-tail leg
    restarts from), then write a small post-cut tail of NEW keys over
    the socket — the restart must replay exactly that tail, nothing
    before the cut.  Returns the post-cut tail bytes."""
    import asyncio
    import shutil

    from constdb_tpu.chaos.cluster import Client
    from constdb_tpu.resp.codec import encode_msg
    from constdb_tpu.resp.message import Arr, Bulk
    from constdb_tpu.server.io import start_node
    from constdb_tpu.server.node import Node as _Node

    shutil.copytree(src_dir, dst_dir)

    async def main():
        node = _Node(node_id=1, alias="ckpt")
        app = await start_node(node, host="127.0.0.1", port=0,
                               work_dir=os.path.dirname(dst_dir),
                               aof=True, aof_fsync="no", aof_dir=dst_dir)
        try:
            await node.oplog.rewrite(app)
            assert node.oplog.checkpoint_uuid > 0
            c = await Client().connect(app.advertised_addr)
            try:
                buf = bytearray()
                for i in range(tail_ops):
                    buf += encode_msg(Arr([Bulk(b"SET"),
                                           Bulk(b"rtail:%d" % i),
                                           Bulk(b"tv%d" % i)]))
                c.writer.write(bytes(buf))
                await c.writer.drain()
                got = 0
                while got < tail_ops:
                    if c.parser.next_msg() is not None:
                        got += 1
                        continue
                    data = await asyncio.wait_for(
                        c.reader.read(1 << 16), 10.0)
                    assert data, "EOF mid-tail"
                    c.parser.feed(data)
            finally:
                c.writer.close()
            return node.oplog.size_bytes() - node.oplog.base_size
        finally:
            await app.close()

    return asyncio.run(main())


def recover_main(args) -> None:
    """`bench.py --mode recover`: the fast-restart curve — an
    always-fsync serve leg produces the log (its visible values are the
    never-crashed reference), then recovery replays it {serial
    per-record, bulk merge rounds, bulk + concurrent shard segments,
    checkpointed tail}, each timed as s/GB.  Serial and bulk must land
    byte-identical (canonical + full-state digest); every leg's alive
    values must equal the reference's."""
    import shutil
    import tempfile

    from constdb_tpu.store.digest import full_state_digest

    n_ops = int(os.environ.get("CONSTDB_BENCH_RECOVER_OPS", 60_000))
    n_conns = int(os.environ.get("CONSTDB_BENCH_SERVE_CONNS", 4))
    pipeline = int(os.environ.get("CONSTDB_BENCH_SERVE_PIPELINE", 64))
    n_keys = int(os.environ.get("CONSTDB_BENCH_SERVE_KEYS", 2000))
    serve_batch = int(os.environ.get("CONSTDB_BENCH_SERVE_BATCH", 512))
    engine_kind = os.environ.get("CONSTDB_BENCH_SERVE_ENGINE", "cpu")
    reps = int(os.environ.get("CONSTDB_BENCH_RECOVER_REPS", 3))

    ensure_native()
    per_ops = n_ops // n_conns
    per_conn = [serve_workload(ci, per_ops, n_keys, pipeline)
                for ci in range(n_conns)]
    total = per_ops * n_conns
    print(f"[bench] recover workload: {total} ops over {n_conns} conns x "
          f"{pipeline}-deep pipelines", file=sys.stderr)

    root = tempfile.mkdtemp(prefix="constdb-recbench-")
    try:
        # -- datasets: one unsharded always-fsync log + one 2-shard log
        flat_dir = os.path.join(root, "flat")
        leg = _serve_leg(serve_batch, engine_kind, per_conn,
                         aof_policy="always", aof_dir=flat_dir)
        live_vis = _alive_values(leg[3])
        log_bytes = sum(os.path.getsize(os.path.join(flat_dir, f))
                        for f in os.listdir(flat_dir)
                        if f.endswith(".log"))
        shard_dir = os.path.join(root, "shards")
        sleg = _serve_leg(serve_batch, engine_kind, per_conn,
                          serve_shards=2, aof_policy="always",
                          aof_dir=shard_dir)
        shard_vis = _alive_values(sleg[3])
        shard_bytes = sum(os.path.getsize(os.path.join(shard_dir, f))
                          for f in os.listdir(shard_dir)
                          if f.endswith(".log"))
        gb = max(log_bytes / 1e9, 1e-9)

        # -- serial reference vs bulk merge rounds, byte-identity bar
        (s_wall, s_node, s_info), (b_wall, b_node, b_info) = \
            _recover_pair(flat_dir, reps)
        s_canon, b_canon = s_node.canonical(), b_node.canonical()
        byte_identical = s_canon == b_canon and \
            full_state_digest(s_node.ks) == full_state_digest(b_node.ks)
        vis_ok = _alive_values(b_canon) == live_vis and \
            _alive_values(s_canon) == live_vis
        speedup = s_wall / b_wall
        print(f"[bench] batch log serial: {s_wall:.3f}s = "
              f"{s_wall / gb:,.1f} s/GB; "
              f"bulk: {b_wall:.3f}s = {b_wall / gb:,.1f} s/GB "
              f"({b_info.merge_rounds} rounds) -> {speedup:.2f}x; "
              f"byte-identical {'OK' if byte_identical else 'MISMATCH'}, "
              f"oracle {'OK' if vis_ok else 'MISMATCH'}", file=sys.stderr)

        # -- frame-record log (interactive shallow-pipeline shape): the
        # serial reference is genuinely one apply per record here, the
        # path the tentpole's s/GB bar is measured against.  The live
        # frame node itself is the never-crashed reference, and serial,
        # bulk and reference must agree byte-for-byte
        frame_dir = os.path.join(root, "frames")
        f_node = _frame_log_build(frame_dir, total, n_keys)
        f_canon = f_node.canonical()
        f_digest = full_state_digest(f_node.ks)
        frame_bytes = sum(os.path.getsize(os.path.join(frame_dir, f))
                          for f in os.listdir(frame_dir)
                          if f.endswith(".log"))
        fgb = max(frame_bytes / 1e9, 1e-9)
        (fs_wall, fs_node, fs_info), (fb_wall, fb_node, fb_info) = \
            _recover_pair(frame_dir, reps)
        frame_identical = \
            fs_node.canonical() == f_canon and \
            fb_node.canonical() == f_canon and \
            full_state_digest(fs_node.ks) == f_digest and \
            full_state_digest(fb_node.ks) == f_digest
        frame_speedup = fs_wall / fb_wall
        print(f"[bench] frame log ({frame_bytes} B): serial per-record: "
              f"{fs_wall:.3f}s = {fs_wall / fgb:,.1f} s/GB; bulk: "
              f"{fb_wall:.3f}s = {fb_wall / fgb:,.1f} s/GB "
              f"({fb_info.merge_rounds} rounds) -> {frame_speedup:.2f}x; "
              f"byte-identical "
              f"{'OK' if frame_identical else 'MISMATCH'}",
              file=sys.stderr)

        # -- shard curve: serial merged stream vs auto per-segment tasks
        sgb = max(shard_bytes / 1e9, 1e-9)
        shard_curve = []
        shards_ok = True
        for knob in (1, 0):
            wall, gauges, vis = _sharded_restart(shard_dir, knob)
            ok = vis == shard_vis
            shards_ok = shards_ok and ok
            shard_curve.append({
                "recover_shards_knob": knob,
                "recovery_wall_s": wall,
                "s_per_gb": round(wall / sgb, 2),
                **gauges,
                "verified": ok,
            })
            print(f"[bench] sharded restart knob={knob}: {wall:.3f}s "
                  f"({gauges['recovery_mode']}, "
                  f"{gauges['recovery_shards']} replay tasks); oracle "
                  f"{'OK' if ok else 'MISMATCH'}", file=sys.stderr)

        # -- checkpointed tail: one cut + a small post-cut tail, then a
        # timed restart that must replay ONLY the tail
        ckpt_dir = os.path.join(root, "ckpt")
        tail_n = max(64, total // 100)
        tail_bytes = _checkpoint_cut(flat_dir, ckpt_dir, tail_n)
        c_wall, c_node, c_info = _recover_leg(ckpt_dir, True, reps)
        full_ops = s_info.frames + s_info.batch_frames
        ckpt_ops = c_info.frames + c_info.batch_frames
        c_vis = _alive_values(c_node.canonical())
        # the tail only ADDS new keys: pre-cut acked state must survive
        # the cut byte-for-byte, and replay must stop at the tail
        ckpt_ok = all(c_vis.get(k) == v for k, v in live_vis.items()) \
            and c_vis.get(b"rtail:0") is not None \
            and 0 < ckpt_ops < full_ops
        print(f"[bench] checkpointed tail: {c_wall:.3f}s "
              f"({ckpt_ops} tail ops from {tail_bytes} tail bytes vs "
              f"{full_ops} full-log ops); oracle "
              f"{'OK' if ckpt_ok else 'MISMATCH'}", file=sys.stderr)

        verified = byte_identical and vis_ok and frame_identical \
            and shards_ok and ckpt_ok
        out = {
            "metric": "recovery_bulk_speedup_vs_serial",
            "value": round(frame_speedup, 2),
            "unit": "ratio",
            "mode": "recover",
            "host_note": "burstable 1-core box: the concurrent shard "
                         "legs cannot show a parallel wall-clock win "
                         "(every replay task shares the core, as in "
                         "BENCH_r19) — the curve still exercises and "
                         "gauge-records the per-segment concurrency; "
                         "the serial-vs-bulk ratios are core-count "
                         "independent (same process, same core).  The "
                         "headline ratio is the frame-record log (the "
                         "interactive shallow-pipeline shape, where "
                         "the serial reference is one apply per "
                         "record); the REPLBATCH log ratio rides in "
                         "legs[] — its records are already columnar, "
                         "so serial replay there is per-record only "
                         "in engine calls, not in python ops",
            "ops": total,
            "log_bytes": log_bytes,
            "frame_log_bytes": frame_bytes,
            "legs": [
                {"leg": "frames-serial", "wall_s": round(fs_wall, 3),
                 "s_per_gb": round(fs_wall / fgb, 2),
                 "ops": fs_info.frames + fs_info.batch_frames},
                {"leg": "frames-bulk", "wall_s": round(fb_wall, 3),
                 "s_per_gb": round(fb_wall / fgb, 2),
                 "merge_rounds": fb_info.merge_rounds,
                 "speedup_vs_serial": round(frame_speedup, 2),
                 "byte_identical": frame_identical},
                {"leg": "batch-serial", "wall_s": round(s_wall, 3),
                 "s_per_gb": round(s_wall / gb, 2),
                 "ops": s_info.frames + s_info.batch_frames},
                {"leg": "batch-bulk", "wall_s": round(b_wall, 3),
                 "s_per_gb": round(b_wall / gb, 2),
                 "merge_rounds": b_info.merge_rounds,
                 "speedup_vs_serial": round(speedup, 2),
                 "byte_identical": byte_identical},
                {"leg": "checkpointed-tail", "wall_s": round(c_wall, 3),
                 "tail_bytes": tail_bytes, "tail_ops": ckpt_ops,
                 "full_log_ops": full_ops},
            ],
            "shard_curve": shard_curve,
            "shard_log_bytes": shard_bytes,
            "engine": engine_kind,
            "verified": verified,
            "host": host_fingerprint(),
        }
        print(json.dumps(out))
        if not verified:
            sys.exit(1)
    finally:
        shutil.rmtree(root, ignore_errors=True)


# ---------------------------------------------------------------------------
# --mode intake: the native intake plane (BENCH_r19).  Serve legs with the
# C intake stage ON vs OFF (CONSTDB_NATIVE_INTAKE) plus the full-fallback
# CONSTDB_NO_NATIVE=1 leg, interleaved best-of-N, reply-stream + stripped-
# export oracle across ALL legs; wire legs time the REPLBATCH blob codec
# hot loops (native/wire.cpp) against the pure pack/unpack with encoded-
# byte identity as the oracle.
# ---------------------------------------------------------------------------


def _intake_wire_legs(reps: int = 3) -> dict:
    """In-process REPLBATCH codec legs: group-encode + decode a real
    repl-log entry stream with the native blob pack/unpack pinned OFF
    (pure Python) and ON, byte-identical encoded payloads required.
    Decode verifies via a column digest of every decoded batch."""
    import hashlib

    import constdb_tpu.replica.wire as W
    from constdb_tpu.server.node import Node

    n_frames = int(os.environ.get("CONSTDB_BENCH_INTAKE_FRAMES", 60_000))
    run_len = int(os.environ.get("CONSTDB_BENCH_WIRE_BATCH", 512))

    # a real encodable entry stream: plannable writes only, driven
    # through a live node so the entries are genuine LogEntry rows
    from constdb_tpu.resp.message import Arr, Bulk
    rng = np.random.default_rng(19)
    node = Node(node_id=1, alias="bench")
    for i in range(n_frames):
        k = b"w%d" % int(rng.integers(0, 4096))
        r = rng.random()
        if r < 0.40:
            body = (b"set", k, b"v%d" % i)
        elif r < 0.60:
            body = (b"incr", k + b":c")
        elif r < 0.80:
            body = (b"sadd", b"s" + k, b"m%d" % int(rng.integers(0, 64)))
        else:
            body = (b"hset", b"h" + k, b"f%d" % int(rng.integers(0, 16)),
                    b"v%d" % i)
        node.execute(Arr([Bulk(b) for b in body]))
    entries = list(node.repl_log._entries)
    runs = [entries[i:i + run_len]
            for i in range(0, len(entries), run_len)]

    def batch_digest(wb) -> bytes:
        h = hashlib.sha256()
        b = wb.batch
        for key in b.keys:
            h.update(key)
        for col in (b.key_enc, b.key_ct, b.key_mt, b.key_dt,
                    b.cnt_ki, b.cnt_val, b.cnt_uuid,
                    b.el_ki, b.el_add_t):
            h.update(np.ascontiguousarray(col).tobytes())
        for m in b.el_member:
            h.update(m or b"\0")
        return h.digest()

    def one_leg(native: bool):
        # pin the codec tier for this leg: [None] forces the pure
        # pack/unpack, a cleared cache re-resolves the extension
        W._WIRE_NATIVE_CACHE[:] = []
        if not native:
            W._WIRE_NATIVE_CACHE.append(None)
        enc_t = dec_t = 0.0
        payloads = []
        t0 = time.perf_counter()
        for run in runs:
            payloads.append(W.build_wire_batch(run, 1))
        enc_t = time.perf_counter() - t0
        assert all(p is not None for p in payloads), \
            "encodable run demoted during the wire bench"
        sink = Node(node_id=2, alias="sink")
        digests = []
        t0 = time.perf_counter()
        for run, payload in zip(runs, payloads):
            wb = W.decode_wire_batch(payload, sink.ks, 1,
                                     run[0].prev_uuid)
            digests.append(wb)
        dec_t = time.perf_counter() - t0
        digests = [batch_digest(wb) for wb in digests]
        return enc_t, dec_t, payloads, digests

    best = {True: None, False: None}
    oracle_ok = True
    for _rep in range(reps):
        for native in (True, False):
            enc_t, dec_t, payloads, digests = one_leg(native)
            cur = best[native]
            if cur is None or enc_t + dec_t < cur[0] + cur[1]:
                best[native] = (enc_t, dec_t, payloads, digests)
    W._WIRE_NATIVE_CACHE[:] = []  # leave the product tiering untouched
    n_enc, n_dec, n_pl, n_dg = best[True]
    p_enc, p_dec, p_pl, p_dg = best[False]
    oracle_ok = n_pl == p_pl and n_dg == p_dg
    frames = len(entries)
    return {
        "frames": frames,
        "runs": len(runs),
        "wire_batch": run_len,
        "payload_bytes": sum(len(p) for p in n_pl),
        "native": {"encode_s": round(n_enc, 4),
                   "decode_s": round(n_dec, 4),
                   "encode_frames_per_sec": round(frames / n_enc, 1),
                   "decode_frames_per_sec": round(frames / n_dec, 1)},
        "pure": {"encode_s": round(p_enc, 4),
                 "decode_s": round(p_dec, 4),
                 "encode_frames_per_sec": round(frames / p_enc, 1),
                 "decode_frames_per_sec": round(frames / p_dec, 1)},
        "encode_speedup": round(p_enc / n_enc, 2),
        "decode_speedup": round(p_dec / n_dec, 2),
        "verified": oracle_ok,
    }


def _intake_stage_legs(per_conn: list, reps: int = 3) -> dict:
    """The intake STAGE in isolation: split + classify + flatten a
    pipelined byte stream into ready-to-plan commands, C scanner
    (intake_scan via native_drain) vs the pure feed/drain-to-Msg loop.
    No planners, no merges — this measures exactly the Python the
    tentpole evicts; the end-to-end serve legs show what remains after
    the (shared) merge machinery floor."""
    from constdb_tpu.resp.codec import make_parser

    chunks = [data for conn in per_conn for data, _n in conn]
    total = sum(n for conn in per_conn for _data, n in conn)

    def native_leg() -> float:
        parser = make_parser()
        got = 0
        t0 = time.perf_counter()
        for data in chunks:
            parser.feed(data)
            while (nat := parser.native_drain()) is not None:
                got += len(nat[0])
            got += len(parser.drain())  # boundary remainders
        wall = time.perf_counter() - t0
        assert got == total, (got, total)
        return wall

    def pure_leg() -> float:
        parser = make_parser()
        got = 0
        t0 = time.perf_counter()
        for data in chunks:
            parser.feed(data)
            got += len(parser.drain())
        wall = time.perf_counter() - t0
        assert got == total, (got, total)
        return wall

    n_wall = min(native_leg() for _ in range(reps))
    p_wall = min(pure_leg() for _ in range(reps))
    return {
        "msgs": total,
        "native_msgs_per_sec": round(total / n_wall, 1),
        "pure_msgs_per_sec": round(total / p_wall, 1),
        "speedup": round(p_wall / n_wall, 2),
    }


def intake_main(args) -> None:
    """`bench.py --mode intake`: the native intake plane end to end
    (BENCH_r19).  Serve legs over real sockets — C intake stage vs the
    pure-Python drain path vs the CONSTDB_NO_NATIVE=1 full fallback —
    interleaved best-of-N on the same deterministic workload, reply
    byte streams + visible-value exports compared across every leg;
    the native leg must show `native_intake_chunks > 0`, the others
    exactly 0.  Emits ONE JSON line."""
    n_ops = int(os.environ.get("CONSTDB_BENCH_SERVE_OPS", 200_000))
    n_conns = int(os.environ.get("CONSTDB_BENCH_SERVE_CONNS", 4))
    pipeline = int(os.environ.get("CONSTDB_BENCH_SERVE_PIPELINE", 64))
    n_keys = int(os.environ.get("CONSTDB_BENCH_SERVE_KEYS", 2000))
    serve_batch = int(os.environ.get("CONSTDB_BENCH_SERVE_BATCH", 512))
    engine_kind = os.environ.get("CONSTDB_BENCH_SERVE_ENGINE", "cpu")
    reps = int(os.environ.get("CONSTDB_BENCH_SERVE_REPS", 2))

    ensure_native()
    from constdb_tpu.utils import native_tables as NT
    ext = NT.load_ext()
    if ext is None or not hasattr(ext, "intake_scan"):
        print("[bench] native extension with intake_scan unavailable — "
              "cannot run the intake legs", file=sys.stderr)
        sys.exit(1)

    per_ops = n_ops // n_conns
    per_conn = [serve_workload(ci, per_ops, n_keys, pipeline)
                for ci in range(n_conns)]
    total = per_ops * n_conns
    print(f"[bench] intake workload: {total} ops over {n_conns} conns x "
          f"{pipeline}-deep pipelines", file=sys.stderr)

    # leg -> env deltas for the FORKED server (fork inherits os.environ)
    legs = {
        "native": {"CONSTDB_NATIVE_INTAKE": "1"},
        "pure": {"CONSTDB_NATIVE_INTAKE": "0"},
        "nonative": {"CONSTDB_NO_NATIVE": "1"},
    }
    best: dict = {name: None for name in legs}
    for rep in range(reps):
        for name, env in legs.items():
            saved = {k: os.environ.get(k) for k in
                     ("CONSTDB_NATIVE_INTAKE", "CONSTDB_NO_NATIVE")}
            try:
                for k, v in env.items():
                    os.environ[k] = v
                leg = _serve_leg(serve_batch, engine_kind, per_conn)
            finally:
                for k, v in saved.items():
                    if v is None:
                        os.environ.pop(k, None)
                    else:
                        os.environ[k] = v
            print(f"[bench] rep {rep + 1} {name}: {leg[0]:.3f}s = "
                  f"{total / leg[0]:,.0f} req/s "
                  f"({leg[4]['native_intake_chunks']} native chunks)",
                  file=sys.stderr)
            if best[name] is None or leg[0] < best[name][0]:
                best[name] = leg

    ref_hashes = best["native"][2]
    ref_canon = strip_canonical_times(best["native"][3])
    verified = True
    legs_out = {}
    for name, (wall, rtts, hashes, canon, stats) in best.items():
        lat = np.asarray(rtts) * 1000.0
        replies_ok = hashes == ref_hashes
        export_ok = strip_canonical_times(canon) == ref_canon
        engaged_ok = stats["native_intake_chunks"] > 0 \
            if name == "native" else stats["native_intake_chunks"] == 0
        verified = verified and replies_ok and export_ok and engaged_ok
        legs_out[name] = {
            "rps": round(total / wall, 1),
            "wall_s": round(wall, 3),
            "reply_p50_ms": round(float(np.percentile(lat, 50)), 3),
            "reply_p99_ms": round(float(np.percentile(lat, 99)), 3),
            "native_intake_chunks": stats["native_intake_chunks"],
            "native_intake_msgs": stats["native_intake_msgs"],
            "serve_msgs_coalesced": stats["serve_msgs_coalesced"],
            "replies_ok": replies_ok,
            "export_ok": export_ok,
        }
        print(f"[bench] {name}: {legs_out[name]['rps']:,.1f} req/s, "
              f"replies {'OK' if replies_ok else 'MISMATCH'}, export "
              f"{'OK' if export_ok else 'MISMATCH'}, intake gauge "
              f"{'OK' if engaged_ok else 'WRONG'}", file=sys.stderr)

    stage = _intake_stage_legs(per_conn)
    print(f"[bench] intake stage alone: {stage['speedup']}x vs pure "
          f"({stage['native_msgs_per_sec']:,.0f} msgs/s)",
          file=sys.stderr)

    wire = _intake_wire_legs()
    verified = verified and wire["verified"]
    print(f"[bench] wire codec: encode {wire['encode_speedup']}x / "
          f"decode {wire['decode_speedup']}x vs pure "
          f"({'OK' if wire['verified'] else 'MISMATCH'})",
          file=sys.stderr)

    native_rps = legs_out["native"]["rps"]
    pure_rps = legs_out["pure"]["rps"]
    out = {
        "metric": "native_intake_serve_requests_per_sec",
        "value": native_rps,
        "unit": "requests/sec",
        "mode": "intake",
        "ops": total,
        "conns": n_conns,
        "pipeline": pipeline,
        "serve_batch": serve_batch,
        "legs": legs_out,
        "vs_pure_intake": round(native_rps / pure_rps, 2),
        "vs_no_native": round(native_rps / legs_out["nonative"]["rps"],
                              2),
        "stage": stage,
        "wire": wire,
        "host_note": "burstable 1-core box: client and server share the "
                     "core, so the serve ratio understates the server-"
                     "side intake win; the merge machinery (shared by "
                     "both legs) is the serving floor here — `stage` "
                     "isolates the evicted intake Python and `wire` the "
                     "REPLBATCH codec; the ROADMAP 3-5x serve target "
                     "applies on a >=4-core box",
        "engine": engine_kind,
        "verified": verified,
        "host": host_fingerprint(),
    }
    print(json.dumps(out))
    if not verified:
        sys.exit(1)


async def _overload_drive(port: int, per_conn: list, tallies: list,
                          rtts: list) -> None:
    """Pipelined driver that CLASSIFIES replies: (ok, oom, other_err)
    per connection, with per-window reply latency sampled exactly like
    _serve_drive — the latency of the non-shed traffic is the livelock
    gauge (a wedged shedding path shows up here, not in the shed
    count)."""
    import asyncio

    from constdb_tpu.resp.codec import make_parser
    from constdb_tpu.resp.message import Err
    from constdb_tpu.server.overload import OOM_ERR

    async def one(chunks, tally, sink):
        reader, writer = await asyncio.open_connection("127.0.0.1", port)
        parser = make_parser()
        clock = time.perf_counter
        # windows are driven synchronously per chunk (send, then read
        # that window's replies) so the tally maps 1:1 onto windows and
        # the server is never more than one window deep per connection —
        # the firehose pressure comes from value size, not queue depth
        try:
            for data, n in chunks:
                t0 = clock()
                writer.write(data)
                await writer.drain()
                seen = 0
                while seen < n:
                    m = parser.next_msg()
                    if m is not None:
                        seen += 1
                        if isinstance(m, Err):
                            if bytes(m.val) == OOM_ERR:
                                tally[1] += 1
                            else:
                                tally[2] += 1
                        else:
                            tally[0] += 1
                        continue
                    b = await asyncio.wait_for(reader.read(1 << 16), 30.0)
                    if not b:
                        raise ConnectionError("server EOF under overload")
                    parser.feed(b)
                sink.append(clock() - t0)
        finally:
            writer.close()

    tallies.extend([0, 0, 0] for _ in per_conn)
    sinks = [[] for _ in per_conn]
    await asyncio.gather(*(one(c, t, s) for c, t, s
                           in zip(per_conn, tallies, sinks)))
    for s in sinks:
        rtts.extend(s)


def serve_overload_main(args) -> None:
    """`bench.py --mode serve --overload`: the overload leg — a real
    socket server with CONSTDB_MAXMEMORY set well below the workload's
    footprint.  The node must SURVIVE the firehose: shed client data
    writes with the exact -OOM error, keep serving the non-shed
    traffic with bounded reply latency (no livelock), and keep its
    accounting gauges consistent.  Emits ONE JSON line with the shed
    rate, req/s over the whole mix, and reply-window p50/p99."""
    import asyncio

    n_ops = int(os.environ.get("CONSTDB_BENCH_OVL_OPS", 40_000))
    n_conns = int(os.environ.get("CONSTDB_BENCH_SERVE_CONNS", 2))
    pipeline = int(os.environ.get("CONSTDB_BENCH_SERVE_PIPELINE", 64))
    val_len = int(os.environ.get("CONSTDB_BENCH_OVL_VAL", 256))
    maxmem = int(os.environ.get("CONSTDB_BENCH_OVL_MAXMEM", 2 << 20))
    engine_kind = os.environ.get("CONSTDB_BENCH_SERVE_ENGINE", "cpu")

    ensure_native()
    from constdb_tpu.resp.codec import encode_msg
    from constdb_tpu.resp.message import Arr, Bulk

    per_ops = n_ops // n_conns
    footprint = n_ops * (val_len + 64)
    print(f"[bench] overload workload: {n_ops} SETs x {val_len}B "
          f"(~{footprint >> 20}MB footprint) vs maxmemory "
          f"{maxmem >> 20}MB", file=sys.stderr)
    per_conn = []
    for ci in range(n_conns):
        chunks = []
        for lo in range(0, per_ops, pipeline):
            n = min(pipeline, per_ops - lo)
            # unique keys: the footprint must really GROW past the cap
            # (a cycling key set converges to its working-set size)
            chunks.append((b"".join(
                encode_msg(Arr([Bulk(b"set"),
                                Bulk(b"ovl:%d:%d" % (ci, lo + j)),
                                Bulk(b"v" * val_len)]))
                for j in range(n)), n))
        per_conn.append(chunks)

    # the forked server child inherits the env: the governor reads the
    # cap at Node construction
    os.environ["CONSTDB_MAXMEMORY"] = str(maxmem)
    try:
        import multiprocessing as mp
        ctx = mp.get_context("fork")
        parent, child = ctx.Pipe()
        p = ctx.Process(target=_serve_bench_server,
                        args=(child, 512, engine_kind, 1), daemon=True)
        p.start()
        child.close()
        try:
            port = parent.recv()
            if isinstance(port, BaseException):
                raise port
            tallies: list = []
            rtts: list = []
            t0 = time.perf_counter()
            asyncio.run(_overload_drive(port, per_conn, tallies, rtts))
            wall = time.perf_counter() - t0
            parent.send("stop")
            result = parent.recv()
            p.join()
            parent.close()
            if isinstance(result, BaseException):
                raise result
        except BaseException:
            if p.is_alive():
                p.terminate()
                p.join(timeout=5)
            raise
    finally:
        os.environ.pop("CONSTDB_MAXMEMORY", None)

    _canon, stats = result
    ok = sum(t[0] for t in tallies)
    oom = sum(t[1] for t in tallies)
    other = sum(t[2] for t in tallies)
    total = ok + oom + other
    lat_ms = np.asarray(rtts) * 1000.0
    p50, p99 = (float(np.percentile(lat_ms, q)) for q in (50, 99))
    survived = total == n_ops and other == 0
    gauges_ok = stats["oom_shed_writes"] == oom and oom > 0 and ok > 0 \
        and stats["used_memory"] >= maxmem * 0.5
    print(f"[bench] overload: {ok} landed / {oom} shed / {other} other "
          f"errors of {total} ({oom / max(total, 1):.1%} shed rate), "
          f"{total / wall:,.0f} req/s, window p50 {p50:.2f}ms "
          f"p99 {p99:.2f}ms; server used_memory={stats['used_memory']} "
          f"state={stats['overload_state']} "
          f"reclaims={stats['oom_hard_reclaims']}", file=sys.stderr)
    out = {
        "metric": "serve_overload_shed_rate",
        "value": round(oom / max(total, 1), 4),
        "unit": "fraction",
        "mode": "serve-overload",
        "ops": total,
        "landed": ok,
        "shed": oom,
        "other_errors": other,
        "rps": round(total / wall, 1),
        "reply_p50_ms": round(p50, 3),
        "reply_p99_ms": round(p99, 3),
        "maxmemory": maxmem,
        "used_memory": stats["used_memory"],
        "overload_state": stats["overload_state"],
        "oom_hard_reclaims": stats["oom_hard_reclaims"],
        "survived": bool(survived),
        "verified": bool(survived and gauges_ok),
        "host": host_fingerprint(),
    }
    print(json.dumps(out))
    if not out["verified"]:
        sys.exit(1)


def serve_shards_main(args) -> None:
    """`bench.py --mode serve --serve-shards 1,2[,4...]`: the
    shard-per-core SCALING CURVE — the same deterministic pipelined
    workload over real sockets against a server running each shard
    count (server/serve_shards.py), oracle-compared against the
    shards=1 leg (per-connection reply streams must be byte-identical,
    visible-value exports equal).  Emits ONE JSON line with req/s per
    shard count, per-shard serving stats, and the host fingerprint —
    plus an explicit host note when this box has too few cores for the
    curve to mean anything (client + router + workers > cores)."""
    n_ops = int(os.environ.get("CONSTDB_BENCH_SERVE_OPS", 200_000))
    n_conns = int(os.environ.get("CONSTDB_BENCH_SERVE_CONNS", 4))
    pipeline = int(os.environ.get("CONSTDB_BENCH_SERVE_PIPELINE", 64))
    n_keys = int(os.environ.get("CONSTDB_BENCH_SERVE_KEYS", 2000))
    serve_batch = int(os.environ.get("CONSTDB_BENCH_SERVE_BATCH", 512))
    engine_kind = os.environ.get("CONSTDB_BENCH_SERVE_ENGINE", "cpu")
    reps = int(os.environ.get("CONSTDB_BENCH_SERVE_REPS", 2))

    counts = sorted({max(1, int(s))
                     for s in str(args.serve_shards).split(",") if s})
    if 1 not in counts:
        counts = [1] + counts  # the oracle + scaling baseline

    ensure_native()
    per_ops = n_ops // n_conns
    total = per_ops * n_conns
    t0 = time.perf_counter()
    per_conn = [serve_workload(ci, per_ops, n_keys, pipeline)
                for ci in range(n_conns)]
    print(f"[bench] serve-shards workload: {total} ops over {n_conns} "
          f"conns x {pipeline}-deep pipelines, shard counts {counts} "
          f"({time.perf_counter() - t0:.1f}s gen)", file=sys.stderr)

    best: dict = {}
    for rep in range(reps):
        for k in counts:
            leg = _serve_leg(serve_batch, engine_kind, per_conn,
                             serve_shards=k)
            print(f"[bench] rep {rep + 1} serve_shards={k}: "
                  f"{leg[0]:.3f}s = {total / leg[0]:,.0f} req/s",
                  file=sys.stderr)
            if k not in best or leg[0] < best[k][0]:
                best[k] = leg

    bwall, _rt, bhashes, bcanon, _bst = best[1]
    base_strip = strip_canonical_times(bcanon)
    curve = []
    verified = True
    for k in counts:
        wall, rtts, hashes, canon, stats = best[k]
        ok = hashes == bhashes and \
            strip_canonical_times(canon) == base_strip
        verified = verified and ok
        lat_ms = np.asarray(rtts) * 1000.0
        curve.append({
            "serve_shards": k,
            "rps": round(total / wall, 1),
            "wall_s": round(wall, 3),
            "speedup_vs_1": round(bwall / wall, 3),
            "reply_p50_ms": round(float(np.percentile(lat_ms, 50)), 3),
            "reply_p99_ms": round(float(np.percentile(lat_ms, 99)), 3),
            "verified_vs_shards1": ok,
            "serve_xshard_barriers": stats.get("serve_xshard_barriers", 0),
            "per_shard": stats.get("per_shard", {}),
        })
        print(f"[bench] serve_shards={k}: {total / wall:,.0f} req/s "
              f"({bwall / wall:.2f}x vs 1) "
              f"{'verified' if ok else 'MISMATCH'}", file=sys.stderr)

    ncpu = os.cpu_count() or 1
    host_note = ""
    if ncpu < max(counts) + 2:
        host_note = (
            f"this box has {ncpu} cores; a serve_shards={max(counts)} leg "
            f"needs ~{max(counts) + 2} (bench client + router + workers) "
            "to show scaling — the curve here measures capacity "
            "CONTENTION, not the architecture's ceiling.  The shards=1 "
            "path is the exact single-loop PR 5 serving path; the "
            "differential suite (tests/test_serve_shards.py) pins the "
            "multi-shard legs byte-identical, so the curve on a "
            ">=4-core box is the number that matters.")
        print(f"[bench] host note: {host_note}", file=sys.stderr)

    out = {
        "metric": "serve_shard_scaling",
        "value": curve[-1]["rps"],
        "unit": "requests/sec",
        "mode": "serve",
        "ops": total,
        "conns": n_conns,
        "pipeline": pipeline,
        "serve_batch": serve_batch,
        "serve_shards_curve": curve,
        "engine": engine_kind,
        "verified": verified,
        "host": host_fingerprint(),
        "host_note": host_note,
    }
    print(json.dumps(out))
    if not verified:
        sys.exit(1)


# --------------------------------------------------------------------------
# --mode resync: digest-driven delta resync vs full snapshot


class _ResyncSink:
    """StreamWriter stand-in for the REAL ReplicaLink push loop: parses
    the pusher's wire stream as it is written, answers digest questions
    from the puller store's matrix (bridged into the link's ack queue
    exactly the way the pull loop does), and collects the
    FULLSYNC/DELTASYNC payload for the timed apply.  Every byte is
    counted in both directions — `bytes_out` is the pusher's stream,
    `bytes_back` the encoded size the puller's acks would occupy."""

    def __init__(self, link, ks):
        from constdb_tpu.resp.codec import make_parser
        self.link = link
        self.ks = ks
        self.parser = make_parser()
        self.bytes_out = 0
        self.bytes_back = 0
        self.payload = bytearray()
        self.payload_kind = None
        self.repl_last = 0
        self.n_buckets = 0
        self.digest_frames = 0
        self.done = asyncio.Event()
        self.closed = False
        self._want = 0
        self._matrix = {}

    def write(self, data: bytes) -> None:
        self.bytes_out += len(data)
        self.parser.feed(bytes(data))
        self._pump()

    async def drain(self) -> None:
        await asyncio.sleep(0)

    def close(self) -> None:
        self.closed = True

    def _pump(self) -> None:
        from constdb_tpu.replica.link import DELTASYNC, DIGEST, FULLSYNC
        from constdb_tpu.resp.message import Arr, as_bytes, as_int
        while True:
            if self._want:
                raw = self.parser.take_raw(self._want)
                if not raw:
                    return
                self.payload += raw
                self._want -= len(raw)
                if self._want:
                    return
                self.done.set()
            msg = self.parser.next_msg()
            if msg is None:
                return
            items = msg.items if isinstance(msg, Arr) else None
            assert items, f"unexpected frame {msg!r}"
            kind = as_bytes(items[0]).lower()
            if kind == DIGEST:
                self.digest_frames += 1
                self._answer(items)
            elif kind in (FULLSYNC, DELTASYNC):
                self.payload_kind = kind
                self._want = as_int(items[1])
                self.repl_last = as_int(items[2])
                if kind == DELTASYNC and len(items) > 3:
                    self.n_buckets = as_int(items[3])
            # PARTSYNC / REPLICATE / REPLACK heartbeats: not part of the
            # resync transfer under measurement

    def _answer(self, items) -> None:
        from constdb_tpu.replica.link import DIGESTACK
        from constdb_tpu.resp.codec import encode_msg
        from constdb_tpu.resp.message import Arr, Bulk, Int, as_bytes, as_int
        from constdb_tpu.store.digest import state_digest_matrix
        token, level = as_int(items[1]), as_int(items[2])
        fanout, leaves = as_int(items[3]), as_int(items[4])
        key = (token, fanout, leaves)
        mat = self._matrix.get(key)
        if mat is None:
            # the puller-side fold runs inside the timed span — it is
            # real resync CPU cost on the receiving node
            mat = state_digest_matrix(self.ks, fanout, leaves)
            self._matrix = {key: mat}
        if level == 0:
            theirs = np.frombuffer(as_bytes(items[5]), dtype="<u8")
            mine = mat.sum(axis=1, dtype=np.uint64)
            reply = np.nonzero(mine != theirs)[0].astype("<i8").tobytes()
        elif level == 2:
            from constdb_tpu.store.digest import stamp_mismatch_indices
            crcs = np.frombuffer(as_bytes(items[5]),
                                 dtype="<u4").astype(np.uint64)
            stamps = np.frombuffer(as_bytes(items[6]), dtype="<u8")
            reply = stamp_mismatch_indices(
                self.ks, crcs, stamps).astype("<i4").tobytes()
        else:
            shards = np.frombuffer(as_bytes(items[5]),
                                   dtype="<i8").astype(np.int64)
            sub = np.frombuffer(as_bytes(items[6]),
                                dtype="<u8").reshape(len(shards), leaves)
            srow, leaf = np.nonzero(mat[shards] != sub)
            reply = (shards[srow] * leaves + leaf).astype("<i8").tobytes()
        ack = [Bulk(DIGESTACK), Int(token), Int(level), Bulk(reply)]
        self.bytes_back += len(encode_msg(Arr(ack)))
        self.link._digest_acks.put_nowait(ack)


class _ResyncDump:
    """shared_dump stand-in producing a REAL full snapshot of the node's
    current state on acquire — the dump cost lands inside the full-sync
    leg's wall, exactly where a cold shared dump pays it."""

    def __init__(self, node, work_dir: str):
        self.node = node
        self.work_dir = work_dir

    async def acquire(self, compressed=False):
        from constdb_tpu.persist.share import Dump
        from constdb_tpu.persist.snapshot import NodeMeta, dump_keyspace
        self.node.ensure_flushed()
        path = os.path.join(self.work_dir, "resync_full.snapshot")
        size = dump_keyspace(path, self.node.ks,
                             NodeMeta(node_id=self.node.node_id),
                             container_level=6 if compressed else 0)
        return Dump(path=path, repl_last=self.node.repl_log.last_uuid,
                    size=size)


def _resync_engine(kind: str):
    if kind == "cpu":
        return CpuMergeEngine()
    from constdb_tpu.engine.tpu import TpuMergeEngine
    return TpuMergeEngine()


def _resync_divergence(ks: KeySpace, kids: np.ndarray, uuid: int,
                       tag: bytes) -> ColumnarBatch:
    """LWW register overwrites of `kids` at `uuid` as ONE state batch
    (the divergent writes a partitioned pusher accumulated)."""
    sel = np.asarray(kids, dtype=_I64)
    idx = sel.tolist()
    n = len(idx)
    b = ColumnarBatch()
    b.rows_unique_per_slot = True
    b.keys = [ks.key_bytes[i] for i in idx]
    b.key_enc = np.ascontiguousarray(ks.keys.enc[sel])
    b.key_ct = np.ascontiguousarray(ks.keys.ct[sel])
    b.key_mt = np.full(n, uuid, dtype=_I64)
    b.key_dt = np.ascontiguousarray(ks.keys.dt[sel])
    b.key_expire = np.ascontiguousarray(ks.keys.expire[sel])
    b.reg_val = [tag] * n
    b.reg_t = np.full(n, uuid, dtype=_I64)
    b.reg_node = np.full(n, 9, dtype=_I64)
    return b


async def _resync_leg(node, app, puller_ks, puller_engine, delta: bool,
                      timeout: float = 900.0):
    """One measured resync: drive the REAL push loop against an off-ring
    peer (resume=0) whose capabilities do/don't include CAP_DELTA_SYNC,
    stream into the sink, then merge the payload into the puller store.
    Wall covers negotiate + stream + apply + flush.  Returns
    (wall_s, sink, stats_delta_dict)."""
    from constdb_tpu.persist.snapshot import SectionDemux
    from constdb_tpu.replica.link import (CAP_DELTA_SYNC,
                                          CAP_FULLSYNC_RESET, ReplicaLink)
    from constdb_tpu.replica.manager import ReplicaMeta
    import io as _io
    st = node.stats
    before = (st.repl_delta_syncs, st.repl_full_syncs,
              st.repl_digest_rounds, st.repl_delta_bytes,
              st.extra.get("repl_delta_demotions", 0))
    link = ReplicaLink(app, ReplicaMeta(addr="bench:0"))
    link._peer_caps = CAP_FULLSYNC_RESET | (CAP_DELTA_SYNC if delta else 0)
    link._digest_acks = asyncio.Queue()
    sink = _ResyncSink(link, puller_ks)
    t0 = time.perf_counter()
    task = asyncio.create_task(link._push_loop(sink, peer_resume=0))
    done_wait = asyncio.create_task(sink.done.wait())
    try:
        # watch the push loop TOO: an exception inside it would leave
        # sink.done unset forever — surface it now instead of burning
        # the whole timeout and failing the oracle with no root cause
        finished, _ = await asyncio.wait(
            {task, done_wait}, timeout=timeout,
            return_when=asyncio.FIRST_COMPLETED)
        if not finished:
            raise TimeoutError(f"resync leg incomplete after {timeout}s")
        if not sink.done.is_set():
            task.result()  # raises the push loop's actual error
            raise RuntimeError("push loop exited without syncing")
    finally:
        for t in (task, done_wait):
            t.cancel()
        try:
            await task
        except (asyncio.CancelledError, Exception):
            pass
    for chunk in SectionDemux(_io.BytesIO(bytes(sink.payload))).batches():
        puller_engine.merge(puller_ks, chunk)
    if getattr(puller_engine, "needs_flush", False):
        puller_engine.flush(puller_ks)
    wall = time.perf_counter() - t0
    return wall, sink, {
        "delta_syncs": st.repl_delta_syncs - before[0],
        "full_syncs": st.repl_full_syncs - before[1],
        "digest_rounds": st.repl_digest_rounds - before[2],
        "delta_bytes": st.repl_delta_bytes - before[3],
        "demotions": st.extra.get("repl_delta_demotions", 0) - before[4],
    }


def resync_main(args) -> None:
    """`bench.py --mode resync`: anti-entropy resync cost at small
    divergence — a converged 2-store pair diverges a configurable key
    fraction past the pusher's repl_log ring, then both resync legs run
    through the REAL ReplicaLink push loop: digest-negotiated delta
    (CAP_DELTA_SYNC peer) vs full snapshot (legacy peer), same pusher
    state, bytes-on-wire and wall measured for each.  Oracle: both
    pullers' canonical exports must equal the pusher's on a
    deterministic subsample (plus a full-state digest cross-check).
    Emits ONE JSON line (BENCH_r11) with the per-fraction curve."""
    import tempfile
    import types as _types
    from constdb_tpu.store.digest import (DIGEST_FANOUT, leaves_for,
                                          state_digest_matrix)
    from constdb_tpu.resp.message import Bulk
    from constdb_tpu.server.node import Node

    n_keys = int(os.environ.get("CONSTDB_BENCH_RESYNC_KEYS", 1_000_000))
    n_rep = int(os.environ.get("CONSTDB_BENCH_RESYNC_REPLICAS", 2))
    fracs = sorted(float(f) for f in os.environ.get(
        "CONSTDB_BENCH_RESYNC_FRACS", "0.001,0.01,0.1").split(",") if f)
    engine_kind = os.environ.get("CONSTDB_BENCH_RESYNC_ENGINE", "tpu")
    verify_target = int(os.environ.get("CONSTDB_BENCH_RESYNC_VERIFY",
                                       100_000))
    chunk = int(os.environ.get("CONSTDB_BENCH_CHUNK", 1 << 17))

    ensure_native()
    t0 = time.perf_counter()
    batches = make_workload(n_keys, n_rep)
    chunks = chunk_batches(batches, chunk)
    n_cnt = int(n_keys * 0.4)
    n_reg = int(n_keys * 0.3)
    if int(fracs[-1] * n_keys) > n_reg:
        raise SystemExit(f"max fraction {fracs[-1]} exceeds the register "
                         f"key range ({n_reg}/{n_keys})")

    # pusher node + two puller stores, all converged on the same state
    pusher = Node(node_id=1, engine=_resync_engine(engine_kind))
    for c in chunks:
        pusher.engine.merge(pusher.ks, c)
    pusher.ensure_flushed()
    pullers = {}
    for name in ("delta", "full"):
        eng = _resync_engine(engine_kind)
        ks = KeySpace()
        for c in chunks:
            eng.merge(ks, c)
        if getattr(eng, "needs_flush", False):
            eng.flush(ks)
        pullers[name] = (ks, eng)
    print(f"[bench] resync pair: {n_keys} keys x {n_rep} replicas built "
          f"({time.perf_counter() - t0:.1f}s gen+merge, engine="
          f"{engine_kind})", file=sys.stderr)

    workdir = tempfile.mkdtemp(prefix="constdb-resync-")
    app = _types.SimpleNamespace(
        node=pusher, heartbeat=0.05, reconnect_delay=0.05,
        handshake_timeout=60.0, work_dir=workdir, delta_sync=True,
        advertised_addr="bench:0")
    app.shared_dump = _ResyncDump(pusher, workdir)
    pusher.repl_log.cap = 16  # any divergence burst falls off this ring

    sample = subsample_keys(batches[0].keys, n_keys, verify_target)
    leaves = leaves_for(n_keys, DIGEST_FANOUT,
                        getattr(app, "delta_bucket_keys", 8))
    total_buckets = DIGEST_FANOUT * leaves

    async def run() -> tuple[list, bool]:
        curve = []
        all_ok = True
        for epoch, frac in enumerate(fracs, start=1):
            n_div = max(1, int(frac * n_keys))
            kids = np.arange(n_cnt, n_cnt + n_div, dtype=_I64)
            uuid = (MS0 + 1_000_000 + epoch * 1000) << SEQ_BITS
            div = _resync_divergence(pusher.ks, kids, uuid,
                                     b"E%d" % epoch)
            pusher.engine.merge(pusher.ks, div)
            pusher.ensure_flushed()
            pusher.hlc.observe(uuid)
            # two real logged writes on a 16-byte ring: the first evicts,
            # so every peer resume below it is off-ring (the resync
            # trigger), while the survivor keeps repl_last coherent
            for i in range(2):
                wu = pusher.hlc.tick(True)
                wkey = b"__resync_ring_%d_%d" % (epoch, i)
                kid, _ = pusher.ks.get_or_create(wkey, S.ENC_BYTES, wu)
                pusher.ks.register_set(kid, b"r", wu, pusher.node_id)
                pusher.ks.touch("env", "reg")
                pusher.repl_log.push(wu, b"set", [Bulk(wkey), Bulk(b"r")])
            assert not pusher.repl_log.can_resume_from(0)

            row = {"frac": frac, "n_div": n_div}
            for name, is_delta in (("delta", True), ("full", False)):
                ks, eng = pullers[name]
                wall, sink, st = await _resync_leg(
                    pusher, app, ks, eng, delta=is_delta)
                wire = sink.bytes_out + sink.bytes_back
                row[f"{name}_wall_s"] = round(wall, 3)
                row[f"{name}_bytes"] = wire
                if is_delta:
                    row["delta_payload_kind"] = \
                        sink.payload_kind.decode()
                    row["digest_rounds"] = st["digest_rounds"]
                    row["digest_frame_bytes"] = wire - len(sink.payload)
                    row["buckets_streamed"] = sink.n_buckets
                    row["demoted"] = st["demotions"] > 0
                print(f"[bench] frac={frac} {name}: {wire:,} bytes, "
                      f"{wall:.3f}s"
                      + (f" ({sink.n_buckets}/{total_buckets} buckets, "
                         f"{st['digest_rounds']} digest rounds)"
                         if is_delta else ""), file=sys.stderr)
            row["bytes_ratio"] = round(row["delta_bytes"]
                                       / row["full_bytes"], 4)
            row["speedup"] = round(row["full_wall_s"]
                                   / max(row["delta_wall_s"], 1e-9), 2)

            # oracle: both pullers converged to the pusher, on an
            # independent canonical subsample + the digest matrix,
            # whose mod-2^64 fold is exactly the chaos oracle's scalar
            # digest (store/digest.py full_state_digest) — derived from
            # the already-computed matrices, not a second keyspace scan
            want = pusher.ks.canonical(keys=sample)
            wmat = state_digest_matrix(pusher.ks, DIGEST_FANOUT, leaves)
            wsum = int(wmat.sum(dtype=np.uint64))
            ok = True
            for name, (ks, _eng) in pullers.items():
                got = ks.canonical(keys=sample)
                pmat = state_digest_matrix(ks, DIGEST_FANOUT, leaves)
                dok = bool((pmat == wmat).all()) and \
                    int(pmat.sum(dtype=np.uint64)) == wsum
                cok = compare_canonical(got, want) == 0
                ok = ok and dok and cok
                print(f"[bench] frac={frac} verify {name}: canonical "
                      f"{'OK' if cok else 'MISMATCH'} ({len(sample)} "
                      f"keys), digest {'OK' if dok else 'MISMATCH'}",
                      file=sys.stderr)
            row["verified"] = ok
            all_ok = all_ok and ok
            curve.append(row)
        return curve, all_ok

    curve, verified = asyncio.run(run())
    # headline: bytes ratio at the largest fraction <= 1% divergence
    # (the ISSUE acceptance bar: <= 0.10 of the full-snapshot bytes)
    small = [r for r in curve if r["frac"] <= 0.01] or curve[:1]
    out = {
        "metric": "resync_delta_bytes_ratio",
        "value": small[-1]["bytes_ratio"],
        "unit": "delta_bytes/full_bytes",
        "mode": "resync",
        "keys": n_keys,
        "replicas": n_rep,
        "engine": engine_kind,
        "digest_fanout": DIGEST_FANOUT,
        "digest_leaves": leaves,
        "curve": curve,
        "verified": verified,
        "host": host_fingerprint(),
    }
    print(json.dumps(out))
    if not verified:
        sys.exit(1)


def snapshot_resident_legs(args, chunks, batches, n_keys, n_rep, group,
                           fold, oracle, verify_on, cpu_rate,
                           device) -> None:
    """`--resident 0,1` snapshot legs: interleaved best-of-2 catch-up
    merges of the SAME chunk stream through a device-resident engine
    (state persists across chunk merges, one flush at the end) vs the
    non-resident engine (per-round state upload + download), both
    oracle-verified, with per-leg transfer counters (BENCH_r12).
    Single-keyspace path only (the process pool pins resident=True)."""
    from constdb_tpu.engine.tpu import TpuMergeEngine
    from constdb_tpu.store.sharded_keyspace import ShardedKeySpace

    legs = [int(x) for x in str(args.resident).split(",")]
    stores = {}
    walls = {r: float("inf") for r in legs}
    for _ in range(2):
        for r in legs:
            sks = stores.get(r)
            if sks is None:
                sks = stores[r] = ShardedKeySpace(
                    n_shards=1, group=group,
                    engine_factory=lambda rr=r: TpuMergeEngine(
                        resident=bool(rr), dense_fold=fold))
            sks.reset()
            t0 = time.perf_counter()
            for c in chunks:
                sks.submit(c)
            sks.flush()
            walls[r] = min(walls[r], time.perf_counter() - t0)
    want = None
    oracle_err = None
    if verify_on and oracle is not None:
        try:
            oracle[1].send("go")
        except OSError as e:
            oracle_err = str(e) or type(e).__name__
    curve = []
    verified = None
    sub_keys = subsample_keys(batches[0].keys, n_keys) if verify_on else None
    if verify_on and oracle is not None and oracle_err is None:
        p, rx = oracle
        try:
            want = rx.recv()
        except (EOFError, OSError) as e:
            want = None
            oracle_err = str(e) or type(e).__name__
        finally:
            p.join()
    for r in legs:
        sks = stores[r]
        secs = sks.host_secs_per_shard()[0]
        leg = {"resident": r, "wall_s": round(walls[r], 3),
               "keys_per_sec": round(n_keys / walls[r], 1),
               "dev_upload_bytes": secs.get("bytes_h2d", 0),
               "dev_download_bytes": secs.get("bytes_d2h", 0),
               "dev_rounds_resident": secs.get("dev_rounds_resident", 0),
               "host_micro_rounds": secs.get("host_micro_rounds", 0),
               "flush_rows_downloaded":
                   secs.get("flush_rows_downloaded", 0),
               "flush_rows_full_equiv":
                   secs.get("flush_rows_full_equiv", 0),
               "folds": secs.get("folds", 0)}
        if want is not None and not isinstance(want, Exception):
            diffs = compare_canonical(sks.canonical(keys=sub_keys), want)
            leg["diffs"] = diffs
            verified = (verified is not False) and diffs == 0
        curve.append(leg)
        print(f"[bench] resident={r}: {walls[r]:.3f}s = "
              f"{leg['keys_per_sec']:,.0f} keys/s; h2d "
              f"{leg['dev_upload_bytes']:,} d2h "
              f"{leg['dev_download_bytes']:,}"
              + (f" ({leg['diffs']} diffs)" if "diffs" in leg else ""),
              file=sys.stderr)
        sks.close() if hasattr(sks, "close") else None
    out = {
        "metric": "snapshot_merge_keys_per_sec",
        "value": curve[-1]["keys_per_sec"],
        "unit": "keys/sec",
        "mode": "snapshot",
        "keys": n_keys,
        "replicas": n_rep,
        "vs_baseline": round(curve[-1]["keys_per_sec"] / cpu_rate, 2),
        "resident_curve": curve,
        "device": device,
        "verified": verified,
        "host": host_fingerprint(),
    }
    if oracle_err is not None:
        out["verify_error"] = oracle_err
    print(json.dumps(out))
    if verified is False:
        sys.exit(1)


def cluster_workload_ops(conn_id: int, n_ops: int, n_keys: int,
                         seed: int = 13) -> list:
    """serve_workload's exact command mix, one entry per op as
    (routing_key, encoded_bytes): the cluster legs partition the SAME
    op stream by slot owner, so every leg applies the identical total
    workload and the union of per-group visible-value exports must
    equal the single group's (the cross-leg oracle).  Keys stay
    conn-prefixed (single writer per key), and a key's ops never change
    group within a leg, so per-key histories are leg-invariant."""
    import random

    from constdb_tpu.resp.codec import encode_into
    from constdb_tpu.resp.message import Arr, Bulk

    rng = random.Random(seed * 1000 + conn_id)
    pfx = b"c%d:" % conn_id
    ops = []
    for i in range(n_ops):
        r = rng.random()
        k = pfx + b"%05d" % rng.randrange(n_keys)
        if r < 0.25:
            body = (b"set", b"r" + k, b"v%08d" % i)
        elif r < 0.50:
            body = (b"incr", b"c" + k, b"%d" % rng.randrange(1, 100))
        elif r < 0.75:
            body = (b"sadd", b"s" + k,
                    *(b"m%03d" % rng.randrange(256) for _ in range(8)))
        elif r < 0.95:
            fv = []
            for f in range(10):
                fv += [b"f%02d" % rng.randrange(32), b"v%07d%d" % (i, f)]
            body = (b"hset", b"h" + k, *fv)
        elif r < 0.97:
            body = (b"get", b"r" + k)
        elif r < 0.995:
            body = (b"srem", b"s" + k, b"m%03d" % rng.randrange(256))
        else:
            body = (b"del", b"r" + k)
        buf = bytearray()
        encode_into(buf, Arr([Bulk(b) for b in body]))
        ops.append((body[1], bytes(buf)))
    return ops


def _partition_cluster_ops(ops_per_conn: list, n_groups: int,
                           pipeline: int) -> list:
    """Route each op to its slot's owner under even_split(n_groups) and
    chunk into pipeline windows: per-group, per-connection pre-encoded
    chunks in _serve_drive's (bytes, n) shape.  Relative op order per
    connection is preserved inside each group, so same-key ops (always
    the same group) keep their history order."""
    from constdb_tpu.cluster import even_split, slot_of

    owner = even_split(n_groups).owner
    groups = []
    for g in range(n_groups):
        per_conn = []
        for ops in ops_per_conn:
            chunks, cur, n = [], bytearray(), 0
            for key, data in ops:
                if owner[slot_of(key)] != g:
                    continue
                cur += data
                n += 1
                if n >= pipeline:
                    chunks.append((bytes(cur), n))
                    cur = bytearray()
                    n = 0
            if n:
                chunks.append((bytes(cur), n))
            if chunks:
                per_conn.append(chunks)
        groups.append(per_conn)
    return groups


def _cluster_bench_server(pipe, serve_batch: int, engine_kind: str,
                          n_groups: int, gid: int,
                          enabled: bool = True) -> None:
    """Forked cluster-group server: _serve_bench_server's GC posture
    and pipe protocol (port up, block until stop, ship back canonical +
    stats), with the slot router enabled at `n_groups` groups.
    enabled=False forks the exact pre-cluster node — the
    redirect-overhead baseline leg."""
    import asyncio
    import gc

    from constdb_tpu.server.io import start_node
    from constdb_tpu.server.node import Node

    gc.collect()
    gc.freeze()
    gc.set_threshold(100_000, 50, 50)

    def make_engine():
        if engine_kind == "cpu":
            from constdb_tpu.engine.cpu import CpuMergeEngine
            return CpuMergeEngine()
        from constdb_tpu.conf import build_engine
        return build_engine(engine_kind)

    async def main():
        node = Node(node_id=1 + gid, alias=f"bench-g{gid}",
                    engine=make_engine())
        app = await start_node(node, host="127.0.0.1", port=0,
                               work_dir="/tmp", serve_batch=serve_batch,
                               serve_shards=1, cluster=enabled,
                               slot_groups=n_groups, cluster_group=gid)
        pipe.send(app.port)
        loop = asyncio.get_running_loop()
        await loop.run_in_executor(None, pipe.recv)  # block until "stop"
        node.ensure_flushed()
        cl = node.cluster
        pipe.send((node.canonical(), {
            "cmds_processed": node.stats.cmds_processed,
            "serve_msgs_coalesced": node.stats.serve_msgs_coalesced,
            "redirects_sent": cl.redirects_sent if cl is not None else 0,
            "epoch": cl.epoch if cl is not None else 0,
            "slots_owned": cl.table.slots_owned(gid)
            if cl is not None else 0,
        }))
        await app.close()

    try:
        asyncio.run(main())
    except BaseException as e:  # parent surfaces the failure
        try:
            pipe.send(e)
        except OSError:
            pass
    finally:
        pipe.close()


def _cluster_leg(serve_batch: int, engine_kind: str, n_groups: int,
                 per_group_conns: list, enabled: bool = True):
    """One cluster leg: fork one server per group, drive every group's
    connections concurrently in a single loop (fully pipelined), return
    (wall_s, reply_hashes, canonicals, stats).  Wall is the envelope
    over all groups — the cluster's throughput clock."""
    import asyncio
    import multiprocessing as mp

    ctx = mp.get_context("fork")
    procs, parents, ports = [], [], []
    try:
        for g in range(n_groups):
            parent, child = ctx.Pipe()
            p = ctx.Process(target=_cluster_bench_server,
                            args=(child, serve_batch, engine_kind,
                                  n_groups, g, enabled),
                            daemon=True)
            p.start()
            child.close()
            procs.append(p)
            parents.append(parent)
        for parent in parents:
            port = parent.recv()
            if isinstance(port, BaseException):
                raise port
            ports.append(port)
        rtts: list = []
        hashes: list = []

        async def drive_all():
            await asyncio.gather(*(
                _serve_drive(ports[g], per_group_conns[g], rtts, hashes)
                for g in range(n_groups) if per_group_conns[g]))

        t0 = time.perf_counter()
        asyncio.run(drive_all())
        wall = time.perf_counter() - t0
        canons, stats = [], []
        for parent in parents:
            parent.send("stop")
            result = parent.recv()
            if isinstance(result, BaseException):
                raise result
            canons.append(result[0])
            stats.append(result[1])
        for p in procs:
            p.join()
        for parent in parents:
            parent.close()
        return wall, hashes, canons, stats
    except BaseException:
        for p in procs:
            if p.is_alive():
                p.terminate()
                p.join(timeout=5)
        raise


def _cluster_migrate_leg(mig_keys: int, mig_slots: int) -> dict:
    """In-process two-group live migration: load group-0 keys, migrate
    slots [0, mig_slots) to group 1, and measure wall + shipped payload
    bytes against (a) the migrated range's own encoded size per round
    and (b) the FULL state's encoded size — the O(slot bytes) evidence:
    a slot move costs the slot's bytes times the round count, not the
    keyspace's."""
    import asyncio

    import numpy as np

    from constdb_tpu.cluster import (NSLOTS, SLOT_FANOUT, SLOT_LEAVES,
                                     bucket_of_slot, slot_of)
    from constdb_tpu.cluster.migrate import migrate_slot_range
    from constdb_tpu.engine.cpu import CpuMergeEngine
    from constdb_tpu.persist.snapshot import _encode_batch
    from constdb_tpu.resp.message import Bulk, Err
    from constdb_tpu.server.commands import execute
    from constdb_tpu.server.io import start_node
    from constdb_tpu.server.node import Node
    from constdb_tpu.store.digest import export_bucket_batch

    async def run() -> dict:
        node0 = Node(node_id=1, alias="mig-src", engine=CpuMergeEngine())
        node1 = Node(node_id=2, alias="mig-dst", engine=CpuMergeEngine())
        app0 = await start_node(node0, host="127.0.0.1", port=0,
                                work_dir="/tmp", cluster=True,
                                slot_groups=2, cluster_group=0)
        app1 = await start_node(node1, host="127.0.0.1", port=0,
                                work_dir="/tmp", cluster=True,
                                slot_groups=2, cluster_group=1)
        try:
            # group-0 state: every key this leg writes is owned by gid 0
            # (even_split(2): slots [0, 8192)); keys in the migrated
            # range double as the post-flip serving probes
            moved_probe = None
            written = 0
            i = 0
            while written < mig_keys:
                key = b"mig:%07d" % i
                i += 1
                s = slot_of(key)
                if s >= NSLOTS // 2:
                    continue
                r = execute(node0, [Bulk(b"set"), Bulk(key),
                                    Bulk(b"v%062d" % i)])
                assert not isinstance(r, Err), r
                written += 1
                if moved_probe is None and s < mig_slots:
                    moved_probe = key
            node0.ensure_flushed()
            full_bytes = len(bytes(_encode_batch(export_bucket_batch(
                node0.ks, SLOT_FANOUT, SLOT_LEAVES,
                np.ones(NSLOTS, dtype=bool)))))
            mask = np.zeros(NSLOTS, dtype=bool)
            for s in range(mig_slots):
                mask[bucket_of_slot(s)] = True
            range_bytes = len(bytes(_encode_batch(export_bucket_batch(
                node0.ks, SLOT_FANOUT, SLOT_LEAVES, mask))))

            t0 = time.perf_counter()
            res = await migrate_slot_range(node0, app0, 0, mig_slots,
                                           app1.advertised_addr)
            wall = time.perf_counter() - t0

            cl0, cl1 = node0.cluster, node1.cluster
            probe_on_target = execute(node1, [Bulk(b"get"),
                                              Bulk(moved_probe)])
            probe_on_source = execute(node0, [Bulk(b"get"),
                                              Bulk(moved_probe)])
            rounds_per_slot = res["rounds"] / max(1, res["slots"])
            ok = (res["slots"] == mig_slots
                  and cl0.epoch == cl1.epoch == 1 + mig_slots
                  and cl0.migrations_out == mig_slots
                  and cl1.migrations_in == mig_slots
                  and not cl0.migrating and not cl1.importing
                  and cl0.gc_pin() is None and cl1.gc_pin() is None
                  and not isinstance(probe_on_target, Err)
                  and isinstance(probe_on_source, Err)
                  and probe_on_source.val.startswith(b"MOVED ")
                  # O(slot bytes): shipped ~= range bytes x rounds, and
                  # the range is a small fraction of the full state
                  and res["bytes"] <= range_bytes * rounds_per_slot * 1.5
                  and range_bytes < full_bytes / 4)
            return {
                "ok": ok,
                "slots": res["slots"],
                "rounds": res["rounds"],
                "wall_s": round(wall, 3),
                "slots_per_sec": round(res["slots"] / wall, 1),
                "shipped_bytes": res["bytes"],
                "range_state_bytes": range_bytes,
                "full_state_bytes": full_bytes,
                "shipped_vs_full": round(res["bytes"] / full_bytes, 4),
                "keys": written,
                "epoch": cl0.epoch,
            }
        finally:
            await app0.close()
            await app1.close()

    return asyncio.run(run())


def cluster_main(args) -> None:
    """`bench.py --mode cluster`: the hash-slot partitioning legs
    (BENCH_r21.json).

    SCALING — one deterministic op stream partitioned by slot owner,
    driven against 1 group vs N groups concurrently; the union of the
    per-group visible-value exports must equal the single group's (no
    key lost or duplicated across the partition), and every leg must
    finish with zero redirects (client partitioning and server routing
    agree on the slot math).  REDIRECT TAX — cluster-on at one group
    (router engaged on every command, every slot owned) vs the exact
    pre-cluster node, interleaved best-of-N with reply-hash + export
    oracle; the slot check must cost <= ~2%.  MIGRATION — a live
    slot-range migration between two in-process groups: wall, shipped
    bytes vs the range's and the full state's encoded bytes (the
    O(slot bytes) evidence), moved keys serving from the target."""
    n_ops = int(os.environ.get("CONSTDB_BENCH_CLUSTER_OPS", 120_000))
    n_conns = int(os.environ.get("CONSTDB_BENCH_CLUSTER_CONNS", 4))
    pipeline = int(os.environ.get("CONSTDB_BENCH_CLUSTER_PIPELINE", 64))
    n_keys = int(os.environ.get("CONSTDB_BENCH_CLUSTER_KEYS", 2000))
    n_groups = int(os.environ.get("CONSTDB_BENCH_CLUSTER_GROUPS", 4))
    serve_batch = int(os.environ.get("CONSTDB_BENCH_SERVE_BATCH", 512))
    engine_kind = os.environ.get("CONSTDB_BENCH_CLUSTER_ENGINE", "cpu")
    reps = int(os.environ.get("CONSTDB_BENCH_CLUSTER_REPS", 3))
    mig_keys = int(os.environ.get("CONSTDB_BENCH_CLUSTER_MIG_KEYS", 20_000))
    mig_slots = int(os.environ.get("CONSTDB_BENCH_CLUSTER_MIG_SLOTS", 128))

    ensure_native()
    per_ops = n_ops // n_conns
    total = per_ops * n_conns
    t0 = time.perf_counter()
    ops_per_conn = [cluster_workload_ops(ci, per_ops, n_keys)
                    for ci in range(n_conns)]
    parts = {g: _partition_cluster_ops(ops_per_conn, g, pipeline)
             for g in {1, n_groups}}
    print(f"[bench] cluster workload: {total} ops over {n_conns} conns x "
          f"{pipeline}-deep pipelines, {n_groups} groups "
          f"({time.perf_counter() - t0:.1f}s gen)", file=sys.stderr)

    # interleaved best-of-N: off (pre-cluster node), on (router engaged,
    # one group), grp (the n_groups partition)
    best: dict = {}

    def run_leg(rep: int, tag: str, g: int, enabled: bool) -> None:
        leg = _cluster_leg(serve_batch, engine_kind, g, parts[g], enabled)
        print(f"[bench] rep {rep} {tag} (groups={g} "
              f"cluster={'on' if enabled else 'off'}): "
              f"{leg[0]:.3f}s = {total / leg[0]:,.0f} req/s",
              file=sys.stderr)
        if tag not in best or leg[0] < best[tag][0]:
            best[tag] = leg

    for rep in range(reps):
        for tag, g, enabled in (("off", 1, False), ("on", 1, True),
                                ("grp", n_groups, True)):
            run_leg(rep + 1, tag, g, enabled)
    # extra interleaved off/on pairs: the tax target (~2%) is far below
    # a burstable box's rep-to-rep swing, so the pair needs more
    # best-of samples than the scaling curve does
    tax_reps = int(os.environ.get("CONSTDB_BENCH_CLUSTER_TAX_REPS", 3))
    for rep in range(tax_reps):
        for tag, g, enabled in (("off", 1, False), ("on", 1, True)):
            run_leg(reps + rep + 1, tag, g, enabled)
    wall_off, hashes_off, canons_off, _ = best["off"]
    wall_on, hashes_on, canons_on, stats_on = best["on"]
    wall_grp, _hashes_grp, canons_grp, stats_grp = best["grp"]
    rps_off, rps_on, rps_grp = (total / w
                                for w in (wall_off, wall_on, wall_grp))
    overhead_pct = (wall_on - wall_off) / wall_off * 100.0
    scaling = rps_grp / rps_on

    # the noise-free tax estimate: the per-command work cluster mode
    # adds to the serve path is exactly one cl.route(key) on an owned
    # slot (commands.py) — time it in-process and express it as a
    # fraction of the measured per-op budget
    from constdb_tpu.cluster import ClusterState, even_split
    rcl = ClusterState(0, even_split(1))
    sample = [k for k, _ in ops_per_conn[0][:2000]]
    route_iters = 50
    t0 = time.perf_counter()
    for _ in range(route_iters):
        for k in sample:
            rcl.route(k)
    route_ns = ((time.perf_counter() - t0)
                / (route_iters * len(sample)) * 1e9)
    route_pct = route_ns * rps_on / 1e7  # ns/op x op/s -> % of budget

    # oracle 1: the redirect-tax pair is the SAME workload on the same
    # connection schedule — reply streams and exports must match exactly
    replies_ok = hashes_on == hashes_off
    tax_export_ok = (strip_canonical_times(canons_on[0])
                     == strip_canonical_times(canons_off[0]))
    # oracle 2: the partition is lossless — per-group exports are
    # disjoint and their union is the single group's export
    grp_strips = [strip_canonical_times(c) for c in canons_grp]
    union: dict = {}
    disjoint = True
    for s in grp_strips:
        disjoint = disjoint and not (union.keys() & s.keys())
        union.update(s)
    union_ok = disjoint and union == strip_canonical_times(canons_on[0])
    # oracle 3: client partitioning agreed with server routing — the
    # router ran on every command yet never redirected
    redirects_ok = (stats_on[0]["redirects_sent"] == 0
                    and all(s["redirects_sent"] == 0 for s in stats_grp))

    print(f"[bench] migration leg: {mig_keys} keys, "
          f"slots [0, {mig_slots})", file=sys.stderr)
    mig = _cluster_migrate_leg(mig_keys, mig_slots)

    verified = (replies_ok and tax_export_ok and union_ok
                and redirects_ok and mig["ok"])
    print(f"[bench] {n_groups} groups: {rps_grp:,.0f} req/s vs 1 group "
          f"{rps_on:,.0f} req/s = {scaling:.2f}x; redirect tax "
          f"{overhead_pct:+.2f}% e2e best-of-{reps + tax_reps}, "
          f"{route_ns:.0f}ns/route = {route_pct:.2f}% of the per-op "
          f"budget (target <= 2%); migration "
          f"{mig['slots']} slots in {mig['wall_s']}s, "
          f"{mig['shipped_bytes']} B shipped = "
          f"{mig['shipped_vs_full']:.2%} of full state", file=sys.stderr)
    print(f"[bench] verify: replies {'OK' if replies_ok else 'MISMATCH'}, "
          f"tax export {'OK' if tax_export_ok else 'MISMATCH'}, "
          f"partition union {'OK' if union_ok else 'MISMATCH'} "
          f"({len(union)} keys), redirects "
          f"{'OK' if redirects_ok else 'NONZERO'}, migration "
          f"{'OK' if mig['ok'] else 'FAILED'}", file=sys.stderr)

    ncpu = os.cpu_count() or 1
    host_note = ""
    if ncpu < n_groups + 2:
        host_note = (
            f"this box has {ncpu} cores; a {n_groups}-group scaling leg "
            f"needs ~{n_groups + 2} (bench client + one core per group) "
            "to show scaling — every group server shares the core here, "
            "so the ratio measures capacity CONTENTION, not the "
            "architecture's ceiling.  The partition itself is pinned "
            "lossless by the union-canonical oracle and the zero-"
            "redirect check (plus tests/test_cluster.py), so the "
            ">=2.5x number applies on a >=4-core box.  The e2e "
            "redirect-tax number is CPU-credit noise-dominated here "
            "(identical legs swing +/-15% rep-to-rep, as in BENCH_r18) "
            "— route_check_pct_of_op is the core-count-independent "
            "measurement of the added per-command work.")
        print(f"[bench] host note: {host_note}", file=sys.stderr)

    out = {
        "metric": "cluster_group_scaling",
        "value": round(scaling, 2),
        "unit": "ratio",
        "mode": "cluster",
        "groups": n_groups,
        "ops": total,
        "conns": n_conns,
        "pipeline": pipeline,
        "serve_batch": serve_batch,
        "rps_1group": round(rps_on, 1),
        "rps_ngroup": round(rps_grp, 1),
        "rps_cluster_off": round(rps_off, 1),
        "redirect_overhead_pct": round(overhead_pct, 2),
        "route_check_ns": round(route_ns, 1),
        "route_check_pct_of_op": round(route_pct, 3),
        "redirect_target_pct": 2.0,
        "slots_owned": [s["slots_owned"] for s in stats_grp],
        "group_cmds": [s["cmds_processed"] for s in stats_grp],
        "migration": mig,
        "engine": engine_kind,
        "verified": verified,
        "host": host_fingerprint(),
        "host_note": host_note,
    }
    print(json.dumps(out))
    if not verified:
        sys.exit(1)


def main() -> None:
    import argparse

    ap = argparse.ArgumentParser(description="constdb-tpu snapshot-merge "
                                 "benchmark")
    ap.add_argument("--mode",
                    choices=["snapshot", "stream", "serve", "resync",
                             "tensor", "intake", "recover", "cluster",
                             "tracked"],
                    default="snapshot",
                    help="snapshot = bulk catch-up merge (default); "
                    "stream = steady-state replication apply through the "
                    "coalescing pull path; serve = pipelined client "
                    "serving over real sockets through the serve "
                    "coalescer; resync = digest-negotiated delta resync "
                    "vs full snapshot at configurable divergence; "
                    "tensor = resident device tensor-register merges + "
                    "reads vs the host reference at micro-batch size; "
                    "intake = the native intake plane — C intake stage "
                    "vs pure-Python serve legs + the REPLBATCH codec "
                    "legs (BENCH_r19); recover = fast-restart s/GB "
                    "curve — serial vs bulk merge rounds vs concurrent "
                    "shard segments vs checkpointed tail (BENCH_r20); "
                    "cluster = hash-slot partitioning — group-scaling "
                    "vs 1 group with a union-canonical oracle, the "
                    "redirect-check tax vs the pre-cluster node, and a "
                    "live slot-range migration's O(slot bytes) cost "
                    "(BENCH_r21); tracked = client-assisted caching — "
                    "K tracked near-cache clients vs K plain clients "
                    "on a hot-key 90:10 storm, server-side read-op "
                    "reduction with a zero-stale + stripped-export "
                    "oracle (BENCH_r22)")
    ap.add_argument("--frame-log", default=None,
                    help="stream mode: record the generated frame log "
                    "here (or replay it if the file exists)")
    ap.add_argument("--wire", action="store_true",
                    help="stream mode: run the socket-to-socket WIRE "
                    "legs instead of the in-process apply replay — "
                    "batch wire (REPLBATCH) vs per-frame wire vs the "
                    "intra-node baseline, plus a 3-node mesh "
                    "differential (BENCH_r14)")
    ap.add_argument("--resident", default=None,
                    help="snapshot/stream modes: comma list of 0|1 legs "
                    "(e.g. 0,1) — interleaves device-resident vs "
                    "host-path engine legs and records per-leg transfer "
                    "counters (BENCH_r12)")
    ap.add_argument("--serve-shards", default=None,
                    help="serve mode: comma list of shard counts (e.g. "
                    "1,2) — runs the shard-per-core scaling curve "
                    "instead of the coalesced-vs-per-command comparison")
    ap.add_argument("--aof", action="store_true",
                    help="serve mode: the DURABILITY legs — AOF off / "
                    "everysec / always interleaved on the same workload "
                    "(fsync tax), plus a timed recovery replay of the "
                    "always leg's log (s/GB) — BENCH_r17")
    ap.add_argument("--overload", action="store_true",
                    help="serve mode: the OVERLOAD leg — maxmemory set "
                    "below the workload's footprint; reports shed rate, "
                    "survival, and non-shed reply latency "
                    "(server/overload.py)")
    ap.add_argument("--read-pct", default=None,
                    help="serve mode: read-heavy legs at these read "
                    "percentages (e.g. '90,50') — coalesced+cache vs "
                    "cache-off vs the per-command baseline, "
                    "reply-hash + stripped-export oracle across all "
                    "legs (BENCH_r18.json)")
    ap.add_argument("--peers", type=int, default=0,
                    help="stream mode: the broadcast FAN-OUT legs — one "
                    "pusher driving 1..N real push loops, encode-once "
                    "cache on vs off interleaved, every peer "
                    "oracle-verified, plus the compressed-vs-plain "
                    "bulk-sync bytes leg (BENCH_r16)")
    args, _ = ap.parse_known_args()
    if args.mode == "stream":
        if args.peers:
            fanout_main(args)
        elif args.wire:
            wire_main(args)
        else:
            stream_main(args)
        return
    if args.mode == "serve":
        if args.aof:
            serve_aof_main(args)
        elif args.overload:
            serve_overload_main(args)
        elif args.serve_shards:
            serve_shards_main(args)
        elif args.read_pct:
            serve_read_main(args)
        else:
            serve_main(args)
        return
    if args.mode == "intake":
        intake_main(args)
        return
    if args.mode == "recover":
        recover_main(args)
        return
    if args.mode == "cluster":
        cluster_main(args)
        return
    if args.mode == "tracked":
        tracked_main(args)
        return
    if args.mode == "resync":
        resync_main(args)
        return
    if args.mode == "tensor":
        tensor_main(args)
        return
    # default = the BASELINE.json north-star scale (10M keys x 8 replicas);
    # the CPU baseline rate is measured on a capped key count (the per-row
    # engine's keys/sec is scale-flat, the 10M run would take ~20 min)
    n_keys = int(os.environ.get("CONSTDB_BENCH_KEYS", 10_000_000))
    n_rep = int(os.environ.get("CONSTDB_BENCH_REPLICAS", 8))
    n_cpu = min(n_keys, int(os.environ.get("CONSTDB_BENCH_CPU_KEYS",
                                           min(n_keys, 200_000))))
    chunk = int(os.environ.get("CONSTDB_BENCH_CHUNK", 1 << 17))

    print(f"[bench] workload: {n_keys} keys x {n_rep} replicas, "
          f"{chunk}-key chunks (cpu baseline on {n_cpu} keys)",
          file=sys.stderr)

    # native tables first: BOTH engines (and the oracle) resolve keys
    # through them, and the pure-Python fallback tiers dominated the
    # round-5 host dispatch profile
    ensure_native()

    t0 = time.perf_counter()
    cpu_chunks = chunk_batches(make_workload(n_cpu, n_rep, seed=7), chunk)
    cpu_t, _ = time_engine(CpuMergeEngine, cpu_chunks, repeats=1)
    cpu_rate = n_cpu / cpu_t
    print(f"[bench] cpu engine: {cpu_t:.3f}s on {n_cpu} keys "
          f"= {cpu_rate:,.0f} keys/s (workload gen+run "
          f"{time.perf_counter() - t0:.1f}s)", file=sys.stderr)

    # Workload gen BEFORE any in-process jax init: the verify oracle forks
    # HERE (forking a JAX-threaded process is unsafe) and then idles until
    # the timed runs complete.
    t0 = time.perf_counter()
    batches = make_workload(n_keys, n_rep, seed=7)
    print(f"[bench] workload gen: {time.perf_counter() - t0:.1f}s",
          file=sys.stderr)
    verify_on = os.environ.get("CONSTDB_BENCH_VERIFY", "1") != "0"
    oracle = start_oracle(batches, n_keys) if verify_on else None

    # bench context: plenty of host RAM is provisioned, so let the win
    # pool cover the whole run — one flush, minimum link round-trips
    # (servers keep the conservative default; see engine pool_flush_bytes)
    os.environ.setdefault("CONSTDB_POOL_FLUSH_MB", "8192")
    fold = os.environ.get("CONSTDB_BENCH_FOLD", "auto")
    # JAX initializes HERE, after the oracle forked, in the one process
    # that holds the chip
    jax, device = require_device(fold)
    from constdb_tpu.engine.tpu import TpuMergeEngine

    t0 = time.perf_counter()
    chunks = chunk_batches(batches, chunk)
    print(f"[bench] chunking: {time.perf_counter() - t0:.1f}s "
          f"({len(chunks)} chunks)", file=sys.stderr)
    # default to the grouped shape: the engine's hierarchical host combine
    # folds each aligned replica-cluster and concatenates the disjoint
    # folds, so a group spanning several key ranges still collapses to ONE
    # device call per family — the same cadence the replica link uses in
    # production (link.py apply_group)
    group = int(os.environ.get("CONSTDB_BENCH_GROUP", str(4 * n_rep)))
    from constdb_tpu.store.sharded_keyspace import ShardedKeySpace
    if args.resident is not None:
        snapshot_resident_legs(args, chunks, batches, n_keys, n_rep, group,
                               fold, oracle, verify_on, cpu_rate, device)
        return
    # one process holds the chip, so the device leg is the single
    # keyspace: the sharded facade's degenerate n_shards == 1 path
    # (byte-identical to driving the engine directly —
    # tests/test_sharded_keyspace.py pins it), kept for its per-shard
    # host_secs.  Process-mode shard workers are CPU engines
    # (parallel/host_pool.py) and no part of a device leg.
    shards = 1
    sks = ShardedKeySpace(
        n_shards=1, group=group,
        engine_factory=lambda: TpuMergeEngine(resident=True,
                                              dense_fold=fold))
    # best-of-2 even at the 10M scale: the driver records a single bench
    # invocation, and one unlucky run (shared box) should not be the
    # round's number
    tpu_t = float("inf")
    for _ in range(2):
        sks.reset()
        t0 = time.perf_counter()
        for c in chunks:
            sks.submit(c)
        sks.flush()
        tpu_t = min(tpu_t, time.perf_counter() - t0)
    dev_store = sks
    shard_secs = sks.host_secs_per_shard()  # last run (reset clears)
    folds = sum(s.get("folds", 0) for s in shard_secs)
    bytes_h2d = sum(s.get("bytes_h2d", 0) for s in shard_secs)
    bytes_d2h = sum(s.get("bytes_d2h", 0) for s in shard_secs)
    fam = {}
    stg = {}
    for s in shard_secs:
        for k, v in s.get("family_secs", {}).items():
            fam[k] = fam.get(k, 0.0) + v
        for k, v in s.get("stage_secs", {}).items():
            stg[k] = stg.get(k, 0.0) + v
    pipeline = os.environ.get("CONSTDB_PIPELINE", "1") != "0"
    rate = n_keys / tpu_t
    # wake the (pre-forked, idle) oracle worker NOW: its CPU replay
    # overlaps the merge epilogue (link probe + device-store canonical
    # extraction) instead of running serially after everything else
    oracle_err = None
    if oracle is not None:
        try:
            oracle[1].send("go")
        except OSError as e:  # worker died (e.g. OOM) during the runs —
            # the measured numbers must still reach the JSON line
            oracle_err = str(e) or type(e).__name__
            print(f"[bench] WARNING: verify worker died before go ({e}); "
                  f"verification unavailable", file=sys.stderr)
    t_verify0 = time.perf_counter()
    print(f"[bench] device engine (resident, {jax.default_backend()}, "
          f"group={group}, shards={shards}, folds={folds}): "
          f"{tpu_t:.3f}s on {n_keys} keys = {rate:,.0f} keys/s",
          file=sys.stderr)
    if fam:
        breakdown = " ".join(f"{k}={v:.3f}s" for k, v in sorted(fam.items()))
        print(f"[bench] stage breakdown (last run, critical-path host "
              f"times; flush includes blocking downloads): {breakdown}",
              file=sys.stderr)
    if stg and pipeline:
        overlapped = " ".join(f"{k}={v:.3f}s" for k, v in sorted(stg.items()))
        print(f"[bench] staging (background worker, overlaps device "
              f"compute — NOT additive with the breakdown above): "
              f"{overlapped}", file=sys.stderr)

    out = {
        "metric": "snapshot_merge_keys_per_sec",
        "value": round(rate, 1),
        "unit": "keys/sec",
        "vs_baseline": round(rate / cpu_rate, 2),
        "keys": n_keys,
        "replicas": n_rep,
        "wall_s": round(tpu_t, 2),
        "folds": folds,
        "device": device,
        "host_secs": {k: round(v, 3) for k, v in sorted(fam.items())},
        "stage_secs": {k: round(v, 3) for k, v in sorted(stg.items())},
        "pipeline": pipeline,
        "shards": shards,
        "host": host_fingerprint(),
    }
    # per-shard host seconds: the whole point of the sharded merge is
    # that cnt/el/flush SPLIT — make that visible per worker (length 1
    # when the degenerate single-shard path ran)
    out["shard_host_secs"] = [
        {k: round(v, 3) for k, v in sorted(s["family_secs"].items())}
        for s in shard_secs]
    out["shard_stage_secs"] = [
        {k: round(v, 3) for k, v in sorted(s["stage_secs"].items())}
        for s in shard_secs]

    # ------- measured link ceiling: what fraction of the wall is transfer
    up_bw, down_bw = probe_link(jax)
    link_secs = bytes_h2d / up_bw + bytes_d2h / down_bw
    out["bytes_h2d"] = bytes_h2d
    out["bytes_d2h"] = bytes_d2h
    out["link_bw_up_mbps"] = round(up_bw / 1e6, 1)
    out["link_bw_down_mbps"] = round(down_bw / 1e6, 1)
    out["link_secs"] = round(link_secs, 2)
    # fraction of the wall explained by moving this run's bytes at the
    # MEASURED link bandwidth; the reciprocal rate is the link-imposed
    # ceiling for this byte footprint
    out["pct_of_link_ceiling"] = round(link_secs / tpu_t, 3)
    if link_secs > 0:
        out["ceiling_keys_per_sec"] = round(n_keys / link_secs, 1)
    print(f"[bench] link: up {up_bw / 1e6:,.0f} MB/s down "
          f"{down_bw / 1e6:,.0f} MB/s; moved h2d "
          f"{bytes_h2d / 1e6:,.0f} MB d2h {bytes_d2h / 1e6:,.0f} MB "
          f"-> link floor {link_secs:.1f}s of {tpu_t:.1f}s wall "
          f"({100 * link_secs / tpu_t:.0f}%)", file=sys.stderr)

    # ------- on-hardware correctness: oracle-verify a ~100k-key subsample.
    # The oracle replay has been running in the forked worker since right
    # after the timed merge; the parent extracts the device store's
    # canonical slice in parallel and only then joins.
    verified = None
    if verify_on:
        sub_keys = subsample_keys(batches[0].keys, n_keys)
        got = dev_store.canonical(keys=sub_keys)
        n_diff = None
        if oracle_err is not None:
            out["verify_error"] = oracle_err
        elif oracle is not None:
            p, rx = oracle
            try:
                want = rx.recv()
            except (EOFError, OSError) as e:
                # a killed worker (e.g. OOM) must not cost the whole run's
                # JSON line — record verification as unavailable instead
                want = e
            finally:
                p.join()
            if isinstance(want, BaseException):
                # same protection for an error the worker itself hit and
                # shipped back (e.g. MemoryError mid-replay)
                print(f"[bench] WARNING: verify worker failed "
                      f"({type(want).__name__}: {want}); verification "
                      f"unavailable", file=sys.stderr)
                out["verify_error"] = \
                    f"{type(want).__name__}: {want}" .strip(": ")
            else:
                n_diff = compare_canonical(got, want)
        else:  # pragma: no cover - fork unavailable
            n_diff = compare_canonical(got, oracle_canonical(batches, n_keys))
        verified = None if n_diff is None else n_diff == 0
        if verified is not None:
            print(f"[bench] verify: {'OK' if verified else 'MISMATCH'} on "
                  f"{len(sub_keys)} sampled keys ({n_diff} diffs, "
                  f"{time.perf_counter() - t_verify0:.1f}s overlapped with "
                  f"the epilogue)", file=sys.stderr)
        out["verified"] = verified
        out["verify_keys"] = len(sub_keys)

    dev_store.close()  # shard workers / engine pools
    print(json.dumps(out))
    if verified is False:
        sys.exit(1)


if __name__ == "__main__":
    main()
