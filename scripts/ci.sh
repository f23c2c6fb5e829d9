#!/usr/bin/env bash
# The full gate, one command:
#   1. invariant lint (baseline mode)        — scripts/lint.sh
#   2. tier-1 suite + slow-marker audit      — scripts/audit_markers.sh
#      (same pytest selection as scripts/t1.sh, plus the per-test
#      budget check, so the suite runs ONCE for both purposes)
# Exit code is the first failure's; each stage prints its own verdict.
set -uo pipefail
cd "$(dirname "$0")/.."

echo "== lint (baseline mode) =="
./scripts/lint.sh || exit $?

echo
echo "== lint baseline ratchet =="
# Retired debt must not silently regrow.  PR 4 retired the last 17
# per-node findings, so the per-node rules ratchet at ZERO: any
# baselined finding from them fails here.  The flow-sensitive rules
# (AWAIT-ATOMICITY / LOCK-DISCIPLINE / CUT-ORDERING) reason about
# interleavings, so a deliberate, documented exception is a legitimate
# outcome — for THOSE rules only, a baselined key is allowed iff it
# carries a tracking note in baseline.json's notes map (a muted alarm
# nobody can explain is still a failure).  The live tree is currently
# clean either way; this gate is what keeps new debt honest.
python - <<'EOF' || exit $?
import json, sys
FLOW_RULES = ("AWAIT-ATOMICITY", "LOCK-DISCIPLINE", "CUT-ORDERING")
base = json.load(open("constdb_tpu/analysis/baseline.json"))
findings = base.get("findings", {})
notes = base.get("notes", {})
bad = []
for key in sorted(findings):
    rule = key.split(":", 1)[0]
    if rule not in FLOW_RULES:
        bad.append(f"  {key}\n    per-node rules ratchet at zero — fix "
                   f"the finding, do not baseline it")
    elif not any(key.startswith(p) for p in notes):
        bad.append(f"  {key}\n    baselined flow finding has no tracking "
                   f"note (add one under notes in baseline.json)")
flow = sum(v for k, v in findings.items()
           if k.split(":", 1)[0] in FLOW_RULES)
print(f"baselined findings: {sum(findings.values())} "
      f"({flow} noted flow-rule, ratchet: 0 for all other rules)")
if bad:
    print("ci.sh: baseline violates the ratchet:")
    print("\n".join(bad))
    sys.exit(1)
EOF

echo
echo "== sanitizer fuzz gate (make -C native san + scripts/fuzz_native.py) =="
# Memory-safety smoke for the four untrusted-byte C scanners
# (resp_parse, intake_scan, wire blob pack/unpack, aof_scan): rebuild
# the extension under ASan+UBSan (native/build/san/, never installed
# into the package) and replay the tier-1 fuzz corpora plus seeded
# mutations through it — any sanitizer report aborts the driver
# non-zero.  The sanitized .so links its runtimes dynamically, so the
# gate needs the toolchain's libasan/libubsan; where they are missing
# the stage SKIPS LOUDLY rather than pretending the check ran.
SAN_LIBS=""
if command -v g++ >/dev/null 2>&1; then
    for lib in libasan.so libubsan.so; do
        p="$(g++ -print-file-name=$lib 2>/dev/null)"
        [ -n "$p" ] && [ "$p" != "$lib" ] && [ -e "$p" ] && \
            SAN_LIBS="$SAN_LIBS $p"
    done
fi
if [ "$(echo $SAN_LIBS | wc -w)" -ne 2 ]; then
    echo "ci.sh: SKIPPING sanitizer fuzz gate — this toolchain lacks the"
    echo "       dynamic ASan/UBSan runtimes (found:${SAN_LIBS:- none})."
    echo "       The untrusted-byte scanners are NOT memory-checked on"
    echo "       this builder; run ci.sh where g++ ships libasan+libubsan."
else
    make -s -C native san || exit $?
    LD_PRELOAD="${SAN_LIBS# }" ASAN_OPTIONS=detect_leaks=0 \
    JAX_PLATFORMS=cpu timeout -k 10 420 python scripts/fuzz_native.py || {
        echo "ci.sh: sanitizer fuzz gate FAILED — ASan/UBSan report (or"
        echo "       driver error) replaying the scanner corpora; rerun"
        echo "       scripts/fuzz_native.py under the LD_PRELOAD above to"
        echo "       reproduce deterministically"
        exit 1
    }
fi

echo
echo "== chip smoke self-test (chip_smoke.py --engine cpu, toy size) =="
# the smoke's own logic off the chip: two real servers on two seeded
# replica snapshots, full sync (sharded-ingest decision taken), served
# traffic held to the parent's model, reboot read-back — and one
# deliberately wrong expectation must fail it.  Proves nothing about
# the device (that is `python chip_smoke.py` through the chip tool).
JAX_PLATFORMS=cpu timeout -k 10 600 python -m pytest \
    tests/test_chip_smoke.py -q -p no:cacheprovider || exit $?

echo
echo "== native intake smoke (make -C native + bench --mode intake) =="
# the C intake plane end to end: rebuild the extension from source (the
# ABI stamp in the .so refuses stale builds loudly), then a tiny
# oracle-verified run of the three serve legs over real sockets — C
# intake stage / pure-Python drain (CONSTDB_NATIVE_INTAKE=0) / full
# fallback (CONSTDB_NO_NATIVE=1) — plus the REPLBATCH codec legs
# (native pack/unpack vs pure, encoded bytes byte-identical).  Reply
# streams and stripped exports must match across ALL legs and the
# native leg must PROVE it engaged (INFO gauge native_intake_chunks);
# the differential suites proper run inside tier-1
# (tests/test_native_intake.py / tests/test_resp_fuzz.py).
make -s -C native || exit $?
JAX_PLATFORMS=cpu CONSTDB_BENCH_SERVE_OPS=6000 CONSTDB_BENCH_SERVE_CONNS=2 \
CONSTDB_BENCH_SERVE_REPS=1 CONSTDB_BENCH_INTAKE_FRAMES=6000 \
    timeout -k 10 300 python bench.py --mode intake \
    > /tmp/_ci_intake.json || exit $?
python - <<'EOF' || exit $?
import json
out = json.load(open("/tmp/_ci_intake.json"))
assert out["verified"], "intake smoke failed oracle verification"
legs = out["legs"]
assert legs["native"]["native_intake_chunks"] > 0, \
    "native intake never engaged"
assert legs["pure"]["native_intake_chunks"] == 0, \
    "pinned pure leg ran the native stage"
assert legs["nonative"]["native_intake_chunks"] == 0, \
    "CONSTDB_NO_NATIVE leg ran the native stage"
for name, leg in legs.items():
    assert leg["replies_ok"] and leg["export_ok"], \
        f"intake leg {name} diverged from the native reference"
assert out["wire"]["verified"], "wire codec legs mismatched"
print("intake smoke verified:",
      f"{legs['native']['rps']:,.0f} req/s native /",
      f"{legs['pure']['rps']:,.0f} pure /",
      f"{legs['nonative']['rps']:,.0f} no-native,",
      f"{legs['native']['native_intake_chunks']} native chunks,",
      f"wire {out['wire']['encode_speedup']}x enc "
      f"{out['wire']['decode_speedup']}x dec")
EOF
# the stream smoke's fallback leg: the same wire protocol run with NO
# native tier anywhere (CONSTDB_NO_NATIVE=1) must still pass its full
# oracle — pure pack/unpack is the reference the native codec is pinned
# against, so a fallback regression fails here, not in production
JAX_PLATFORMS=cpu CONSTDB_NO_NATIVE=1 CONSTDB_BENCH_FRAMES=3000 \
CONSTDB_BENCH_WIRE_REPS=1 \
    timeout -k 10 300 python bench.py --mode stream --wire \
    > /tmp/_ci_wire_nonative.json || exit $?
python - <<'EOF' || exit $?
import json
out = json.load(open("/tmp/_ci_wire_nonative.json"))
assert out["verified"], "CONSTDB_NO_NATIVE wire smoke failed its oracle"
print("no-native wire smoke verified:",
      f"batch leg {out['legs'][0]['fps']} fps, pure codec end to end")
EOF

echo
echo "== serve-shards smoke (bench --mode serve --serve-shards 2) =="
# tiny oracle-verified run of the shard-per-core serving plane over
# real sockets: reply streams + visible-value export of every shard
# count must match the shards=1 leg (the differential suite proper runs
# inside tier-1 — tests/test_serve_shards.py)
JAX_PLATFORMS=cpu CONSTDB_BENCH_SERVE_OPS=3000 CONSTDB_BENCH_SERVE_CONNS=2 \
CONSTDB_BENCH_SERVE_REPS=1 \
    timeout -k 10 300 python bench.py --mode serve --serve-shards 2 \
    > /tmp/_ci_serve_shards.json || exit $?
python - <<'EOF' || exit $?
import json
out = json.load(open("/tmp/_ci_serve_shards.json"))
assert out["verified"], "serve-shards smoke failed oracle verification"
print("serve-shards smoke verified:",
      [(leg["serve_shards"], leg["rps"]) for leg in out["serve_shards_curve"]])
EOF

echo
echo "== cluster smoke (bench --mode cluster) =="
# tiny oracle-verified run of the hash-slot partitioning legs: the
# same op stream partitioned by slot owner must union back to the
# single group's visible-value export with zero redirects (client
# partitioning and server routing agree on the slot math), the
# redirect-tax pair must match reply-for-reply, and a live slot-range
# migration must flip ownership with the moved keys serving from the
# target at O(slot bytes) shipped (the differential suite proper runs
# inside tier-1 — tests/test_cluster.py; the partition/flap/
# resurrection convergence cells run in the chaos smoke below)
JAX_PLATFORMS=cpu CONSTDB_BENCH_CLUSTER_OPS=4000 \
CONSTDB_BENCH_CLUSTER_CONNS=2 CONSTDB_BENCH_CLUSTER_GROUPS=2 \
CONSTDB_BENCH_CLUSTER_REPS=1 CONSTDB_BENCH_CLUSTER_TAX_REPS=1 \
CONSTDB_BENCH_CLUSTER_MIG_KEYS=2000 CONSTDB_BENCH_CLUSTER_MIG_SLOTS=16 \
    timeout -k 10 300 python bench.py --mode cluster \
    > /tmp/_ci_cluster.json || exit $?
python - <<'EOF' || exit $?
import json
out = json.load(open("/tmp/_ci_cluster.json"))
assert out["verified"], "cluster smoke failed oracle verification"
mig = out["migration"]
assert mig["ok"] and mig["slots"] == 16, mig
assert mig["shipped_vs_full"] < 0.25, \
    f"migration shipped {mig['shipped_vs_full']:.0%} of the full state"
assert out["route_check_pct_of_op"] < 5.0, \
    f"route check at {out['route_check_pct_of_op']}% of the op budget"
print("cluster smoke verified:",
      f"{out['groups']} groups {out['value']}x,",
      f"route check {out['route_check_ns']}ns,",
      f"migration {mig['slots']} slots =",
      f"{mig['shipped_vs_full']:.1%} of full state shipped")
EOF

echo
echo "== read-path smoke (bench --mode serve --read-pct 90) =="
# tiny oracle-verified run of the coalesced read plane over real
# sockets: a mixed 90:10 pipelined workload on the coalesced+cache,
# cache-off, and per-command legs — every reply stream and the
# timestamp-stripped export must match the per-command reference
# byte-for-byte (a stale cached serve is an oracle MISMATCH, not a
# slowdown), the read planner must actually engage, and the cache must
# serve real hits (the differential suite proper runs inside tier-1 —
# tests/test_read_path.py)
JAX_PLATFORMS=cpu CONSTDB_BENCH_SERVE_OPS=6000 CONSTDB_BENCH_SERVE_CONNS=2 \
CONSTDB_BENCH_SERVE_REPS=1 \
    timeout -k 10 300 python bench.py --mode serve --read-pct 90 \
    > /tmp/_ci_read.json || exit $?
python - <<'EOF' || exit $?
import json
out = json.load(open("/tmp/_ci_read.json"))
assert out["verified"], "read-path smoke failed oracle verification"
leg = out["curve"][0]
assert leg["cache"]["replies_ok"] and leg["nocache"]["replies_ok"], \
    "stale replies on a coalesced read leg"
assert leg["cache"]["serve_reads_coalesced"] > 0, \
    "read planner never engaged"
assert leg["cache"]["read_cache_hits"] > 0, "reply cache never hit"
assert leg["nocache"]["read_cache_hits"] == 0, \
    "disabled cache served hits"
print("read-path smoke verified:",
      f"{leg['cache']['rps']:,.0f} req/s cached /",
      f"{leg['percmd']['rps']:,.0f} per-command =",
      f"{leg['speedup_vs_percmd']}x, hit rate {leg['cache_hit_rate']},",
      f"{leg['cache']['serve_reads_coalesced']} planned reads")
EOF

echo
echo "== tracking smoke (bench --mode tracked) =="
# tiny oracle-verified run of the client-assisted caching tier over
# real sockets: K tracked RESP3 near-cache clients vs K plain clients
# on the same deterministic hot-key 90:10 storm.  The server must have
# actually pushed invalidations (tracking_invalidations_sent > 0, no
# loud demotions), every entry still resident in a near-cache at
# quiesce must equal a direct server read (zero-stale), the stripped
# exports must match across legs, and the reads that reached the
# server must shrink by the advertised floor (the unit/property suites
# proper run inside tier-1 — tests/test_tracking.py /
# tests/test_resp_fuzz.py; the track-partition chaos cell rides the
# chaos smoke below, the full tracking cell set the slow matrix)
JAX_PLATFORMS=cpu CONSTDB_BENCH_TRACKED_OPS=8000 \
CONSTDB_BENCH_TRACKED_REPS=1 \
    timeout -k 10 300 python bench.py --mode tracked \
    > /tmp/_ci_tracked.json || exit $?
python - <<'EOF' || exit $?
import json
out = json.load(open("/tmp/_ci_tracked.json"))
assert out["verified"], "tracking smoke failed oracle verification"
trk = out["tracked"]
assert trk["tracking_invalidations_sent"] > 0, \
    "server never pushed an invalidation"
assert trk["tracking_demotions"] == 0, "a tracker was demoted"
assert trk["stale_entries"] == 0, "near-cache served stale entries"
assert out["export_ok"], "tracked leg diverged from the plain leg"
assert out["value"] >= 5.0, \
    f"server-side read reduction collapsed: {out['value']}x"
print("tracking smoke verified:",
      f"{out['plain']['server_read_ops']} -> {trk['server_read_ops']}",
      f"server reads = {out['value']}x, hit rate",
      f"{trk['near_cache_hit_rate']},",
      f"{trk['tracking_invalidations_sent']} invalidations pushed")
EOF

echo
echo "== resync smoke (bench --mode resync) =="
# tiny oracle-verified run of the digest-negotiated delta resync vs the
# full-snapshot leg through the REAL push loop: both pullers must
# converge to the pusher's canonical export + full-state digest at
# every divergence fraction (the differential suite proper runs inside
# tier-1 — tests/test_delta_sync.py)
JAX_PLATFORMS=cpu CONSTDB_BENCH_RESYNC_KEYS=20000 \
CONSTDB_BENCH_RESYNC_VERIFY=5000 CONSTDB_BENCH_RESYNC_FRACS=0.01 \
    timeout -k 10 300 python bench.py --mode resync \
    > /tmp/_ci_resync.json || exit $?
python - <<'EOF' || exit $?
import json
out = json.load(open("/tmp/_ci_resync.json"))
assert out["verified"], "resync smoke failed oracle verification"
print("resync smoke verified:",
      [(leg["frac"], leg["bytes_ratio"]) for leg in out["curve"]])
EOF

echo
echo "== wire smoke (bench --mode stream --wire) =="
# tiny oracle-verified run of the batch wire protocol over a real
# socket pair: REPLBATCH legs vs the per-frame wire on the same frame
# log, both receivers byte-identical to the per-frame CPU oracle, the
# 3-node mesh differential converged, and the columnar payload actually
# paying for itself on the wire (the differential suite proper runs
# inside tier-1 — tests/test_wire_batch.py / test_repl_capabilities.py)
JAX_PLATFORMS=cpu CONSTDB_BENCH_FRAMES=5000 CONSTDB_BENCH_WIRE_REPS=1 \
    timeout -k 10 300 python bench.py --mode stream --wire \
    > /tmp/_ci_wire.json || exit $?
python - <<'EOF' || exit $?
import json
out = json.load(open("/tmp/_ci_wire.json"))
assert out["verified"], "wire smoke failed oracle verification"
assert out["wire_bytes_ratio"] >= 2.0, \
    f"columnar wire stopped paying: {out['wire_bytes_ratio']}x bytes"
assert out["mesh_differential"]["converged"], "wire mesh diverged"
assert out["legs"][0]["wire_demotions"] == 0, "wire codec demoted"
print("wire smoke verified:",
      f"{out['speedup_vs_per_frame_wire']}x frames/s,",
      f"{out['wire_bytes_ratio']}x wire bytes,",
      f"batch leg {out['legs'][0]['fps']} fps")
EOF

echo
echo "== broadcast smoke (bench --mode stream --peers 4) =="
# tiny oracle-verified run of the broadcast plane: one pusher fanning
# out to 4 peers with the encode-once cache on vs off (every peer's
# captured stream applied + export-compared against the per-frame CPU
# oracle), plus the compressed-vs-plain bulk-sync bytes leg (the
# differential suites proper run inside tier-1 —
# tests/test_encode_cache.py / tests/test_wire_compress.py)
JAX_PLATFORMS=cpu CONSTDB_BENCH_FRAMES=5000 CONSTDB_BENCH_FANOUT_REPS=1 \
CONSTDB_BENCH_FSYNC_KEYS=20000 CONSTDB_BENCH_FSYNC_REPLICAS=2 \
    timeout -k 10 300 python bench.py --mode stream --peers 4 \
    > /tmp/_ci_fanout.json || exit $?
python - <<'EOF' || exit $?
import json
out = json.load(open("/tmp/_ci_fanout.json"))
assert out["verified"], "broadcast smoke failed oracle verification"
top = out["curve"][-1]
assert top["cache_on"]["cache_hit_rate"] >= 0.7, \
    f"encode-once reuse collapsed: {top['cache_on']['cache_hit_rate']}"
assert top["speedup_vs_cache_off"] >= 1.5, \
    f"fan-out stopped paying: {top['speedup_vs_cache_off']}x"
fs = out["fullsync"]
assert fs["bytes_ratio_vs_uncompressed"] <= 0.4, \
    f"bulk compression stopped paying: {fs['bytes_ratio_vs_uncompressed']}"
print("broadcast smoke verified:",
      f"{top['speedup_vs_cache_off']}x agg fan-out at 4 peers,",
      f"hit rate {top['cache_on']['cache_hit_rate']},",
      f"bulk {fs['bytes_ratio_vs_uncompressed']}x of uncompressed")
EOF

echo
echo "== resident smoke (pallas-interpret snapshot + stream) =="
# tiny oracle-verified runs of the device-resident steady path with the
# Pallas kernels forced through the interpreter: a kernel that drifts
# from the host semantics fails HERE on CPU-only builders, not on the
# first real-TPU round.  Snapshot leg = bulk catch-up through the fold
# kernels; stream leg = in-place micro merges through the resident XLA
# scatter, which a forced fold leaves as it is (the differential suite
# proper runs inside tier-1 — tests/test_resident_steady.py /
# tests/test_pallas_dense.py).
JAX_PLATFORMS=cpu CONSTDB_BENCH_KEYS=20000 CONSTDB_BENCH_REPLICAS=2 \
CONSTDB_BENCH_CPU_KEYS=5000 CONSTDB_BENCH_FOLD=pallas-interpret \
    timeout -k 10 300 python bench.py --mode snapshot --resident 1 \
    > /tmp/_ci_resident_snap.json || exit $?
JAX_PLATFORMS=cpu CONSTDB_BENCH_FRAMES=3000 CONSTDB_BENCH_STREAM_KEYS=500 \
CONSTDB_BENCH_APPLY_BATCH=256 CONSTDB_BENCH_FOLD=pallas-interpret \
    timeout -k 10 300 python bench.py --mode stream --resident 1 \
    > /tmp/_ci_resident_stream.json || exit $?
python - <<'EOF' || exit $?
import json
snap = json.load(open("/tmp/_ci_resident_snap.json"))
assert snap["verified"], "resident snapshot smoke failed oracle verification"
stream = json.load(open("/tmp/_ci_resident_stream.json"))
assert stream["verified"], "resident stream smoke failed oracle verification"
leg = stream["resident_curve"][0]
assert leg["dev_rounds_resident"] > 0, "steady path never engaged"
assert 0 < leg["flush_rows_downloaded"] < leg["flush_rows_full_equiv"], \
    "flush downloads were not partial"
print("resident smoke verified: snapshot",
      snap["resident_curve"][0]["keys_per_sec"], "keys/s; stream",
      leg["fps"], "fps,", leg["dev_rounds_resident"], "resident rounds,",
      f"{leg['flush_rows_downloaded']}/{leg['flush_rows_full_equiv']}",
      "rows flushed")
EOF

echo
echo "== tensor smoke (bench --mode tensor, pallas-interpret) =="
# tiny oracle-verified run of the tensor-register family with the
# reduce kernels forced through the Pallas interpreter: device-resident
# merges + reads must stay BIT-identical to the host reference (the
# canonical-order law) and the steady path must actually engage
# (dev_rounds_resident / tns_dev_rows) — the differential suite proper
# runs inside tier-1 (tests/test_tensor_family.py).
JAX_PLATFORMS=cpu CONSTDB_BENCH_TNS_KEYS=8 CONSTDB_BENCH_TNS_ELEMS=4096 \
CONSTDB_BENCH_TNS_ROUNDS=6 CONSTDB_BENCH_TNS_BATCH=32 \
CONSTDB_BENCH_TNS_REPS=1 CONSTDB_BENCH_TNS_STRATS=avg,trimmed-mean \
CONSTDB_BENCH_FOLD=pallas-interpret \
    timeout -k 10 300 python bench.py --mode tensor \
    > /tmp/_ci_tensor.json || exit $?
python - <<'EOF' || exit $?
import json
out = json.load(open("/tmp/_ci_tensor.json"))
assert out["verified"], "tensor smoke failed oracle verification"
for leg in out["curve"]:
    assert leg["dev_rounds_resident"] > 0, \
        f"tensor steady path never engaged ({leg['strategy']})"
    assert leg["tns_dev_rows"] > 0 and leg["tns_host_rows"] == 0, \
        f"tensor rows did not ride the device path ({leg['strategy']})"
print("tensor smoke verified:",
      [(leg["strategy"], leg["speedup"]) for leg in out["curve"]])
EOF

echo
echo "== overload smoke (bench --mode serve --overload + chaos resource cells) =="
# a memory-capped node under a firehose pipeline: survives, sheds with
# the exact -OOM error, non-shed reply latency stays bounded, and the
# accounting gauges match the pressure (server/overload.py).  Then the
# chaos resource cells certify the convergence half: shed writes were
# never partially applied or replicated, replication intake stayed
# admitted, a peer converges byte-identical to the CPU reference, a
# stalled client is cut at the outbuf cap, and a stalled peer recovers
# through the repl-window pause -> eviction -> resync path.
JAX_PLATFORMS=cpu CONSTDB_BENCH_OVL_OPS=12000 \
    timeout -k 10 300 python bench.py --mode serve --overload \
    > /tmp/_ci_overload.json || exit $?
python - <<'EOF' || exit $?
import json
out = json.load(open("/tmp/_ci_overload.json"))
assert out["verified"], "overload smoke failed verification"
assert out["survived"] and out["other_errors"] == 0
assert out["shed"] > 0 and out["landed"] > 0, "no shed/landed split"
assert out["reply_p99_ms"] < 1000, \
    f"non-shed p99 {out['reply_p99_ms']}ms — shedding is livelocking"
print("overload smoke verified:",
      f"{out['value']:.0%} shed at {out['rps']} req/s,",
      f"p99 {out['reply_p99_ms']}ms, state {out['overload_state']}")
EOF
JAX_PLATFORMS=cpu timeout -k 10 300 python -m constdb_tpu.chaos \
    --resource --seed 7 || exit $?

echo
echo "== durability smoke (AOF kill -9 + bench --mode serve --aof) =="
# a REAL server process with the durable op log under fsync=always:
# firehose it over a socket, kill -9 mid-stream, restart from the
# node's own log, and oracle-compare — every acknowledged write must
# be present (or superseded by a LATER write of the same key that also
# survived), the recovery gauges must report the replay, and a second
# clean restart must be idempotent.  Then the tiny bench legs verify
# off/everysec/always exports match and the recovery replay
# round-trips (tests/test_oplog.py runs the differential suites in
# tier-1; the chaos kill9/torn cells run in the chaos smoke below).
JAX_PLATFORMS=cpu timeout -k 10 300 python - <<'EOF' || exit $?
import asyncio, os, signal, socket, subprocess, sys, tempfile, time

async def main():
    with tempfile.TemporaryDirectory(prefix="constdb-dur-") as work:
        s = socket.socket(); s.bind(("127.0.0.1", 0))
        port = s.getsockname()[1]; s.close()
        args = [sys.executable, "-m", "constdb_tpu.bin.server",
                "--port", str(port), "--work-dir", work,
                "--aof", "--aof-fsync", "always", "--node-id", "1"]
        env = dict(os.environ, JAX_PLATFORMS="cpu")
        proc = subprocess.Popen(args, env=env)
        from constdb_tpu.chaos.cluster import Client
        c = Client()
        for _ in range(100):
            try:
                await c.connect(f"127.0.0.1:{port}"); break
            except OSError:
                await asyncio.sleep(0.1)
        else:
            raise SystemExit("server never came up")
        # firehose: sequential acked writes (the client-side journal),
        # then a pipelined burst we kill the server in the middle of
        acked = {}
        for i in range(400):
            k = f"k{i % 16}"
            r = await c.cmd("set", k, f"v{i:06d}")
            acked[k] = i
        from constdb_tpu.resp.codec import encode_msg
        from constdb_tpu.resp.message import Arr, Bulk
        buf = bytearray()
        for i in range(400, 2400):
            buf += encode_msg(Arr([Bulk(b"set"), Bulk(b"k%d" % (i % 16)),
                                   Bulk(b"v%06d" % i)]))
        c.writer.write(bytes(buf))
        await c.writer.drain()
        # count replies until the kill lands mid-stream (the short
        # sleep lets the server get INTO the burst first, so the kill
        # really is mid-write, not before it)
        got = 0
        t0 = time.monotonic()
        await asyncio.sleep(0.05)
        os.kill(proc.pid, signal.SIGKILL)
        try:
            while got < 2000 and time.monotonic() - t0 < 5:
                data = await asyncio.wait_for(c.reader.read(1 << 16), 2.0)
                if not data:
                    break
                c.parser.feed(data)
                while c.parser.next_msg() is not None:
                    acked[f"k{(400 + got) % 16}"] = 400 + got
                    got += 1
        except (asyncio.TimeoutError, ConnectionError, OSError):
            pass
        proc.wait(timeout=10)
        print(f"[smoke] killed -9 mid-firehose after {400 + got} acked "
              f"writes")
        # cold restart: recovery from the node's own log
        proc = subprocess.Popen(args, env=env)
        c2 = Client()
        for _ in range(150):
            try:
                await c2.connect(f"127.0.0.1:{port}"); break
            except OSError:
                await asyncio.sleep(0.1)
        else:
            raise SystemExit("server never came back after kill -9")
        lost = []
        for k, serial in acked.items():
            r = await c2.cmd("get", k)
            v = r.val.decode() if hasattr(r, "val") and r.val else ""
            if not v.startswith("v") or int(v[1:]) < serial:
                lost.append((k, serial, v))
        assert not lost, f"acked writes lost after kill -9: {lost[:5]}"
        info = (await c2.cmd("info", "durability")).val.decode()
        assert "aof_enabled:1" in info
        assert "aof_recovery_source:log-only" in info, info
        ops = int(next(l for l in info.splitlines()
                       if l.startswith("aof_recovered_ops:"))
                  .split(":")[1])
        assert ops >= 400 + got, (ops, 400 + got)
        await c2.close()
        os.kill(proc.pid, signal.SIGTERM)
        proc.wait(timeout=15)
        print(f"[smoke] durability smoke verified: {ops} ops replayed, "
              f"zero acked writes lost")

asyncio.run(main())
EOF
JAX_PLATFORMS=cpu CONSTDB_BENCH_AOF_OPS=6000 CONSTDB_BENCH_SERVE_CONNS=2 \
CONSTDB_BENCH_AOF_REPS=1 \
    timeout -k 10 300 python bench.py --mode serve --aof \
    > /tmp/_ci_aof.json || exit $?
python - <<'EOF' || exit $?
import json
out = json.load(open("/tmp/_ci_aof.json"))
assert out["verified"], "aof bench legs failed oracle verification"
assert out["recovery_verified"], "aof recovery replay mismatched"
assert out["recovery_ops"] > 0
print("aof bench smoke verified:",
      [(leg["aof"], leg["rps"]) for leg in out["legs"]],
      f"recovery {out['recovery_s_per_gb']} s/GB")
EOF

echo
echo "== recovery smoke (parallel bulk-merge restart + checkpointed tail) =="
# the fast-restart plane end to end on a REAL server process: firehose
# acked writes over a socket, kill -9 mid-burst, and time the cold
# restart — the default parallel bulk-merge recovery must come up with
# ZERO acked writes lost and say so in the INFO Recovery gauges
# (recovery_mode/recovery_wall_s/recovery_merge_rounds).  Then an
# incremental-checkpoint phase (CONSTDB_CHECKPOINT_SECS cadence) cuts a
# mid-run checkpoint and proves the NEXT restart replays only the
# post-checkpoint tail, gauge-asserted (aof_recovered_ops collapses,
# checkpoint_last_uuid survives the restart).  The differential suites
# proper run inside tier-1 (tests/test_oplog.py); the crash-mid-
# checkpoint cells run in the chaos smoke below.
JAX_PLATFORMS=cpu timeout -k 10 420 python - <<'EOF' || exit $?
import asyncio, os, signal, socket, subprocess, sys, tempfile, time

async def connect(port, tries=150):
    from constdb_tpu.chaos.cluster import Client
    c = Client()
    for _ in range(tries):
        try:
            await c.connect(f"127.0.0.1:{port}")
            return c
        except OSError:
            await asyncio.sleep(0.1)
    raise SystemExit("server never came up")

async def info_map(c, section):
    raw = (await c.cmd("info", section)).val.decode()
    out = {}
    for line in raw.splitlines():
        if ":" in line and not line.startswith("#"):
            k, _, v = line.partition(":")
            out[k] = v.strip()
    return out

async def main():
    with tempfile.TemporaryDirectory(prefix="constdb-rec-") as work:
        s = socket.socket(); s.bind(("127.0.0.1", 0))
        port = s.getsockname()[1]; s.close()
        args = [sys.executable, "-m", "constdb_tpu.bin.server",
                "--port", str(port), "--work-dir", work,
                "--aof", "--aof-fsync", "always", "--node-id", "1"]
        env = dict(os.environ, JAX_PLATFORMS="cpu")
        proc = subprocess.Popen(args, env=env)
        c = await connect(port)
        # -- phase 1: acked firehose (mixed columnar shapes so the bulk
        # replay actually group-encodes), then a pipelined burst the
        # kill -9 lands inside
        acked = {}
        for i in range(300):
            k = f"k{i % 16}"
            await c.cmd("set", k, f"v{i:06d}")
            acked[k] = i
            if i % 3 == 0:
                await c.cmd("sadd", f"s{i % 8}", f"m{i}")
            elif i % 3 == 1:
                await c.cmd("hset", f"h{i % 8}", f"f{i % 5}", f"w{i}")
        from constdb_tpu.resp.codec import encode_msg
        from constdb_tpu.resp.message import Arr, Bulk
        buf = bytearray()
        for i in range(300, 2300):
            buf += encode_msg(Arr([Bulk(b"set"), Bulk(b"k%d" % (i % 16)),
                                   Bulk(b"v%06d" % i)]))
        c.writer.write(bytes(buf))
        await c.writer.drain()
        got = 0
        t0 = time.monotonic()
        await asyncio.sleep(0.05)
        os.kill(proc.pid, signal.SIGKILL)
        try:
            while got < 2000 and time.monotonic() - t0 < 5:
                data = await asyncio.wait_for(c.reader.read(1 << 16), 2.0)
                if not data:
                    break
                c.parser.feed(data)
                while c.parser.next_msg() is not None:
                    acked[f"k{(300 + got) % 16}"] = 300 + got
                    got += 1
        except (asyncio.TimeoutError, ConnectionError, OSError):
            pass
        proc.wait(timeout=10)
        print(f"[smoke] killed -9 mid-firehose after {500 + got} acked "
              f"writes")
        # -- phase 2: timed cold restart through the default parallel
        # bulk-merge recovery; every acked write must be back
        t0 = time.monotonic()
        proc = subprocess.Popen(args, env=env)
        c2 = await connect(port)
        boot_wall = time.monotonic() - t0
        lost = []
        for k, serial in acked.items():
            r = await c2.cmd("get", k)
            v = r.val.decode() if hasattr(r, "val") and r.val else ""
            if not v.startswith("v") or int(v[1:]) < serial:
                lost.append((k, serial, v))
        assert not lost, f"acked writes lost after kill -9: {lost[:5]}"
        rec = await info_map(c2, "recovery")
        assert rec["recovery_mode"].startswith("bulk"), rec
        assert float(rec["recovery_wall_s"]) > 0, rec
        assert int(rec["recovery_merge_rounds"]) >= 1, rec
        dur = await info_map(c2, "durability")
        full_ops = int(dur["aof_recovered_ops"])
        assert full_ops >= 500 + got, (full_ops, 500 + got)
        await c2.close()
        os.kill(proc.pid, signal.SIGTERM)
        proc.wait(timeout=15)
        print(f"[smoke] parallel restart verified: {full_ops} ops "
              f"replayed in {rec['recovery_wall_s']}s "
              f"({rec['recovery_mode']}, {rec['recovery_merge_rounds']} "
              f"merge rounds; boot-to-serve {boot_wall:.2f}s), zero "
              f"acked writes lost")
        # -- phase 3: incremental checkpoints — run with a fast cadence
        # until a checkpoint cuts, append a small tail, and prove the
        # next clean restart replays ONLY the tail
        env_ck = dict(env, CONSTDB_CHECKPOINT_SECS="0.3",
                      CONSTDB_CHECKPOINT_MIN_MB="0")
        proc = subprocess.Popen(args, env=env_ck)
        c3 = await connect(port)
        ck_uuid = 0
        for i in range(200):
            await c3.cmd("set", f"ck{i % 8}", f"x{i:04d}")
            rec = await info_map(c3, "recovery")
            ck_uuid = int(rec.get("checkpoint_last_uuid", 0))
            if ck_uuid:
                break
            await asyncio.sleep(0.1)
        assert ck_uuid > 0, "checkpoint cadence never cut"
        assert float(rec["checkpoint_age_s"]) >= 0, rec
        for i in range(40):
            await c3.cmd("set", f"t{i}", f"y{i:04d}")
        await c3.close()
        os.kill(proc.pid, signal.SIGTERM)
        proc.wait(timeout=15)
        proc = subprocess.Popen(args, env=env)
        c4 = await connect(port)
        dur = await info_map(c4, "durability")
        tail_ops = int(dur["aof_recovered_ops"])
        assert dur["aof_recovery_source"].startswith("aof-base-snapshot"), \
            dur
        assert tail_ops < full_ops // 4, (tail_ops, full_ops)
        rec = await info_map(c4, "recovery")
        assert int(rec["checkpoint_last_uuid"]) > 0, rec
        v = (await c4.cmd("get", "t39")).val
        assert v == b"y0039", v
        await c4.close()
        os.kill(proc.pid, signal.SIGTERM)
        proc.wait(timeout=15)
        print(f"[smoke] checkpointed restart verified: {tail_ops} "
              f"tail ops replayed (vs {full_ops} full-log), "
              f"checkpoint uuid {ck_uuid} survived the restart")

asyncio.run(main())
EOF
JAX_PLATFORMS=cpu CONSTDB_BENCH_RECOVER_OPS=8000 \
CONSTDB_BENCH_RECOVER_REPS=1 \
    timeout -k 10 420 python bench.py --mode recover \
    > /tmp/_ci_recover.json || exit $?
python - <<'EOF' || exit $?
import json
out = json.load(open("/tmp/_ci_recover.json"))
assert out["verified"], "recover bench legs failed oracle verification"
legs = {leg["leg"]: leg for leg in out["legs"]}
assert legs["frames-bulk"]["byte_identical"], "bulk replay diverged"
assert legs["batch-bulk"]["byte_identical"], "batch bulk replay diverged"
assert legs["checkpointed-tail"]["tail_ops"] < out["ops"] // 4, \
    "checkpointed restart replayed more than the tail"
assert all(s["verified"] for s in out["shard_curve"]), \
    "sharded restart failed its oracle"
print("recover bench smoke verified:",
      f"frames {legs['frames-bulk']['speedup_vs_serial']}x,",
      f"batches {legs['batch-bulk']['speedup_vs_serial']}x,",
      f"tail {legs['checkpointed-tail']['tail_ops']} of",
      out["ops"], "ops")
EOF

echo
echo "== chaos smoke (fixed-seed certification cells) =="
# the scripted chaos scenario — partitions + reorder + duplication +
# mid-frame truncation + connection/process kills + clock jitter + one
# mixed-version peer — on one representative capability cell per fast
# path (everything-on, everything-off, resident engine, sharded
# serving, and the AOF always/everysec durability cells, whose
# schedules add kill9_mid_write + torn_write cold restarts recovering
# from the node's own op log), with the full invariant oracle verified:
# convergence to the
# CPU-engine reference, digest agreement, watermark monotonicity,
# no-resurrection, GC drain, and loud demotion accounting.  Fixed seed:
# a failure here replays exactly (the full matrix + randomized soak are
# slow-marked in tests/test_chaos.py).
JAX_PLATFORMS=cpu timeout -k 10 420 python -m constdb_tpu.chaos --seed 7 \
    || exit $?

echo
echo "== tier-1 tests + slow-marker audit =="
./scripts/audit_markers.sh "$@" || exit $?

echo
echo "ci.sh: all gates green"
