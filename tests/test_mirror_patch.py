"""A stale device mirror is repaired by scattering the rows the op path
wrote (engine/tpu.py `_patch_mirror`), not by re-uploading the plane.

The invariant (docs/INVARIANTS.md, MIRROR-JOURNAL): for a family with a
mirror, mirror == host on every row that is not in the family's
`KeySpace.journal`.  Pinned here, on JAX-CPU:

  * differential — seeded interleavings of op-path writes (HSET, HDEL,
    SADD, SREM, SET, INCR, DEL), replicated ops, host-twin micro rounds
    and resident micro rounds: at every checkpoint the repaired mirror's
    columns, downloaded, equal the host columns (padding neutral), the
    end state equals a CPU-engine node fed the same operations, and a
    mirror rebuilt from scratch by a second engine is the same arrays —
    for el, reg and cnt, under every forced kernel choice;
  * rows appended past the mirror's `n` and past its `cap`, duplicate
    rows in one journal, a version that moved with no row written;
  * overflow falls back to ONE rebuild and counts
    `mirror_patch_overflows`; `gc` / `compact` / `reset` still rebuild;
    the unflushed-`written` raise still fires; a second engine on the
    store cannot trust the journal (epoch);
  * who must NOT append: a bulk ingest and a flush leave the journal
    empty, and a store no device engine mirrors never journals at all;
  * the patch programs are named outside `jit_bulk_*` / `jit_dense_*`
    and are compiled with the mirror, one per bucket the plane can need.
"""

import random

import numpy as np
import pytest

from constdb_tpu.engine.base import batch_from_keyspace
from constdb_tpu.engine.tpu import (_FAMILIES, TpuMergeEngine, _fam_rows,
                                    _host_table)
from constdb_tpu.ops import bulk as B
from constdb_tpu.replica.coalesce import CoalescingApplier
from constdb_tpu.replica.manager import ReplicaMeta
from constdb_tpu.resp.message import Bulk
from constdb_tpu.server.node import Node
from constdb_tpu.server.serve import ServeCoalescer
from constdb_tpu.store import keyspace as KS
from constdb_tpu.store.keyspace import (JOURNAL_FAMILIES, JOURNAL_MAX_ROWS,
                                        RowJournal)

from test_coalesce_apply import mixed_stream, u
from test_serve_coalesce import cmd


def device_node(warmup: int = 1, fold: str = "auto", node_id: int = 1):
    eng = TpuMergeEngine(resident=True, steady=True, warmup=warmup,
                         dense_fold=fold)
    return Node(node_id=node_id, engine=eng), eng


def req(*parts) -> list:
    return [Bulk(p if isinstance(p, bytes) else str(p).encode())
            for p in parts]


def mirror_cols(eng, fam: str) -> dict:
    """The family's resident planes, joined and downloaded whole."""
    return {c: np.asarray(B.plane_rows(a, n=a.shape[0]))
            for c, a in eng._res[fam]["cols"].items()}


def assert_mirror_is_host(eng, ks, fam: str) -> None:
    res = eng._res[fam]
    assert res["ver"] == ks.fam_ver[fam]
    n, cap = res["n"], res["cap"]
    assert n == _fam_rows(ks, fam)
    table = _host_table(ks, fam)
    got = mirror_cols(eng, fam)
    for c, fill in _FAMILIES[fam]:
        assert got[c].shape == (cap,)
        np.testing.assert_array_equal(got[c][:n], table.col(c)[:n],
                                      err_msg=f"{fam}.{c}")
        assert (got[c][n:] == fill).all(), f"{fam}.{c} padding"
    j = ks.journal[fam]
    assert not j.whole and len(j.rows) == 0


def repair_and_check(node) -> None:
    """Flush, ask for every mirrored family's state as the micro path
    would (a stale one is repaired), and hold the mirror to the host."""
    node.ensure_flushed()
    eng, ks = node.engine, node.ks
    for fam in JOURNAL_FAMILIES:
        if fam in eng._res:
            eng._resident_state(ks, fam, _fam_rows(ks, fam))
            assert_mirror_is_host(eng, ks, fam)


def sadd_round(node, first: int, members: int = 6, key: bytes = b"s"):
    out = bytearray()
    ServeCoalescer(node).run_chunk(
        [cmd(b"sadd", key, b"m%d" % (first + i)) for i in range(members)],
        out)
    assert out.count(b":1\r\n") == members


def warm_el(node, rounds: int = 2) -> None:
    """Coalesced SADD rounds until the el mirror is resident and fresh:
    ten members, then two a round, inside its first build's capacity (a
    plane a micro round grows keeps the engine's GROW_FLOOR rows)."""
    for i in range(rounds + node.engine.warmup):
        sadd_round(node, 1000 + 10 * i, members=2 if i else 10,
                   key=b"warm")
    assert node.engine._res["el"]["ver"] == node.ks.fam_ver["el"]


# ------------------------------------------------------------ the journal


def test_journal_is_born_whole_and_a_cpu_node_never_journals():
    j = RowJournal()
    j.add(3)
    j.add_rows(np.array([4, 5]))
    assert j.whole and len(j.rows) == 0 and j.take() is None
    node = Node(node_id=1)
    for i in range(5):
        node.execute(req(b"hset", b"h", b"f%d" % i, b"v"))
        node.execute(req(b"set", b"r%d" % i, b"v"))
        node.execute(req(b"incr", b"c"))
    assert all(j.whole and not j.rows for j in node.ks.journal.values())
    assert set(node.ks.journal) == set(JOURNAL_FAMILIES)


def test_journal_dedupes_sorts_and_counts_resets():
    j = RowJournal()
    assert j.reset() == 1 and not j.whole
    for r in (9, 2, 9, 5):
        j.add(r)
    j.add_rows(np.array([5, 7, 2], dtype=np.int64))
    j.add_rows(np.array([], dtype=np.int64))
    assert j.take().tolist() == [2, 5, 7, 9]
    assert list(j.rows) == [2, 5, 7, 9]        # taking does not clear
    assert j.reset() == 2 and j.rows.tolist() == [] and j.take().tolist() == []


@pytest.mark.parametrize("distinct, whole", [(JOURNAL_MAX_ROWS, False),
                                             (JOURNAL_MAX_ROWS + 1, True)])
def test_journal_goes_whole_past_its_limit_of_distinct_rows(distinct, whole):
    j = RowJournal()
    j.reset()
    rows = np.arange(distinct, dtype=np.int64)
    # hot rows repeat: raw entries far over the limit, distinct rows under
    for _ in range(3):
        j.add_rows(rows)
    assert len(j.rows) <= 2 * JOURNAL_MAX_ROWS + distinct
    got = j.take()
    assert j.whole is whole and j.over is whole
    assert (got is None) if whole else (len(got) == distinct)
    j.reset()
    assert not j.whole and not j.over


@pytest.mark.parametrize("cause", sorted(KS.WHOLE_CAUSES))
def test_a_touch_whose_rows_moved_marks_the_journal_whole(cause):
    ks = KS.KeySpace()
    for j in ks.journal.values():
        j.reset()
    ks.journal["el"].add(1)
    ks.touch("el", "env", cause=cause)
    assert ks.journal["el"].whole and not ks.journal["el"].over
    assert not ks.journal["cnt"].whole
    ks.touch("cnt", cause="client_op")
    ks.touch("reg", cause="repl_op")
    ks.touch("env", cause="expire")
    assert not ks.journal["cnt"].whole and not ks.journal["reg"].whole


# ----------------------------------------------------------- differential


def local_op(rng, i: int, keys: int) -> list:
    """One op-path write on the stream's own key space (the rows the
    micro rounds merge into are the rows these write)."""
    k = b"k%03d" % rng.randrange(keys)
    r = rng.random()
    if r < 0.25:
        return req(b"hset", b"h" + k, b"f%d" % rng.randrange(6),
                   b"L%d" % i)
    if r < 0.35:
        return req(b"hdel", b"h" + k, b"f%d" % rng.randrange(6))
    if r < 0.55:
        return req(b"sadd", b"s" + k, b"m%d" % rng.randrange(10),
                   b"n%d" % i)
    if r < 0.65:
        return req(b"srem", b"s" + k, b"m%d" % rng.randrange(10))
    if r < 0.80:
        return req(b"set", b"r" + k, b"L%d" % i)
    if r < 0.93:
        return req(b"incr", b"c" + k)
    return req(b"del", rng.choice((b"h", b"s", b"r", b"c")) + k)


def script(seed: int, n_frames: int, keys: int) -> list:
    """[("frames", max_frames, [frame...]) | ("local", uuid, req) |
    ("check",)]: a replicated stream cut into runs applied coalesced (micro
    rounds: host twins while a plane is cold, resident rounds once warm)
    or frame by frame (replicated ops on the per-command path), with
    local op-path writes between the frames' uuids and checkpoints."""
    frames, _last = mixed_stream(n_frames, seed=seed, keys=keys)
    rng = random.Random(1000 + seed)
    out, i, n_local = [], 0, 0
    while i < len(frames):
        run = rng.choice((1, 3, 8, 24, 40))
        out.append(("frames", rng.choice((1, 8, 32)), frames[i:i + run]))
        i += run
        for _ in range(rng.choice((0, 0, 1, 2, 5))):
            n_local += 1
            # between frame i's uuid and frame i+1's, in order
            out.append(("local", u(i) + n_local % (1 << 20),
                        local_op(rng, n_local, keys)))
        if rng.random() < 0.15:
            out.append(("check",))
    out.append(("check",))
    return out


def play(node, steps: list, check=None) -> None:
    ap = CoalescingApplier(node, ReplicaMeta("peer:1"), max_frames=64,
                           max_latency=999.0)
    for step in steps:
        if step[0] == "frames":
            ap.max_frames = step[1]
            for f in step[2]:
                ap.apply(f)
            ap.flush()
        elif step[0] == "local":
            node.execute(step[2], uuid=step[1])
        elif check is not None:
            check(node)
    ap.flush()


def rebuilt_from_scratch_is_the_same(node) -> None:
    """A second engine's first build of every family: the same arrays as
    the patched mirror, cap for cap."""
    node.ensure_flushed()
    eng, ks = node.engine, node.ks
    fresh = TpuMergeEngine(resident=True)
    fresh._growing |= eng._growing      # the tables micro rounds grew
    for fam in JOURNAL_FAMILIES:
        if fam not in eng._res:
            continue
        n = _fam_rows(ks, fam)
        eng._resident_state(ks, fam, n)
        patched = mirror_cols(eng, fam)
        _cols, cap = fresh._resident_state(ks, fam, n)
        assert cap == eng._res[fam]["cap"]
        built = mirror_cols(fresh, fam)
        for c, _ in _FAMILIES[fam]:
            np.testing.assert_array_equal(patched[c], built[c],
                                          err_msg=f"{fam}.{c}")


@pytest.mark.parametrize("seed, warmup, fold", [
    (0, 1, "auto"), (1, 1, "auto"), (2, 2, "auto"), (3, 0, "auto"),
    (4, 2, "xla"), (5, 1, "pallas-interpret")])
def test_interleavings_patched_mirror_is_host_is_rebuilt(seed, warmup, fold):
    steps = script(seed, n_frames=420, keys=24)
    node, eng = device_node(warmup=warmup, fold=fold)
    ref = Node(node_id=1)
    play(node, steps, check=repair_and_check)
    play(ref, [("frames", 1, s[2]) if s[0] == "frames" else s
               for s in steps])
    node.ensure_flushed()
    assert node.canonical() == ref.canonical()
    # the mix really ran every path it names
    assert eng.dev_rounds_resident > 0
    assert (eng.host_micro_rounds > 0) is (warmup > 0)
    assert sum(eng.mirror_patches.values()) > 5
    assert sum(eng.mirror_patch_rows.values()) > \
        sum(eng.mirror_patches.values())
    assert eng.mirror_patch_overflows == 0
    assert sum(eng.mirror_rebuilds.values()) == 0
    assert {f for f, c in eng.mirror_patches.items() if c} == \
        set(JOURNAL_FAMILIES)
    rebuilt_from_scratch_is_the_same(node)
    horizon = steps[-2][1] if steps[-2][0] == "local" else u(10 ** 6)
    assert node.ks.gc(horizon) == ref.ks.gc(horizon)
    assert node.canonical() == ref.canonical()


# ------------------------------------------------------------------ shapes


def test_rows_appended_past_n_and_past_cap_ride_the_patch():
    node, eng = device_node(warmup=0)
    warm_el(node)
    res = eng._res["el"]
    n0, cap0 = res["n"], res["cap"]
    assert n0 < cap0
    # op-path appends: one row under the cap, then far past it
    node.execute(req(b"sadd", b"warm", b"one-more"))
    repair_and_check(node)
    assert eng._res["el"]["n"] == n0 + 1 and eng._res["el"]["cap"] == cap0
    for i in range(3 * cap0):
        node.execute(req(b"hset", b"big", b"f%d" % i, b"v%d" % i))
    node.execute(req(b"hdel", b"big", b"f0", b"never-there"))
    repair_and_check(node)
    assert eng._res["el"]["cap"] >= 4 * cap0
    assert eng.mirror_patches["el"] == 2 and eng.mirror_rebuilds["el"] == 0
    assert eng.mirror_patch_rows["el"] == 1 + 3 * cap0 + 1
    # ... and the next resident round merges against the patched rows
    sadd_round(node, 5000, key=b"big2")
    repair_and_check(node)
    assert node.execute(req(b"hlen", b"big")).val == 3 * cap0 - 1


def test_duplicate_rows_in_one_journal_scatter_once():
    node, eng = device_node(warmup=0)
    warm_el(node)
    for i in range(7):
        node.execute(req(b"hset", b"h", b"f", b"v%d" % i))
    node.execute(req(b"hdel", b"h", b"f"))
    node.execute(req(b"hset", b"h", b"f", b"last"))
    assert len(node.ks.journal["el"].rows) == 9
    repair_and_check(node)
    assert eng.mirror_patches["el"] == 1
    assert eng.mirror_patch_rows["el"] == 1
    assert node.execute(req(b"hget", b"h", b"f")).val == b"last"


def test_a_version_that_moved_with_no_row_written_patches_nothing():
    node, eng = device_node(warmup=0)
    warm_el(node)
    node.execute(req(b"sadd", b"warm", b"x"))    # stamped by the wall clock
    repair_and_check(node)
    n_before = eng.stages.snapshot()["mirror_patch"][1]
    # a replicated add OLDER than the one that landed: it loses the LWW,
    # writes no column, and still bumps the plane's version
    node.apply_replicated(b"sadd", req(b"warm", b"x"), 7, u(10))
    assert node.ks.journal["el"].rows.tolist() == []
    assert eng._res["el"]["ver"] != node.ks.fam_ver["el"]
    h2d = eng.bytes_h2d
    repair_and_check(node)
    assert eng.mirror_patches["el"] == 2 and eng.mirror_patch_rows["el"] == 1
    assert eng.bytes_h2d == h2d                  # nothing went up
    assert eng.stages.snapshot()["mirror_patch"][1] == n_before


# --------------------------------------------------------------- fallbacks


def test_overflow_falls_back_to_one_rebuild_and_is_counted(monkeypatch):
    monkeypatch.setattr(KS, "JOURNAL_MAX_ROWS", 8)
    monkeypatch.setattr(TpuMergeEngine, "MIRROR_PATCH_BUCKETS", (4, 8))
    node, eng = device_node(warmup=0)
    warm_el(node)
    for i in range(8):                           # the largest bucket: fits
        node.execute(req(b"sadd", b"a", b"m%d" % i))
    repair_and_check(node)
    assert eng.mirror_patches["el"] == 1 and eng.mirror_patch_rows["el"] == 8
    for i in range(9):                           # one row more: over
        node.execute(req(b"sadd", b"b", b"m%d" % i))
    node.execute(req(b"sadd", b"b", b"m0"))
    repair_and_check(node)
    assert eng.mirror_patch_overflows == 1
    assert eng.mirror_rebuilds["el"] == 1 and eng.mirror_patches["el"] == 1
    assert eng.mirror_rebuild_causes["client_op"] == 1
    # the rebuild starts the journal over: the next write is a patch
    node.execute(req(b"sadd", b"c", b"m"))
    repair_and_check(node)
    assert eng.mirror_patches["el"] == 2 and eng.mirror_rebuilds["el"] == 1
    assert eng.mirror_patch_overflows == 1


@pytest.mark.parametrize("cause", ("gc", "compact", "reset"))
def test_rows_that_moved_still_rebuild_the_plane(cause):
    node, eng = device_node(warmup=0)
    warm_el(node)
    for i in range(40):
        node.execute(req(b"sadd", b"s", b"m%d" % i))
    for i in range(30):
        node.execute(req(b"srem", b"s", b"m%d" % i))
    repair_and_check(node)
    assert eng.mirror_patches["el"] == 1
    if cause == "gc":
        assert node.gc() >= 30                   # frees rows: touch("el")
    elif cause == "compact":
        node.ensure_flushed()
        node.ks.el_dead = 0
        node.ks.gc(node.gc_horizon())
        node.ks._compact_elements()
    else:
        node.ensure_flushed()
        node.ks.version += 1
    assert node.ks.fam_cause["el"] == cause and node.ks.journal["el"].whole
    node.execute(req(b"sadd", b"s", b"after"))   # row-scoped, but too late
    repair_and_check(node)
    assert eng.mirror_rebuilds["el"] == 1 and eng.mirror_patches["el"] == 1
    assert eng.mirror_patch_overflows == 0
    sadd_round(node, 3000)
    node.execute(req(b"sadd", b"s", b"again"))
    repair_and_check(node)
    assert eng.mirror_patches["el"] == 2         # row-scoped once more


def test_unflushed_merge_data_under_a_stale_mirror_still_raises():
    node, eng = device_node(warmup=0)
    warm_el(node)
    sadd_round(node, 0)
    assert eng._res["el"]["wins"]                # merged, not flushed
    node.ks.elem_add(node.ks.lookup(b"warm"), b"behind-the-node", None,
                     u(9), 1)
    node.ks.touch("el")                          # no flush before the touch
    with pytest.raises(RuntimeError, match="flush-before-touch"):
        eng._resident_state(node.ks, "el", _fam_rows(node.ks, "el"))
    assert eng.mirror_patches["el"] == 0


def test_a_second_engine_on_the_store_cannot_trust_the_journal():
    node, eng = device_node(warmup=0)
    warm_el(node)
    node.ensure_flushed()
    other = TpuMergeEngine(resident=True)
    other._resident_state(node.ks, "el", _fam_rows(node.ks, "el"))
    node.execute(req(b"sadd", b"warm", b"x"))
    repair_and_check(node)                       # not its epoch: rebuild
    assert eng.mirror_rebuilds["el"] == 1 and eng.mirror_patches["el"] == 0
    node.execute(req(b"sadd", b"warm", b"y"))
    repair_and_check(node)                       # its own again: patch
    assert eng.mirror_patches["el"] == 1
    n = _fam_rows(node.ks, "el")
    other._resident_state(node.ks, "el", n)
    assert other.mirror_rebuilds["el"] == 1 and other.mirror_patches["el"] == 0
    np.testing.assert_array_equal(
        np.asarray(B.plane_rows(other._res["el"]["cols"]["add_t"], n=n)),
        node.ks.el.add_t[:n])


# -------------------------------------------------- who must not append


def test_bulk_ingest_and_flush_leave_the_journal_empty():
    src = Node(node_id=2)
    for i in range(120):
        src.execute(req(b"hset", b"h%d" % (i % 9), b"f%d" % i, b"v"))
        src.execute(req(b"sadd", b"s%d" % (i % 7), b"m%d" % i))
        src.execute(req(b"set", b"r%d" % (i % 5), b"v%d" % i))
        src.execute(req(b"incr", b"c%d" % (i % 4)))
    node = Node(node_id=1, engine=TpuMergeEngine(resident=True))
    eng, ks = node.engine, node.ks
    node.merge_batch(batch_from_keyspace(src.ks))
    assert set(JOURNAL_FAMILIES) <= set(eng._res)
    for fam in JOURNAL_FAMILIES:                 # built: row-scoped, empty
        j = ks.journal[fam]
        assert not j.whole and j.rows.tolist() == []
    assert eng.needs_flush
    node.ensure_flushed()
    for fam in JOURNAL_FAMILIES:
        assert ks.journal[fam].rows.tolist() == []
        assert_mirror_is_host(eng, ks, fam)
    # a second ingest over resident planes: merged on the device, flushed
    # down — still nothing journaled, nothing patched or rebuilt
    for i in range(40):
        src.execute(req(b"hset", b"h%d" % (i % 9), b"g%d" % i, b"w"))
        src.execute(req(b"incr", b"c%d" % (i % 4)))
    node.merge_batch(batch_from_keyspace(src.ks))
    node.ensure_flushed()
    for fam in JOURNAL_FAMILIES:
        assert ks.journal[fam].rows.tolist() == []
        assert_mirror_is_host(eng, ks, fam)
    assert sum(eng.mirror_patches.values()) == 0
    assert sum(eng.mirror_rebuilds.values()) == 0
    # ... and an op-path write after it is one journaled row, one patch
    node.execute(req(b"hset", b"h0", b"f0", b"mine"))
    assert len(ks.journal["el"].rows) == 1
    repair_and_check(node)
    assert eng.mirror_patches["el"] == 1 and eng.mirror_patch_rows["el"] == 1


def test_a_bulk_rounds_host_only_delete_side_is_journaled():
    """The bulk src path advances el.del_t on the HOST only (its kernels
    never read it): those rows are where mirror != host, so they are in
    the journal, and the next patch carries them."""
    src = Node(node_id=2)
    for i in range(30):
        src.execute(req(b"sadd", b"s", b"m%d" % i))
    node = Node(node_id=1, engine=TpuMergeEngine(resident=True))
    eng, ks = node.engine, node.ks
    node.merge_batch(batch_from_keyspace(src.ks))
    for i in range(10):
        src.execute(req(b"srem", b"s", b"m%d" % i))
    node.merge_batch(batch_from_keyspace(src.ks))
    assert len(ks.journal["el"].rows) == 10
    node.ensure_flushed()
    node.execute(req(b"sadd", b"s", b"mine"))
    repair_and_check(node)
    assert eng.mirror_patch_rows["el"] == 11
    assert node.execute(req(b"scnt", b"s")).val == 21


# ------------------------------------------------------------ the programs


def test_patch_programs_are_named_apart_from_the_merge_kernels():
    import jax
    import jax.numpy as jnp
    assert set(B.MIRROR_PATCH) == set(JOURNAL_FAMILIES)
    for fam, fn in B.MIRROR_PATCH.items():
        nc = len(_FAMILIES[fam])
        cols = tuple(B.Plane(jax.ShapeDtypeStruct((64,), jnp.int32),
                             jax.ShapeDtypeStruct((64,), jnp.uint32))
                     for _ in range(nc))
        text = fn.lower(cols, jax.ShapeDtypeStruct((16,), jnp.int32),
                        jax.ShapeDtypeStruct((16, nc), jnp.int64)).as_text()
        name = f"jit_mirror_patch_{fam}"
        assert f"module @{name}" in text
        assert not name.startswith(("jit_bulk_", "jit_dense_"))
    assert TpuMergeEngine.MIRROR_PATCH_BUCKETS[-1] == JOURNAL_MAX_ROWS
    assert list(TpuMergeEngine.MIRROR_PATCH_BUCKETS) == \
        sorted(TpuMergeEngine.MIRROR_PATCH_BUCKETS)
    # under 1% of a 16,777,216-row plane, idx and all
    assert JOURNAL_MAX_ROWS * (4 + 8 * 3) < 0.01 * (1 << 24) * 8 * 3


def test_patch_programs_compile_at_the_flush_before_a_mirror_can_go_stale():
    node, eng = device_node(warmup=0)
    calls = []
    real = dict(B.MIRROR_PATCH)

    def spy(fam):
        def run(cols, idx, vals):
            cap = cols[0].shape[0]
            # a warm-up call writes nothing: every row is out of range
            kind = "warm" if int(np.asarray(idx)[0]) == cap else "patch"
            calls.append((kind, fam, idx.shape[0], cap))
            return real[fam](cols, idx, vals)
        return run
    B.MIRROR_PATCH.update({fam: spy(fam) for fam in real})
    try:
        warm_el(node)
        node.ensure_flushed()
        cap = eng._res["el"]["cap"]
        # warmed at a flush, once a cap, and one bucket covers so small a
        # plane; nothing patched yet
        assert calls and all(c[:3] == ("warm", "el", 1 << 10) for c in calls)
        caps = [c[3] for c in calls]
        assert caps == sorted(set(caps)) and caps[-1] == cap
        n_warm = len(calls)

        def again():                             # a round that adds no row
            ServeCoalescer(node).run_chunk(
                [cmd(b"sadd", b"warm", b"m%d" % (1000 + i))
                 for i in range(3)], bytearray())
        again()
        node.execute(req(b"srem", b"warm", b"m1000"))    # flushes: same cap
        again()                                  # the patch itself runs warm
        assert calls[n_warm:] == [("patch", "el", 1 << 10, cap)]
        for i in range(2 * cap):                 # a plane that grew:
            node.execute(req(b"sadd", b"s", b"g%d" % i))
        sadd_round(node, 4, members=2)           # patched cold, at cap2,
        node.ensure_flushed()                    # warmed at its next flush
        cap2 = eng._res["el"]["cap"]
        # a micro round grew it: the floor, which every bucket fits
        assert cap2 == eng.GROW_FLOOR > cap
        assert calls[n_warm + 1:] == [("patch", "el", 1 << 10, cap2)] + [
            ("warm", "el", bp, cap2) for bp in eng.MIRROR_PATCH_BUCKETS]
        repair_and_check(node)
    finally:
        B.MIRROR_PATCH.update(real)


@pytest.mark.parametrize("fold", ("auto", "pallas-interpret"))
def test_an_xla_resident_round_imports_no_pallas(fold):
    """Importing Pallas costs over a second; done lazily inside the first
    resident round it stalled the event loop inside a served window
    (PERF.md §6, PR 31).  A resident round is XLA whatever `dense_fold`
    says, and must never import it."""
    import os
    import subprocess
    import sys
    code = (
        "import sys\n"
        "from constdb_tpu.engine.tpu import TpuMergeEngine\n"
        "from constdb_tpu.server.node import Node\n"
        "from constdb_tpu.server.serve import ServeCoalescer\n"
        "from constdb_tpu.resp.message import Arr, Bulk\n"
        "eng = TpuMergeEngine(resident=True, steady=True, warmup=0,\n"
        f"                     dense_fold={fold!r})\n"
        "node = Node(node_id=1, engine=eng)\n"
        "for r in range(3):\n"
        "    ServeCoalescer(node).run_chunk(\n"
        "        [Arr([Bulk(b'hset'), Bulk(b'h'), Bulk(b'f%d' % (4 * r + i)),\n"
        "              Bulk(b'v')]) for i in range(4)], bytearray())\n"
        "    node.execute([Bulk(b'hset'), Bulk(b'h'), Bulk(b'lone'),\n"
        "                  Bulk(b'%d' % r)])\n"
        "node.ensure_flushed()\n"
        "assert eng.dev_rounds_resident == 3 and eng.mirror_patches['el'] == 2\n"
        "assert 'constdb_tpu.ops.pallas_dense' not in sys.modules\n"
        "print('ok')\n")
    r = subprocess.run([sys.executable, "-c", code], capture_output=True,
                       text=True, timeout=120,
                       cwd=os.path.dirname(os.path.dirname(
                           os.path.abspath(__file__))),
                       env={**os.environ, "JAX_PLATFORMS": "cpu",
                            "JAX_ENABLE_X64": "true"})
    assert r.returncode == 0 and r.stdout.strip() == "ok", r.stderr[-2000:]
