"""A resident int64 stamp plane lives on the device as an ops/bulk.py
`Plane` — (hi int32, lo uint32), value = (hi << 32) | lo — and every
program of ops/bulk.py takes and returns that pair (docs/INVARIANTS.md,
PLANE-PAIR).  Pinned here, on JAX-CPU, the programs the chip runs:

  * the order: split and join round-trip, and the pair compare IS the
    int64 compare — on NEUTRAL_T, -2^63, 2^63-1, negatives, values equal
    in `hi` and apart across `lo`'s 2^31 boundary, seeded random pairs;
  * the programs: each against a per-row numpy reference through chained
    donated rounds over the engine's padding protocol (pads land past the
    plane and drop), batch columns int64 or int32 as the engine uploads
    them;
  * the engine's record: after a bulk catch-up, micro rounds, a patch, a
    grow and a flush every plane in `_res` is the 32-bit pair, and the
    store equals the CPU engine's.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from constdb_tpu.engine.base import batch_from_keyspace
from constdb_tpu.engine.tpu import _FAMILIES, _pad_idx
from constdb_tpu.ops import bulk as B
from constdb_tpu.ops.bulk import NEUTRAL_T
from constdb_tpu.ops.segment import next_pow2
from constdb_tpu.server.node import Node
from constdb_tpu.store.keyspace import JOURNAL_FAMILIES

from test_coalesce_apply import u
from test_pallas_dense import _host as host, _plane as plane
from test_mirror_patch import device_node, play, repair_and_check, req, script

I64_MIN, I64_MAX = -(1 << 63), (1 << 63) - 1

# each set holds values whose ORDER a wrong pair compare would get wrong
EDGES = {
    "neutral": [NEUTRAL_T, NEUTRAL_T + 1, NEUTRAL_T - 1, 0, -1, 1],
    "extremes": [I64_MIN, I64_MIN + 1, I64_MAX, I64_MAX - 1, 0],
    "negatives": [-1, -2, -(1 << 31), -(1 << 31) - 1, -(1 << 32),
                  -(1 << 32) - 1, -(1 << 32) + 1, -(1 << 40), -7],
    # equal in hi, apart across lo's 2^31 boundary: a SIGNED lo compare
    # orders these backwards
    "lo_boundary": [(5 << 32) | 0x7FFFFFFF, (5 << 32) | 0x80000000,
                    (5 << 32) | 0xFFFFFFFF, 5 << 32, (-5 << 32) | 0x7FFFFFFF,
                    (-5 << 32) | 0x80000000, 0x7FFFFFFF, 0x80000000,
                    0xFFFFFFFF, 1 << 32],
}


def edge_values(case: str) -> np.ndarray:
    if case in EDGES:
        return np.array(EDGES[case], dtype=np.int64)
    rng = np.random.default_rng(int(case.removeprefix("random")))
    v = rng.integers(I64_MIN, I64_MAX, 48, dtype=np.int64, endpoint=True)
    v[::4] = (v[::4] >> 32) << 32 | rng.integers(0, 1 << 32, len(v[::4]))
    v[1::4] = v[::4][: len(v[1::4])] ^ 0x80000000  # same hi, lo apart
    return v


@pytest.mark.parametrize("case", [*EDGES, "random0", "random1", "random2"])
def test_split_join_round_trip_and_the_pair_order_is_int64_order(case):
    v = edge_values(case)
    # the split (a plane's build and a batch operand's, one rule) is the
    # two's-complement words, and joins back
    dev = B.plane_split(jnp.array(v))
    assert dev.hi.dtype == jnp.int32 and dev.lo.dtype == jnp.uint32
    words = v.view(np.uint32).reshape(-1, 2)     # little-endian host
    np.testing.assert_array_equal(np.asarray(dev.hi), words[:, 1].view(np.int32))
    np.testing.assert_array_equal(np.asarray(dev.lo), words[:, 0])
    np.testing.assert_array_equal(host(plane(v)), v)
    np.testing.assert_array_equal(np.asarray(jax.jit(B._join)(dev)), v)
    # every ordered pair of the set: 32-bit lanes agree with int64
    a, b = (x.ravel() for x in np.meshgrid(v, v))
    pa, pb = plane(a), plane(b)
    np.testing.assert_array_equal(np.asarray(B._gt(pa, pb)), a > b)
    np.testing.assert_array_equal(np.asarray(B._eq(pa, pb)), a == b)
    # an int32 batch column (engine/tpu.py _i32_up) sign-extends
    small = v[(v >= -(1 << 31)) & (v < (1 << 31))].astype(np.int32)
    if len(small):
        np.testing.assert_array_equal(
            np.asarray(jax.jit(B._join)(jax.jit(B._split)(jnp.array(small)))),
            small.astype(np.int64))


# ------------------------------------------------------------ the programs
# Per-row numpy references (the rule of crdt/semantics.py, one row at a
# time) and one chained-rounds driver: every program, same protocol.

def draw(rng, n: int) -> np.ndarray:
    """Stamps that stress the order: full-range, small, edges."""
    pool = np.concatenate([np.array(e, dtype=np.int64)
                           for e in EDGES.values()])
    v = rng.integers(-(1 << 62), 1 << 62, n, dtype=np.int64)
    pick = rng.random(n)
    v = np.where(pick < 0.25, rng.integers(-9, 9, n), v)
    return np.where(pick > 0.8, rng.choice(pool, n), v).astype(np.int64)


def ref_pair(t, v, idx, bt, bv):
    win = np.zeros(len(idx), dtype=bool)
    for j, r in enumerate(idx.tolist()):
        win[j] = bt[j] > t[r] or (bt[j] == t[r] and bv[j] > v[r])
        if win[j]:
            t[r], v[r] = bt[j], bv[j]
    return win


def ref_src(src, idx, win, base):
    for j, r in enumerate(idx.tolist()):
        if win[j]:
            src[r] = base + j
    return src


def ref_max(state, idx, vals):
    for j, r in enumerate(idx.tolist()):
        state[r] = np.maximum(state[r], vals[j])


def padded(sp, idx, cols, fills, i32=()):
    """The engine's upload of one batch (`_batch_idx` / `_upload_batch`):
    pow2 length, pad rows target >= sp and carry the fill; columns named
    in `i32` go up as int32 where they fit (`_i32_up`)."""
    n = len(idx)
    np2 = next_pow2(n)
    idx_x = np.concatenate([idx, sp + np.arange(np2 - n)]).astype(np.int32)
    out = [jnp.array(idx_x)]
    for k, (c, fill) in enumerate(zip(cols, fills)):
        full = np.full((np2,) + c.shape[1:], fill, dtype=np.int64)
        full[:n] = c
        if k in i32 and np.abs(c).max(initial=0) < (1 << 31):
            full = np.where(np.arange(np2) < n, full, -1).astype(np.int32)
        out.append(jnp.array(full))
    return out


def run_lww(fn, st, sp, idx, rng, base, src, iota):
    bt, bn = draw(rng, len(idx)), draw(rng, len(idx))
    ties = rng.random(len(idx)) < 0.3
    bt[ties] = st["t"][idx[ties]]
    i, dt_, dn = padded(sp, idx, (bt, bn), (NEUTRAL_T, NEUTRAL_T), i32=(1,))
    win = ref_pair(st["t"], st["n"], idx, bt, bn)
    if src:
        ref_src(st["src"], idx, win, base)
        if iota:
            out = fn(st["T"], st["N"], st["SRC"], np.int32(idx[0]),
                     np.int32(len(idx)), dt_, dn, np.int32(base),
                     np_=len(np.asarray(i)))
        else:
            out = fn(st["T"], st["N"], st["SRC"], i, dt_, dn, np.int32(base))
        st["T"], st["N"], st["SRC"] = out
    else:
        st["T"], st["N"], got_win = fn(st["T"], st["N"], i, dt_, dn)
        np.testing.assert_array_equal(np.asarray(got_win)[: len(idx)], win)


def run_lww_win(fn, st, sp, idx, rng, *_):
    """The micro round's program: one int32 block (the engine's
    `_scatter_pair`: idx, then both columns' words, lo's bits as int32),
    the win vector back."""
    n = len(idx)
    bt, bn = draw(rng, n), draw(rng, n)
    ties = rng.random(n) < 0.3
    bt[ties] = st["t"][idx[ties]]
    np2 = next_pow2(n)
    blk = np.zeros((5, np2), dtype=np.int32)
    blk[0] = _pad_idx(idx, sp, np2)
    for r, col in ((1, bt), (3, bn)):
        words = col.view(np.uint32).reshape(-1, 2)   # little-endian host
        blk[r, :n], blk[r + 1, :n] = (words[:, 1].view(np.int32),
                                      words[:, 0].view(np.int32))
    win = ref_pair(st["t"], st["n"], idx, bt, bn)
    st["T"], st["N"], got_win = fn(st["T"], st["N"], jnp.array(blk))
    assert got_win.dtype == jnp.bool_ and got_win.shape == (np2,)
    np.testing.assert_array_equal(np.asarray(got_win)[:n], win)
    assert not np.asarray(got_win)[n:].any()         # pads never win


def run_counters(fn, st, sp, idx, rng, base, src, iota, with_base):
    n = len(idx)
    bv, bt, bb, bbt = (draw(rng, n) for _ in range(4))
    ties = rng.random(n) < 0.3
    bt[ties] = st["uuid"][idx[ties]]
    win = ref_pair(st["uuid"], st["val"], idx, bt, bv)
    i, dv, dt_, db, dbt = padded(sp, idx, (bv, bt, bb, bbt),
                                 (0, NEUTRAL_T, 0, NEUTRAL_T), i32=(0,))
    if src:
        ref_src(st["src"], idx, win, base)
    if with_base:
        ref_pair(st["base_t"], st["base"], idx, bbt, bb)
        if src:
            out = fn(st["VAL"], st["UUID"], st["BASE"], st["BASE_T"],
                     st["SRC"], i, dv, dt_, db, dbt, np.int32(base))
            (st["VAL"], st["UUID"], st["BASE"], st["BASE_T"],
             st["SRC"]) = out
        else:
            (st["VAL"], st["UUID"], st["BASE"], st["BASE_T"]) = fn(
                st["VAL"], st["UUID"], st["BASE"], st["BASE_T"], i, dv, dt_,
                db, dbt)
    elif src and iota:
        st["VAL"], st["UUID"], st["SRC"] = fn(
            st["VAL"], st["UUID"], st["SRC"], np.int32(idx[0]), np.int32(n),
            dv, dt_, np.int32(base), np_=len(np.asarray(i)))
    elif src:
        st["VAL"], st["UUID"], st["SRC"] = fn(
            st["VAL"], st["UUID"], st["SRC"], i, dv, dt_, np.int32(base))
    else:
        st["VAL"], st["UUID"] = fn(st["VAL"], st["UUID"], i, dv, dt_)


def run_elems(fn, st, sp, idx, rng, *_):
    n = len(idx)
    bat, ban, bdt = draw(rng, n), draw(rng, n), draw(rng, n)
    win = ref_pair(st["t"], st["n"], idx, bat, ban)
    ref_max(st["dt"], idx, bdt)
    i, a, x, d = padded(sp, idx, (bat, ban, bdt), (NEUTRAL_T, NEUTRAL_T, 0))
    st["T"], st["N"], st["DT"], got_win = fn(st["T"], st["N"], st["DT"],
                                             i, a, x, d)
    np.testing.assert_array_equal(np.asarray(got_win)[:n], win)


def run_max(fn, st, sp, idx, rng, *_):
    key = "env" if fn is B.bulk_max else "dt"
    vals = draw(rng, len(idx) * 4).reshape(-1, 4) if key == "env" \
        else draw(rng, len(idx))
    ref_max(st[key], idx, vals)
    i, v = padded(sp, idx, (vals,), (0,))
    st[key.upper()] = fn(st[key.upper()], i, v)


def run_patch(fn, st, sp, idx, rng, *_):
    names = ("t", "n", "dt")
    vals = draw(rng, len(idx) * 3).reshape(-1, 3)
    for c, name in enumerate(names):
        st[name][idx] = vals[:, c]
    # the engine's pad targets distinct rows past the plane ...
    bp = next_pow2(len(idx) + 1)
    vals_p = np.concatenate([vals, np.zeros((bp - len(idx), 3), np.int64)])
    out = fn(tuple(st[k.upper()] for k in names),
             jnp.array(_pad_idx(idx, sp, bp)), jnp.array(vals_p))
    # ... and the warm-up call's rows are all pad: nothing set
    out = fn(out, jnp.array(_pad_idx(np.zeros(0, np.int32), sp, bp)),
             jnp.zeros((bp, 3), jnp.int64))
    st["T"], st["N"], st["DT"] = out


PROGRAMS = {
    "bulk_lww": (B.bulk_lww, run_lww, False, False),
    "bulk_lww_win": (B.bulk_lww_win, run_lww_win),
    "bulk_lww_src": (B.bulk_lww_src, run_lww, True, False),
    "bulk_lww_src_iota": (B.bulk_lww_src_iota, run_lww, True, True),
    "bulk_counters_vu": (B.bulk_counters_vu, run_counters, False, False,
                         False),
    "bulk_counters": (B.bulk_counters, run_counters, False, False, True),
    "bulk_counters_vu_src": (B.bulk_counters_vu_src, run_counters, True,
                             False, False),
    "bulk_counters_vu_src_iota": (B.bulk_counters_vu_src_iota, run_counters,
                                  True, True, False),
    "bulk_counters_src": (B.bulk_counters_src, run_counters, True, False,
                          True),
    "bulk_elems": (B.bulk_elems, run_elems),
    "bulk_max": (B.bulk_max, run_max),
    "bulk_max1": (B.bulk_max1, run_max),
    "mirror_patch_el": (B.MIRROR_PATCH["el"], run_patch),
}


@pytest.mark.parametrize("name", list(PROGRAMS))
@pytest.mark.parametrize("seed", [0, 1])
def test_pair_program_equals_the_per_row_reference_over_chained_rounds(
        name, seed):
    fn, run, *flags = PROGRAMS[name]
    rng = np.random.default_rng(seed)
    sp = 64
    st = {k: draw(rng, sp) for k in ("t", "n", "dt", "val", "uuid", "base",
                                     "base_t")}
    st["env"] = draw(rng, sp * 4).reshape(sp, 4)
    st["src"] = np.full(sp, -1, np.int32)
    for k in list(st):
        st[k.upper()] = jnp.array(st[k]) if k == "src" else plane(st[k])
    base = 0
    for _ in range(5):
        n = int(rng.integers(1, sp))
        if flags[1:2] == [True]:             # contiguous rows: derived idx
            r0 = int(rng.integers(0, sp - n + 1))
            idx = np.arange(r0, r0 + n)
        else:
            idx = np.sort(rng.choice(sp, n, replace=False))
        run(fn, st, sp, idx, rng, base, *flags)
        base += next_pow2(n)
        for k in ("t", "n", "dt", "val", "uuid", "base", "base_t", "env",
                  "src"):
            np.testing.assert_array_equal(host(st[k.upper()]), st[k],
                                          err_msg=f"{name}: {k}")
            if k != "src":
                p = st[k.upper()]
                assert (p.hi.dtype, p.lo.dtype) == (jnp.int32, jnp.uint32)


@pytest.mark.parametrize("seed", [0, 1])
def test_the_whole_plane_edges(seed):
    """Build, neutral fill, grow, gather, whole-plane join and the
    counter contribution: the edges engine/tpu.py has, each in one place."""
    rng = np.random.default_rng(seed)
    v, w = draw(rng, 96), draw(rng, 96)
    p = plane(v)
    np.testing.assert_array_equal(np.asarray(B.plane_rows(p, n=50)), v[:50])
    idx = rng.integers(0, 96, 32).astype(np.int32)
    got = B.gather_rows(p, jnp.array(idx))
    assert got.dtype == jnp.int64
    np.testing.assert_array_equal(np.asarray(got), v[idx])
    src = jnp.arange(96, dtype=jnp.int32)      # not a Plane: as it is
    np.testing.assert_array_equal(
        np.asarray(B.gather_rows(src, jnp.array(idx))), idx)
    np.testing.assert_array_equal(np.asarray(B.plane_diff(p, plane(w))),
                                  v - w)       # wraps as int64 does
    for fill in (0, NEUTRAL_T, -1, I64_MIN, I64_MAX):
        np.testing.assert_array_equal(host(B.device_full(8, fill)),
                                      np.full(8, fill, np.int64))
        g = B.grown_plane(p, 32, fill)
        np.testing.assert_array_equal(
            host(g), np.concatenate([v, np.full(32, fill, np.int64)]))
    e = B.device_full(8, 0, cols=4)
    assert e.shape == (8, 4) and not host(e).any()
    assert B.device_full(8, -1, i32=True).dtype == jnp.int32


# ------------------------------------------------------ the engine's record

def assert_planes_are_pairs(eng) -> int:
    seen = 0
    for fam, res in eng._res.items():
        for name, p in res["cols"].items():
            assert isinstance(p, B.Plane), f"{fam}.{name}: {type(p)}"
            assert p.hi.dtype == jnp.int32 and p.lo.dtype == jnp.uint32
            assert p.hi.shape == p.lo.shape and p.shape[0] == res["cap"]
            seen += 1
        if res.get("src") is not None:
            assert res["src"].dtype == jnp.int32
    return seen


def test_every_resident_plane_the_engine_holds_is_the_pair():
    """Bulk catch-up, micro rounds and patches (a seeded interleaving of
    replicated runs and op-path writes), a grow, a flush: at every
    checkpoint `_res` holds Planes only and the repaired mirror is the
    host, and at the end the store is a CPU-engine node's."""
    src = Node(node_id=2)
    for i in range(90):
        src.execute(req(b"hset", b"h%d" % (i % 9), b"f%d" % i, b"v"))
        src.execute(req(b"sadd", b"s%d" % (i % 7), b"m%d" % i))
        src.execute(req(b"set", b"r%d" % (i % 5), b"v%d" % i))
        src.execute(req(b"incr", b"c%d" % (i % 4)))
    src.execute(req(b"del", b"c0"))              # a counter base pair
    src.execute(req(b"srem", b"s1", b"m1"))      # a delete side
    node, eng = device_node(warmup=0)
    ref = Node(node_id=1)
    n_planes = sum(len(_FAMILIES[f]) for f in JOURNAL_FAMILIES)
    for nd in (node, ref):                                         # bulk
        nd.merge_batch(batch_from_keyspace(src.ks))
    assert set(JOURNAL_FAMILIES) <= set(eng._res)
    assert assert_planes_are_pairs(eng) == n_planes

    def check(nd):
        assert_planes_are_pairs(nd.engine)
        repair_and_check(nd)
        assert_planes_are_pairs(nd.engine)

    steps = script(7, n_frames=240, keys=12)
    play(node, steps, check=check)                        # micro + patch
    play(ref, [("frames", 1, s[2]) if s[0] == "frames" else s
               for s in steps])
    assert eng.dev_rounds_resident > 0
    assert {f for f, c in eng.mirror_patches.items() if c} == \
        set(JOURNAL_FAMILIES)
    cap0 = eng._res["el"]["cap"]
    # a grow: a peer's hash of cap0 fields lands as one bulk round (the
    # micro rounds above grew the plane to the floor, 2^17 rows)
    big = Node(node_id=3)
    fields = []
    for i in range(cap0):
        fields += [b"g%d" % i, b"v"]
    big.execute(req(b"hset", b"big", *fields), uuid=u(10 ** 6))
    for nd in (node, ref):
        nd.merge_batch(batch_from_keyspace(big.ks))
    check(node)
    assert eng._res["el"]["cap"] > cap0
    assert sum(eng.mirror_rebuilds.values()) == 0
    node.ensure_flushed()                                          # flush
    assert assert_planes_are_pairs(eng) == n_planes
    assert node.canonical() == ref.canonical()
