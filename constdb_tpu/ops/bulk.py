"""Compact bulk-merge kernels: per-batch gather → merge → scatter on device.

The transfer-optimal device path for bulk merges (snapshot ingest, replica
catch-up).  The host ships each batch as COMPACT rows — int32 slot ids plus
value columns — and folds batches into per-slot device state one kernel call
per batch.  State is donated, so it never leaves the device between calls,
and `jax.device_put` is async, so batch b+1 uploads while batch b merges.

Within one batch every slot appears at most once
(`ColumnarBatch.rows_unique_per_slot`), so scatters carry
`unique_indices=True` and run at HBM speed; collisions exist only ACROSS
batches, which the call sequence serializes by construction.

Contrast with ops/dense.py (the [R, S] pad-align strategy): dense inflates
host→device traffic by R× the slot space, which is the dominant cost when
the device hangs off a slow host link; compact moves each row exactly once.
Measured on v5e: the merge step itself is ~0.5 ms for 8×1M rows — bulk
merge throughput is bounded by the interconnect, not the VPU.

Plane layout: a per-slot int64 stamp plane lives on the device as a
`Plane` — two flat arrays `(hi: int32[cap], lo: uint32[cap])` with
`value = (hi << 32) | lo`.  Signed hi, unsigned lo: lexicographic
(hi, lo) order IS signed int64 order, so every merge below compares and
scatters 32-bit lanes.  The TPU has no 64-bit integer unit: an int64
plane is split into halves at a program's entry and recombined at its
exit, whole-plane passes whatever the batch holds.  The pair is the ONLY
form a state operand has here, on every backend; BATCH operands (`bt`,
`bn`, patch `vals`) stay int64 (or int32) and the program splits them
itself over the batch's rows, and gathers join their rows back to int64;
the micro round's `bulk_lww_win` takes its batch already split, as one
int32 block.

Padding protocol: rows are padded to a power-of-two count; padded rows get
slot id = state_size + offset (distinct, out of bounds), so scatters drop
them (`mode='drop'`), gathers clamp, and win-flags mask them off.

All semantics mirror crdt/semantics.py exactly:
  * LWW pair: (t, writer-node) lexicographic max — registers, element adds;
  * counter slot pair: (time, value) lexicographic max — max-value on ties;
  * plain max: envelopes ct/mt/dt/expire, element del_t.
"""

from __future__ import annotations

from functools import partial
from typing import NamedTuple

import jax

jax.config.update("jax_enable_x64", True)

import jax.numpy as jnp  # noqa: E402

from ..crdt.semantics import NEUTRAL_T  # noqa: E402

__all__ = ["NEUTRAL_T", "Plane", "plane_split", "plane_rows", "plane_diff",
           "neutral_plane", "grown_plane", "device_full", "bulk_max",
           "bulk_max1", "bulk_lww", "bulk_lww_win",
           "bulk_counters", "bulk_counters_vu", "bulk_counters_vu_src",
           "bulk_counters_src", "bulk_elems",
           "bulk_lww_src", "bulk_elems_src_nodt", "bulk_elems_nodt",
           "bulk_lww_src_iota", "bulk_counters_vu_src_iota",
           "bulk_elems_src_nodt_iota", "gather_rows", "MIRROR_PATCH"]

# An element add-side without its del side IS the plain LWW pair — same
# kernels, no duplicate _pair_win call sites:
#   * bulk_elems_src_nodt(at, an, src, idx, bat, ban, base)
#   * bulk_elems_nodt(at, an, idx, bat, ban) -> (at, an, win-ignored)
# (aliases assigned after the definitions below).  The element DEL side
# never touches the device in the resident src path: del-merge is a plain
# max the engine applies straight to the host column (engine/tpu.py).
#
# Who won is told to the host in one of two ways, by the kind of round:
#
#   * A WHOLE-PLANE round (boot restore, catch-up: tens of thousands of
#     rows a call, many calls before anything reads) runs a *_src kernel,
#     which tracks DEFERRED win resolution: instead of returning win flags
#     (whose download blocks the pipeline every call — fatal when the
#     device hangs off a high-latency link), the winning batch row's host
#     value-pool id scatters into a resident int32 `src` plane.  Ids are
#     NOT uploaded — pool entries are consecutive, so the kernel derives
#     them as `base + iota` (zero extra host→device bytes).  The engine
#     downloads the int32 `src` plane ONCE at flush and both resolves win
#     values and RECONSTRUCTS the winner-carried columns (el
#     add_t/add_node, reg rv_t/rv_node, cnt val/uuid) from host-side
#     pools — those columns then never cross the link at all (the round-4
#     flush was ~45% of wall time, dominated by exactly these downloads).
#   * A resident MICRO round (a coalesced run of served or replicated
#     writes: a handful of rows, and a read flushes a few hundred
#     microseconds later) runs `bulk_lww_win`, which RETURNS the win flags
#     of its own batch — `np2` bytes, downloaded as the program ends.  The
#     host holds the batch (its pool entry) and applies the rounds in
#     order at the flush: no plane-sized `src` array is created, scattered
#     into or gathered from.  A micro round that lands on a family still
#     carrying a whole-plane round's unflushed `src` keeps the *_src
#     kernel (engine/tpu.py _scatter_pair chooses on the family's record).


class Plane(NamedTuple):
    """One int64 stamp plane as the device holds it (see the module
    docstring): a pytree of two same-shape arrays, so it passes through
    `jit`, `donate_argnums` and shardings as one operand."""
    hi: jax.Array   # int32: the signed high word
    lo: jax.Array   # uint32: the unsigned low word

    @property
    def shape(self):
        return self.hi.shape


def _split(x) -> Plane:
    """A batch operand's int64 (or int32) values as a pair, in-program."""
    x = x.astype(jnp.int64)
    return Plane((x >> 32).astype(jnp.int32),
                 (x & 0xFFFFFFFF).astype(jnp.uint32))


def _join(p: Plane):
    return (p.hi.astype(jnp.int64) << 32) | p.lo.astype(jnp.int64)


def _take(p: Plane, ic) -> Plane:
    return Plane(p.hi[ic], p.lo[ic])


def _where(m, a: Plane, b: Plane) -> Plane:
    return Plane(jnp.where(m, a.hi, b.hi), jnp.where(m, a.lo, b.lo))


def _set(p: Plane, idx, v: Plane, **kw) -> Plane:
    return Plane(p.hi.at[idx].set(v.hi, mode="drop", **kw),
                 p.lo.at[idx].set(v.lo, mode="drop", **kw))


def _gt(a: Plane, b: Plane):
    """a > b as int64s: (signed hi, unsigned lo) lexicographic."""
    return (a.hi > b.hi) | ((a.hi == b.hi) & (a.lo > b.lo))


def _eq(a: Plane, b: Plane):
    return (a.hi == b.hi) & (a.lo == b.lo)


@jax.jit
def gather_rows(state, idx):
    """Compact dirty-row gather: the flush path downloads ONLY the rows a
    resident plane's merges touched since the last flush — gather them
    into one contiguous [D] (or [D, C]) buffer on device, then a single
    small transfer replaces the whole-plane download.  A Plane's rows
    join to int64 here, over D rows; any other array (the int32 src
    plane, a tensor payload pool) gathers as it is.  Non-donating: the
    resident plane stays put."""
    got = jax.tree.map(lambda a: jnp.take(a, idx, axis=0), state)
    return _join(got) if isinstance(state, Plane) else got


@jax.jit
def plane_split(x) -> Plane:
    """An uploaded int64 array as a Plane — the build edge (engine/tpu.py
    _put_state): one split pass over a plane built whole, where a host
    split of 16,777,216 rows costs 25 times the upload itself."""
    return _split(x)


@partial(jax.jit, static_argnames=("n",))
def plane_rows(plane: Plane, *, n: int):
    """Rows [0, n) of a Plane as int64: the whole-plane download of a
    bulk-merged family, joined once a flush."""
    return _join(Plane(plane.hi[:n], plane.lo[:n]))


@jax.jit
def plane_diff(a: Plane, b: Plane):
    """a - b as int64 (counter contributions val - base, for the
    flush-time segment sum)."""
    return _join(a) - _join(b)


def neutral_plane(shape, fill: int) -> Plane:
    """A Plane of `shape` holding `fill` everywhere (traced: the engine
    wraps it where it needs an output sharding)."""
    return Plane(jnp.full(shape, fill >> 32, dtype=jnp.int32),
                 jnp.full(shape, fill & 0xFFFFFFFF, dtype=jnp.uint32))


def grown_plane(old: Plane, delta: int, fill: int) -> Plane:
    """`old` extended by `delta` rows of `fill` (traced or eager, like
    neutral_plane)."""
    new = neutral_plane((delta,) + old.shape[1:], fill)
    return Plane(jnp.concatenate([old.hi, new.hi]),
                 jnp.concatenate([old.lo, new.lo]))


@partial(jax.jit, static_argnames=("n", "fill", "i32", "cols"))
def device_full(n: int, fill: int, i32: bool = False, cols: int = 0):
    """Neutral state created ON device (avoids uploading zeros when every
    touched slot is brand new): a Plane of [n] (or [n, cols]) rows.
    `i32` for the src plane — pool ids fit one int32 array, halving its
    flush download."""
    if i32:
        return jnp.full((n,), fill, dtype=jnp.int32)
    return neutral_plane((n, cols) if cols else (n,), fill)


def _mirror_patch_fn(fam: str):
    """Mirror repair for one family: SET the host's values at the rows
    the op path wrote (engine/tpu.py _patch_mirror).  cols = the family's
    resident Planes (donated), idx [Bp] int32 sorted, vals [Bp, C] int64
    the host columns gathered at idx.  A plain assignment, not a merge.
    The rows are distinct and the pad targets distinct rows past the plane
    and drops (the one pad protocol every scatter here has; the warm-up
    call is all pad).  The chip scatters row by row either way: 1.2 ms of
    device time for a 1,024-row bucket whatever it holds.  Named outside
    `jit_bulk_*` / `jit_dense_*` — it moves no merge byte, and the
    benchmark's merge roofline reads those modules' seconds."""
    def patch(cols, idx, vals):
        return tuple(_set(c, idx, _split(vals[:, i]),
                          indices_are_sorted=True, unique_indices=True)
                     for i, c in enumerate(cols))
    patch.__name__ = patch.__qualname__ = f"mirror_patch_{fam}"
    return jax.jit(patch, donate_argnums=(0,))


MIRROR_PATCH = {fam: _mirror_patch_fn(fam) for fam in ("reg", "cnt", "el")}


def _iota_src(base, np_: int):
    """Pool ids of one batch: consecutive from `base` (int32 on device)."""
    return base + jax.lax.iota(jnp.int32, np_)


def _max_body(state: Plane, idx, vals) -> Plane:
    """Per-slot max as gather-compare-set (each slot once per batch, so
    the set is collision-free); rows past the plane drop."""
    ic = jnp.minimum(idx, state.shape[0] - 1)
    cur, b = _take(state, ic), _split(vals)
    return _set(state, idx, _where(_gt(b, cur), b, cur), unique_indices=True)


@partial(jax.jit, donate_argnums=(0,))
def bulk_max(state, idx, cols):
    """state [Sp, C] ← elementwise max with one batch; idx [Np] int32,
    cols [Np, C].  Envelope merge (ct/mt/dt/expire are all max-merges)."""
    return _max_body(state, idx, cols)


@partial(jax.jit, donate_argnums=(0,))
def bulk_max1(state, idx, vals):
    """One-column twin of bulk_max: state [Sp] ← per-slot max (the
    element DEL plane on the resident micro path — the host column and
    the device mirror advance together so a later bulk round never
    merges against a stale device del_t)."""
    return _max_body(state, idx, vals)


def _pair_win(cv: Plane, ct: Plane, vi: Plane, ti: Plane, in_range):
    """Lexicographic (t, v) winner — shared by registers/elements/counters
    (the tie-rule core of crdt/semantics.py lww_wins/merge_counter_slot),
    as a four-level compare on 32-bit lanes."""
    return (_gt(ti, ct) | (_eq(ti, ct) & _gt(vi, cv))) & in_range


def _merge_planes(t: Plane, v: Plane, idx, bt: Plane, bv: Plane):
    """One (t, v) LWW pair merged with a batch already in pairs
    -> (t, v, win [Np] bool): gather the current rows, pick the winner,
    set both planes.  32-bit lanes throughout."""
    size = t.shape[0]
    ic = jnp.minimum(idx, size - 1)
    ct, cv = _take(t, ic), _take(v, ic)
    win = _pair_win(cv, ct, bv, bt, idx < size)
    return (_set(t, idx, _where(win, bt, ct), unique_indices=True),
            _set(v, idx, _where(win, bv, cv), unique_indices=True), win)


def _merge_pair(t: Plane, v: Plane, idx, bt, bv):
    """_merge_planes for int64 (or int32) batch columns, split here."""
    return _merge_planes(t, v, idx, _split(bt), _split(bv))


def _track_src(src, idx, win, base):
    """Winners' pool ids (`base + iota`) scattered into the src plane."""
    cs = src[jnp.minimum(idx, src.shape[0] - 1)]
    return src.at[idx].set(jnp.where(win, _iota_src(base, idx.shape[0]), cs),
                           mode="drop", unique_indices=True)


@partial(jax.jit, donate_argnums=(0, 1))
def bulk_lww(t, n, idx, bt, bn):
    """Plain LWW slots (registers): lexicographic (t, node) winner.
    -> (t [Sp], n [Sp], win [Np] bool) — win marks batch rows whose VALUE
    must replace the slot's value."""
    return _merge_pair(t, n, idx, bt, bn)


@partial(jax.jit, donate_argnums=(0, 1))
def bulk_lww_win(t, n, blk):
    """bulk_lww for a resident micro round, one operand up and the win
    flags down: `blk` int32 [5, Np] = the padded idx, then (hi, lo) of the
    primary and of the secondary column, split on the host over the
    batch's rows (lo's bit pattern as int32).  No int64 in the program.
    -> (t, n, win [Np] bool); the engine applies `win` to the host
    columns at the flush (engine/tpu.py _apply_wins)."""
    def col(r):
        return Plane(blk[r], jax.lax.bitcast_convert_type(blk[r + 1],
                                                          jnp.uint32))
    return _merge_planes(t, n, blk[0], col(1), col(3))


@partial(jax.jit, donate_argnums=(0, 1))
def bulk_counters_vu(val, uuid, idx, bv, bt):
    """Counter value pair only — batches with a neutral base plane (no
    counter deletes anywhere in the batch, the overwhelmingly common case)
    skip uploading and merging the base columns entirely."""
    uuid, val, _ = _merge_pair(uuid, val, idx, bt, bv)
    return val, uuid


@partial(jax.jit, donate_argnums=(0, 1, 2, 3))
def bulk_counters(val, uuid, base, base_t, idx, bv, bt, bb, bbt):
    """Counter slots: two independent (value @ time) pairs per slot, each
    LWW on time with max-value tie-break.  -> merged (val, uuid, base,
    base_t), all [Sp]."""
    uuid, val, _ = _merge_pair(uuid, val, idx, bt, bv)
    base_t, base, _ = _merge_pair(base_t, base, idx, bbt, bb)
    return val, uuid, base, base_t


def _lww_src_body(t, n, src, idx, bt, bn, base):
    t, n, win = _merge_pair(t, n, idx, bt, bn)
    return t, n, _track_src(src, idx, win, base)


@partial(jax.jit, donate_argnums=(0, 1, 2))
def bulk_lww_src(t, n, src, idx, bt, bn, base):
    """bulk_lww with deferred win resolution (see the *_src block comment
    at the top of the file): winners scatter `base + iota` into `src`."""
    return _lww_src_body(t, n, src, idx, bt, bn, base)


def _idx_iota(r0, nrows, np_: int, size):
    """Contiguous batch idx derived on device: [r0, r0+nrows) then
    out-of-range pad slots — same protocol as the host-built vector."""
    i = jax.lax.iota(jnp.int32, np_)
    return jnp.where(i < nrows, r0 + i, size + i)


@partial(jax.jit, donate_argnums=(0, 1, 2), static_argnames=("np_",))
def bulk_lww_src_iota(t, n, src, r0, nrows, bt, bn, base, *, np_: int):
    """bulk_lww_src for CONTIGUOUS batch rows: the idx vector is derived
    inside the same kernel from (r0, nrows) scalars — one dispatch instead
    of an iota build plus a scatter, and no intermediate idx buffer."""
    idx = _idx_iota(r0, nrows, np_, t.shape[0])
    return _lww_src_body(t, n, src, idx, bt, bn, base)


def _counters_vu_src_body(val, uuid, src, idx, bv, bt, base):
    uuid, val, win = _merge_pair(uuid, val, idx, bt, bv)
    return val, uuid, _track_src(src, idx, win, base)


@partial(jax.jit, donate_argnums=(0, 1, 2))
def bulk_counters_vu_src(val, uuid, src, idx, bv, bt, base):
    """bulk_counters_vu with deferred win resolution: the merged val/uuid
    pair is RECONSTRUCTED at flush from the host pool via `src`, so the two
    widest counter columns never download."""
    return _counters_vu_src_body(val, uuid, src, idx, bv, bt, base)


@partial(jax.jit, donate_argnums=(0, 1, 2), static_argnames=("np_",))
def bulk_counters_vu_src_iota(val, uuid, src, r0, nrows, bv, bt, base, *,
                              np_: int):
    """bulk_counters_vu_src for CONTIGUOUS batch rows (see
    bulk_lww_src_iota)."""
    idx = _idx_iota(r0, nrows, np_, val.shape[0])
    return _counters_vu_src_body(val, uuid, src, idx, bv, bt, base)


@partial(jax.jit, donate_argnums=(0, 1, 2, 3, 4))
def bulk_counters_src(val, uuid, base_c, base_t, src, idx, bv, bt, bb, bbt,
                      base):
    """bulk_counters with deferred win resolution on the val/uuid pair
    (the base pair keeps its own winner on device and downloads when
    written — counter deletes are rare)."""
    val, uuid, src = _counters_vu_src_body(val, uuid, src, idx, bv, bt, base)
    base_t, base_c, _ = _merge_pair(base_t, base_c, idx, bbt, bb)
    return val, uuid, base_c, base_t, src


@partial(jax.jit, donate_argnums=(0, 1, 2))
def bulk_elems(at, an, dt, idx, bat, ban, bdt):
    """Element slots (set members / dict fields): add side = lexicographic
    (add_t, add_node) LWW, del side = plain max.
    -> (at, an, dt [Sp], win [Np] bool) — win marks rows whose dict VALUE
    must replace the slot's value."""
    at, an, win = _merge_pair(at, an, idx, bat, ban)
    return at, an, _max_body(dt, idx, bdt), win


bulk_elems_src_nodt = bulk_lww_src
bulk_elems_src_nodt_iota = bulk_lww_src_iota
bulk_elems_nodt = bulk_lww
