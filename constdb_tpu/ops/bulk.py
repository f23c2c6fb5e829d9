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

import jax

jax.config.update("jax_enable_x64", True)

import jax.numpy as jnp  # noqa: E402

from ..crdt.semantics import NEUTRAL_T  # noqa: E402

__all__ = ["NEUTRAL_T", "device_full", "bulk_max", "bulk_max1", "bulk_lww",
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
# The *_src kernels track DEFERRED win resolution: instead of returning win
# flags (whose download blocks the pipeline every call — fatal when the
# device hangs off a high-latency link), the winning batch row's host
# value-pool id scatters into a resident int32 `src` plane.  Ids are NOT
# uploaded — pool entries are consecutive, so the kernel derives them as
# `base + iota` (zero extra host→device bytes).  The engine downloads the
# int32 `src` plane ONCE at flush and both resolves win values and
# RECONSTRUCTS the winner-carried columns (el add_t/add_node, reg
# rv_t/rv_node, cnt val/uuid) from host-side pools — those columns then
# never cross the link at all (the round-4 flush was ~45% of wall time,
# dominated by exactly these downloads).


@jax.jit
def gather_rows(state, idx):
    """Compact dirty-row gather: the flush path downloads ONLY the rows a
    resident plane's merges touched since the last flush — gather them
    into one contiguous [D] (or [D, C]) buffer on device, then a single
    small transfer replaces the whole-plane download.  Non-donating: the
    resident plane stays put."""
    return jnp.take(state, idx, axis=0)


@partial(jax.jit, static_argnames=("n", "fill", "i32"))
def device_full(n: int, fill: int, i32: bool = False):
    """Neutral state created ON device (avoids uploading zeros when every
    touched slot is brand new).  `i32` for the src plane — pool ids fit
    int32, halving its flush download."""
    return jnp.full((n,), fill, dtype=jnp.int32 if i32 else jnp.int64)


def _mirror_patch_fn(fam: str):
    """Mirror repair for one family: SET the host's values at the rows
    the op path wrote (engine/tpu.py _patch_mirror).  cols = the family's
    resident int64 planes (donated), idx [Bp] int32 sorted, vals [Bp, C]
    the host columns gathered at idx.  A plain assignment, not a merge:
    duplicate rows carry identical values (the pad repeats the last row),
    and an out-of-range idx drops (the warm-up call).  Named outside
    `jit_bulk_*` / `jit_dense_*` — it moves no merge byte, and the
    benchmark's merge roofline reads those modules' seconds."""
    def patch(cols, idx, vals):
        return tuple(c.at[idx].set(vals[:, i], mode="drop",
                                   indices_are_sorted=True)
                     for i, c in enumerate(cols))
    patch.__name__ = patch.__qualname__ = f"mirror_patch_{fam}"
    return jax.jit(patch, donate_argnums=(0,))


MIRROR_PATCH = {fam: _mirror_patch_fn(fam) for fam in ("reg", "cnt", "el")}


def _iota_src(base, np_: int):
    """Pool ids of one batch: consecutive from `base` (int32 on device)."""
    return base + jax.lax.iota(jnp.int32, np_)


@partial(jax.jit, donate_argnums=(0,))
def bulk_max(state, idx, cols):
    """state [Sp, C] ← elementwise max with one batch; idx [Np] int32,
    cols [Np, C].  Envelope merge (ct/mt/dt/expire are all max-merges)."""
    return state.at[idx].max(cols, mode="drop", unique_indices=True)


@partial(jax.jit, donate_argnums=(0,))
def bulk_max1(state, idx, vals):
    """One-column twin of bulk_max: state [Sp] ← per-slot max (the
    element DEL plane on the resident micro path — the host column and
    the device mirror advance together so a later bulk round never
    merges against a stale device del_t)."""
    return state.at[idx].max(vals, mode="drop", unique_indices=True)




def _pair_win(cv, ct, vi, ti, in_range):
    """Lexicographic (t, v) winner — shared by registers/elements/counters
    (the tie-rule core of crdt/semantics.py lww_wins/merge_counter_slot)."""
    return ((ti > ct) | ((ti == ct) & (vi > cv))) & in_range


@partial(jax.jit, donate_argnums=(0, 1))
def bulk_lww(t, n, idx, bt, bn):
    """Plain LWW slots (registers): lexicographic (t, node) winner.
    -> (t [Sp], n [Sp], win [Np] bool) — win marks batch rows whose VALUE
    must replace the slot's value."""
    size = t.shape[0]
    ic = jnp.minimum(idx, size - 1)
    ct, cn = t[ic], n[ic]
    win = _pair_win(cn, ct, bn, bt, idx < size)
    t = t.at[idx].set(jnp.where(win, bt, ct), mode="drop", unique_indices=True)
    n = n.at[idx].set(jnp.where(win, bn, cn), mode="drop", unique_indices=True)
    return t, n, win


@partial(jax.jit, donate_argnums=(0, 1))
def bulk_counters_vu(val, uuid, idx, bv, bt):
    """Counter value pair only — batches with a neutral base plane (no
    counter deletes anywhere in the batch, the overwhelmingly common case)
    skip uploading and merging the base columns entirely."""
    size = val.shape[0]
    ic = jnp.minimum(idx, size - 1)
    cv, ct = val[ic], uuid[ic]
    win = _pair_win(cv, ct, bv, bt, idx < size)
    val = val.at[idx].set(jnp.where(win, bv, cv), mode="drop",
                          unique_indices=True)
    uuid = uuid.at[idx].set(jnp.where(win, bt, ct), mode="drop",
                            unique_indices=True)
    return val, uuid


@partial(jax.jit, donate_argnums=(0, 1, 2, 3))
def bulk_counters(val, uuid, base, base_t, idx, bv, bt, bb, bbt):
    """Counter slots: two independent (value @ time) pairs per slot, each
    LWW on time with max-value tie-break.  -> merged (val, uuid, base,
    base_t), all [Sp]."""
    size = val.shape[0]
    ic = jnp.minimum(idx, size - 1)
    in_range = idx < size

    cv, ct = val[ic], uuid[ic]
    win = _pair_win(cv, ct, bv, bt, in_range)
    val = val.at[idx].set(jnp.where(win, bv, cv), mode="drop",
                          unique_indices=True)
    uuid = uuid.at[idx].set(jnp.where(win, bt, ct), mode="drop",
                            unique_indices=True)

    cb, cbt = base[ic], base_t[ic]
    win = _pair_win(cb, cbt, bb, bbt, in_range)
    base = base.at[idx].set(jnp.where(win, bb, cb), mode="drop",
                            unique_indices=True)
    base_t = base_t.at[idx].set(jnp.where(win, bbt, cbt), mode="drop",
                                unique_indices=True)
    return val, uuid, base, base_t


def _lww_src_body(t, n, src, idx, bt, bn, base):
    size = t.shape[0]
    ic = jnp.minimum(idx, size - 1)
    ct, cn, cs = t[ic], n[ic], src[ic]
    win = _pair_win(cn, ct, bn, bt, idx < size)
    t = t.at[idx].set(jnp.where(win, bt, ct), mode="drop", unique_indices=True)
    n = n.at[idx].set(jnp.where(win, bn, cn), mode="drop", unique_indices=True)
    src = src.at[idx].set(jnp.where(win, _iota_src(base, idx.shape[0]), cs),
                          mode="drop", unique_indices=True)
    return t, n, src


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
    size = val.shape[0]
    ic = jnp.minimum(idx, size - 1)
    cv, ct, cs = val[ic], uuid[ic], src[ic]
    win = _pair_win(cv, ct, bv, bt, idx < size)
    val = val.at[idx].set(jnp.where(win, bv, cv), mode="drop",
                          unique_indices=True)
    uuid = uuid.at[idx].set(jnp.where(win, bt, ct), mode="drop",
                            unique_indices=True)
    src = src.at[idx].set(jnp.where(win, _iota_src(base, idx.shape[0]), cs),
                          mode="drop", unique_indices=True)
    return val, uuid, src


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
    size = val.shape[0]
    ic = jnp.minimum(idx, size - 1)
    in_range = idx < size

    cv, ct, cs = val[ic], uuid[ic], src[ic]
    win = _pair_win(cv, ct, bv, bt, in_range)
    val = val.at[idx].set(jnp.where(win, bv, cv), mode="drop",
                          unique_indices=True)
    uuid = uuid.at[idx].set(jnp.where(win, bt, ct), mode="drop",
                            unique_indices=True)
    src = src.at[idx].set(jnp.where(win, _iota_src(base, idx.shape[0]), cs),
                          mode="drop", unique_indices=True)

    cb, cbt = base_c[ic], base_t[ic]
    win = _pair_win(cb, cbt, bb, bbt, in_range)
    base_c = base_c.at[idx].set(jnp.where(win, bb, cb), mode="drop",
                                unique_indices=True)
    base_t = base_t.at[idx].set(jnp.where(win, bbt, cbt), mode="drop",
                                unique_indices=True)
    return val, uuid, base_c, base_t, src


@partial(jax.jit, donate_argnums=(0, 1, 2))
def bulk_elems(at, an, dt, idx, bat, ban, bdt):
    """Element slots (set members / dict fields): add side = lexicographic
    (add_t, add_node) LWW, del side = plain max.
    -> (at, an, dt [Sp], win [Np] bool) — win marks rows whose dict VALUE
    must replace the slot's value."""
    size = at.shape[0]
    ic = jnp.minimum(idx, size - 1)
    ca, cn, cd = at[ic], an[ic], dt[ic]
    win = _pair_win(cn, ca, ban, bat, idx < size)
    at = at.at[idx].set(jnp.where(win, bat, ca), mode="drop",
                        unique_indices=True)
    an = an.at[idx].set(jnp.where(win, ban, cn), mode="drop",
                        unique_indices=True)
    dt = dt.at[idx].max(bdt, mode="drop", unique_indices=True)
    return at, an, dt, win


bulk_elems_src_nodt = bulk_lww_src
bulk_elems_src_nodt_iota = bulk_lww_src_iota
bulk_elems_nodt = bulk_lww
