"""Fused dense + resident-scatter CRDT merge kernels in Pallas (TPU).

Two kernel families live here:

  * FOLD kernels (`merge_elems`, `merge_counters`): one VMEM pass computes
    what the XLA path (ops/dense.py) expresses as several reductions + an
    argmax — the lexicographic (add_t, add_node) winner, the merged del
    side, and the winning replica row, over [R, S] dense merge tensors
    blocked along S.
  * RESIDENT-SCATTER kernels (`scatter_pair_src`, `segment_sum`): the
    steady-state path for device-resident planes (engine/tpu.py micro
    merges).  `scatter_pair_src` is a gather-compare-scatter over one LWW
    pair: a scalar-prefetched slot-id vector drives the BlockSpec index
    maps, so each grid step DMAs exactly the state row the batch row
    targets, runs the lexicographic compare, and writes the winner back
    in place (`input_output_aliases` — untouched rows never move).
    `segment_sum` re-derives per-key counter sums from resident slot
    contributions with a VMEM scratch accumulator carried across the
    sequential TPU grid.

TPU VMEM lanes are 32-bit, so int64 columns travel as two int32/uint32
planes; a signed 64-bit comparison is exactly the lexicographic
(hi signed, lo unsigned) comparison.  All merge values here (uuids,
NEUTRAL_T, node ids) are ordinary int64s, so the split/join is lossless;
`segment_sum` accumulates the pair with an explicit unsigned carry, which
is exact mod 2^64 (host sums are int64, so no real sum can wrap).

`merge_elems(..., interpret=True)` (and every kernel here) runs through
the Pallas interpreter on CPU — that is how tests/test_pallas_dense.py
differential-tests them against ops/dense.py, ops/bulk.py, and the host
reference without TPU hardware.

On the chip: the fold kernels and `tensor_reduce` compile through Mosaic
for the v5e; `scatter_pair_src_split` and `segment_sum` do not — both
walk (1, 1) blocks over (N, 1) column planes, one grid step per row,
which the (8, 128) tile rule refuses and a lane-dense redesign would
have to replace — so the engine selects their XLA twins there
(engine/tpu.py AUTO_TPU_KERNELS) and they run in interpret mode only.
"""

from __future__ import annotations

from functools import partial

import jax
import numpy as np

jax.config.update("jax_enable_x64", True)

import jax.numpy as jnp  # noqa: E402
from jax.experimental import pallas as pl  # noqa: E402
from jax.experimental.pallas import tpu as pltpu  # noqa: E402

BLOCK_S = 512
_I32_MIN = np.int32(np.iinfo(np.int32).min)
# index-map literal: a bare Python 0 traces as int64 under x64, which
# Mosaic does not take
_Z = np.int32(0)


def _split64(x):
    """int64 -> (hi int32, lo uint32); (hi, lo) lex order == int64 order."""
    return ((x >> 32).astype(jnp.int32),
            (x & jnp.int64(0xFFFFFFFF)).astype(jnp.uint32))


def _join64(hi, lo):
    return (hi.astype(jnp.int64) << 32) | lo.astype(jnp.int64)


def _split64_ord(x):
    """int64 -> (hi int32, lo int32 with its sign bit flipped): SIGNED
    lex order on the pair == int64 order.  The fold kernels reduce along
    sublanes, and Mosaic has no unsigned reduction ("Reductions over
    unsigned integers not implemented"), so their lo halves travel
    order-preserved as int32."""
    hi, lo = _split64(x)
    return hi, jax.lax.bitcast_convert_type(lo ^ jnp.uint32(1 << 31),
                                            jnp.int32)


def _join64_ord(hi, lo):
    return _join64(hi, jax.lax.bitcast_convert_type(lo, jnp.uint32)
                   ^ jnp.uint32(1 << 31))


def _lex_mask(hi, lo, mask):
    """Among rows where `mask`, the rows achieving the (hi, lo) lex max
    (`_split64_ord` halves: both signed).
    -> (new_mask, m_hi [1, S], m_lo [1, S])."""
    hi_c = jnp.where(mask, hi, _I32_MIN)
    m_hi = jnp.max(hi_c, axis=0, keepdims=True)
    mask = mask & (hi == m_hi)
    lo_c = jnp.where(mask, lo, _I32_MIN)
    m_lo = jnp.max(lo_c, axis=0, keepdims=True)
    mask = mask & (lo == m_lo)
    return mask, m_hi, m_lo


def _elems_kernel(at_hi, at_lo, an_hi, an_lo, dt_hi, dt_lo,
                  o_at_hi, o_at_lo, o_an_hi, o_an_lo, o_dt_hi, o_dt_lo,
                  o_win):
    R = at_hi.shape[0]
    full = jnp.ones(at_hi.shape, dtype=jnp.bool_)

    # 4-level lexicographic winner: (at_hi, at_lo, an_hi, an_lo)
    m, ah, al = _lex_mask(at_hi[...], at_lo[...], full)
    m, nh, nl = _lex_mask(an_hi[...], an_lo[...], m)

    # first winning row (ties share identical (t, node) == the same write)
    rows = jax.lax.broadcasted_iota(jnp.int32, at_hi.shape, 0)
    win = jnp.min(jnp.where(m, rows, jnp.int32(R)), axis=0, keepdims=True)

    # del side: independent 2-level max
    _, dh, dl = _lex_mask(dt_hi[...], dt_lo[...], full)

    o_at_hi[...] = ah
    o_at_lo[...] = al
    o_an_hi[...] = nh
    o_an_lo[...] = nl
    o_dt_hi[...] = dh
    o_dt_lo[...] = dl
    o_win[...] = win


@partial(jax.jit, static_argnames=("interpret",))
def merge_elems(at, an, dt, interpret: bool = False):
    """Fused [R, S] element merge: lexicographic (add_t, add_node) winner +
    max del_t.  -> (at[S], an[S], dt[S], win_batch[S]) — bit-identical to
    ops/dense.py dense_merge_elems."""
    R, S = at.shape
    sp = -(-S // BLOCK_S) * BLOCK_S
    neutral = jnp.int64(-(1 << 62))

    def prep(x, fill):
        if sp != S:
            x = jnp.concatenate(
                [x, jnp.full((R, sp - S), fill, dtype=jnp.int64)], axis=1)
        return _split64_ord(x)

    planes = [*prep(at, neutral), *prep(an, neutral), *prep(dt, 0)]
    grid = (sp // BLOCK_S,)
    in_spec = pl.BlockSpec((R, BLOCK_S), lambda i: (_Z, i))
    out_spec = pl.BlockSpec((1, BLOCK_S), lambda i: (_Z, i))
    shapes = [jax.ShapeDtypeStruct((1, sp), jnp.int32)] * 7
    out = pl.pallas_call(
        _elems_kernel,
        grid=grid,
        in_specs=[in_spec] * 6,
        out_specs=[out_spec] * 7,
        out_shape=shapes,
        interpret=interpret,
    )(*planes)
    ah, al, nh, nl, dh, dl, win = (o[0] for o in out)
    return (_join64_ord(ah, al)[:S], _join64_ord(nh, nl)[:S],
            _join64_ord(dh, dl)[:S], win.astype(jnp.int64)[:S])


def _counters_kernel(v_hi, v_lo, t_hi, t_lo, o_v_hi, o_v_lo, o_t_hi, o_t_lo):
    full = jnp.ones(v_hi.shape, dtype=jnp.bool_)
    # (uuid, value) lexicographic max == LWW with max-value tie-break
    m, th, tl = _lex_mask(t_hi[...], t_lo[...], full)
    _, vh, vl = _lex_mask(v_hi[...], v_lo[...], m)
    o_v_hi[...] = vh
    o_v_lo[...] = vl
    o_t_hi[...] = th
    o_t_lo[...] = tl


@partial(jax.jit, static_argnames=("interpret",))
def merge_counters(vals, ts, interpret: bool = False):
    """Fused [R, S] counter-slot merge: per-slot (value @ uuid) LWW with
    max-value tie — bit-identical to ops/dense.py dense_merge_counters."""
    R, S = vals.shape
    sp = -(-S // BLOCK_S) * BLOCK_S
    neutral = jnp.int64(-(1 << 62))

    def prep(x, fill):
        if sp != S:
            x = jnp.concatenate(
                [x, jnp.full((R, sp - S), fill, dtype=jnp.int64)], axis=1)
        return _split64_ord(x)

    planes = [*prep(vals, neutral), *prep(ts, neutral)]
    in_spec = pl.BlockSpec((R, BLOCK_S), lambda i: (_Z, i))
    out_spec = pl.BlockSpec((1, BLOCK_S), lambda i: (_Z, i))
    shapes = [jax.ShapeDtypeStruct((1, sp), jnp.int32)] * 4
    out = pl.pallas_call(
        _counters_kernel,
        grid=(sp // BLOCK_S,),
        in_specs=[in_spec] * 4,
        out_specs=[out_spec] * 4,
        out_shape=shapes,
        interpret=interpret,
    )(*planes)
    vh, vl, th, tl = (o[0] for o in out)
    return _join64_ord(vh, vl)[:S], _join64_ord(th, tl)[:S]


# ------------------------------------------------------- resident scatter
# The steady-state kernels: engine/tpu.py's resident micro path folds a
# micro-batch's duplicate slots on host (rows become unique) and then
# merges the folded rows IN PLACE against device-resident planes.  The
# slot-id vector is scalar-prefetched, so the BlockSpec index maps gather
# (and scatter back) exactly the touched state rows — the gather-compare-
# scatter the XLA twins in ops/bulk.py express as `state.at[idx].set`.
#
# Contract shared with the XLA twins: slot ids are UNIQUE within one call
# (the host fold guarantees it) — each real state row is visited by at
# most one grid step, so the aliased in-place writes can never race.  The
# caller PRE-PADS (idx, bp, bs) to one shared pow2 length (the jit then
# retraces per pow2 bucket, not per batch size): padded rows carry
# (NEUTRAL_T, NEUTRAL_T) batch values — which lose every comparison, so
# they rewrite their target row with its own current value — and MUST
# target an in-range row that NO real row targets (unique rows over a
# pow2 plane leave one whenever padding is needed; engine/tpu.py
# _scatter_pad_row finds it).  A pad aliased onto a real row's target
# would re-write it from a STALE pre-merge read and silently revert the
# merge — pinned by test_pallas_dense.py's pad-collision case.

_NEUTRAL64 = jnp.int64(-(1 << 62))

# ONE pow2-rounding policy across the ops modules (callers and tests
# reach it as PD._pow2)
from .segment import next_pow2 as _pow2  # noqa: E402


def split_plane(x):
    """int64 plane [Sp] -> pre-split ((Sp, 1) int32 hi, (Sp, 1) uint32
    lo) COLUMN form — the storage layout `scatter_pair_src_split`
    consumes and produces, so consecutive micro rounds never pay the
    O(plane) split/join wrapper (the PR 8 flagged follow-up).  Column
    shape on purpose: the kernel reads (1, 1) blocks of (Sp, 1) planes,
    and keeping the stored form identical to the kernel form lets the
    jit-level donation alias buffers across rounds."""
    hi, lo = _split64(x)
    return hi.reshape(-1, 1), lo.reshape(-1, 1)


split_plane = jax.jit(split_plane)


@jax.jit
def join_plane(hi, lo):
    """Pre-split (Sp, 1) pair -> int64 [Sp] (the bulk kernels and the
    resident-state grow path still speak int64)."""
    return _join64(hi[:, 0], lo[:, 0])


def _scatter_pair_kernel(idx_ref, base_ref,
                         p_hi, p_lo, s_hi, s_lo, src,
                         bp_hi, bp_lo, bs_hi, bs_lo,
                         o_p_hi, o_p_lo, o_s_hi, o_s_lo, o_src):
    i = pl.program_id(0)
    cp_hi, cp_lo = p_hi[0, 0], p_lo[0, 0]
    cs_hi, cs_lo = s_hi[0, 0], s_lo[0, 0]
    np_hi, np_lo = bp_hi[0, 0], bp_lo[0, 0]
    ns_hi, ns_lo = bs_hi[0, 0], bs_lo[0, 0]
    # 64-bit lexicographic (primary, secondary) >: exactly ops/bulk.py
    # _pair_win with the int64s split (hi signed, lo unsigned)
    gt_p = (np_hi > cp_hi) | ((np_hi == cp_hi) & (np_lo > cp_lo))
    eq_p = (np_hi == cp_hi) & (np_lo == cp_lo)
    gt_s = (ns_hi > cs_hi) | ((ns_hi == cs_hi) & (ns_lo > cs_lo))
    win = gt_p | (eq_p & gt_s)
    o_p_hi[0, 0] = jnp.where(win, np_hi, cp_hi)
    o_p_lo[0, 0] = jnp.where(win, np_lo, cp_lo)
    o_s_hi[0, 0] = jnp.where(win, ns_hi, cs_hi)
    o_s_lo[0, 0] = jnp.where(win, ns_lo, cs_lo)
    o_src[0, 0] = jnp.where(win, base_ref[0] + jnp.int32(i), src[0, 0])


@partial(jax.jit, static_argnames=("interpret",),
         donate_argnums=(0, 1, 2, 3, 4))
def scatter_pair_src_split(p_hi, p_lo, s_hi, s_lo, src, idx, bp, bs, base,
                           interpret: bool = False):
    """Gather-compare-scatter one LWW pair against PRE-SPLIT resident
    state planes — the steady-state form of `scatter_pair_src`.

    `p_hi`/`s_hi` [Sp, 1] int32 and `p_lo`/`s_lo` [Sp, 1] uint32 are the
    hi/lo halves of the int64 planes in `split_plane`'s column layout;
    `src` [Sp] int32; `idx`/`bp`/`bs`/`base` exactly as in the int64
    wrapper below.  -> (p_hi, p_lo, s_hi, s_lo, src) merged IN PLACE:
    input and output dtypes now MATCH, so the `input_output_aliases` are
    true aliases and the jit-level donations are live — consecutive
    micro rounds on a warm plane run ZERO whole-plane passes (the PR 8
    flagged follow-up: the old wrapper re-split and re-joined the full
    plane around every call).  engine/tpu.py keeps the split pair as the
    plane's truth between rounds and joins only at bulk-round / grow
    boundaries (`join_plane`)."""
    np_ = idx.shape[0]
    sp = p_hi.shape[0]
    bp_hi, bp_lo = (x.reshape(np_, 1) for x in _split64(bp))
    bs_hi, bs_lo = (x.reshape(np_, 1) for x in _split64(bs))
    state_spec = pl.BlockSpec((1, 1), lambda i, idx_ref, base_ref:
                              (idx_ref[i], 0))
    batch_spec = pl.BlockSpec((1, 1), lambda i, idx_ref, base_ref: (i, 0))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(np_,),
        in_specs=[state_spec] * 5 + [batch_spec] * 4,
        out_specs=[state_spec] * 5,
    )
    shapes = [jax.ShapeDtypeStruct((sp, 1), jnp.int32),
              jax.ShapeDtypeStruct((sp, 1), jnp.uint32)] * 2 + \
        [jax.ShapeDtypeStruct((sp, 1), jnp.int32)]
    out = pl.pallas_call(
        _scatter_pair_kernel,
        grid_spec=grid_spec,
        out_shape=shapes,
        # operand numbering includes the scalar-prefetch args: 0=idx,
        # 1=base, 2..6 = the five state planes -> outputs 0..4 in place
        input_output_aliases={2: 0, 3: 1, 4: 2, 5: 3, 6: 4},
        interpret=interpret,
    )(idx, jnp.full(1, base, dtype=jnp.int32),
      p_hi, p_lo, s_hi, s_lo, src.reshape(sp, 1),
      bp_hi, bp_lo, bs_hi, bs_lo)
    o_p_hi, o_p_lo, o_s_hi, o_s_lo, o_src = out
    return o_p_hi, o_p_lo, o_s_hi, o_s_lo, o_src[:, 0]


def scatter_pair_src(p, s, src, idx, bp, bs, base, interpret: bool = False):
    """Gather-compare-scatter one LWW pair against resident state planes.

    `p`/`s` [Sp] int64 (primary/secondary: registers (t, node), element
    adds (add_t, add_node), counter pairs (uuid, val)); `src` [Sp] int32
    win-source plane; `idx` [Np] int32 slot rows, UNIQUE over the real
    prefix and PRE-PADDED to a pow2 length (padding targets an in-range
    state row, ideally a plane padding row); `bp`/`bs` [Np] int64 batch
    columns, padded with NEUTRAL (losing) values; `base` int32 pool id of
    the batch's first row — row j's pool id is derived as base + j, so
    ids never upload.  -> (p, s, src) merged in place — bit-identical to
    ops/bulk.py bulk_lww_src (differential-tested).

    Compatibility wrapper: splits the int64 planes, runs
    `scatter_pair_src_split`, joins back.  The split/join are O(plane)
    XLA passes PER CALL — steady-state callers (engine/tpu.py) keep the
    planes pre-split across rounds instead and call the split kernel
    directly, which is the whole point of the layout change."""
    p_hi, p_lo = split_plane(p)
    s_hi, s_lo = split_plane(s)
    o_p_hi, o_p_lo, o_s_hi, o_s_lo, o_src = scatter_pair_src_split(
        p_hi, p_lo, s_hi, s_lo, src, idx, bp, bs, base,
        interpret=interpret)
    return join_plane(o_p_hi, o_p_lo), join_plane(o_s_hi, o_s_lo), o_src


# ------------------------------------------------------ tensor registers
# Strategy reduction over contributor stacks (crdt/tensor.py): one grid
# step owns one (key, K-block) tile, loads the [n, BLOCK] contributor
# slab, and folds it with the EXACT sequential operation chain of
# crdt.tensor.reduce_rows (the canonical-order law: float reductions are
# order-fixed so replicas cannot diverge through summation order; the
# XLA twin in ops/dense.py unrolls the same chain).  f32 only — TPU VMEM
# lanes are 32-bit; the engine routes f64 tensors onto the XLA twin.

TENSOR_BLOCK = 512


def _tensor_reduce_kernel(div, mat, out, *, strat: int, n: int):
    # avg never reaches the kernel: its multiply-add chain would FMA-
    # contract (no intermediate rounding — diverging from the host's
    # rounded products), so it composes as scale → STRAT_SUM → divide
    # across dispatch boundaries (ops/dense.py tensor_scale docstring).
    # `div` is the trimmed divisor as a RUNTIME operand (an SMEM scalar)
    # — a constant divisor gets strength-reduced to a reciprocal
    # multiply, which rounds differently from the host's true division.
    from ..crdt.tensor import STRAT_MAXMAG, STRAT_SUM, STRAT_TRIMMED
    if strat == STRAT_SUM:
        acc = mat[0, 0:1, :]
        for i in range(1, n):
            acc = acc + mat[0, i:i + 1, :]
    elif strat == STRAT_MAXMAG:
        acc = mat[0, 0:1, :]
        for i in range(1, n):
            row = mat[0, i:i + 1, :]
            acc = jnp.where(jnp.abs(row) > jnp.abs(acc), row, acc)
    elif strat == STRAT_TRIMMED and n <= 2:
        acc = mat[0, 0:1, :]
        for i in range(1, n):
            acc = acc + mat[0, i:i + 1, :]
        acc = acc / div[0]
    elif strat == STRAT_TRIMMED:
        s = mat[0, 0:1, :]
        mn = s
        mx = s
        for i in range(1, n):
            row = mat[0, i:i + 1, :]
            s = s + row
            mn = jnp.minimum(mn, row)
            mx = jnp.maximum(mx, row)
        acc = (s - mn - mx) / div[0]
    else:
        raise ValueError(f"tensor_reduce kernel: strategy {strat}")
    out[0] = acc


@partial(jax.jit, static_argnames=("strat", "n", "interpret"))
def tensor_reduce(mat, cnts, div, *, strat: int, n: int,
                  interpret: bool = False):
    """[G, n, Kp] f32 contributor stacks (canonical (node, uuid) row
    order, Kp a TENSOR_BLOCK multiple) -> [G, Kp] strategy reduction;
    `cnts` [G, n] f32 (counts only weight avg, which composes outside —
    accepted for signature parity with the XLA twin, never shipped to
    the kernel); `div` the trimmed divisor as a runtime f32 scalar.
    Bit-identical to ops/dense.py tensor_reduce and
    crdt.tensor.reduce_rows."""
    del cnts
    G, n_, Kp = mat.shape
    assert n_ == n and Kp % TENSOR_BLOCK == 0
    assert mat.dtype == jnp.float32, "pallas tensor_reduce is f32-only"
    # the [G, Kp] result travels as [G, 1, Kp]: a (1, BLOCK) block over
    # a [G, Kp] array breaks the (8, 128) tile rule unless G == 1
    out = pl.pallas_call(
        partial(_tensor_reduce_kernel, strat=strat, n=n),
        grid=(G, Kp // TENSOR_BLOCK),
        in_specs=[pl.BlockSpec((1,), lambda g, k: (_Z,),
                               memory_space=pltpu.SMEM),
                  pl.BlockSpec((1, n, TENSOR_BLOCK),
                               lambda g, k: (g, _Z, k))],
        out_specs=pl.BlockSpec((1, 1, TENSOR_BLOCK),
                               lambda g, k: (g, _Z, k)),
        out_shape=jax.ShapeDtypeStruct((G, 1, Kp), jnp.float32),
        interpret=interpret,
    )(jnp.reshape(div, (1,)), mat)
    return out[:, 0, :]


# per-key counter-sum scratch cap: two (1, n_seg) 32-bit planes.  Never
# sized against a real VMEM (a (1, N) plane pads to 8 sublanes there);
# the kernel runs interpreted only, and the engine routes larger
# keyspaces onto the XLA twin (ops/dense.py)
SEGMENT_SUM_MAX_SEG = 1 << 20


def _segment_sum_kernel(ids_ref, v_hi, v_lo, o_hi, o_lo, acc_hi, acc_lo):
    i = pl.program_id(0)
    n = pl.num_programs(0)

    @pl.when(i == 0)
    def _init():
        acc_hi[...] = jnp.zeros_like(acc_hi)
        acc_lo[...] = jnp.zeros_like(acc_lo)

    sl = (slice(None), pl.ds(ids_ref[i], 1))
    cur_lo = acc_lo[sl]
    new_lo = cur_lo + v_lo[0, 0]          # uint32: wraps mod 2^32
    carry = (new_lo < cur_lo).astype(jnp.int32)
    acc_lo[sl] = new_lo
    acc_hi[sl] = acc_hi[sl] + v_hi[0, 0] + carry

    @pl.when(i == n - 1)
    def _emit():
        o_hi[...] = acc_hi[...]
        o_lo[...] = acc_lo[...]


@partial(jax.jit, static_argnames=("n_seg", "interpret"))
def segment_sum(ids, vals, n_seg: int, interpret: bool = False):
    """Per-segment int64 sums over unsorted segment ids (the counter-sum
    re-derivation: ids = slot kid, vals = val - base).  Accumulates in a
    VMEM scratch carried across the sequential grid — exact mod 2^64 via
    an explicit unsigned carry — and emits on the last step.  Bit-
    identical to ops/dense.py segment_sum / numpy add.at."""
    n = ids.shape[0]
    if n == 0:
        return jnp.zeros(n_seg, dtype=jnp.int64)
    if n_seg > SEGMENT_SUM_MAX_SEG:
        raise ValueError(f"segment_sum scratch cap: {n_seg} segments "
                         f"> {SEGMENT_SUM_MAX_SEG}")
    np_ = _pow2(n)
    if np_ != n:
        ids = jnp.concatenate([ids, jnp.zeros(np_ - n, dtype=jnp.int32)])
        vals = jnp.concatenate([vals, jnp.zeros(np_ - n, dtype=jnp.int64)])
    sg = _pow2(n_seg)
    v_hi, v_lo = (x.reshape(np_, 1) for x in _split64(vals))
    batch_spec = pl.BlockSpec((1, 1), lambda i, ids_ref: (i, 0))
    out_spec = pl.BlockSpec((1, sg), lambda i, ids_ref: (0, 0))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(np_,),
        in_specs=[batch_spec, batch_spec],
        out_specs=[out_spec, out_spec],
        scratch_shapes=[pltpu.VMEM((1, sg), jnp.int32),
                        pltpu.VMEM((1, sg), jnp.uint32)],
    )
    o_hi, o_lo = pl.pallas_call(
        _segment_sum_kernel,
        grid_spec=grid_spec,
        out_shape=[jax.ShapeDtypeStruct((1, sg), jnp.int32),
                   jax.ShapeDtypeStruct((1, sg), jnp.uint32)],
        interpret=interpret,
    )(ids, v_hi, v_lo)
    return _join64(o_hi[0], o_lo[0])[:n_seg]
