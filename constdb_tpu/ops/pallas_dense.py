"""Fused dense CRDT merge kernels in Pallas (TPU).

  * FOLD kernels (`merge_elems`, `merge_counters`): one VMEM pass computes
    what the XLA path (ops/dense.py) expresses as several reductions + an
    argmax — the lexicographic (add_t, add_node) winner, the merged del
    side, and the winning replica row, over [R, S] dense merge tensors
    blocked along S.
  * `tensor_reduce`: the strategy reduction over tensor-register
    contributor stacks, one (key, K-block) tile per grid step.

All three compile through Mosaic for the v5e; engine/tpu.py reaches them
only on an aligned multi-batch fold or a tensor read (`_pallas_or_xla`).
The resident micro scatter and the counter segment-sum are XLA programs
(ops/bulk.py `bulk_lww_src`, ops/dense.py `segment_sum`).

TPU VMEM lanes are 32-bit, so int64 columns travel as two int32 planes;
a signed 64-bit comparison is exactly the lexicographic (hi, lo)
comparison of the order-preserving halves (`_split64_ord`).  All merge
values here (uuids, NEUTRAL_T, node ids) are ordinary int64s, so the
split/join is lossless.

`merge_elems(..., interpret=True)` (and every kernel here) runs through
the Pallas interpreter on CPU — that is how tests/test_pallas_dense.py
differential-tests them against ops/dense.py without TPU hardware.
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


def _split64_ord(x):
    """int64 -> (hi int32, lo int32 with its sign bit flipped): SIGNED
    lex order on the pair == int64 order.  The fold kernels reduce along
    sublanes, and Mosaic has no unsigned reduction ("Reductions over
    unsigned integers not implemented"), so their lo halves travel
    order-preserved as int32."""
    lo = (x & jnp.int64(0xFFFFFFFF)).astype(jnp.uint32)
    return ((x >> 32).astype(jnp.int32),
            jax.lax.bitcast_convert_type(lo ^ jnp.uint32(1 << 31), jnp.int32))


def _join64_ord(hi, lo):
    lo = jax.lax.bitcast_convert_type(lo, jnp.uint32) ^ jnp.uint32(1 << 31)
    return (hi.astype(jnp.int64) << 32) | lo.astype(jnp.int64)


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
