"""Dense R-way CRDT merge kernels — the TPU fast path.

XLA scatter on TPU serializes colliding updates (measured ~11M updates/s on
v5e), so the batched engine avoids it for bulk merges: the host pad-aligns
every batch's rows into the store's dense row space (numpy fancy writes at
C speed), producing [R, S] tensors whose row 0 is the current store state.
The merge is then a dense reduction over the R axis — pure VPU elementwise
work at HBM bandwidth, the same shape trick used to batch ragged data for
the MXU.

Absent slots carry NEUTRAL_T and lose every comparison.  Row 0 is the local
state, so `win_batch == 0` means "no value copy needed" — and argmax's
first-match tie rule makes that automatic when the local write is the winner.
"""

from __future__ import annotations

from functools import partial

import jax

jax.config.update("jax_enable_x64", True)

import jax.numpy as jnp  # noqa: E402

from .segment import NEUTRAL_T  # noqa: E402


@partial(jax.jit, donate_argnums=(0, 1))
def dense_merge_counters(vals, ts):
    """[R, S] per-slot (value, uuid) LWW with max-value tie.
    -> (val[S], t[S])."""
    t_max = ts.max(axis=0)
    val = jnp.where(ts == t_max[None, :], vals, NEUTRAL_T).max(axis=0)
    return val, t_max


@partial(jax.jit, donate_argnums=(0, 1, 2))
def dense_merge_elems(at, an, dt):
    """[R, S] element merge: lexicographic (add_t, add_node) winner + max
    del_t.  -> (at[S], an[S], dt[S], win_batch[S]); win_batch==0 keeps the
    local value."""
    at_max = at.max(axis=0)
    an_cand = jnp.where(at == at_max[None, :], an, NEUTRAL_T)
    an_max = an_cand.max(axis=0)
    winner = (at == at_max[None, :]) & (an == an_max[None, :])
    win_batch = jnp.argmax(winner, axis=0)  # first winner; row 0 = local
    return at_max, an_max, dt.max(axis=0), win_batch


@partial(jax.jit, donate_argnums=(0, 1))
def dense_merge_lww(t, n):
    """[R, S] plain LWW slots (registers): lexicographic (t, node) winner.
    -> (t[S], n[S], win_batch[S])."""
    t_max = t.max(axis=0)
    n_cand = jnp.where(t == t_max[None, :], n, NEUTRAL_T)
    n_max = n_cand.max(axis=0)
    winner = (t == t_max[None, :]) & (n == n_max[None, :])
    return t_max, n_max, jnp.argmax(winner, axis=0)


@partial(jax.jit, donate_argnums=(0,))
def dense_max(cols):
    """[R, S, C] pointwise max over R — envelopes."""
    return cols.max(axis=0)


@partial(jax.jit, static_argnames=("n_seg",))
def segment_sum(ids, vals, n_seg: int):
    """Per-segment int64 sums over unsorted segment ids (counter-sum
    re-derivation from resident slot contributions)."""
    return jnp.zeros(n_seg, dtype=jnp.int64).at[ids].add(vals)


# ----------------------------------------------------- tensor registers
# Device twins for the tensor-register family (crdt/tensor.py).  The
# reductions UNROLL the canonical sequential operation chain of
# crdt.tensor.reduce_rows — same IEEE ops in the same order, so host,
# XLA and Pallas reads are bit-identical (the canonical-order law).


@partial(jax.jit, donate_argnums=(0,))
def pool_scatter(buf, idx, vals):
    """Resident tensor payload pool update: buf [C, Kp] ← vals [W, Kp]
    at unique rows idx [W] int32 (donated — the pool never copies)."""
    return buf.at[idx].set(vals, mode="drop", unique_indices=True)


@jax.jit
def tensor_scale(mat, cnts):
    """avg stage 1: weight the [G, n, Kp] contributor slab by the [G, n]
    counts — a SEPARATE dispatch on purpose.  XLA contracts an adjacent
    multiply-add chain into FMAs (no intermediate rounding), which would
    silently diverge from the host reference's rounded products; a
    dispatch boundary forces the products to materialize as f32/f64
    exactly like numpy does.  The canonical avg chain is therefore
    scale → sequential sum (tensor_reduce STRAT_SUM) → divide
    (tensor_div), on every backend including the host
    (crdt.tensor.reduce_rows runs the same rounded-product chain)."""
    return mat * cnts[:, :, None]


@jax.jit
def tensor_div(acc, tot):
    """avg stage 3: [G, Kp] / [G, 1] count totals (totals accumulate on
    host with the same sequential dtype chain)."""
    return acc / tot


@partial(jax.jit, static_argnames=("strat", "n", "g"))
def tensor_take_reduce(buf, idx, div, *, strat: int, n: int, g: int):
    """Fused pool-gather + strategy reduction: one dispatch, no
    materialized [G, n, Kp] intermediate (XLA fuses the take into the
    fold loop — on the CPU backend this halves the read's memory
    traffic, which is exactly what the device-vs-host bench measures).
    Same sequential chain as tensor_reduce, so still bit-identical to
    the host reference; `sum`/`maxmag`/`trimmed-mean` only — avg's
    products must round at a dispatch boundary (tensor_take_scale)."""
    mat = buf[idx].reshape(g, n, buf.shape[1])
    return _reduce_chain(mat, strat, n, div)


@partial(jax.jit, static_argnames=("n", "g"))
def tensor_take_scale(buf, idx, cnts, *, n: int, g: int):
    """avg stage 1, fused with the pool gather (products still round at
    this dispatch's boundary — the FMA fence tensor_scale documents)."""
    return buf[idx].reshape(g, n, buf.shape[1]) * cnts[:, :, None]


@partial(jax.jit, static_argnames=("n",))
def tensor_sum_div(wmat, tot, *, n: int):
    """avg stages 2+3 fused: sequential sum of the rounded products,
    then the count-total divide (adds and a divide cannot contract)."""
    acc = wmat[:, 0]
    for i in range(1, n):
        acc = acc + wmat[:, i]
    return acc / tot


def _reduce_chain(mat, strat: int, n: int, div):
    """The canonical sequential fold over a [G, n, Kp] stack — the one
    chain crdt.tensor.reduce_rows defines, branch for branch.  `div` is
    the trimmed-mean divisor (n or n-2) as a RUNTIME scalar of the
    payload dtype: a compile-time-constant divisor gets rewritten by
    XLA into a reciprocal multiply, which rounds differently from the
    host's true division (caught by the bench oracle at n=8 — n-2=6 is
    the first non-pow2 divisor)."""
    from ..crdt.tensor import STRAT_MAXMAG, STRAT_SUM, STRAT_TRIMMED
    if strat == STRAT_SUM:
        acc = mat[:, 0]
        for i in range(1, n):
            acc = acc + mat[:, i]
        return acc
    if strat == STRAT_MAXMAG:
        acc = mat[:, 0]
        for i in range(1, n):
            acc = jnp.where(jnp.abs(mat[:, i]) > jnp.abs(acc),
                            mat[:, i], acc)
        return acc
    if strat == STRAT_TRIMMED:
        if n <= 2:
            acc = mat[:, 0]
            for i in range(1, n):
                acc = acc + mat[:, i]
            return acc / div
        s = mat[:, 0]
        mn = mat[:, 0]
        mx = mat[:, 0]
        for i in range(1, n):
            s = s + mat[:, i]
            mn = jnp.minimum(mn, mat[:, i])
            mx = jnp.maximum(mx, mat[:, i])
        return (s - mn - mx) / div
    raise ValueError(f"tensor_reduce: strategy {strat} reduces on host")


@partial(jax.jit, static_argnames=("strat", "n"))
def tensor_reduce(mat, cnts, div, *, strat: int, n: int):
    """[G, n, Kp] contributor stacks (canonical (node, uuid) row order)
    -> [G, Kp] strategy reduction; `cnts` [G, n] in the payload dtype.
    Bit-identical to crdt.tensor.reduce_rows — the sequential chains
    mirror it branch for branch.  `avg` and `lww` never reach this
    kernel: avg composes scale/sum/div (see tensor_scale — FMA
    contraction), lww picks its winner from host stamps."""
    del cnts  # counts only weight avg, which composes outside
    return _reduce_chain(mat, strat, n, div)
