"""Multi-device sharded CRDT merge: SPMD over a 2D device mesh.

The reference's distributed axes (SURVEY.md §2.7) map onto the mesh as:
  * "rep" — replica parallelism: the R axis of the dense [R, S] merge
    tensors (one row per replica snapshot + the local state) is split
    across devices; per-device partial LWW reductions combine with
    `lax.pmax`/`lax.pmin` collectives — the analogue of data-parallel
    gradient reduction, riding ICI.
  * "kv"  — keyspace parallelism: the slot axis S is range-partitioned
    across devices; slots are independent, so this axis needs no
    collectives (the analogue of sequence/context sharding).

Everything compiles under `jit(shard_map(...))` with static shapes; XLA
inserts the collectives.  Works identically on a virtual CPU mesh
(xla_force_host_platform_device_count) and a real TPU slice.
"""

from __future__ import annotations

from functools import partial
from typing import Optional

import jax

jax.config.update("jax_enable_x64", True)

import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402
from jax import lax, shard_map  # noqa: E402
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P  # noqa: E402

from ..ops.segment import NEUTRAL_T  # noqa: E402


def make_mesh(n_devices: Optional[int] = None, rep: int = 1) -> Mesh:
    """A (rep × kv) mesh over the first `n_devices` devices."""
    devs = jax.devices()
    n = n_devices or len(devs)
    if n % rep:
        raise ValueError(f"{n} devices do not factor into rep={rep}")
    grid = np.asarray(devs[:n]).reshape(rep, n // rep)
    return Mesh(grid, ("rep", "kv"))


def engine_mesh(n_devices: Optional[int] = None) -> Mesh:
    """A 1D ("kv",) mesh for `TpuMergeEngine(mesh=...)`: the production
    merge path range-partitions per-slot state over this axis (batches
    arrive sequentially from the replica links, so the engine's only
    intra-node parallel axis is the keyspace)."""
    devs = jax.devices()
    n = n_devices or len(devs)
    return Mesh(np.asarray(devs[:n]), ("kv",))


def _local_merge(vals, ts, at, an, dt, env):
    """Per-device partial reduction over the local R-shard, then global
    combination over the "rep" mesh axis."""
    # ---- counters: (value, uuid) LWW, max value on uuid tie ----
    t_lmax = ts.max(axis=0)
    T = lax.pmax(t_lmax, "rep")
    v_l = jnp.where(ts == T[None, :], vals, NEUTRAL_T).max(axis=0)
    V = lax.pmax(v_l, "rep")

    # ---- elements: lexicographic (add_t, add_node) + max del_t ----
    at_lmax = at.max(axis=0)
    AT = lax.pmax(at_lmax, "rep")
    an_l = jnp.where(at == AT[None, :], an, NEUTRAL_T).max(axis=0)
    AN = lax.pmax(an_l, "rep")
    DT = lax.pmax(dt.max(axis=0), "rep")
    # winning (replica-global) row index; smallest wins so that row 0 — the
    # local store state, living on rep-shard 0 — is preferred on exact ties
    r_local = at.shape[0]
    winner = (at == AT[None, :]) & (an == AN[None, :])
    local_win = jnp.argmax(winner, axis=0)
    local_has = winner.any(axis=0)
    offset = lax.axis_index("rep") * r_local
    cand = jnp.where(local_has, offset + local_win, jnp.iinfo(jnp.int64).max)
    WIN = lax.pmin(cand, "rep")

    # ---- envelopes: pointwise max over [R, S, 4] ----
    ENV = lax.pmax(env.max(axis=0), "rep")

    # a demo global statistic: slots touched by any replica (psum over both
    # mesh axes would double count "kv" — slots are partitioned, so psum
    # over "kv" after the "rep" reduction gives the true global count)
    touched = jnp.sum(T > NEUTRAL_T)
    total_touched = lax.psum(lax.pmax(touched, "rep"), "kv")

    return V, T, AT, AN, DT, WIN, ENV, total_touched


def sharded_merge_step(mesh: Mesh):
    """Build the jitted SPMD merge step for a mesh.

    Inputs (global shapes): vals/ts [R, S] counters, at/an/dt [R, S]
    elements, env [R, S, 4] envelopes.  R splits over "rep", S over "kv".
    Returns per-slot merged columns (sharded over "kv") plus a replicated
    scalar stat.
    """
    fn = shard_map(
        _local_merge,
        mesh=mesh,
        in_specs=(P("rep", "kv"), P("rep", "kv"), P("rep", "kv"),
                  P("rep", "kv"), P("rep", "kv"), P("rep", "kv", None)),
        out_specs=(P("kv"), P("kv"), P("kv"), P("kv"), P("kv"), P("kv"),
                   P("kv", None), P()),
    )
    # the [R, S] batch stacks are one-shot uploads staged solely for this
    # reduction — donating them lets XLA reuse their HBM for the outputs
    # instead of holding both footprints live across the step
    return jax.jit(fn, donate_argnums=(0, 1, 2, 3, 4, 5))


def shard_batch_arrays(mesh: Mesh, *arrays):
    """Place [R, S] (or [R, S, C]) host arrays onto the mesh with the
    step's input sharding."""
    out = []
    for a in arrays:
        spec = P("rep", "kv") if a.ndim == 2 else P("rep", "kv", None)
        out.append(jax.device_put(a, NamedSharding(mesh, spec)))
    return tuple(out)
