"""Batched JAX MergeEngine: the TPU path for bulk CRDT merges.

Device strategies, picked per CRDT family:

  * bulk (the fast path, ops/bulk.py): each batch ships as COMPACT rows
    (int32 slot ids + value columns) and folds into full per-slot device
    state, one gather→merge→scatter kernel call per batch.  State is
    donated between calls (never re-uploaded), uploads are async (batch
    b+1 transfers while b merges), and when every touched slot is brand
    new — snapshot ingest into an empty region — the initial state is
    materialized ON device and only the merged block downloads.
  * scatter (ops/segment.py): touched-slot gather + scatter-max kernels.
    Chosen for sparse merges when state is host-resident.

Per-slot device state is held as ops/bulk.py `Plane`s — (hi int32, lo
uint32) pairs, never int64 arrays (docs/INVARIANTS.md, PLANE-PAIR); the
batch columns this module stages, uploads and downloads stay int64.

**Resident mode** (`TpuMergeEngine(resident=True)`): the per-family device
state persists ACROSS merge calls, so streaming replica catch-up — the
replica link applies a snapshot chunk-by-chunk, and each chunk is one
`merge()` — pays row uploads only, never a state round-trip per chunk.
Merged state flushes back to the host keyspace lazily (`flush()`), which
the Node triggers before any command touches the numeric plane
(`Node.ensure_flushed`); op-path writes bump the touched plane's
`KeySpace.fam_ver` entry and journal the rows they wrote
(`KeySpace.journal`), so the engine repairs ONLY that plane's mirror, by
scattering those rows (`_patch_mirror`; a whole rebuild only after GC,
compaction, a reset or a journal over its limit — mixed op/merge traffic
keeps every mirror resident).  Win VALUES
(dict fields / register bytes) resolve through a device src plane at
flush — no per-call win-flag download; value bytes live only on the host.

**Steady state** (round 12): op-stream micro-batches — the
serve/replication coalescers' flushes, previously always routed to the
host micro strategy — merge IN PLACE against the resident planes too
(`_merge_micro_resident`): duplicate slots fold on host with the shared
hostbatch reductions, unique winners scatter once per family
(ops/bulk.py `bulk_lww_src`), the env plane stays host-authoritative,
and `flush()` downloads only the rows touched since the last flush
(dirty-row accounting; counter sums update incrementally or re-derive
via ops/dense.py `segment_sum` on the device).  This inverts
HOST_SCATTER_MAX into a FALLBACK threshold — per family for cold planes
(`_micro_placement`), whole-round only when the steady path is off
(CONSTDB_RESIDENT=0, non-resident engines, mesh-partitioned state).

Bulk batches whose rows are NOT unique per slot (raw op streams) above the
micro ceiling take the scatter path — its reductions tolerate intra-batch
collisions; the bulk kernels require `rows_unique_per_slot` (one scatter
per slot per call).

Must be semantically bit-identical to engine/cpu.py — differential-tested in
tests/test_engine_equivalence.py and tests/test_resident_engine.py.
"""

from __future__ import annotations

import logging
from typing import Optional

import numpy as np

from ..crdt import semantics as S
from ..ops import bulk as B
from ..ops import segment as K
from ..store.keyspace import (FAMILIES, JOURNAL_FAMILIES, JOURNAL_MAX_ROWS,
                              TOUCH_CAUSES, KeySpace)
from ..utils.stagetime import StageClock, seconds_into
from .base import ColumnarBatch, MergeStats, has_values
from .hostbatch import HOST_MICRO_MAX

log = logging.getLogger(__name__)

_I64 = np.int64
_I32 = np.int32


def _pad(arr: np.ndarray, size: int, fill) -> np.ndarray:
    arr = np.asarray(arr)
    if len(arr) == size:
        return arr
    # empty + two slice writes touches each element once (np.full would
    # write the fill over the whole buffer first)
    out = np.empty((size,) + arr.shape[1:], dtype=arr.dtype)
    out[: len(arr)] = arr
    out[len(arr):] = fill
    return out


def _copy_async(arr) -> None:
    """Start a device array's copy to the host, where the backend has
    one to start (the later np.asarray then waits for it alone)."""
    try:
        arr.copy_to_host_async()
    except AttributeError:
        pass


def _pad_idx(rows: np.ndarray, sp: int, size: int) -> np.ndarray:
    """int32 scatter idx of `size` rows: `rows`, then distinct slots past
    the plane (`sp`), which every scatter drops."""
    n = len(rows)
    idx = np.empty(size, dtype=_I32)
    idx[:n] = rows
    idx[n:] = sp + np.arange(size - n, dtype=_I32)
    return idx


# family -> [(column name in the family's host table, neutral fill)]
_FAMILIES = {
    "env": [("ct", 0), ("mt", 0), ("dt", 0), ("expire", 0)],
    "reg": [("rv_t", 0), ("rv_node", 0)],
    "cnt": [("val", 0), ("uuid", K.NEUTRAL_T), ("base", 0),
            ("base_t", K.NEUTRAL_T)],
    "el": [("add_t", 0), ("add_node", 0), ("del_t", 0)],
}


def _host_table(store: KeySpace, fam: str):
    return store.el if fam == "el" else (store.cnt if fam == "cnt"
                                         else store.keys)


# ------------------------------------------------------- host group combine
# Host→device traffic costs per BYTE and per TRANSFER, so a group of staged
# batches is pre-combined ON HOST whenever that shrinks either:
#   * aligned rows (R replica snapshots of one keyspace) fold R× down with
#     vectorized numpy lex-max — upload drops R×;
#   * disjoint rows (consecutive chunks of ONE snapshot) concatenate into a
#     single batch — same bytes, one transfer + one kernel instead of R.
# Both reductions compute exactly crdt/semantics.py (lexicographic (t, v)
# max / plain max), so device results are bit-identical either way.


def _rows_aligned(staged) -> bool:
    if len(staged) < 2:
        return False
    r0 = staged[0][0]
    return all(len(s[0]) == len(r0) and np.array_equal(s[0], r0)
               for s in staged[1:])


def _rows_disjoint_cat(staged):
    """Concatenated row array if no row repeats across entries, else None.

    Cheap interval test first: slot rows are created in contiguous blocks
    during catch-up, so non-overlapping [min, max] ranges prove cross-part
    disjointness without the O(n log n) sort."""
    parts = [np.asarray(s[0]) for s in staged]
    nonempty = [p for p in parts if len(p)]
    if len(nonempty) < 2:
        return np.concatenate(parts) if parts else np.zeros(0, _I64)
    iv = sorted((int(p.min()), int(p.max())) for p in nonempty)
    if all(iv[i][1] < iv[i + 1][0] for i in range(len(iv) - 1)):
        return np.concatenate(parts)
    cat = np.concatenate(parts)
    if len(np.unique(cat)) == len(cat):
        return cat
    return None


def _lex_fold(t_list, v_list):
    """RUNNING lexicographic (t, v) max over R same-shape arrays ->
    (t[N], v[N], win_batch[N]).  Mirrors ops/bulk.py _pair_win /
    crdt/semantics.py lww_wins; ties keep the EARLIEST batch (the
    stacked-argmax formulation's winner).  Running beats stacking: no
    [R, N] materialization, ~3 memory-bound passes per batch."""
    t = np.array(t_list[0], copy=True)
    v = np.array(v_list[0], copy=True)
    wb = np.zeros(len(t), dtype=_I64)
    for i in range(1, len(t_list)):
        ti = np.asarray(t_list[i])
        vi = np.asarray(v_list[i])
        win = (ti > t) | ((ti == t) & (vi > v))
        np.copyto(t, ti, where=win)
        np.copyto(v, vi, where=win)
        wb[win] = i
    return t, v, wb


def _sel_obj(lists, wb: np.ndarray) -> np.ndarray:
    """Pick lists[wb[j]][j] for every j, vectorized via an object matrix.
    A None entry in `lists` stands for an all-None value column (valueless
    batches skip materializing [None] * n lists entirely)."""
    obj = np.empty((len(lists), len(wb)), dtype=object)
    for i, v in enumerate(lists):
        obj[i, :] = v  # numpy broadcasts a bare None across the row
    return obj[wb, np.arange(len(wb))]


def _fam_rows(store: KeySpace, fam: str) -> int:
    return _host_table(store, fam).n


class TpuMergeEngine:
    name = "tpu"
    # bulk when staged rows cover >= 1/BULK_FRACTION of the slot region
    # (resident mode always prefers bulk: there is no state upload to avoid)
    BULK_FRACTION = 8
    # contiguous-row batches at or above this length derive their idx
    # vector on device (iota) instead of uploading it; below it the jit
    # dispatch overhead outweighs the saved bytes (tests lower it to 1)
    IDX_IOTA_MIN = 4096
    # op-stream micro-batches (rows_unique_per_slot=False) at or below
    # this many total rows merge on HOST (engine/hostbatch.py): at that
    # scale device dispatch fixed costs dwarf the merge, and the
    # steady-state coalescer flushes such batches every few ms
    # single source of truth in engine/hostbatch.py: the CPU engine's
    # micro routing and this ceiling must move together, or the two
    # engines route the same batch onto different strategies
    HOST_SCATTER_MAX = HOST_MICRO_MAX
    # win-source pool ids live in an int32 device plane; merge_many flushes
    # before staging a round that could cross this (tests lower it)
    POOL_ID_CEILING = 1 << 31
    # pow2 pad FLOORS for the steady micro path: batch/dirty vectors pad
    # up to these before the pow2 round, so the jitted scatter/gather
    # kernels re-trace per PLANE CAP only, not per batch-size bucket —
    # per-shape tracing dominated small-stream walls, while scattering/
    # gathering a few hundred padded rows costs microseconds on any
    # backend.
    MICRO_SCATTER_PAD = 256
    FLUSH_GATHER_PAD = 512
    # a stale mirror's journaled rows (store/keyspace.py RowJournal) pad
    # up to one of these before they scatter, so a family has at most
    # three patch programs per plane cap, compiled at the flush before the
    # mirror can go stale (_warm_patch); a journal over the largest falls
    # back to the whole-plane rebuild
    MIRROR_PATCH_BUCKETS = (1 << 10, 1 << 13, JOURNAL_MAX_ROWS)
    # the least capacity of a family a micro round has grown (a table that
    # grows under load): every new capacity loads its own programs (the
    # grow, the scatter, the patch, the split: seven or eight), and on a
    # TPU v5e those loads hold the loop 0.2-0.3 s at each capacity, so a
    # table that starts near empty jumps to this at its first grow — 3 MB
    # of `el` stamp planes — instead of doubling through each power of two
    # below it while it serves.  Past it, each doubling still pays them
    GROW_FLOOR = 1 << 17
    # staging order = dispatch order = the on-store plane contract
    FAM_ORDER = ("env", "reg", "cnt", "el")

    def __init__(self, resident: bool = False, mesh=None,
                 dense_fold: str = "auto",
                 pipeline: Optional[bool] = None,
                 steady: Optional[bool] = None,
                 warmup: Optional[int] = None) -> None:
        """`mesh`: an optional jax.sharding.Mesh with a "kv" axis.  When
        given, per-slot device state range-partitions over that axis
        (NamedSharding P("kv")) while batch rows replicate — GSPMD then
        partitions the very same bulk kernels across the slice, with each
        device scattering the rows that land in its slot range.  Sharding
        is placement policy only: kernels, semantics, and host plumbing
        are identical to the single-chip path (SURVEY.md §7 item 6).

        `dense_fold`: strategy for ALIGNED multi-batch merges (several
        batches staging the exact same slot rows — R replica snapshots of
        one keyspace, the bulk catch-up shape).  Aligned batches reduce
        on-device in one fused [R, N] pass, then scatter ONCE instead of
        R times.  "auto" = the Mosaic-compiled Pallas kernels
        (ops/pallas_dense.py) on a TPU backend without a mesh; their XLA
        twins (ops/dense.py) elsewhere.  "pallas" / "pallas-interpret" /
        "xla" force one backend for the fold and tensor-reduce kernels
        (the interpreter is for CPU tests; no server selects it); "off"
        disables folding.  The backends are differential-tested
        bit-identical.

        `steady`: device-resident STEADY-STATE path — op-stream
        micro-batches (the serve/replication coalescers' flushes) merge
        IN PLACE against the resident device planes instead of falling
        back to the host micro strategy; flushes then download only the
        rows those merges touched (dirty-row accounting).  This is the
        routing inversion that makes HOST_SCATTER_MAX a FALLBACK
        threshold: the host micro path runs only when the engine is not
        resident, a mesh partitions the state, or — per family — a
        touched plane is COLD (no warm mirror and the plane's host
        version has not been stable for `warmup` consecutive micro
        rounds — op-path writes between rounds would otherwise force a
        full mirror re-upload per round).  None = CONSTDB_RESIDENT:
        "auto" (default) engages only over a real non-CPU backend, "1"
        forces on, "0" off; `warmup` defaults to
        CONSTDB_RESIDENT_WARMUP (2).

        `pipeline`: double-buffered merge dispatch.  Each CRDT family's
        work splits into STAGE (pure host prep: columnarization, slot
        resolution, group combine — touches ONLY that family's host
        plane) and DISPATCH (device uploads/kernels + pool bookkeeping,
        main thread, family order).  With the pipeline on, a background
        pool stages the families concurrently while the main thread
        dispatches each plan as it lands and the device crunches earlier
        kernels — host staging overlaps device compute instead of
        serializing behind it.  Results are byte-identical to the serial
        path: the safety invariant is PER-PLANE INDEPENDENCE, not
        ordering — every plane's appends happen inside exactly one stage,
        in batch order, and no stage reads another family's store plane
        (a stage that needs one must move that read into merge_many's
        serial prologue or its own dispatch).  None = on unless
        CONSTDB_PIPELINE=0.  The serial path stays selectable for
        debugging (pipeline=False / CONSTDB_PIPELINE=0)."""
        import jax  # ensure a backend exists before we advertise ourselves

        self._jax = jax
        self._devices = jax.devices()
        self.dense_fold = dense_fold
        self._fold_on = dense_fold != "off"
        self.folds = 0          # aligned folds performed (observability)
        # stale-mirror rebuilds per family (observability: mixed op/merge
        # traffic must keep these O(writes-to-that-plane), never O(ops))
        self.mirror_rebuilds = dict.fromkeys(FAMILIES, 0)
        # ... and what invalidated the mirror each rebuild replaced (the
        # family's last KeySpace.touch cause)
        self.mirror_rebuild_causes = dict.fromkeys(TOUCH_CAUSES, 0)
        # ... and the planes grown in place as the host table passed their
        # capacity (INFO mirror_grows_<fam>; not rebuilds: nothing stale)
        self.mirror_grows = dict.fromkeys(FAMILIES, 0)
        # the families a micro round grew: tables that grow under load,
        # whose planes keep GROW_FLOOR rows from then on
        self._growing: set = set()
        # ... and the stale mirrors repaired in place instead: patches and
        # the distinct rows they scattered, per journaled family, and the
        # rebuilds taken because a journal outgrew the largest bucket
        # (engagement = patches / (patches + rebuilds))
        self.mirror_patches = dict.fromkeys(JOURNAL_FAMILIES, 0)
        self.mirror_patch_rows = dict.fromkeys(JOURNAL_FAMILIES, 0)
        self.mirror_patch_overflows = 0
        self._patch_warm: dict[str, int] = {}   # fam -> cap warmed at
        # rows merged per family and per path (INFO merge_rows_dev_<fam> /
        # merge_rows_host_<fam>): on the device path the rows handed to
        # the scatter AFTER the host fold, on the host path the rows the
        # twin merged.  env on the micro path is always host.
        self.merge_rows_dev = dict.fromkeys(self.FAM_ORDER, 0)
        self.merge_rows_host = dict.fromkeys(self.FAM_ORDER, 0)
        # the served path's stage clock (utils/stagetime.py): every host
        # clock below is taken through it, and while a profiler trace runs
        # its stages land in the device trace's host plane.  The Node
        # adopts it.
        ann = jax.profiler.TraceAnnotation
        self.stages = StageClock(trace=(ann, ann.is_enabled))
        # cumulative host-side seconds per family on the CRITICAL PATH
        # (stage-wait + dispatch; device work is async; read by bench.py,
        # ROADMAP D1).  Inclusive totals that overlap the stage clock's
        # self times by design: "micro" covers a whole resident micro
        # round, "flush" includes the blocking downloads.
        self.family_secs = {"env": 0.0, "reg": 0.0, "cnt": 0.0, "el": 0.0,
                            "flush": 0.0, "host": 0.0, "micro": 0.0}
        from ..conf import env_flag, env_int
        if pipeline is None:
            pipeline = env_flag("CONSTDB_PIPELINE", True)
        self.pipeline = bool(pipeline)
        # steady-state residency (see __init__ docstring): micro rounds
        # merged in place on device vs routed to the host fallback, and
        # the flush download accounting the acceptance criterion reads.
        # "auto" (the default) engages only over a REAL accelerator: on
        # a CPU-only backend the "device" IS the host, so in-place
        # XLA-CPU scatters just add dispatch overhead over the numpy
        # micro strategy — the healthy-device clause of the routing
        # inversion.  Tests/bench legs force steady=True to exercise the
        # path on CPU builders.
        if steady is None:
            from ..conf import env_str
            mode = env_str("CONSTDB_RESIDENT", "auto")
            steady = jax.default_backend() != "cpu" if mode == "auto" \
                else mode != "0"
        self.steady = bool(steady)
        self.warmup = env_int("CONSTDB_RESIDENT_WARMUP", 2) \
            if warmup is None else int(warmup)
        self._warm_streak: dict[str, tuple[int, int]] = {}
        self.dev_rounds_resident = 0
        self.host_micro_rounds = 0
        # the micro round's link protocol (_scatter_pair): scatters that
        # returned their win vector, micro scatters that fell back to the
        # `src` plane (a whole-plane round's src still unflushed), and
        # the rows the flush applied from win vectors
        self.micro_win_scatters = 0
        self.micro_src_scatters = 0
        self.micro_win_rows = 0
        self.flush_rows_downloaded = 0
        # rows a whole-plane flush WOULD have downloaded at the same
        # points — the denominator that proves partial, not full,
        # downloads (bench legs report both)
        self.flush_rows_full_equiv = 0
        self._stage_ex = None          # lazy single-worker staging executor
        self._stage_pending = None     # in-flight stage futures (flush joins)
        # resident tensor payload pools (the tensor-register family,
        # crdt/tensor.py): one [cap, Kp] device pool per (dtype, elems)
        # class, holding contributor payload rows; slot STAMPS stay
        # host-authoritative (like the env plane on the micro path), so
        # only payload bytes ever cross the link.  `dirty` pool slots
        # are device-newer than the host side list; flush gathers and
        # downloads exactly those (ops/bulk.py gather_rows).
        self._tns_pools: dict[tuple, dict] = {}
        self._tns_ver = 0
        self._tns_epoch = 0            # bumped whenever pools drop
        self._tns_read_cache: dict = {}
        self._tns_bytes = 0            # device payload bytes resident
        self.tns_dev_rows = 0          # tensor rows merged on device
        self.tns_host_rows = 0         # tensor rows merged on host
        self.tns_pool_cap = env_int("CONSTDB_TENSOR_POOL_MB", 512) << 20
        # host<->device transfer accounting (INFO dev_upload_bytes /
        # dev_download_bytes; bench.py turns these into a measured
        # fraction of the host-link ceiling)
        self.bytes_h2d = 0
        self.bytes_d2h = 0
        self.resident = resident
        self._res: dict[str, dict] = {}   # fam -> {cols: {name: dev arr}, n, cap}
        # deferred win-value resolution (resident mode): host value pool the
        # device-resident `src` planes index into; resolved once at flush.
        # Entries pin their batch column arrays until then, so merge_many
        # auto-flushes once the pinned bytes pass `pool_flush_bytes` —
        # a streamed catch-up with no interleaved reads stays O(cap), not
        # O(total ingested bytes).
        self._val_pool: list[tuple[int, Optional[list], dict]] = []
        self._pool_size = 0
        self._pool_bytes = 0
        # el rows whose HOST del_t advanced since the last flush (the del
        # plane never touches the device in the src path); flush turns
        # newly-dead ones into GC queue entries after add_t reconstruction
        self._el_del_touched: list[np.ndarray] = []
        self._jit_cache: dict = {}  # keyed per-shape jitted builders
        self.pool_flush_bytes = env_int("CONSTDB_POOL_FLUSH_MB", 1536) << 20
        self.needs_flush = False
        self._mesh = mesh
        if mesh is not None:
            from jax.sharding import NamedSharding, PartitionSpec
            self._kv_n = int(mesh.shape["kv"])
            self._sh_state = (None, NamedSharding(mesh, PartitionSpec("kv")),
                              NamedSharding(mesh, PartitionSpec("kv", None)))
            self._sh_rep = NamedSharding(mesh, PartitionSpec())
        else:
            self._kv_n = 1

    def device_info(self) -> tuple:
        """(platform, device_kind, device count) as JAX reports them —
        the INFO lines beside `engine:`."""
        d = self._devices
        return d[0].platform, d[0].device_kind, len(d)

    def _host_combine(self) -> bool:
        """Host group pre-combine is on unless a device fold backend is
        explicitly forced (those test paths must still execute) or folding
        is off entirely."""
        return self.dense_fold == "auto"

    def _combine_groups(self, staged, fold_fn, cat_fn):
        """Collapse a multi-batch staged list on host (see the host-combine
        block comment above), hierarchically: entries with IDENTICAL row
        sets cluster and fold R× via `fold_fn` (a large group covering
        several key ranges from several replicas folds per range); then,
        if the folded survivors are pairwise disjoint, they concatenate
        into one transfer via `cat_fn`.  Overlapping-unaligned leftovers
        stay as-is (sequential kernels).  -> (combined, n_folds) — the
        fold COUNT is returned, not applied to self.folds: this runs on
        the staging worker, and the dispatching main thread applies it
        (no racing `+=` on shared counters)."""
        if not self._host_combine() or len(staged) < 2:
            return staged, 0
        clusters: list[list] = []
        by_sig: dict = {}
        for s in staged:
            r = s[0]
            sig = (len(r), int(r[0]) if len(r) else -1,
                   int(r[-1]) if len(r) else -1)
            placed = False
            for cl in by_sig.get(sig, ()):
                r0 = cl[0][0]
                # identity first: replica batches stage the very same row
                # array object (memoized key/element resolution), so most
                # clusters match without an O(n) compare
                if r0 is r or np.array_equal(r0, r):
                    cl.append(s)
                    placed = True
                    break
            if not placed:
                cl = [s]
                clusters.append(cl)
                by_sig.setdefault(sig, []).append(cl)
        folded = []
        n_folds = 0
        for cl in clusters:
            if len(cl) > 1:
                n_folds += 1
                folded.append(fold_fn(cl))
            else:
                folded.append(cl[0])
        if len(folded) == 1:
            return folded, n_folds
        cat = _rows_disjoint_cat(folded)
        if cat is not None:
            return [cat_fn(folded, cat)], n_folds
        return folded, n_folds

    def _pool_add(self, vals, **cols) -> np.int32:
        """Stage one batch's winner-carried payload in the host pool and
        return its base pool id (the kernels derive per-row ids as
        base + iota — ids never upload).  `vals` feeds win-value
        resolution (None = every value is None — a winning valueless row
        still CLEARS the slot's value, without materializing a list);
        `cols` are the host column arrays reconstructed at flush (e.g.
        add_t=..., add_node=...), held by reference until the next
        flush (merge_many bounds the pinned bytes via auto-flush).

        The int32 src-plane ceiling is checked BEFORE any pool state
        mutates; merge_many pre-flushes rounds that could cross it, so
        tripping this means one single round stages > 2^31 rows."""
        base = self._pool_size
        n = -1
        nbytes = 0
        if vals is not None:
            vals = list(vals)
            n = len(vals)
            # count the real pinned payload, not just pointers: the
            # auto-flush bound must trip on value-heavy ingests too
            # (filter(None) drops None at C speed; empty bytes are falsy
            # too, but len(b"") contributes 0 anyway)
            nbytes += 8 * n + sum(map(len, filter(None, vals)))
        for a in cols.values():
            n = len(a)
            nbytes += int(getattr(a, "nbytes", 8 * n))
        if base + n >= self.POOL_ID_CEILING:  # int32 src plane ceiling
            raise RuntimeError(
                "win-source pool would exceed int32 range within a single "
                "merge round; split the ingest into smaller merge_many "
                "calls so flush() can run between them")
        self._val_pool.append((base, vals, cols))
        self._pool_size = base + n
        self._pool_bytes += nbytes
        return np.int32(base)

    def _src_state(self, fam: str, sp: int):
        """Device win-source plane for `fam`, grown to sp (fill -1).
        int32 — pool ids fit, and the plane is downloaded every flush."""
        jnp = self._jax.numpy
        res = self._res.get(fam) or {}
        src = res.get("src")
        if src is not None and src.shape[0] >= sp:
            return src
        with self.stages.stage("state_alloc", fam):
            if src is None:
                return B.device_full(sp, -1, i32=True)
            return jnp.concatenate(
                [src, B.device_full(sp - src.shape[0], -1, i32=True)])

    # ----------------------------------------------------- device placement

    def _sp_size(self, size: int) -> int:
        """Padded state size: pow2, rounded up to a multiple of the kv
        axis (a non-pow2 device count otherwise fails sharding)."""
        sp = K.next_pow2(max(size, 1))
        if self._kv_n > 1 and sp % self._kv_n:
            sp = -(-sp // self._kv_n) * self._kv_n
        return sp

    def _put_state(self, host: np.ndarray):
        """A host int64 column (or [n, C] stack) as a device Plane:
        uploaded under the state sharding, split once on the device (the
        int64 upload is dropped as the split returns)."""
        self.bytes_h2d += host.nbytes
        sh = None if self._mesh is None else self._sh_state[host.ndim]
        return B.plane_split(self._jax.device_put(host, sh))

    def _put_batch(self, arr: np.ndarray):
        self.bytes_h2d += arr.nbytes
        with self.stages.stage("h2d"):
            if self._mesh is None:
                return self._jax.device_put(arr)
            return self._jax.device_put(arr, self._sh_rep)

    def _device_get(self, x):
        out = self._jax.device_get(x)
        seq = out if isinstance(out, (tuple, list)) else (out,)
        self.bytes_d2h += sum(int(a.nbytes) for a in seq)
        return out

    def _full(self, n: int, fill: int, cols: int = 0):
        """Neutral Plane materialized on device with the state sharding
        (cols=0 → [n]; cols=C → [n, C])."""
        with self.stages.stage("state_alloc"):
            if self._mesh is None:
                return B.device_full(n, fill, cols=cols)
            key = ("full", n, fill, cols)
            fn = self._jit_cache.get(key)
            if fn is None:
                shape = (n, cols) if cols else (n,)
                fn = self._jax.jit(
                    lambda: B.neutral_plane(shape, fill),
                    out_shardings=self._sh_state[2 if cols else 1])
                self._jit_cache[key] = fn
            return fn()

    def _grow(self, old, delta: int, fill: int):
        """Extend a resident Plane by `delta` neutral rows, preserving the
        state sharding."""
        if self._mesh is None:
            return B.grown_plane(old, delta, fill)
        key = ("grow", delta, fill, old.hi.ndim)
        fn = self._jit_cache.get(key)
        if fn is None:
            fn = self._jax.jit(
                lambda o: B.grown_plane(o, delta, fill),
                out_shardings=self._sh_state[old.hi.ndim])
            self._jit_cache[key] = fn
        return fn(old)

    def _plane_get(self, plane, n: int) -> np.ndarray:
        """Rows [0, n) of a device Plane as a host int64 array: one join
        program over n rows, one download."""
        return np.asarray(self._device_get(B.plane_rows(plane, n=n)))

    # ------------------------------------------------------------------ API

    def merge(self, store: KeySpace, batch: ColumnarBatch) -> MergeStats:
        return self.merge_many(store, [batch])

    def merge_many(self, store: KeySpace, batches: list[ColumnarBatch]) -> MergeStats:
        """Fold any number of columnar batches into the store.  Reductions
        are associative + commutative, so all batches merge in one device
        pass per CRDT family — and the same properties license the
        pipelined stage/dispatch overlap (see __init__).

        The returned MergeStats carries this call's device-transfer
        deltas (dev_upload_bytes / dev_download_bytes /
        dev_rounds_resident / flush_rows_downloaded) sliced out of the
        engine's cumulative counters."""
        h0, d0 = self.bytes_h2d, self.bytes_d2h
        r0, f0 = self.dev_rounds_resident, self.flush_rows_downloaded
        st = self._merge_many_impl(store, batches)
        st.dev_upload_bytes = self.bytes_h2d - h0
        st.dev_download_bytes = self.bytes_d2h - d0
        st.dev_rounds_resident = self.dev_rounds_resident - r0
        st.flush_rows_downloaded = self.flush_rows_downloaded - f0
        return st

    def _merge_many_impl(self, store: KeySpace,
                         batches: list[ColumnarBatch]) -> MergeStats:
        st = MergeStats()
        # the bulk path scatters each slot once per batch, which is only a
        # merge if slots are unique within every batch
        self._unique_ok = all(b.rows_unique_per_slot for b in batches)
        # resident-mirror staleness is checked PER FAMILY in
        # _resident_state (KeySpace.fam_ver): an op write to one CRDT
        # plane no longer drops every other plane's device mirror
        self._n0_keys = store.keys.n
        # pool-id headroom (int32 src plane): flush completed rounds BEFORE
        # staging one that could cross the ceiling — the round boundary is
        # the only safe flush point (mid-round, in-flight family state is
        # not yet in self._res and its pool ids would be dropped)
        if self.resident and self._pool_size and \
                self._pool_size + sum(b.n_rows for b in batches) >= \
                self.POOL_ID_CEILING:
            log.info("win-source pool near int32 ceiling; flushing before "
                     "this merge round")
            self.flush(store)
        # replica snapshots of one keyspace share the key-list object (or,
        # when chunked, a key_shape identity token — batch_chunks); resolve
        # each distinct list/shape once (ids are stable within this merge,
        # and shape tokens pin their parents via shape_refs)
        memo: dict = {}
        resolved = []
        stage = self.stages.stage
        with stage("stage_rows", "keys"):
            for b in batches:
                mk = b.key_shape if b.key_shape is not None \
                    else ("id", id(b.keys), id(b.key_enc))
                kid_of = memo.get(mk)
                if kid_of is None:
                    kid_of = self._resolve_keys(store, b, st)
                    memo[mk] = kid_of
                resolved.append((b, kid_of))
        if not self._unique_ok and self._mesh is None and \
                sum(b.n_rows for b in batches) <= self.HOST_SCATTER_MAX:
            # op-stream micro-batches (the steady-state coalescers'
            # flushes).  DEFAULT placement for a resident engine: fold
            # each batch's duplicate slots on host (a few hundred rows)
            # and scatter-merge the unique winners IN PLACE against the
            # resident device planes — state never round-trips, and the
            # next flush downloads only the touched (dirty) rows.  The
            # host micro strategy (engine/hostbatch.py) is the FALLBACK,
            # per family (cold planes — see _micro_placement) or for the
            # whole round (non-resident engines, CONSTDB_RESIDENT=0,
            # mesh-partitioned state).
            placement = self._micro_placement(store, resolved)
            if placement is not None:
                with seconds_into(self.family_secs, "micro"):
                    for b, kid_of in resolved:
                        self._merge_micro_resident(store, b, kid_of, st,
                                                   placement)
                if any(placement.values()):
                    self.dev_rounds_resident += 1
                elif placement:
                    self.host_micro_rounds += 1
                # empty placement (env-only / delete-only round): neither
                # gauge — no device family was touched at all
                if self.needs_flush and \
                        self._pool_bytes > self.pool_flush_bytes:
                    self.flush(store)
                return st
            # legacy whole-round fallback (steady path off): any resident
            # mirror of the touched planes syncs down first, exactly like
            # the device scatter path would via _drop_family
            from .hostbatch import merge_host_batch
            for fam in list(self._res):
                self._drop_family(store, fam)
            self.host_micro_rounds += 1
            rows0 = st.tensor_rows
            with stage("host_twin", total=(self.family_secs, "host")):
                for b, kid_of in resolved:
                    merge_host_batch(store, b, kid_of, st,
                                     counts=self.merge_rows_host)
            self.tns_host_rows += st.tensor_rows - rows0
            return st
        # a src-tracked pool from resident MICRO rounds must resolve
        # before a bulk branch that does not track src (forced dense_fold
        # configs skip the src kernels) scatters into the same planes —
        # flush would otherwise assign stale pool values over the bulk
        # round's winners
        if self.resident and self._pool_size and not self._host_combine():
            self.flush(store)
        stage_fn = {"env": self._stage_envelopes,
                    "reg": self._stage_registers,
                    "cnt": self._stage_counter_rows,
                    "el": self._stage_elem_rows}
        dispatch = {"env": self._dispatch_envelopes,
                    "reg": self._dispatch_registers,
                    "cnt": self._dispatch_counter_rows,
                    "el": self._dispatch_elem_rows}
        if self.pipeline:
            # double-buffered: the staging pool runs the family stages
            # (possibly concurrently — each touches only its own host
            # plane) while the main thread dispatches each plan in family
            # order as it lands.  The only cross-plane seam is flush,
            # which joins the in-flight stages first.
            ex = self._staging_executor()
            futs = {f: ex.submit(self._timed_stage, f, stage_fn[f],
                                 store, resolved, st)
                    for f in self.FAM_ORDER}
            self._stage_pending = futs
            try:
                for fam in self.FAM_ORDER:
                    with seconds_into(self.family_secs, fam):
                        self._dispatch_plan(fam, dispatch[fam], store,
                                            futs[fam].result(), st)
            finally:
                # a dispatch error must not leave stages mutating the
                # store behind the caller's back
                import concurrent.futures as _cf
                _cf.wait(list(futs.values()))
                self._stage_pending = None
        else:
            for fam in self.FAM_ORDER:
                with seconds_into(self.family_secs, fam):
                    plan = self._timed_stage(fam, stage_fn[fam], store,
                                             resolved, st)
                    self._dispatch_plan(fam, dispatch[fam], store, plan, st)
        # tensor rows (few, payload-heavy) ride the resident payload
        # pools whenever the steady path is on — bulk catch-up seeds the
        # pools the micro rounds then merge into; the host twin covers
        # everything else (meshes partition slot rows the pools don't)
        tns_device = self.resident and self.steady and self._mesh is None
        for b, kid_of in resolved:
            if len(b.tns_ki):
                with stage("dispatch" if tns_device else "host_twin", "tns"):
                    self._merge_micro_tns(store, b, kid_of, st,
                                          device=tns_device)
        for b, _ in resolved:
            for i, key in enumerate(b.del_keys):
                store.record_key_delete(key, int(b.del_t[i]))
        # slot merges bypass the incremental sum cache — re-derive it in one
        # vectorized pass (envelope-only merges cannot change counter sums);
        # resident mode re-derives at flush time instead
        if not (self.resident and self.needs_flush) and \
                any(len(b.cnt_ki) for b, _ in resolved):
            store.recompute_counter_sums()
        # bound the win pool: a long streamed catch-up with no interleaved
        # reads would otherwise pin every staged batch's columns in host
        # RAM until the (read-triggered) flush
        if self.resident and self.needs_flush and \
                self._pool_bytes > self.pool_flush_bytes:
            self.flush(store)
        return st

    # ------------------------------------------------------ stage pipeline

    def _staging_executor(self):
        """Staging pool.  Family stages are mutually independent (each
        touches only its own host plane — see the per-stage docstrings),
        so they stage CONCURRENTLY, not just ahead of dispatch; results
        stay byte-identical because each plane's appends happen inside
        exactly one stage, in batch order.  Sized to the spare cores
        (CONSTDB_STAGE_WORKERS overrides)."""
        if self._stage_ex is None:
            import os as _os
            from concurrent.futures import ThreadPoolExecutor

            from ..conf import env_int
            n = env_int("CONSTDB_STAGE_WORKERS",
                        max(1, min(len(self.FAM_ORDER),
                                   (_os.cpu_count() or 2) - 1)))
            self._stage_ex = ThreadPoolExecutor(
                max_workers=max(n, 1), thread_name_prefix="constdb-stage")
        return self._stage_ex

    def close(self) -> None:
        """Release the staging pool's threads (idempotent; the pool is
        recreated lazily if the engine merges again).  Engines are
        long-lived in production, but short-lived ones — bench repeats,
        full-resync rebuilds — should not each strand a thread pool
        until interpreter exit."""
        ex = self._stage_ex
        if ex is not None:
            self._stage_ex = None
            ex.shutdown(wait=False)

    def __del__(self):  # pragma: no cover - GC timing
        try:
            self.close()
        except Exception:
            pass

    def _timed_stage(self, fam: str, fn, store, resolved, st):
        """One family's STAGE step under the `stage_rows` clock — on a
        staging-pool thread when the pipeline is on (its own stage stack:
        the time is counted but never nests into the dispatching
        thread's stages)."""
        with self.stages.stage("stage_rows", fam):
            return fn(store, resolved, st)

    def _dispatch_plan(self, fam: str, fn, store, plan, st) -> None:
        """One family's DISPATCH step under the `dispatch` clock, and its
        staged rows counted by the path they took: every bulk and scatter
        mode launches device kernels; only the resident envelope fold
        ("host" mode) merges on the host, under `host_twin`."""
        if plan is None:
            return
        host = plan.get("mode") == "host"
        with self.stages.stage("host_twin" if host else "dispatch", fam):
            fn(store, plan, st)
        by_path = self.merge_rows_host if host else self.merge_rows_dev
        by_path[fam] += sum(len(s[0]) for s in plan["staged"])

    def _join_staging(self) -> None:
        """Wait for in-flight family stages before any cross-plane mutation
        (flush rebuilds/writes tables a stage may be appending to).  Errors
        are NOT swallowed here — the merge loop re-raises them from
        future.result()."""
        futs = self._stage_pending
        if futs:
            import concurrent.futures as _cf
            _cf.wait(list(futs.values()))

    # ---------------------------------------------------------------- flush

    def flush(self, store: KeySpace) -> None:
        """Write resident device state back into the host keyspace (resident
        mode only; a no-op otherwise).  Also re-derives counter sums and
        enqueues element tombstones whose del_t advanced on device.

        Dirty-row accounting: a family whose merges since the last flush
        were all resident MICRO rounds carries an explicit dirty-row set
        and each round's win vector, already on its way to the host: the
        flush launches NOTHING for it — it reads the vectors and applies
        the rounds in order (_apply_wins).  Only a micro round that fell
        back to `src` tracking (_scatter_pair) has its dirty rows
        gathered on device (ops/bulk.py gather_rows) and downloaded;
        whole-plane downloads happen only for bulk catch-up merges
        (dirty=None) that really did touch the plane wholesale, and an
        untouched family costs nothing.  Counter sums update
        INCREMENTALLY over the dirty rows (old-vs-new contribution delta)
        instead of the O(table) recompute.

        Download protocol: EVERY family's downloads dispatch up front
        (device-side [:n] slice / dirty-row gather so padding and
        untouched rows never cross the link; copy_to_host_async overlaps
        transfers), then families are consumed one at a time — family f's
        host-side application (column writes, src resolution, tombstone
        scans) runs while the remaining families' transfers are still in
        flight, and each consumed device slice is dropped immediately so
        its buffer frees without waiting for the whole flush."""
        if not self.needs_flush:
            return
        self._join_staging()
        with self.stages.stage("d2h_flush",
                               total=(self.family_secs, "flush")):
            self._flush_resident(store)

    def _flush_resident(self, store: KeySpace) -> None:
        """flush()'s body, under its `d2h_flush` stage."""
        self._warm_patch()
        pending: dict[str, dict] = {}
        partial: dict[str, tuple] = {}  # fam -> (rows_d, {name: dev}, src)
        for fam, res in self._res.items():
            n = res["n"]
            if n == 0:
                continue
            dirty = res.get("dirty")
            if dirty is not None and not dirty:
                continue  # untouched since the last flush: host == device
            cols = res["cols"]
            names = ["stack"] if fam == "env" else \
                [name for name, _ in _FAMILIES[fam]]
            written = res.get("written")
            recon = res.get("recon") if res.get("src") is not None else None
            want = [name for name in names
                    # mirror column never scattered into: the host column
                    # it was built from is still exact
                    if not (written is not None and name not in written)
                    # winner-carried column: reconstructed on host from
                    # the win pool via the (int32) src plane — the int64
                    # column itself never crosses the link
                    and not (recon and name in recon)]
            self.flush_rows_full_equiv += n
            if dirty is None:
                fp = {name: B.plane_rows(cols[name], n=n) for name in want}
                if res.get("src") is not None:
                    fp["src"] = res["src"][:n]
                if fp or res.get("wins"):
                    pending[fam] = fp
                    self.flush_rows_downloaded += n
                continue
            rows_d = np.unique(np.concatenate(dirty))
            g, src_dev = {}, None
            if want or res.get("src") is not None:
                # a micro round fell back to the bulk protocol: gather
                # its rows.  pow2-padded gather idx (pad rows re-gather
                # row 0 and are sliced off after download): with the
                # FLUSH_GATHER_PAD floor, the gather jit re-traces per
                # plane cap only
                np2 = K.next_pow2(max(len(rows_d), self.FLUSH_GATHER_PAD))
                idx_dev = self._put_batch(_pad(rows_d.astype(_I32), np2, 0))
                g = {name: B.gather_rows(cols[name], idx_dev)
                     for name in want}
                if res.get("src") is not None:
                    src_dev = B.gather_rows(res["src"], idx_dev)
            partial[fam] = (rows_d, g, src_dev)
            self.flush_rows_downloaded += len(rows_d)
        for fp in pending.values():
            for arr in fp.values():
                _copy_async(arr)
        for _rows_d, g, src_dev in partial.values():
            for arr in g.values():
                _copy_async(arr)
            if src_dev is not None:
                _copy_async(src_dev)

        for fam, fp in pending.items():
            res = self._res[fam]
            n = res["n"]
            host = {}
            for name in list(fp):
                h = np.asarray(fp.pop(name))  # blocks on THIS slice only
                self.bytes_d2h += int(h.nbytes)
                host[name] = h
            table = _host_table(store, fam)
            # micro rounds that preceded the whole-plane ones: their
            # winners first, what the device says of the later rounds over
            # them
            self._apply_wins(store, fam, res)
            # the tombstone scan below only matters when the device could
            # have advanced del_t — skipped (all-add catch-up) it is
            # old_dt == del_t by construction
            el_dt_changed = fam == "el" and "del_t" in host
            if el_dt_changed:
                old_dt = table.del_t[:n].copy()
            if fam == "env":
                out = host["stack"]
                for i, (name, _) in enumerate(_FAMILIES["env"]):
                    table.col(name)[:n] = out[:, i]
            else:
                for name, _ in _FAMILIES[fam]:
                    if name in host:
                        table.col(name)[:n] = host[name]
            if "src" in host:
                self._apply_src(store, fam, host["src"], res)
                res["src"] = None  # resolved; fresh tracking next round
            if res.get("written") is not None:
                # downloaded state now equals the host columns: only columns
                # dirtied AFTER this flush need the next download
                res["written"] = set()
            if el_dt_changed:
                self._enqueue_elem_garbage(store, np.arange(n),
                                           table.add_t[:n], table.del_t[:n],
                                           old_dt)
            # host now equals device for the whole plane: later flushes
            # skip this family until new merges dirty it again
            res["dirty"] = []

        for fam, (rows_d, g, src_dev) in partial.items():
            res = self._res[fam]
            table = _host_table(store, fam)
            nd = len(rows_d)
            if fam == "cnt":
                # incremental sum delta needs the PRE-flush host
                # contributions of exactly the dirty rows
                old_contrib = store.cnt.val[rows_d] - store.cnt.base[rows_d]
            # win-vector rounds always precede a fallback's (once `src`
            # is tracked every scatter falls back until this flush)
            self._apply_wins(store, fam, res)
            host = {}
            for name in list(g):
                h = np.asarray(g.pop(name))[:nd]
                self.bytes_d2h += int(h.nbytes)
                host[name] = h
            if fam == "env":
                out = host.get("stack")
                if out is not None:
                    for i, (name, _) in enumerate(_FAMILIES["env"]):
                        table.col(name)[rows_d] = out[:, i]
            else:
                for name, _ in _FAMILIES[fam]:
                    if name in host:
                        table.col(name)[rows_d] = host[name]
            if src_dev is not None:
                src_h = np.asarray(src_dev)[:nd]
                self.bytes_d2h += int(src_h.nbytes)
                self._apply_src(store, fam, src_h, res, rows=rows_d)
                res["src"] = None
            if fam == "cnt":
                new_contrib = store.cnt.val[rows_d] - store.cnt.base[rows_d]
                delta = new_contrib - old_contrib
                changed = np.nonzero(delta)[0]
                if len(changed):
                    np.add.at(store.keys.cnt_sum,
                              store.cnt.kid[rows_d[changed]],
                              delta[changed])
            # el del side is host-maintained on the micro path; its GC
            # entries ride _el_del_touched below
            res["written"] = set()
            res["dirty"] = []

        if self._el_del_touched:
            # host-maintained del side (el src path): with add_t now
            # reconstructed, queue rows that ended up dead.  old_dt=-1:
            # every touched row's del_t advanced by construction, so the
            # shared helper's "newly dead" filter reduces to at < dt.
            rows = np.unique(np.concatenate(self._el_del_touched))
            self._el_del_touched.clear()
            self._enqueue_elem_garbage(
                store, rows, store.el.add_t[rows], store.el.del_t[rows],
                np.full(len(rows), -1, dtype=_I64))
        self._val_pool.clear()
        self._pool_size = 0
        self._pool_bytes = 0
        # host val/base mutate ONLY through the two consume loops above:
        # a whole-plane cnt flush re-derives every sum (device segment-sum
        # when the backend supports it), the dirty path already applied
        # its incremental deltas, and an untouched cnt mirror left the
        # sums exact from the previous flush
        if "cnt" in pending and self._res["cnt"]["n"]:
            self._recompute_sums(store)
        self._flush_tns(store)
        self.needs_flush = False

    def release_device_pools(self, store: KeySpace) -> None:
        """Hard-watermark memory reclaim (server/overload.py): flush
        resident state down to the host, then RELEASE the device
        mirrors, win-value pools, and tensor payload pools — they
        refill lazily on the next merge round (mirror_rebuilds counts
        it).  Unlike discard_resident this is loss-free: flush() runs
        first, so host state is exact when the device copies drop."""
        self.flush(store)
        self._res.clear()
        self._val_pool.clear()
        self._pool_size = 0
        self._pool_bytes = 0
        self._el_del_touched.clear()
        if self._tns_pools:
            self._tns_pools.clear()
            self._tns_bytes = 0
            self._tns_epoch += 1

    def discard_resident(self) -> None:
        """Forget ALL resident device state WITHOUT flushing — only valid
        when the host store itself is being discarded (Node.
        reset_for_full_resync); a fresh store's fam_ver could otherwise
        collide with a stale mirror's recorded version."""
        self._res.clear()
        self._val_pool.clear()
        self._pool_size = 0
        self._pool_bytes = 0
        self._el_del_touched.clear()
        self._tns_pools.clear()
        self._tns_bytes = 0
        self._tns_epoch += 1
        self.needs_flush = False

    def _apply_wins(self, store: KeySpace, fam: str, res: dict) -> None:
        """Apply the family's micro rounds since the last flush to the
        host columns, IN ROUND ORDER, from the win vector each scatter
        returned (`bulk_lww_win`): a winning batch row's pair replaces the
        host row's, a losing one leaves it.  Round k's vector was computed
        against the planes after round k-1, and the host columns equalled
        the mirror when the first round started (flush-before-touch), so
        this reproduces the device's planes bit for bit.  Values by
        _apply_src's rule: a winning row of a value-carrying key takes its
        batch value — None where the batch carried none, which CLEARS the
        slot; set members and counters carry no values.  Reading a vector
        blocks on the scatter that produced it, and on nothing later."""
        wins = res.get("wins")
        if not wins:
            return
        table = _host_table(store, fam)
        for pcol, scol, wr, win_dev, (_base, vals, cols) in wins:
            win = np.asarray(win_dev)[:len(wr)]
            self.bytes_d2h += len(wr)
            at = np.flatnonzero(win)
            if not len(at):
                continue
            rw = wr[at]
            self.micro_win_rows += len(rw)
            table.col(pcol)[rw] = np.asarray(cols[pcol])[at]
            table.col(scol)[rw] = np.asarray(cols[scol])[at]
            if fam == "reg":
                target = store.reg_val
            elif fam == "el":
                keep = np.isin(store.keys.enc[store.el.kid[rw]],
                               S.VALUE_ENCS)
                rw, at = rw[keep], at[keep]
                target = store.el_val
            else:
                continue  # counters carry no object values
            for r, j in zip(rw.tolist(), at.tolist()):
                target[r] = None if vals is None else vals[j]
        res["wins"] = []

    def _apply_src(self, store: KeySpace, fam: str, src_h: np.ndarray,
                   res: dict, rows: Optional[np.ndarray] = None) -> None:
        """Consume the downloaded src plane: (a) RECONSTRUCT the
        winner-carried int64 columns from the host pool (bit-identical to
        the device state by construction — the kernels set column and src
        under the same win predicate), and (b) assign deferred win VALUES
        (set rows — valueless by construction — are skipped wholesale).

        `rows`: table rows src_h's positions map to (the dirty-row
        partial flush downloads a GATHERED src slice); None = src_h is
        the whole plane and positions ARE table rows."""
        rows_all = np.nonzero(src_h >= 0)[0]
        if not len(rows_all):
            return
        pool = self._val_pool
        gids_all = src_h[rows_all].astype(_I64)
        if rows is not None:
            # sorted-unique dirty rows: positions map through in order,
            # so rows_all stays strictly ascending (the contiguity fast
            # path below still holds)
            rows_all = rows[rows_all]
        if len(pool) == 1:
            # single staged segment (fully combined round): skip the
            # segment sort entirely
            order = np.arange(len(gids_all))
            uniq = np.zeros(1, dtype=_I64)
            starts = np.zeros(1, dtype=_I64)
            ends = np.array([len(order)])
        else:
            bases = np.fromiter((b for b, _, _ in pool), dtype=_I64,
                                count=len(pool))
            segs_all = np.searchsorted(bases, gids_all, side="right") - 1
            order = np.argsort(segs_all, kind="stable")
            uniq, starts = np.unique(segs_all[order], return_index=True)
            ends = np.append(starts[1:], len(order))
        # (a) column reconstruction, vectorized one pool segment at a time
        recon = res.get("recon")
        if recon:
            table = _host_table(store, fam)
            for s, lo, hi in zip(uniq.tolist(), starts.tolist(),
                                 ends.tolist()):
                sel = order[lo:hi]
                r_sel = rows_all[sel]
                off = gids_all[sel] - pool[s][0]
                cols = pool[s][2]
                for host_col, pool_col in recon.items():
                    table.col(host_col)[r_sel] = \
                        np.asarray(cols[pool_col])[off]
        # (b) win values — per SEGMENT, not per row: catch-up slots are
        # created in contiguous blocks, so most segments assign via one
        # C-speed list-slice write (the per-row loop with a pool lookup
        # each iteration dominated value-heavy flushes)
        if fam == "cnt":
            return  # counters carry no object values
        if fam == "reg":
            vmask = np.ones(len(rows_all), dtype=bool)
            target = store.reg_val
        else:
            vmask = np.isin(store.keys.enc[store.el.kid[rows_all]],
                            S.VALUE_ENCS)
            target = store.el_val
        for s, lo, hi in zip(uniq.tolist(), starts.tolist(), ends.tolist()):
            sel = order[lo:hi]
            m = vmask[sel]
            if not m.any():
                continue
            sel = sel[m]
            r_sel = rows_all[sel]
            b, vals, _ = pool[s]
            if vals is None:
                # all-valueless batch: winning rows CLEAR the slot value
                # (CPU parity — local-loses replaces with None)
                picked = [None] * len(r_sel)
            else:
                picked = list(map(vals.__getitem__,
                                  (gids_all[sel] - b).tolist()))
            r0 = int(r_sel[0])
            # r_sel is strictly ascending and unique by construction
            # (np.nonzero order preserved through the stable argsort), so
            # the endpoint check alone proves contiguity
            if int(r_sel[-1]) == r0 + len(r_sel) - 1:
                target[r0:r0 + len(r_sel)] = picked
            else:
                for r, v in zip(r_sel.tolist(), picked):
                    target[r] = v

    # ------------------------------------------------------ resident state

    def _resident_state(self, store: KeySpace, fam: str, n: int,
                        micro: bool = False):
        """Device state dict for family `fam` covering rows [0, n); grows
        (neutral-filled) as the host table grows.  Returns (cols, cap).

        Staleness: the mirror records the host plane's write version at
        build time; an op-path write or GC to THIS plane (KeySpace.touch)
        makes it stale — other planes' mirrors survive.  A stale mirror
        of a journaled family is REPAIRED: the rows written since it last
        equalled the host are in the plane's RowJournal, and one scatter
        sets them (`_patch_mirror`).  Only a journal that is whole (GC,
        compaction, a reset), over the largest bucket, or not this
        engine's to trust forces the whole-plane rebuild from host.

        `res["cols"]` is the only copy of a family's planes, each an
        ops/bulk.py `Plane` — (hi int32, lo uint32), never an int64 array:
        bulk rounds, micro rounds, the grow path and the patch all read
        and replace the same pairs.  `micro`: the caller is a micro round,
        whose grow marks the family as growing under load: its planes keep
        at least GROW_FLOOR rows from then on, rebuilt or grown.  (A boot
        restore's bulk rounds grow a plane once, to the size its table
        keeps: no floor.)"""
        res = self._res.get(fam)
        if micro and res is not None and n > res["cap"]:
            self._growing.add(fam)   # rebuilt or grown, past its planes
        ver = store.fam_ver[fam]
        stale = res is not None and res.get("ver") != ver
        journal = store.journal.get(fam) if self._mesh is None else None
        rows = None
        if stale:
            # A stale mirror never holds unflushed device data: the Node
            # flushes before every op-path write, so whatever bumped this
            # plane's version found the mirror already synced.
            # (needs_flush may be True here from EARLIER families of this
            # same merge round — their mirrors are not stale.)  Patching
            # over or dropping a stale mirror that still holds unflushed
            # merged columns would silently lose merge results — that is a
            # broken flush-before-touch invariant somewhere upstream; fail
            # loud (a real raise, not an assert: `python -O` must not
            # strip the only guard between a dispatch-table bug and
            # silent data loss)
            if res.get("written") or res.get("wins"):
                raise RuntimeError(
                    f"{fam} mirror invalidated with unflushed merge data "
                    "(flush-before-touch invariant broken upstream)")
            if journal is not None and res.get("jepoch") == journal.epoch:
                rows = journal.take()
                if rows is None and journal.over:
                    self.mirror_patch_overflows += 1
            if rows is None:
                self.mirror_rebuilds[fam] += 1
                self.mirror_rebuild_causes[store.fam_cause[fam]] += 1
                res = None
        cap = self._sp_size(max(n, self.GROW_FLOOR) if fam in self._growing
                            else n)
        spec = _FAMILIES[fam]
        if res is None:
            # a whole-plane upload (first build or stale rebuild)
            with self.stages.stage("mirror_rebuild", fam):
                table = _host_table(store, fam)
                if fam == "env":
                    host = np.stack([table.col(c)[:n] for c, _ in spec],
                                    axis=-1)
                    cols = {"stack": self._put_state(_pad(host, cap, 0))}
                else:
                    cols = {c: self._put_state(
                        _pad(table.col(c)[:n], cap, fill))
                        for c, fill in spec}
        elif n > res["cap"]:
            old = res["cols"]
            delta = cap - res["cap"]
            self.mirror_grows[fam] += 1
            with self.stages.stage("mirror_rebuild", fam):
                if fam == "env":
                    cols = {"stack": self._grow(old["stack"], delta, 0)}
                else:
                    cols = {c: self._grow(old[c], delta, fill)
                            for c, fill in spec}
        else:
            cols = res["cols"]
            cap = res["cap"]
        if rows is not None:
            # (rows past the old `n` ride the same scatter, after the grow)
            self._patch_mirror(store, fam, cols, rows)
        # mirror == host from here: the journal starts over, and the
        # epoch says whose it is
        jepoch = res.get("jepoch") if res else None
        if journal is not None and (res is None or rows is not None):
            jepoch = journal.reset()
        # `dirty`/`recon`/`wins` survive a reuse/grow (the micro path
        # appends touched rows between flushes); a fresh build starts
        # CLEAN (dirty=[] — host == device at build, nothing to download).
        # A patch writes nothing the host lacks: neither written nor dirty
        self._res[fam] = {"cols": cols, "n": n, "cap": cap, "ver": ver,
                          "jepoch": jepoch,
                          "src": res.get("src") if res else None,
                          "written": res.get("written", set()) if res
                          else set(),
                          "recon": res.get("recon") if res else None,
                          "wins": res.get("wins", []) if res else [],
                          "dirty": res.get("dirty") if res else []}
        return cols, cap

    def _patch_mirror(self, store: KeySpace, fam: str, cols: dict,
                      rows: np.ndarray) -> None:
        """Repair a stale mirror in place: gather the host columns at the
        journaled `rows` (sorted, distinct), upload them as one [Bp, C]
        block behind its int32 idx, and SET them into the resident planes
        (donated).  Bp is one of MIRROR_PATCH_BUCKETS; the pad targets
        distinct rows past the plane and drops, as a batch's does."""
        k = len(rows)
        self.mirror_patches[fam] += 1
        self.mirror_patch_rows[fam] += k
        if not k:
            return   # the version moved and no row did (a lost LWW write)
        with self.stages.stage("mirror_patch", fam):
            bp = next(b for b in self.MIRROR_PATCH_BUCKETS if b >= k)
            table = _host_table(store, fam)
            vals = np.stack([table.col(c)[rows] for c, _ in _FAMILIES[fam]],
                            axis=-1)
            cap = next(iter(cols.values())).shape[0]
            self._run_patch(fam, cols, _pad_idx(rows, cap, bp),
                            _pad(vals, bp, 0))

    def _run_patch(self, fam: str, cols: dict, idx: np.ndarray,
                   vals: np.ndarray) -> None:
        """Upload one patch block and launch `fam`'s program on `cols`
        (donated; the dict takes the outputs)."""
        names = [c for c, _ in _FAMILIES[fam]]
        out = B.MIRROR_PATCH[fam](tuple(cols[c] for c in names),
                                  self._put_batch(idx), self._put_batch(vals))
        cols.update(zip(names, out))

    def _warm_patch(self) -> None:
        """Run the patch programs of every journaled mirror once at its
        present cap, all rows out of range so nothing is written.  Called
        by flush: a mirror goes stale only after one (flush-before-touch),
        so the programs a later patch needs compile here — after a boot
        restore that is inside set-up — and never inside a served window.
        (Distinct rows never outnumber the plane, so no bucket past the
        first that covers it is used.)"""
        if self._mesh is not None:
            return
        for fam in JOURNAL_FAMILIES:
            res = self._res.get(fam)
            if res is None or self._patch_warm.get(fam) == res["cap"]:
                continue
            cap = self._patch_warm[fam] = res["cap"]
            with self.stages.stage("state_alloc", fam):
                for bp in self.MIRROR_PATCH_BUCKETS:
                    self._run_patch(
                        fam, res["cols"], _pad_idx(np.zeros(0, _I32), cap, bp),
                        np.zeros((bp, len(_FAMILIES[fam])), dtype=_I64))
                    if bp >= cap:
                        break

    def _family_done(self, fam: str, cols: dict, n: int, cap: int,
                     src=None, written=None, recon=None) -> None:
        """Record post-merge device state.  `written` marks which columns
        the kernels actually scattered into since the mirror was created —
        flush downloads only those (an untouched mirror column equals the
        host column it was uploaded from, padding included).  None = all.
        `recon` maps winner-carried device columns to their pool column
        name — those skip the flush download entirely and reconstruct on
        host from the win pool (valid only while `src` is tracked)."""
        prev = self._res.get(fam) or {}
        w = prev.get("written", set())
        w |= set(cols) if written is None else written
        self._res[fam] = {"cols": cols, "n": n, "cap": cap, "written": w,
                          "ver": prev.get("ver"),
                          "jepoch": prev.get("jepoch"),
                          "src": src if src is not None else prev.get("src"),
                          "recon": recon if recon is not None
                          else prev.get("recon"),
                          # earlier micro rounds' unapplied win vectors:
                          # the flush applies them before this round's src
                          "wins": prev.get("wins", [])}
        self.needs_flush = True

    def _drop_family(self, store: KeySpace, fam: str) -> None:
        """A host-side (scatter) update is about to touch this family: sync
        device state down first, then forget the mirror."""
        if fam in self._res:
            self.flush(store)
            del self._res[fam]

    # ------------------------------------------------- resident micro merges
    # The steady-state placement (the ISSUE 8 routing inversion): op-stream
    # micro-batches — the serve/replication coalescers' flushes — merge IN
    # PLACE against the resident device planes instead of falling back to
    # the host micro strategy.  Duplicate slots fold on host with the exact
    # shared reductions from engine/hostbatch.py, the unique winners
    # scatter once per family (ops/bulk.py bulk_lww_win: one block up,
    # the win vector down), the env plane stays HOST-AUTHORITATIVE
    # (its merge is a collision-free max into host columns — zero device
    # bytes, and key-dt reads never need a flush), and every scatter's
    # rows land in the family's dirty set so flush() touches only them.

    def host_stale(self, families) -> bool:
        """True when any of `families` holds unflushed device-side merge
        state (its host columns lag the device: columns written and not
        downloaded, a tracked `src`, or win vectors not yet applied).
        Callers that provably
        read only planes OUTSIDE the stale set may skip the flush — the
        narrow read-barrier Node.ensure_flushed_for exposes to the
        steady-state coalescers (env is host-authoritative on the micro
        path, so dt reads cost no round-trip)."""
        if not self.needs_flush:
            return False
        for fam in families:
            if fam == "tns":
                if any(p["dirty"] for p in self._tns_pools.values()):
                    return True
                continue
            res = self._res.get(fam)
            if res is not None and (res.get("written") or res.get("wins")
                                    or res.get("src") is not None):
                return True
        return False

    @staticmethod
    def _micro_touched(resolved):
        """Device families a micro round actually merges (env is host-side
        and never gates the routing decision)."""
        from ..utils.native_tables import nonnull_mask
        fams = set()
        for b, _ in resolved:
            if "reg" not in fams and b.n_keys and \
                    nonnull_mask(b.reg_val).any():
                fams.add("reg")
            if len(b.cnt_ki):
                fams.add("cnt")
            if len(b.el_ki):
                fams.add("el")
            if len(b.tns_ki):
                fams.add("tns")
        return fams

    def _micro_placement(self, store: KeySpace, resolved):
        """Per-family steady-state routing: {fam: True=device in-place,
        False=host twin} over the device families this round touches —
        or None when the steady path is off entirely (the legacy
        whole-round host fallback, pre-round-12 behavior).  Families
        route INDEPENDENTLY: CRDT planes are independent by construction
        (the same property that licenses the stage/dispatch overlap), so
        a cold el plane — its version just bumped by a barrier op —
        merges on its host twin while a warm cnt plane keeps merging in
        place.  Warm = mirror already resident and fresh, or host
        version stable for more than `warmup` consecutive micro rounds
        (mixed op/merge traffic would otherwise re-upload a full mirror
        every round just to merge a few hundred rows into it)."""
        if not (self.steady and self.resident):
            return None
        placement = {}
        for fam in self._micro_touched(resolved):
            ver = store.fam_ver[fam]
            if fam == "tns":
                # the tensor plane's mirror is its payload pool set
                if self._tns_pools and self._tns_ver == ver:
                    placement[fam] = True
                    continue
                res = None
            else:
                res = self._res.get(fam)
            if res is not None and res.get("ver") == ver:
                placement[fam] = True  # resident and fresh: free to ride
                continue
            last_ver, streak = self._warm_streak.get(fam, (-1, 0))
            streak = streak + 1 if last_ver == ver else 1
            self._warm_streak[fam] = (ver, streak)
            placement[fam] = streak > self.warmup
        return placement

    def _merge_micro_resident(self, store: KeySpace, b: ColumnarBatch,
                              kid_of: np.ndarray, st: MergeStats,
                              placement: dict) -> None:
        """Merge ONE op-stream micro-batch under the steady placement:
        warm families scatter in place against resident device planes —
        the device twin of engine/hostbatch.merge_host_batch, fold for
        fold (both sides use the very same fold_* reductions, so the
        scattered winners ARE the host path's winners) — and cold
        families take their host twins directly.  Differential-tested
        byte-identical in tests/test_resident_steady.py."""
        from ..utils.native_tables import nonnull_mask
        from .hostbatch import (_apply_cnt_pair, _merge_el, _merge_env,
                                _merge_reg, _resolve_el_rows, fold_el_rows,
                                fold_pair_rows)
        if "env" in self._res:
            # forced-fold catch-ups can leave a device env mirror; the
            # micro path keeps env host-authoritative, so sync it down
            # once and merge on host from here on
            self._drop_family(store, "env")
        stage = self.stages.stage
        rows_dev, rows_host = self.merge_rows_dev, self.merge_rows_host
        valid = kid_of >= 0
        all_valid = bool(valid.all())
        if b.n_keys:
            kids = kid_of if all_valid else kid_of[valid]
            if len(kids):
                with stage("host_twin", "env"):
                    mat = np.stack([b.key_ct, b.key_mt, b.key_dt,
                                    b.key_expire], axis=-1)
                    _merge_env(store, kids, mat if all_valid else mat[valid])
                rows_host["env"] += len(kids)
            em = valid & (b.key_enc == S.ENC_BYTES) & \
                nonnull_mask(b.reg_val)
            idx = np.nonzero(em)[0]
            if len(idx):
                if placement.get("reg"):
                    with stage("stage_rows", "reg"):
                        wk, wt, wn, srci = fold_pair_rows(
                            kid_of[idx], b.reg_t[idx], b.reg_node[idx])
                        vals = list(map(b.reg_val.__getitem__,
                                        idx[srci].tolist()))
                    self._micro_scatter_pair(store, "reg",
                                             ("rv_t", "rv_node"),
                                             wk, wt, wn, vals)
                    rows_dev["reg"] += len(wk)
                else:
                    with stage("host_twin", "reg"):
                        _merge_reg(store, kid_of[idx], b.reg_t[idx],
                                   b.reg_node[idx],
                                   list(map(b.reg_val.__getitem__,
                                            idx.tolist())))
                    rows_host["reg"] += len(idx)

        if len(b.cnt_ki):
            kid_arr = kid_of[b.cnt_ki]
            keep = np.nonzero(kid_arr >= 0)[0]
            if len(keep):
                st.counter_rows += len(keep)
                sel = slice(None) if len(keep) == len(kid_arr) else keep
                on_dev = bool(placement.get("cnt"))
                with stage("stage_rows", "cnt"):
                    rows = self._resolve_cnt_rows(store, kid_arr[sel],
                                                  b.cnt_node[sel])
                    bt = b.cnt_base_t[sel]
                    base_neutral = bool((bt == K.NEUTRAL_T).all())
                    if on_dev:
                        # (uuid, val) pair: LWW on uuid, max-value tie —
                        # the winners reconstruct from the pool at flush,
                        # so the two widest counter columns never download
                        wr, wu, wv, _ = fold_pair_rows(
                            rows, b.cnt_uuid[sel], b.cnt_val[sel])
                        if not base_neutral:
                            # base pair (counter deletes — rare): no src
                            # tracking, its win vector tells the flush
                            wr2, wbt, wb, _ = fold_pair_rows(
                                rows, bt, b.cnt_base[sel])
                if on_dev:
                    self._micro_scatter_pair(store, "cnt", ("uuid", "val"),
                                             wr, wu, wv, None)
                    if not base_neutral:
                        self._micro_scatter_pair(store, "cnt",
                                                 ("base_t", "base"),
                                                 wr2, wbt, wb, None,
                                                 src=False)
                    rows_dev["cnt"] += len(wr)
                else:
                    with stage("host_twin", "cnt"):
                        _apply_cnt_pair(store, rows, b.cnt_val[sel],
                                        b.cnt_uuid[sel], "val", "uuid", 1)
                        if not base_neutral:
                            _apply_cnt_pair(store, rows, b.cnt_base[sel],
                                            bt, "base", "base_t", -1)
                    rows_host["cnt"] += len(rows)

        if len(b.el_ki):
            kid_arr = kid_of[b.el_ki]
            keep = np.nonzero(kid_arr >= 0)[0]
            if len(keep):
                st.elem_rows += len(keep)
                if len(keep) == len(kid_arr):
                    sel = slice(None)
                    members = b.el_member
                    vals = b.el_val
                else:
                    sel = keep
                    members = list(map(b.el_member.__getitem__,
                                       keep.tolist()))
                    vals = list(map(b.el_val.__getitem__, keep.tolist()))
                on_dev = bool(placement.get("el"))
                with stage("stage_rows", "el"):
                    rows = _resolve_el_rows(store, kid_arr[sel], members)
                    if on_dev:
                        wr, wat, wan, d_red, srci = fold_el_rows(
                            rows, b.el_add_t[sel], b.el_add_node[sel],
                            b.el_del_t[sel])
                        if b.el_has_vals is False or not has_values(vals):
                            wvals = None  # winning valueless adds still
                            # CLEAR the slot value at flush (pool
                            # vals=None contract)
                        else:
                            wvals = list(map(vals.__getitem__,
                                             srci.tolist()))
                if not on_dev:
                    with stage("host_twin", "el"):
                        _merge_el(store, rows, b.el_add_t[sel],
                                  b.el_add_node[sel], b.el_del_t[sel], vals)
                    rows_host["el"] += len(rows)
                else:
                    self._micro_scatter_pair(store, "el",
                                             ("add_t", "add_node"),
                                             wr, wat, wan, wvals)
                    rows_dev["el"] += len(wr)
                    # del side: plain max applied straight to the HOST
                    # column, with the DEVICE del_t plane advanced in
                    # lockstep (one max scatter, only when the batch
                    # actually carries deletes — rare in steady state).
                    # A host-only write would leave the mirror's del_t
                    # stale-but-"fresh", and a later forced-fold bulk
                    # round (bulk_elems reads and re-downloads del_t)
                    # would regress the host column and resurrect the
                    # deleted elements.  Newly-dead rows queue for GC at
                    # flush, after add_t reconstruction.
                    nz = np.flatnonzero(d_red)
                    if len(nz):
                        sel_r = wr[nz]
                        cur = store.el.del_t[sel_r]
                        dv = d_red[nz]
                        adv = dv > cur
                        if adv.any():
                            rows_adv = sel_r[adv]
                            dv_adv = dv[adv]
                            store.el.del_t[rows_adv] = dv_adv
                            self._el_del_touched.append(rows_adv)
                            res = self._res["el"]
                            sp = res["cap"]
                            np2 = K.next_pow2(max(len(rows_adv),
                                                  self.MICRO_SCATTER_PAD))
                            with stage("dispatch", "el"):
                                res["cols"]["del_t"] = B.bulk_max1(
                                    res["cols"]["del_t"],
                                    self._batch_idx(rows_adv, 0, sp, np2),
                                    self._put_batch(_pad(dv_adv, np2, 0)))

        if len(b.tns_ki):
            on_dev = bool(placement.get("tns"))
            with stage("dispatch" if on_dev else "host_twin", "tns"):
                self._merge_micro_tns(store, b, kid_of, st, device=on_dev)

        for i, key in enumerate(b.del_keys):
            store.record_key_delete(key, int(b.del_t[i]))

    def _micro_scatter_pair(self, store: KeySpace, fam: str, pair, wr,
                            wp, ws, vals, src: bool = True) -> None:
        """Scatter one folded LWW pair in place against `fam`'s resident
        planes.  `pair` = (primary, secondary) column names; the win rule
        is lexicographic (primary, secondary) > current — exactly
        hostbatch's fold rule and ops/bulk._pair_win.  `vals` are the
        batch rows' values (None: valueless).  How the host learns who
        won is _scatter_pair's choice; src=False (the rare counter base
        pair) is never tracked in the family's `src` plane."""
        if not len(wr):
            return
        with self.stages.stage("dispatch", fam):
            self._scatter_pair(store, fam, pair, wr, wp, ws, vals, src)

    def _scatter_pair(self, store: KeySpace, fam: str, pair, wr, wp, ws,
                      vals, src: bool) -> None:
        """_micro_scatter_pair's body, under its `dispatch` stage (the
        host-side launch: asynchronous, so launch time, not device time).

        The round crosses the link once each way: ONE int32 [5, np2]
        block up (the padded idx, then (hi, lo) of both columns, split
        here over the batch's rows), ONE program (`bulk_lww_win`), and
        its win vector down — `np2` bytes, copied as the program ends and
        read at the flush (_apply_wins).  Pool ids never go to the device.

        That holds while every round since the family's last flush was a
        micro round (`dirty` is a list).  A family still carrying a
        whole-plane round's unflushed state — `dirty` None, or a tracked
        `src` — keeps the bulk protocol (`bulk_lww_src`: the winner's pool
        id into the `src` plane, resolved by flush's gather): its flush
        reads `src` anyway, and a later whole-plane round may win the same
        rows again.  src=False (the counter base pair: the family's `src`
        plane belongs to its value pair) always takes the win vector."""
        nw = len(wr)
        n = _fam_rows(store, fam)
        cols, sp = self._resident_state(store, fam, n, micro=True)
        res = self._res[fam]
        pcol, scol = pair
        # pad-floor the batch length (see MICRO_SCATTER_PAD); a batch
        # covering every plane row pads to itself (nw == sp == pow2)
        np2 = K.next_pow2(nw if nw >= sp
                          else max(nw, self.MICRO_SCATTER_PAD))
        if src and (res["dirty"] is None or res["src"] is not None):
            self.micro_src_scatters += 1
            idx = self._batch_idx(wr, 0, sp, np2)
            bp = self._put_batch(_pad(wp, np2, K.NEUTRAL_T))
            bs = self._put_batch(_pad(ws, np2, K.NEUTRAL_T))
            pb = self._pool_add(vals, **{pcol: wp, scol: ws})
            p2, s2, src2 = B.bulk_lww_src(
                cols[pcol], cols[scol], self._src_state(fam, sp),
                idx, bp, bs, pb)
            self._micro_done(fam, {pcol: p2, scol: s2}, src=src2,
                             recon={pcol: pcol, scol: scol},
                             written={pcol, scol}, rows=wr)
            return
        blk = np.zeros((5, np2), dtype=_I32)
        blk[0] = _pad_idx(wr, sp, np2)
        for r, col in ((1, wp), (3, ws)):
            # an int64's little-endian words: [lo, hi], lo's bit pattern
            # as int32 (the program bitcasts it back to uint32)
            words = np.ascontiguousarray(col, dtype="<i8").view("<i4")
            blk[r, :nw] = words[1::2]
            blk[r + 1, :nw] = words[0::2]
        p2, s2, win = B.bulk_lww_win(cols[pcol], cols[scol],
                                     self._put_batch(blk))
        _copy_async(win)
        # the pool entry pins the batch (values and both columns) until
        # the flush applies it, under the same pool_flush_bytes bound
        self._pool_add(vals, **{pcol: wp, scol: ws})
        self._pool_bytes += np2
        res["wins"].append((pcol, scol, wr, win, self._val_pool[-1]))
        self.micro_win_scatters += 1
        self._micro_done(fam, {pcol: p2, scol: s2}, rows=wr)

    def _micro_done(self, fam: str, cols: dict, rows: np.ndarray,
                    src=None, recon=None, written=frozenset()) -> None:
        """Fold a micro scatter's results into the family record: updated
        device columns, src/recon tracking, the columns the flush must
        download (`written`: none where a win vector tells the host), and
        the touched rows appended to the dirty set (a bulk-merged plane —
        dirty None — stays whole-plane)."""
        res = self._res[fam]
        res["cols"].update(cols)
        if src is not None:
            res["src"] = src
        if recon is not None:
            res["recon"] = dict(recon) if res.get("recon") is None \
                else {**res["recon"], **recon}
        res["written"] |= written
        if res.get("dirty") is not None:
            res["dirty"].append(np.asarray(rows))
        self.needs_flush = True

    def _recompute_sums(self, store: KeySpace) -> None:
        """Counter-sum re-derivation after a whole-plane cnt flush.  On
        an accelerator the segment-sum runs ON DEVICE over the resident
        slot contributions (slot kids upload as int32, only the [n_keys]
        sums download — val/base never cross the link); on the CPU
        backend the host bincount pass does it (uploading to sum would
        cost more than it saves).  Both are exact int64 — bit-identical
        to KeySpace.recompute_counter_sums."""
        res = self._res.get("cnt")
        n = store.cnt.n
        nk = store.keys.n
        if not (self._jax.default_backend() != "cpu"
                and self._mesh is None and res is not None
                and res["n"] == n and n and nk):
            store.recompute_counter_sums()
            return
        from ..ops import dense as D
        cols = res["cols"]
        # whole padded planes, pow2 segment count: pad rows contribute
        # 0 (val/base fill) to segment 0, and the jit re-traces per
        # plane cap, not per row count
        ids = self._put_batch(_pad(store.cnt.kid[:n].astype(_I32),
                                   res["cap"], 0))
        sums = D.segment_sum(ids, B.plane_diff(cols["val"], cols["base"]),
                             n_seg=K.next_pow2(nk))
        store.keys.cnt_sum[:nk] = np.asarray(self._device_get(sums))[:nk]

    # ------------------------------------------------------ tensor registers
    # The tensor-valued register family (crdt/tensor.py): contributor
    # slot STAMPS (uuid/cnt columns) are host-authoritative — the merge
    # decisions are tiny LWW compares, exactly the env-plane rule — while
    # the payload ARRAYS, the part whose per-value work actually
    # dominates, live in resident device pools keyed by (dtype, elems).
    # A micro round folds each batch's duplicate slots on host, wins
    # against the host uuid column, and scatters ONLY the winning
    # payloads into the pool (one device call per class per batch);
    # flush gathers and downloads exactly the dirty pool slots.  Batched
    # reads (`tensor_read_many`) reduce contributor stacks ON DEVICE
    # with the canonical-order kernels (ops/pallas_dense.py
    # tensor_reduce + XLA twins) — byte-identical to the host reference
    # (KeySpace.tensor_read), differential-tested.

    def _tns_check(self, store: KeySpace) -> None:
        """Tensor-pool staleness: an op-path tensor write bumped the
        plane version, so every clean payload mirror may be stale —
        drop the pools (they refill lazily).  Dirty slots present at a
        version bump mean the flush-before-touch invariant broke
        upstream: fail loud, exactly like _resident_state."""
        ver = store.fam_ver["tns"]
        if self._tns_ver != ver:
            if any(p["dirty"] for p in self._tns_pools.values()):
                raise RuntimeError(
                    "tns pools invalidated with unflushed payloads "
                    "(flush-before-touch invariant broken upstream)")
            self._tns_pools.clear()
            self._tns_bytes = 0
            self._tns_ver = ver
            self._tns_epoch += 1

    def _tns_pool(self, store: KeySpace, meta) -> dict:
        key = (meta.dtype_code, meta.elems)
        pool = self._tns_pools.get(key)
        if pool is None:
            from ..ops import pallas_dense as PD
            kp = max(K.next_pow2(meta.elems), PD.TENSOR_BLOCK)
            pool = {"buf": None, "rows": np.full(0, -1, dtype=_I64),
                    "map": {}, "n": 0, "cap": 0, "dirty": set(),
                    "Kp": kp, "elems": meta.elems, "dtype": meta.dtype}
            self._tns_pools[key] = pool
        return pool

    def _tns_slots(self, pool: dict, rows_store) -> np.ndarray:
        """Pool slots for store rows, allocating (and growing the device
        buffer with zero rows) for rows not yet resident."""
        jnp = self._jax.numpy
        m = pool["map"]
        need = sum(1 for r in rows_store if r not in m)
        if pool["n"] + need > pool["cap"]:
            cap = K.next_pow2(max(pool["n"] + need, 64))
            grown = np.full(cap, -1, dtype=_I64)
            grown[: len(pool["rows"])] = pool["rows"]
            pool["rows"] = grown
            zeros = jnp.zeros((cap - pool["cap"], pool["Kp"]),
                              dtype=pool["dtype"].name)
            pool["buf"] = zeros if pool["buf"] is None else \
                jnp.concatenate([pool["buf"], zeros])
            self._tns_bytes += \
                (cap - pool["cap"]) * pool["Kp"] * pool["dtype"].itemsize
            pool["cap"] = cap
        out = np.empty(len(rows_store), dtype=_I64)
        for j, r in enumerate(rows_store):
            slot = m.get(r)
            if slot is None:
                slot = pool["n"]
                pool["n"] = slot + 1
                m[r] = slot
                pool["rows"][slot] = r
            out[j] = slot
        return out

    # pow2 pad floor for tensor scatter stacks: winner counts vary per
    # micro round, and each pow2 bucket is a pool_scatter re-trace —
    # padding to a floor collapses the shape space (same reasoning as
    # MICRO_SCATTER_PAD; pad rows scatter out of range and drop)
    TNS_SCATTER_PAD = 128

    def _tns_scatter(self, pool: dict, slots: np.ndarray,
                     mats: list, dirty: bool) -> None:
        """Scatter payload rows into a pool in one device call.  `mats`
        are SIZE-VALIDATED payloads (wire bytes or flat arrays of the
        pool dtype); `dirty` marks the slots device-newer than the host
        list (merge winners) — uploads that MIRROR host payloads (read
        staging) stay clean.

        Hot path: an all-bytes batch whose elems fill the pool width
        stacks via one C-speed join + zero-copy frombuffer instead of a
        per-row fill loop (the fill loop was a top merge cost in the
        tensor bench)."""
        from ..ops import dense as D
        w = len(slots)
        wp = K.next_pow2(max(w, self.TNS_SCATTER_PAD))
        kp = pool["Kp"]
        dt = pool["dtype"]
        # (wire payloads are little-endian; the zero-copy path needs the
        # native order to match — every supported target is LE)
        if pool["elems"] == kp and w and np.little_endian and \
                all(type(m) is bytes for m in mats):
            flat = np.frombuffer(b"".join(mats), dtype=dt).reshape(w, kp)
            stack = flat if wp == w else \
                np.concatenate([flat, np.zeros((wp - w, kp), dtype=dt)])
        else:
            stack = np.zeros((wp, kp), dtype=dt)
            for j, m in enumerate(mats):
                arr = m if isinstance(m, np.ndarray) \
                    else np.frombuffer(m, dtype=dt.newbyteorder("<"))
                stack[j, : len(arr)] = arr
        idx = np.empty(wp, dtype=_I32)
        idx[:w] = slots
        if wp > w:  # out-of-range pads drop
            idx[w:] = pool["cap"] + np.arange(wp - w, dtype=_I32)
        pool["buf"] = D.pool_scatter(pool["buf"], self._put_batch(idx),
                                     self._put_batch(stack))
        if dirty:
            pool["dirty"].update(slots.tolist())

    def _merge_micro_tns(self, store: KeySpace, b: ColumnarBatch,
                         kid_of: np.ndarray, st: MergeStats,
                         device: bool) -> None:
        """Merge one batch's tensor rows.  `device=False` is the host
        reference (engine/hostbatch.merge_host_tns — the per-row loop);
        `device=True` makes the same decisions in batch: fold duplicate
        slots, win against the host uuid column, scatter the winning
        payloads into the resident pools.  Differential-tested
        byte-identical (tests/test_tensor_family.py)."""
        from ..crdt import tensor as T
        from .hostbatch import merge_host_tns
        if not device:
            n0 = st.tensor_rows
            merge_host_tns(store, b, kid_of, st)
            self.tns_host_rows += st.tensor_rows - n0
            return
        self._tns_check(store)
        kid_arr = kid_of[b.tns_ki]
        keep = np.nonzero(kid_arr >= 0)[0]
        if not len(keep):
            return
        st.tensor_rows += len(keep)
        self.tns_dev_rows += len(keep)
        # count gate FIRST, matching the host reference's check order:
        # tensor_merge_row runs check_count BEFORE installing a fresh
        # key's config, so a batch whose every row for a key is
        # count-invalid must leave tns_meta uninstalled on BOTH paths
        cnt_ok = b.tns_cnt[keep] >= 1
        if not cnt_ok.all():
            log.error("skipping %d tensor rows: contribution count < 1",
                      int((~cnt_ok).sum()))
            keep = keep[cnt_ok]
            if not len(keep):
                return
        # per-key config install/validate + per-row payload checks: the
        # same skip rules as KeySpace.tensor_merge_row, decided once per
        # distinct key where possible (bad rows drop exactly like type
        # conflicts).  The common case — one config across the whole
        # batch (a homogeneous aggregation stream) — validates once per
        # DISTINCT KEY plus one vectorized size pass, no per-row python.
        idx_list = keep.tolist()
        metas: dict = {}
        ok = np.ones(len(keep), dtype=bool)
        cfg0 = b.tns_cfg[idx_list[0]]
        uniform = True
        for i in idx_list[1:]:
            c = b.tns_cfg[i]
            if c is not cfg0 and c != cfg0:
                uniform = False
                break
        if uniform:
            bad_kids = None
            for kid in np.unique(kid_arr[keep]).tolist():
                meta = store.tns_meta.get(kid)
                try:
                    if meta is None:
                        meta = T.unpack_config(cfg0)
                        store.tns_meta[kid] = meta
                    elif T.pack_config(meta) != bytes(cfg0):
                        raise T.TensorConfigError("tensor config mismatch")
                    metas[kid] = meta
                except T.TensorConfigError as e:
                    log.error("skipping tensor rows for kid %d: %s",
                              kid, e)
                    metas[kid] = False
                    bad_kids = True
            if bad_kids:
                ok &= np.fromiter(
                    (metas[int(k)] is not False for k in kid_arr[keep]),
                    dtype=bool, count=len(keep))
            meta_u = next((m for m in metas.values()
                           if m is not False), None)
            if meta_u is not None:
                # the shared validity predicate (T.payload_ok) — the
                # same rule tensor_merge_row enforces via payload_array
                bad_sz = np.fromiter(
                    (not T.payload_ok(meta_u, p)
                     for p in (b.tns_payload[i] for i in idx_list)),
                    dtype=bool, count=len(keep))
                if bad_sz.any():
                    log.error("skipping %d tensor rows: bad payload "
                              "(size/dtype)", int(bad_sz.sum()))
                    ok &= ~bad_sz
        else:
            for j, i in enumerate(idx_list):
                kid = int(kid_arr[i])
                meta = metas.get(kid)
                if meta is None:
                    meta = store.tns_meta.get(kid)
                    cfg = b.tns_cfg[i]
                    try:
                        if meta is None:
                            meta = T.unpack_config(cfg)
                            store.tns_meta[kid] = meta
                        elif T.pack_config(meta) != bytes(cfg):
                            raise T.TensorConfigError(
                                "tensor config mismatch")
                    except T.TensorConfigError as e:
                        log.error("skipping tensor rows for kid %d: %s",
                                  kid, e)
                        metas[kid] = False
                        ok[j] = False
                        continue
                    metas[kid] = meta
                elif meta is False:
                    ok[j] = False
                    continue
                else:
                    cfg = b.tns_cfg[i]
                    if T.pack_config(meta) != bytes(cfg):
                        log.error("skipping tensor row for kid %d: "
                                  "config mismatch", kid)
                        ok[j] = False
                        continue
                if not T.payload_ok(meta, b.tns_payload[i]):
                    log.error("skipping tensor row for kid %d: bad "
                              "payload (size/dtype)", kid)
                    ok[j] = False
                    continue
                store.tensor_count_merge(meta)
        keep = keep[ok]
        if not len(keep):
            return
        if uniform:
            # per-strategy gauge: one bump per VALIDATED delivered row
            # (the host reference counts in tensor_merge_row at the
            # same point; a per-win count would depend on routing —
            # the device path folds duplicates before its win test)
            meta0 = next((m for m in metas.values() if m is not False),
                         None)
            if meta0 is not None:
                store.tensor_count_merge(meta0, len(keep))
        kids = kid_arr[keep]
        nodes = b.tns_node[keep]
        uuids = b.tns_uuid[keep]
        cnts = b.tns_cnt[keep]
        # resolve (kid, node) -> slot rows (creates neutral rows), then
        # fold intra-batch duplicates: LWW on uuid, FIRST occurrence on
        # exact ties (one node's equal stamps are the same write — the
        # host loop's strict > keeps the first too)
        rows = self._resolve_tns_rows(store, kids, nodes)
        order = np.lexsort((-np.arange(len(rows)), uuids, rows))
        r_s = rows[order]
        last = np.nonzero(np.append(r_s[1:] != r_s[:-1], True))[0]
        src = order[last]
        wr = r_s[last]
        wu = uuids[src]
        cur = store.tns.uuid[wr]
        win = wu > cur
        if not win.any():
            return
        w_rows = wr[win]
        w_src = src[win]
        store.tns.uuid[w_rows] = wu[win]
        store.tns.cnt[w_rows] = cnts[w_src]
        # winners grouped per pool class, scattered in one call each
        # (size-validated RAW payloads — _tns_scatter normalizes); host
        # payload entries stay STALE until flush (the stamps above are
        # what later merge decisions read — host-authoritative)
        if uniform:
            meta = next((m for m in metas.values() if m is not False),
                        None)
            if meta is not None:
                mats = [b.tns_payload[int(keep[s_i])]
                        for s_i in w_src.tolist()]
                pool = self._tns_pool(store, meta)
                slots = self._tns_slots(pool, w_rows.tolist())
                self._tns_scatter(pool, slots, mats, dirty=True)
        else:
            classes: dict = {}
            for r, s_i in zip(w_rows.tolist(), w_src.tolist()):
                kid = int(kids[s_i])
                meta = metas[kid]
                ent = classes.setdefault((meta.dtype_code, meta.elems),
                                         (meta, [], []))
                ent[1].append(r)
                ent[2].append(b.tns_payload[int(keep[s_i])])
            for meta, rws, mats in classes.values():
                pool = self._tns_pool(store, meta)
                slots = self._tns_slots(pool, rws)
                self._tns_scatter(pool, slots, mats, dirty=True)
        self.needs_flush = True
        if self._tns_bytes > self.tns_pool_cap:
            # residency cap: sync the dirty payloads down and release
            # the device pools (they refill lazily); loud in the log —
            # a workload thrashing the cap should raise it
            log.info("tensor pools over CONSTDB_TENSOR_POOL_MB; flushing "
                     "and dropping %d pools (%d bytes)",
                     len(self._tns_pools), self._tns_bytes)
            self._flush_tns(store)
            self._tns_pools.clear()
            self._tns_bytes = 0
            self._tns_epoch += 1

    def _resolve_tns_rows(self, store: KeySpace, kids: np.ndarray,
                          nodes: np.ndarray) -> np.ndarray:
        """(kid, node) -> store tensor slot rows, creating neutral slots
        for misses — the batched twin of KeySpace.tensor_slot_row."""
        ranks = np.fromiter((store.rank_of(int(x)) for x in nodes),
                            dtype=_I64, count=len(nodes))
        combos = (kids << KeySpace.NODE_RANK_BITS) | ranks
        rn0 = store.tns.n
        rows, n_new = store.tns_index.get_or_assign_batch(combos,
                                                          next_val=rn0)
        if n_new:
            created = np.nonzero(rows >= rn0)[0]
            uniq_rows, first = np.unique(rows[created], return_index=True)
            pos = created[first]
            if len(uniq_rows) != n_new or int(uniq_rows[0]) != rn0 or \
                    int(uniq_rows[-1]) != rn0 + n_new - 1:
                span = f"[{int(uniq_rows[0])}, {int(uniq_rows[-1])}]" \
                    if len(uniq_rows) else "[]"
                raise RuntimeError(
                    f"tns combo index issued non-contiguous rows {span} "
                    f"(n={len(uniq_rows)}) for block "
                    f"[{rn0}, {rn0 + n_new - 1}]")
            store.tns.append_block(n_new, kid=kids[pos], node=nodes[pos],
                                   uuid=K.NEUTRAL_T, cnt=0)
            store.tns_payload.extend([None] * n_new)
        return rows

    def _flush_tns(self, store: KeySpace) -> None:
        """Download dirty pool slots back into the host payload list —
        the tensor half of the dirty-row flush discipline."""
        for pool in self._tns_pools.values():
            dirty = pool["dirty"]
            if not dirty:
                continue
            slots = np.fromiter(dirty, dtype=_I64, count=len(dirty))
            slots.sort()
            self.flush_rows_full_equiv += pool["n"]
            self.flush_rows_downloaded += len(slots)
            np2 = K.next_pow2(max(len(slots), 1))
            idx = self._put_batch(_pad(slots.astype(_I32), np2, 0))
            got = np.asarray(self._device_get(
                B.gather_rows(pool["buf"], idx)))[: len(slots)]
            elems = pool["elems"]
            rows = pool["rows"]
            for j, slot in enumerate(slots.tolist()):
                store.tensor_assign_payload(int(rows[slot]),
                                            got[j, :elems].copy())
            pool["dirty"] = set()

    def tensor_read_many(self, store: KeySpace, kids) -> dict:
        """Batched tensor reads: {kid: flat payload array (None when no
        contribution landed)}.  With resident pools on, contributor
        stacks reduce ON DEVICE (canonical-order kernels via
        _pallas_or_xla; f64 and `lww` route to their exact twins) and
        only the [G, K] results download — dirty payloads never
        round-trip through the host.  Host-only engines/config read the
        reference reduction (KeySpace.tensor_read).

        The grouping/upload pass (contributor enumeration, pool-slot
        resolution, missing-row staging, the device idx vector) is
        CACHED between calls: contributor membership and canonical
        order change only when slot rows are created (one slot per
        (key, node), ordered by node), and pool slots only when pools
        drop — the cache stamp covers both, so a steady read loop pays
        per round only the per-round truth (count columns, lww stamps,
        the reduce dispatches, the result download)."""
        if not (self.resident and self.steady and self._mesh is None):
            return {kid: store.tensor_read(kid) for kid in kids}
        # launches AND the blocking download of the reduced rows
        with self.stages.stage("dispatch", "tns_read"):
            return self._tensor_read_resident(store, kids)

    def _tensor_read_resident(self, store: KeySpace, kids) -> dict:
        """tensor_read_many's device half, under its `dispatch` stage."""
        from ..crdt import tensor as T
        from ..ops import dense as D
        from ..ops import pallas_dense as PD
        self._tns_check(store)
        kids_t = tuple(kids)
        # one staleness stamp for ALL cached key sets, then one entry
        # per requested kids tuple — interleaved single-key GETs (the
        # production Node.tensor_read pattern) each keep their own
        # cached group/idx structure instead of thrashing one slot
        stamp = (self._tns_epoch, self._tns_ver, store.tns.n)
        rc = self._tns_read_cache
        if rc.get("stamp") != stamp:
            rc = self._tns_read_cache = {"stamp": stamp, "by_kids": {}}
        cache = rc["by_kids"].get(kids_t)
        if cache is None:
            if len(rc["by_kids"]) >= 8192:  # bound a huge-keyspace scan
                rc["by_kids"].clear()
            cache = self._tns_read_build(store, kids_t)
            rc["by_kids"][kids_t] = cache
        out = dict(cache["empty"])
        for grp in cache["groups"]:
            (dcode, elems, strat, n, g, members, pool, idx_dev,
             flat_rows, rows_mat, nodes_mat, slots_mat) = grp
            buf = pool["buf"]
            f32 = dcode == 0
            if strat == T.STRAT_LWW:
                # winner from host-authoritative stamps, vectorized:
                # max uuid per key, writer node breaking exact ties;
                # payload served from the pool (the dirty row's truth)
                u = store.tns.uuid[rows_mat]
                cand = u == u.max(axis=1, keepdims=True)
                w = np.where(cand, nodes_mat,
                             np.int64(-1) << 62).argmax(axis=1)
                idx = slots_mat[np.arange(g), w].astype(_I32)
                got = np.asarray(self._device_get(B.gather_rows(
                    buf, self._put_batch(idx))))
                for j, kid in enumerate(members):
                    out[kid] = got[j, :elems]
                continue
            # trimmed-mean divisor as a RUNTIME scalar (a constant
            # divisor strength-reduces to a reciprocal multiply and
            # rounds away from the host's true division)
            div = pool["dtype"].type(n if n <= 2 else n - 2)
            cnts_f = store.tns.cnt[flat_rows].reshape(g, n).astype(
                pool["dtype"])
            cnts_dev = self._put_batch(cnts_f)

            def _reduce(s_id):
                # XLA fuses the pool gather INTO the fold
                # (tensor_take_reduce — one dispatch, no [G, n, Kp]
                # intermediate); the Pallas leg keeps the
                # correctness-pinned two-step (gather + block kernel)
                if f32:
                    return self._pallas_or_xla(
                        lambda interp: PD.tensor_reduce(
                            B.gather_rows(buf, idx_dev).reshape(
                                g, n, pool["Kp"]),
                            cnts_dev, div, strat=s_id, n=n,
                            interpret=interp),
                        lambda: D.tensor_take_reduce(buf, idx_dev, div,
                                                     strat=s_id, n=n,
                                                     g=g))
                return D.tensor_take_reduce(buf, idx_dev, div,
                                            strat=s_id, n=n, g=g)

            if strat == T.STRAT_AVG:
                # gather+scale fused, then sum+div — the product
                # rounding still lands on the dispatch boundary between
                # them (ops/dense.py tensor_take_scale); count totals
                # accumulate on host with the canonical sequential
                # dtype chain
                # vectorized over KEYS, sequential over contributors:
                # elementwise float adds in the same per-key order as
                # the scalar chain — bit-identical, n numpy ops instead
                # of g*n interpreted iterations per read round
                t = cnts_f[:, 0].copy()
                for i in range(1, n):
                    t = t + cnts_f[:, i]
                tots_dev = self._put_batch(t.reshape(g, 1))
                wmat = D.tensor_take_scale(buf, idx_dev, cnts_dev,
                                           n=n, g=g)
                if f32:
                    red = self._pallas_or_xla(
                        lambda interp: D.tensor_div(
                            PD.tensor_reduce(wmat, cnts_dev, div,
                                             strat=T.STRAT_SUM, n=n,
                                             interpret=interp),
                            tots_dev),
                        lambda: D.tensor_sum_div(wmat, tots_dev, n=n))
                else:
                    red = D.tensor_sum_div(wmat, tots_dev, n=n)
            else:
                red = _reduce(strat)
            got = np.asarray(self._device_get(red))
            for j, kid in enumerate(members):
                out[kid] = got[j, :elems]
        return out

    def _tns_read_build(self, store: KeySpace, kids_t: tuple) -> dict:
        """Build (and stage) the cached read-group structure for one key
        set: contributor rows in canonical order per key, grouped by
        (dtype, elems, strategy, n); rows not yet pool-resident upload
        as CLEAN mirrors; the flat pool-slot idx vector ships to the
        device once."""
        raw: dict = {}
        empty: dict = {}
        for kid in kids_t:
            meta = store.tns_meta.get(kid)
            rows = store.tensor_contrib_rows(kid)
            if meta is None or not rows:
                empty[kid] = None
                continue
            raw.setdefault((meta.dtype_code, meta.elems, meta.strat,
                            len(rows)), []).append((kid, meta, rows))
        groups = []
        for (dcode, elems, strat, n), mem in raw.items():
            pool = self._tns_pool(store, mem[0][1])
            flat = np.fromiter((r for _k, _m, rows in mem for r in rows),
                               dtype=_I64, count=len(mem) * n)
            missing = [r for r in dict.fromkeys(flat.tolist())
                       if r not in pool["map"]]
            if missing:
                mats = [store.tns_payload[r] for r in missing]
                slots = self._tns_slots(pool, missing)
                self._tns_scatter(pool, slots, mats, dirty=False)
            g = len(mem)
            m = pool["map"]
            slots_mat = np.fromiter((m[r] for r in flat.tolist()),
                                    dtype=_I64,
                                    count=g * n).reshape(g, n)
            rows_mat = flat.reshape(g, n)
            groups.append((dcode, elems, strat, n, g,
                           [kid for kid, _m2, _r in mem], pool,
                           self._put_batch(
                               slots_mat.reshape(-1).astype(_I32)),
                           flat, rows_mat, store.tns.node[rows_mat],
                           slots_mat))
        return {"empty": empty, "groups": groups}

    # ------------------------------------------------------- key resolution

    def _resolve_keys(self, store: KeySpace, batch: ColumnarBatch,
                      st: MergeStats) -> np.ndarray:
        """batch key position -> local kid (-1 on type conflict).  ONE
        shared implementation with the host micro path
        (engine/hostbatch.py resolve_keys) — `resident=True` zeroes
        created rows' host ct/dt so host and device mirrors start
        neutral together."""
        from .hostbatch import resolve_keys
        return resolve_keys(store, batch, st, resident=self.resident)

    # --------------------------------------------------- bulk-path plumbing

    def _use_bulk(self, total_rows: int, region: int) -> bool:
        if not self._unique_ok:
            return False
        if self.resident or self._mesh is not None:
            # resident: no state upload to amortize — bulk always wins.
            # mesh: bulk is the sharded path; the scatter fallback would
            # run single-device.
            return True
        return region > 0 and total_rows * self.BULK_FRACTION >= region

    @staticmethod
    def _bulk_region(staged_rows: list[np.ndarray], n0: int, n: int
                     ) -> tuple[int, int, bool]:
        """-> (base, size, all_new): the slot region the kernels operate on.
        When every staged row is brand new (>= n0, the pre-merge table size)
        only the new block [n0, n) participates — its initial state is
        neutral and can be materialized on device with zero upload."""
        lo = min(int(r.min()) for r in staged_rows if len(r))
        if lo >= n0:
            return n0, n - n0, True
        return 0, n, False

    def _upload_batch(self, rows: np.ndarray, base: int, sp: int,
                      cols: list[tuple[np.ndarray, int]]):
        """Async-upload one batch: int32 ids (padded with distinct
        out-of-range slots) + padded value columns.  On a mesh, batch rows
        replicate to every device (each scatters its slot range)."""
        n = len(rows)
        np_ = K.next_pow2(max(n, 1))
        return [self._batch_idx(rows, base, sp, np_)] + \
            [self._put_batch(_pad(c, np_, fill)) for c, fill in cols]

    def _iota_r0(self, rows: np.ndarray, base: int):
        """Device-relative start (np.int32) when `rows` is one long
        contiguous run — the catch-up shape — else None.  The ONE home
        for the contiguity predicate + IDX_IOTA_MIN threshold; the fused
        src kernels and _batch_idx's derived-iota path both use it."""
        n = len(rows)
        if n < self.IDX_IOTA_MIN:
            return None
        r0 = int(rows[0])
        if int(rows[n - 1]) - r0 + 1 != n or not (np.diff(rows) == 1).all():
            return None
        return np.int32(r0 - base)

    def _bulk_src_call(self, fn, fn_iota, states, rows, base: int, sp: int,
                       cols, pb):
        """One src-tracking scatter dispatch: contiguous rows take the
        FUSED variant (idx derived inside the kernel from two scalars —
        one dispatch, no intermediate idx buffer); anything else uploads
        or derives an idx vector and calls the classic kernel."""
        n = len(rows)
        np_ = K.next_pow2(max(n, 1))
        dev = [self._put_batch(_pad(c, np_, fill)) for c, fill in cols]
        if self._mesh is None:  # fused iota kernels are single-device
            r0 = self._iota_r0(rows, base)
            if r0 is not None:
                return fn_iota(*states, r0, np.int32(n), *dev, pb, np_=np_)
        idx = self._batch_idx(rows, base, sp, np_)
        return fn(*states, idx, *dev, pb)

    def _batch_idx(self, rows: np.ndarray, base: int, sp: int, np_: int):
        n = len(rows)
        # catch-up chunks create (and re-touch) slot rows in contiguous
        # blocks; a contiguous idx is DERIVED on device from three
        # scalars (iota) — the int32 index vector never crosses the
        # link.  Padded positions land at >= sp (out of range) exactly
        # like the host-built vector's, so scatters drop them.
        r0 = self._iota_r0(rows, base)
        if r0 is not None:
            return self._iota_idx(np_)(r0, np.int32(n), np.int32(sp))
        return self._put_batch(_pad_idx(rows - base, sp, np_))

    def _iota_idx(self, np_: int):
        """Jitted idx builder for one padded batch length (cached).  On a
        mesh the idx replicates like every other batch array (out
        sharding = self._sh_rep) so downstream kernels never mix device
        commitments."""
        key = ("iota_idx", np_)
        fn = self._jit_cache.get(key)
        if fn is None:
            jnp = self._jax.numpy

            def make(r0, n, sp_):
                i = self._jax.lax.iota(jnp.int32, np_)
                return jnp.where(i < n, r0 + i, sp_ + i)

            fn = self._jax.jit(make, out_shardings=self._sh_rep) \
                if self._mesh is not None else self._jax.jit(make)
            self._jit_cache[key] = fn
        return fn

    def _state_up(self, col: np.ndarray, base: int, size: int, sp: int,
                  fill: int, all_new: bool):
        if all_new:
            return self._full(sp, fill)
        return self._put_state(_pad(col[base:base + size], sp, fill))

    @staticmethod
    def _i32_up(arr: np.ndarray, fill64: int):
        """Opportunistic int32 upload spec: halves the bytes whenever the
        column's values fit (node ids, small counter values); the kernels
        sign-extend and split every batch column themselves, so results
        are bit-identical."""
        arr = np.asarray(arr)
        if len(arr) and -(1 << 31) <= int(arr.min()) and \
                int(arr.max()) < (1 << 31):
            # padded rows scatter nowhere (out-of-range idx), so any
            # representable pad value works
            return (arr.astype(np.int32), -1)
        return (arr, fill64)

    # ---------------------------------------------------- aligned-batch fold
    # R batches staging the exact same slot rows (R replica snapshots of one
    # keyspace — the bulk catch-up shape) reduce on-device in one fused
    # [R, N] pass, then scatter ONCE.  Counter rows fold too, but only
    # align for repeated syncs from the SAME origin (replica snapshots
    # carry per-(key, node) slots, which differ per replica).

    # the device-fold path shares the host pre-combine's alignment rule
    _aligned = staticmethod(_rows_aligned)

    def _fold_prep(self, staged, base: int, sp: int):
        """Common fold staging: (rows0, nA, np_, device idx)."""
        rows0 = staged[0][0]
        nA = len(rows0)
        np_ = K.next_pow2(max(nA, 1))
        self.folds += 1
        return rows0, nA, np_, self._batch_idx(rows0, base, sp, np_)

    @staticmethod
    def _stacked(staged, i: int, fill, np_: int) -> np.ndarray:
        return np.stack([_pad(s[i], np_, fill) for s in staged])

    def _kernel_backend(self) -> str:
        """"pallas" | "pallas-interpret" | "xla" for the fold and
        tensor-reduce kernels (ops/pallas_dense.py; each has an XLA twin
        in ops/dense.py).  A forced dense_fold is taken as given; "auto"
        is the Mosaic-compiled kernels on a TPU backend and XLA
        everywhere else (the interpreter is for CPU tests and is only
        ever FORCED; a mesh keeps XLA — pallas_call inside GSPMD needs
        per-shard shapes).  A lowering failure raises: nothing at run
        time falls back."""
        mode = self.dense_fold
        if mode in ("pallas", "pallas-interpret", "xla"):
            return mode
        if mode == "off" or self._mesh is not None or \
                self._jax.default_backend() != "tpu":
            return "xla"
        return "pallas"

    def _pallas_or_xla(self, pallas_fn, xla_fn):
        """ONE home for kernel-backend resolution: every Pallas call site
        passes both twins."""
        be = self._kernel_backend()
        if be == "xla":
            return xla_fn()
        return pallas_fn(be == "pallas-interpret")

    def _fold_lex(self, t_s, n_s, d_s):
        """[R, N] stacks -> per-slot lexicographic (t, n) winner, max d,
        winning batch row: (t[N], n[N], d[N], win_batch[N]) on device."""
        from ..ops import dense as D
        from ..ops import pallas_dense as PD
        return self._pallas_or_xla(
            lambda interp: PD.merge_elems(
                self._put_batch(t_s), self._put_batch(n_s),
                self._put_batch(d_s), interpret=interp),
            lambda: D.dense_merge_elems(
                self._put_batch(t_s), self._put_batch(n_s),
                self._put_batch(d_s)))

    def _fold_lww(self, t_s, n_s):
        """[R, N] stacks -> plain (t, node) LWW winner: (t[N], n[N],
        win_batch[N]) on device.  The del side the element kernel wants is
        fabricated ON DEVICE (zeros never cross the host link)."""
        from ..ops import dense as D
        from ..ops import pallas_dense as PD

        def _pallas(interp):
            t_d = self._put_batch(t_s)
            at, an, _dt, win = PD.merge_elems(
                t_d, self._put_batch(n_s),
                self._jax.numpy.zeros_like(t_d), interpret=interp)
            return at, an, win

        return self._pallas_or_xla(
            _pallas, lambda: D.dense_merge_lww(self._put_batch(t_s),
                                               self._put_batch(n_s)))

    def _fold_pair(self, v_s, t_s):
        """[R, N] stacks -> per-slot (value @ time) LWW with max-value tie:
        (val[N], t[N]) on device (counter slots — no win flags needed)."""
        from ..ops import dense as D
        from ..ops import pallas_dense as PD
        return self._pallas_or_xla(
            lambda interp: PD.merge_counters(
                self._put_batch(v_s), self._put_batch(t_s),
                interpret=interp),
            lambda: D.dense_merge_counters(self._put_batch(v_s),
                                           self._put_batch(t_s)))

    # ------------------------------------------------------------ envelopes

    def _stage_envelopes(self, store: KeySpace, resolved, st):
        """STAGE (host-only): columnarize + group-combine the envelope
        plane as [n, 4] ct/mt/dt/expire matrices, then make the WHOLE
        placement decision (host-fold vs bulk vs scatter, device fold or
        not) and pre-build every host-side array the dispatch twin will
        upload — including the [R, N, 4] fold stack and the non-resident
        state matrix (both were dispatch-side host work on the critical
        path; STAGE-PURE).  Reading the store's env columns here is safe:
        this plane is only written by _dispatch_envelopes, which the
        pipeline orders strictly after this stage."""
        staged = []  # (pos, [n, 4] matrix)
        for b, kid_of in resolved:
            valid = np.nonzero(kid_of >= 0)[0]
            if not len(valid):
                continue
            if len(valid) == len(kid_of):
                # full batch: stage the shared kid array itself so the
                # combiner can cluster replicas by object identity
                staged.append((kid_of, np.stack(
                    [b.key_ct, b.key_mt, b.key_dt, b.key_expire], axis=-1)))
            else:
                staged.append((kid_of[valid], np.stack(
                    [b.key_ct[valid], b.key_mt[valid], b.key_dt[valid],
                     b.key_expire[valid]], axis=-1)))
        if not staged:
            return None
        staged, folds = self._combine_groups(
            staged,
            lambda st_: (st_[0][0], np.maximum.reduce([s[1] for s in st_])),
            lambda st_, cat: (cat, np.concatenate([s[1] for s in st_])))
        plan = {"staged": staged, "folds": folds}
        if self.resident and self._host_combine() and self._unique_ok:
            plan["mode"] = "host"
            return plan
        total = sum(len(p) for p, _ in staged)
        n = store.keys.n
        base, size, all_new = self._bulk_region([p for p, _ in staged],
                                                self._n0_keys, n)
        if not self._use_bulk(total, size):
            plan["mode"] = "scatter"
            return plan
        plan["mode"] = "bulk"
        plan.update(n=n, base=base, size=size, all_new=all_new)
        plan["fold"] = self._fold_on and self._aligned(staged)
        if plan["fold"]:
            np_ = K.next_pow2(max(len(staged[0][0]), 1))
            plan["stack"] = np.stack([_pad(m, np_, 0) for _, m in staged])
        if not self.resident and not all_new:
            sp = self._sp_size(size)
            host = np.stack([store.keys.ct[base:n], store.keys.mt[base:n],
                             store.keys.dt[base:n],
                             store.keys.expire[base:n]], axis=-1)
            plan["state_host"] = _pad(host, sp, 0)
        return plan

    def _dispatch_envelopes(self, store: KeySpace, plan, st) -> None:
        if plan is None:
            return
        staged = plan["staged"]
        self.folds += plan["folds"]
        if plan["mode"] == "host":
            # envelope merge is plain per-column max with no cross-family
            # device dependency: fold it straight into the host columns
            # (rows are unique per staged entry, so gather-max-scatter is
            # collision-free) — the [N, 4] int64 plane then never crosses
            # the link in either direction.  Bit-identical to the device
            # path: both are int64 max.
            self._drop_family(store, "env")  # sync any device mirror first
            keys = store.keys
            for pos, m in staged:
                for i, (name, _) in enumerate(_FAMILIES["env"]):
                    col = keys.col(name)
                    cur = col[pos]
                    np.maximum(cur, m[:, i], out=cur)
                    col[pos] = cur
            return

        if plan["mode"] == "bulk":
            n, base = plan["n"], plan["base"]
            size, all_new = plan["size"], plan["all_new"]
            if self.resident:
                cols, sp = self._resident_state(store, "env", n)
                state = cols["stack"]
                base = 0
            else:
                sp = self._sp_size(size)
                if all_new:
                    state = self._full(sp, 0, cols=4)
                else:
                    state = self._put_state(plan["state_host"])
            if plan["fold"]:
                # envelopes are plain max — one stacked XLA reduction, one
                # scatter (no win flags to track); the [R, N, 4] stack was
                # pre-built by the stage twin
                from ..ops import dense as D
                rows0, _nA, np_, idx = self._fold_prep(staged, base, sp)
                state = B.bulk_max(state, idx,
                                   D.dense_max(self._put_batch(plan["stack"])))
            else:
                dev = [self._upload_batch(p, base, sp, [(m, 0)])
                       for p, m in staged]
                for idx, c in dev:
                    state = B.bulk_max(state, idx, c)
            if self.resident:
                self._family_done("env", {"stack": state}, n, sp)
                return
            out = self._plane_get(state, size)
            store.keys.ct[base:n] = out[:, 0]
            store.keys.mt[base:n] = out[:, 1]
            store.keys.dt[base:n] = out[:, 2]
            store.keys.expire[base:n] = out[:, 3]
            return
        # scatter path over touched slots.  The store-state gathers stay
        # HERE (not in the stage): _drop_family may flush a resident
        # mirror into these very columns first.
        self._drop_family(store, "env")
        kv = np.concatenate([p for p, _ in staged])
        cat = np.concatenate([m for _, m in staged])
        trows, slot_idx = np.unique(kv, return_inverse=True)
        n_slots = K.next_pow2(len(trows) + 1)
        n_rows = K.next_pow2(len(kv))
        out = K.scatter_max4(
            _pad(slot_idx.astype(_I64), n_rows, n_slots - 1),
            _pad(cat[:, 0], n_rows, K.NEUTRAL_T),
            _pad(cat[:, 1], n_rows, K.NEUTRAL_T),
            _pad(cat[:, 2], n_rows, K.NEUTRAL_T),
            _pad(cat[:, 3], n_rows, K.NEUTRAL_T),
            _pad(store.keys.ct[trows], n_slots, 0),
            _pad(store.keys.mt[trows], n_slots, 0),
            _pad(store.keys.dt[trows], n_slots, 0),
            _pad(store.keys.expire[trows], n_slots, 0),
            n_slots)
        ct, mt, dt, exp = (a[: len(trows)] for a in self._device_get(out))
        store.keys.ct[trows] = ct
        store.keys.mt[trows] = mt
        store.keys.dt[trows] = dt
        store.keys.expire[trows] = exp

    # ------------------------------------------------------------ registers

    def _stage_registers(self, store: KeySpace, resolved, st):
        """STAGE (host-only): select + columnarize register writes, then
        group-combine.  The (kid_of, key_enc) eligibility mask is memoized
        per shared object pair — replica snapshots of one keyspace compute
        it once, not once per replica."""
        from ..utils.native_tables import nonnull_mask
        staged = []  # (pos=kids, t, node, vals)
        emask_memo: dict = {}
        for b, kid_of in resolved:
            if not b.n_keys:
                continue
            mk = (id(kid_of), id(b.key_enc))
            em = emask_memo.get(mk)
            if em is None:
                em = (kid_of >= 0) & (b.key_enc == S.ENC_BYTES)
                emask_memo[mk] = em
            has = nonnull_mask(b.reg_val)
            idx = np.nonzero(em & has)[0]
            if len(idx):
                staged.append((kid_of[idx], b.reg_t[idx], b.reg_node[idx],
                               list(map(b.reg_val.__getitem__,
                                        idx.tolist()))))
        if not staged:
            return None
        def _fold_reg(st_):
            t_f, n_f, wb = _lex_fold([s[1] for s in st_],
                                     [s[2] for s in st_])
            return (st_[0][0], t_f, n_f,
                    list(_sel_obj([s[3] for s in st_], wb)))

        def _cat_reg(st_, cat):
            vals_cat: list = []
            for s in st_:
                vals_cat.extend(s[3])
            return (cat, np.concatenate([s[1] for s in st_]),
                    np.concatenate([s[2] for s in st_]), vals_cat)

        staged, folds = self._combine_groups(staged, _fold_reg, _cat_reg)
        plan = {"staged": staged, "folds": folds}
        # placement decision + fold-stack builds, staged (STAGE-PURE)
        total = sum(len(p) for p, *_ in staged)
        n = store.keys.n
        base, size, all_new = self._bulk_region([p for p, *_ in staged],
                                                self._n0_keys, n)
        plan.update(n=n, base=base, size=size, all_new=all_new,
                    use_bulk=self._use_bulk(total, size), fold=False)
        if plan["use_bulk"] and not (self.resident and self._host_combine()):
            plan["fold"] = self._fold_on and self._aligned(staged)
            if plan["fold"]:
                np_ = K.next_pow2(max(len(staged[0][0]), 1))
                plan["t_s"] = self._stacked(staged, 1, K.NEUTRAL_T, np_)
                plan["n_s"] = self._stacked(staged, 2, K.NEUTRAL_T, np_)
        return plan

    def _dispatch_registers(self, store: KeySpace, plan, st) -> None:
        if plan is None:
            return
        staged = plan["staged"]
        self.folds += plan["folds"]
        n, base = plan["n"], plan["base"]
        size, all_new = plan["size"], plan["all_new"]

        if plan["use_bulk"]:
            if self.resident:
                cols, sp = self._resident_state(store, "reg", n)
                t, nd = cols["rv_t"], cols["rv_node"]
                base = 0
            else:
                sp = self._sp_size(size)
                t = self._state_up(store.keys.rv_t, base, size, sp, 0, all_new)
                nd = self._state_up(store.keys.rv_node, base, size, sp, 0,
                                    all_new)
            if self.resident and self._host_combine():
                # deferred win resolution: no blocking win download — the
                # winning row's pool id lands in the resident src plane
                # (derived on device as base + iota, zero upload), and at
                # flush BOTH the win values and the rv_t/rv_node columns
                # reconstruct from the host pool (ops/bulk.py bulk_lww_src)
                src = self._src_state("reg", sp)
                for p, bt_, bn_, vals in staged:
                    pb = self._pool_add(vals, rv_t=bt_, rv_node=bn_)
                    t, nd, src = self._bulk_src_call(
                        B.bulk_lww_src, B.bulk_lww_src_iota, (t, nd, src),
                        p, base, sp, [(bt_, K.NEUTRAL_T),
                                      self._i32_up(bn_, K.NEUTRAL_T)], pb)
                self._family_done("reg", {"rv_t": t, "rv_node": nd}, n, sp,
                                  src=src,
                                  recon={"rv_t": "rv_t",
                                         "rv_node": "rv_node"})
                return
            fold = plan["fold"]
            if fold:
                rows0, nA, np_, idx = self._fold_prep(staged, base, sp)
                ft, fn, winb = self._fold_lww(plan["t_s"], plan["n_s"])
                t, nd, win = B.bulk_lww(t, nd, idx, ft, fn)
                wins = [win]
            else:
                dev = [self._upload_batch(p, base, sp,
                                          [(bt, K.NEUTRAL_T),
                                           (bn, K.NEUTRAL_T)])
                       for p, bt, bn, _ in staged]
                wins = []
                for idx, bt, bn in dev:
                    t, nd, win = B.bulk_lww(t, nd, idx, bt, bn)
                    wins.append(win)
            if self.resident:
                self._family_done("reg", {"rv_t": t, "rv_node": nd}, n, sp)
            else:
                store.keys.rv_t[base:n] = self._plane_get(t, size)
                store.keys.rv_node[base:n] = self._plane_get(nd, size)
            reg_val = store.reg_val
            if fold:
                winb_h = np.asarray(winb)
                for j in np.nonzero(np.asarray(wins[0])[:nA])[0]:
                    reg_val[int(rows0[j])] = staged[int(winb_h[j])][3][int(j)]
                return
            for (pos, _, _, vals), win in zip(staged, wins):
                for j in np.nonzero(np.asarray(win)[: len(pos)])[0]:
                    reg_val[int(pos[j])] = vals[int(j)]
            return
        # scatter path: registers are LWW slots — reuse the element add-side
        # kernel with a zero del side
        self._drop_family(store, "reg")
        kids = np.concatenate([p for p, *_ in staged])
        vals: list = []
        for _, _, _, v in staged:
            vals.extend(v)
        trows, slot_idx = np.unique(kids, return_inverse=True)
        n_slots = K.next_pow2(len(trows) + 1)
        n_rows = K.next_pow2(len(kids))
        out = K.merge_elems(
            _pad(slot_idx.astype(_I64), n_rows, n_slots - 1),
            _pad(np.concatenate([t for _, t, _, _ in staged]), n_rows, K.NEUTRAL_T),
            _pad(np.concatenate([n_ for _, _, n_, _ in staged]), n_rows, K.NEUTRAL_T),
            np.zeros(n_rows, dtype=_I64),
            _pad(store.keys.rv_t[trows], n_slots, 0),
            _pad(store.keys.rv_node[trows], n_slots, 0),
            np.zeros(n_slots, dtype=_I64),
            n_slots)
        t, node, _dt, win_row = (a[: len(trows)] for a in self._device_get(out))
        store.keys.rv_t[trows] = t
        store.keys.rv_node[trows] = node
        reg_val = store.reg_val
        for di in np.nonzero(win_row >= 0)[0]:
            reg_val[int(trows[di])] = vals[int(win_row[di])]

    # ------------------------------------------------------------- counters

    def _stage_counter_rows(self, store: KeySpace, resolved, st):
        """STAGE (host-only for OTHER planes; appends missing slot rows to
        the cnt plane itself via _resolve_cnt_rows): columnarize + combine
        counter slot writes."""
        n0 = store.cnt.n
        staged = []  # (rows, total, uuid, base, base_t)
        for b, kid_of in resolved:
            if not len(b.cnt_ki):
                continue
            kid_arr = kid_of[b.cnt_ki]
            keep = np.nonzero(kid_arr >= 0)[0]
            if not len(keep):
                continue
            st.counter_rows += len(keep)
            # slice(None) when every row was kept: views, not copies
            sel = slice(None) if len(keep) == len(kid_arr) else keep
            rows = self._resolve_cnt_rows(store, kid_arr[sel],
                                          b.cnt_node[sel])
            staged.append((rows, b.cnt_val[sel], b.cnt_uuid[sel],
                           b.cnt_base[sel], b.cnt_base_t[sel]))
        if not staged:
            return None
        def _fold_cnt(st_):
            # both (value @ time) pairs fold independently on host
            f_uuid, f_val, _ = _lex_fold([s[2] for s in st_],
                                         [s[1] for s in st_])
            f_bt, f_base, _ = _lex_fold([s[4] for s in st_],
                                        [s[3] for s in st_])
            return (st_[0][0], f_val, f_uuid, f_base, f_bt)

        # disjoint is the common catch-up shape here: R replicas each carry
        # their own node's slots
        staged, folds = self._combine_groups(
            staged, _fold_cnt,
            lambda st_, cat: (cat,) + tuple(
                np.concatenate([s[i] for s in st_]) for i in range(1, 5)))
        plan = {"staged": staged, "folds": folds, "n0": n0}
        # placement decision + fold-stack builds, staged (STAGE-PURE).
        # store.cnt.n is stable from here: only this family's stage
        # appends counter rows, and its dispatch runs strictly after.
        total = sum(len(r) for r, *_ in staged)
        n = store.cnt.n
        base, size, all_new = self._bulk_region([r for r, *_ in staged],
                                                n0, n)
        plan.update(n=n, base=base, size=size, all_new=all_new,
                    use_bulk=self._use_bulk(total, size), fold=False)
        if plan["use_bulk"] and not (self.resident and self._host_combine()):
            plan["fold"] = self._fold_on and self._aligned(staged)
            if plan["fold"]:
                np_ = K.next_pow2(max(len(staged[0][0]), 1))
                plan["v_s"] = self._stacked(staged, 1, 0, np_)
                plan["u_s"] = self._stacked(staged, 2, K.NEUTRAL_T, np_)
                plan["b_s"] = self._stacked(staged, 3, 0, np_)
                plan["bt_s"] = self._stacked(staged, 4, K.NEUTRAL_T, np_)
        return plan

    def _dispatch_counter_rows(self, store: KeySpace, plan, st) -> None:
        if plan is None:
            return
        staged = plan["staged"]
        self.folds += plan["folds"]
        n, base = plan["n"], plan["base"]
        size, all_new = plan["size"], plan["all_new"]

        if plan["use_bulk"]:
            if self.resident:
                cols, sp = self._resident_state(store, "cnt", n)
                val, uuid = cols["val"], cols["uuid"]
                cb, cbt = cols["base"], cols["base_t"]
                base = 0
            else:
                sp = self._sp_size(size)
                val = self._state_up(store.cnt.val, base, size, sp, 0, all_new)
                uuid = self._state_up(store.cnt.uuid, base, size, sp,
                                      K.NEUTRAL_T, all_new)
                cb = self._state_up(store.cnt.base, base, size, sp, 0, all_new)
                cbt = self._state_up(store.cnt.base_t, base, size, sp,
                                     K.NEUTRAL_T, all_new)
            if self.resident and self._host_combine():
                # deferred win resolution (see _dispatch_registers): winners
                # land in the src plane, and at flush the val/uuid pair —
                # the two widest counter columns — reconstructs from the
                # host pool instead of downloading.  The (rare) base pair
                # keeps its own on-device winner and downloads when written.
                src = self._src_state("cnt", sp)
                written = {"val", "uuid"}
                for r, v, u, bb, bt in staged:
                    pb = self._pool_add(None, val=v, uuid=u)
                    if (bt == K.NEUTRAL_T).all():
                        # neutral base plane (no counter deletes anywhere in
                        # the batch, the common case): skip uploading it
                        val, uuid, src = self._bulk_src_call(
                            B.bulk_counters_vu_src,
                            B.bulk_counters_vu_src_iota, (val, uuid, src),
                            r, base, sp, [self._i32_up(v, 0),
                                          (u, K.NEUTRAL_T)], pb)
                    else:
                        idx, dv, du, dbb, dbt = self._upload_batch(
                            r, base, sp, [(v, 0), (u, K.NEUTRAL_T), (bb, 0),
                                          (bt, K.NEUTRAL_T)])
                        val, uuid, cb, cbt, src = B.bulk_counters_src(
                            val, uuid, cb, cbt, src, idx, dv, du, dbb, dbt,
                            pb)
                        written |= {"base", "base_t"}
                self._family_done("cnt", {"val": val, "uuid": uuid,
                                          "base": cb, "base_t": cbt}, n, sp,
                                  src=src, written=written,
                                  recon={"val": "val", "uuid": "uuid"})
                return
            if plan["fold"]:
                # aligned counter rows (same (key, node) slots per batch —
                # repeated syncs from one origin): fold both (value @ time)
                # pairs on-device (stacks pre-built by the stage twin),
                # scatter once
                rows0, _nA, np_, idx = self._fold_prep(staged, base, sp)
                fv, fu = self._fold_pair(plan["v_s"], plan["u_s"])
                fb, fbt = self._fold_pair(plan["b_s"], plan["bt_s"])
                val, uuid, cb, cbt = B.bulk_counters(val, uuid, cb, cbt,
                                                     idx, fv, fu, fb, fbt)
            else:
                dev = []  # [(uploaded arrays, with_base)]
                for r, v, u, bb, bt in staged:
                    if self.resident and (bt == K.NEUTRAL_T).all():
                        dev.append((self._upload_batch(
                            r, base, sp, [(v, 0), (u, K.NEUTRAL_T)]), False))
                    else:
                        dev.append((self._upload_batch(
                            r, base, sp, [(v, 0), (u, K.NEUTRAL_T), (bb, 0),
                                          (bt, K.NEUTRAL_T)]), True))
                for up, with_base in dev:
                    if with_base:
                        idx, v, u, bb, bt = up
                        val, uuid, cb, cbt = B.bulk_counters(
                            val, uuid, cb, cbt, idx, v, u, bb, bt)
                    else:
                        idx, v, u = up
                        val, uuid = B.bulk_counters_vu(val, uuid, idx, v, u)
            if self.resident:
                self._family_done("cnt", {"val": val, "uuid": uuid,
                                          "base": cb, "base_t": cbt}, n, sp)
                return
            store.cnt.val[base:n] = self._plane_get(val, size)
            store.cnt.uuid[base:n] = self._plane_get(uuid, size)
            store.cnt.base[base:n] = self._plane_get(cb, size)
            store.cnt.base_t[base:n] = self._plane_get(cbt, size)
            return  # sums re-derived in one pass by merge_many

        self._drop_family(store, "cnt")
        all_rows = np.concatenate([s[0] for s in staged])
        trows, slot_idx = np.unique(all_rows, return_inverse=True)
        n_slots = K.next_pow2(len(trows) + 1)
        n_rows = K.next_pow2(len(all_rows))
        slot_ids = _pad(slot_idx.astype(_I64), n_rows, n_slots - 1)
        for vcol, tcol, vi, ti in (("val", "uuid", 1, 2),
                                   ("base", "base_t", 3, 4)):
            out = K.merge_counters(
                slot_ids,
                _pad(np.concatenate([s[vi] for s in staged]), n_rows, 0),
                _pad(np.concatenate([s[ti] for s in staged]), n_rows, K.NEUTRAL_T),
                _pad(store.cnt.col(vcol)[trows], n_slots, 0),
                _pad(store.cnt.col(tcol)[trows], n_slots, K.NEUTRAL_T),
                n_slots)
            new_val, new_t = (a[: len(trows)] for a in self._device_get(out))
            store.cnt.col(vcol)[trows] = new_val
            store.cnt.col(tcol)[trows] = new_t
        if self.resident:
            # merge_many's sum pass is skipped while other families hold
            # unflushed device state — this path already wrote the host
            store.recompute_counter_sums()
        # else: sums re-derived in one pass by merge_many

    def _resolve_cnt_rows(self, store: KeySpace, kids: np.ndarray,
                          nodes: np.ndarray) -> np.ndarray:
        """(kid, node) pairs -> store cnt rows via the per-rank direct
        index (KeySpace.cnt_rows_lookup — dense window or sparse hash,
        the keyspace picks): one vectorized lookup per distinct origin
        node — replica batches carry one or few — with missing slots
        bulk-created as neutral (val=0, t=NEUTRAL_T)."""
        out = np.empty(len(kids), dtype=_I64)
        if not len(kids):
            return out
        # replica batches stage ONE origin node: a single memory-bound
        # equality pass beats np.unique's sort
        first = int(nodes[0])
        if (nodes == first).all():
            groups = [(first, slice(None))]
        else:
            uniq_nodes, inv = np.unique(nodes, return_inverse=True)
            groups = [(int(nd), np.nonzero(inv == i)[0])
                      for i, nd in enumerate(uniq_nodes.tolist())]
        for node, sel in groups:
            k = kids[sel]
            got = store.cnt_rows_lookup(store.rank_of(node), k)
            miss = got < 0
            if miss.any():
                # a raw op-stream batch may repeat a (kid, node): one row
                # per unique missing kid
                mk = k[miss]
                uk = np.unique(mk)
                new_rows = store.cnt.append_block(
                    len(uk), kid=uk, node=node, val=0,
                    uuid=K.NEUTRAL_T, base=0, base_t=K.NEUTRAL_T)
                store.cnt_rows_assign(store.rank_of(node), uk, new_rows)
                # uk is sorted-unique and aligned with new_rows: map each
                # missing kid to its row without a second index probe
                got[miss] = new_rows[np.searchsorted(uk, mk)]
            out[sel] = got
        return out

    # ------------------------------------------------------------- elements

    def _stage_elem_rows(self, store: KeySpace, resolved, st):
        """STAGE (appends missing element rows to the el plane; all other
        work is host prep): resolve (kid, member) combos to rows,
        columnarize, group-combine.  Valueless batches (the set-member
        catch-up shape) stage `vals=None` — no [None] * n list is ever
        materialized or concatenated for them."""
        n0 = store.el.n
        staged = []  # (rows, at, an, dt, vals-or-None, has_vals)
        # replica snapshots of one keyspace share el_ki/el_member list
        # OBJECTS (and, via the caller's key memo, the kid_of array), so
        # their (kid, member) combos resolve to the same rows — resolve
        # each distinct shape once instead of once per replica (the
        # interning + slot resolution was the top dispatch cost for
        # field-heavy workloads)
        row_memo: dict = {}
        for b, kid_of in resolved:
            if not len(b.el_ki):
                continue
            mk = (b.el_shape if b.el_shape is not None
                  else ("id", id(b.el_ki), id(b.el_member)), id(kid_of))
            cached = row_memo.get(mk)
            if cached is not None:
                rows, keep, all_kept = cached
                if rows is None:
                    continue  # nothing kept for this shape
                st.elem_rows += len(keep)
            else:
                kid_arr = kid_of[b.el_ki]
                keep = np.nonzero(kid_arr >= 0)[0]
                if not len(keep):
                    row_memo[mk] = (None, None, False)
                    continue
                st.elem_rows += len(keep)
                all_kept = len(keep) == len(b.el_ki)
                members = b.el_member if all_kept \
                    else list(map(b.el_member.__getitem__, keep.tolist()))
                # two native batch calls: intern members, then
                # resolve/create (kid, member) combo slots — no per-row
                # Python
                mids, _ = store.member_index.get_or_insert_batch(members)
                combos = (kid_arr[keep] << KeySpace.MEMBER_BITS) | mids
                rn0 = store.el.n
                rows, n_new = store.el_index.get_or_assign_batch(
                    combos, next_val=rn0)
                if n_new:
                    created = np.nonzero(rows >= rn0)[0]
                    uniq_rows, first = np.unique(rows[created],
                                                 return_index=True)
                    pos = created[first]
                    # combo-index ids must be exactly the next el block —
                    # checked BEFORE append_block mutates the plane
                    # (CHECK-THEN-MUTATE; real raise, python -O safe)
                    if len(uniq_rows) != n_new or \
                            int(uniq_rows[0]) != rn0 or \
                            int(uniq_rows[-1]) != rn0 + n_new - 1:
                        span = f"[{int(uniq_rows[0])}, " \
                            f"{int(uniq_rows[-1])}]" \
                            if len(uniq_rows) else "[]"
                        raise RuntimeError(
                            f"el combo index issued non-contiguous rows "
                            f"{span} (n={len(uniq_rows)}) for block "
                            f"[{rn0}, {rn0 + n_new - 1}]")
                    store.el.append_block(
                        n_new, kid=kid_arr[keep][pos],
                        add_t=0, add_node=0, del_t=0)
                    store.el_member.extend(
                        map(members.__getitem__, pos.tolist()))
                    store.el_val.extend([None] * n_new)
                row_memo[mk] = (rows, keep, all_kept)
            # has-values: an inherited False hint is exact (any subset of
            # an all-None list is all None) and skips both the scan AND
            # the value-list build; anything else re-scans locally so a
            # lone dict value in the parent cannot push every all-None
            # sibling chunk down the value path.
            if b.el_has_vals is False:
                vals, hv = None, False
            else:
                vals = b.el_val if all_kept \
                    else list(map(b.el_val.__getitem__, keep.tolist()))
                hv = has_values(vals)
                if not hv:
                    vals = None
            esel = slice(None) if all_kept else keep
            staged.append((rows, b.el_add_t[esel], b.el_add_node[esel],
                           b.el_del_t[esel], vals, hv))
        if not staged:
            return None
        def _fold_el(st_):
            f_at, f_an, wb = _lex_fold([s[1] for s in st_],
                                       [s[2] for s in st_])
            f_dt = np.maximum.reduce([s[3] for s in st_])
            hv = any(s[5] for s in st_)
            vals = list(_sel_obj([s[4] for s in st_], wb)) if hv else None
            return (st_[0][0], f_at, f_an, f_dt, vals, hv)

        def _cat_el(st_, cat):
            hv = any(s[5] for s in st_)
            if hv:
                vals_cat: list = []
                for s in st_:
                    vals_cat.extend(s[4] if s[4] is not None
                                    else [None] * len(s[0]))
            else:
                vals_cat = None
            return (cat,
                    np.concatenate([s[1] for s in st_]),
                    np.concatenate([s[2] for s in st_]),
                    np.concatenate([s[3] for s in st_]),
                    vals_cat, hv)

        staged, folds = self._combine_groups(staged, _fold_el, _cat_el)
        plan = {"staged": staged, "folds": folds, "n0": n0,
                "el_epoch": store.el_compact_epoch}
        # placement decision + fold-stack builds, staged (STAGE-PURE).
        # store.el.n is stable from here: only this stage appends element
        # rows, and its dispatch runs strictly after.
        total = sum(len(r) for r, *_ in staged)
        n = store.el.n
        base, size, all_new = self._bulk_region([r for r, *_ in staged],
                                                n0, n)
        plan.update(n=n, base=base, size=size, all_new=all_new,
                    use_bulk=self._use_bulk(total, size), fold=False)
        if plan["use_bulk"] and not (self.resident and self._host_combine()):
            plan["fold"] = self._fold_on and self._aligned(staged)
            if plan["fold"]:
                np_ = K.next_pow2(max(len(staged[0][0]), 1))
                plan["a_s"] = self._stacked(staged, 1, K.NEUTRAL_T, np_)
                plan["x_s"] = self._stacked(staged, 2, K.NEUTRAL_T, np_)
                plan["d_s"] = self._stacked(staged, 3, 0, np_)
        return plan

    def _dispatch_elem_rows(self, store: KeySpace, plan, st) -> None:
        if plan is None:
            return
        # staged element ROW INDICES are only valid while row ids are
        # stable; _compact_elements re-identifies every row and bumps the
        # epoch.  The single-writer discipline means this can never fire in
        # correct usage — if it does, scattering would alias rows, so fail
        # loudly before touching any column.
        if plan["el_epoch"] != store.el_compact_epoch:
            raise RuntimeError(
                "element rows were compacted between stage and dispatch "
                "(row-id stability broken: staged indices are stale)")
        staged = plan["staged"]
        self.folds += plan["folds"]
        n, base = plan["n"], plan["base"]
        size, all_new = plan["size"], plan["all_new"]

        if plan["use_bulk"]:
            if self.resident:
                cols, sp = self._resident_state(store, "el", n)
                at, an, dt = cols["add_t"], cols["add_node"], cols["del_t"]
                base, size = 0, n
                old_dt = None  # garbage enqueue deferred to flush
                if self._host_combine():
                    # deferred win resolution (see _dispatch_registers): the
                    # src plane is ALWAYS tracked — at flush it costs one
                    # int32 download and replaces the add_t + add_node
                    # int64 downloads (4 bytes/slot vs 16) while also
                    # resolving dict win values.
                    #
                    # The DEL side never touches the device here: the add
                    # kernels don't read del_t for win decisions, and
                    # del-merge is a plain max — applied straight to the
                    # host column (rows are unique per staged entry, so
                    # gather-max-scatter is collision-free).  Zero del
                    # bytes cross the link in either direction; newly-dead
                    # rows are queued for GC at flush (after add_t
                    # reconstruction) via _el_del_touched.
                    src = self._src_state("el", sp)
                    host_dt = store.el.del_t
                    for rows_, a_, x_, d_, vals, _hv in staged:
                        x_arr = np.asarray(x_)
                        x_up = self._i32_up(x_arr, K.NEUTRAL_T)
                        pb = self._pool_add(vals, add_t=a_, add_node=x_arr)
                        at, an, src = self._bulk_src_call(
                            B.bulk_elems_src_nodt, B.bulk_elems_src_nodt_iota,
                            (at, an, src), rows_, base, sp,
                            [(a_, K.NEUTRAL_T), x_up], pb)
                        d_arr = np.asarray(d_)
                        nz = np.flatnonzero(d_arr)
                        if len(nz):
                            sel = np.asarray(rows_)[nz]
                            cur = host_dt[sel]
                            dv = d_arr[nz]
                            adv = dv > cur
                            if adv.any():
                                host_dt[sel[adv]] = dv[adv]
                                self._el_del_touched.append(sel[adv])
                                # host-only: the device del_t lags on
                                # these rows until a patch carries them
                                store.journal["el"].add_rows(sel[adv])
                    self._family_done("el", {"add_t": at, "add_node": an,
                                             "del_t": dt}, n, sp, src=src,
                                      written={"add_t", "add_node"},
                                      recon={"add_t": "add_t",
                                             "add_node": "add_node"})
                    return
            else:
                sp = self._sp_size(size)
                old_dt = (np.zeros(size, dtype=_I64) if all_new
                          else store.el.del_t[base:n].copy())
                at = self._state_up(store.el.add_t, base, size, sp, 0, all_new)
                an = self._state_up(store.el.add_node, base, size, sp, 0,
                                    all_new)
                dt = self._state_up(store.el.del_t, base, size, sp, 0, all_new)
            fold = plan["fold"]
            if fold:
                rows0, nA, np_, idx = self._fold_prep(staged, base, sp)
                fa, fx, fd, winb = self._fold_lex(plan["a_s"], plan["x_s"],
                                                  plan["d_s"])
                at, an, dt, win = B.bulk_elems(at, an, dt, idx, fa, fx, fd)
                wins = [win]
            else:
                dev = [self._upload_batch(
                    r, base, sp, [(a, K.NEUTRAL_T), (x, K.NEUTRAL_T), (d, 0)])
                    for r, a, x, d, _, _ in staged]
                wins = []
                for idx, a, x, d in dev:
                    at, an, dt, win = B.bulk_elems(at, an, dt, idx, a, x, d)
                    wins.append(win)
            if self.resident:
                self._family_done("el", {"add_t": at, "add_node": an,
                                         "del_t": dt}, n, sp)
            else:
                m_at = self._plane_get(at, size)
                m_dt = self._plane_get(dt, size)
                store.el.add_t[base:n] = m_at
                store.el.add_node[base:n] = self._plane_get(an, size)
                store.el.del_t[base:n] = m_dt
                self._enqueue_elem_garbage(store, np.arange(base, n), m_at,
                                           m_dt, old_dt)
            el_val = store.el_val
            el_kid = store.el.kid
            enc = store.keys.enc
            if fold:
                # CPU parity: the winning row's value — None included —
                # replaces the slot's.  Values live only on dict kids, so
                # the Python loop is vectorized down to dict rows; set rows
                # are None-over-None no-ops.
                winb_h = np.asarray(winb)
                cand = np.asarray(wins[0])[:nA] & \
                    np.isin(enc[el_kid[rows0]], S.VALUE_ENCS)
                for j in np.nonzero(cand)[0]:
                    sv = staged[int(winb_h[j])][4]
                    el_val[int(rows0[j])] = None if sv is None \
                        else sv[int(j)]
                return
            for (pos, _, _, _, vals, has_vals), win in zip(staged, wins):
                win_arr = np.asarray(win)[: len(pos)]
                if has_vals:
                    for j in np.nonzero(win_arr)[0]:
                        el_val[int(pos[j])] = vals[int(j)]
                else:
                    # valueless batch: winning None adds must still CLEAR
                    # stored values (CPU parity); set rows need no touch
                    cand = win_arr & np.isin(enc[el_kid[pos]], S.VALUE_ENCS)
                    for j in np.nonzero(cand)[0]:
                        el_val[int(pos[j])] = None
            return

        self._drop_family(store, "el")
        all_rows = np.concatenate([r for r, *_ in staged])
        vals_flat: list = []
        for r, _, _, _, v, _ in staged:
            vals_flat.extend(v if v is not None else [None] * len(r))
        trows, slot_idx = np.unique(all_rows, return_inverse=True)
        cur_dt = store.el.del_t[trows].copy()
        n_slots = K.next_pow2(len(trows) + 1)
        n_rows = K.next_pow2(len(all_rows))
        out = K.merge_elems(
            _pad(slot_idx.astype(_I64), n_rows, n_slots - 1),
            _pad(np.concatenate([a for _, a, *_ in staged]), n_rows, K.NEUTRAL_T),
            _pad(np.concatenate([x for _, _, x, *_ in staged]), n_rows, K.NEUTRAL_T),
            _pad(np.concatenate([d for _, _, _, d, _, _ in staged]), n_rows, 0),
            _pad(store.el.add_t[trows], n_slots, 0),
            _pad(store.el.add_node[trows], n_slots, 0),
            _pad(cur_dt, n_slots, 0),
            n_slots)
        kk = len(trows)
        m_at, m_an, m_dt, win_row = (a[:kk] for a in self._device_get(out))
        store.el.add_t[trows] = m_at
        store.el.add_node[trows] = m_an
        store.el.del_t[trows] = m_dt
        el_val = store.el_val
        for di in np.nonzero(win_row >= 0)[0]:
            el_val[int(trows[di])] = vals_flat[int(win_row[di])]
        self._enqueue_elem_garbage(store, trows, m_at, m_dt, cur_dt)

    @staticmethod
    def _enqueue_elem_garbage(store: KeySpace, rows, at, dt, old_dt) -> None:
        """Queue tombstones whose del_t advanced (dead rows need GC once the
        cluster horizon passes).  Bulk path: one heapify, not n pushes —
        a snapshot-merge flush queues millions."""
        newly = np.nonzero((at < dt) & (dt > old_dt))[0]
        if not len(newly):
            return
        rws = np.asarray(rows)[newly]
        kids = store.el.kid[rws].tolist()
        store.enqueue_garbage_bulk(
            np.asarray(dt)[newly].tolist(),
            list(map(store.key_bytes.__getitem__, kids)),
            list(map(store.el_member.__getitem__, rws.tolist())))


class ShardDispatcher:
    """Thin shard-aware dispatcher: one resident engine per hash shard,
    all sharing THIS process's device queue.

    The sharded keyspace (store/sharded_keyspace.py) partitions keys into
    independent stores; each shard gets its own engine so per-shard
    resident mirrors, win pools, and staging pipelines never interact.
    Dispatching shard s+1's merge while shard s's device kernels are
    still in flight interleaves their batches on the same queue — JAX
    dispatch is async, so the host moves on to the next shard's staging
    while the device drains the previous one's scatters.  Semantics need
    no care beyond that: shards share no rows, so any interleaving is
    equivalent to any other.
    """

    def __init__(self, n_shards: int, engine_factory=None) -> None:
        if engine_factory is None:
            engine_factory = lambda: TpuMergeEngine(resident=True)  # noqa: E731
        self.engines = [engine_factory() for _ in range(n_shards)]

    def merge_shard(self, shard: int, store: KeySpace,
                    batches: list) -> MergeStats:
        return self.engines[shard].merge_many(store, batches)

    def flush_all(self, stores: list) -> None:
        for eng, store in zip(self.engines, stores):
            if getattr(eng, "needs_flush", False):
                eng.flush(store)

    @property
    def needs_flush(self) -> bool:
        return any(getattr(e, "needs_flush", False) for e in self.engines)

    def discard_resident(self) -> None:
        for e in self.engines:
            if hasattr(e, "discard_resident"):
                e.discard_resident()

    def close(self) -> None:
        for e in self.engines:
            if hasattr(e, "close"):
                e.close()
