"""No program a served window runs passes a whole resident plane through
the 64-bit emulation (docs/INVARIANTS.md, PLANE-PAIR).

The TPU has no 64-bit integer unit: XLA splits every s64 array into two
u32 halves at a program's entry (`X64SplitLow/High`) and recombines it at
the exit (`X64Combine`) — whole-plane passes of 134 MB each at the
benchmark's 16,777,216-row planes, whatever the batch holds.  The planes
are (hi int32, lo uint32) pairs (ops/bulk.py `Plane`) so that this never
happens; two guards, neither needs a chip:

  * compiled for a DESCRIBED v5e (nothing runs): no `X64*` custom call on
    a plane-length operand and under 1 MB of temporaries (the 65,536-row
    patch bucket: under twice its own 1.8 MB upload);
  * backend-free: the lowered programs carry no 64-bit array of the
    plane's length at all.

The topology is described inside a module-scoped fixture, never at
import: every xdist worker imports this file, and only the one that runs
it may load the TPU's library.  Keep every such compile in THIS file.
"""

import os
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from constdb_tpu.engine.tpu import TpuMergeEngine
from constdb_tpu.ops import bulk as B

CAP = 1 << 24                       # the benchmark's el planes
BP = TpuMergeEngine.MICRO_SCATTER_PAD
GP = TpuMergeEngine.FLUSH_GATHER_PAD


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def lowered(name: str, sharding=None):
    """The named program lowered at the window's shapes: planes CAP,
    micro batches BP, gathers GP, patches by bucket."""
    def sds(shape, dt):
        return jax.ShapeDtypeStruct(shape, dt, sharding=sharding)

    def plane(cols=0):
        shape = (CAP, cols) if cols else (CAP,)
        return B.Plane(sds(shape, jnp.int32), sds(shape, jnp.uint32))

    def b64(n=BP, cols=0):
        return sds((n, cols) if cols else (n,), jnp.int64)

    idx, src, i32 = sds((BP,), jnp.int32), sds((CAP,), jnp.int32), \
        sds((), jnp.int32)
    if name == "bulk_lww_win":
        # the donated planes and ONE int32 block (idx + both columns'
        # halves); `win` is its only output that is not a plane
        return B.bulk_lww_win.lower(plane(), plane(),
                                    sds((5, BP), jnp.int32))
    if name.startswith("mirror_patch_"):
        fam, bp = name.removeprefix("mirror_patch_").split("@")
        nc = {"reg": 2, "cnt": 4, "el": 3}[fam]
        return B.MIRROR_PATCH[fam].lower(
            tuple(plane() for _ in range(nc)), sds((int(bp),), jnp.int32),
            b64(int(bp), nc))
    if name in ("bulk_lww_src_iota", "bulk_counters_vu_src_iota"):
        n = 1 << 16                 # a boot-restore chunk
        return getattr(B, name).lower(plane(), plane(), src, i32, i32,
                                      b64(n), b64(n), i32, np_=n)
    args = {
        "bulk_lww_src": (plane(), plane(), src, idx, b64(), b64(), i32),
        "bulk_lww": (plane(), plane(), idx, b64(), b64()),
        "bulk_max1": (plane(), idx, b64()),
        "bulk_max": (plane(4), idx, b64(cols=4)),
        "bulk_counters_vu": (plane(), plane(), idx, b64(), b64()),
        "bulk_counters": (plane(),) * 4 + (idx,) + (b64(),) * 4,
        "bulk_counters_vu_src": (plane(), plane(), src, idx, b64(), b64(),
                                 i32),
        "bulk_counters_src": (plane(),) * 4 + (src, idx) + (b64(),) * 4
        + (i32,),
        "bulk_elems": (plane(),) * 3 + (idx,) + (b64(),) * 3,
        "gather_rows": (plane(), sds((GP,), jnp.int32)),
    }
    if name == "device_full":
        return B.device_full.lower(n=CAP, fill=B.NEUTRAL_T)
    return getattr(B, name).lower(*args[name])


# what a served window launches: the micro round's scatter, which returns
# its win vector (every pair of every family), the element del side, a
# stale mirror's patch at each bucket.  Since PR 38 no served window
# launches `bulk_lww_src` or `gather_rows` (a micro round tracks `src` only
# on a family that still carries a whole-plane round's, and only then does
# the flush gather), nor `bulk_lww` on the micro path: they are FALLBACK,
# which a window after a catch-up may still reach — compiled for the chip
# and lowered like the window's own.
WINDOW = ["bulk_lww_win", "bulk_max1",
          *(f"mirror_patch_el@{bp}"
            for bp in TpuMergeEngine.MIRROR_PATCH_BUCKETS)]
# ... and the rest of ops/bulk.py's state programs (boot restore, forced
# folds, the other families' patches).
# `plane_rows` and `plane_diff` are the two that JOIN a plane on purpose,
# once a whole-plane flush.
FALLBACK = ["bulk_lww_src", "bulk_lww", "gather_rows"]
EVERY = WINDOW + FALLBACK + ["bulk_max", "bulk_counters_vu", "bulk_counters",
                  "bulk_counters_vu_src", "bulk_counters_src", "bulk_elems",
                  "bulk_lww_src_iota", "bulk_counters_vu_src_iota",
                  "device_full", "mirror_patch_reg@1024",
                  "mirror_patch_cnt@1024"]


@pytest.mark.parametrize("name", WINDOW + FALLBACK)
def test_compiled_for_v5e_no_x64_pass_over_a_plane(one_chip, name):
    compiled = lowered(name, one_chip).compile()
    text = compiled.as_text()
    x64 = [ln.strip() for ln in text.splitlines()
           if re.search(r"X64(Split|Combine)", ln)]
    on_plane = [ln[:200] for ln in x64 if str(CAP) in ln]
    assert not on_plane, f"{name}: 64-bit emulation over a plane: {on_plane}"
    assert not re.search(r"[su]64\[%d[,\]]" % CAP, text), \
        f"{name}: a plane-length 64-bit array in the compiled program"
    # temporaries are of the batch's size, never the plane's (134 MB):
    # under 1 MB, or for the largest patch bucket twice its own upload
    limit = 1 << 20
    if name.startswith("mirror_patch_"):
        limit = max(limit, 2 * int(name.split("@")[1]) * (4 + 8 * 3))
    temp = compiled.memory_analysis().temp_size_in_bytes
    assert temp < limit, f"{name}: {temp} B of temporaries"


@pytest.mark.parametrize("name", EVERY)
def test_lowered_program_holds_no_plane_length_64_bit_array(name):
    text = lowered(name).as_text()
    assert f"tensor<{CAP}x" in text, "the plane is not in the program"
    wide = re.findall(r"tensor<%d(?:x\d+)*x(?:[su]?i|f)64>" % CAP, text)
    assert not wide, f"{name}: {sorted(set(wide))}"


def test_the_micro_rounds_program_returns_two_planes_and_the_win_flags():
    out = jax.tree.leaves(lowered("bulk_lww_win").out_info)
    assert [(o.shape, o.dtype.name) for o in out] == \
        [((CAP,), "int32"), ((CAP,), "uint32")] * 2 + [((BP,), "bool")]
