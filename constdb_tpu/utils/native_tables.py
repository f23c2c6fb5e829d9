"""Staging index tables: native C++, three binding tiers.

The merge hot path resolves millions of (bytes -> id) and (int64 -> int64)
probes per batch; native/tables.cpp does them in C with BATCH entry points
so Python crosses the FFI boundary once per column, not once per row.

Binding tiers, best available wins:
  1. CPython extension (native/pyext.cpp, `cst_ext`) — walks bytes lists
     directly in C, no Python-side blob packing at all;
  2. ctypes over libconstdb_native.so — caller packs a blob + offsets;
  3. pure Python dicts — keeps everything working on a fresh checkout
     before `make -C native`, at dict speed.

API shape is numpy-first: batch methods take/return int64 arrays.
"""

from __future__ import annotations

import ctypes
import importlib.util
import os
from typing import Optional

import numpy as np

_I64 = np.int64

_lib = None
_ext = None


def nonnull_mask(items: list):
    """Bool ndarray marking entries that are not None — C-speed when the
    extension is built (the per-row generator over multi-million-row
    value columns is a top merge-dispatch cost), pure-Python otherwise."""
    import numpy as np
    ext = load_ext()
    # exact-list gate mirrors the C side's PyList_CheckExact: other
    # sized iterables must take the same (pure) path on BOTH tiers
    if type(items) is list and ext is not None and \
            hasattr(ext, "nonnull_mask"):
        return np.frombuffer(ext.nonnull_mask(items), dtype=bool)
    return np.fromiter((v is not None for v in items), dtype=bool,
                       count=len(items))


_ABI_STAMP_CACHE: list = []


def expected_abi_stamp() -> Optional[str]:
    """sha256 over the sorted native/*.cpp sources — the same hash the
    Makefile compiles into cst_ext.so as CST_ABI_STAMP (native/Makefile
    $(STAMP) rule: `cat $(sort $(wildcard *.cpp)) | sha256sum`).  The
    extension and serve.py share frozen row layouts (opcode numbering,
    payload shapes); a .so built from different sources could emit rows
    the Python side misreads, so load_ext compares this against the
    module's own abi_stamp() and refuses a mismatch.  None when the
    source tree is absent (artifact-only deployments have nothing to
    compare against — the shipped .so is trusted as-is)."""
    if not _ABI_STAMP_CACHE:
        here = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
        src = os.path.join(os.path.dirname(here), "native")
        try:
            names = sorted(n for n in os.listdir(src) if n.endswith(".cpp"))
        except OSError:
            names = []
        if not names:
            _ABI_STAMP_CACHE.append(None)
        else:
            import hashlib
            h = hashlib.sha256()
            for n in names:
                with open(os.path.join(src, n), "rb") as f:
                    h.update(f.read())
            _ABI_STAMP_CACHE.append(h.hexdigest())
    return _ABI_STAMP_CACHE[0]


def load_ext():
    """The CPython extension module, or None.  CONSTDB_NO_NATIVE=1 forces
    the pure-Python tiers (A/B floor measurement — opbench.py).  A .so
    whose compiled-in ABI stamp does not match the native/*.cpp sources
    on disk is refused LOUDLY (stale build: its row layouts may disagree
    with what serve.py expects) — rebuild with `make -C native`
    (`build_native` below does, for bench.py, the tests and the chip
    smoke)."""
    global _ext
    from ..conf import env_str
    if env_str("CONSTDB_NO_NATIVE"):
        return None
    if _ext is not None:
        return _ext or None
    here = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    for cand in (
        os.path.join(here, "_native", "cst_ext.so"),
        os.path.join(os.path.dirname(here), "native", "build", "cst_ext.so"),
    ):
        if os.path.exists(cand):
            try:
                spec = importlib.util.spec_from_file_location("cst_ext", cand)
                mod = importlib.util.module_from_spec(spec)
                spec.loader.exec_module(mod)
            except (ImportError, OSError):
                continue
            want = expected_abi_stamp()
            got = getattr(mod, "abi_stamp", lambda: "")()
            if want is not None and got != want:
                import logging
                logging.getLogger("constdb.native").warning(
                    "stale cst_ext.so at %s (abi stamp %s != sources %s): "
                    "refusing to load it — rebuild with `make -C native`",
                    cand, (got or "<unstamped>")[:12], want[:12])
                continue
            _ext = mod
            return mod
    _ext = False
    return None


def reload_tiers() -> bool:
    """Forget the (possibly negative) loader caches and retry — the public
    hook for callers that build the native artifacts at runtime
    (build_native).  Returns True when the CPython extension loads."""
    global _ext, _lib
    _ext = None
    _lib = None
    _ABI_STAMP_CACHE.clear()
    return load_ext() is not None


def build_native(timeout: float = 600.0) -> None:
    """Build the native artifacts from the tracked sources (`make -C
    native`; the .so files are gitignored) unless the extension already
    loads, then reload the tiers.  Raises RuntimeError when the build
    fails or its product does not load — callers decide whether that
    is fatal (chip_smoke.py) or a loud degrade to the pure tiers
    (bench.py ensure_native, tests/conftest.py)."""
    if load_ext() is not None:
        return
    import subprocess
    import sys
    here = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    src = os.path.join(os.path.dirname(here), "native")
    try:
        r = subprocess.run(["make", "-C", src, f"PYTHON={sys.executable}"],
                           capture_output=True, timeout=timeout, text=True)
    except (OSError, subprocess.TimeoutExpired) as e:
        raise RuntimeError(f"native build did not run: {e}") from e
    if r.returncode != 0:
        raise RuntimeError(f"native build failed rc={r.returncode}:\n"
                           f"{(r.stderr or r.stdout)[-2000:]}")
    if not reload_tiers():
        raise RuntimeError("native build succeeded but cst_ext.so does "
                           "not load (see the constdb.native log)")


def load_native() -> Optional[ctypes.CDLL]:
    global _lib
    if _lib is not None:
        return _lib or None
    here = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    for cand in (
        os.path.join(here, "_native", "libconstdb_native.so"),
        os.path.join(os.path.dirname(here), "native", "build",
                     "libconstdb_native.so"),
    ):
        if os.path.exists(cand):
            try:
                lib = ctypes.CDLL(cand)
                _bind(lib)
                _lib = lib
                return lib
            except (OSError, AttributeError):
                continue
    _lib = False
    return None


def _bind(lib: ctypes.CDLL) -> None:
    c = ctypes
    P8 = c.POINTER(c.c_uint8)
    P64 = c.POINTER(c.c_int64)
    sigs = {
        "cst_strtab_new": (c.c_void_p, [c.c_int64]),
        "cst_strtab_free": (None, [c.c_void_p]),
        "cst_strtab_len": (c.c_int64, [c.c_void_p]),
        "cst_strtab_get_or_insert": (c.c_int64, [c.c_void_p, P8, c.c_int64]),
        "cst_strtab_lookup": (c.c_int64, [c.c_void_p, P8, c.c_int64]),
        "cst_strtab_get_or_insert_batch":
            (c.c_int64, [c.c_void_p, P8, P64, c.c_int64, P64]),
        "cst_strtab_lookup_batch": (None, [c.c_void_p, P8, P64, c.c_int64, P64]),
        "cst_strtab_bytes_len": (c.c_int64, [c.c_void_p, c.c_int64]),
        "cst_strtab_bytes_get": (None, [c.c_void_p, c.c_int64, P8]),
        "cst_i64_new": (c.c_void_p, [c.c_int64]),
        "cst_i64_free": (None, [c.c_void_p]),
        "cst_i64_len": (c.c_int64, [c.c_void_p]),
        "cst_i64_get": (c.c_int64, [c.c_void_p, c.c_int64, c.c_int64]),
        "cst_i64_put": (None, [c.c_void_p, c.c_int64, c.c_int64]),
        "cst_i64_del": (c.c_int64, [c.c_void_p, c.c_int64, c.c_int64]),
        "cst_i64_lookup_batch": (None, [c.c_void_p, P64, c.c_int64, c.c_int64, P64]),
        "cst_i64_put_batch": (None, [c.c_void_p, P64, P64, c.c_int64]),
        "cst_i64_get_or_assign_batch":
            (c.c_int64, [c.c_void_p, P64, c.c_int64, c.c_int64, P64]),
    }
    for name, (res, args) in sigs.items():
        fn = getattr(lib, name)
        fn.restype = res
        fn.argtypes = args


def _as_i64_ptr(arr: np.ndarray):
    return arr.ctypes.data_as(ctypes.POINTER(ctypes.c_int64))


def _as_u8_ptr(buf):
    return ctypes.cast(ctypes.c_char_p(bytes(buf) if not isinstance(buf, bytes)
                                       else buf),
                       ctypes.POINTER(ctypes.c_uint8))


def pack_bytes_list(items: list) -> tuple[bytes, np.ndarray]:
    """-> (blob, offs[n+1]) for batch string calls."""
    lens = np.fromiter((len(b) for b in items), dtype=_I64, count=len(items))
    offs = np.zeros(len(items) + 1, dtype=_I64)
    np.cumsum(lens, out=offs[1:])
    return b"".join(items), offs


# ----------------------------------------------------------------- StrTable

class _NativeStrTable:
    """bytes -> dense id, insertion-ordered."""

    __slots__ = ("_h", "_lib")

    def __init__(self, cap_hint: int = 16):
        self._lib = load_native()
        self._h = self._lib.cst_strtab_new(cap_hint)

    def __len__(self) -> int:
        return self._lib.cst_strtab_len(self._h)

    def __del__(self):
        try:
            self._lib.cst_strtab_free(self._h)
        except (AttributeError, TypeError):
            pass

    def get_or_insert(self, b: bytes) -> int:
        return self._lib.cst_strtab_get_or_insert(self._h, _as_u8_ptr(b), len(b))

    def lookup(self, b: bytes) -> int:
        return self._lib.cst_strtab_lookup(self._h, _as_u8_ptr(b), len(b))

    def get_or_insert_batch(self, items: list) -> tuple[np.ndarray, int]:
        """-> (ids[n], n_new).  New ids are sequential from the previous
        table size, in first-occurrence order."""
        blob, offs = pack_bytes_list(items)
        out = np.empty(len(items), dtype=_I64)
        n_new = self._lib.cst_strtab_get_or_insert_batch(
            self._h, _as_u8_ptr(blob), _as_i64_ptr(offs), len(items),
            _as_i64_ptr(out))
        return out, int(n_new)

    def lookup_batch(self, items: list) -> np.ndarray:
        blob, offs = pack_bytes_list(items)
        out = np.empty(len(items), dtype=_I64)
        self._lib.cst_strtab_lookup_batch(
            self._h, _as_u8_ptr(blob), _as_i64_ptr(offs), len(items),
            _as_i64_ptr(out))
        return out

    def bytes_of(self, idx: int) -> bytes:
        n = self._lib.cst_strtab_bytes_len(self._h, idx)
        if n < 0:
            raise IndexError(idx)
        buf = ctypes.create_string_buffer(n)
        self._lib.cst_strtab_bytes_get(
            self._h, idx, ctypes.cast(buf, ctypes.POINTER(ctypes.c_uint8)))
        return buf.raw


class _PyStrTable:
    __slots__ = ("_d", "_items")

    def __init__(self, cap_hint: int = 16):
        self._d: dict[bytes, int] = {}
        self._items: list[bytes] = []

    def __len__(self) -> int:
        return len(self._d)

    def get_or_insert(self, b: bytes) -> int:
        i = self._d.get(b, -1)
        if i < 0:
            i = len(self._items)
            self._d[b] = i
            self._items.append(b)
        return i

    def lookup(self, b: bytes) -> int:
        return self._d.get(b, -1)

    def get_or_insert_batch(self, items: list) -> tuple[np.ndarray, int]:
        before = len(self._items)
        gi = self.get_or_insert
        out = np.fromiter((gi(b) for b in items), dtype=_I64, count=len(items))
        return out, len(self._items) - before

    def lookup_batch(self, items: list) -> np.ndarray:
        g = self._d.get
        return np.fromiter((g(b, -1) for b in items), dtype=_I64,
                           count=len(items))

    def bytes_of(self, idx: int) -> bytes:
        return self._items[idx]


# ----------------------------------------------------------------- I64Dict

class _NativeI64Dict:
    """int64 -> int64 with batch ops and deletion."""

    __slots__ = ("_h", "_lib")

    def __init__(self, cap_hint: int = 16):
        self._lib = load_native()
        self._h = self._lib.cst_i64_new(cap_hint)

    def __len__(self) -> int:
        return self._lib.cst_i64_len(self._h)

    def __del__(self):
        try:
            self._lib.cst_i64_free(self._h)
        except (AttributeError, TypeError):
            pass

    def get(self, k: int, dflt: int = -1) -> int:
        return self._lib.cst_i64_get(self._h, k, dflt)

    def put(self, k: int, v: int) -> None:
        self._lib.cst_i64_put(self._h, k, v)

    def delete(self, k: int, dflt: int = -1) -> int:
        return self._lib.cst_i64_del(self._h, k, dflt)

    def lookup_batch(self, keys: np.ndarray, dflt: int = -1) -> np.ndarray:
        keys = np.ascontiguousarray(keys, dtype=_I64)
        out = np.empty(len(keys), dtype=_I64)
        self._lib.cst_i64_lookup_batch(self._h, _as_i64_ptr(keys), len(keys),
                                       dflt, _as_i64_ptr(out))
        return out

    def put_batch(self, keys: np.ndarray, vals: np.ndarray) -> None:
        keys = np.ascontiguousarray(keys, dtype=_I64)
        vals = np.ascontiguousarray(vals, dtype=_I64)
        self._lib.cst_i64_put_batch(self._h, _as_i64_ptr(keys),
                                    _as_i64_ptr(vals), len(keys))

    def get_or_assign_batch(self, keys: np.ndarray, next_val: int
                            ) -> tuple[np.ndarray, int]:
        """Missing keys get sequential values from next_val (first-occurrence
        order).  -> (vals[n], n_new)."""
        keys = np.ascontiguousarray(keys, dtype=_I64)
        out = np.empty(len(keys), dtype=_I64)
        n_new = self._lib.cst_i64_get_or_assign_batch(
            self._h, _as_i64_ptr(keys), len(keys), next_val, _as_i64_ptr(out))
        return out, int(n_new)


class _PyI64Dict:
    __slots__ = ("_d",)

    def __init__(self, cap_hint: int = 16):
        self._d: dict[int, int] = {}

    def __len__(self) -> int:
        return len(self._d)

    def get(self, k: int, dflt: int = -1) -> int:
        return self._d.get(k, dflt)

    def put(self, k: int, v: int) -> None:
        self._d[k] = v

    def delete(self, k: int, dflt: int = -1) -> int:
        return self._d.pop(k, dflt)

    def lookup_batch(self, keys: np.ndarray, dflt: int = -1) -> np.ndarray:
        g = self._d.get
        return np.fromiter((g(k, dflt) for k in keys.tolist()), dtype=_I64,
                           count=len(keys))

    def put_batch(self, keys: np.ndarray, vals: np.ndarray) -> None:
        self._d.update(zip(keys.tolist(), vals.tolist()))

    def get_or_assign_batch(self, keys: np.ndarray, next_val: int
                            ) -> tuple[np.ndarray, int]:
        d = self._d
        out = np.empty(len(keys), dtype=_I64)
        start = next_val
        for i, k in enumerate(keys.tolist()):
            v = d.get(k)
            if v is None:
                v = next_val
                d[k] = v
                next_val += 1
            out[i] = v
        return out, next_val - start


# ------------------------------------------------- CPython-extension tier

class _ExtStrTable:
    """bytes -> dense id via the C extension (no blob packing)."""

    __slots__ = ("_h", "_m")

    def __init__(self, cap_hint: int = 16):
        self._m = load_ext()
        self._h = self._m.strtab_new(cap_hint)

    def __len__(self) -> int:
        return self._m.strtab_len(self._h)

    def get_or_insert(self, b: bytes) -> int:
        return self._m.strtab_get_or_insert(self._h, b)

    def lookup(self, b: bytes) -> int:
        return self._m.strtab_lookup(self._h, b)

    def get_or_insert_batch(self, items: list) -> tuple[np.ndarray, int]:
        out = np.empty(len(items), dtype=_I64)
        n_new = self._m.strtab_get_or_insert_batch(self._h, items, out)
        return out, n_new

    def lookup_batch(self, items: list) -> np.ndarray:
        out = np.empty(len(items), dtype=_I64)
        self._m.strtab_lookup_batch(self._h, items, out)
        return out

    def bytes_of(self, idx: int) -> bytes:
        return self._m.strtab_bytes_of(self._h, idx)


class _ExtI64Dict:
    __slots__ = ("_h", "_m")

    def __init__(self, cap_hint: int = 16):
        self._m = load_ext()
        self._h = self._m.i64_new(cap_hint)

    def __len__(self) -> int:
        return self._m.i64_len(self._h)

    def get(self, k: int, dflt: int = -1) -> int:
        return self._m.i64_get(self._h, k, dflt)

    def put(self, k: int, v: int) -> None:
        self._m.i64_put(self._h, k, v)

    def delete(self, k: int, dflt: int = -1) -> int:
        return self._m.i64_del(self._h, k, dflt)

    def lookup_batch(self, keys: np.ndarray, dflt: int = -1) -> np.ndarray:
        keys = np.ascontiguousarray(keys, dtype=_I64)
        out = np.empty(len(keys), dtype=_I64)
        self._m.i64_lookup_batch(self._h, keys, dflt, out)
        return out

    def put_batch(self, keys: np.ndarray, vals: np.ndarray) -> None:
        keys = np.ascontiguousarray(keys, dtype=_I64)
        vals = np.ascontiguousarray(vals, dtype=_I64)
        self._m.i64_put_batch(self._h, keys, vals)

    def get_or_assign_batch(self, keys: np.ndarray, next_val: int
                            ) -> tuple[np.ndarray, int]:
        keys = np.ascontiguousarray(keys, dtype=_I64)
        out = np.empty(len(keys), dtype=_I64)
        n_new = self._m.i64_get_or_assign_batch(self._h, keys, next_val, out)
        return out, n_new


def StrTable(cap_hint: int = 16):
    if load_ext():
        return _ExtStrTable(cap_hint)
    return (_NativeStrTable if load_native() else _PyStrTable)(cap_hint)


def I64Dict(cap_hint: int = 16):
    if load_ext():
        return _ExtI64Dict(cap_hint)
    return (_NativeI64Dict if load_native() else _PyI64Dict)(cap_hint)
