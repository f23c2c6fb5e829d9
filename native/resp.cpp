// Native RESP fast path: parse flat command arrays at C speed.
//
// The op path is parse-bound (OPBENCH.md): every pipelined client command
// is a flat `*N` array of `$` bulks / `:` ints, and the pure-Python
// scanner costs ~10us per message.  The reference answers the same
// pressure with N parse THREADS feeding one exec thread (reference
// README.md:12, src/lib.rs:138-142); this build keeps the single-writer
// asyncio loop and moves the parse itself into C instead.
//
// resp_parse(buffer, pos, Arr, Bulk, Int, Simple, Err, nil[, max_msgs])
// scans from `pos` and returns (messages, new_pos, fallback):
//   * messages — list of fully-constructed message objects (instances
//     built via tp_alloc + slot set, skipping __init__ bytecode).
//     Coverage: the full value grammar recursively — `*N` arrays
//     (including `*0` → Arr([]) and `*-1` → nil, nested to a small C
//     depth cap), `+simple`, `-err`, `:int`, `$bulk`, `$-1` (nil) —
//     i.e. both directions of the protocol, commands AND replies
//     (r18: reply arrays used to defer on `*0`/nesting, which made
//     every pipelined read client pay the pure-parser price for empty
//     and hash-pair replies);
//   * new_pos  — first unconsumed byte (a partial trailing message is
//     left unconsumed);
//   * fallback — true when the next message needs the general parser:
//     over-deep nesting, unknown type byte, or ANY shape this fast
//     path cannot parse cleanly (overlong integers, malformed framing,
//     oversized bulks...).  The pure-Python parser is the semantics
//     reference — it either accepts what C was too strict for (e.g. a
//     bare CR inside a simple line, a >64-bit integer) or raises its own
//     InvalidRequestMsg — so deferring to it on every non-clean parse
//     keeps behavior bit-identical, error text included.  The C side
//     itself raises only on CPython allocation failures.
//
// Messages parsed BEFORE a bad frame in the same scan are still returned
// (the caller executes them, then the pure parser surfaces the error) —
// the same delivery order the pure parser produces one call at a time.

#define PY_SSIZE_T_CLEAN
#include <Python.h>

#include <algorithm>
#include <cstdint>
#include <cstring>
#include <string>
#include <vector>

namespace resp {

constexpr Py_ssize_t kMaxLine = 1 << 20;
constexpr Py_ssize_t kMaxArr = 1 << 20;
constexpr long long kMaxBulk = 512LL << 20;
constexpr Py_ssize_t kMaxDigits = 18;  // always < LLONG_MAX: no overflow UB

struct Names {
    PyObject* val = nullptr;
    PyObject* items = nullptr;
};

inline Names& names() {
    static Names n;
    if (!n.val) {
        n.val = PyUnicode_InternFromString("val");
        n.items = PyUnicode_InternFromString("items");
    }
    return n;
}

// Object construction without __init__: alloc the (slotted, dict-less)
// instance and set its single slot.  Steals `value`.
inline PyObject* make1(PyObject* type, PyObject* name, PyObject* value) {
    if (!value) return nullptr;
    PyTypeObject* t = reinterpret_cast<PyTypeObject*>(type);
    PyObject* obj = t->tp_alloc(t, 0);
    if (!obj) {
        Py_DECREF(value);
        return nullptr;
    }
    if (PyObject_SetAttr(obj, name, value) != 0) {
        Py_DECREF(value);
        Py_DECREF(obj);
        return nullptr;
    }
    Py_DECREF(value);
    return obj;
}

// Scan an integer line "<digits>\r\n" (optionally signed) starting at p.
// Returns: 1 ok, 0 need-more, -1 not fast-parseable (caller falls back to
// the pure parser; no python error is set).
inline int int_line(const char* b, Py_ssize_t len, Py_ssize_t p,
                    long long* out, Py_ssize_t* next) {
    const char* cr = static_cast<const char*>(
        memchr(b + p, '\r', static_cast<size_t>(len - p)));
    if (!cr || cr - b + 1 >= len) {
        if (len - p > kMaxLine) return -1;  // pure parser raises
        return 0;
    }
    Py_ssize_t e = cr - b;
    if (b[e + 1] != '\n') return -1;  // bare CR: defer to pure parser
    bool neg = false;
    Py_ssize_t i = p;
    if (i < e && (b[i] == '-' || b[i] == '+')) {
        neg = b[i] == '-';
        i++;
    }
    // > kMaxDigits would overflow long long (UB) — and the pure parser
    // handles arbitrary-precision integers correctly, so defer
    if (i >= e || e - i > kMaxDigits) return -1;
    long long v = 0;
    for (; i < e; i++) {
        if (b[i] < '0' || b[i] > '9') return -1;
        v = v * 10 + (b[i] - '0');
    }
    *out = neg ? -v : v;
    *next = e + 2;
    return 1;
}

// the C recursion cap for nested reply arrays: well under the pure
// parser's max_depth=32, so anything deeper defers (the pure parser
// then builds it or raises "nesting too deep" — identical either way)
constexpr int kMaxCDepth = 8;

struct ParseCtx {
    const char* b;
    Py_ssize_t len;
    PyObject *arr_t, *bulk_t, *int_t, *simple_t, *err_t, *nil_obj;
    long long bulk_cap;
};

// Parse ONE value of the RESP grammar starting at *pos.
// Returns: 1 ok (*out set, *pos advanced), 0 need-more, -1 defer to the
// pure parser, -2 CPython error (exception set).  *pos is only advanced
// on success; `fullsync` (top-level arrays only) reports a frame whose
// first element is the bulk "fullsync" — raw snapshot bytes follow it on
// the stream, so the caller must stop the batch scan there.
inline int parse_any(const ParseCtx& c, Py_ssize_t* pos, int depth,
                     PyObject** out, bool* fullsync) {
    if (*pos >= c.len) return 0;
    const char* b = c.b;
    const Py_ssize_t len = c.len;
    Names& nm = names();
    const char t = b[*pos];
    if (t == '+' || t == '-') {
        // simple / error line.  The pure parser's _line scans for the
        // CRLF PAIR, so a bare CR inside the line is part of the payload
        // there — defer rather than diverge.
        const char* cr = static_cast<const char*>(
            memchr(b + *pos, '\r', static_cast<size_t>(len - *pos)));
        if (!cr || cr - b + 1 >= len) {
            if (len - *pos > kMaxLine) return -1;  // pure parser raises
            return 0;
        }
        Py_ssize_t e = cr - b;
        if (b[e + 1] != '\n') return -1;
        PyObject* obj = make1(
            t == '+' ? c.simple_t : c.err_t, nm.val,
            PyBytes_FromStringAndSize(b + *pos + 1, e - *pos - 1));
        if (!obj) return -2;
        *out = obj;
        *pos = e + 2;
        return 1;
    }
    if (t == ':') {
        long long v;
        Py_ssize_t q;
        int st = int_line(b, len, *pos + 1, &v, &q);
        if (st <= 0) return st;
        PyObject* obj = make1(c.int_t, nm.val, PyLong_FromLongLong(v));
        if (!obj) return -2;
        *out = obj;
        *pos = q;
        return 1;
    }
    if (t == '$') {
        long long ln;
        Py_ssize_t q;
        int st = int_line(b, len, *pos + 1, &ln, &q);
        if (st <= 0) return st;
        if (ln < 0) {
            if (ln != -1) return -1;  // pure parser raises
            Py_INCREF(c.nil_obj);
            *out = c.nil_obj;
            *pos = q;
            return 1;
        }
        if (ln > c.bulk_cap) return -1;  // pure parser raises "too large"
        if (q + ln + 2 > len) return 0;  // need more
        if (b[q + ln] != '\r' || b[q + ln + 1] != '\n')
            return -1;  // pure parser raises "missing CRLF"
        PyObject* obj = make1(c.bulk_t, nm.val,
                              PyBytes_FromStringAndSize(b + q, ln));
        if (!obj) return -2;
        *out = obj;
        *pos = q + ln + 2;
        return 1;
    }
    if (t != '*') return -1;  // unknown type byte: pure parser raises
    if (depth >= kMaxCDepth) return -1;  // pure parser handles/raises
    long long cnt;
    Py_ssize_t p;
    int st = int_line(b, len, *pos + 1, &cnt, &p);
    if (st <= 0) return st;
    if (cnt < 0) {
        if (cnt != -1) return -1;  // pure parser raises
        Py_INCREF(c.nil_obj);
        *out = c.nil_obj;
        *pos = p;
        return 1;
    }
    if (cnt > kMaxArr) return -1;  // pure parser raises "too large"
    PyObject* items = PyList_New(cnt);
    if (!items) return -2;
    for (long long i = 0; i < cnt; i++) {
        PyObject* obj = nullptr;
        int st2 = parse_any(c, &p, depth + 1, &obj, nullptr);
        if (st2 != 1) {
            Py_DECREF(items);  // safe: unfilled tail slots are NULL
            return st2;
        }
        PyList_SET_ITEM(items, i, obj);
        // a FULLSYNC frame is followed by RAW (non-RESP) snapshot bytes
        // on the same stream; scanning past it would consume them as
        // frames (replica/link.py drains them via take_raw)
        if (i == 0 && fullsync != nullptr && Py_TYPE(obj) ==
                reinterpret_cast<PyTypeObject*>(c.bulk_t)) {
            PyObject* v = PyObject_GetAttr(obj, nm.val);
            if (!v) {
                Py_DECREF(items);
                return -2;
            }
            if (PyBytes_Check(v) && PyBytes_GET_SIZE(v) == 8 &&
                strncasecmp(PyBytes_AS_STRING(v), "fullsync", 8) == 0)
                *fullsync = true;
            Py_DECREF(v);
        }
    }
    PyObject* arr = make1(c.arr_t, nm.items, items);
    if (!arr) return -2;
    *out = arr;
    *pos = p;
    return 1;
}

}  // namespace resp

static PyObject* py_resp_parse(PyObject*, PyObject* args) {
    Py_buffer view;
    Py_ssize_t pos;
    PyObject *arr_t, *bulk_t, *int_t, *simple_t, *err_t, *nil_obj;
    Py_ssize_t max_msgs = 1024;
    // configurable parse-time bulk ceiling (CONSTDB_PROTO_MAX_BULK):
    // a $-header past it defers to the pure parser, which raises the
    // protocol error — never buffers toward the declared length.
    // Clamped to the wire format's hard 512MB ceiling; <= 0 = default.
    long long max_bulk = 0;
    if (!PyArg_ParseTuple(args, "y*nOOOOOO|nL", &view, &pos, &arr_t, &bulk_t,
                          &int_t, &simple_t, &err_t, &nil_obj, &max_msgs,
                          &max_bulk))
        return nullptr;
    const long long bulk_cap =
        (max_bulk > 0 && max_bulk < resp::kMaxBulk) ? max_bulk
                                                    : resp::kMaxBulk;
    resp::ParseCtx ctx{static_cast<const char*>(view.buf), view.len,
                       arr_t, bulk_t, int_t, simple_t, err_t, nil_obj,
                       bulk_cap};

    PyObject* out = PyList_New(0);
    int fallback = 0;
    if (!out) {
        PyBuffer_Release(&view);
        return nullptr;
    }

    while (PyList_GET_SIZE(out) < max_msgs && pos < ctx.len) {
        PyObject* obj = nullptr;
        bool is_fullsync = false;
        Py_ssize_t p = pos;
        int st = resp::parse_any(ctx, &p, 0, &obj, &is_fullsync);
        if (st == 0) break;  // partial trailing message: need more bytes
        if (st == -1) {
            fallback = 1;  // defer this message to the pure parser
            break;
        }
        if (st == -2) goto fail;
        int rc = PyList_Append(out, obj);
        Py_DECREF(obj);
        if (rc != 0) goto fail;
        pos = p;
        if (is_fullsync) break;  // raw snapshot bytes follow
    }

    PyBuffer_Release(&view);
    return Py_BuildValue("(Nni)", out, pos, fallback);

fail:
    Py_DECREF(out);
    PyBuffer_Release(&view);
    return nullptr;
}

// ---------------------------------------------------------------- encoder
//
// resp_encode(out_bytearray, msg, Arr, Bulk, Int, Simple, Err, NilT, NoReplyT)
// appends msg's wire encoding to `out` and returns True, or returns False
// when msg has ANY shape this fast path cannot encode cleanly (subclass,
// non-bytes payload, >64-bit int, NoReply inside an Arr...) — the caller
// then falls back to the pure-Python encoder, which either handles it or
// raises its own error, keeping behavior identical.  Small non-negative
// int replies are interned (parity: reference src/resp.rs:12-27 pre-builds
// the common counter replies).

namespace resp {

constexpr int kInternedInts = 10000;

inline const std::string* interned_int(long long v) {
    static std::string table[kInternedInts];
    static bool built = false;
    if (!built) {
        char buf[32];
        for (int i = 0; i < kInternedInts; i++) {
            int n = snprintf(buf, sizeof buf, ":%d\r\n", i);
            table[i].assign(buf, static_cast<size_t>(n));
        }
        built = true;
    }
    return (v >= 0 && v < kInternedInts) ? &table[v] : nullptr;
}

struct EncTypes {
    PyTypeObject *arr, *bulk, *i, *simple, *err, *nil, *noreply;
};

// returns 1 ok, 0 fallback-needed (no python error set), -1 python error
inline int encode1(std::string& out, PyObject* m, const EncTypes& t,
                   int depth, bool top) {
    if (depth > 32) return 0;
    PyTypeObject* ty = Py_TYPE(m);
    if (ty == t.noreply) return top ? 1 : 0;  // inside Arr: pure path raises
    if (ty == t.nil) {
        out.append("$-1\r\n", 5);
        return 1;
    }
    Names& nm = names();
    if (ty == t.i) {
        PyObject* val = PyObject_GetAttr(m, nm.val);
        if (!val) return -1;
        if (!PyLong_CheckExact(val)) {
            Py_DECREF(val);
            return 0;
        }
        int overflow = 0;
        long long v = PyLong_AsLongLongAndOverflow(val, &overflow);
        Py_DECREF(val);
        if (overflow || (v == -1 && PyErr_Occurred())) {
            PyErr_Clear();
            return 0;  // arbitrary-precision: pure path formats it
        }
        if (const std::string* s = interned_int(v)) {
            out.append(*s);
        } else {
            char buf[32];
            int n = snprintf(buf, sizeof buf, ":%lld\r\n", v);
            out.append(buf, static_cast<size_t>(n));
        }
        return 1;
    }
    if (ty == t.bulk || ty == t.simple || ty == t.err) {
        PyObject* val = PyObject_GetAttr(m, nm.val);
        if (!val) return -1;
        if (!PyBytes_CheckExact(val)) {
            Py_DECREF(val);
            return 0;
        }
        char* p;
        Py_ssize_t n;
        PyBytes_AsStringAndSize(val, &p, &n);
        if (ty == t.bulk) {
            char head[32];
            int hn = snprintf(head, sizeof head, "$%lld\r\n",
                              static_cast<long long>(n));
            out.append(head, static_cast<size_t>(hn));
            out.append(p, static_cast<size_t>(n));
            out.append("\r\n", 2);
        } else {
            out.push_back(ty == t.simple ? '+' : '-');
            out.append(p, static_cast<size_t>(n));
            out.append("\r\n", 2);
        }
        Py_DECREF(val);
        return 1;
    }
    if (ty == t.arr) {
        PyObject* items = PyObject_GetAttr(m, nm.items);
        if (!items) return -1;
        if (!PyList_CheckExact(items)) {
            Py_DECREF(items);
            return 0;
        }
        Py_ssize_t n = PyList_GET_SIZE(items);
        char head[32];
        int hn = snprintf(head, sizeof head, "*%lld\r\n",
                          static_cast<long long>(n));
        out.append(head, static_cast<size_t>(hn));
        for (Py_ssize_t j = 0; j < n; j++) {
            int rc = encode1(out, PyList_GET_ITEM(items, j), t, depth + 1,
                             false);
            if (rc != 1) {
                Py_DECREF(items);
                return rc;
            }
        }
        Py_DECREF(items);
        return 1;
    }
    return 0;  // unknown / subclassed message type
}

}  // namespace resp

static PyObject* py_resp_encode(PyObject*, PyObject* args) {
    PyObject *out, *msg;
    resp::EncTypes t;
    if (!PyArg_ParseTuple(args, "OOOOOOOOO", &out, &msg, &t.arr, &t.bulk,
                          &t.i, &t.simple, &t.err, &t.nil, &t.noreply))
        return nullptr;
    if (!PyByteArray_CheckExact(out)) {
        PyErr_SetString(PyExc_TypeError, "out must be a bytearray");
        return nullptr;
    }
    std::string buf;
    int rc = resp::encode1(buf, msg, t, 0, true);
    if (rc < 0) return nullptr;
    if (rc == 0) Py_RETURN_FALSE;
    Py_ssize_t old = PyByteArray_GET_SIZE(out);
    if (PyByteArray_Resize(out, old + static_cast<Py_ssize_t>(buf.size())))
        return nullptr;
    memcpy(PyByteArray_AS_STRING(out) + old, buf.data(), buf.size());
    Py_RETURN_TRUE;
}

// ----------------------------------------------------- row-reply encoder
//
// resp_encode_rows(out_bytearray, kind, rows, members, vals) -> bytes | None
// appends the wire reply of one planned row-scan read (server/serve.py
// _read_misses) straight from the element blob planes — no Arr/Bulk tree —
// and returns the appended payload (the reply cache stores it as is):
//   kind 0  members: *<n> then one bulk of members[r] per row
//   kind 1  pairs:   *<n> then *2 + bulk members[r] + bulk vals[r] per row
//   kind 2  values:  *<n> then one bulk of vals[r] per row
// a None value is the empty bulk.  One size pre-pass, one memcpy per
// piece.  Returns None, with nothing appended, for any shape it will not
// encode (non-list, row out of range, non-bytes blob): the caller's pure
// twin then encodes it or raises its own error, keeping behavior
// identical.

namespace resp {

struct Blob {
    const char* p;
    Py_ssize_t n;
};

inline int dec_digits(Py_ssize_t v) {
    int d = 1;
    while (v >= 10) {
        v /= 10;
        d++;
    }
    return d;
}

inline char* put_head(char* w, char tag, Py_ssize_t v) {
    *w++ = tag;
    int d = dec_digits(v);
    for (int i = d - 1; i >= 0; i--) {
        w[i] = static_cast<char>('0' + v % 10);
        v /= 10;
    }
    w += d;
    *w++ = '\r';
    *w++ = '\n';
    return w;
}

inline char* put_bulk(char* w, const Blob& b) {
    w = put_head(w, '$', b.n);
    if (b.n) memcpy(w, b.p, static_cast<size_t>(b.n));
    w += b.n;
    *w++ = '\r';
    *w++ = '\n';
    return w;
}

// bytes -> its buffer, None -> empty (when allowed); false = decline
inline bool blob_of(PyObject* list, Py_ssize_t r, bool none_ok, Blob* b) {
    PyObject* o = PyList_GET_ITEM(list, r);
    if (PyBytes_CheckExact(o)) {
        b->p = PyBytes_AS_STRING(o);
        b->n = PyBytes_GET_SIZE(o);
        return true;
    }
    if (none_ok && o == Py_None) {
        b->p = "";
        b->n = 0;
        return true;
    }
    return false;
}

// The one writer behind resp_encode_rows and resp_scan_reply: add() the
// reply's rows in order (a size pre-pass over their blobs), then finish()
// resizes `out` once and writes.
struct RowReply {
    const int kind;
    PyObject* const members;
    PyObject* const vals;
    const Py_ssize_t n_members, n_vals;
    std::vector<Blob> blobs;
    Py_ssize_t n = 0, body = 0;

    // nullptr planes (not lists) leave ok() false: the caller declines
    RowReply(int k, PyObject* m, PyObject* v, Py_ssize_t reserve)
        : kind(k),
          members(PyList_Check(m) ? m : nullptr),
          vals(PyList_Check(v) ? v : nullptr),
          n_members(members ? PyList_GET_SIZE(members) : 0),
          n_vals(vals ? PyList_GET_SIZE(vals) : 0) {
        blobs.reserve(static_cast<size_t>(reserve * (k == 1 ? 2 : 1)));
    }

    bool ok() const { return kind >= 0 && kind <= 2 && members && vals; }

    // row r's blobs; false = decline (row past a plane, non-bytes blob)
    bool add(Py_ssize_t r) {
        Blob b;
        if (kind != 2) {
            if (r >= n_members || !blob_of(members, r, false, &b))
                return false;
            body += 5 + dec_digits(b.n) + b.n;
            blobs.push_back(b);
        }
        if (kind != 0) {
            if (r >= n_vals || !blob_of(vals, r, true, &b)) return false;
            body += 5 + dec_digits(b.n) + b.n;
            blobs.push_back(b);
        }
        n++;
        return true;
    }

    // "*<n>\r\n", then per blob "$<len>\r\n<bytes>\r\n", per pair "*2\r\n";
    // returns the appended payload (new reference) or nullptr on error
    PyObject* finish(PyObject* out) const {
        const Py_ssize_t total =
            3 + dec_digits(n) + (kind == 1 ? 4 * n : 0) + body;
        const Py_ssize_t old = PyByteArray_GET_SIZE(out);
        if (PyByteArray_Resize(out, old + total)) return nullptr;
        char* const base = PyByteArray_AS_STRING(out) + old;
        char* w = put_head(base, '*', n);
        const Blob* b = blobs.data();
        for (Py_ssize_t j = 0; j < n; j++) {
            if (kind == 1) {
                memcpy(w, "*2\r\n", 4);
                w += 4;
                w = put_bulk(w, *b++);
            }
            w = put_bulk(w, *b++);
        }
        return PyBytes_FromStringAndSize(base, total);
    }
};

// a list item as a row index; -1 (error cleared) = decline: not an int,
// negative, or past Py_ssize_t — the pure twin's cases
inline Py_ssize_t row_of(PyObject* ro) {
    if (!PyLong_CheckExact(ro)) return -1;
    Py_ssize_t r = PyLong_AsSsize_t(ro);
    if (r < 0) {
        PyErr_Clear();
        return -1;
    }
    return r;
}

// One C-contiguous int64 column through the buffer protocol (no copy).
struct I64Col {
    Py_buffer view;
    bool held = false;
    const int64_t* p = nullptr;
    Py_ssize_t n = 0;

    // false (error cleared) = decline: no buffer, strided, not int64
    bool take(PyObject* o) {
        if (PyObject_GetBuffer(o, &view,
                               PyBUF_C_CONTIGUOUS | PyBUF_FORMAT) != 0) {
            PyErr_Clear();
            return false;
        }
        held = true;
        const char* f = view.format ? view.format : "";
        if (*f == '@' || *f == '=' || *f == '<') f++;
        if (view.itemsize != 8 || (*f != 'l' && *f != 'q') || f[1])
            return false;
        p = static_cast<const int64_t*>(view.buf);
        n = view.len / 8;
        return true;
    }

    ~I64Col() {
        if (held) PyBuffer_Release(&view);
    }
};

}  // namespace resp

static bool out_is_bytearray(PyObject* out) {
    if (PyByteArray_CheckExact(out)) return true;
    PyErr_SetString(PyExc_TypeError, "out must be a bytearray");
    return false;
}

static PyObject* py_resp_encode_rows(PyObject*, PyObject* args) {
    PyObject *out, *rows, *members, *vals;
    int kind;
    if (!PyArg_ParseTuple(args, "OiOOO", &out, &kind, &rows, &members,
                          &vals))
        return nullptr;
    if (!out_is_bytearray(out)) return nullptr;
    if (!PyList_CheckExact(rows)) Py_RETURN_NONE;
    const Py_ssize_t n = PyList_GET_SIZE(rows);
    resp::RowReply reply(kind, members, vals, n);
    if (!reply.ok()) Py_RETURN_NONE;
    for (Py_ssize_t j = 0; j < n; j++) {
        Py_ssize_t r = resp::row_of(PyList_GET_ITEM(rows, j));
        if (r < 0 || !reply.add(r)) Py_RETURN_NONE;
    }
    return reply.finish(out);
}

// ------------------------------------------------------- fused scan reply
//
// resp_scan_reply(out_bytearray, kind, kid, rows, el_kid, add_t, del_t,
//                 members, vals) -> bytes | None
// answers one planned SMEMBERS (kind 0) / HGETALL (kind 1) miss in ONE
// pass from the key's row list (KeySpace.el_rows_by_kid[kid]) to its
// reply bytes: per row, in list order, keep it iff
//   el_kid[r] == kid and add_t[r] >= del_t[r]
// (the compaction-staleness check and the liveness rule of
// KeySpace.elem_live_rows_batch, letter for letter), then write the kept
// rows exactly as resp_encode_rows would.  The three columns arrive
// through the buffer protocol: no gather, no row array, no second call.
// Returns None, with nothing appended, for any shape it will not take
// (what resp_encode_rows declines; a row past a column; a column that is
// not C-contiguous int64): the caller's pure twin answers or raises.

static PyObject* py_resp_scan_reply(PyObject*, PyObject* args) {
    PyObject *out, *rows, *o_kid, *o_add, *o_del, *members, *vals;
    int kind;
    long long kid;
    if (!PyArg_ParseTuple(args, "OiLOOOOOO", &out, &kind, &kid, &rows,
                          &o_kid, &o_add, &o_del, &members, &vals))
        return nullptr;
    if (!out_is_bytearray(out)) return nullptr;
    if (kind == 2 || !PyList_CheckExact(rows)) Py_RETURN_NONE;
    const Py_ssize_t n = PyList_GET_SIZE(rows);
    resp::RowReply reply(kind, members, vals, n);
    if (!reply.ok()) Py_RETURN_NONE;
    resp::I64Col el_kid, add_t, del_t;
    if (!el_kid.take(o_kid) || !add_t.take(o_add) || !del_t.take(o_del))
        Py_RETURN_NONE;
    const Py_ssize_t n_col = std::min(el_kid.n, std::min(add_t.n, del_t.n));
    for (Py_ssize_t j = 0; j < n; j++) {
        Py_ssize_t r = resp::row_of(PyList_GET_ITEM(rows, j));
        if (r < 0 || r >= n_col) Py_RETURN_NONE;
        if (el_kid.p[r] == kid && add_t.p[r] >= del_t.p[r] && !reply.add(r))
            Py_RETURN_NONE;
    }
    return reply.finish(out);
}
