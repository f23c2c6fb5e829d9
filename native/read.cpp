// Native reader (server/read_pump.py): one thread, owned by the extension,
// receives the bytes of gathered client connections, so the event loop's
// thread takes a pass's frames in one call and never pays a recv() system
// call or a task step per connection.
//
// The thread never holds the GIL and never touches a Python object: it
// recv()s into its own buffer, moves the bytes into the connection's queue
// under the reader's mutex, and signals one eventfd the loop watches.
// Laws (docs/INVARIANTS.md "Read-path laws"):
//
//  * One reading path.  A connection is read here or by its transport,
//    never both: `read_detach` waits out a recv in flight on it, takes
//    it off the epoll set and gives back every byte the reader held for
//    it before the loop's transport reads again.
//  * One segment in flight.  Bytes handed to the loop (`read_take`) put
//    the connection IN FLIGHT: the reader does not recv from it again
//    until the loop releases it (`read_release`), after the pass that
//    holds those bytes has handed its replies over.  An edge that comes
//    meanwhile is remembered (`pending`) and served at the release.
//  * The descriptor is the reader's.  `read_open` dup()s the socket when
//    the connection is accepted, and only `read_detach` closes that dup.
//    The end of the stream (EOF or an error) reaches the loop as an entry
//    without bytes, after every byte before it.
//
// The epoll set is edge-triggered: a recv that returns less than it asked
// for drained the socket, and the next byte to arrive is a new edge.
// Counters are atomics, read at INFO time (`read_stats`).

#define PY_SSIZE_T_CLEAN
#include <Python.h>

#include <algorithm>
#include <atomic>
#include <cerrno>
#include <chrono>
#include <condition_variable>
#include <cstdint>
#include <mutex>
#include <string>
#include <system_error>
#include <thread>
#include <unordered_map>
#include <utility>
#include <vector>

#include <fcntl.h>
#include <pthread.h>
#include <sys/epoll.h>
#include <sys/eventfd.h>
#include <sys/socket.h>
#include <unistd.h>

namespace readp {

using Clock = std::chrono::steady_clock;

constexpr size_t kChunk = 1 << 16;   // one recv's room
constexpr size_t kCap = 1 << 18;     // bytes handed over in one take
constexpr int kEvents = 256;

struct Conn {
    int fd = -1;              // the reader's dup of the socket
    std::string buf;          // received, not yet taken
    bool listed = false;      // on the ready list for the next take
    bool inflight = false;    // handed over (or listed): waits for release
    bool pending = false;     // an edge came while it could not be read
    bool hup = false;         // the peer's end or an error was signalled
    bool ended = false;       // recv saw the end: reported after the bytes
    bool reading = false;     // a recv of this connection is in flight
};

struct Reader {
    std::mutex mu;
    std::condition_variable idle_cv;  // a detach waits here
    std::unordered_map<uint64_t, Conn*> conns;
    std::vector<uint64_t> ready;      // for the next take, in order
    std::vector<uint64_t> retry;      // released with an edge pending
    uint64_t next_id = 1;
    bool signalled = false;           // the loop was told since its take
    bool stop = false;
    int detach_waiters = 0;
    int epfd = -1;
    int kick = -1;                    // wakes the thread (id 0 in epoll)
    int efd = -1;                     // the loop's eventfd
    std::thread th;
    std::atomic<uint64_t> takes{0}, bytes{0}, recvs{0}, wakes{0},
        recv_ns{0}, handbacks{0};

    ~Reader() {
        halt();
        for (auto& kv : conns) {
            if (kv.second->fd >= 0) ::close(kv.second->fd);
            delete kv.second;
        }
        if (efd >= 0) ::close(efd);
        if (kick >= 0) ::close(kick);
        if (epfd >= 0) ::close(epfd);
    }

    void poke(int fd) {
        uint64_t one = 1;
        ssize_t r = ::write(fd, &one, sizeof one);
        (void)r;  // a full counter still reads as readable
    }

    void halt() {
        {
            std::lock_guard<std::mutex> lk(mu);
            if (stop) return;
            stop = true;
        }
        if (kick >= 0) poke(kick);
        if (th.joinable()) th.join();
    }

    // caller holds mu; -> the loop must be told
    bool list(uint64_t id, Conn* c) {
        c->inflight = true;
        if (!c->listed) {
            c->listed = true;
            ready.push_back(id);
        }
        if (signalled) return false;
        signalled = true;
        return true;
    }

    // caller holds mu (released around the recv calls); -> tell the loop.
    // `tmp` is the thread's kChunk bytes of room for one recv
    bool read_conn(uint64_t id, Conn* c, std::unique_lock<std::mutex>& lk,
                   std::string& got, char* tmp) {
        c->pending = false;
        c->reading = true;
        int fd = c->fd;
        bool hup = c->hup;
        lk.unlock();
        got.clear();
        bool end = false, full = false;
        uint64_t n_recv = 0;
        auto t0 = Clock::now();
        for (;;) {
            size_t room = std::min(kChunk, kCap - got.size());
            ssize_t n = ::recv(fd, tmp, room, MSG_DONTWAIT);
            n_recv++;
            if (n > 0) {
                got.append(tmp, (size_t)n);
                if (got.size() >= kCap) {
                    full = true;
                    break;
                }
                if ((size_t)n < room && !hup) break;   // drained
                continue;
            }
            if (n == 0) {
                end = true;
                break;
            }
            if (errno == EINTR) continue;
            if (errno != EAGAIN && errno != EWOULDBLOCK) end = true;
            break;
        }
        recv_ns.fetch_add(
            (uint64_t)std::chrono::duration_cast<std::chrono::nanoseconds>(
                Clock::now() - t0).count(),
            std::memory_order_relaxed);
        recvs.fetch_add(n_recv, std::memory_order_relaxed);
        lk.lock();
        c->reading = false;
        if (detach_waiters) idle_cv.notify_all();
        c->buf += got;
        if (end) c->ended = true;
        if (full) c->pending = true;   // more may wait: read at the release
        if (c->buf.empty() && !c->ended) return false;
        return list(id, c);
    }

    void run() {
        pthread_setname_np(pthread_self(), "cst-read");
        epoll_event evs[kEvents];
        std::vector<uint64_t> todo;
        std::string got;
        std::vector<char> tmp(kChunk);
        for (;;) {
            int n = ::epoll_wait(epfd, evs, kEvents, -1);
            if (n < 0 && errno != EINTR) break;
            std::unique_lock<std::mutex> lk(mu);
            if (stop) break;
            todo.clear();
            todo.swap(retry);
            for (int i = 0; i < n; i++) {
                uint64_t id = evs[i].data.u64;
                if (id == 0) {
                    uint64_t cnt;
                    ssize_t r = ::read(kick, &cnt, sizeof cnt);
                    (void)r;
                    continue;
                }
                auto it = conns.find(id);
                if (it == conns.end()) continue;   // released meanwhile
                Conn* c = it->second;
                if (evs[i].events & (EPOLLRDHUP | EPOLLHUP | EPOLLERR))
                    c->hup = true;
                if (c->inflight) c->pending = true;
                else todo.push_back(id);
            }
            bool tell = false;
            for (uint64_t id : todo) {
                auto it = conns.find(id);
                if (it == conns.end()) continue;
                Conn* c = it->second;
                if (c->inflight || c->ended) continue;
                tell |= read_conn(id, c, lk, got, tmp.data());
            }
            lk.unlock();
            if (tell) {
                wakes.fetch_add(1, std::memory_order_relaxed);
                poke(efd);
            }
        }
    }
};

const char* kCapsule = "constdb.Reader";

void destroy(PyObject* cap) {
    // the join is bounded: the thread never waits on the GIL, and its
    // recv calls never block
    delete static_cast<Reader*>(PyCapsule_GetPointer(cap, kCapsule));
}

Reader* get(PyObject* cap) {
    return static_cast<Reader*>(PyCapsule_GetPointer(cap, kCapsule));
}

bool ids_of(PyObject* seq, std::vector<uint64_t>& out) {
    PyObject* f = PySequence_Fast(seq, "ids must be a sequence");
    if (!f) return false;
    Py_ssize_t n = PySequence_Fast_GET_SIZE(f);
    out.resize((size_t)n);
    for (Py_ssize_t i = 0; i < n; i++)
        out[i] = PyLong_AsUnsignedLongLong(PySequence_Fast_GET_ITEM(f, i));
    Py_DECREF(f);
    return !PyErr_Occurred();
}

}  // namespace readp

// read_new() -> reader capsule (no thread yet)
static PyObject* py_read_new(PyObject*, PyObject*) {
    return PyCapsule_New(new readp::Reader(), readp::kCapsule,
                         readp::destroy);
}

// read_start(reader) -> the eventfd the loop reads for bytes to take
static PyObject* py_read_start(PyObject*, PyObject* args) {
    PyObject* cap;
    if (!PyArg_ParseTuple(args, "O", &cap)) return nullptr;
    readp::Reader* r = readp::get(cap);
    if (!r) return nullptr;
    if (r->th.joinable() || r->stop) {
        PyErr_SetString(PyExc_RuntimeError, "reader already started");
        return nullptr;
    }
    r->epfd = ::epoll_create1(EPOLL_CLOEXEC);
    r->kick = ::eventfd(0, EFD_NONBLOCK | EFD_CLOEXEC);
    r->efd = ::eventfd(0, EFD_NONBLOCK | EFD_CLOEXEC);
    epoll_event ev{};
    ev.events = EPOLLIN;
    ev.data.u64 = 0;
    if (r->epfd < 0 || r->kick < 0 || r->efd < 0 ||
        ::epoll_ctl(r->epfd, EPOLL_CTL_ADD, r->kick, &ev) != 0)
        return PyErr_SetFromErrno(PyExc_OSError);
    try {
        r->th = std::thread(&readp::Reader::run, r);
    } catch (const std::system_error&) {
        PyErr_SetString(PyExc_OSError, "could not start the reader");
        return nullptr;
    }
    return PyLong_FromLong(r->efd);
}

// read_stop(reader): stop and join the thread (the descriptors close with
// the capsule)
static PyObject* py_read_stop(PyObject*, PyObject* args) {
    PyObject* cap;
    if (!PyArg_ParseTuple(args, "O", &cap)) return nullptr;
    readp::Reader* r = readp::get(cap);
    if (!r) return nullptr;
    Py_BEGIN_ALLOW_THREADS
    r->halt();
    Py_END_ALLOW_THREADS
    Py_RETURN_NONE;
}

// read_open(reader, fd) -> connection id (> 0); the reader reads its own
// dup of fd from here on
static PyObject* py_read_open(PyObject*, PyObject* args) {
    PyObject* cap;
    int fd;
    if (!PyArg_ParseTuple(args, "Oi", &cap, &fd)) return nullptr;
    readp::Reader* r = readp::get(cap);
    if (!r) return nullptr;
    int own = ::fcntl(fd, F_DUPFD_CLOEXEC, 0);
    if (own < 0) return PyErr_SetFromErrno(PyExc_OSError);
    std::lock_guard<std::mutex> lk(r->mu);
    uint64_t id = r->next_id++;
    readp::Conn* c = new readp::Conn();
    c->fd = own;
    r->conns.emplace(id, c);
    epoll_event ev{};
    ev.events = EPOLLIN | EPOLLRDHUP | EPOLLET;
    ev.data.u64 = id;
    if (::epoll_ctl(r->epfd, EPOLL_CTL_ADD, own, &ev) != 0) {
        int err = errno;
        r->conns.erase(id);
        delete c;
        ::close(own);
        errno = err;
        return PyErr_SetFromErrno(PyExc_OSError);
    }
    return PyLong_FromUnsignedLongLong(id);
}

// read_take(reader) -> [(id, bytes | None)]: every connection that
// delivered since the last take, in the order it did; None is the end of
// its stream (clears the eventfd)
static PyObject* py_read_take(PyObject*, PyObject* args) {
    PyObject* cap;
    if (!PyArg_ParseTuple(args, "O", &cap)) return nullptr;
    readp::Reader* r = readp::get(cap);
    if (!r) return nullptr;
    std::vector<std::pair<uint64_t, std::string>> got;
    std::vector<bool> end;
    // clear the eventfd first: a connection listed after this read either
    // finds `signalled` still set and is taken below, or signals again
    uint64_t cnt;
    ssize_t n = ::read(r->efd, &cnt, sizeof cnt);
    (void)n;  // EAGAIN: nothing signalled since the last take
    {
        std::lock_guard<std::mutex> lk(r->mu);
        r->signalled = false;
        for (uint64_t id : r->ready) {
            auto it = r->conns.find(id);
            if (it == r->conns.end()) continue;
            readp::Conn* c = it->second;
            c->listed = false;
            got.emplace_back(id, std::string());
            got.back().second.swap(c->buf);
            // the end follows the bytes before it, in a later take
            end.push_back(got.back().second.empty());
        }
        r->ready.clear();
    }
    PyObject* out = PyList_New((Py_ssize_t)got.size());
    if (!out) return nullptr;
    uint64_t total = 0;
    for (size_t i = 0; i < got.size(); i++) {
        const std::string& b = got[i].second;
        total += b.size();
        PyObject* t = end[i]
            ? Py_BuildValue("(KO)", (unsigned long long)got[i].first, Py_None)
            : Py_BuildValue("(Ky#)", (unsigned long long)got[i].first,
                            b.data(), (Py_ssize_t)b.size());
        if (!t) {
            Py_DECREF(out);
            return nullptr;
        }
        PyList_SET_ITEM(out, (Py_ssize_t)i, t);
    }
    r->takes.fetch_add(1, std::memory_order_relaxed);
    r->bytes.fetch_add(total, std::memory_order_relaxed);
    return out;
}

// read_release(reader, ids): the passes that held these connections'
// bytes have handed their replies over; the reader reads them again
static PyObject* py_read_release(PyObject*, PyObject* args) {
    PyObject *cap, *ids;
    if (!PyArg_ParseTuple(args, "OO", &cap, &ids)) return nullptr;
    readp::Reader* r = readp::get(cap);
    std::vector<uint64_t> v;
    if (!r || !readp::ids_of(ids, v)) return nullptr;
    bool kick = false, tell = false;
    {
        std::lock_guard<std::mutex> lk(r->mu);
        for (uint64_t id : v) {
            auto it = r->conns.find(id);
            if (it == r->conns.end()) continue;
            readp::Conn* c = it->second;
            if (c->listed) continue;
            c->inflight = false;
            if (c->ended) {
                tell |= r->list(id, c);
            } else if (c->pending) {
                r->retry.push_back(id);
                kick = true;
            }
        }
    }
    if (tell) {
        r->wakes.fetch_add(1, std::memory_order_relaxed);
        r->poke(r->efd);
    }
    if (kick) r->poke(r->kick);
    Py_RETURN_NONE;
}

// read_detach(reader, id, handback) -> bytes | None: wait out a recv in
// flight, take the connection off the reader, close the reader's dup and
// give back every byte it held for it: the transport reads it from here
// on.  handback=1 is a switch of a live stream (read_pump_handbacks, and
// its bytes in read_pump_bytes); the end of a connection passes 0 and
// drops what the reader held
static PyObject* py_read_detach(PyObject*, PyObject* args) {
    PyObject* cap;
    unsigned long long id;
    int handback;
    if (!PyArg_ParseTuple(args, "OKp", &cap, &id, &handback))
        return nullptr;
    readp::Reader* r = readp::get(cap);
    if (!r) return nullptr;
    std::string held;
    bool found = false;
    Py_BEGIN_ALLOW_THREADS
    {
        std::unique_lock<std::mutex> lk(r->mu);
        auto it = r->conns.find(id);
        if (it != r->conns.end()) {
            readp::Conn* c = it->second;
            r->detach_waiters++;
            r->idle_cv.wait(lk, [c] { return !c->reading; });
            r->detach_waiters--;
            found = true;
            held.swap(c->buf);
            // closing a dup does not take it off the set: the socket's
            // other descriptor keeps the file open.  A stale ready or
            // retry entry finds no id and is skipped
            ::epoll_ctl(r->epfd, EPOLL_CTL_DEL, c->fd, nullptr);
            ::close(c->fd);
            delete c;
            r->conns.erase(it);
        }
    }
    Py_END_ALLOW_THREADS
    if (!found || !handback) Py_RETURN_NONE;  // an end drops what it held
    r->handbacks.fetch_add(1, std::memory_order_relaxed);
    r->bytes.fetch_add(held.size(), std::memory_order_relaxed);
    if (held.empty()) Py_RETURN_NONE;
    return PyBytes_FromStringAndSize(held.data(), (Py_ssize_t)held.size());
}

// read_stats(reader) -> (takes, bytes, recvs, wakes, recv_us, handbacks)
static PyObject* py_read_stats(PyObject*, PyObject* args) {
    PyObject* cap;
    if (!PyArg_ParseTuple(args, "O", &cap)) return nullptr;
    readp::Reader* r = readp::get(cap);
    if (!r) return nullptr;
    return Py_BuildValue(
        "(KKKKKK)", (unsigned long long)r->takes.load(),
        (unsigned long long)r->bytes.load(),
        (unsigned long long)r->recvs.load(),
        (unsigned long long)r->wakes.load(),
        (unsigned long long)(r->recv_ns.load() / 1000),
        (unsigned long long)r->handbacks.load());
}
