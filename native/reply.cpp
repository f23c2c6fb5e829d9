// Native reply sender (server/reply_pump.py): one thread, owned by the
// extension, writes client replies, so the event loop's thread hands a
// pass's replies over in one call and never pays a send() system call.
//
// The thread never holds the GIL and never touches a Python object: the
// loop's thread copies each reply's bytes into a connection's queue under
// the sender's mutex, and the sender sends from its own copy with the
// mutex released.  Laws (docs/INVARIANTS.md "Reply-path laws"):
//
//  * Order.  One FIFO of ready connections, one sender: a connection's
//    bytes leave in the order they were handed over.
//  * The descriptor is the sender's.  `reply_open` dup()s the socket when
//    the connection is accepted and only `reply_detach(.., release=1)`
//    closes that dup, so no byte can reach another connection that reused
//    the number after a close.
//  * Never block.  send() is non-blocking.  On EAGAIN or a partial send
//    the connection is SPILLED: the unsent remainder and whatever was
//    queued behind it are handed back, in order, through an eventfd the
//    loop reads (`reply_take_spills`), and the connection belongs to the
//    loop's transport until the loop resumes it (`reply_resume`).  Bytes
//    handed over to a spilled connection join its hand-back.  EPIPE /
//    ECONNRESET drop the connection's bytes; the loop's own read sees the
//    end.
//  * One path at a time.  `reply_detach` waits for a send in flight on
//    that connection (bounded: sends never block), then takes back
//    everything the sender still holds for it and gives the connection to
//    the transport.
//
// Counters are atomics, read at INFO time (`reply_stats`).

#define PY_SSIZE_T_CLEAN
#include <Python.h>

#include <atomic>
#include <cerrno>
#include <chrono>
#include <condition_variable>
#include <cstdint>
#include <cstring>
#include <deque>
#include <mutex>
#include <string>
#include <system_error>
#include <thread>
#include <unordered_map>
#include <vector>

#include <fcntl.h>
#include <pthread.h>
#include <sys/eventfd.h>
#include <sys/socket.h>
#include <unistd.h>

namespace reply {

using Clock = std::chrono::steady_clock;

// how long the sender polls for more work before it parks on its
// condition variable (a wake costs the loop's thread a futex call)
constexpr auto kSpin = std::chrono::microseconds(50);

struct Conn {
    int fd = -1;              // the sender's dup of the socket
    std::string queued;       // handed over, not yet taken by the sender
    std::string spill;        // handed back to the loop, in order
    bool ready = false;       // on the ready FIFO
    bool sending = false;     // a send() of this connection is in flight
    bool loop_owned = false;  // the loop's transport writes it (spilled
                              // or detached)
    bool listed = false;      // on the spill list
    bool dead = false;        // the peer reset: bytes are dropped
};

struct Sender {
    std::mutex mu;
    std::condition_variable work_cv;  // the sender parks here
    std::condition_variable idle_cv;  // a detach waits here
    std::unordered_map<uint64_t, Conn*> conns;
    std::deque<uint64_t> ready;
    std::vector<uint64_t> spilled;
    uint64_t next_id = 1;
    bool parked = false;
    bool stop = false;
    int detach_waiters = 0;
    int efd = -1;
    std::thread th;
    std::atomic<uint64_t> post_seq{0};  // bumped by every hand-over
    std::atomic<uint64_t> posts{0}, bytes{0}, wakes{0}, spills{0},
        send_ns{0};

    ~Sender() {
        halt();
        for (auto& kv : conns) {
            if (kv.second->fd >= 0) ::close(kv.second->fd);
            delete kv.second;
        }
        if (efd >= 0) ::close(efd);
    }

    void halt() {
        {
            std::lock_guard<std::mutex> lk(mu);
            stop = true;
        }
        work_cv.notify_one();
        if (th.joinable()) th.join();
    }

    // caller holds mu
    void list_spill(uint64_t id, Conn* c) {
        if (c->listed) return;
        c->listed = true;
        spilled.push_back(id);
        uint64_t one = 1;
        if (efd >= 0) {
            ssize_t r = ::write(efd, &one, sizeof one);
            (void)r;  // a full counter still reads as readable
        }
    }

    void run() {
        pthread_setname_np(pthread_self(), "cst-reply");
        std::string cur;
        std::unique_lock<std::mutex> lk(mu);
        for (;;) {
            if (ready.empty()) {
                if (stop) break;
                uint64_t seen = post_seq.load(std::memory_order_acquire);
                lk.unlock();
                auto until = Clock::now() + kSpin;
                while (post_seq.load(std::memory_order_acquire) == seen &&
                       Clock::now() < until) {
                }
                lk.lock();
                if (ready.empty() && !stop) {
                    parked = true;
                    work_cv.wait(lk, [this] { return !parked || stop; });
                    parked = false;
                }
                continue;
            }
            uint64_t id = ready.front();
            ready.pop_front();
            auto it = conns.find(id);
            if (it == conns.end()) continue;
            Conn* c = it->second;
            c->ready = false;
            if (c->loop_owned || c->dead || c->queued.empty()) continue;
            cur.swap(c->queued);
            c->sending = true;
            int fd = c->fd;
            lk.unlock();
            size_t off = 0;
            int err = 0;
            auto t0 = Clock::now();
            while (off < cur.size()) {
                ssize_t n = ::send(fd, cur.data() + off, cur.size() - off,
                                   MSG_NOSIGNAL | MSG_DONTWAIT);
                if (n > 0) {
                    off += (size_t)n;
                } else if (n < 0 && errno == EINTR) {
                    continue;
                } else {
                    err = n < 0 ? errno : EAGAIN;
                    break;
                }
            }
            send_ns.fetch_add(
                (uint64_t)std::chrono::duration_cast<std::chrono::nanoseconds>(
                    Clock::now() - t0).count(),
                std::memory_order_relaxed);
            lk.lock();
            c->sending = false;
            if (off < cur.size()) {
                if (err == EAGAIN || err == EWOULDBLOCK || err == ENOBUFS) {
                    // hand back the remainder, then what queued behind it
                    c->spill.append(cur, off, std::string::npos);
                    c->spill += c->queued;
                    c->queued.clear();
                    c->loop_owned = true;
                    spills.fetch_add(1, std::memory_order_relaxed);
                    list_spill(id, c);
                } else {
                    c->dead = true;
                    c->queued.clear();
                }
            } else if (!c->queued.empty() && !c->ready) {
                c->ready = true;
                ready.push_back(id);
            }
            cur.clear();
            if (detach_waiters) idle_cv.notify_all();
        }
    }
};

const char* kCapsule = "constdb.ReplySender";

void destroy(PyObject* cap) {
    // the join is bounded: the thread never waits on the GIL, and a send
    // in flight never blocks
    delete static_cast<Sender*>(PyCapsule_GetPointer(cap, kCapsule));
}

Sender* get(PyObject* cap) {
    return static_cast<Sender*>(PyCapsule_GetPointer(cap, kCapsule));
}

PyObject* bytes_or_none(const std::string& b) {
    if (b.empty()) Py_RETURN_NONE;
    return PyBytes_FromStringAndSize(b.data(), (Py_ssize_t)b.size());
}

}  // namespace reply

// reply_new() -> sender capsule (no thread yet)
static PyObject* py_reply_new(PyObject*, PyObject*) {
    return PyCapsule_New(new reply::Sender(), reply::kCapsule,
                         reply::destroy);
}

// reply_start(sender) -> the eventfd the loop reads for spills
static PyObject* py_reply_start(PyObject*, PyObject* args) {
    PyObject* cap;
    if (!PyArg_ParseTuple(args, "O", &cap)) return nullptr;
    reply::Sender* s = reply::get(cap);
    if (!s) return nullptr;
    if (s->th.joinable() || s->stop) {
        PyErr_SetString(PyExc_RuntimeError, "reply sender already started");
        return nullptr;
    }
    s->efd = ::eventfd(0, EFD_NONBLOCK | EFD_CLOEXEC);
    if (s->efd < 0) return PyErr_SetFromErrno(PyExc_OSError);
    try {
        s->th = std::thread(&reply::Sender::run, s);
    } catch (const std::system_error&) {
        ::close(s->efd);
        s->efd = -1;
        PyErr_SetString(PyExc_OSError, "could not start the reply sender");
        return nullptr;
    }
    return PyLong_FromLong(s->efd);
}

// reply_stop(sender): stop and join the thread (what it still holds is
// taken back by reply_detach; the descriptors close with the capsule)
static PyObject* py_reply_stop(PyObject*, PyObject* args) {
    PyObject* cap;
    if (!PyArg_ParseTuple(args, "O", &cap)) return nullptr;
    reply::Sender* s = reply::get(cap);
    if (!s) return nullptr;
    Py_BEGIN_ALLOW_THREADS
    s->halt();
    Py_END_ALLOW_THREADS
    Py_RETURN_NONE;
}

// reply_open(sender, fd) -> connection id (> 0); the sender sends on its
// own dup of fd from here on
static PyObject* py_reply_open(PyObject*, PyObject* args) {
    PyObject* cap;
    int fd;
    if (!PyArg_ParseTuple(args, "Oi", &cap, &fd)) return nullptr;
    reply::Sender* s = reply::get(cap);
    if (!s) return nullptr;
    int own = ::fcntl(fd, F_DUPFD_CLOEXEC, 0);
    if (own < 0) return PyErr_SetFromErrno(PyExc_OSError);
    uint64_t id;
    {
        std::lock_guard<std::mutex> lk(s->mu);
        id = s->next_id++;
        reply::Conn* c = new reply::Conn();
        c->fd = own;
        s->conns.emplace(id, c);
    }
    return PyLong_FromUnsignedLongLong(id);
}

// reply_post(sender, buf, ids, ends) -> bytes handed over.  Item i is
// buf[ends[i-1]:ends[i]] (from 0 for the first) for connection ids[i];
// id 0 skips its span.  One call per pass of the loop.
static PyObject* py_reply_post(PyObject*, PyObject* args) {
    PyObject *cap, *ids, *ends;
    Py_buffer view;
    if (!PyArg_ParseTuple(args, "Oy*OO", &cap, &view, &ids, &ends))
        return nullptr;
    reply::Sender* s = reply::get(cap);
    PyObject* fi = s ? PySequence_Fast(ids, "ids must be a sequence") : nullptr;
    PyObject* fe = fi ? PySequence_Fast(ends, "ends must be a sequence")
                      : nullptr;
    if (!fe) {
        Py_XDECREF(fi);
        PyBuffer_Release(&view);
        return nullptr;
    }
    Py_ssize_t n = PySequence_Fast_GET_SIZE(fi);
    std::vector<uint64_t> vid((size_t)n);
    std::vector<Py_ssize_t> vend((size_t)n);
    bool ok = PySequence_Fast_GET_SIZE(fe) == n;
    Py_ssize_t prev = 0;
    for (Py_ssize_t i = 0; ok && i < n; i++) {
        vid[i] = PyLong_AsUnsignedLongLong(PySequence_Fast_GET_ITEM(fi, i));
        vend[i] = PyLong_AsSsize_t(PySequence_Fast_GET_ITEM(fe, i));
        if (PyErr_Occurred()) ok = false;
        else if (vend[i] < prev || vend[i] > view.len) ok = false;
        else prev = vend[i];
    }
    Py_DECREF(fi);
    Py_DECREF(fe);
    if (!ok) {
        PyBuffer_Release(&view);
        if (!PyErr_Occurred())
            PyErr_SetString(PyExc_ValueError, "reply spans out of order");
        return nullptr;
    }
    const char* b = (const char*)view.buf;
    uint64_t total = 0, count = 0;
    bool wake = false;
    {
        std::lock_guard<std::mutex> lk(s->mu);
        Py_ssize_t a = 0;
        for (Py_ssize_t i = 0; i < n; a = vend[i], i++) {
            Py_ssize_t len = vend[i] - a;
            if (!vid[i] || len <= 0) continue;
            auto it = s->conns.find(vid[i]);
            if (it == s->conns.end()) continue;  // released: replies lost
            reply::Conn* c = it->second;
            total += (uint64_t)len;
            count++;
            if (c->dead) continue;
            if (c->loop_owned) {
                // spilled before the loop saw it: joins the hand-back
                c->spill.append(b + a, (size_t)len);
                s->list_spill(vid[i], c);
                continue;
            }
            c->queued.append(b + a, (size_t)len);
            if (!c->ready && !c->sending) {
                c->ready = true;
                s->ready.push_back(vid[i]);
            }
        }
        if (s->parked && !s->ready.empty()) {
            s->parked = false;
            wake = true;
        }
        s->post_seq.fetch_add(1, std::memory_order_release);
    }
    PyBuffer_Release(&view);
    if (wake) {
        s->wakes.fetch_add(1, std::memory_order_relaxed);
        s->work_cv.notify_one();
    }
    s->posts.fetch_add(count, std::memory_order_relaxed);
    s->bytes.fetch_add(total, std::memory_order_relaxed);
    return PyLong_FromUnsignedLongLong(total);
}

// reply_detach(sender, id, release) -> bytes | None: wait out a send in
// flight, take back everything the sender holds for the connection (what
// was handed back first, then what was queued) and give it to the loop's
// transport; release=1 also closes the sender's dup and forgets the id
static PyObject* py_reply_detach(PyObject*, PyObject* args) {
    PyObject* cap;
    unsigned long long id;
    int release;
    if (!PyArg_ParseTuple(args, "OKp", &cap, &id, &release)) return nullptr;
    reply::Sender* s = reply::get(cap);
    if (!s) return nullptr;
    std::string held;
    Py_BEGIN_ALLOW_THREADS
    {
        std::unique_lock<std::mutex> lk(s->mu);
        auto it = s->conns.find(id);
        if (it != s->conns.end()) {
            reply::Conn* c = it->second;
            s->detach_waiters++;
            s->idle_cv.wait(lk, [c] { return !c->sending; });
            s->detach_waiters--;
            if (!c->dead) {
                held.swap(c->spill);
                held += c->queued;
            }
            c->spill.clear();
            c->queued.clear();
            c->loop_owned = true;
            if (release) {
                // a stale FIFO or spill-list entry finds no id and is skipped
                ::close(c->fd);
                delete c;
                s->conns.erase(it);
            }
        }
    }
    Py_END_ALLOW_THREADS
    return reply::bytes_or_none(held);
}

// reply_resume(sender, id) -> None (the connection is the sender's again)
// | bytes (still handed back: the loop writes them and stays on its
// transport)
static PyObject* py_reply_resume(PyObject*, PyObject* args) {
    PyObject* cap;
    unsigned long long id;
    if (!PyArg_ParseTuple(args, "OK", &cap, &id)) return nullptr;
    reply::Sender* s = reply::get(cap);
    if (!s) return nullptr;
    std::string held;
    {
        std::lock_guard<std::mutex> lk(s->mu);
        auto it = s->conns.find(id);
        if (it != s->conns.end()) {
            reply::Conn* c = it->second;
            if (c->spill.empty()) c->loop_owned = false;
            else held.swap(c->spill);
        }
    }
    return reply::bytes_or_none(held);
}

// reply_take_spills(sender) -> [(id, bytes)]: every connection's
// hand-back, in the order they spilled (clears the eventfd)
static PyObject* py_reply_take_spills(PyObject*, PyObject* args) {
    PyObject* cap;
    if (!PyArg_ParseTuple(args, "O", &cap)) return nullptr;
    reply::Sender* s = reply::get(cap);
    if (!s) return nullptr;
    std::vector<std::pair<uint64_t, std::string>> got;
    {
        std::lock_guard<std::mutex> lk(s->mu);
        uint64_t cnt;
        if (s->efd >= 0) {
            ssize_t r = ::read(s->efd, &cnt, sizeof cnt);
            (void)r;  // EAGAIN: nothing signalled since the last read
        }
        for (uint64_t id : s->spilled) {
            auto it = s->conns.find(id);
            if (it == s->conns.end()) continue;
            reply::Conn* c = it->second;
            c->listed = false;
            if (c->spill.empty()) continue;
            got.emplace_back(id, std::string());
            got.back().second.swap(c->spill);
        }
        s->spilled.clear();
    }
    PyObject* out = PyList_New((Py_ssize_t)got.size());
    if (!out) return nullptr;
    for (size_t i = 0; i < got.size(); i++) {
        PyObject* t = Py_BuildValue(
            "(Ky#)", (unsigned long long)got[i].first, got[i].second.data(),
            (Py_ssize_t)got[i].second.size());
        if (!t) {
            Py_DECREF(out);
            return nullptr;
        }
        PyList_SET_ITEM(out, (Py_ssize_t)i, t);
    }
    return out;
}

// reply_stats(sender) -> (posts, bytes, wakes, spills, send_us)
static PyObject* py_reply_stats(PyObject*, PyObject* args) {
    PyObject* cap;
    if (!PyArg_ParseTuple(args, "O", &cap)) return nullptr;
    reply::Sender* s = reply::get(cap);
    if (!s) return nullptr;
    return Py_BuildValue(
        "(KKKKK)", (unsigned long long)s->posts.load(),
        (unsigned long long)s->bytes.load(),
        (unsigned long long)s->wakes.load(),
        (unsigned long long)s->spills.load(),
        (unsigned long long)(s->send_ns.load() / 1000));
}
