// maxmq_sender — CPython extension: the thread that writes the flush
// pass's bursts to their sockets (ADR 019, "Who writes a socket").
//
// Why a thread of its own, and why it never holds the interpreter: a
// burst's send on the loop thread is ~60-75 us of kernel work (TCP
// transmit, the loopback receive path in softirq, the wake-up of the
// reading process) and none of it needs Python. ADR 014's lesson is that
// every interpreter crossing of a helper thread is a wait of the loop's,
// so this thread touches no Python object, calls no C-API function and
// never takes the GIL; the loop hands it bytes (``submit``, a copy made
// with the GIL held) and reads back what it could not finish (``events``).
//
// Per fd a FIFO of bytes, written with the same non-blocking send asyncio
// performs. Three guarantees:
//   order        one FIFO an fd; the loop never writes through the
//                transport while this FIFO holds bytes (client.py)
//   back-pressure a short write is not retried here: the remainder, and
//                whatever queued behind it, goes back to the loop (SPILL
//                event), which gives it to the transport's own buffer
//   FIN          the thread writes to a dup of the socket: closing the
//                transport's fd sends no FIN while bytes are held; the
//                dup is closed once the FIFO is empty after ``forget``

#define PY_SSIZE_T_CLEAN
#include <Python.h>

#include <errno.h>
#include <fcntl.h>
#include <pthread.h>
#include <signal.h>
#include <sys/eventfd.h>
#include <sys/socket.h>
#include <unistd.h>

#include <chrono>
#include <condition_variable>
#include <cstdint>
#include <cstring>
#include <deque>
#include <mutex>
#include <string>
#include <thread>
#include <unordered_map>
#include <utility>
#include <vector>

namespace {

enum State : uint8_t {
  IDLE,     // holds nothing
  QUEUED,   // bytes waiting in the ready list
  BUSY,     // the thread is in send() with this fd's bytes
  SPILLED,  // a short write: the rest waits for the loop (events())
  FAILED,   // the socket refused a write: bytes dropped, submits too
};

enum Kind : int { EV_IDLE = 0, EV_SPILL = 1, EV_ERROR = 2 };

struct Channel {
  uint64_t handle;
  int fd;                    // the dup this thread writes and closes
  State state = IDLE;
  bool forgotten = false;    // the loop is done with it: close when empty
  bool watched = false;      // the loop waits for it to go idle
  std::vector<char> pending; // the loop's appends
  std::vector<char> writing; // the thread's, swapped from pending
  size_t off = 0;            // written of ``writing``
  uint64_t npending = 0;     // submits in each buffer
  uint64_t nwriting = 0;
};

// a buffer that grew past this is given back once empty: a socket keeps
// its few hundred bytes of capacity, not the largest burst it ever had
constexpr size_t KEEP_BYTES = 16384;

void reset_buf(std::vector<char> &v) {
  if (v.capacity() > KEEP_BYTES)
    std::vector<char>().swap(v);
  else
    v.clear();
}

struct Event {
  uint64_t handle;
  int kind;
  int err;
};

struct Core {
  std::mutex mu;
  std::condition_variable cv;
  std::unordered_map<uint64_t, Channel *> chans;
  std::deque<Channel *> ready;
  std::vector<Event> events;
  std::thread thread;
  int efd = -1;
  uint64_t next = 1;
  bool sleeping = false;
  bool stop = false;
  bool closed = false;
  // the five exported counters
  uint64_t bursts = 0, spills = 0, errors = 0, wakes = 0, busy_ns = 0;
};

// with ``mu`` held
void drop(Core *c, Channel *ch) {
  close(ch->fd);
  c->chans.erase(ch->handle);
  delete ch;
}

// with ``mu`` held: one eventfd write when the list turns non-empty
void post(Core *c, uint64_t handle, int kind, int err) {
  bool was_empty = c->events.empty();
  c->events.push_back({handle, kind, err});
  if (was_empty) {
    uint64_t one = 1;
    ssize_t r = write(c->efd, &one, sizeof one);
    (void)r;  // EAGAIN only at a counter of 2^64-2: already readable
  }
}

void run(Core *c) {
  std::unique_lock<std::mutex> lk(c->mu);
  for (;;) {
    while (c->ready.empty() && !c->stop) {
      c->sleeping = true;
      c->cv.wait(lk);
    }
    c->sleeping = false;
    if (c->ready.empty()) return;  // stop, and nothing left to write
    Channel *ch = c->ready.front();
    c->ready.pop_front();
    std::swap(ch->writing, ch->pending);
    ch->nwriting = ch->npending;
    ch->npending = 0;
    ch->off = 0;
    ch->state = BUSY;
    const int fd = ch->fd;
    const char *data = ch->writing.data();
    const size_t len = ch->writing.size();
    lk.unlock();
    auto t0 = std::chrono::steady_clock::now();
    ssize_t n;
    do {
      n = send(fd, data, len, MSG_NOSIGNAL | MSG_DONTWAIT);
    } while (n < 0 && errno == EINTR);
    const int err = n < 0 ? errno : 0;
    auto t1 = std::chrono::steady_clock::now();
    lk.lock();
    c->busy_ns += std::chrono::duration_cast<std::chrono::nanoseconds>(
        t1 - t0).count();
    if (n >= 0 && static_cast<size_t>(n) == len) {
      c->bursts += ch->nwriting;
      reset_buf(ch->writing);
      ch->nwriting = 0;
      if (!ch->pending.empty()) {
        ch->state = QUEUED;        // appended while we wrote
        c->ready.push_back(ch);
      } else {
        ch->state = IDLE;
        if (ch->watched) {
          ch->watched = false;
          post(c, ch->handle, EV_IDLE, 0);
        }
        if (ch->forgotten) drop(c, ch);
      }
    } else if (n >= 0 || err == EAGAIN || err == EWOULDBLOCK) {
      ch->off = n > 0 ? static_cast<size_t>(n) : 0;
      ch->state = SPILLED;
      post(c, ch->handle, EV_SPILL, 0);
    } else {
      c->errors += 1;
      reset_buf(ch->writing);
      reset_buf(ch->pending);
      ch->nwriting = ch->npending = 0;
      ch->state = FAILED;
      post(c, ch->handle, EV_ERROR, err);
      if (ch->forgotten) drop(c, ch);
    }
  }
}

// ----------------------------------------------------------------- //
//  the Python type                                                  //
// ----------------------------------------------------------------- //

struct SenderObject {
  PyObject_HEAD
  Core *core;
};

void shutdown_core(Core *c) {
  {
    std::lock_guard<std::mutex> lk(c->mu);
    if (c->closed) return;
    c->closed = true;
    c->stop = true;
  }
  c->cv.notify_one();
  Py_BEGIN_ALLOW_THREADS
  if (c->thread.joinable()) c->thread.join();
  Py_END_ALLOW_THREADS
  std::lock_guard<std::mutex> lk(c->mu);
  for (auto &kv : c->chans) {
    close(kv.second->fd);
    delete kv.second;
  }
  c->chans.clear();
  c->ready.clear();
  c->events.clear();
  if (c->efd >= 0) close(c->efd);
  c->efd = -1;
}

PyObject *sender_new(PyTypeObject *type, PyObject *, PyObject *) {
  auto *self = reinterpret_cast<SenderObject *>(type->tp_alloc(type, 0));
  if (!self) return nullptr;
  auto *c = new Core();
  c->efd = eventfd(0, EFD_NONBLOCK | EFD_CLOEXEC);
  if (c->efd < 0) {
    delete c;
    Py_DECREF(self);
    return PyErr_SetFromErrno(PyExc_OSError);
  }
  // no signal is ever delivered to this thread: Python's handlers
  // belong to the main thread, and a send is never interrupted
  sigset_t all, old;
  sigfillset(&all);
  pthread_sigmask(SIG_BLOCK, &all, &old);
  try {
    c->thread = std::thread(run, c);
  } catch (const std::exception &) {
    pthread_sigmask(SIG_SETMASK, &old, nullptr);
    close(c->efd);
    delete c;
    Py_DECREF(self);
    PyErr_SetString(PyExc_RuntimeError, "sender thread did not start");
    return nullptr;
  }
  pthread_sigmask(SIG_SETMASK, &old, nullptr);
  self->core = c;
  return reinterpret_cast<PyObject *>(self);
}

void sender_dealloc(PyObject *o) {
  auto *self = reinterpret_cast<SenderObject *>(o);
  if (self->core) {
    shutdown_core(self->core);
    delete self->core;
    self->core = nullptr;
  }
  PyTypeObject *tp = Py_TYPE(o);
  tp->tp_free(o);
  Py_DECREF(tp);  // heap types own a ref from each instance
}

Core *open_core(PyObject *o) {
  Core *c = reinterpret_cast<SenderObject *>(o)->core;
  if (c->closed) {
    errno = EBADF;
    PyErr_SetFromErrno(PyExc_OSError);
    return nullptr;
  }
  return c;
}

PyObject *sender_fileno(PyObject *o, PyObject *) {
  return PyLong_FromLong(reinterpret_cast<SenderObject *>(o)->core->efd);
}

PyObject *sender_open(PyObject *o, PyObject *arg) {
  Core *c = open_core(o);
  if (!c) return nullptr;
  int fd = PyObject_AsFileDescriptor(arg);
  if (fd < 0) return nullptr;
  int dup_fd = fcntl(fd, F_DUPFD_CLOEXEC, 0);
  if (dup_fd < 0) return PyErr_SetFromErrno(PyExc_OSError);
  auto *ch = new Channel();
  ch->fd = dup_fd;
  std::lock_guard<std::mutex> lk(c->mu);
  ch->handle = c->next++;
  c->chans.emplace(ch->handle, ch);
  return PyLong_FromUnsignedLongLong(ch->handle);
}

// submit(handle, bufs): copy a burst's bytes into the fd's FIFO. True
// when queued; False where the socket already refused a write (FAILED)
// or the handle is gone: the bytes go nowhere, as the transport's would.
PyObject *sender_submit(PyObject *o, PyObject *const *args,
                        Py_ssize_t nargs) {
  if (nargs != 2) {
    PyErr_SetString(PyExc_TypeError, "submit(handle, bufs)");
    return nullptr;
  }
  Core *c = open_core(o);
  if (!c) return nullptr;
  uint64_t handle = PyLong_AsUnsignedLongLong(args[0]);
  if (handle == static_cast<uint64_t>(-1) && PyErr_Occurred()) return nullptr;
  PyObject *seq = PySequence_Fast(args[1], "bufs must be a sequence");
  if (!seq) return nullptr;
  Py_ssize_t n = PySequence_Fast_GET_SIZE(seq);
  PyObject **items = PySequence_Fast_ITEMS(seq);
  std::vector<Py_buffer> views(static_cast<size_t>(n));
  Py_ssize_t got = 0;
  size_t total = 0;
  for (; got < n; ++got) {
    if (PyObject_GetBuffer(items[got], &views[got], PyBUF_SIMPLE) < 0) break;
    total += static_cast<size_t>(views[got].len);
  }
  PyObject *ret = nullptr;
  if (got == n) {
    bool queued = false;
    {
      std::lock_guard<std::mutex> lk(c->mu);
      auto it = c->chans.find(handle);
      if (it != c->chans.end() && it->second->state != FAILED) {
        Channel *ch = it->second;
        size_t at = ch->pending.size();
        ch->pending.resize(at + total);
        char *dst = ch->pending.data() + at;
        for (Py_ssize_t i = 0; i < n; ++i) {
          std::memcpy(dst, views[i].buf, static_cast<size_t>(views[i].len));
          dst += views[i].len;
        }
        ch->npending += 1;
        if (ch->state == IDLE) {
          ch->state = QUEUED;
          c->ready.push_back(ch);
        }
        queued = true;
      }
    }
    ret = Py_NewRef(queued ? Py_True : Py_False);
  }
  for (Py_ssize_t i = 0; i < got; ++i) PyBuffer_Release(&views[i]);
  Py_DECREF(seq);
  return ret;
}

Channel *find(Core *c, PyObject *arg, bool *bad) {
  uint64_t handle = PyLong_AsUnsignedLongLong(arg);
  *bad = handle == static_cast<uint64_t>(-1) && PyErr_Occurred();
  if (*bad) return nullptr;
  auto it = c->chans.find(handle);
  return it == c->chans.end() ? nullptr : it->second;
}

// idle(handle): the sender holds nothing for this fd (nothing queued,
// in a send, or handed back and not yet collected)
PyObject *sender_idle(PyObject *o, PyObject *arg) {
  Core *c = reinterpret_cast<SenderObject *>(o)->core;
  bool bad;
  std::lock_guard<std::mutex> lk(c->mu);
  Channel *ch = find(c, arg, &bad);
  if (bad) return nullptr;
  return Py_NewRef(!ch || ch->state == IDLE || ch->state == FAILED
                       ? Py_True : Py_False);
}

// watch(handle): idle() now, or False and an IDLE event when it drains
// (a spill's event stands in for it: the loop empties the FIFO there)
PyObject *sender_watch(PyObject *o, PyObject *arg) {
  Core *c = reinterpret_cast<SenderObject *>(o)->core;
  bool bad;
  std::lock_guard<std::mutex> lk(c->mu);
  Channel *ch = find(c, arg, &bad);
  if (bad) return nullptr;
  if (!ch || ch->state == IDLE || ch->state == FAILED) Py_RETURN_TRUE;
  ch->watched = true;
  Py_RETURN_FALSE;
}

// kick(): wake the thread if it sleeps and has work; one futex at most
PyObject *sender_kick(PyObject *o, PyObject *) {
  Core *c = reinterpret_cast<SenderObject *>(o)->core;
  bool wake = false;
  {
    std::lock_guard<std::mutex> lk(c->mu);
    if (c->sleeping && !c->ready.empty()) {
      c->sleeping = false;
      c->wakes += 1;
      wake = true;
    }
  }
  if (wake) c->cv.notify_one();
  Py_RETURN_NONE;
}

// forget(handle): no more submits; the dup closes once the FIFO is empty
PyObject *sender_forget(PyObject *o, PyObject *arg) {
  Core *c = reinterpret_cast<SenderObject *>(o)->core;
  bool bad;
  std::lock_guard<std::mutex> lk(c->mu);
  Channel *ch = find(c, arg, &bad);
  if (bad) return nullptr;
  if (ch) {
    if (ch->state == IDLE || ch->state == FAILED)
      drop(c, ch);
    else
      ch->forgotten = true;
  }
  Py_RETURN_NONE;
}

// events(): [(handle, kind, payload)] since the last call. kind 0: idle
// (payload None); 1: spill (payload the bytes not written, in order, now
// the loop's; the fd is idle again); 2: error (payload errno)
PyObject *sender_events(PyObject *o, PyObject *) {
  Core *c = reinterpret_cast<SenderObject *>(o)->core;
  if (c->efd < 0) return PyList_New(0);
  uint64_t count;
  ssize_t r = read(c->efd, &count, sizeof count);
  (void)r;  // EAGAIN: nothing signalled, but look anyway
  std::vector<Event> evs;
  std::vector<std::string> spilled;
  {
    std::lock_guard<std::mutex> lk(c->mu);
    evs.swap(c->events);
    for (const Event &e : evs) {
      if (e.kind != EV_SPILL) continue;
      std::string data;
      auto it = c->chans.find(e.handle);
      if (it != c->chans.end() && it->second->state == SPILLED) {
        Channel *ch = it->second;
        data.reserve(ch->writing.size() - ch->off + ch->pending.size());
        data.append(ch->writing.data() + ch->off, ch->writing.size() - ch->off);
        data.append(ch->pending.data(), ch->pending.size());
        c->spills += ch->nwriting + ch->npending;
        reset_buf(ch->writing);
        reset_buf(ch->pending);
        ch->nwriting = ch->npending = 0;
        ch->off = 0;
        ch->state = IDLE;
        ch->watched = false;
        if (ch->forgotten) drop(c, ch);
      }
      spilled.push_back(std::move(data));
    }
  }
  PyObject *out = PyList_New(static_cast<Py_ssize_t>(evs.size()));
  if (!out) return nullptr;
  size_t s = 0;
  for (size_t i = 0; i < evs.size(); ++i) {
    const Event &e = evs[i];
    PyObject *payload;
    if (e.kind == EV_SPILL) {
      const std::string &d = spilled[s++];
      payload = PyBytes_FromStringAndSize(d.data(),
                                          static_cast<Py_ssize_t>(d.size()));
    } else if (e.kind == EV_ERROR) {
      payload = PyLong_FromLong(e.err);
    } else {
      payload = Py_NewRef(Py_None);
    }
    PyObject *t = payload ? Py_BuildValue("(KiN)", static_cast<unsigned long long>(
                                              e.handle), e.kind, payload)
                          : nullptr;
    if (!t) {
      Py_DECREF(out);
      return nullptr;
    }
    PyList_SET_ITEM(out, static_cast<Py_ssize_t>(i), t);
  }
  return out;
}

// stats(): (bursts, spills, errors, busy_seconds, wakes)
PyObject *sender_stats(PyObject *o, PyObject *) {
  Core *c = reinterpret_cast<SenderObject *>(o)->core;
  std::lock_guard<std::mutex> lk(c->mu);
  return Py_BuildValue("(KKKdK)", static_cast<unsigned long long>(c->bursts),
                       static_cast<unsigned long long>(c->spills),
                       static_cast<unsigned long long>(c->errors),
                       static_cast<double>(c->busy_ns) / 1e9,
                       static_cast<unsigned long long>(c->wakes));
}

// close(): write what is queued, stop the thread, close every dup
PyObject *sender_close(PyObject *o, PyObject *) {
  shutdown_core(reinterpret_cast<SenderObject *>(o)->core);
  Py_RETURN_NONE;
}

PyMethodDef sender_methods[] = {
    {"fileno", sender_fileno, METH_NOARGS,
     "The eventfd that turns readable when events() has something."},
    {"open", sender_open, METH_O,
     "open(sock_or_fd) -> handle: a FIFO for a dup of this socket."},
    {"submit", reinterpret_cast<PyCFunction>(
                   reinterpret_cast<void (*)(void)>(sender_submit)),
     METH_FASTCALL, "submit(handle, bufs) -> bool: copy a burst in."},
    {"idle", sender_idle, METH_O, "idle(handle) -> bool"},
    {"watch", sender_watch, METH_O,
     "watch(handle) -> bool: idle now, else an idle event later."},
    {"kick", sender_kick, METH_NOARGS, "Wake the thread if it sleeps."},
    {"forget", sender_forget, METH_O,
     "forget(handle): close the dup once its FIFO is empty."},
    {"events", sender_events, METH_NOARGS,
     "events() -> [(handle, kind, payload)]"},
    {"stats", sender_stats, METH_NOARGS,
     "stats() -> (bursts, spills, errors, busy_seconds, wakes)"},
    {"close", sender_close, METH_NOARGS, "Stop the thread (idempotent)."},
    {nullptr, nullptr, 0, nullptr}};

PyType_Slot sender_slots[] = {
    {Py_tp_doc, const_cast<char *>(
                    "Sender(): one writer thread, a FIFO a socket.")},
    {Py_tp_new, reinterpret_cast<void *>(sender_new)},
    {Py_tp_dealloc, reinterpret_cast<void *>(sender_dealloc)},
    {Py_tp_methods, sender_methods},
    {0, nullptr}};

PyType_Spec sender_spec = {"maxmq_sender.Sender", sizeof(SenderObject), 0,
                           Py_TPFLAGS_DEFAULT, sender_slots};

PyModuleDef sender_module = {
    PyModuleDef_HEAD_INIT, "maxmq_sender",
    "The flush pass's socket writer: one thread that never holds the "
    "interpreter (native/maxmq_sender.cpp).",
    -1, nullptr, nullptr, nullptr, nullptr, nullptr};

}  // namespace

PyMODINIT_FUNC PyInit_maxmq_sender(void) {
  PyObject *m = PyModule_Create(&sender_module);
  if (!m) return nullptr;
  PyObject *type = PyType_FromSpec(&sender_spec);
  if (!type || PyModule_AddObjectRef(m, "Sender", type) < 0 ||
      PyModule_AddIntConstant(m, "IDLE", EV_IDLE) < 0 ||
      PyModule_AddIntConstant(m, "SPILL", EV_SPILL) < 0 ||
      PyModule_AddIntConstant(m, "ERROR", EV_ERROR) < 0) {
    Py_XDECREF(type);
    Py_DECREF(m);
    return nullptr;
  }
  Py_DECREF(type);
  return m;
}
