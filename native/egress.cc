// Socket writes off the event loop (broker/egress.py).
//
// The broker's Outbox hands every chunk list of a flush to ONE call of
// Writer.submit; a writer thread, which never takes the interpreter
// lock, sends each connection's hand-offs back to back with
// non-blocking send / sendmsg, in hand-off order. Bytes a send could
// not take (EAGAIN, a partial write) stay at the head of that
// connection's backlog until its descriptor reports EPOLLOUT on the
// writer's own epoll; later hand-offs of the connection queue behind
// them.
//
// Descriptor lifetime: the writer sends on a dup of the connection's
// socket that it owns, never on the loop's descriptor number, so the
// loop's transport may close its own descriptor at any time (and the
// number be reused by the next accept) without a byte reaching the
// wrong peer. Connections are named by ids that are never reused.
// close(id, drain=True) closes the dup after the last byte (the FIN
// follows it); close(id, drain=False) drops the backlog and closes at
// once (the socket is lost). A send that fails with EPIPE, ECONNRESET
// or any other error drops the backlog and closes the dup; the read
// side sees the loss as it always did.
//
// Buffers: a hand-off of at most join_max bytes is copied (joined) into
// the record at hand-off; a larger one borrows its chunks' buffers
// (PyObject_GetBuffer, under the lock), which the loop releases at a
// later hand-off once the writer has finished with them.
//
// The core (namespace egress, and the eg_* C entry points that
// tsan_stress.cc drives) touches no Python object; the CPython module
// is compiled unless VMQ_EGRESS_CORE_ONLY is defined.

#include <atomic>
#include <cerrno>
#include <cstdint>
#include <cstring>
#include <deque>
#include <mutex>
#include <new>
#include <thread>
#include <unordered_map>
#include <vector>

#include <fcntl.h>
#include <pthread.h>
#include <sched.h>
#include <sys/epoll.h>
#include <sys/eventfd.h>
#include <sys/socket.h>
#include <sys/uio.h>
#include <time.h>
#include <unistd.h>

namespace egress {

struct Piece {
  const char* p;
  size_t n;
};

// One hand-off: the chunks one transport collected for one flush. A
// joined hand-off's bytes sit right behind the record, in the same
// allocation (Rec::make / Rec::drop).
struct Rec {
  const char* joined = nullptr;  // the joined bytes, or null
  std::vector<Piece> pieces;     // borrowed buffers (a large hand-off)
  void* keep = nullptr;          // what keeps `pieces` alive: the owner's
  size_t total = 0;
  size_t off = 0;  // bytes already sent
  int64_t t0 = 0;  // hand-off time, CLOCK_MONOTONIC ns

  static Rec* make(size_t joined_bytes) {
    void* mem = ::operator new(sizeof(Rec) + joined_bytes);
    Rec* r = new (mem) Rec();
    if (joined_bytes) r->joined = reinterpret_cast<const char*>(r + 1);
    return r;
  }
  static void drop(Rec* r) {
    r->~Rec();
    ::operator delete(r);
  }
  char* joined_buf() { return reinterpret_cast<char*>(this + 1); }
};

enum Op { ATTACH, DATA, CLOSE, DROP };

struct Cmd {
  Op op;
  uint64_t id;
  int fd;
  Rec* rec;
};

inline int64_t now_ns() {
  timespec ts;
  clock_gettime(CLOCK_MONOTONIC, &ts);
  return int64_t(ts.tv_sec) * 1000000000LL + ts.tv_nsec;
}

class Writer {
 public:
  Writer() {
    ep_ = epoll_create1(EPOLL_CLOEXEC);
    ev_ = eventfd(0, EFD_CLOEXEC | EFD_NONBLOCK);
    if (ep_ < 0 || ev_ < 0) return;
    epoll_event e{};
    e.events = EPOLLIN;
    e.data.u64 = 0;  // ids start at 1: 0 is the wake-up
    if (epoll_ctl(ep_, EPOLL_CTL_ADD, ev_, &e) != 0) return;
    thread_ = std::thread([this] { run(); });
  }

  ~Writer() {
    stop();
    if (ep_ >= 0) close(ep_);
    if (ev_ >= 0) close(ev_);
  }

  bool ok() const { return thread_.joinable(); }

  // A dup of `fd` the writer owns from now on; its id, or 0 (errno set).
  uint64_t attach(int fd) {
    if (stopped_) {
      errno = ESHUTDOWN;
      return 0;
    }
    int d = fcntl(fd, F_DUPFD_CLOEXEC, 0);
    if (d < 0) return 0;
    uint64_t id = next_id_.fetch_add(1, std::memory_order_relaxed);
    std::lock_guard<std::mutex> g(mu_);
    in_.push_back(Cmd{ATTACH, id, d, nullptr});  // sent with the next wake
    return id;
  }

  // Queue `cmds` in order; wake the thread once if `wake`.
  void push(std::vector<Cmd>& cmds, bool wake) {
    if (stopped_) {
      for (Cmd& c : cmds) discard(c);
      cmds.clear();
      return;
    }
    {
      std::lock_guard<std::mutex> g(mu_);
      in_.insert(in_.end(), cmds.begin(), cmds.end());
    }
    cmds.clear();
    if (wake) {
      uint64_t one = 1;
      ssize_t r = write(ev_, &one, sizeof one);
      (void)r;  // EAGAIN: the counter is full, the thread is awake anyway
    }
  }

  // Counters since the last take, and the records with a `keep` the
  // writer is done with (the caller releases them).
  void take(uint64_t* sent, uint64_t* lag_ns, uint64_t* dropped,
            std::vector<Rec*>* done) {
    if (sent) *sent = sent_.exchange(0, std::memory_order_relaxed);
    if (lag_ns) *lag_ns = lag_ns_.exchange(0, std::memory_order_relaxed);
    if (dropped) *dropped = dropped_.exchange(0, std::memory_order_relaxed);
    std::lock_guard<std::mutex> g(mu_);
    done->insert(done->end(), done_.begin(), done_.end());
    done_.clear();
  }

  // Join the thread. It first applies what was queued and tries each
  // backlog once more without blocking; then every descriptor is
  // closed and every record still held goes to `done`.
  void stop() {
    if (stopped_) return;
    stopped_ = true;
    if (thread_.joinable() && getpid() != owner_) {
      thread_.detach();  // a forked child: the thread was never its own
    } else if (thread_.joinable()) {
      stop_.store(true, std::memory_order_release);
      uint64_t one = 1;
      ssize_t r = write(ev_, &one, sizeof one);
      (void)r;
      thread_.join();
    }
    std::lock_guard<std::mutex> g(mu_);
    for (Cmd& c : in_) discard_locked(c);
    in_.clear();
  }

 private:
  struct Conn {
    int fd = -1;
    std::deque<Rec*> q;
    bool registered = false;  // in the epoll set
    bool armed = false;       // waiting for EPOLLOUT
    bool marked = false;      // in ready_
    bool closing = false;     // close after the last byte
  };

  void run() {
    // a batch thread never preempts the thread that woke it: the loop
    // hands off and goes on, the writer takes a core of its own or
    // waits for the loop's slice to end
    sched_param sp{};
    pthread_setschedparam(pthread_self(), SCHED_BATCH, &sp);
    epoll_event evs[128];
    std::vector<Cmd> cmds;
    for (;;) {
      int n = epoll_wait(ep_, evs, 128, -1);
      if (n < 0) n = 0;  // EINTR
      for (int i = 0; i < n; i++) {
        uint64_t id = evs[i].data.u64;
        if (id == 0) {
          uint64_t x;
          ssize_t r = read(ev_, &x, sizeof x);
          (void)r;
          continue;
        }
        auto it = conns_.find(id);
        if (it != conns_.end()) {
          it->second.armed = false;
          mark(id, it->second);
        }
      }
      {
        std::lock_guard<std::mutex> g(mu_);
        cmds.swap(in_);
      }
      for (Cmd& c : cmds) apply(c);
      cmds.clear();
      pump_ready();
      if (stop_.load(std::memory_order_acquire)) {
        finish();
        return;
      }
    }
  }

  void apply(const Cmd& c) {
    if (c.op == ATTACH) {
      conns_[c.id].fd = c.fd;
      return;
    }
    auto it = conns_.find(c.id);
    if (it == conns_.end()) {
      if (c.rec) dispose(c.rec);
      return;
    }
    Conn& k = it->second;
    switch (c.op) {
      case DATA:
        if (k.fd < 0 || k.closing) {
          dispose(c.rec);
          return;
        }
        k.q.push_back(c.rec);
        mark(c.id, k);
        return;
      case CLOSE:
        k.closing = true;
        mark(c.id, k);
        return;
      case DROP:
        lose(k, true);
        conns_.erase(it);
        return;
      default:
        return;
    }
  }

  void mark(uint64_t id, Conn& k) {
    if (!k.marked) {
      k.marked = true;
      ready_.push_back(id);
    }
  }

  void pump_ready() {
    for (uint64_t id : ready_) {
      auto it = conns_.find(id);
      if (it == conns_.end()) continue;
      it->second.marked = false;
      if (pump(id, it->second)) conns_.erase(it);
    }
    ready_.clear();
    hand_back();
  }

  // Send what the connection holds, in order; true when it is to go.
  bool pump(uint64_t id, Conn& k) {
    if (k.armed) return false;  // waiting for EPOLLOUT
    while (k.fd >= 0 && !k.q.empty()) {
      Rec* r = k.q.front();
      ssize_t n = send_rec(k.fd, r);
      if (n < 0) {
        if (errno == EINTR) continue;
        if (errno == EAGAIN || errno == EWOULDBLOCK) {
          arm(id, k);
          return false;
        }
        lose(k, true);  // EPIPE, ECONNRESET, ...: the connection is gone
        break;
      }
      r->off += size_t(n);
      if (r->off < r->total) continue;
      sent_.fetch_add(1, std::memory_order_relaxed);
      lag_ns_.fetch_add(uint64_t(now_ns() - r->t0),
                        std::memory_order_relaxed);
      k.q.pop_front();
      dispose(r);
    }
    if (!k.closing) return false;
    lose(k, false);  // drained (or lost): release the descriptor
    return true;
  }

  static ssize_t send_rec(int fd, Rec* r) {
    const int fl = MSG_NOSIGNAL | MSG_DONTWAIT;
    if (r->pieces.empty())
      return send(fd, r->joined + r->off, r->total - r->off, fl);
    iovec iov[64];
    size_t skip = r->off;
    int n = 0;
    for (const Piece& p : r->pieces) {
      if (skip >= p.n) {
        skip -= p.n;
        continue;
      }
      iov[n].iov_base = const_cast<char*>(p.p + skip);
      iov[n].iov_len = p.n - skip;
      skip = 0;
      if (++n == 64) break;
    }
    msghdr m{};
    m.msg_iov = iov;
    m.msg_iovlen = size_t(n);
    return sendmsg(fd, &m, fl);
  }

  void arm(uint64_t id, Conn& k) {
    epoll_event e{};
    e.events = EPOLLOUT | EPOLLONESHOT;
    e.data.u64 = id;
    if (epoll_ctl(ep_, k.registered ? EPOLL_CTL_MOD : EPOLL_CTL_ADD, k.fd,
                  &e) == 0) {
      k.registered = k.armed = true;
    } else {
      lose(k, true);
    }
  }

  // Drop the backlog (counted when `count` and it held anything) and
  // close the descriptor.
  void lose(Conn& k, bool count) {
    if (count && !k.q.empty())
      dropped_.fetch_add(1, std::memory_order_relaxed);
    for (Rec* r : k.q) dispose(r);
    k.q.clear();
    if (k.fd >= 0) {
      // the loop's descriptor may still share the open file: take the
      // registration off explicitly, close() would not
      if (k.registered) epoll_ctl(ep_, EPOLL_CTL_DEL, k.fd, nullptr);
      close(k.fd);
      k.fd = -1;
    }
    k.registered = k.armed = false;
  }

  void finish() {
    std::vector<Cmd> cmds;
    {
      std::lock_guard<std::mutex> g(mu_);
      cmds.swap(in_);
    }
    for (Cmd& c : cmds) apply(c);
    for (auto& kv : conns_) {
      kv.second.marked = false;
      pump(kv.first, kv.second);
      lose(kv.second, false);
    }
    conns_.clear();
    ready_.clear();
    hand_back();
  }

  void dispose(Rec* r) {
    if (r->keep)
      gone_.push_back(r);
    else
      Rec::drop(r);
  }

  void hand_back() {
    if (gone_.empty()) return;
    std::lock_guard<std::mutex> g(mu_);
    done_.insert(done_.end(), gone_.begin(), gone_.end());
    gone_.clear();
  }

  // a command that never reached the thread (mu_ held)
  void discard_locked(Cmd& c) {
    if (c.op == ATTACH && c.fd >= 0) close(c.fd);
    if (c.rec) {
      if (c.rec->keep)
        done_.push_back(c.rec);
      else
        Rec::drop(c.rec);
    }
  }

  void discard(Cmd& c) {
    std::lock_guard<std::mutex> g(mu_);
    discard_locked(c);
  }

  int ep_ = -1;
  int ev_ = -1;
  const pid_t owner_ = getpid();
  std::thread thread_;
  std::atomic<bool> stop_{false};
  std::atomic<bool> stopped_{false};  // stop() was called
  std::atomic<uint64_t> next_id_{1};
  std::atomic<uint64_t> sent_{0};
  std::atomic<uint64_t> lag_ns_{0};
  std::atomic<uint64_t> dropped_{0};
  std::mutex mu_;
  std::vector<Cmd> in_;     // owner -> thread (mu_)
  std::vector<Rec*> done_;  // thread -> owner (mu_)
  // the thread's own
  std::unordered_map<uint64_t, Conn> conns_;
  std::vector<uint64_t> ready_;
  std::vector<Rec*> gone_;
};

}  // namespace egress

// ------------------------------------------------------------ C entry
// points (the TSAN stress harness; no Python)

extern "C" {

egress::Writer* eg_create(void) {
  auto* w = new egress::Writer();
  if (!w->ok()) {
    delete w;
    return nullptr;
  }
  return w;
}

uint64_t eg_attach(egress::Writer* w, int fd) { return w->attach(fd); }

// One hand-off of n bytes, copied (the harness's records own their bytes).
void eg_submit(egress::Writer* w, uint64_t id, const char* p, size_t n) {
  egress::Rec* r = egress::Rec::make(n);
  std::memcpy(r->joined_buf(), p, n);
  r->total = n;
  r->t0 = egress::now_ns();
  std::vector<egress::Cmd> cmds{egress::Cmd{egress::DATA, id, -1, r}};
  w->push(cmds, true);
}

void eg_close(egress::Writer* w, uint64_t id, int drain) {
  std::vector<egress::Cmd> cmds{
      egress::Cmd{drain ? egress::CLOSE : egress::DROP, id, -1, nullptr}};
  w->push(cmds, true);
}

void eg_take(egress::Writer* w, uint64_t* sent, uint64_t* lag_ns,
             uint64_t* dropped) {
  std::vector<egress::Rec*> done;
  w->take(sent, lag_ns, dropped, &done);
  for (egress::Rec* r : done) egress::Rec::drop(r);
}

void eg_destroy(egress::Writer* w) {
  w->stop();
  eg_take(w, nullptr, nullptr, nullptr);
  delete w;
}

}  // extern "C"

#ifndef VMQ_EGRESS_CORE_ONLY

#define PY_SSIZE_T_CLEAN
#include <Python.h>

namespace {

struct Keep {
  std::vector<Py_buffer> bufs;
};

// (the interpreter lock held)
void release(std::vector<egress::Rec*>& recs) {
  for (egress::Rec* r : recs) {
    auto* k = static_cast<Keep*>(r->keep);
    for (Py_buffer& b : k->bufs) PyBuffer_Release(&b);
    delete k;
    egress::Rec::drop(r);
  }
  recs.clear();
}

struct WriterObject {
  PyObject_HEAD egress::Writer* w;
  Py_ssize_t join_max;
};

void take_done(WriterObject* self) {
  std::vector<egress::Rec*> done;
  self->w->take(nullptr, nullptr, nullptr, &done);
  release(done);
}

PyObject* Writer_new(PyTypeObject* type, PyObject* args, PyObject* kw) {
  static const char* kwlist[] = {"join_max", nullptr};
  Py_ssize_t join_max = 0;
  if (!PyArg_ParseTupleAndKeywords(args, kw, "n",
                                   const_cast<char**>(kwlist), &join_max))
    return nullptr;
  auto* self = reinterpret_cast<WriterObject*>(type->tp_alloc(type, 0));
  if (self == nullptr) return nullptr;
  self->join_max = join_max;
  self->w = new egress::Writer();
  if (!self->w->ok()) {
    delete self->w;
    self->w = nullptr;
    Py_DECREF(self);
    return PyErr_SetFromErrno(PyExc_OSError);
  }
  return reinterpret_cast<PyObject*>(self);
}

void Writer_dealloc(WriterObject* self) {
  if (self->w != nullptr) {
    self->w->stop();
    take_done(self);
    delete self->w;
  }
  PyTypeObject* type = Py_TYPE(self);
  type->tp_free(reinterpret_cast<PyObject*>(self));
  Py_DECREF(type);  // a heap type
}

PyObject* Writer_attach(WriterObject* self, PyObject* arg) {
  long fd = PyLong_AsLong(arg);
  if (fd == -1 && PyErr_Occurred()) return nullptr;
  uint64_t id = self->w->attach(int(fd));
  if (id == 0) return PyErr_SetFromErrno(PyExc_OSError);
  return PyLong_FromUnsignedLongLong(id);
}

// [id, chunks, id, chunks, ...] -> (handed, joined, scattered)
PyObject* Writer_submit(WriterObject* self, PyObject* batch) {
  if (!PyList_Check(batch)) {
    PyErr_SetString(PyExc_TypeError, "submit takes a list");
    return nullptr;
  }
  take_done(self);
  Py_ssize_t n = PyList_GET_SIZE(batch);
  std::vector<egress::Cmd> cmds;
  cmds.reserve(size_t(n / 2));
  std::vector<egress::Rec*> undo;
  std::vector<Py_buffer> bufs;
  const int64_t t0 = egress::now_ns();
  long joined = 0, scattered = 0;
  for (Py_ssize_t i = 0; i + 1 < n; i += 2) {
    uint64_t id = PyLong_AsUnsignedLongLong(PyList_GET_ITEM(batch, i));
    if (id == uint64_t(-1) && PyErr_Occurred()) goto fail;
    Py_ssize_t nch;
    PyObject** items;
    size_t total;
    egress::Rec* r;
    PyObject* seq = PySequence_Fast(PyList_GET_ITEM(batch, i + 1),
                                    "chunks must be a sequence");
    if (seq == nullptr) goto fail;
    nch = PySequence_Fast_GET_SIZE(seq);
    items = PySequence_Fast_ITEMS(seq);
    bufs.resize(size_t(nch));
    total = 0;
    for (Py_ssize_t j = 0; j < nch; j++) {
      if (PyObject_GetBuffer(items[j], &bufs[size_t(j)], PyBUF_SIMPLE) < 0) {
        for (Py_ssize_t q = 0; q < j; q++) PyBuffer_Release(&bufs[size_t(q)]);
        Py_DECREF(seq);
        goto fail;
      }
      total += size_t(bufs[size_t(j)].len);
    }
    Py_DECREF(seq);
    if (total <= size_t(self->join_max)) {
      r = egress::Rec::make(total);
      char* w = r->joined_buf();
      for (Py_buffer& b : bufs) {
        std::memcpy(w, b.buf, size_t(b.len));
        w += b.len;
        PyBuffer_Release(&b);
      }
      if (nch > 1) joined++;
    } else {
      r = egress::Rec::make(0);
      auto* k = new Keep();
      k->bufs = bufs;  // the views, and the references they hold
      for (Py_buffer& b : k->bufs)
        r->pieces.push_back(
            egress::Piece{static_cast<const char*>(b.buf), size_t(b.len)});
      r->keep = k;
      if (nch > 1) scattered++;
    }
    r->total = total;
    r->t0 = t0;
    cmds.push_back(egress::Cmd{egress::DATA, id, -1, r});
  }
  {
    // the lock stays held: giving it up for one eventfd write would
    // hand the loop's thread to whoever waits for it
    long handed = long(cmds.size());
    self->w->push(cmds, true);
    return Py_BuildValue("(lll)", handed, joined, scattered);
  }
fail:
  for (egress::Cmd& c : cmds) {
    if (c.rec->keep)
      undo.push_back(c.rec);
    else
      egress::Rec::drop(c.rec);
  }
  release(undo);
  return nullptr;
}

PyObject* Writer_close(WriterObject* self, PyObject* args) {
  unsigned long long id = 0;
  int drain = 1;
  if (!PyArg_ParseTuple(args, "Kp", &id, &drain)) return nullptr;
  std::vector<egress::Cmd> cmds{egress::Cmd{
      drain ? egress::CLOSE : egress::DROP, uint64_t(id), -1, nullptr}};
  self->w->push(cmds, true);
  Py_RETURN_NONE;
}

PyObject* Writer_take(WriterObject* self, PyObject*) {
  uint64_t sent = 0, lag_ns = 0, dropped = 0;
  std::vector<egress::Rec*> done;
  self->w->take(&sent, &lag_ns, &dropped, &done);
  release(done);
  return Py_BuildValue("(KKK)", (unsigned long long)sent,
                       (unsigned long long)(lag_ns / 1000),
                       (unsigned long long)dropped);
}

PyObject* Writer_stop(WriterObject* self, PyObject*) {
  Py_BEGIN_ALLOW_THREADS
  self->w->stop();
  Py_END_ALLOW_THREADS
  take_done(self);
  Py_RETURN_NONE;
}

PyMethodDef writer_methods[] = {
    {"attach", reinterpret_cast<PyCFunction>(Writer_attach), METH_O,
     "attach(fd) -> id: the writer takes a dup of the socket `fd`."},
    {"submit", reinterpret_cast<PyCFunction>(Writer_submit), METH_O,
     "submit([id, chunks, ...]) -> (handed, joined, scattered): one "
     "flush's hand-offs, in order, with one wake-up of the thread."},
    {"close", reinterpret_cast<PyCFunction>(Writer_close), METH_VARARGS,
     "close(id, drain): release a connection's dup — after its last "
     "byte (drain) or at once, dropping its backlog."},
    {"take", reinterpret_cast<PyCFunction>(Writer_take), METH_NOARGS,
     "take() -> (sent, lag_us, dropped) since the last take."},
    {"stop", reinterpret_cast<PyCFunction>(Writer_stop), METH_NOARGS,
     "stop(): join the thread and close every descriptor it holds."},
    {nullptr, nullptr, 0, nullptr}};

PyType_Slot writer_slots[] = {
    {Py_tp_doc,
     const_cast<char*>("A writer thread that sends hand-offs in order a "
                       "connection: Writer(join_max).")},
    {Py_tp_new, reinterpret_cast<void*>(Writer_new)},
    {Py_tp_dealloc, reinterpret_cast<void*>(Writer_dealloc)},
    {Py_tp_methods, writer_methods},
    {0, nullptr}};

PyType_Spec writer_spec = {"_vmq_egress.Writer", sizeof(WriterObject), 0,
                           Py_TPFLAGS_DEFAULT, writer_slots};

PyModuleDef module = {PyModuleDef_HEAD_INIT, "_vmq_egress",
                      "Socket writes off the event loop", -1, nullptr,
                      nullptr, nullptr, nullptr, nullptr};

// Bumped whenever a signature or result layout changes (the loader
// refuses an older prebuilt .so).
constexpr long EGRESS_VERSION = 1;

}  // namespace

PyMODINIT_FUNC PyInit__vmq_egress() {
  PyObject* m = PyModule_Create(&module);
  if (m == nullptr) return nullptr;
  PyObject* type = PyType_FromSpec(&writer_spec);
  if (type == nullptr || PyModule_AddObject(m, "Writer", type) < 0 ||
      PyModule_AddIntConstant(m, "EGRESS_VERSION", EGRESS_VERSION) < 0) {
    Py_XDECREF(type);
    Py_DECREF(m);
    return nullptr;
  }
  return m;
}

#endif  // VMQ_EGRESS_CORE_ONLY
