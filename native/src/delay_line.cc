// The timer of tendermint_tpu/p2p/delay_line.py, the only one there is:
// chunks stamped `due` (CLOCK_MONOTONIC seconds, the clock Python's
// time.monotonic() reads) wait in one heap a node; one thread writes each to
// its link's socket at or after `due`, never before, FIFO per link.
//
// Why native: a Python timer thread needs the interpreter lock to wake and
// again after every send(), in a process whose 60-70 threads contend for it;
// 16 such nodes on the benchmark's machine read a mean lateness of 13-21 ms
// and a p95 of 64 ms beside one-way delays of 12.5-156 ms (PERF.md, PR 32).
// This thread never takes the lock.
//
// A link holds a dup() of its socket, so the interpreter closing (or
// reusing) its own descriptor can never turn a queued chunk into a write on
// somebody else's connection. The socket may be in non-blocking mode (Python
// implements its timeouts so, and the flag is shared with the dup): EAGAIN is
// waited out with poll(). A blocked peer holds back every link of the node.
#include <poll.h>
#include <pthread.h>
#include <sys/socket.h>
#include <time.h>
#include <unistd.h>

#include <algorithm>
#include <atomic>
#include <cerrno>
#include <condition_variable>
#include <cstdint>
#include <cstring>
#include <deque>
#include <mutex>
#include <queue>
#include <string>
#include <thread>
#include <vector>

namespace {

// upper edges, in seconds, of a link's lateness histogram (written less due),
// and one open bucket above them; Python and the benchmark's readers learn
// them from tm_delay_line_late_edges
constexpr int kLateBuckets = 21;
const double kLateEdges[kLateBuckets - 1] = {
    0.00005, 0.0001, 0.0002, 0.0003, 0.0005, 0.00075, 0.001, 0.0015, 0.002, 0.003,
    0.005,   0.0075, 0.01,   0.015,  0.02,   0.03,    0.05,  0.1,    0.2,   0.5};

double now_s() {
  timespec ts;
  clock_gettime(CLOCK_MONOTONIC, &ts);
  return (double)ts.tv_sec + 1e-9 * (double)ts.tv_nsec;
}

struct Link {
  int fd = -1;
  std::atomic<bool> closed{false};  // read by a write in flight, unlocked
  bool sending = false;
  int error = 0;  // errno of the write that failed
  double last_due = 0.0;  // of the newest chunk queued: a link is FIFO
  int64_t depth = 0, queue_max = 0, frames = 0, bytes = 0;
  double late_sum = 0.0, late_max = 0.0;
  int64_t hist[kLateBuckets] = {0};
};

struct Chunk {
  double due;
  uint64_t seq;
  int link;
  std::string data;
};

struct Later {
  bool operator()(const Chunk& a, const Chunk& b) const {
    return a.due != b.due ? a.due > b.due : a.seq > b.seq;
  }
};

struct Line {
  std::mutex mtx;
  std::condition_variable wake, sent;
  std::priority_queue<Chunk, std::vector<Chunk>, Later> heap;
  std::deque<Link> links;  // grows only: references and ids stay good
  uint64_t seq = 0;
  bool stopped = false;
  std::thread thread;

  // 0, or the errno that ended the write. Called without the mutex.
  static int write_all(int fd, const char* p, size_t n,
                       const std::atomic<bool>* closed) {
    while (n > 0) {
      ssize_t k = send(fd, p, n, MSG_NOSIGNAL);
      if (k > 0) {
        p += k;
        n -= (size_t)k;
      } else if (k < 0 && errno == EINTR) {
        continue;
      } else if (k < 0 && (errno == EAGAIN || errno == EWOULDBLOCK)) {
        if (closed->load()) return EPIPE;
        pollfd pfd{fd, POLLOUT, 0};
        poll(&pfd, 1, 100);
      } else {
        return k < 0 ? errno : EPIPE;
      }
    }
    return 0;
  }

  void run() {
    pthread_setname_np(pthread_self(), "p2p.delayLine");
    std::unique_lock<std::mutex> lk(mtx);
    while (!stopped) {
      if (heap.empty()) {
        wake.wait(lk);
        continue;
      }
      double left = heap.top().due - now_s();
      if (left > 0) {
        wake.wait_for(lk, std::chrono::duration<double>(left));
        continue;
      }
      Chunk c = std::move(const_cast<Chunk&>(heap.top()));
      heap.pop();
      Link& l = links[c.link];
      l.depth--;
      if (l.closed || l.error) continue;
      l.sending = true;
      int fd = l.fd;
      lk.unlock();
      int err = write_all(fd, c.data.data(), c.data.size(), &l.closed);
      double late = std::max(0.0, now_s() - c.due);
      lk.lock();
      l.sending = false;
      if (err) {
        l.error = err;
        shutdown(l.fd, SHUT_RDWR);  // the reader learns by the end of file
      } else {
        int i = 0;
        while (i < kLateBuckets - 1 && late > kLateEdges[i]) i++;
        l.frames++;
        l.bytes += (int64_t)c.data.size();
        l.late_sum += late;
        l.late_max = std::max(l.late_max, late);
        l.hist[i]++;
      }
      sent.notify_all();
    }
  }
};

}  // namespace

extern "C" {

void* tm_delay_line_new() {
  Line* ln = new Line();
  ln->thread = std::thread([ln] { ln->run(); });
  return ln;
}

// Stops the thread, drops what is queued, closes every link's descriptor.
void tm_delay_line_free(void* h) {
  Line* ln = (Line*)h;
  {
    std::lock_guard<std::mutex> g(ln->mtx);
    ln->stopped = true;
    for (Link& l : ln->links) {
      l.closed = true;
      if (l.fd >= 0) shutdown(l.fd, SHUT_RDWR);  // ends a write in flight
    }
    ln->wake.notify_all();
  }
  ln->thread.join();
  for (Link& l : ln->links)
    if (l.fd >= 0) close(l.fd);
  delete ln;
}

// A link over a dup of `fd`; its id, or -errno.
int tm_delay_line_add_link(void* h, int fd) {
  Line* ln = (Line*)h;
  int mine = dup(fd);
  if (mine < 0) return -errno;
  std::lock_guard<std::mutex> g(ln->mtx);
  ln->links.emplace_back();
  ln->links.back().fd = mine;
  return (int)ln->links.size() - 1;
}

// Queue `len` bytes for `link`, due `delay_s` from now: the stamp and the
// push are one step under the mutex, so a link's chunks keep their order.
// 0, or -errno of the write that broke the link (-EPIPE once closed).
int tm_delay_line_put(void* h, int link, double delay_s, const char* data,
                      uint64_t len) {
  Line* ln = (Line*)h;
  std::lock_guard<std::mutex> g(ln->mtx);
  Link& l = ln->links[link];
  if (l.error) return -l.error;
  if (l.closed || ln->stopped) return -EPIPE;
  // a chunk is never due before the one queued ahead of it, so a delay that
  // was shortened meanwhile cannot let it overtake
  l.last_due = std::max(l.last_due, now_s() + delay_s);
  Chunk c{l.last_due, ++ln->seq, link, std::string(data, (size_t)len)};
  bool first = ln->heap.empty() || c.due < ln->heap.top().due;
  ln->heap.push(std::move(c));
  l.depth++;
  l.queue_max = std::max(l.queue_max, l.depth);
  if (first) ln->wake.notify_one();
  return 0;
}

// What is queued for the link is dropped; a write in flight is ended by
// shutting the socket down and waited for; the dup is closed. The caller
// closes its own descriptor after this returns.
void tm_delay_line_close_link(void* h, int link) {
  Line* ln = (Line*)h;
  std::unique_lock<std::mutex> lk(ln->mtx);
  Link& l = ln->links[link];
  if (l.closed) return;
  l.closed = true;
  shutdown(l.fd, SHUT_RDWR);
  while (l.sending) ln->sent.wait(lk);
  close(l.fd);
  l.fd = -1;
}

// The histogram's upper edges into out[0..cap): how many there are.
int tm_delay_line_late_edges(double* out, int cap) {
  for (int i = 0; i < kLateBuckets - 1 && i < cap; i++) out[i] = kLateEdges[i];
  return kLateBuckets - 1;
}

// out[0..4] = frames, bytes, queue_max, late_sum_s, late_max_s;
// out[5..] = the lateness histogram's counts, one more than it has edges.
void tm_delay_line_stats(void* h, int link, double* out) {
  Line* ln = (Line*)h;
  std::lock_guard<std::mutex> g(ln->mtx);
  const Link& l = ln->links[link];
  out[0] = (double)l.frames;
  out[1] = (double)l.bytes;
  out[2] = (double)l.queue_max;
  out[3] = l.late_sum;
  out[4] = l.late_max;
  for (int i = 0; i < kLateBuckets; i++) out[5 + i] = (double)l.hist[i];
}

}  // extern "C"
