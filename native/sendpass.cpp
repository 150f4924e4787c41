// One pass over a flush's ZeroMQ frames (ISSUE 33).
//
// A tick's fan-out writes to every peer's own libzmq PUSH socket. Each
// peer's first frame hands its pipe to libzmq's ONE I/O thread with a
// command, and a command that finds that thread asleep wakes it: a
// write on its mailbox's eventfd, paid by the SENDER. On a plain kernel
// that is a handful of cheap wakes a pass, and this loop saves the
// interpreter between two sends. On a host whose kernel is sandboxed
// (the v5e hosts: a system call 6 us, a write that wakes a sleeper
// 38 us) the sender sits in that write while the I/O thread serves the
// one peer and goes back to sleep: a wake a PEER whatever the sender's
// speed, 24 ms a flush of 512 peers. There the caller asks for the
// peers to be cut into a few shares, one a thread: the wakes are paid
// side by side. A libzmq socket may be used from any thread, one at a
// time, across a full fence; each peer here belongs to exactly one
// share, and the caller's thread sends the first share itself and
// returns when all are done.
//
// The library does not link libzmq. The caller passes the address of
// zmq_send as resolved from the libzmq instance pyzmq has loaded (a
// second copy would not know the sockets) and the sockets' own handles
// (Socket.underlying).

#include <cerrno>
#include <cstddef>
#include <cstdint>
#include <exception>
#include <thread>
#include <vector>

namespace {

using ZmqSend = int (*)(void* socket, const void* buf, size_t len, int flags);
constexpr int kDontWait = 1;  // ZMQ_DONTWAIT
// A share smaller than this does not repay the start of its thread.
constexpr int64_t kMinShare = 8;

struct Table {
  ZmqSend send;
  void* const* sockets;
  const int64_t* off;
  const int32_t* idx;
  const uint8_t* const* bufs;
  const int64_t* lens;
  int32_t* taken;
  int32_t* err;
};

// Peers [first, last), PEER-major, a peer's frames in batch order.
void send_share(const Table& t, int64_t first, int64_t last) {
  for (int64_t p = first; p < last; p++) {
    void* sock = t.sockets[p];
    int32_t n = 0;
    int32_t stopped = 0;
    for (int64_t k = t.off[p]; k < t.off[p + 1]; k++) {
      int32_t i = t.idx[k];
      int rc;
      do {
        rc = t.send(sock, t.bufs[i], static_cast<size_t>(t.lens[i]),
                    kDontWait);
      } while (rc < 0 && errno == EINTR);
      if (rc < 0) {
        stopped = errno;
        break;
      }
      n++;
    }
    t.taken[p] = n;
    t.err[p] = stopped;
  }
}

}  // namespace

extern "C" int64_t wql_sendpass_abi(void) { return 2; }

// Peer p owes the messages idx[off[p]] .. idx[off[p + 1] - 1], each its
// own non-blocking send of bufs[i] / lens[i]. EINTR is retried; EAGAIN
// (the high-water mark) or any other error stops THAT peer, the next
// peer is tried all the same. taken[p] = frames the socket took, from
// the front; err[p] = the errno that stopped it (0 = took them all).
// n_threads > 1 cuts the peers into that many contiguous shares (fewer
// where a share would hold under kMinShare peers), one a thread, the
// caller's included; a thread that cannot be started leaves its share
// to the caller's. Returns the frames taken in all.
extern "C" int64_t wql_send_pass(
    void* zmq_send_fn, void* const* sockets, int64_t n_peers,
    const int64_t* off, const int32_t* idx,
    const uint8_t* const* bufs, const int64_t* lens,
    int32_t* taken, int32_t* err, int64_t n_threads) {
  const Table t{reinterpret_cast<ZmqSend>(zmq_send_fn), sockets, off, idx,
                bufs, lens, taken, err};
  if (n_threads > n_peers / kMinShare) n_threads = n_peers / kMinShare;
  if (n_threads < 1) n_threads = 1;
  const int64_t share = (n_peers + n_threads - 1) / n_threads;
  // helpers take the shares from the back, so that what a failed start
  // leaves over is one contiguous run behind the caller's own share
  std::vector<std::thread> helpers;
  int64_t left_to_caller = n_peers;
  for (int64_t k = n_threads - 1; k >= 1; k--) {
    const int64_t first = k * share;
    const int64_t last = left_to_caller;
    if (first >= last) continue;
    try {
      helpers.emplace_back([&t, first, last] { send_share(t, first, last); });
    } catch (const std::exception&) {
      break;  // no thread to be had: the caller's thread sends the rest
    }
    left_to_caller = first;
  }
  send_share(t, 0, left_to_caller);
  for (auto& helper : helpers) helper.join();
  int64_t total = 0;
  for (int64_t p = 0; p < n_peers; p++) total += taken[p];
  return total;
}
