// ThreadSanitizer stress harness for the native components (SURVEY.md
// §5.2: the reference's race defenses are architectural; for our C++ the
// defense is TSAN). Build + run with `make -C native tsan` — any data
// race aborts with a TSAN report (exit != 0).
//
// Covers the three concurrently-used components:
//  - counters: 8 writer threads hammering shard-local cells while a
//    reader snapshots (the wait-free mzmetrics contract)
//  - kvstore: 4 threads doing put/get/delete on one Store (the
//    per-instance mutex contract the bucketed msg store relies on)
//  - egress: two producer threads hand numbered records to one writer
//    over 16 socket pairs with a small send buffer (so backlogs build
//    and drain on EPOLLOUT) while a third thread takes the counters;
//    every peer must read its records whole and in order

#include <atomic>
#include <cstdint>
#include <cstdio>
#include <cstring>
#include <string>
#include <sys/socket.h>
#include <unistd.h>
#include <thread>
#include <vector>

struct Block;
extern "C" {
Block* ctr_create(uint32_t n);
void ctr_destroy(Block* b);
int ctr_shards(void);
void ctr_incr(Block* b, uint32_t idx, int64_t delta, uint32_t shard);
int64_t ctr_read(Block* b, uint32_t idx);
void ctr_snapshot(Block* b, int64_t* out);
}

struct Store;
extern "C" {
Store* kv_open(const char* path);
void kv_close(Store* s);
int kv_put(Store* s, const uint8_t* key, uint32_t klen, const uint8_t* val,
           uint32_t vlen);
int kv_put_batch(Store* s, uint32_t n, const uint8_t* keys,
                 const uint32_t* klens, const uint8_t* vals,
                 const uint32_t* vlens);
int kv_get(Store* s, const uint8_t* key, uint32_t klen, uint8_t** out,
           uint32_t* outlen);
int kv_delete(Store* s, const uint8_t* key, uint32_t klen);
void kv_free(void* p);
}

namespace egress {
class Writer;
}
extern "C" {
egress::Writer* eg_create(void);
uint64_t eg_attach(egress::Writer* w, int fd);
void eg_submit(egress::Writer* w, uint64_t id, const char* p, size_t n);
void eg_close(egress::Writer* w, uint64_t id, int drain);
void eg_take(egress::Writer* w, uint64_t* sent, uint64_t* lag_ns,
             uint64_t* dropped);
void eg_destroy(egress::Writer* w);
}

// 16 connections, 2 producers of 8 each, 2,000 records of 1-700 bytes a
// connection; a record is its connection's sequence number (4 bytes)
// repeated. Returns 0 when every peer read exactly its records in order.
static int egress_stress() {
  constexpr int kConns = 16, kRecs = 2000;
  egress::Writer* w = eg_create();
  if (!w) {
    std::fprintf(stderr, "eg_create failed\n");
    return 1;
  }
  int peers[kConns];
  uint64_t ids[kConns];
  for (int c = 0; c < kConns; c++) {
    int sv[2];
    if (socketpair(AF_UNIX, SOCK_STREAM, 0, sv) != 0) return 1;
    int small = 4096;
    setsockopt(sv[0], SOL_SOCKET, SO_SNDBUF, &small, sizeof small);
    peers[c] = sv[1];
    ids[c] = eg_attach(w, sv[0]);
    close(sv[0]);  // the writer's dup is the connection's only handle
  }
  auto size_of = [](int c, int i) { return 1 + (c * 131 + i * 17) % 700; };
  std::atomic<int> bad{0};
  std::vector<std::thread> ts;
  for (int c = 0; c < kConns; c++) {
    ts.emplace_back([&, c] {  // each peer reads and checks its stream
      std::string want, got;
      for (int i = 0; i < kRecs; i++) {
        uint32_t seq = uint32_t(i);
        for (int k = 0, n = size_of(c, i); k < n; k++)
          want.push_back(reinterpret_cast<char*>(&seq)[k % 4]);
      }
      char buf[8192];
      for (;;) {
        ssize_t n = read(peers[c], buf, sizeof buf);
        if (n <= 0) break;
        got.append(buf, size_t(n));
      }
      if (got != want) bad.fetch_add(1);
      close(peers[c]);
    });
  }
  std::atomic<bool> done{false};
  std::thread taker([&] {
    uint64_t sent, lag, dropped;
    while (!done.load(std::memory_order_acquire)) eg_take(w, &sent, &lag, &dropped);
  });
  std::vector<std::thread> producers;
  for (int p = 0; p < 2; p++) {
    producers.emplace_back([&, p] {
      std::string rec;
      for (int i = 0; i < kRecs; i++) {
        for (int c = p; c < kConns; c += 2) {
          rec.clear();
          uint32_t seq = uint32_t(i);
          for (int k = 0, n = size_of(c, i); k < n; k++)
            rec.push_back(reinterpret_cast<char*>(&seq)[k % 4]);
          eg_submit(w, ids[c], rec.data(), rec.size());
        }
      }
      for (int c = p; c < kConns; c += 2) eg_close(w, ids[c], 1);
    });
  }
  for (auto& t : producers) t.join();
  for (auto& t : ts) t.join();  // every peer saw EOF: drained and closed
  done.store(true, std::memory_order_release);
  taker.join();
  eg_destroy(w);
  if (bad.load()) {
    std::fprintf(stderr, "egress: %d streams differ\n", bad.load());
    return 1;
  }
  return 0;
}

int main() {
  // ---- counters
  Block* b = ctr_create(16);
  const int nshards = ctr_shards();
  std::vector<std::thread> ts;
  std::atomic<bool> stop{false};
  for (int t = 0; t < 8; t++) {
    ts.emplace_back([&, t] {
      for (int i = 0; i < 200000; i++)
        ctr_incr(b, uint32_t(i % 16), 1, uint32_t(t % nshards));
    });
  }
  std::thread reader([&] {
    int64_t snap[16];
    while (!stop.load(std::memory_order_acquire)) ctr_snapshot(b, snap);
  });
  for (auto& t : ts) t.join();
  stop.store(true, std::memory_order_release);
  reader.join();
  int64_t total = 0;
  for (uint32_t i = 0; i < 16; i++) total += ctr_read(b, i);
  if (total != 8 * 200000) {
    std::fprintf(stderr, "counter total %lld != %d\n",
                 (long long)total, 8 * 200000);
    return 1;
  }
  ctr_destroy(b);

  // ---- kvstore
  std::string path = "/tmp/vmq_tsan_kv_XXXXXX";
  (void)mkstemp(path.data());
  Store* s = kv_open(path.c_str());
  if (!s) {
    std::fprintf(stderr, "kv_open failed\n");
    return 1;
  }
  ts.clear();
  for (int t = 0; t < 4; t++) {
    ts.emplace_back([&, t] {
      char key[32], val[32];
      for (int i = 0; i < 5000; i++) {
        int klen = std::snprintf(key, sizeof key, "k%d-%d", t, i % 100);
        int vlen = std::snprintf(val, sizeof val, "v%d", i);
        kv_put(s, (const uint8_t*)key, klen, (const uint8_t*)val, vlen);
        uint8_t* out = nullptr;
        uint32_t outlen = 0;
        if (kv_get(s, (const uint8_t*)key, klen, &out, &outlen) == 0 && out)
          kv_free(out);
        if (i % 7 == 0) kv_delete(s, (const uint8_t*)key, klen);
        if (i % 11 == 0) {
          // batched writes race against the single-put/get/delete
          // threads on the same store mutex
          char kb[64];
          int k1 = std::snprintf(kb, sizeof kb, "b%d-%da", t, i % 50);
          int k2 = std::snprintf(kb + k1, sizeof kb - k1, "b%d-%db", t,
                                 i % 50);
          uint32_t klens[2] = {(uint32_t)k1, (uint32_t)k2};
          uint32_t vlens[2] = {(uint32_t)vlen, (uint32_t)vlen};
          char vb[64];
          std::memcpy(vb, val, vlen);
          std::memcpy(vb + vlen, val, vlen);
          kv_put_batch(s, 2, (const uint8_t*)kb, klens,
                       (const uint8_t*)vb, vlens);
        }
      }
    });
  }
  for (auto& t : ts) t.join();
  kv_close(s);
  std::remove(path.c_str());
  if (egress_stress() != 0) return 1;
  std::puts("tsan stress OK");
  return 0;
}
