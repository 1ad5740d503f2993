// autodist_tpu native IO: memory-mapped record dataset + multi-threaded
// shuffled batch assembly with a prefetch ring.
//
// Role in the framework: the host-side input pipeline.  The reference
// delegates its data path to TensorFlow's C++ input stack (vendored
// tf-official pipelines in examples/benchmark/utils/); this is the
// TPU-framework equivalent: training steps consume device batches while
// these threads assemble the next host batches from an mmap'd dataset —
// the feed half of runner.py's double buffering.
//
// C ABI (ctypes-friendly):
//   ds  = adio_open(path, record_bytes)        // mmap a packed record file
//   n   = adio_num_records(ds)
//   adio_read_batch(ds, indices, n, out)       // gather records -> out
//   ld  = adio_loader_new(ds, batch, threads, shuffle, seed, prefetch)
//   buf = adio_loader_next(ld)                 // blocks; returns batch ptr
//   adio_loader_release(ld, buf)               // recycle the slot
//   adio_loader_free(ld); adio_close(ds);
#include <atomic>
#include <condition_variable>
#include <cstdint>
#include <cstring>
#include <deque>
#include <mutex>
#include <random>
#include <thread>
#include <vector>

#include <fcntl.h>
#include <sys/mman.h>
#include <sys/stat.h>
#include <unistd.h>

extern "C" {

struct AdioDataset {
  int fd = -1;
  const uint8_t* base = nullptr;
  size_t file_bytes = 0;
  size_t record_bytes = 0;
  size_t num_records = 0;
};

AdioDataset* adio_open(const char* path, uint64_t record_bytes) {
  int fd = ::open(path, O_RDONLY);
  if (fd < 0) return nullptr;
  struct stat st;
  if (fstat(fd, &st) != 0 || record_bytes == 0) { ::close(fd); return nullptr; }
  // a truncated file or a wrong record_bytes (mis-specified shape/dtype)
  // must be an error, not silent clipping into garbled batches
  if (st.st_size == 0 ||
      static_cast<uint64_t>(st.st_size) % record_bytes != 0) {
    ::close(fd);
    return nullptr;
  }
  void* p = mmap(nullptr, st.st_size, PROT_READ, MAP_PRIVATE, fd, 0);
  if (p == MAP_FAILED) { ::close(fd); return nullptr; }
  madvise(p, st.st_size, MADV_WILLNEED);
  auto* ds = new AdioDataset();
  ds->fd = fd;
  ds->base = static_cast<const uint8_t*>(p);
  ds->file_bytes = st.st_size;
  ds->record_bytes = record_bytes;
  ds->num_records = st.st_size / record_bytes;
  return ds;
}

uint64_t adio_num_records(AdioDataset* ds) { return ds ? ds->num_records : 0; }

void adio_close(AdioDataset* ds) {
  if (!ds) return;
  munmap(const_cast<uint8_t*>(ds->base), ds->file_bytes);
  ::close(ds->fd);
  delete ds;
}

// Gather `n` records by index into `out` (caller-allocated, n*record_bytes).
int adio_read_batch(AdioDataset* ds, const uint64_t* indices, uint64_t n,
                    uint8_t* out) {
  if (!ds) return -1;
  const size_t rb = ds->record_bytes;
  for (uint64_t i = 0; i < n; ++i) {
    if (indices[i] >= ds->num_records) return -2;
    memcpy(out + i * rb, ds->base + indices[i] * rb, rb);
  }
  return 0;
}

struct AdioLoader {
  AdioDataset* ds;
  size_t batch;
  size_t prefetch;
  bool shuffle;
  uint64_t seed;

  std::vector<std::thread> workers;
  std::mutex mu;
  std::condition_variable cv_full, cv_free;
  std::deque<uint8_t*> ready;       // assembled batches
  std::deque<uint8_t*> free_slots;  // recycled buffers
  std::vector<uint8_t*> slabs;
  std::atomic<bool> stop{false};
  // epoch permutation state (guarded by mu)
  std::vector<uint64_t> perm;
  size_t cursor = 0;
  std::mt19937_64 rng;
  // multi-host sharding: this loader only yields records with
  // index % shard_count == shard_index (each host feeds its slice)
  uint64_t shard_index = 0;
  uint64_t shard_count = 1;

  void refill_perm() {
    if (perm.empty()) {
      for (uint64_t i = shard_index; i < ds->num_records; i += shard_count)
        perm.push_back(i);
    }
    if (shuffle) {
      for (size_t i = perm.size(); i > 1; --i)
        std::swap(perm[i - 1], perm[rng() % i]);
    }
    cursor = 0;
  }

  void worker() {
    const size_t rb = ds->record_bytes;
    std::vector<uint64_t> idx(batch);
    while (!stop.load()) {
      uint8_t* slot = nullptr;
      {
        std::unique_lock<std::mutex> lk(mu);
        cv_free.wait(lk, [&] { return stop.load() || !free_slots.empty(); });
        if (stop.load()) return;
        slot = free_slots.front();
        free_slots.pop_front();
        for (size_t i = 0; i < batch; ++i) {
          if (cursor >= perm.size()) refill_perm();
          idx[i] = perm[cursor++];
        }
      }
      for (size_t i = 0; i < batch; ++i)
        memcpy(slot + i * rb, ds->base + idx[i] * rb, rb);
      {
        std::lock_guard<std::mutex> lk(mu);
        ready.push_back(slot);
      }
      cv_full.notify_one();
    }
  }
};

AdioLoader* adio_loader_new_sharded(AdioDataset* ds, uint64_t batch,
                                    uint64_t threads, int shuffle,
                                    uint64_t seed, uint64_t prefetch,
                                    uint64_t shard_index,
                                    uint64_t shard_count) {
  if (!ds || batch == 0 || ds->num_records == 0) return nullptr;
  if (shard_count == 0 || shard_index >= shard_count) return nullptr;
  if (shard_index >= ds->num_records) return nullptr;  // empty shard
  auto* ld = new AdioLoader();
  ld->ds = ds;
  ld->batch = batch;
  ld->shuffle = shuffle != 0;
  ld->seed = seed;
  ld->rng.seed(seed);
  ld->prefetch = prefetch ? prefetch : 2;
  ld->shard_index = shard_index;
  ld->shard_count = shard_count;
  ld->refill_perm();
  const size_t slab_bytes = batch * ds->record_bytes;
  for (size_t i = 0; i < ld->prefetch + 1; ++i) {
    auto* s = static_cast<uint8_t*>(aligned_alloc(64, ((slab_bytes + 63) / 64) * 64));
    ld->slabs.push_back(s);
    ld->free_slots.push_back(s);
  }
  const uint64_t nthreads = threads ? threads : 1;
  for (uint64_t t = 0; t < nthreads; ++t)
    ld->workers.emplace_back([ld] { ld->worker(); });
  return ld;
}

AdioLoader* adio_loader_new(AdioDataset* ds, uint64_t batch, uint64_t threads,
                            int shuffle, uint64_t seed, uint64_t prefetch) {
  return adio_loader_new_sharded(ds, batch, threads, shuffle, seed, prefetch,
                                 0, 1);
}

const uint8_t* adio_loader_next(AdioLoader* ld) {
  if (!ld) return nullptr;
  std::unique_lock<std::mutex> lk(ld->mu);
  ld->cv_full.wait(lk, [&] { return ld->stop.load() || !ld->ready.empty(); });
  if (ld->ready.empty()) return nullptr;
  const uint8_t* b = ld->ready.front();
  ld->ready.pop_front();
  return b;
}

// assembled batches waiting in the ring (the loader's queue depth)
uint64_t adio_loader_ready(AdioLoader* ld) {
  if (!ld) return 0;
  std::lock_guard<std::mutex> lk(ld->mu);
  return ld->ready.size();
}

void adio_loader_release(AdioLoader* ld, const uint8_t* buf) {
  if (!ld || !buf) return;
  {
    std::lock_guard<std::mutex> lk(ld->mu);
    ld->free_slots.push_back(const_cast<uint8_t*>(buf));
  }
  ld->cv_free.notify_one();
}

void adio_loader_free(AdioLoader* ld) {
  if (!ld) return;
  ld->stop.store(true);
  ld->cv_free.notify_all();
  ld->cv_full.notify_all();
  for (auto& t : ld->workers) t.join();
  for (auto* s : ld->slabs) free(s);
  delete ld;
}

}  // extern "C"
