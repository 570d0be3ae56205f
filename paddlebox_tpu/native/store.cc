// Native host store engine: incremental key->row index + threaded
// sorted-array store primitives.
//
// Two roles from the reference's CPU-side PS machinery:
//  1. The incremental index (pbx_index_*) is the host half of the
//     TPU-resident feature store (embedding/device_store.py): the role of
//     the HeterPS GPU hashtable's key->slot mapping (heter_ps/hashtable.h)
//     moved to the host, where it is cheap, so the device side stays a
//     plain dense array. Rows are assigned in first-insertion order and
//     never move (append-only), so device value rows never need rehashing.
//  2. The sorted-store primitives (pbx_ss_*, pbx_merge_*, pbx_init_*,
//     pbx_gather/scatter_rows) are the hot loops of the host-RAM tier
//     (embedding/store.py): the role of PreBuildTask/BuildPull's
//     multithreaded C++ table walk (ps_gpu_wrapper.cc:114,362) — numpy's
//     single-threaded searchsorted/fancy-index was the r02 bottleneck
//     (406K keys/s store build; VERDICT r02 task 3).
//
// Exposed via a C ABI consumed by ctypes (native/store_py.py). Calls
// release the GIL (ctypes does) and thread internally. The index is NOT
// internally synchronized: callers serialize mutating calls (the pass
// lifecycle already does).

#include <algorithm>
#include <atomic>
#include <cstdint>
#include <cstring>
#include <thread>
#include <vector>

#ifdef __linux__
#include <sys/mman.h>
#endif

namespace {

static inline uint64_t mix64(uint64_t x) {
  x += 0x9e3779b97f4a7c15ULL;
  x = (x ^ (x >> 30)) * 0xbf58476d1ce4e5b9ULL;
  x = (x ^ (x >> 27)) * 0x94d049bb133111ebULL;
  return x ^ (x >> 31);
}

static inline int num_threads_for(int64_t n) {
  unsigned hw = std::thread::hardware_concurrency();
  if (hw == 0) hw = 4;
  int t = static_cast<int>(std::min<int64_t>(hw, (n + (1 << 16) - 1) >> 16));
  return t < 1 ? 1 : t;
}

template <typename Fn>
static void parallel_chunks(int64_t n, int nt, Fn fn) {
  if (nt <= 1) {
    fn(0, 0, n);
    return;
  }
  std::vector<std::thread> ths;
  int64_t chunk = (n + nt - 1) / nt;
  for (int t = 0; t < nt; ++t) {
    int64_t lo = t * chunk, hi = std::min<int64_t>(n, lo + chunk);
    ths.emplace_back([fn, t, lo, hi]() { fn(t, lo, hi); });
  }
  for (auto& th : ths) th.join();
}

// Resizable open-addressing map: key -> row (insertion order). Load
// factor kept <= 0.5 by doubling. Entries interleave (key, row) in one
// 16-byte slot (one cache line touch per probe, not two), and batch
// operations software-prefetch a window of slots ahead — on this class
// of host (single core, ~100ns memory) memory-level parallelism is the
// only lever, worth ~5x on random probes. The slot array lives in an
// anonymous mmap with MADV_HUGEPAGE: at production sizes (50M keys ->
// 2 GiB of slots) random probes on 4 KiB pages page-walk on every
// access, and 2 MiB pages measured 2.66 -> 7.0 M upserts/s on this
// host (with the window at 32); vector/new allocations don't reliably
// get THP-backed.
struct Entry {
  uint64_t key;
  int64_t row;
};

constexpr int kPrefetchWindow = 32;

// out_mmapped records which allocator produced the block — the free
// path must match it exactly (munmap on a new[] fallback pointer would
// be heap corruption; delete[] on an mmap would abort).
static Entry* slots_alloc(size_t cap, bool* out_mmapped) {
#ifdef __linux__
  void* p = mmap(nullptr, cap * sizeof(Entry), PROT_READ | PROT_WRITE,
                 MAP_PRIVATE | MAP_ANONYMOUS, -1, 0);
  if (p != MAP_FAILED) {
    madvise(p, cap * sizeof(Entry), MADV_HUGEPAGE);
    *out_mmapped = true;
    return static_cast<Entry*>(p);  // zero-filled: key 0 == empty
  }
#endif
  *out_mmapped = false;
  return new Entry[cap]();
}

static void slots_free(Entry* p, size_t cap, bool mmapped) {
  if (p == nullptr) return;
#ifdef __linux__
  if (mmapped) {
    munmap(p, cap * sizeof(Entry));
    return;
  }
#endif
  (void)cap;
  delete[] p;
}

struct GrowMap {
  Entry* slots = nullptr;
  size_t cap = 0;
  bool slots_mmapped = false;
  std::vector<uint64_t> by_row;  // row -> key (append order)
  uint64_t mask = 0;
  int64_t used = 0;

  GrowMap() { rehash(1 << 16); }
  ~GrowMap() { slots_free(slots, cap, slots_mmapped); }

  void rehash(size_t new_cap) {
    Entry* old = slots;
    size_t old_cap = cap;
    bool old_mmapped = slots_mmapped;
    slots = slots_alloc(new_cap, &slots_mmapped);
    cap = new_cap;
    mask = new_cap - 1;
    if (old != nullptr) {
      for (size_t i = 0; i + kPrefetchWindow < old_cap; ++i) {
        __builtin_prefetch(
            &slots[mix64(old[i + kPrefetchWindow].key) & mask], 1, 1);
        if (old[i].key != 0) place(old[i].key, old[i].row);
      }
      for (size_t i = old_cap > kPrefetchWindow
                          ? old_cap - kPrefetchWindow : 0;
           i < old_cap; ++i) {
        if (old[i].key != 0) place(old[i].key, old[i].row);
      }
      slots_free(old, old_cap, old_mmapped);
    }
  }

  inline void place(uint64_t k, int64_t r) {
    uint64_t i = mix64(k) & mask;
    while (slots[i].key != 0) i = (i + 1) & mask;
    slots[i] = Entry{k, r};
  }

  inline int64_t find(uint64_t k) const {
    uint64_t i = mix64(k) & mask;
    while (true) {
      if (slots[i].key == k) return slots[i].row;
      if (slots[i].key == 0) return -1;
      i = (i + 1) & mask;
    }
  }

  // (Find-or-insert lives ONLY in pbx_index_upsert's inlined batch loop
  // — a per-element member with its own growth check would be a second
  // diverging copy of the probe logic.)

  inline void prefetch(uint64_t k, int write) const {
    __builtin_prefetch(&slots[mix64(k) & mask], write, 1);
  }
};

}  // namespace

extern "C" {

// ---------------------------------------------------------------------------
// Incremental key -> row index (device-store host half).
// ---------------------------------------------------------------------------

void* pbx_index_new() { return new GrowMap(); }

int64_t pbx_index_size(void* h) {
  return static_cast<int64_t>(static_cast<GrowMap*>(h)->by_row.size());
}

// Pre-size for an expected total key count (avoids rehash churn when the
// caller knows the build size, e.g. a base-model load or bulk prebuild).
void pbx_index_reserve(void* h, int64_t n) {
  GrowMap* m = static_cast<GrowMap*>(h);
  uint64_t want = static_cast<uint64_t>(m->used + n);
  if (want * 2 > m->mask + 1) {
    size_t cap = m->mask + 1;
    while (want * 2 > cap) cap <<= 1;
    m->rehash(cap);
  }
  m->by_row.reserve(want);
}

// Lookup only: out_rows[i] = row of keys[i], or -1 when absent (key 0 is
// always absent — the null feasign). Threaded, read-only.
void pbx_index_lookup(void* h, const uint64_t* keys, int64_t n,
                      int64_t* out_rows) {
  GrowMap* m = static_cast<GrowMap*>(h);
  parallel_chunks(n, num_threads_for(n), [&](int, int64_t lo, int64_t hi) {
    for (int64_t i = lo; i < hi; ++i) {
      if (i + kPrefetchWindow < hi && keys[i + kPrefetchWindow])
        m->prefetch(keys[i + kPrefetchWindow], 0);
      out_rows[i] = (keys[i] == 0) ? -1 : m->find(keys[i]);
    }
  });
}

// Find-or-insert: new keys get rows size.. in first-appearance order.
// Returns the number of newly inserted keys. Serial over the input (row
// assignment must be deterministic); pre-sizes the table for the worst
// case so a bulk insert never rehashes mid-stream (rehash churn on a
// growing multi-GB table was measured at ~9x the insert cost itself).
int64_t pbx_index_upsert(void* h, const uint64_t* keys, int64_t n,
                         int64_t* out_rows) {
  GrowMap* m = static_cast<GrowMap*>(h);
  uint64_t want = static_cast<uint64_t>(m->used + n);
  if (want * 2 > m->mask + 1) {
    size_t cap = m->mask + 1;
    while (want * 2 > cap) cap <<= 1;
    m->rehash(cap);
  }
  m->by_row.reserve(m->by_row.size() + n);
  int64_t before = static_cast<int64_t>(m->by_row.size());
  // Hot loop: the pre-size above guarantees no rehash can fire inside
  // this batch, so probe inline WITHOUT the per-element growth check —
  // keeping the loop body small enough to stay inlined preserves the
  // prefetch pipeline (measured ~1.8x on the 50M fresh build vs calling
  // the checking member function per element).
  Entry* slots = m->slots;
  const uint64_t mask = m->mask;
  auto& by_row = m->by_row;
  for (int64_t i = 0; i < n; ++i) {
    if (i + kPrefetchWindow < n && keys[i + kPrefetchWindow])
      __builtin_prefetch(&slots[mix64(keys[i + kPrefetchWindow]) & mask],
                         1, 1);
    uint64_t k = keys[i];
    if (k == 0) {
      out_rows[i] = -1;
      continue;
    }
    uint64_t j = mix64(k) & mask;
    while (true) {
      if (slots[j].key == k) {
        out_rows[i] = slots[j].row;
        break;
      }
      if (slots[j].key == 0) {
        int64_t r = static_cast<int64_t>(by_row.size());
        slots[j] = Entry{k, r};
        by_row.push_back(k);
        out_rows[i] = r;
        break;
      }
      j = (j + 1) & mask;
    }
  }
  int64_t n_new = static_cast<int64_t>(m->by_row.size()) - before;
  m->used += n_new;
  return n_new;
}

// Dump keys in row order into out[size].
void pbx_index_keys_fill(void* h, uint64_t* out) {
  GrowMap* m = static_cast<GrowMap*>(h);
  if (!m->by_row.empty())
    std::memcpy(out, m->by_row.data(), m->by_row.size() * sizeof(uint64_t));
}

void pbx_index_free(void* h) { delete static_cast<GrowMap*>(h); }

// Fresh-build bypass (sorted-run store build, round 13): populate an
// EMPTY index from n sorted unique nonzero keys with rows 0..n-1 —
// bit-identical to upserting the same array into an empty index, but
// the uniqueness precondition removes the serial find-or-insert
// dependency chain, so placement parallelizes across cores (each
// thread claims slots with a CAS on the key word; rows publish at the
// join). Returns n, or -1 when the index is non-empty / the input is
// not sorted-unique-nonzero (caller falls back to upsert).
int64_t pbx_index_bulk_build(void* h, const uint64_t* keys, int64_t n) {
  GrowMap* m = static_cast<GrowMap*>(h);
  if (m->used != 0) return -1;
  if (n > 0 && keys[0] == 0) return -1;
  for (int64_t i = 1; i < n; ++i)
    if (keys[i] <= keys[i - 1]) return -1;
  uint64_t want = static_cast<uint64_t>(n);
  if (want * 2 > m->mask + 1) {
    size_t cap = m->mask + 1;
    while (want * 2 > cap) cap <<= 1;
    m->rehash(cap);
  }
  m->by_row.assign(keys, keys + n);
  Entry* slots = m->slots;
  const uint64_t mask = m->mask;
  parallel_chunks(n, num_threads_for(n), [&](int, int64_t lo, int64_t hi) {
    for (int64_t i = lo; i < hi; ++i) {
      if (i + kPrefetchWindow < hi)
        __builtin_prefetch(&slots[mix64(keys[i + kPrefetchWindow]) & mask],
                           1, 1);
      uint64_t k = keys[i];
      uint64_t j = mix64(k) & mask;
      while (true) {
        uint64_t expected = 0;
        if (__atomic_compare_exchange_n(&slots[j].key, &expected, k, false,
                                        __ATOMIC_ACQ_REL,
                                        __ATOMIC_RELAXED)) {
          slots[j].row = static_cast<int64_t>(i);
          break;
        }
        // expected now holds the occupant; unique input means it is
        // never k — probe on.
        j = (j + 1) & mask;
      }
    }
  });
  m->used = n;
  return n;
}

// ---------------------------------------------------------------------------
// Sorted-store primitives (host-RAM tier hot loops).
// ---------------------------------------------------------------------------

// Threaded searchsorted + equality: for each query, pos = lower_bound in
// sorted[n]; found = pos < n && sorted[pos] == q. out_pos clipped to n-1.
void pbx_ss_locate(const uint64_t* sorted, int64_t n, const uint64_t* q,
                   int64_t m, int64_t* out_pos, uint8_t* out_found) {
  parallel_chunks(m, num_threads_for(m), [&](int, int64_t lo, int64_t hi) {
    for (int64_t i = lo; i < hi; ++i) {
      const uint64_t* p = std::lower_bound(sorted, sorted + n, q[i]);
      int64_t pos = p - sorted;
      out_found[i] = (pos < n && *p == q[i]) ? 1 : 0;
      out_pos[i] = std::min<int64_t>(pos, n > 0 ? n - 1 : 0);
    }
  });
}

// Threaded row gather: out[i] = src[idx[i]] (rows of `width` floats).
void pbx_gather_rows(const float* src, const int64_t* idx, int64_t m,
                     int64_t width, float* out) {
  parallel_chunks(m, num_threads_for(m * width / 16),
                  [&](int, int64_t lo, int64_t hi) {
    for (int64_t i = lo; i < hi; ++i) {
      if (i + kPrefetchWindow < hi)
        __builtin_prefetch(src + idx[i + kPrefetchWindow] * width, 0, 1);
      std::memcpy(out + i * width, src + idx[i] * width,
                  static_cast<size_t>(width) * sizeof(float));
    }
  });
}

// Threaded row scatter: dst[idx[i]] = src[i]. idx must be duplicate-free.
void pbx_scatter_rows(float* dst, const int64_t* idx, int64_t m,
                      int64_t width, const float* src) {
  parallel_chunks(m, num_threads_for(m * width / 16),
                  [&](int, int64_t lo, int64_t hi) {
    for (int64_t i = lo; i < hi; ++i) {
      if (i + kPrefetchWindow < hi)
        __builtin_prefetch(dst + idx[i + kPrefetchWindow] * width, 1, 1);
      std::memcpy(dst + idx[i] * width, src + i * width,
                  static_cast<size_t>(width) * sizeof(float));
    }
  });
}

// Masked variants: process only rows with mask[i] != 0 (the found subset
// of a locate), avoiding a host-side index compaction pass.
void pbx_gather_rows_masked(const float* src, const int64_t* idx,
                            const uint8_t* mask, int64_t m, int64_t width,
                            float* out) {
  parallel_chunks(m, num_threads_for(m * width / 16),
                  [&](int, int64_t lo, int64_t hi) {
    for (int64_t i = lo; i < hi; ++i) {
      if (mask[i])
        std::memcpy(out + i * width, src + idx[i] * width,
                    static_cast<size_t>(width) * sizeof(float));
    }
  });
}

void pbx_scatter_rows_masked(float* dst, const int64_t* idx,
                             const uint8_t* mask, int64_t m, int64_t width,
                             const float* src) {
  parallel_chunks(m, num_threads_for(m * width / 16),
                  [&](int, int64_t lo, int64_t hi) {
    for (int64_t i = lo; i < hi; ++i) {
      if (mask[i])
        std::memcpy(dst + idx[i] * width, src + i * width,
                    static_cast<size_t>(width) * sizeof(float));
    }
  });
}

// Merge positions for two sorted key arrays (old[n], add[m], disjoint):
// out_keys[n+m] = merged ascending; out_src[i] = source row (j < n -> old
// row j; else add row out_src[i] - n). Threaded by output partition: each
// thread owns an equal slice of `add` and the matching old range.
void pbx_merge_sorted(const uint64_t* old_keys, int64_t n,
                      const uint64_t* add_keys, int64_t m,
                      uint64_t* out_keys, int64_t* out_src) {
  if (m == 0) {
    if (n) std::memcpy(out_keys, old_keys, n * sizeof(uint64_t));
    for (int64_t i = 0; i < n; ++i) out_src[i] = i;
    return;
  }
  int nt = num_threads_for(n + m);
  // Partition by add index; old split via binary search on add boundaries
  // (all old keys < the boundary add key belong to earlier threads).
  std::vector<int64_t> add_lo(nt + 1), old_lo(nt + 1);
  for (int t = 0; t <= nt; ++t) {
    add_lo[t] = t * m / nt;
    old_lo[t] = (t == 0) ? 0
                : (t == nt ? n
                   : std::lower_bound(old_keys, old_keys + n,
                                      add_keys[add_lo[t]]) -
                         old_keys);
  }
  parallel_chunks(nt, nt, [&](int, int64_t tlo, int64_t thi) {
    for (int64_t t = tlo; t < thi; ++t) {
      int64_t ia = add_lo[t], ib = old_lo[t];
      int64_t w = ia + ib;
      while (ia < add_lo[t + 1] || ib < old_lo[t + 1]) {
        bool take_old =
            (ia >= add_lo[t + 1]) ||
            (ib < old_lo[t + 1] && old_keys[ib] < add_keys[ia]);
        if (take_old) {
          out_keys[w] = old_keys[ib];
          out_src[w] = ib;
          ++ib;
        } else {
          out_keys[w] = add_keys[ia];
          out_src[w] = n + ia;
          ++ia;
        }
        ++w;
      }
    }
  });
}

// Union of two sorted unique key arrays (a[n], b[m], which may share
// keys) into out (room for n + m); returns how many it holds. Threaded as
// pbx_merge_sorted is: each thread owns an equal slice of b and the range
// of a that sorts into it (a key equal to one of the slice's lies in that
// range), writes its union where the slice would start were nothing
// shared, and the slices are then packed to the left in order.
int64_t pbx_union_sorted(const uint64_t* a, int64_t n, const uint64_t* b,
                         int64_t m, uint64_t* out) {
  if (m == 0 || n == 0) {
    const uint64_t* src = m == 0 ? a : b;
    if (n + m) std::memcpy(out, src, (n + m) * sizeof(uint64_t));
    return n + m;
  }
  int nt = num_threads_for(n + m);
  std::vector<int64_t> b_lo(nt + 1), a_lo(nt + 1), count(nt);
  for (int t = 0; t <= nt; ++t) {
    b_lo[t] = t * m / nt;
    a_lo[t] = (t == 0) ? 0
              : (t == nt ? n
                 : std::lower_bound(a, a + n, b[b_lo[t]]) - a);
  }
  parallel_chunks(nt, nt, [&](int, int64_t tlo, int64_t thi) {
    for (int64_t t = tlo; t < thi; ++t) {
      int64_t ia = a_lo[t], ib = b_lo[t];
      const int64_t ea = a_lo[t + 1], eb = b_lo[t + 1];
      uint64_t* w = out + ia + ib;
      const uint64_t* w0 = w;
      while (ia < ea && ib < eb) {
        const uint64_t x = a[ia], y = b[ib];
        *w++ = x < y ? x : y;
        ia += (x <= y);
        ib += (y <= x);
      }
      if (ia < ea) {
        std::memcpy(w, a + ia, (ea - ia) * sizeof(uint64_t));
        w += ea - ia;
      }
      if (ib < eb) {
        std::memcpy(w, b + ib, (eb - ib) * sizeof(uint64_t));
        w += eb - ib;
      }
      count[t] = w - w0;
    }
  });
  int64_t total = count[0];
  for (int t = 1; t < nt; ++t) {
    if (count[t])
      std::memmove(out + total, out + a_lo[t] + b_lo[t],
                   count[t] * sizeof(uint64_t));
    total += count[t];
  }
  return total;
}

// Deterministic per-key uniform init (store.py _per_key_uniform contract):
// out[i, j] = uniform(-scale, scale) from a murmur3-finalizer hash of
// (key's low 32 bits, column j+1, seed) — order-independent; bit-exact
// with the numpy twin AND the on-device jnp twin (32-bit ops only, so the
// device tier can initialize rows from a 4-byte-per-key transfer).
void pbx_init_uniform(const uint64_t* keys, int64_t n, int64_t dim,
                      uint64_t seed, double scale, float* out) {
  uint32_t seed32 = static_cast<uint32_t>(seed & 0xFFFFFFFFULL);
  float fscale = static_cast<float>(scale);
  parallel_chunks(n, num_threads_for(n * dim / 8),
                  [&](int, int64_t lo, int64_t hi) {
    for (int64_t i = lo; i < hi; ++i) {
      uint32_t k = static_cast<uint32_t>(keys[i] & 0xFFFFFFFFULL);
      for (int64_t j = 1; j <= dim; ++j) {
        uint32_t z = k + static_cast<uint32_t>(j) * 0x9E3779B9u + seed32;
        z ^= z >> 16;
        z *= 0x85EBCA6Bu;
        z ^= z >> 13;
        z *= 0xC2B2AE35u;
        z ^= z >> 16;
        float u = static_cast<float>(z >> 8) * (1.0f / 16777216.0f);
        out[i * dim + (j - 1)] = (2.0f * u - 1.0f) * fscale;
      }
    }
  });
}

}  // extern "C"
