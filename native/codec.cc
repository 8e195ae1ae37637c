// Host ingest codec — the native fast path for record decode/encode.
//
// ref roles: PyFlink's Cython coders (flink-python/pyflink/fn_execution/
// coder_impl_fast.pyx — serialization inner loops compiled to C) and the
// byte→record half of the network stack's deserializers
// (runtime/io/network/api/serialization/
// SpillingAdaptiveSpanningRecordDeserializer.java). SURVEY §3.10 item 2.
//
// Interface is plain C (ctypes binding — no pybind11 in the image): the
// Python side passes raw numpy buffers; everything here is branch-light
// single-pass scanning suitable for saturating a core on the ingest
// plane while the device does the real aggregation.
//
// Hash: 63-bit FNV-1a, BIT-IDENTICAL to records.hash_string_key — keys
// encoded here and keys hashed in Python MUST route identically.

#include <algorithm>
#include <chrono>
#include <cstdint>
#include <cstdlib>
#include <cstring>
#include <cmath>
#include <condition_variable>
#include <functional>
#include <memory>
#include <mutex>
#include <system_error>
#include <thread>
#include <vector>

extern "C" {

// Tokenize concatenated text and hash each whitespace-separated token.
//   buf/len        : UTF-8 text of all lines, concatenated
//   line_offs      : (n_lines+1) offsets of each line in buf
//   out_ids        : token hash ids (63-bit FNV-1a)
//   out_line       : originating line index per token
//   max_out        : capacity of out arrays
// Returns number of tokens written (or -1 if capacity exceeded).
int64_t tokenize_hash(const char* buf, int64_t /*len*/,
                      const int64_t* line_offs, int64_t n_lines,
                      int64_t* out_ids, int64_t* out_line,
                      int64_t max_out) {
  int64_t n = 0;
  for (int64_t li = 0; li < n_lines; ++li) {
    const char* p = buf + line_offs[li];
    const char* end = buf + line_offs[li + 1];
    while (p < end) {
      while (p < end && (*p == ' ' || *p == '\t' || *p == '\n' || *p == '\r'))
        ++p;
      if (p >= end) break;
      uint64_t h = 0xCBF29CE484222325ULL;
      while (p < end && !(*p == ' ' || *p == '\t' || *p == '\n' || *p == '\r')) {
        h = (h ^ (uint8_t)(*p)) * 0x100000001B3ULL;
        ++p;
      }
      if (n >= max_out) return -1;
      out_ids[n] = (int64_t)(h & 0x7FFFFFFFFFFFFFFFULL);
      out_line[n] = li;
      ++n;
    }
  }
  return n;
}

// Hash fixed-offset byte strings (dictionary encoding of a string
// column; ref role: StringSerializer + key-group hash).
void hash_strings(const char* buf, const int64_t* offs, int64_t n,
                  int64_t* out_ids) {
  for (int64_t i = 0; i < n; ++i) {
    uint64_t h = 0xCBF29CE484222325ULL;
    for (const char* p = buf + offs[i]; p < buf + offs[i + 1]; ++p)
      h = (h ^ (uint8_t)(*p)) * 0x100000001B3ULL;
    out_ids[i] = (int64_t)(h & 0x7FFFFFFFFFFFFFFFULL);
  }
}

// Parse delimiter-separated integer records: n_rows lines, n_cols each.
//   Unparseable / missing cells read as 0. Returns rows parsed.
int64_t parse_i64_table(const char* buf, int64_t len, char delim,
                        int64_t n_cols, int64_t* out, int64_t max_rows) {
  int64_t row = 0;
  const char* p = buf;
  const char* end = buf + len;
  while (p < end && row < max_rows) {
    for (int64_t c = 0; c < n_cols; ++c) {
      int64_t v = 0;
      bool neg = false;
      if (p < end && *p == '-') { neg = true; ++p; }
      while (p < end && *p >= '0' && *p <= '9') v = v * 10 + (*p++ - '0');
      out[row * n_cols + c] = neg ? -v : v;
      if (p < end && *p == delim) ++p;
    }
    while (p < end && *p != '\n') ++p;  // tolerate ragged tails
    if (p < end) ++p;
    ++row;
  }
  return row;
}

// Parse float32 table (same framing as parse_i64_table).
int64_t parse_f32_table(const char* buf, int64_t len, char delim,
                        int64_t n_cols, float* out, int64_t max_rows) {
  int64_t row = 0;
  const char* p = buf;
  const char* end = buf + len;
  while (p < end && row < max_rows) {
    for (int64_t c = 0; c < n_cols; ++c) {
      double v = 0.0;
      bool neg = false;
      if (p < end && *p == '-') { neg = true; ++p; }
      while (p < end && *p >= '0' && *p <= '9') v = v * 10.0 + (*p++ - '0');
      if (p < end && *p == '.') {
        ++p;
        double scale = 0.1;
        while (p < end && *p >= '0' && *p <= '9') {
          v += (*p++ - '0') * scale;
          scale *= 0.1;
        }
      }
      out[row * n_cols + c] = (float)(neg ? -v : v);
      if (p < end && *p == delim) ++p;
    }
    while (p < end && *p != '\n') ++p;
    if (p < end) ++p;
    ++row;
  }
  return row;
}

// Encode fired-window rows into a delimited byte sink buffer
// (egress half; returns bytes written or -1 on overflow).
int64_t encode_i64_rows(const int64_t* vals, int64_t n_rows, int64_t n_cols,
                        char delim, char* out, int64_t cap) {
  int64_t w = 0;
  for (int64_t r = 0; r < n_rows; ++r) {
    for (int64_t c = 0; c < n_cols; ++c) {
      int64_t v = vals[r * n_cols + c];
      char tmp[24];
      int t = 0;
      if (v < 0) { if (w >= cap) return -1; out[w++] = '-'; v = -v; }
      do { tmp[t++] = '0' + (char)(v % 10); v /= 10; } while (v);
      if (w + t + 1 > cap) return -1;
      while (t) out[w++] = tmp[--t];
      out[w++] = (c + 1 < n_cols) ? delim : '\n';
    }
  }
  return w;
}

// ---------------------------------------------------------------------------
// int64 -> int64 open-addressing hash table: the key-directory probe loop
// (ref role: CopyOnWriteStateMap.get/put — the per-record state-map probe —
// batched and compiled). The mix MUST stay bit-identical to
// records.hash_keys_numpy / hash_keys_device: host ingest, device keyBy,
// and this table all route by the same splitmix64 finalizer.

static inline uint64_t ht_mix(uint64_t x) {
  x = (x ^ (x >> 30)) * 0xBF58476D1CE4E5B9ULL;
  x = (x ^ (x >> 27)) * 0x94D049BB133111EBULL;
  x = x ^ (x >> 31);
  return x & 0x7FFFFFFFFFFFFFFFULL;
}

// A buffer that lives as long as its owner and only grows; what it
// holds is never read before it is written.
struct Scratch {
  std::unique_ptr<char[]> buf;
  int64_t len = 0;
  void* get(int64_t bytes) {
    if (bytes > len) { buf.reset(new char[bytes]); len = bytes; }
    return buf.get();
  }
};

// What ht_assign (below) keeps between calls: its memo and the lists of
// a batch's distinct new keys. Made at a table's first ht_assign.
static const int64_t MEMO_SIZE = 1024;     // entries of 16 bytes: L1-sized
struct MemoEntry { int64_t key, val; };
struct KeyRef { int64_t key, u; };         // a distinct miss and its index
struct AssignScratch {
  MemoEntry memo[MEMO_SIZE];
  Scratch uniq, bucket, alloc, refs, starts;
};

struct FtHashTable {
  int64_t* keys;
  int64_t* vals;
  uint8_t* used;
  uint64_t mask;   // size - 1
  int64_t count;
  AssignScratch* assign;   // ht_assign's workspace, made at its first call
  int64_t grows;           // doublings so far, and what they took on a
  int64_t grow_ns;         // steady clock (ht_growth reads both)
};

static void ht_alloc(FtHashTable* t, uint64_t size) {
  t->keys = (int64_t*)calloc(size, sizeof(int64_t));
  t->vals = (int64_t*)calloc(size, sizeof(int64_t));
  t->used = (uint8_t*)calloc(size, 1);
  t->mask = size - 1;
  t->count = 0;
}

static void ht_grow(FtHashTable* t) {
  const auto began = std::chrono::steady_clock::now();
  FtHashTable old = *t;
  ht_alloc(t, (old.mask + 1) * 2);
  for (uint64_t i = 0; i <= old.mask; ++i) {
    if (!old.used[i]) continue;
    uint64_t ix = ht_mix((uint64_t)old.keys[i]) & t->mask;
    while (t->used[ix]) ix = (ix + 1) & t->mask;
    t->keys[ix] = old.keys[i];
    t->vals[ix] = old.vals[i];
    t->used[ix] = 1;
    ++t->count;
  }
  free(old.keys); free(old.vals); free(old.used);
  ++t->grows;
  t->grow_ns += std::chrono::duration_cast<std::chrono::nanoseconds>(
      std::chrono::steady_clock::now() - began).count();
}

// Load factor at most one half: doubles before the entry that would pass it.
static inline void ht_grow_if_due(FtHashTable* t) {
  if ((t->count + 1) * 2 > (int64_t)(t->mask + 1)) ht_grow(t);
}

void* ht_new(int64_t capacity_hint) {
  uint64_t size = 16;
  while ((int64_t)size < capacity_hint * 2) size *= 2;
  FtHashTable* t = (FtHashTable*)malloc(sizeof(FtHashTable));
  ht_alloc(t, size);
  t->assign = nullptr;
  t->grows = t->grow_ns = 0;
  return t;
}

void ht_free(void* h) {
  FtHashTable* t = (FtHashTable*)h;
  delete t->assign;
  free(t->keys); free(t->vals); free(t->used); free(t);
}

int64_t ht_count(void* h) { return ((FtHashTable*)h)->count; }

// The table's growth so far, counted where it happens: out[0] doublings
// (whichever call's entry was due one), out[1] the nanoseconds they
// took, out[2] the buckets it has now.
void ht_growth(void* h, int64_t* out) {
  FtHashTable* t = (FtHashTable*)h;
  out[0] = t->grows;
  out[1] = t->grow_ns;
  out[2] = (int64_t)(t->mask + 1);
}

// Batch lookup; hashes computed inline. out_vals[i] untouched-where-miss
// semantics are NOT provided: misses write -1 and out_found[i]=0 (vals may
// legitimately be negative sentinels, so found is a separate byte).
void ht_lookup(void* h, const int64_t* keys, int64_t n,
               int64_t* out_vals, uint8_t* out_found) {
  FtHashTable* t = (FtHashTable*)h;
  for (int64_t i = 0; i < n; ++i) {
    uint64_t ix = ht_mix((uint64_t)keys[i]) & t->mask;
    for (;;) {
      if (!t->used[ix]) { out_vals[i] = -1; out_found[i] = 0; break; }
      if (t->keys[ix] == keys[i]) {
        out_vals[i] = t->vals[ix]; out_found[i] = 1; break;
      }
      ix = (ix + 1) & t->mask;
    }
  }
}

// Batch insert-or-update (keys need not be distinct; later wins).
void ht_insert(void* h, const int64_t* keys, const int64_t* vals, int64_t n) {
  FtHashTable* t = (FtHashTable*)h;
  for (int64_t i = 0; i < n; ++i) {
    ht_grow_if_due(t);
    uint64_t ix = ht_mix((uint64_t)keys[i]) & t->mask;
    for (;;) {
      if (!t->used[ix]) {
        t->keys[ix] = keys[i]; t->vals[ix] = vals[i]; t->used[ix] = 1;
        ++t->count;
        break;
      }
      if (t->keys[ix] == keys[i]) { t->vals[ix] = vals[i]; break; }
      ix = (ix + 1) & t->mask;
    }
  }
}

// Lookup that also CLAIMS what it misses: a key that is absent is
// entered with the placeholder value HT_PENDING - u, u its index among
// the batch's distinct misses in first-occurrence order, and written to
// out_uniq[u]; every record of that key reads the same placeholder. The
// caller allocates a value for each of the out_uniq keys, stores them
// (ht_insert updates in place) and resolves the placeholders it was
// given. Placeholders never outlive the caller's batch. Returns the
// number of distinct misses; out_uniq holds room for n. This is the
// claim as ht_assign makes it, a full probe a record and nothing after
// it: the key directory no longer calls it; the parity test and
// tools/scan_micro.py build the two-step assign of before from it.
static const int64_t HT_PENDING = -16;   // below the callers' sentinels

int64_t ht_lookup_claim(void* h, const int64_t* keys, int64_t n,
                        int64_t* out_vals, int64_t* out_uniq) {
  FtHashTable* t = (FtHashTable*)h;
  int64_t u = 0;
  for (int64_t i = 0; i < n; ++i) {
    ht_grow_if_due(t);
    uint64_t ix = ht_mix((uint64_t)keys[i]) & t->mask;
    for (;;) {
      if (!t->used[ix]) {
        t->keys[ix] = keys[i]; t->vals[ix] = HT_PENDING - u; t->used[ix] = 1;
        ++t->count;
        out_vals[i] = HT_PENDING - u;
        out_uniq[u++] = keys[i];
        break;
      }
      if (t->keys[ix] == keys[i]) { out_vals[i] = t->vals[ix]; break; }
      ix = (ix + 1) & t->mask;
    }
  }
  return u;
}

// ---------------------------------------------------------------------------
// KeyDirectory.assign (state/keyed.py) in one call: per RECORD a memo
// hit and a store, everything else once per DISTINCT key.
//
//   1. the claim, as ht_lookup_claim makes it, behind a memo: a direct-
//      mapped table of MEMO_SIZE (key -> the value or placeholder the
//      probe gave), empty at the call's start and dead at its end, so
//      nothing that happens to the table between calls can leave it
//      stale; it holds values, never buckets, so a doubling mid-call
//      does not touch it. A key's bids arrive together (a NEXmark
//      auction's within ~1,700 events): of a 2^20-bid batch 93 % of the
//      records repeat a key of a few hundred records before, and the
//      probes left are the ~68 k that touch a cold bucket. Those are
//      fetched CLAIM_AHEAD records early. The memo counts its hits;
//      where a stretch of MEMO_STRETCH records shows under one hit in
//      MEMO_PAYS it steps aside for the next MEMO_REST stretches and
//      then looks again (keys without locality pay the compare for one
//      stretch in MEMO_REST + 1, else only the probe; a batch whose
//      first tenth is records held back from seconds ago, each of a key
//      of its own time, has the memo back for the nine tenths that
//      repeat their keys: stepping aside for the whole call read
//      0.002 % hits and twice the time there, ISSUE 50). What it holds
//      from before a rest is still true: a key's value does not change
//      within a call;
//   2. slots for the distinct misses, with the outcome of
//      KeyDirectory._alloc_slots to the slot: per shard in ascending key
//      order, reclaimed slots first (newest first), then the shard's
//      free pointer, FULL past capacity, -1 outside [shard_lo,
//      shard_hi); the slot goes to the key's bucket, which is where the
//      claim left it unless the table doubled since (then by a probe);
//   3. the records' placeholders replaced by their slots, in place.
static const int64_t MEMO_STRETCH = 4096;
static const int64_t MEMO_PAYS = 8;
static const int64_t MEMO_REST = 7;
static const int64_t CLAIM_AHEAD = 64;
static const int64_t DIRECTORY_FULL = -2;   // KeyDirectory.FULL

// Records [i0, i1) of the claim: values or placeholders to out_vals,
// each distinct miss to uniq[*u] with the bucket it was entered at.
// Returns the memo's hits (0 when ``memo`` is off).
static inline __attribute__((always_inline)) int64_t claim_range(
    FtHashTable* t, MemoEntry* memo_tab, const bool memo,
    const int64_t* keys, int64_t i0, int64_t i1, int64_t n,
    int64_t* out_vals, int64_t* uniq, uint64_t* bucket, int64_t* u) {
  int64_t hits = 0;
  for (int64_t i = i0; i < i1; ++i) {
    if (i + CLAIM_AHEAD < n) {
      const int64_t ka = keys[i + CLAIM_AHEAD];
      if (!memo || memo_tab[ka & (MEMO_SIZE - 1)].key != ka) {
        const uint64_t ia = ht_mix((uint64_t)ka) & t->mask;
        __builtin_prefetch(&t->used[ia]);
        __builtin_prefetch(&t->keys[ia]);
        __builtin_prefetch(&t->vals[ia]);
      }
    }
    const int64_t k = keys[i];
    MemoEntry* m = &memo_tab[k & (MEMO_SIZE - 1)];
    if (memo && m->key == k) { out_vals[i] = m->val; ++hits; continue; }
    uint64_t ix = ht_mix((uint64_t)k) & t->mask;
    int64_t v;
    for (;;) {
      if (!t->used[ix]) {
        v = HT_PENDING - *u;
        t->keys[ix] = k; t->vals[ix] = v; t->used[ix] = 1;
        ++t->count;
        uniq[*u] = k; bucket[*u] = ix; ++*u;
        // where ht_lookup_claim would double before its next probe
        ht_grow_if_due(t);
        break;
      }
      if (t->keys[ix] == k) { v = t->vals[ix]; break; }
      ix = (ix + 1) & t->mask;
    }
    out_vals[i] = v;
    if (memo) { m->key = k; m->val = v; }
  }
  return hits;
}

// out_stats: [memo hits, records that consulted the memo, slots handed
// out (out_fresh holds them, by shard, ascending key within; room for
// n), of them reclaimed ones]. free_stacks is read only where n_free
// says a shard has slots back.
void ht_assign(void* h, const int64_t* keys, int64_t n, int64_t* out_slots,
               int64_t num_shards, int64_t shard_lo, int64_t shard_hi,
               int64_t slots_per_shard, int64_t* next_free, int64_t* n_free,
               const int32_t* free_stacks, int64_t* rev_keys,
               uint8_t* rev_used, int64_t* out_fresh, int64_t* out_stats) {
  FtHashTable* t = (FtHashTable*)h;
  out_stats[0] = out_stats[1] = out_stats[2] = out_stats[3] = 0;
  if (n == 0) return;
  if (!t->assign) t->assign = new AssignScratch;
  AssignScratch* ws = t->assign;
  // an entry's key never indexes to the entry: it matches no lookup
  for (int64_t j = 0; j < MEMO_SIZE; ++j) ws->memo[j].key = j ^ 1;
  int64_t* uniq = (int64_t*)ws->uniq.get(n * sizeof(int64_t));
  uint64_t* bucket = (uint64_t*)ws->bucket.get(n * sizeof(uint64_t));

  ht_grow_if_due(t);
  const uint64_t mask0 = t->mask;
  int64_t u = 0;
  int64_t rest = 0;   // stretches the memo still sits out
  for (int64_t i0 = 0; i0 < n; i0 += MEMO_STRETCH) {
    const int64_t i1 = n - i0 < MEMO_STRETCH ? n : i0 + MEMO_STRETCH;
    // two loops, one with the memo compiled out
    const int64_t hits =
        rest == 0 ? claim_range(t, ws->memo, true, keys, i0, i1, n,
                                out_slots, uniq, bucket, &u)
                  : claim_range(t, ws->memo, false, keys, i0, i1, n,
                                out_slots, uniq, bucket, &u);
    if (rest > 0) { --rest; continue; }
    out_stats[0] += hits;
    out_stats[1] += i1 - i0;
    if (hits * MEMO_PAYS < i1 - i0) rest = MEMO_REST;
  }
  if (u == 0) return;

  // the distinct misses by local shard (a counting sort), each shard's
  // by key
  const int64_t n_local = shard_hi - shard_lo;
  int64_t* alloc = (int64_t*)ws->alloc.get(u * sizeof(int64_t));
  KeyRef* refs = (KeyRef*)ws->refs.get(u * sizeof(KeyRef));
  int64_t* starts =
      (int64_t*)ws->starts.get((n_local + 1) * sizeof(int64_t));
  memset(starts, 0, (n_local + 1) * sizeof(int64_t));
  // alloc[j] holds key j's local shard until its slot replaces it, and
  // the verdict -1 from the start where the shard is not this range's
  for (int64_t j = 0; j < u; ++j) {
    const int64_t s =
        (int64_t)(ht_mix((uint64_t)uniq[j]) % (uint64_t)num_shards);
    alloc[j] = s >= shard_lo && s < shard_hi ? s - shard_lo : -1;
    if (alloc[j] >= 0) ++starts[alloc[j] + 1];
  }
  for (int64_t ls = 0; ls < n_local; ++ls) starts[ls + 1] += starts[ls];
  for (int64_t j = 0; j < u; ++j)
    if (alloc[j] >= 0) refs[starts[alloc[j]]++] = KeyRef{uniq[j], j};
  // starts[ls] is now the END of shard ls's run
  int64_t n_fresh = 0, n_reused = 0;
  for (int64_t ls = 0, a = 0; ls < n_local; a = starts[ls++]) {
    const int64_t b = starts[ls];
    if (a == b) continue;
    std::sort(refs + a, refs + b,
              [](const KeyRef& x, const KeyRef& y) { return x.key < y.key; });
    const int64_t depth = n_free[ls];
    const int64_t taken = depth < b - a ? depth : b - a;
    const int64_t free_ptr = next_free[shard_lo + ls];
    const int64_t room = slots_per_shard - free_ptr;
    for (int64_t r = 0; r < b - a; ++r) {
      int64_t local;
      if (r < taken) local = free_stacks[ls * slots_per_shard + depth - 1 - r];
      else if (r - taken < room) local = free_ptr + (r - taken);
      else { alloc[refs[a + r].u] = DIRECTORY_FULL; continue; }
      const int64_t slot = ls * slots_per_shard + local;
      rev_keys[slot] = refs[a + r].key;
      rev_used[slot] = 1;
      out_fresh[n_fresh++] = slot;
      alloc[refs[a + r].u] = slot;
    }
    const int64_t fresh = b - a - taken;
    n_free[ls] = depth - taken;
    next_free[shard_lo + ls] = free_ptr + (fresh < room ? fresh : room);
    n_reused += taken;
  }
  out_stats[2] = n_fresh;
  out_stats[3] = n_reused;

  if (t->mask == mask0) {
    for (int64_t j = 0; j < u; ++j) t->vals[bucket[j]] = alloc[j];
  } else {
    for (int64_t j = 0; j < u; ++j) {
      uint64_t ix = ht_mix((uint64_t)uniq[j]) & t->mask;
      while (!(t->used[ix] && t->keys[ix] == uniq[j])) ix = (ix + 1) & t->mask;
      t->vals[ix] = alloc[j];
    }
  }
  for (int64_t i = 0; i < n; ++i) {
    const int64_t v = out_slots[i];
    if (v <= HT_PENDING) out_slots[i] = alloc[HT_PENDING - v];
  }
}

// Batch delete by BACKWARD SHIFT (Knuth 6.4 algorithm R): the hole a
// deleted entry leaves is filled by the next entry of its run whose
// home bucket does not lie cyclically after the hole, and so on to the
// run's end. No tombstones: ``used`` stays a plain occupancy byte, every
// probe (ht_lookup, ht_insert, scan_range's inline one) is unchanged,
// and a probe never walks further than a table that never held the
// deleted keys would make it: run lengths are those of load <= 0.5
// whatever the number of deletes before. Keys that are absent are
// skipped. Returns how many were deleted. The table does not shrink.
int64_t ht_delete(void* h, const int64_t* keys, int64_t n) {
  FtHashTable* t = (FtHashTable*)h;
  int64_t gone = 0;
  for (int64_t i = 0; i < n; ++i) {
    uint64_t ix = ht_mix((uint64_t)keys[i]) & t->mask;
    for (;;) {
      if (!t->used[ix]) { ix = UINT64_MAX; break; }
      if (t->keys[ix] == keys[i]) break;
      ix = (ix + 1) & t->mask;
    }
    if (ix == UINT64_MAX) continue;
    uint64_t hole = ix;
    for (uint64_t j = (hole + 1) & t->mask; t->used[j];
         j = (j + 1) & t->mask) {
      const uint64_t home = ht_mix((uint64_t)t->keys[j]) & t->mask;
      // entry j may move to the hole unless its home lies in (hole, j]
      if (((j - home) & t->mask) >= ((j - hole) & t->mask)) {
        t->keys[hole] = t->keys[j];
        t->vals[hole] = t->vals[j];
        hole = j;
      }
    }
    t->used[hole] = 0;
    --t->count;
    ++gone;
  }
  return gone;
}

// The longest run of occupied buckets (a probe's worst case), for the
// tests that hold deletes to the bound above.
int64_t ht_longest_run(void* h) {
  FtHashTable* t = (FtHashTable*)h;
  int64_t best = 0, run = 0;
  // twice around: a run may wrap past the table's end
  for (uint64_t i = 0; i <= 2 * t->mask + 1 && run <= (int64_t)t->mask; ++i) {
    if (t->used[i & t->mask]) { if (++run > best) best = run; }
    else run = 0;
  }
  return best;
}

// newest[slot] = max(newest[slot], pane) for every valid record with a
// slot (>= 0): what the key directory keeps to tell when a key's last
// pane has been purged (state/keyed.py KeyDirectory.note_panes).
void slot_panes_note(int64_t n, const int64_t* slots, const int64_t* panes,
                     const uint8_t* valid, int64_t* newest) {
  for (int64_t i = 0; i < n; ++i) {
    const int64_t s = slots[i];
    if (s >= 0 && valid[i] && panes[i] > newest[s]) newest[s] = panes[i];
  }
}

// What the general lane asks of a batch's timestamps, in ONE pass:
// out = [records stamped below ``seen``, the oldest, the newest]. The
// batch's lowest and highest pane follow from the last two (a pane is
// monotone in the timestamp), so the lane makes no pass of its own for
// them; ``seen`` is the newest timestamp folded in before the batch.
// (compiled once more for AVX2 and AVX-512 and chosen at load time: the
// baseline has no 64-bit vector compare, and a scalar pass is three
// dependent chains)
__attribute__((target_clones("avx512f", "avx2", "default")))
void ts_order_stats(const int64_t* ts, int64_t n, int64_t seen,
                    int64_t* out) {
  int64_t below = 0, lo = INT64_MAX, hi = INT64_MIN;
  for (int64_t i = 0; i < n; ++i) {
    const int64_t t = ts[i];
    below += t < seen;
    lo = t < lo ? t : lo;
    hi = t > hi ? t : hi;
  }
  out[0] = below; out[1] = lo; out[2] = hi;
}

// The same from a fused scan's distinct (slot * ring + column) pairs:
// the batch's panes lie in [pane_lo, pane_lo + ring), so a column names
// one pane. Once a distinct pair, not once a record.
void slot_panes_note_pairs(int64_t np_, const int32_t* pairs, int64_t ring,
                           int64_t pane_lo, int64_t* newest) {
  const int64_t lo_col = ((pane_lo % ring) + ring) % ring;
  const uint64_t r = (uint64_t)ring;
  // p / r as a multiply and a shift: exact while p * r < 2^40, and a
  // pair id is under the fused lanes' domain of 2^23 (a division a pair
  // was most of this pass, which every batch of the fused lanes makes)
  const bool fast = r < (1u << 16);
  const uint64_t inv = (UINT64_C(1) << 40) / r + 1;
  for (int64_t j = 0; j < np_; ++j) {
    const uint64_t p = (uint32_t)pairs[j];
    const uint64_t slot = fast && p < (1u << 24) ? (p * inv) >> 40 : p / r;
    const int64_t col = (int64_t)(p - slot * r);
    const int64_t pane = pane_lo + (col >= lo_col ? col - lo_col
                                                  : col + ring - lo_col);
    if (pane > newest[slot]) newest[slot] = pane;
  }
}

// splitmix64 finalizer over a batch (hash_keys_numpy fast path).
void hash_keys(const int64_t* keys, int64_t n, int64_t* out) {
  for (int64_t i = 0; i < n; ++i)
    out[i] = (int64_t)ht_mix((uint64_t)keys[i]);
}

}  // extern "C"

// ---------------------------------------------------------------------------
// Host ingest socket reader (SURVEY §3.10 item 3: the Netty-native-
// transport analogue — a C socket layer feeding the codec above).
// One TCP listener, one connection at a time, line-framed text records;
// reads return blocks that END at a newline so the caller can hand the
// bytes straight to parse_i64_table/parse_f32_table without reassembly.
// poll()-based timeouts keep the Python caller cancellable.

#include <netinet/in.h>
#include <netinet/tcp.h>
#include <poll.h>
#include <sys/socket.h>
#include <unistd.h>

extern "C" {

struct SockReader {
  int listen_fd;
  int conn_fd;
  // carry: bytes after the last newline of the previous read
  char* carry;
  int64_t carry_len;
  int64_t carry_cap;
};

void* sr_listen(int port) {
  int fd = socket(AF_INET, SOCK_STREAM, 0);
  if (fd < 0) return nullptr;
  int one = 1;
  setsockopt(fd, SOL_SOCKET, SO_REUSEADDR, &one, sizeof(one));
  sockaddr_in addr{};
  addr.sin_family = AF_INET;
  addr.sin_addr.s_addr = htonl(INADDR_LOOPBACK);
  addr.sin_port = htons((uint16_t)port);
  if (bind(fd, (sockaddr*)&addr, sizeof(addr)) != 0 ||
      listen(fd, 1) != 0) {
    close(fd);
    return nullptr;
  }
  SockReader* r = (SockReader*)calloc(1, sizeof(SockReader));
  r->listen_fd = fd;
  r->conn_fd = -1;
  r->carry_cap = 1 << 16;
  r->carry = (char*)malloc(r->carry_cap);
  return r;
}

int sr_port(void* h) {
  SockReader* r = (SockReader*)h;
  sockaddr_in addr{};
  socklen_t len = sizeof(addr);
  if (getsockname(r->listen_fd, (sockaddr*)&addr, &len) != 0) return -1;
  return ntohs(addr.sin_port);
}

// 1 = connected, 0 = timeout, -1 = error
int sr_accept(void* h, int timeout_ms) {
  SockReader* r = (SockReader*)h;
  if (r->conn_fd >= 0) return 1;
  pollfd p{r->listen_fd, POLLIN, 0};
  int rc = poll(&p, 1, timeout_ms);
  if (rc == 0) return 0;
  if (rc < 0) return -1;
  r->conn_fd = accept(r->listen_fd, nullptr, nullptr);
  if (r->conn_fd < 0) return -1;
  int one = 1;
  setsockopt(r->conn_fd, IPPROTO_TCP, TCP_NODELAY, &one, sizeof(one));
  return 1;
}

static int64_t sr_last_newline(const char* buf, int64_t n) {
  for (int64_t i = n - 1; i >= 0; --i)
    if (buf[i] == '\n') return i;
  return -1;
}

// Flush out[0..nl] as the block; out[nl+1..have) goes back onto the
// FRONT of the carry (it precedes anything already carried).
static int64_t sr_flush(SockReader* r, char* out, int64_t have,
                        int64_t nl) {
  int64_t tail = have - (nl + 1);
  if (tail > 0) {
    if (r->carry_len + tail > r->carry_cap) {
      r->carry_cap = (r->carry_len + tail) * 2;
      r->carry = (char*)realloc(r->carry, r->carry_cap);
    }
    memmove(r->carry + tail, r->carry, r->carry_len);
    memcpy(r->carry, out + nl + 1, tail);
    r->carry_len += tail;
  }
  return nl + 1;
}

// Read COMPLETE lines into out (<= cap bytes, ending at a newline).
// Returns bytes written; 0 = timeout (no complete line yet);
// -1 = connection closed (an unterminated tail at EOF is not a
// record under line framing and is discarded); -2 = error
// (including a single line longer than cap).
int64_t sr_read_block(void* h, char* out, int64_t cap, int timeout_ms) {
  SockReader* r = (SockReader*)h;
  if (r->conn_fd < 0) return -2;
  int64_t have = r->carry_len < cap ? r->carry_len : cap;
  memcpy(out, r->carry, have);
  memmove(r->carry, r->carry + have, r->carry_len - have);
  r->carry_len -= have;
  for (;;) {
    int64_t nl = sr_last_newline(out, have);
    if (nl >= 0 && (have == cap || r->carry_len > 0))
      return sr_flush(r, out, have, nl);  // buffer full / carry pending
    if (have == cap)
      return -2;  // full buffer, no newline: oversized line
    pollfd p{r->conn_fd, POLLIN, 0};
    int rc = poll(&p, 1, timeout_ms);
    if (rc == 0)
      return nl >= 0 ? sr_flush(r, out, have, nl) : 0;
    if (rc < 0) return -2;
    int64_t n = read(r->conn_fd, out + have, cap - have);
    if (n == 0) {
      int64_t nl2 = sr_last_newline(out, have);
      return nl2 >= 0 ? nl2 + 1 : -1;  // EOF
    }
    if (n < 0) return -2;
    have += n;
  }
}

void sr_close(void* h) {
  SockReader* r = (SockReader*)h;
  if (r->conn_fd >= 0) close(r->conn_fd);
  close(r->listen_fd);
  free(r->carry);
  free(r);
}

// NEXMark bid-batch generator (the benchmark workload's native
// data-loader; ref role: the optimized Java generator in the external
// nexmark/nexmark repo). splitmix64 PRNG, log-normal prices via a
// 4-uniform Irwin-Hall normal approximation + expf. Deterministic in
// (seed) — the replayable-source contract. On the single-core bench
// host this replaces ~116ms/batch of numpy RNG with ~10ms of C.
static inline uint64_t smx(uint64_t* s) {
  uint64_t z = (*s += 0x9E3779B97F4A7C15ULL);
  z = (z ^ (z >> 30)) * 0xBF58476D1CE4E5B9ULL;
  z = (z ^ (z >> 27)) * 0x94D049BB133111EBULL;
  return z ^ (z >> 31);
}

// Schraudolph-style fast e^x: the synthetic price distribution needs
// shape, not ulps (|rel err| < ~4%); real expf costs ~40ms per 2^20
// batch on the single-core bench host, this ~2ms.
static inline float fast_exp(float x) {
  union { float f; int32_t i; } u;
  u.i = (int32_t)(12102203.0f * x + 1064866805.0f);
  return u.f;
}

void nexmark_bids(int64_t seed, int64_t n, int64_t hot_ratio, int64_t n_hot,
                  int64_t n_auctions, int64_t n_people,
                  int64_t* auction, int64_t* bidder, float* price) {
  // counter-based (stateless per index): no serial PRNG dependency
  // chain, so the loop pipelines/vectorizes
  const uint64_t G = 0x9E3779B97F4A7C15ULL;
  const uint64_t b1 = (uint64_t)seed * 0xD1342543DE82EF95ULL + 1;
  const uint64_t b2 = b1 ^ 0x94D049BB133111EBULL;
  const float inv16 = 1.0f / 65536.0f;
  const uint64_t na = (uint64_t)n_auctions, nh = (uint64_t)n_hot,
                 np_ = (uint64_t)n_people;
  for (int64_t i = 0; i < n; ++i) {
    uint64_t c1 = b1 + (uint64_t)i * G, c2 = b2 + (uint64_t)i * G;
    uint64_t r1 = smx(&c1), r2 = smx(&c2);
    // multiply-shift range reduction instead of % (uniform enough for
    // a workload generator, ~10x cheaper than div)
    int hot = (int)((r1 & 0xFF) % (uint64_t)hot_ratio) == 0;
    uint64_t a32 = (r1 >> 8) & 0xFFFFFFFFULL;
    auction[i] = (int64_t)((a32 * (hot ? nh : na)) >> 32);
    bidder[i] = (int64_t)((((r1 >> 40) & 0xFFFFFFULL) * np_) >> 24);
    // Irwin-Hall(4) ~ N(2, 1/3) from four u16 lanes -> N(6, 1) -> exp
    float u = ((uint16_t)r2 + (uint16_t)(r2 >> 16) +
               (uint16_t)(r2 >> 32) + (uint16_t)(r2 >> 48)) * inv16;
    float z = (u - 2.0f) * 1.7320508f;
    price[i] = fast_exp(6.0f + z);
  }
}

// Host pre-aggregation combine (mini-batch local aggregation, the
// window operator's upload shrinker): histogram one microbatch per
// (slot, ring-column) pair, with optional f64-accumulated sum lanes
// per pair. ``hist`` (domain i32) and ``lane_acc`` (domain*nlanes f64)
// are caller-owned workspaces that must be ZERO on entry; every touched
// entry is reset before returning, so steady-state calls never pay a
// full-domain clear. ``lanes`` is lane-major: lanes[l*n + i].
// Returns the distinct-pair count, or -1 when it exceeds ``cap`` — in
// that case recording stopped at cap and the workspaces are left DIRTY:
// the caller must re-zero them before the next call.
int64_t preagg_combine(int64_t n, const int64_t* slots, const int64_t* panes,
                       const uint8_t* valid, int64_t ring, int64_t domain,
                       int64_t nlanes, const double* lanes,
                       int32_t* hist, double* lane_acc,
                       int32_t* out_pairs, int32_t* out_counts,
                       float* out_lanes, int64_t cap) {
  int64_t np_ = 0;
  for (int64_t i = 0; i < n; ++i) {
    if (!valid[i]) continue;
    int64_t pm = panes[i] % ring;
    if (pm < 0) pm += ring;
    int64_t p = slots[i] * ring + pm;  // caller guarantees p < domain
    if (hist[p] == 0) {
      if (np_ >= cap) return -1;  // workspaces dirty; caller re-zeros
      out_pairs[np_++] = (int32_t)p;
    }
    hist[p] += 1;
    for (int64_t l = 0; l < nlanes; ++l)
      lane_acc[p * nlanes + l] += lanes[l * n + i];
  }
  for (int64_t j = 0; j < np_; ++j) {
    int64_t p = out_pairs[j];
    out_counts[j] = hist[p];
    hist[p] = 0;
    for (int64_t l = 0; l < nlanes; ++l) {
      out_lanes[j * nlanes + l] = (float)lane_acc[p * nlanes + l];
      lane_acc[p * nlanes + l] = 0.0;
    }
  }
  return np_;
}

// The pane of event time that the record before fell in, kept between
// records. A record costs two 64-bit divisions when its pane is worked
// out from its timestamp (t / pane_ms floored, pane % ring), and an
// in-order stream changes pane once in ~10^6 records (PERF.md section 5:
// a 2^20 batch spans 23 ms against 2 s panes). So a record whose
// t = ts - offset_ms lies in [lo, lo + pane_ms) takes pane, ring column,
// dead and refire from here for one compare; any other moves the cursor
// with the divisions above. Nothing is assumed of the stream's order: a
// stream whose panes alternate record by record moves every time and
// pays the divisions plus that compare.
//
// pmin / pmax and the refire bitmap change only with the pane, so the
// first VALID record of each cursor position writes them (`noted`); a
// move on a late or bad-slot record writes nothing, as a scan record by
// record would not.
struct PaneCursor {
  int64_t pane_ms, ring, dead_below, refire_below, bitmap_base, bitmap_bits;
  int64_t lo;      // first t of the cached pane
  uint64_t width;  // pane_ms, or 0 while nothing is cached: no t matches
  int64_t pane, col;
  int64_t pmin, pmax;
  int64_t moves;   // times the pane was worked out by division
  bool dead;       // pane < dead_below: its records are late
  bool refire;     // a late-refire candidate that has a bit in the bitmap
  bool noted;      // pmin / pmax / bitmap already hold this pane
};

static inline void pane_cursor_init(
    PaneCursor* c, int64_t pane_ms, int64_t ring, int64_t dead_below,
    int64_t refire_below, int64_t bitmap_base, int64_t bitmap_len,
    int64_t pmin, int64_t pmax) {
  c->pane_ms = pane_ms; c->ring = ring; c->dead_below = dead_below;
  c->refire_below = refire_below; c->bitmap_base = bitmap_base;
  c->bitmap_bits = bitmap_len * 8;
  c->lo = 0; c->width = 0; c->pane = 0; c->col = 0;
  c->pmin = pmin; c->pmax = pmax; c->moves = 0;
  c->dead = c->refire = c->noted = false;
}

// Point the cursor at t's pane (t = ts - offset_ms; floored division,
// so negative event times land in the pane below zero). True when it
// had to move.
static inline bool pane_cursor_seek(PaneCursor* c, int64_t t) {
  if ((uint64_t)t - (uint64_t)c->lo < c->width) return false;
  int64_t rem = t % c->pane_ms;
  int64_t pane = t / c->pane_ms - (rem < 0 ? 1 : 0);
  if (rem < 0) rem += c->pane_ms;
  c->lo = t - rem;
  c->width = (uint64_t)c->pane_ms;
  c->pane = pane;
  c->col = pane % c->ring;
  if (c->col < 0) c->col += c->ring;
  c->dead = pane < c->dead_below;
  c->refire = false;
  if (pane < c->refire_below) {
    int64_t off = pane - c->bitmap_base;
    c->refire = off >= 0 && off < c->bitmap_bits;
  }
  c->noted = false;
  ++c->moves;
  return true;
}

// A valid record landed in the cursor's pane: fold the pane into
// pmin / pmax and the refire bitmap, once per cursor position.
static inline void pane_cursor_note(PaneCursor* c, uint8_t* refire_bitmap) {
  if (c->noted) return;
  c->noted = true;
  if (c->pane < c->pmin) c->pmin = c->pane;
  if (c->pane > c->pmax) c->pmax = c->pane;
  if (c->refire) {
    int64_t off = c->pane - c->bitmap_base;
    refire_bitmap[off >> 3] |= (uint8_t)(1u << (off & 7));
  }
}

// Count one record into pair p = slot * ring + column; a pair's first
// touch appends it to out_pairs. False when that would pass ``cap``.
static inline bool pair_count(int32_t* hist, int64_t p, int32_t* out_pairs,
                              int64_t* np_, int64_t cap) {
  if (hist[p] == 0) {
    if (*np_ >= cap) return false;
    out_pairs[(*np_)++] = (int32_t)p;
  }
  ++hist[p];
  return true;
}

// Fused ingest pass over (ts, slots) for a caller that already holds
// the slots: per record the event-time pane (through the cursor above),
// the late-beyond-lateness drop, bad-slot accounting, pane min / max,
// late-refire candidates, and the (slot, ring-column) histogram that the
// pre-agg upload ships, in one scan instead of four or five full-array
// numpy passes. ingest_fused_scan below is the same semantics behind a
// key probe, and the one the window operator calls; the two share the
// cursor so that they stay one algorithm.
// ``hist`` must be zero on entry; touched entries are reset (see
// preagg_combine). Returns distinct-pair count, or -1 on cap overflow
// (workspaces left dirty — caller re-zeros).
// out_stats: [n_valid, n_late, n_bad, pane_min, pane_max, n_refire]
int64_t ingest_combine(
    int64_t n, const int64_t* ts, const int64_t* slots,
    int64_t pane_ms, int64_t offset_ms, int64_t ring, int64_t /*domain*/,
    int64_t dead_below, int64_t refire_below,
    int32_t* hist, int32_t* out_pairs, int32_t* out_counts, int64_t cap,
    int64_t* out_stats, uint8_t* refire_bitmap, int64_t bitmap_base,
    int64_t bitmap_len) {
  int64_t np_ = 0, n_valid = 0, n_late = 0, n_bad = 0, n_refire = 0;
  PaneCursor cur;
  pane_cursor_init(&cur, pane_ms, ring, dead_below, refire_below,
                   bitmap_base, bitmap_len, INT64_MAX, INT64_MIN);
  for (int64_t i = 0; i < n; ++i) {
    pane_cursor_seek(&cur, ts[i] - offset_ms);
    if (cur.dead) { ++n_late; continue; }
    if (slots[i] < 0) { ++n_bad; continue; }
    ++n_valid;
    pane_cursor_note(&cur, refire_bitmap);
    n_refire += cur.refire;
    if (!pair_count(hist, slots[i] * ring + cur.col, out_pairs, &np_, cap))
      return -1;
  }
  for (int64_t j = 0; j < np_; ++j) {
    int64_t p = out_pairs[j];
    out_counts[j] = hist[p];
    hist[p] = 0;
  }
  out_stats[0] = n_valid;
  out_stats[1] = n_late;
  out_stats[2] = n_bad;
  out_stats[3] = cur.pmin;
  out_stats[4] = cur.pmax;
  out_stats[5] = n_refire;
  return np_;
}

// Fully-fused count-only ingest: key->slot directory probe (the open-
// addressing table above) + event-time pane + late/refire accounting +
// (slot, ring-column) histogram in ONE scan over (keys, ts), so no
// slots array is written and read back. This call is the span
// window.key_scan, most of the host's time a batch (PERF.md section 5),
// and its loop does per record only what differs per record:
//
//   - the batch is walked in blocks of SCAN_BLOCK records. Pass A
//     resolves each key of the block to its slot; its iterations are
//     independent, so the core overlaps the table's cache misses. Pass
//     B, in record order, does everything that depends on order: the
//     miss list, late / bad counts, first-touch pair order, the
//     histogram, and the -1 / -2 returns at the record that overflows;
//   - the pane comes from the cursor above (no division while the pane
//     holds), and with it pmin / pmax / the refire bit. Pass B takes a
//     record "the long way" (everything the semantics ask, in their
//     order) until one finds its pane cached, live and noted; from there
//     it RUNS: while the next record has a known slot >= 0 and lies in
//     the same pane there is nothing to decide but the pair, and
//     n_valid / n_refire grow by the run's length. Any other record
//     ends the run and goes the long way;
//   - cmax, the largest count of any pair, is one pass over the pairs
//     at the end: counts only grow, so the largest final count IS the
//     running maximum, also across a ``cont`` call, which walks the
//     workspace's pairs of both calls.
//
// Records whose key is NOT in the table are skipped and their indices
// written to out_miss, ascending (caller registers the new keys, then
// re-invokes over the miss subset with np_in continuing — at steady
// state with a bounded key domain the miss list is empty). The probe
// comes before the pane: an unknown key must reach the miss list even
// when its record is late (registration is not drop-sensitive). Keys
// mapped to a NEGATIVE slot (directory FULL sentinel) count into n_bad
// exactly as the unfused path did.
//
// stats accumulate ACROSS calls: [n_valid, n_late, n_bad, pmin, pmax,
// n_refire, n_miss, cmax, pane_moves]; the caller seeds pmin=INT64_MAX,
// pmax=INT64_MIN, rest 0. pane_moves counts the records whose pane was
// worked out by division: 1-2 a batch on an in-order stream, ~n where
// panes alternate. Returns the running distinct-pair count, or
// -1 on pair-cap overflow / -2 on miss-cap overflow (stats untouched,
// workspace left dirty; caller re-zeros and falls back).
static const int64_t SCAN_BLOCK = 512;

// Records [i0, i1) of the batch through the loop described above, into
// the workspace it is given; miss indices are the records' own (batch)
// indices. The serial entry runs it over the whole batch into the
// caller's workspace; the split entry runs it over contiguous ranges
// side by side, each into a workspace of its own. Writes ``stats`` only
// when it succeeds, and never stats[7] (cmax: the entry's to compute).
static int64_t scan_range(
    int64_t i0, int64_t i1, const int64_t* keys, const int64_t* ts,
    const FtHashTable* t, int64_t pane_ms, int64_t offset_ms, int64_t ring,
    int64_t dead_below, int64_t refire_below,
    int32_t* hist, int32_t* out_pairs, int64_t np_in, int64_t cap,
    int64_t* stats, uint8_t* refire_bitmap, int64_t bitmap_base,
    int64_t bitmap_len, int64_t* out_miss, int64_t miss_cap) {
  int64_t np_ = np_in, n_valid = 0, n_late = 0, n_bad = 0;
  int64_t n_refire = 0, n_miss = stats[6];
  PaneCursor cur;
  pane_cursor_init(&cur, pane_ms, ring, dead_below, refire_below,
                   bitmap_base, bitmap_len, stats[3], stats[4]);
  int64_t slot_of[SCAN_BLOCK];  // INT64_MIN = key not in the table
  // the last record taken the long way found its pane cached: the
  // stream runs along one pane, it does not hop between panes
  bool steady = false;
  for (int64_t b0 = i0; b0 < i1; b0 += SCAN_BLOCK) {
    const int64_t bn = i1 - b0 < SCAN_BLOCK ? i1 - b0 : SCAN_BLOCK;
    const int64_t* bk = keys + b0;
    const int64_t* bts = ts + b0;
    for (int64_t j = 0; j < bn; ++j) {
      uint64_t ix = ht_mix((uint64_t)bk[j]) & t->mask;
      int64_t slot;
      for (;;) {
        if (!t->used[ix]) { slot = INT64_MIN; break; }
        if (t->keys[ix] == bk[j]) { slot = t->vals[ix]; break; }
        ix = (ix + 1) & t->mask;
      }
      slot_of[j] = slot;
    }
    for (int64_t j = 0; j < bn;) {
      if (steady && cur.noted && !cur.dead) {
        // the run: nothing to decide for a record but its pair
        const uint64_t lo_ts = (uint64_t)cur.lo + (uint64_t)offset_ms;
        const uint64_t width = cur.width;
        const int64_t col = cur.col, j0 = j;
        for (; j < bn; ++j) {
          const int64_t slot = slot_of[j];
          if (slot < 0 || (uint64_t)bts[j] - lo_ts >= width) break;
          if (!pair_count(hist, slot * ring + col, out_pairs, &np_, cap))
            return -1;
        }
        n_valid += j - j0;
        if (cur.refire) n_refire += j - j0;
        if (j == bn) break;
      }
      // one record the long way: anything may happen to it
      const int64_t slot = slot_of[j];
      if (slot == INT64_MIN) {
        if (n_miss >= miss_cap) return -2;
        out_miss[n_miss++] = b0 + j++;
        continue;
      }
      steady = !pane_cursor_seek(&cur, bts[j++] - offset_ms);
      if (cur.dead) { ++n_late; continue; }
      if (slot < 0) { ++n_bad; continue; }
      ++n_valid;
      pane_cursor_note(&cur, refire_bitmap);
      n_refire += cur.refire;
      if (!pair_count(hist, slot * ring + cur.col, out_pairs, &np_, cap))
        return -1;
    }
  }
  stats[0] += n_valid;
  stats[1] += n_late;
  stats[2] += n_bad;
  stats[3] = cur.pmin;
  stats[4] = cur.pmax;
  stats[5] += n_refire;
  stats[6] = n_miss;
  stats[8] += cur.moves;
  return np_;
}

// The largest count of any recorded pair, no less than ``cmax``.
static int64_t pairs_cmax(const int32_t* hist, const int32_t* out_pairs,
                          int64_t np_, int64_t cmax) {
  for (int64_t j = 0; j < np_; ++j)
    if (hist[out_pairs[j]] > cmax) cmax = hist[out_pairs[j]];
  return cmax;
}

int64_t ingest_fused_scan(
    int64_t n, const int64_t* keys, const int64_t* ts, void* ht,
    int64_t pane_ms, int64_t offset_ms, int64_t ring,
    int64_t dead_below, int64_t refire_below,
    int32_t* hist, int32_t* out_pairs, int64_t np_in, int64_t cap,
    int64_t* stats, uint8_t* refire_bitmap, int64_t bitmap_base,
    int64_t bitmap_len, int64_t* out_miss, int64_t miss_cap) {
  const int64_t np_ = scan_range(
      0, n, keys, ts, (const FtHashTable*)ht, pane_ms, offset_ms, ring,
      dead_below, refire_below, hist, out_pairs, np_in, cap, stats,
      refire_bitmap, bitmap_base, bitmap_len, out_miss, miss_cap);
  if (np_ >= 0) stats[7] = pairs_cmax(hist, out_pairs, np_, stats[7]);
  return np_;
}

// What one later range of a split scan left behind: its pairs in its
// own first-touch order with their counts beside them (the range's
// thread packs them out of its private histogram and zeroes what it
// touched there, so the merge reads two dense arrays).
struct RangeOut {
  int32_t* hist;        // domain entries, zero on entry and after packing
  int32_t* pairs;
  int32_t* counts;
  int64_t np_;          // pair count, or scan_range's -1 / -2
  int64_t stats[9];
  uint8_t* bitmap;
  int64_t* miss;        // ascending batch indices, stats[6] of them
};

// Fold a later range into the workspace that holds the ranges before
// it. A pair's first occurrence in record order lies in the first range
// that holds it, at its place in that range's own first-touch order; so
// appending each range's NEW pairs in range order, in the range's order,
// gives the list a serial scan would have written. Counts add, misses
// are appended (ranges ascend, so the list does). -1 when the merged
// pairs pass ``cap``, -2 when the merged misses pass ``miss_cap``, else
// the merged pair count.
static int64_t merge_range(int32_t* hist, int32_t* out_pairs, int64_t np_,
                           int64_t cap, int64_t* stats, uint8_t* bitmap,
                           int64_t bitmap_len, int64_t* out_miss,
                           int64_t miss_cap, const RangeOut* r) {
  if (stats[6] + r->stats[6] > miss_cap) return -2;
  for (int64_t j = 0; j < r->np_; ++j) {
    const int32_t p = r->pairs[j];
    if (hist[p] == 0) {
      if (np_ >= cap) return -1;
      out_pairs[np_++] = p;
    }
    hist[p] += r->counts[j];
  }
  stats[0] += r->stats[0];
  stats[1] += r->stats[1];
  stats[2] += r->stats[2];
  if (r->stats[3] < stats[3]) stats[3] = r->stats[3];
  if (r->stats[4] > stats[4]) stats[4] = r->stats[4];
  stats[5] += r->stats[5];
  memcpy(out_miss + stats[6], r->miss, r->stats[6] * sizeof(int64_t));
  stats[6] += r->stats[6];
  stats[8] += r->stats[8];
  for (int64_t b = 0; b < bitmap_len; ++b) bitmap[b] |= r->bitmap[b];
  return np_;
}

// Threads that wait between batches for the later ranges of a split
// scan, and those ranges' scratch. Worker w runs range w of a round
// that has that many; the set grows to the ranges asked of it. One
// caller owns a set (scan_workers_new / _free) and runs one round at a
// time. Started per batch instead, three threads held range 0 back by
// 0.25 ms and eight by 1.1 ms on the chip's host (PERF.md, PR 30).
struct ScanWorkers {
  std::mutex mu;
  std::condition_variable wake, done;
  std::vector<std::thread> threads;
  const std::function<void(int64_t)>* job = nullptr;
  uint64_t round = 0;
  int64_t ranges = 0;   // this round's: workers 1 .. ranges - 1 run
  int64_t left = 0;     // of them, still running
  bool stop = false;
  Scratch pairs, counts, miss, bitmaps;
};

static void scan_worker_main(ScanWorkers* w, int64_t me, uint64_t seen) {
  std::unique_lock<std::mutex> lk(w->mu);
  for (;;) {
    w->wake.wait(lk, [&] { return w->stop || w->round != seen; });
    if (w->stop) return;
    seen = w->round;
    if (me >= w->ranges) continue;
    const std::function<void(int64_t)>* job = w->job;
    lk.unlock();
    (*job)(me);
    lk.lock();
    if (--w->left == 0) w->done.notify_one();
  }
}

void* scan_workers_new() { return new ScanWorkers(); }

void scan_workers_free(void* h) {
  ScanWorkers* w = (ScanWorkers*)h;
  {
    std::lock_guard<std::mutex> lk(w->mu);
    w->stop = true;
  }
  w->wake.notify_all();
  for (std::thread& th : w->threads) th.join();
  delete w;
}

// job(1) .. job(ranges - 1) on the workers, job(0) here; back when all
// are done. A range whose worker cannot be started runs here too.
static void scan_workers_run(ScanWorkers* w, int64_t ranges,
                             const std::function<void(int64_t)>& job) {
  int64_t have;
  {
    std::lock_guard<std::mutex> lk(w->mu);
    while ((int64_t)w->threads.size() < ranges - 1) {
      try {
        w->threads.emplace_back(scan_worker_main, w,
                                (int64_t)w->threads.size() + 1, w->round);
      } catch (const std::system_error&) {
        break;
      }
    }
    have = (int64_t)w->threads.size() + 1;
    if (have > ranges) have = ranges;
    w->job = &job;
    w->ranges = have;
    w->left = have - 1;
    ++w->round;
  }
  w->wake.notify_all();
  job(0);
  for (int64_t j = have; j < ranges; ++j) job(j);
  std::unique_lock<std::mutex> lk(w->mu);
  w->done.wait(lk, [&] { return w->left == 0; });
}

// ingest_fused_scan with its first pass split by record range over
// ``ranges`` threads: range j is [n*j/ranges, n*(j+1)/ranges). Range 0
// runs on the calling thread into the caller's workspace (its pairs are
// the head of the serial list); each later range runs scan_range on a
// worker into a private histogram (``range_hist``: ranges - 1 rows of
// ``domain`` entries, zero on entry and on every return but an
// overflow's), pair list, statistics, bitmap and miss list. While
// ranges run nothing shared is written: the key table is read-only
// here. Then merge_range folds them in, in range order, and everything
// the caller sees — pairs and their order, counts, stats[0..7], bitmap,
// miss list — is what the serial call over the same batch leaves.
// stats[8] (pane_moves) is the sum over the ranges: each seeks its
// first record's pane, so an in-order batch reads up to ranges + 1
// where the serial call reads 1-2. A first call only (no np_in: the
// ``cont`` pass over the registered misses stays serial). -1 when any
// range or the merged list passes ``cap``, -2 when the merged misses
// pass ``miss_cap``: stats untouched, workspaces dirty.
int64_t ingest_fused_scan_split(
    int64_t n, const int64_t* keys, const int64_t* ts, void* ht,
    int64_t pane_ms, int64_t offset_ms, int64_t ring,
    int64_t dead_below, int64_t refire_below,
    int32_t* hist, int32_t* out_pairs, int64_t cap,
    int64_t* stats, uint8_t* refire_bitmap, int64_t bitmap_base,
    int64_t bitmap_len, int64_t* out_miss, int64_t miss_cap,
    void* workers, int64_t ranges, int32_t* range_hist, int64_t domain) {
  const FtHashTable* t = (const FtHashTable*)ht;
  ScanWorkers* w = (ScanWorkers*)workers;
  const int64_t later = ranges - 1;
  auto lo = [=](int64_t j) { return n * j / ranges; };
  std::vector<RangeOut> out(later);
  int32_t* pairs = (int32_t*)w->pairs.get(later * cap * sizeof(int32_t));
  int32_t* counts = (int32_t*)w->counts.get(later * cap * sizeof(int32_t));
  uint8_t* bitmaps = (uint8_t*)w->bitmaps.get(later * bitmap_len);
  memset(bitmaps, 0, later * bitmap_len);
  // a range has no more misses than records: its slice of one
  // n-entry buffer, from its own first index on, is room enough
  int64_t* miss = (int64_t*)w->miss.get(n * sizeof(int64_t));
  for (int64_t j = 1; j < ranges; ++j) {
    RangeOut* r = &out[j - 1];
    r->hist = range_hist + (j - 1) * domain;
    r->pairs = pairs + (j - 1) * cap;
    r->counts = counts + (j - 1) * cap;
    r->bitmap = bitmaps + (j - 1) * bitmap_len;
    r->miss = miss + lo(j);
    memcpy(r->stats, stats, sizeof r->stats);
  }
  int64_t s[9];
  memcpy(s, stats, sizeof s);
  int64_t np_ = 0;
  const std::function<void(int64_t)> run = [&](int64_t j) {
    if (j == 0) {
      np_ = scan_range(
          0, lo(1), keys, ts, t, pane_ms, offset_ms, ring, dead_below,
          refire_below, hist, out_pairs, 0, cap, s, refire_bitmap,
          bitmap_base, bitmap_len, out_miss, miss_cap);
      return;
    }
    RangeOut* r = &out[j - 1];
    r->np_ = scan_range(
        lo(j), lo(j + 1), keys, ts, t, pane_ms, offset_ms, ring, dead_below,
        refire_below, r->hist, r->pairs, 0, cap, r->stats, r->bitmap,
        bitmap_base, bitmap_len, r->miss, lo(j + 1) - lo(j));
    for (int64_t k = 0; k < r->np_; ++k) {
      r->counts[k] = r->hist[r->pairs[k]];
      r->hist[r->pairs[k]] = 0;
    }
  };
  scan_workers_run(w, ranges, run);
  for (const RangeOut& r : out)
    if (np_ >= 0 && r.np_ < 0) np_ = r.np_;
  for (const RangeOut& r : out)
    if (np_ >= 0)
      np_ = merge_range(hist, out_pairs, np_, cap, s, refire_bitmap,
                        bitmap_len, out_miss, miss_cap, &r);
  if (np_ < 0) return np_;
  s[7] = pairs_cmax(hist, out_pairs, np_, s[7]);
  memcpy(stats, s, sizeof s);
  return np_;
}

// Finalize a fused scan into the packed u32 upload buffer the device
// kernel consumes: out_u32[hdr + j] = (pair << 12) | count for the np_
// recorded pairs, -1 padding elsewhere (header region included — the
// pending advance fills it before dispatch). Resets every touched hist
// entry, so steady-state calls never pay a full-domain clear.
// Precondition: every count < 0xFFF (the caller checked stats[7]).
void ingest_fused_finalize_u32(
    int64_t np_, int32_t* hist, const int32_t* out_pairs,
    int32_t* out_u32, int64_t hdr, int64_t cap_out) {
  for (int64_t j = 0; j < hdr; ++j) out_u32[j] = -1;
  for (int64_t j = 0; j < np_; ++j) {
    int32_t p = out_pairs[j];
    out_u32[hdr + j] = (int32_t)(((uint32_t)p << 12) | (uint32_t)hist[p]);
    hist[p] = 0;
  }
  for (int64_t j = hdr + np_; j < hdr + cap_out; ++j) out_u32[j] = -1;
}

// Finalize into separate (pairs, counts) arrays — the fallback when a
// count overflows the u32 pack's 12-bit field (u16/i32 encode paths).
void ingest_fused_finalize_pairs(
    int64_t np_, int32_t* hist, const int32_t* out_pairs,
    int32_t* out_counts) {
  for (int64_t j = 0; j < np_; ++j) {
    int32_t p = out_pairs[j];
    out_counts[j] = hist[p];
    hist[p] = 0;
  }
}

// CRC-32 (ISO-HDLC, polynomial 0xEDB88320), slice-by-8 —
// BIT-IDENTICAL to Python's zlib.crc32, so a native-checksummed DCN
// frame verifies on a fallback (zlib) peer and vice versa. The point
// of the native path is not raw speed alone: ctypes calls DROP the
// GIL, so the exchange's per-peer I/O threads checksum frames in
// parallel — CPython 3.10's zlib.crc32 holds the GIL for the whole
// pass, serializing every frame checksum in the process
// (exchange/frames.py; measured 2-3x whole-exchange cost at 1MB).
static uint32_t g_crc_tab[8][256];
static int crc_tables_init() {
  for (int i = 0; i < 256; ++i) {
    uint32_t c = (uint32_t)i;
    for (int k = 0; k < 8; ++k)
      c = (c & 1) ? 0xEDB88320u ^ (c >> 1) : c >> 1;
    g_crc_tab[0][i] = c;
  }
  for (int i = 0; i < 256; ++i)
    for (int s = 1; s < 8; ++s)
      g_crc_tab[s][i] =
          (g_crc_tab[s - 1][i] >> 8) ^ g_crc_tab[0][g_crc_tab[s - 1][i] & 0xff];
  return 0;
}
static const int g_crc_ready = crc_tables_init();  // load-time init

static uint32_t crc32_slice8(const uint8_t* p, int64_t len, uint32_t init) {
  (void)g_crc_ready;
  uint32_t c = ~init;
  while (len > 0 && ((uintptr_t)p & 7)) {
    c = g_crc_tab[0][(c ^ *p++) & 0xff] ^ (c >> 8);
    --len;
  }
  while (len >= 8) {  // little-endian slicing (x86/arm64)
    uint32_t lo, hi;
    memcpy(&lo, p, 4);
    memcpy(&hi, p + 4, 4);
    lo ^= c;
    c = g_crc_tab[7][lo & 0xff] ^ g_crc_tab[6][(lo >> 8) & 0xff] ^
        g_crc_tab[5][(lo >> 16) & 0xff] ^ g_crc_tab[4][lo >> 24] ^
        g_crc_tab[3][hi & 0xff] ^ g_crc_tab[2][(hi >> 8) & 0xff] ^
        g_crc_tab[1][(hi >> 16) & 0xff] ^ g_crc_tab[0][hi >> 24];
    p += 8;
    len -= 8;
  }
  while (len-- > 0) c = g_crc_tab[0][(c ^ *p++) & 0xff] ^ (c >> 8);
  return ~c;
}

#if defined(__x86_64__) || defined(__i386__)
// PCLMULQDQ-folded CRC-32 (same ISO-HDLC polynomial, reflected) —
// the Intel "Fast CRC Computation Using PCLMULQDQ" folding scheme for
// the IEEE polynomial, as shipped in zlib-ng / Chromium zlib / the
// Linux kernel. Folding constants are x^n mod P in the reflected
// domain; a load-time SELF-CHECK against the table path (below)
// guards the constants — a mismatch disables this path entirely, so
// a wrong constant can only ever cost speed, never correctness.
// Measured on a CPU container: slice-by-8 ~1.8 GB/s, PCLMUL ~10+ GB/s —
// the log tier's decode bandwidth is CRC-bound without it.
#include <immintrin.h>

__attribute__((target("pclmul,sse4.1")))
static uint32_t crc32_pclmul(const uint8_t* p, int64_t len, uint32_t init) {
  // k1 = x^(4*128+32) mod P, k2 = x^(4*128-32) mod P  (64B fold)
  // k3 = x^(128+32)  mod P, k4 = x^(128-32)  mod P  (16B fold)
  // k5 = x^64 mod P; poly/mu: Barrett reduction pair
  const __m128i k1k2 = _mm_set_epi64x(0x00000001c6e41596ll,
                                      0x0000000154442bd4ll);
  const __m128i k3k4 = _mm_set_epi64x(0x00000000ccaa009ell,
                                      0x00000001751997d0ll);
  const __m128i k5 = _mm_set_epi64x(0, 0x0000000163cd6124ll);
  const __m128i pmu = _mm_set_epi64x(0x00000001f7011641ll,
                                     0x00000001db710641ll);
  const __m128i mask32 = _mm_setr_epi32(-1, 0, -1, 0);
  uint32_t c = ~init;
  __m128i x0, x1, x2, x3, y;
  // seed: first 64 bytes, crc folded into the low lane
  x0 = _mm_loadu_si128((const __m128i*)(p + 0));
  x1 = _mm_loadu_si128((const __m128i*)(p + 16));
  x2 = _mm_loadu_si128((const __m128i*)(p + 32));
  x3 = _mm_loadu_si128((const __m128i*)(p + 48));
  x0 = _mm_xor_si128(x0, _mm_cvtsi32_si128((int)c));
  p += 64;
  len -= 64;
  while (len >= 64) {  // fold 4 lanes by 64 bytes
    __m128i t;
    t = _mm_clmulepi64_si128(x0, k1k2, 0x00);
    x0 = _mm_clmulepi64_si128(x0, k1k2, 0x11);
    x0 = _mm_xor_si128(_mm_xor_si128(x0, t),
                       _mm_loadu_si128((const __m128i*)(p + 0)));
    t = _mm_clmulepi64_si128(x1, k1k2, 0x00);
    x1 = _mm_clmulepi64_si128(x1, k1k2, 0x11);
    x1 = _mm_xor_si128(_mm_xor_si128(x1, t),
                       _mm_loadu_si128((const __m128i*)(p + 16)));
    t = _mm_clmulepi64_si128(x2, k1k2, 0x00);
    x2 = _mm_clmulepi64_si128(x2, k1k2, 0x11);
    x2 = _mm_xor_si128(_mm_xor_si128(x2, t),
                       _mm_loadu_si128((const __m128i*)(p + 32)));
    t = _mm_clmulepi64_si128(x3, k1k2, 0x00);
    x3 = _mm_clmulepi64_si128(x3, k1k2, 0x11);
    x3 = _mm_xor_si128(_mm_xor_si128(x3, t),
                       _mm_loadu_si128((const __m128i*)(p + 48)));
    p += 64;
    len -= 64;
  }
  // reduce 4 lanes -> 1 (fold by 16 bytes each step)
  y = _mm_clmulepi64_si128(x0, k3k4, 0x00);
  x0 = _mm_clmulepi64_si128(x0, k3k4, 0x11);
  x1 = _mm_xor_si128(x1, _mm_xor_si128(x0, y));
  y = _mm_clmulepi64_si128(x1, k3k4, 0x00);
  x1 = _mm_clmulepi64_si128(x1, k3k4, 0x11);
  x2 = _mm_xor_si128(x2, _mm_xor_si128(x1, y));
  y = _mm_clmulepi64_si128(x2, k3k4, 0x00);
  x2 = _mm_clmulepi64_si128(x2, k3k4, 0x11);
  x3 = _mm_xor_si128(x3, _mm_xor_si128(x2, y));
  while (len >= 16) {  // remaining whole 16B blocks
    y = _mm_clmulepi64_si128(x3, k3k4, 0x00);
    x3 = _mm_clmulepi64_si128(x3, k3k4, 0x11);
    x3 = _mm_xor_si128(_mm_xor_si128(x3, y),
                       _mm_loadu_si128((const __m128i*)p));
    p += 16;
    len -= 16;
  }
  // 128 -> 64 bits
  y = _mm_clmulepi64_si128(x3, k3k4, 0x10);
  x3 = _mm_srli_si128(x3, 8);
  x3 = _mm_xor_si128(x3, y);
  // 64 -> 32 bits
  y = _mm_srli_si128(x3, 4);
  x3 = _mm_and_si128(x3, mask32);
  x3 = _mm_clmulepi64_si128(x3, k5, 0x00);
  x3 = _mm_xor_si128(x3, y);
  // Barrett reduction
  y = _mm_and_si128(x3, mask32);
  y = _mm_clmulepi64_si128(y, pmu, 0x10);
  y = _mm_and_si128(y, mask32);
  y = _mm_clmulepi64_si128(y, pmu, 0x00);
  x3 = _mm_xor_si128(x3, y);
  c = (uint32_t)_mm_extract_epi32(x3, 1);
  // tail (<16B): continue from raw register c — slice8 seeds ~init,
  // so ~c hands it exactly c, and its return is already final-inverted
  if (len > 0) return crc32_slice8(p, len, ~c);
  return ~c;
}

// -1 = unprobed, 0 = unavailable/failed self-check, 1 = verified good.
// The self-check runs the first time a large-enough buffer arrives:
// both paths checksum a 256B counter pattern at several offsets — a
// wrong fold constant or a CPU lying about pclmul support disables
// the fast path for the process lifetime (correctness never depends
// on the constants being right).
static int g_pclmul_state = -1;
static int pclmul_ok() {
  if (g_pclmul_state >= 0) return g_pclmul_state;
#if defined(__x86_64__) || defined(__i386__)
  if (__builtin_cpu_supports("pclmul") && __builtin_cpu_supports("sse4.1")) {
    uint8_t buf[256 + 7];
    for (int i = 0; i < 256 + 7; ++i) buf[i] = (uint8_t)(i * 73 + 11);
    int good = 1;
    for (int off = 0; off < 8 && good; ++off)
      for (int n = 64; n <= 256 && good; n += 13)
        for (uint32_t seed = 0; seed < 2 && good; ++seed)
          if (crc32_pclmul(buf + off, n, seed ? 0xDEADBEEFu : 0) !=
              crc32_slice8(buf + off, n, seed ? 0xDEADBEEFu : 0))
            good = 0;
    g_pclmul_state = good;
  } else {
    g_pclmul_state = 0;
  }
#else
  g_pclmul_state = 0;
#endif
  return g_pclmul_state;
}
#else
static int pclmul_ok() { return 0; }
#endif

uint32_t crc32_zlib(const uint8_t* p, int64_t len, uint32_t init) {
#if defined(__x86_64__) || defined(__i386__)
  if (len >= 64 && pclmul_ok()) return crc32_pclmul(p, len, init);
#endif
  return crc32_slice8(p, len, init);
}

}  // extern "C"
