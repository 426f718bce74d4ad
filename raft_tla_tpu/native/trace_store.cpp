// Native predecessor-trace store — TLC's trace file rebuilt as an in-memory
// open-addressing hash map (SURVEY §2.4 R5).
//
// TLC reconstructs counterexamples from a disk-backed trace of (fingerprint
// -> predecessor fingerprint) records [TLC semantics — external].  Here the
// engine streams one compacted (fp, parent fp, action id) triple per newly
// discovered state off the device each batch; this store ingests those
// batches at memcpy-like rates so the host-side bookkeeping never throttles
// the device pipeline.  Python binds via ctypes (native/__init__.py loads
// the .so; engine/trace.py wraps it) — no pybind11 dependency.
//
// Layout: open addressing, linear probing, power-of-two capacity, grow at
// 70% load.  First insert wins (BFS reaches a state first along a shortest
// path; later duplicates arrive only from in-flight batches of the same
// level and must not overwrite the shortest-path parent).

#include <chrono>
#include <cstdint>
#include <cstdlib>
#include <cstring>

namespace {

struct Entry {
    uint64_t fp;
    uint64_t parent;
    int32_t action;
    uint8_t used;
};

struct Store {
    Entry* slots;
    uint64_t capacity;   // power of two
    uint64_t size;
    // What growing has cost this store (ts_stats): a rehash is a calloc
    // of the new table and every entry inserted again, inside whichever
    // add_batch crossed the load, so the caller's clock sees one slow
    // call and nothing that says why.
    uint64_t rehashes;
    uint64_t rehash_ns;
};

// splitmix64: decorrelates slot index from the engine's own fingerprint
// mixing so pathological fp batches cannot cluster probes.
inline uint64_t mix(uint64_t x) {
    x += 0x9e3779b97f4a7c15ull;
    x = (x ^ (x >> 30)) * 0xbf58476d1ce4e5b9ull;
    x = (x ^ (x >> 27)) * 0x94d049bb133111ebull;
    return x ^ (x >> 31);
}

void rehash(Store* s, uint64_t new_capacity);

inline bool crowded(uint64_t size, uint64_t capacity) {
    return size * 10 >= capacity * 7;
}

inline void insert_one(Store* s, uint64_t fp, uint64_t parent,
                       int32_t action) {
    uint64_t mask = s->capacity - 1;
    uint64_t i = mix(fp) & mask;
    while (s->slots[i].used) {
        if (s->slots[i].fp == fp) return;  // first insert wins
        i = (i + 1) & mask;
    }
    s->slots[i] = Entry{fp, parent, action, 1};
    s->size++;
    if (crowded(s->size, s->capacity)) rehash(s, s->capacity << 1);
}

void rehash(Store* s, uint64_t new_capacity) {
    auto t0 = std::chrono::steady_clock::now();
    Entry* old = s->slots;
    uint64_t old_cap = s->capacity;
    s->capacity = new_capacity;
    s->slots = static_cast<Entry*>(calloc(s->capacity, sizeof(Entry)));
    s->size = 0;
    for (uint64_t i = 0; i < old_cap; i++)
        if (old[i].used)
            insert_one(s, old[i].fp, old[i].parent, old[i].action);
    free(old);
    s->rehashes++;
    s->rehash_ns += std::chrono::duration_cast<std::chrono::nanoseconds>(
        std::chrono::steady_clock::now() - t0).count();
}

// Room for `n` more records before the first of them goes in.  A batch
// that comes from another store's export arrives in THAT store's slot
// order, i.e. sorted by the low bits of the same mix(): fed to a table
// that doubles on the way, the records wrap onto slots the batch's own
// head already filled and pile into one run that every later insert
// walks (19.8 M records of a level-12 snapshot: over half an hour, where
// a table sized first takes them in slot order, in seconds).
void reserve(Store* s, uint64_t n) {
    uint64_t cap = s->capacity;
    while (crowded(s->size + n, cap)) cap <<= 1;
    if (cap != s->capacity) rehash(s, cap);
}

}  // namespace

extern "C" {

void* ts_create(uint64_t initial_capacity) {
    uint64_t cap = 1024;
    while (cap < initial_capacity) cap <<= 1;
    Store* s = static_cast<Store*>(malloc(sizeof(Store)));
    s->slots = static_cast<Entry*>(calloc(cap, sizeof(Entry)));
    s->capacity = cap;
    s->size = 0;
    s->rehashes = s->rehash_ns = 0;
    return s;
}

void ts_destroy(void* h) {
    Store* s = static_cast<Store*>(h);
    free(s->slots);
    free(s);
}

uint64_t ts_size(void* h) { return static_cast<Store*>(h)->size; }

// out[0..1]: rehashes so far, nanoseconds spent in them.
void ts_stats(void* h, uint64_t* out) {
    Store* s = static_cast<Store*>(h);
    out[0] = s->rehashes;
    out[1] = s->rehash_ns;
}

void ts_add_batch(void* h, const uint64_t* fps, const uint64_t* parents,
                  const int32_t* actions, uint64_t n) {
    Store* s = static_cast<Store*>(h);
    reserve(s, n);
    for (uint64_t k = 0; k < n; k++)
        insert_one(s, fps[k], parents[k], actions[k]);
}

int ts_get(void* h, uint64_t fp, uint64_t* parent, int32_t* action) {
    Store* s = static_cast<Store*>(h);
    uint64_t mask = s->capacity - 1;
    uint64_t i = mix(fp) & mask;
    while (s->slots[i].used) {
        if (s->slots[i].fp == fp) {
            *parent = s->slots[i].parent;
            *action = s->slots[i].action;
            return 1;
        }
        i = (i + 1) & mask;
    }
    return 0;
}

// Bulk export for checkpointing: writes up to `cap` triples; returns the
// number written (== size when cap is sufficient).
uint64_t ts_export(void* h, uint64_t* fps, uint64_t* parents,
                   int32_t* actions, uint64_t cap) {
    Store* s = static_cast<Store*>(h);
    uint64_t k = 0;
    for (uint64_t i = 0; i < s->capacity && k < cap; i++) {
        if (s->slots[i].used) {
            fps[k] = s->slots[i].fp;
            parents[k] = s->slots[i].parent;
            actions[k] = s->slots[i].action;
            k++;
        }
    }
    return k;
}

}  // extern "C"
