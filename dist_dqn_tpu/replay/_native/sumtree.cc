// Prioritized-replay sum-tree, native (C++) hot path.
//
// The Ape-X replay shard (BASELINE.json:5 "distributed prioritized replay")
// keeps its priority mass in a flat binary sum-tree over host DRAM. The
// numpy implementation in replay/host.py vectorizes writes level-by-level
// and sampling in lockstep; this port removes the remaining numpy overhead
// (temporary arrays, per-level unique/dispatch) for the learner service's
// per-grad-step critical path: sample(batch) before every train step and
// set(batch) twice per step (insert priorities + post-update corrections).
//
// Write strategy: delta propagation. Each leaf write adds (new - old) along
// its root path — n*log2(cap) scalar adds, no temporaries, duplicate
// indices in one batch compose correctly because items apply sequentially.
// Float64 delta accumulation can drift from the exact subtree sums over
// hundreds of millions of writes, so writes are counted and the Python
// wrapper triggers rebuild() (exact bottom-up recompute, O(cap)) on a
// coarse schedule — the same freshness contract the numpy tree provides
// every call, at ~1e-8 of the cost.
//
// Sampling descends each query independently (u >= left ? right : left),
// identical tie semantics to the numpy lockstep descent so both trees are
// exchangeable under tests/test_prioritized.py.
//
// Built on demand with g++ via actors/transport.build_native_lib, loaded
// with ctypes — no pybind11 in this image.
#include <cstddef>
#include <cstdint>
#include <vector>

namespace {

struct Tree {
  int64_t capacity = 1;  // padded to a power of two
  int depth = 0;
  std::vector<double> node;  // 1-based heap layout, node[1] = total
  uint64_t writes = 0;       // leaf writes since last rebuild
};

}  // namespace

extern "C" {

void* dqn_tree_create(int64_t capacity) {
  auto* t = new Tree();
  while (t->capacity < capacity) {
    t->capacity *= 2;
    t->depth += 1;
  }
  t->node.assign(2 * t->capacity, 0.0);
  return t;
}

void dqn_tree_destroy(void* h) { delete static_cast<Tree*>(h); }

double dqn_tree_total(void* h) { return static_cast<Tree*>(h)->node[1]; }

uint64_t dqn_tree_writes(void* h) { return static_cast<Tree*>(h)->writes; }

void dqn_tree_get(void* h, const int64_t* idx, double* out, int64_t n) {
  auto* t = static_cast<Tree*>(h);
  for (int64_t i = 0; i < n; ++i) out[i] = t->node[idx[i] + t->capacity];
}

void dqn_tree_set(void* h, const int64_t* idx, const double* vals,
                  int64_t n) {
  auto* t = static_cast<Tree*>(h);
  for (int64_t i = 0; i < n; ++i) {
    int64_t pos = idx[i] + t->capacity;
    const double delta = vals[i] - t->node[pos];
    t->node[pos] = vals[i];
    for (pos >>= 1; pos >= 1; pos >>= 1) t->node[pos] += delta;
  }
  t->writes += static_cast<uint64_t>(n);
}

// Exact bottom-up recompute of every interior node; resets the write count.
void dqn_tree_rebuild(void* h) {
  auto* t = static_cast<Tree*>(h);
  for (int64_t p = t->capacity - 1; p >= 1; --p)
    t->node[p] = t->node[2 * p] + t->node[2 * p + 1];
  t->writes = 0;
}

// Exact state serialization (checkpoint/resume): dump/load the full node
// heap plus the write counter. Delta propagation makes interior sums
// PATH-DEPENDENT (bounded fp drift), so a resumed tree rebuilt from leaf
// values alone would differ from the live one in the last ulp — enough to
// break a bit-identical resume pin. Serializing the heap preserves the
// drift (and, via the counter, the periodic-rebuild cadence) exactly.
void dqn_tree_dump(void* h, double* nodes, uint64_t* writes) {
  auto* t = static_cast<Tree*>(h);
  for (size_t i = 0; i < t->node.size(); ++i) nodes[i] = t->node[i];
  *writes = t->writes;
}

void dqn_tree_load(void* h, const double* nodes, uint64_t writes) {
  auto* t = static_cast<Tree*>(h);
  for (size_t i = 0; i < t->node.size(); ++i) t->node[i] = nodes[i];
  t->writes = writes;
}

void dqn_tree_sample(void* h, const double* mass, int64_t* out, int64_t n) {
  auto* t = static_cast<Tree*>(h);
  for (int64_t i = 0; i < n; ++i) {
    double u = mass[i];
    int64_t pos = 1;
    for (int d = 0; d < t->depth; ++d) {
      const int64_t left = 2 * pos;
      const double lmass = t->node[left];
      const bool right = u >= lmass;
      u -= right ? lmass : 0.0;
      pos = left + (right ? 1 : 0);
    }
    out[i] = pos - t->capacity;
  }
}

}  // extern "C"
