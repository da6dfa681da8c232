// Native wire-boundary helpers for split_learning_tpu.
//
// The reference has no native code at all (SURVEY.md §2: zero C++/CUDA
// components); its wire hot path is pickle-over-HTTP of the 5.28 MiB
// cut-layer tensor (src/client_part.py:117-131). Here the host-side wire
// hot ops — int8 absmax quantize/dequantize (the 4x compression of that
// tensor) and frame checksumming — run in C++ with a thread pool, bound
// into Python via ctypes (split_learning_tpu/native/codec.py). The NumPy
// form is split_learning_tpu/transport/codec.py; both implement the same
// math and are parity-tested (tests/test_native.py).
//
// Semantics match the NumPy fallback bit-for-bit:
//   scale = max(absmax(x) / 127, 1e-12)
//   q     = clip(nearbyint(x / scale), -127, 127)   // round-half-even,
//                                                   // same as np.round
//   x'    = q * scale
//
// Build: g++ -O3 -shared -fPIC (driven by codec.py; no build system
// dependency, the toolchain in the image is enough).

#include <algorithm>
#include <cmath>
#include <cstdint>
#include <thread>
#include <vector>

namespace {

int clamp_threads(int n_threads, int64_t n, int64_t min_chunk) {
  int hw = static_cast<int>(std::thread::hardware_concurrency());
  if (hw <= 0) hw = 1;
  int t = n_threads > 0 ? std::min(n_threads, hw) : hw;
  int64_t max_useful = std::max<int64_t>(n / min_chunk, 1);
  return static_cast<int>(std::min<int64_t>(t, max_useful));
}

template <typename Fn>
void parallel_for(int64_t n, int n_threads, Fn fn) {
  if (n_threads <= 1) {
    fn(0, n);
    return;
  }
  std::vector<std::thread> pool;
  pool.reserve(n_threads - 1);
  int64_t chunk = (n + n_threads - 1) / n_threads;
  for (int t = 1; t < n_threads; ++t) {
    int64_t lo = t * chunk;
    int64_t hi = std::min(n, lo + chunk);
    if (lo >= hi) break;
    pool.emplace_back([=] { fn(lo, hi); });
  }
  fn(0, std::min(n, chunk));
  for (auto& th : pool) th.join();
}

}  // namespace

extern "C" {

// Max |x| over n floats. Exact regardless of the split: max is
// order-independent.
float slt_absmax_f32(const float* src, int64_t n, int n_threads) {
  int t = clamp_threads(n_threads, n, 1 << 16);
  std::vector<float> partial(t, 0.0f);
  std::vector<std::thread> pool;
  int64_t chunk = (n + t - 1) / t;
  auto work = [&](int idx, int64_t lo, int64_t hi) {
    float m = 0.0f;
    for (int64_t i = lo; i < hi; ++i) m = std::max(m, std::fabs(src[i]));
    partial[idx] = m;
  };
  for (int i = 1; i < t; ++i) {
    int64_t lo = i * chunk;
    int64_t hi = std::min(n, lo + chunk);
    if (lo >= hi) break;
    pool.emplace_back(work, i, lo, hi);
  }
  work(0, 0, std::min(n, chunk));
  for (auto& th : pool) th.join();
  float m = 0.0f;
  for (float p : partial) m = std::max(m, p);
  return m;
}

// x -> (q, scale). Returns the scale; q written into dst.
// The scale is computed in double then narrowed for the division — the
// exact arithmetic of the NumPy fallback (a Python float is f64; the
// array division then runs in f32 against the narrowed scale).
double slt_q8_quantize_f32(const float* src, int64_t n, int8_t* dst,
                           int n_threads) {
  double scale =
      n > 0 ? std::max(
                  static_cast<double>(slt_absmax_f32(src, n, n_threads)) /
                      127.0,
                  1e-12)
            : 1e-12;
  float s32 = static_cast<float>(scale);
  int t = clamp_threads(n_threads, n, 1 << 16);
  parallel_for(n, t, [=](int64_t lo, int64_t hi) {
    for (int64_t i = lo; i < hi; ++i) {
      // divide (not multiply by a reciprocal) to match NumPy's x/scale
      // exactly; nearbyintf = round-half-even = np.round
      float r = std::nearbyintf(src[i] / s32);
      r = std::min(127.0f, std::max(-127.0f, r));
      dst[i] = static_cast<int8_t>(r);
    }
  });
  return scale;
}

void slt_q8_dequantize_f32(const int8_t* src, int64_t n, float scale,
                           float* dst, int n_threads) {
  int t = clamp_threads(n_threads, n, 1 << 16);
  parallel_for(n, t, [=](int64_t lo, int64_t hi) {
    for (int64_t i = lo; i < hi; ++i) {
      dst[i] = static_cast<float>(src[i]) * scale;
    }
  });
}

// Top-k-|x| selection for the topk8 sparse wire mode. Deterministic
// selection rule, shared bit-for-bit with the NumPy fallback
// (_topk8_select_numpy in transport/codec.py): every element strictly
// above the k-th-largest magnitude, then threshold ties in ascending
// index order until exactly k survive; output indices ascending.
//
// Parallel scheme: abs pass -> nth_element for the threshold -> per-chunk
// counts of (>thr) and (==thr) -> prefix sums give each chunk a disjoint
// write window (chunk c starts at gt_pre[c] + min(eq_pre[c], need)), so
// chunks write their ascending in-chunk survivors concurrently with no
// atomics and the concatenation is globally ascending.
void slt_topk8_select_f32(const float* src, int64_t n, int64_t k,
                          int32_t* idx_out, float* vals_out, int n_threads) {
  if (k >= n) {
    for (int64_t i = 0; i < n; ++i) {
      idx_out[i] = static_cast<int32_t>(i);
      vals_out[i] = src[i];
    }
    return;
  }
  std::vector<float> absv(n);
  int t = clamp_threads(n_threads, n, 1 << 16);
  parallel_for(n, t, [&](int64_t lo, int64_t hi) {
    for (int64_t i = lo; i < hi; ++i) absv[i] = std::fabs(src[i]);
  });
  std::vector<float> part(absv);
  std::nth_element(part.begin(), part.begin() + (k - 1), part.end(),
                   std::greater<float>());
  const float thr = part[k - 1];

  int64_t chunk = (n + t - 1) / t;
  std::vector<int64_t> gt_pre(t + 1, 0), eq_pre(t + 1, 0);
  {
    std::vector<std::thread> pool;
    auto count = [&](int c, int64_t lo, int64_t hi) {
      int64_t gt = 0, eq = 0;
      for (int64_t i = lo; i < hi; ++i) {
        if (absv[i] > thr) ++gt;
        else if (absv[i] == thr) ++eq;
      }
      gt_pre[c + 1] = gt;
      eq_pre[c + 1] = eq;
    };
    for (int c = 1; c < t; ++c) {
      int64_t lo = c * chunk, hi = std::min(n, lo + chunk);
      if (lo >= hi) break;
      pool.emplace_back(count, c, lo, hi);
    }
    count(0, 0, std::min(n, chunk));
    for (auto& th : pool) th.join();
  }
  for (int c = 0; c < t; ++c) {
    gt_pre[c + 1] += gt_pre[c];
    eq_pre[c + 1] += eq_pre[c];
  }
  const int64_t need = k - gt_pre[t];  // ties to keep, lowest-index first
  {
    std::vector<std::thread> pool;
    auto write = [&](int c, int64_t lo, int64_t hi) {
      int64_t out = gt_pre[c] + std::min(eq_pre[c], need);
      int64_t tie_rank = eq_pre[c];
      for (int64_t i = lo; i < hi; ++i) {
        float a = absv[i];
        if (a > thr) {
          idx_out[out] = static_cast<int32_t>(i);
          vals_out[out] = src[i];
          ++out;
        } else if (a == thr) {
          if (tie_rank < need) {
            idx_out[out] = static_cast<int32_t>(i);
            vals_out[out] = src[i];
            ++out;
          }
          ++tie_rank;
        }
      }
    };
    for (int c = 1; c < t; ++c) {
      int64_t lo = c * chunk, hi = std::min(n, lo + chunk);
      if (lo >= hi) break;
      pool.emplace_back(write, c, lo, hi);
    }
    write(0, 0, std::min(n, chunk));
    for (auto& th : pool) th.join();
  }
}

// Sparse dequantize-scatter: dst (pre-zeroed, n floats) gets
// dst[idx[i]] = q[i] * scale. Indices are unique by construction
// (selection output), so parallel writes are disjoint.
void slt_topk8_scatter_f32(const int64_t* idx, const int8_t* q, int64_t k,
                           float scale, float* dst, int n_threads) {
  int t = clamp_threads(n_threads, k, 1 << 16);
  parallel_for(k, t, [=](int64_t lo, int64_t hi) {
    for (int64_t i = lo; i < hi; ++i) {
      dst[idx[i]] = static_cast<float>(q[i]) * scale;
    }
  });
}

// CRC-32 (IEEE 802.3, reflected polynomial 0xEDB88320), identical to
// zlib.crc32. NOT on the wire hot path — the Python side uses zlib (which
// is copy-free and GIL-releasing); this exists as the parity reference for
// the C framing story and is exercised by tests/test_native.py.
namespace {
struct Crc32Table {
  uint32_t t[256];
  Crc32Table() {
    for (uint32_t i = 0; i < 256; ++i) {
      uint32_t c = i;
      for (int k = 0; k < 8; ++k)
        c = (c & 1) ? 0xEDB88320u ^ (c >> 1) : c >> 1;
      t[i] = c;
    }
  }
};
}  // namespace

uint32_t slt_crc32(const uint8_t* data, int64_t n, uint32_t seed) {
  // magic static: thread-safe initialization under C++11, unlike a
  // hand-rolled "static bool init" flag
  static const Crc32Table table;
  uint32_t crc = seed ^ 0xFFFFFFFFu;
  for (int64_t i = 0; i < n; ++i)
    crc = table.t[(crc ^ data[i]) & 0xFFu] ^ (crc >> 8);
  return crc ^ 0xFFFFFFFFu;
}

}  // extern "C"
