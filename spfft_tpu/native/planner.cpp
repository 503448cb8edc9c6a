// Native index planner: sparse frequency triplets -> z-stick tables.
//
// C++ implementation of the semantics of the reference index conversion
// (reference: src/compression/indices.hpp:120-186 convert_index_triplets,
// :49-55 to_storage_index) — the plan-time hot loop of the framework. The
// NumPy path in spfft_tpu/indexing.py is the fallback and the executable
// specification; this library exists because planning a 256^3 spherical
// cutoff (8.8M triplets) takes seconds through generic sort-based
// np.unique, while the dense bitmap-rank algorithm here is O(n + dimX*dimY)
// and runs in tens of milliseconds.
//
// Exposed via a plain C ABI loaded with ctypes (no pybind11 in this image).

#include <algorithm>
#include <climits>
#include <cstdint>
#include <cstring>
#include <vector>

#if defined(_OPENMP)
#include <omp.h>
#endif

namespace {

// Error codes mirrored in spfft_tpu/native/__init__.py.
constexpr int64_t kErrInvalidBounds = -1;
constexpr int64_t kErrTooManyValues = -2;
// Allocation failure / grid too large for the dense-bitmap algorithm — the
// caller falls back to the NumPy path (no C++ exception may cross the C ABI).
constexpr int64_t kErrNoNativePath = -3;

}  // namespace

extern "C" {

// Convert (n, 3) int64 row-major triplets into per-value flat indices and
// the ascending unique stick-key list.
//
// Outputs:
//   value_indices[n]  int32 : stick_id * dim_z + z_storage  per value
//   stick_keys[n]     int32 : first num_sticks entries hold the ascending
//                             unique keys x_storage * dim_y + y_storage
//   centered_out      int32 : 1 if any index was negative
// Returns num_sticks (>= 0) or a negative error code.
int64_t spfft_tpu_plan_indices(int32_t hermitian, int64_t dim_x,
                               int64_t dim_y, int64_t dim_z,
                               const int64_t* xyz, int64_t n,
                               int32_t* value_indices, int32_t* stick_keys,
                               int32_t* centered_out) {
  if (n > dim_x * dim_y * dim_z) return kErrTooManyValues;

  // Pass 1: centered detection (any negative index, indices.hpp:129-135).
  bool centered = false;
#pragma omp parallel for reduction(|| : centered) schedule(static)
  for (int64_t i = 0; i < 3 * n; ++i) centered = centered || (xyz[i] < 0);
  *centered_out = centered ? 1 : 0;

  // Bounds, exactly as reference indices.hpp:137-149.
  const int64_t max_x = (hermitian || centered ? dim_x / 2 + 1 : dim_x) - 1;
  const int64_t max_y = (centered ? dim_y / 2 + 1 : dim_y) - 1;
  const int64_t max_z = (centered ? dim_z / 2 + 1 : dim_z) - 1;
  const int64_t min_x = hermitian ? 0 : max_x - dim_x + 1;
  const int64_t min_y = max_y - dim_y + 1;
  const int64_t min_z = max_z - dim_z + 1;

  const int64_t plane = dim_x * dim_y;
  std::vector<uint8_t> present;
  std::vector<int32_t> rank;
  try {
    present.assign(static_cast<size_t>(plane), 0);
    rank.resize(static_cast<size_t>(plane));
  } catch (...) {
    return kErrNoNativePath;
  }

  // Pass 2: bounds check + mark present stick keys. Benign write races on
  // the bitmap (all writers store 1).
  bool oob = false;
#pragma omp parallel for reduction(|| : oob) schedule(static)
  for (int64_t i = 0; i < n; ++i) {
    const int64_t x = xyz[3 * i], y = xyz[3 * i + 1], z = xyz[3 * i + 2];
    if (x < min_x || x > max_x || y < min_y || y > max_y || z < min_z ||
        z > max_z) {
      oob = true;
      continue;
    }
    const int64_t xs = x < 0 ? x + dim_x : x;
    const int64_t ys = y < 0 ? y + dim_y : y;
    // Relaxed atomic store: many threads may mark the same key; all store 1.
    __atomic_store_n(&present[static_cast<size_t>(xs * dim_y + ys)],
                     static_cast<uint8_t>(1), __ATOMIC_RELAXED);
  }
  if (oob) return kErrInvalidBounds;

  // Pass 3: rank present keys in ascending order (the ordered-map semantics
  // of indices.hpp:152-165, without the map).
  int32_t num_sticks = 0;
  for (int64_t k = 0; k < plane; ++k) {
    if (present[static_cast<size_t>(k)]) {
      rank[static_cast<size_t>(k)] = num_sticks;
      stick_keys[num_sticks++] = static_cast<int32_t>(k);
    }
  }

  // Pass 4: per-value flat index stick_id * dim_z + z (indices.hpp:168-176).
#pragma omp parallel for schedule(static)
  for (int64_t i = 0; i < n; ++i) {
    const int64_t x = xyz[3 * i], y = xyz[3 * i + 1], z = xyz[3 * i + 2];
    const int64_t xs = x < 0 ? x + dim_x : x;
    const int64_t ys = y < 0 ? y + dim_y : y;
    const int64_t zs = z < 0 ? z + dim_z : z;
    value_indices[i] = static_cast<int32_t>(
        static_cast<int64_t>(rank[static_cast<size_t>(xs * dim_y + ys)]) *
            dim_z +
        zs);
  }
  return num_sticks;
}

// Inverse maps (indexing.inverse_slot_map / inverse_col_map): scatter of
// iota, included so the whole plan build can run natively. The scatter loop
// is serial so that duplicate indices resolve to the *last* occurrence,
// matching the NumPy fallback's fancy-assignment semantics. Returns 0, or
// -1 if any index is out of [0, num_slots).
int32_t spfft_tpu_inverse_map(const int32_t* indices, int64_t n,
                              int32_t* out, int64_t num_slots,
                              int32_t sentinel) {
  bool oob = false;
#pragma omp parallel for reduction(|| : oob) schedule(static)
  for (int64_t i = 0; i < n; ++i)
    oob = oob || indices[i] < 0 || indices[i] >= num_slots;
  if (oob) return -1;
#pragma omp parallel for schedule(static)
  for (int64_t s = 0; s < num_slots; ++s) out[s] = sentinel;
  for (int64_t i = 0; i < n; ++i) out[indices[i]] = static_cast<int32_t>(i);
  return 0;
}

}  // extern "C"

// ---------------------------------------------------------------------------
// Wide-gather table builder (ops/gather_kernel.build_wide_gather_tables).
//
// The NumPy builder is the executable specification; this native version
// exists because the vectorised multi-round cover makes ~20 full passes
// over (G_s, P, 1024) arrays (122 s at 512^3 — 105M slots). Here every
// super-tile is covered independently (sequential rounds over its P * 1024
// slots), parallel over super-tiles: one pass, cache-resident.
//
// Geometry and cover semantics replicate the Python builder EXACTLY (the
// parity test builds both and compares every table): padding with the last
// index / valid=false, the kp cost model over the candidate list, the
// linear-interpolation 0.99 quantile for K, byte-packed sub offsets,
// lane | row << 7 | valid << 12 int16 packed words, first=1 on a
// super-tile's round-0 chunk, and the 16 * G_s + 64 chunk blowup limit.

namespace {

constexpr int kTile = 1024;
constexpr int kLane = 128;
constexpr int32_t kBig = INT32_C(1) << 30;
// Most tiles per super-tile (fused_kernel.MAX_P_TILES): the per-tile
// cover state below lives on the stack, 64 KiB of it at this bound.
constexpr int kMaxP = 64;

bool bad_p(int32_t P) { return P < 4 || P > kMaxP || P % 4 != 0; }

struct WideGeom {
  int P;
  int kp;
  int K;
  int64_t G_s;
  int32_t r_clamp;  // max allowed row0 (kBig = unclamped): keeps every
                    // DMA window inside ceil(num_src/128) source rows so
                    // the runtime needs no source zero-padding pass
};

// Per-super-tile cover: returns the chunk count; when fill outputs are
// non-null, also writes row0 / sub words / packed for each chunk emitted
// (chunks for this super-tile start at chunk offset base_c).
int64_t cover_super_tile(const int64_t* idx_p, const uint8_t* valid_p,
                         int64_t st, const WideGeom& g, int64_t limit,
                         int32_t* row0_out, int32_t* sub_out,
                         int16_t* packed_out, int64_t base_c) {
  const int P = g.P, kp = g.kp, K = g.K;
  const int64_t s0 = st * P * kTile;
  // Uncovered = valid (invalid slots never need covering).
  bool uncovered[kMaxP][kTile];  // P <= kMaxP enforced at the ABI
  bool any_unc = false;
  for (int p = 0; p < P; ++p)
    for (int t = 0; t < kTile; ++t) {
      bool u = valid_p[s0 + p * kTile + t] != 0;
      uncovered[p][t] = u;
      any_unc = any_unc || u;
    }
  int64_t c = 0;
  for (int round = 0;; ++round) {
    if (round > 0 && !any_unc) break;
    if (c >= limit) return limit + 1;  // caller treats as blowup
    // base[p] = min uncovered row
    int32_t base[kMaxP];
    bool hasu[kMaxP];
    int32_t r0 = kBig;
    for (int p = 0; p < P; ++p) {
      int32_t b = kBig;
      for (int t = 0; t < kTile; ++t)
        if (uncovered[p][t]) {
          int32_t r = static_cast<int32_t>(idx_p[s0 + p * kTile + t] / kLane);
          if (r < b) b = r;
        }
      base[p] = b;
      hasu[p] = b != kBig;
      if (b < r0) r0 = b;
    }
    if (r0 == kBig) r0 = 0;
    if (r0 > g.r_clamp) r0 = g.r_clamp;
    bool inwin[kMaxP];
    int32_t basec[kMaxP];
    for (int p = 0; p < P; ++p) {
      // sub-window saturates at the window top so tail rows stay
      // coverable when r0 is clamped (see r_clamp)
      inwin[p] = hasu[p] && base[p] <= r0 + (K - 1);
      basec[p] = inwin[p] ? std::min(base[p], r0 + (K - kp)) : r0;
    }
    if (row0_out != nullptr) {
      const int64_t cc = base_c + c;
      row0_out[cc] = r0;
      for (int w = 0; w < P / 4; ++w) {
        int32_t word = 0;
        for (int j = 0; j < 4; ++j) {
          int p = 4 * w + j;
          int32_t rel = basec[p] - r0;
          if (rel < 0) rel = 0;
          if (rel > K - kp) rel = K - kp;
          word |= rel << (8 * j);
        }
        sub_out[cc * (P / 4) + w] = word;
      }
      int16_t* pk = packed_out + cc * int64_t(P) * kTile;
      for (int p = 0; p < P; ++p)
        for (int t = 0; t < kTile; ++t) {
          const int64_t v = idx_p[s0 + p * kTile + t];
          const int32_t lane = static_cast<int32_t>(v % kLane);
          int32_t rin = static_cast<int32_t>(v / kLane) - basec[p];
          if (rin < 0) rin = 0;
          if (rin > kp - 1) rin = kp - 1;
          const bool cov =
              uncovered[p][t] && inwin[p] &&
              static_cast<int32_t>(v / kLane) >= basec[p] &&
              static_cast<int32_t>(v / kLane) < basec[p] + kp;
          pk[p * kTile + t] = static_cast<int16_t>(
              lane | (rin << 7) | ((cov ? 1 : 0) << 12));
        }
    }
    // Un-cover
    any_unc = false;
    for (int p = 0; p < P; ++p) {
      if (!inwin[p]) {
        for (int t = 0; t < kTile; ++t)
          any_unc = any_unc || uncovered[p][t];
        continue;
      }
      for (int t = 0; t < kTile; ++t)
        if (uncovered[p][t]) {
          const int32_t r =
              static_cast<int32_t>(idx_p[s0 + p * kTile + t] / kLane);
          if (r >= basec[p] && r < basec[p] + kp)
            uncovered[p][t] = false;
          else
            any_unc = true;
        }
    }
    ++c;
  }
  return c;
}

}  // namespace

extern "C" {

// Phase 1: choose geometry + count chunks.
//
// idx[L] int64 (any order), valid[L] uint8; P a multiple of 4 in
// [4, kMaxP]. kp_in / k_in force the sub-window / DMA-window heights (0 = choose
// from the data, replicating the Python cost model / quantile). On
// success writes kp/K/C and returns 0; returns -1 when the cover exceeds
// the blowup limit (caller falls back), -2 on invalid arguments.
int32_t spfft_tpu_wide_tables_plan(const int64_t* idx, const uint8_t* valid,
                                   int64_t L, int64_t num_src, int32_t P,
                                   int32_t kp_in, int32_t k_in,
                                   int32_t* kp_out, int32_t* k_out,
                                   int64_t* c_out) {
  if (L <= 0 || bad_p(P)) return -2;
  const int64_t SUPER = int64_t(P) * kTile;
  const int64_t G_s = (L + SUPER - 1) / SUPER;
  const int64_t Lp = G_s * SUPER;

  // Padded copies (pad index = last index, pad valid = 0).
  std::vector<int64_t> idx_p(Lp);
  std::vector<uint8_t> valid_p(Lp);
  std::memcpy(idx_p.data(), idx, sizeof(int64_t) * L);
  std::memcpy(valid_p.data(), valid, L);
  for (int64_t i = L; i < Lp; ++i) {
    idx_p[i] = idx[L - 1];
    valid_p[i] = 0;
  }

  // Per-tile spread / base stats (valid slots only).
  std::vector<int32_t> spread(G_s * P), rmin(G_s * P);
  std::vector<uint8_t> has(G_s * P);
#pragma omp parallel for schedule(static)
  for (int64_t tp = 0; tp < G_s * P; ++tp) {
    int32_t lo = kBig, hi = -1;
    const int64_t s0 = tp * kTile;
    for (int t = 0; t < kTile; ++t)
      if (valid_p[s0 + t]) {
        const int32_t r = static_cast<int32_t>(idx_p[s0 + t] / kLane);
        if (r < lo) lo = r;
        if (r > hi) hi = r;
      }
    has[tp] = hi >= 0;
    rmin[tp] = lo;
    spread[tp] = hi >= 0 ? hi - lo + 1 : 1;
  }

  int kp = kp_in;
  if (kp == 0) {
    // cost(kp) = C_est * (P*kp + 64), C_est = sum of per-super-tile max
    // round counts (gather_kernel.WIDE_KP_CANDIDATES).
    const int cands[5] = {8, 12, 16, 24, 32};
    int64_t best_cost = INT64_MAX;
    for (int cand : cands) {
      int64_t c_est = 0;
#pragma omp parallel for reduction(+ : c_est) schedule(static)
      for (int64_t st = 0; st < G_s; ++st) {
        int32_t mx = 1;
        for (int p = 0; p < P; ++p) {
          const int32_t r = (spread[st * P + p] + cand - 1) / cand;
          if (r > mx) mx = r;
        }
        c_est += mx;
      }
      const int64_t cost = c_est * (int64_t(P) * cand + 64);
      if (cost < best_cost) {
        best_cost = cost;
        kp = cand;
      }
    }
  }
  if (kp < 1 || kp > 32) return -2;

  int K = k_in;
  if (K == 0) {
    // bspan quantile 0.99 with linear interpolation (np.quantile).
    std::vector<int32_t> bspan(G_s);
#pragma omp parallel for schedule(static)
    for (int64_t st = 0; st < G_s; ++st) {
      int32_t b0 = kBig, mx = 0;
      for (int p = 0; p < P; ++p)
        if (has[st * P + p] && rmin[st * P + p] < b0)
          b0 = rmin[st * P + p];
      for (int p = 0; p < P; ++p)
        if (has[st * P + p] && rmin[st * P + p] - b0 > mx)
          mx = rmin[st * P + p] - b0;
      bspan[st] = mx;
    }
    std::sort(bspan.begin(), bspan.end());
    double q;
    if (G_s == 1) {
      q = bspan[0];
    } else {
      const double pos = 0.99 * double(G_s - 1);
      const int64_t i0 = static_cast<int64_t>(pos);
      const double frac = pos - double(i0);
      q = bspan[i0] +
          frac * (bspan[std::min(i0 + 1, G_s - 1)] - bspan[i0]);
    }
    const int64_t qi = static_cast<int64_t>(q);  // int(np.quantile(...))
    int64_t k64 = (qi + kp + 7) / 8 * 8;
    if (k64 > 512) k64 = 512;
    if (k64 > kp + 248) k64 = kp + 248;
    if (k64 < kp + 8) k64 = kp + 8;
    K = static_cast<int32_t>(k64);
  }
  if (K - kp > 255) K = kp + 248;

  int32_t r_clamp = kBig;
  const int64_t r_exact = (num_src + kLane - 1) / kLane;
  if (num_src > 0 && r_exact >= K)
    r_clamp = static_cast<int32_t>(r_exact - K);
  const WideGeom geom{P, kp, K, G_s, r_clamp};
  const int64_t limit = 16 * G_s + 64;
  std::vector<int64_t> counts(G_s);
  bool blowup = false;
#pragma omp parallel for reduction(|| : blowup) schedule(dynamic, 16)
  for (int64_t st = 0; st < G_s; ++st) {
    counts[st] = cover_super_tile(idx_p.data(), valid_p.data(), st, geom,
                                  limit, nullptr, nullptr, nullptr, 0);
    blowup = blowup || counts[st] > limit;
  }
  int64_t total = 0;
  for (int64_t st = 0; st < G_s; ++st) total += counts[st];
  if (blowup || total > limit) return -1;
  *kp_out = kp;
  *k_out = K;
  *c_out = total;
  return 0;
}

// Phase 2: fill the tables (geometry and C from phase 1). Outputs:
//   row0[C] i32, sub[C * P/4] i32, out_tile[C] i32, first[C] i32,
//   packed[C * P * 1024] i16, max_row0_out (for src_rows).
// Returns 0, or -2 if the recomputed chunk count disagrees with C.
int32_t spfft_tpu_wide_tables_fill(const int64_t* idx, const uint8_t* valid,
                                   int64_t L, int64_t num_src, int32_t P,
                                   int32_t kp, int32_t K, int64_t C,
                                   int32_t* row0,
                                   int32_t* sub, int32_t* out_tile,
                                   int32_t* first, int16_t* packed,
                                   int32_t* max_row0_out) {
  if (L <= 0 || bad_p(P)) return -2;
  const int64_t SUPER = int64_t(P) * kTile;
  const int64_t G_s = (L + SUPER - 1) / SUPER;
  const int64_t Lp = G_s * SUPER;
  std::vector<int64_t> idx_p(Lp);
  std::vector<uint8_t> valid_p(Lp);
  std::memcpy(idx_p.data(), idx, sizeof(int64_t) * L);
  std::memcpy(valid_p.data(), valid, L);
  for (int64_t i = L; i < Lp; ++i) {
    idx_p[i] = idx[L - 1];
    valid_p[i] = 0;
  }
  int32_t r_clamp = kBig;
  const int64_t r_exact = (num_src + kLane - 1) / kLane;
  if (num_src > 0 && r_exact >= K)
    r_clamp = static_cast<int32_t>(r_exact - K);
  const WideGeom geom{P, kp, K, G_s, r_clamp};
  const int64_t limit = 16 * G_s + 64;

  std::vector<int64_t> counts(G_s);
#pragma omp parallel for schedule(dynamic, 16)
  for (int64_t st = 0; st < G_s; ++st)
    counts[st] = cover_super_tile(idx_p.data(), valid_p.data(), st, geom,
                                  limit, nullptr, nullptr, nullptr, 0);
  std::vector<int64_t> offs(G_s + 1, 0);
  for (int64_t st = 0; st < G_s; ++st) offs[st + 1] = offs[st] + counts[st];
  if (offs[G_s] != C) return -2;

#pragma omp parallel for schedule(dynamic, 16)
  for (int64_t st = 0; st < G_s; ++st) {
    cover_super_tile(idx_p.data(), valid_p.data(), st, geom, limit, row0,
                     sub, packed, offs[st]);
    for (int64_t c = offs[st]; c < offs[st + 1]; ++c) {
      out_tile[c] = static_cast<int32_t>(st);
      first[c] = c == offs[st] ? 1 : 0;
    }
  }
  int32_t mx = 0;
#pragma omp parallel for reduction(max : mx) schedule(static)
  for (int64_t c = 0; c < C; ++c)
    if (row0[c] > mx) mx = row0[c];
  *max_row0_out = mx;
  return 0;
}


// Compression gather inputs (ops/gather_kernel.compression_gather_inputs,
// decompress direction): occupied mask + forward-filled position map.
// dec_idx[s] = position in the value array of the nearest occupied slot at
// or below s (leading gap: the first occupied slot); duplicates resolve to
// the LAST occurrence — both exactly as the NumPy path. Returns 0, or -1
// if any index is out of [0, num_slots).
int32_t spfft_tpu_compression_inputs(const int64_t* vi, int64_t n,
                                     int64_t num_slots, int64_t* dec_idx,
                                     uint8_t* occupied) {
  bool oob = false;
#pragma omp parallel for reduction(|| : oob) schedule(static)
  for (int64_t i = 0; i < n; ++i)
    oob = oob || vi[i] < 0 || vi[i] >= num_slots;
  if (oob) return -1;
#pragma omp parallel for schedule(static)
  for (int64_t s = 0; s < num_slots; ++s) {
    occupied[s] = 0;
    dec_idx[s] = -1;
  }
  // last occurrence wins (serial, like the NumPy fancy assignment)
  for (int64_t i = 0; i < n; ++i) {
    occupied[vi[i]] = 1;
    dec_idx[vi[i]] = i;
  }
  if (n > 0) {
    // forward fill; leading gap takes the first occupied slot's position
    int64_t first = -1;
    for (int64_t s = 0; s < num_slots; ++s)
      if (occupied[s]) {
        first = dec_idx[s];
        break;
      }
    int64_t cur = first;
    for (int64_t s = 0; s < num_slots; ++s) {
      if (occupied[s])
        cur = dec_idx[s];
      dec_idx[s] = cur;
    }
  } else {
#pragma omp parallel for schedule(static)
    for (int64_t s = 0; s < num_slots; ++s) dec_idx[s] = 0;
  }
  return 0;
}

}  // extern "C"
