// Native JSON blob-body formatter: the egress hot loop.
//
// The reference-format egress must turn ~tens of millions of
// (row, col, value) aggregates into per-blob JSON documents
// '{"z_r_c": v, ...}'. numpy's per-aggregate number->string formatting
// is the floor of that path (GIL-bound, so threads don't help Python).
// This formatter does the same work in C with integer formatting and
// OS threads: the Python side passes the (already sorted) level
// columns plus the blob-start mask, and receives ONE buffer of
// NUL-separated '{...}' documents in order — the exact contract of the
// numpy join/split trick it replaces (pipeline/cascade.py
// json_blobs_from_level_arrays).
//
// Scope: values must be integral doubles with |v| < 1e15 (cascade
// counts always are — weights never reach blob egress). The Python
// caller verifies that precondition and falls back to the numpy path
// otherwise, so float-repr parity questions never arise here:
// "%lld.0" is exactly repr(float(k)) == json.dumps(float(k)) for
// integral doubles below 1e16.

#include <cstdint>
#include <cstdio>
#include <cstdlib>
#include <cstring>
#include <thread>
#include <vector>

namespace {

// Max chars one aggregate can contribute:
//   sep (3: '}\0{' or ', ') + '"' + zoom(2) + '_' + row(12) + '_' +
//   col(12) + '": ' + digits(16) + '.0'  => < 56. Use 64.
constexpr int64_t kMaxPer = 64;

inline char* put_i64(char* p, long long v) {
  if (v < 0) {  // not produced by tile math, but stay correct
    *p++ = '-';
    v = -v;
  }
  char tmp[24];
  int n = 0;
  do {
    tmp[n++] = static_cast<char>('0' + (v % 10));
    v /= 10;
  } while (v);
  while (n) *p++ = tmp[--n];
  return p;
}

struct Slice {
  int64_t lo, hi;  // aggregate range, lo aligned to a blob start
  char* buf = nullptr;
  int64_t len = 0;
};

// Concatenate per-slice buffers (freeing them) into one malloc'd
// result, with `tail` extra bytes reserved past the payload. Returns
// the payload length written so far; a failed slice's negative len is
// propagated as-is, and -1 is returned if the final allocation fails
// (slices are always freed either way).
int64_t merge_slices(std::vector<Slice>& slices, int64_t tail, char** out) {
  int64_t total = tail;
  int64_t failed = 0;
  for (auto& s : slices) {
    if (s.len < 0 && failed == 0) failed = s.len;
    if (s.len > 0) total += s.len;
  }
  char* merged =
      failed ? nullptr : static_cast<char*>(std::malloc(total ? total : 1));
  int64_t off = 0;
  for (auto& s : slices) {
    if (merged != nullptr && s.len > 0) {
      std::memcpy(merged + off, s.buf, s.len);
      off += s.len;
    }
    std::free(s.buf);
  }
  if (failed) return failed;
  if (merged == nullptr) return -1;
  *out = merged;
  return off;
}

void format_slice(const int64_t* rows, const int64_t* cols,
                  const double* vals, const uint8_t* is_start,
                  int32_t zoom, bool first_slice, Slice* s) {
  const int64_t n = s->hi - s->lo;
  s->buf = static_cast<char*>(std::malloc(static_cast<size_t>(n) * kMaxPer));
  if (s->buf == nullptr) {
    s->len = -1;
    return;
  }
  char* p = s->buf;
  char zbuf[8];
  char* zend = put_i64(zbuf, zoom);
  const int zlen = static_cast<int>(zend - zbuf);
  for (int64_t i = s->lo; i < s->hi; ++i) {
    if (is_start[i]) {
      if (i == s->lo && first_slice) {
        *p++ = '{';
      } else {
        *p++ = '}';
        *p++ = '\0';
        *p++ = '{';
      }
    } else {
      *p++ = ',';
      *p++ = ' ';
    }
    *p++ = '"';
    std::memcpy(p, zbuf, zlen);
    p += zlen;
    *p++ = '_';
    p = put_i64(p, rows[i]);
    *p++ = '_';
    p = put_i64(p, cols[i]);
    *p++ = '"';
    *p++ = ':';
    *p++ = ' ';
    p = put_i64(p, static_cast<long long>(vals[i]));
    *p++ = '.';
    *p++ = '0';
  }
  s->len = p - s->buf;
}

}  // namespace

extern "C" {

// Format NUL-separated '{...}' blob documents for one (sorted) level.
// rows/cols: int64[n]; vals: double[n] (integral, |v| < 1e15 —
// caller-checked); is_start: uint8[n] with is_start[0] == 1.
// On success returns the byte length and stores a malloc'd buffer in
// *out (free with hm_blobfmt_free); returns -1 on allocation failure,
// 0 with *out = nullptr for n == 0.
int64_t hm_format_blob_bodies(const int64_t* rows, const int64_t* cols,
                              const double* vals, const uint8_t* is_start,
                              int64_t n, int32_t zoom, int32_t n_threads,
                              char** out) {
  *out = nullptr;
  if (n <= 0) return 0;
  if (n_threads < 1) n_threads = 1;
  if (n_threads > 16) n_threads = 16;

  // Slice boundaries aligned to blob starts so every document is
  // formatted by exactly one thread.
  std::vector<Slice> slices;
  int64_t lo = 0;
  for (int t = 1; t < n_threads && lo < n; ++t) {
    int64_t target = (n * t) / n_threads;
    while (target < n && !is_start[target]) ++target;
    if (target > lo && target < n) {
      slices.push_back({lo, target});
      lo = target;
    }
  }
  slices.push_back({lo, n});

  std::vector<std::thread> workers;
  for (size_t k = 0; k < slices.size(); ++k) {
    workers.emplace_back(format_slice, rows, cols, vals, is_start, zoom,
                         k == 0, &slices[k]);
  }
  for (auto& w : workers) w.join();

  int64_t off = merge_slices(slices, /*tail=*/1, out);
  if (off < 0) return -1;
  (*out)[off++] = '}';  // trailing close of the last document
  return off;
}

// Format NUL-separated blob id strings "user|timespan|z_r_c" for one
// level's blob-run starts. user_idx/ts_idx: int32[n] dictionary
// indices; coarse_row/coarse_col: int32[n]; the name tables arrive as
// one UTF-8 buffer each with n_* offsets[i]..offsets[i+1] spans
// (offsets arrays have n_*+1 entries). Returns the byte length with a
// malloc'd buffer in *out (free with hm_blobfmt_free), 0 for n == 0,
// or a distinct negative code: -1 allocation failure, -2 dictionary
// index out of range, -3 coarse_zoom out of [0, 999].
int64_t hm_format_blob_ids(const int32_t* user_idx, const int32_t* ts_idx,
                           const int32_t* coarse_row,
                           const int32_t* coarse_col, int64_t n,
                           int32_t coarse_zoom, const char* user_buf,
                           const int64_t* user_offs, int32_t n_users,
                           const char* ts_buf, const int64_t* ts_offs,
                           int32_t n_ts, int32_t n_threads, char** out) {
  *out = nullptr;
  if (n <= 0) return 0;
  // Tile zooms are tiny non-negatives (<= 31 in practice); the 3-digit
  // budget in `per` and the zbuf below depend on this bound.
  if (coarse_zoom < 0 || coarse_zoom > 999) return -3;
  if (n_threads < 1) n_threads = 1;
  if (n_threads > 16) n_threads = 16;

  int64_t max_user = 0, max_ts = 0;
  for (int32_t i = 0; i < n_users; ++i) {
    const int64_t l = user_offs[i + 1] - user_offs[i];
    if (l > max_user) max_user = l;
  }
  for (int32_t i = 0; i < n_ts; ++i) {
    const int64_t l = ts_offs[i + 1] - ts_offs[i];
    if (l > max_ts) max_ts = l;
  }
  // user + '|' + timespan + '|' + zoom(3) + '_' + row(12) + '_' +
  // col(12) + NUL, padded.
  const int64_t per = max_user + max_ts + 34;

  const int64_t kMinPerThread = 1 << 15;
  int64_t want = (n + kMinPerThread - 1) / kMinPerThread;
  if (want < n_threads) n_threads = static_cast<int32_t>(want);
  std::vector<Slice> slices;
  const int64_t chunk = (n + n_threads - 1) / n_threads;
  for (int64_t lo = 0; lo < n; lo += chunk)
    slices.push_back({lo, lo + chunk < n ? lo + chunk : n});

  char zbuf[8];
  char* zend = put_i64(zbuf, coarse_zoom);
  const int zlen = static_cast<int>(zend - zbuf);

  std::vector<std::thread> workers;
  for (auto& s : slices) {
    workers.emplace_back([&, sp = &s] {
      const int64_t m = sp->hi - sp->lo;
      sp->buf = static_cast<char*>(
          std::malloc(static_cast<size_t>(m) * per));
      if (sp->buf == nullptr) {
        sp->len = -1;
        return;
      }
      char* p = sp->buf;
      for (int64_t i = sp->lo; i < sp->hi; ++i) {
        const int32_t u = user_idx[i], t = ts_idx[i];
        if (u < 0 || u >= n_users || t < 0 || t >= n_ts) {
          sp->len = -2;
          std::free(sp->buf);
          sp->buf = nullptr;
          return;
        }
        const int64_t ul = user_offs[u + 1] - user_offs[u];
        std::memcpy(p, user_buf + user_offs[u], ul);
        p += ul;
        *p++ = '|';
        const int64_t tl = ts_offs[t + 1] - ts_offs[t];
        std::memcpy(p, ts_buf + ts_offs[t], tl);
        p += tl;
        *p++ = '|';
        std::memcpy(p, zbuf, zlen);
        p += zlen;
        *p++ = '_';
        p = put_i64(p, coarse_row[i]);
        *p++ = '_';
        p = put_i64(p, coarse_col[i]);
        *p++ = '\0';
      }
      sp->len = p - sp->buf;
    });
  }
  for (auto& w : workers) w.join();

  return merge_slices(slices, /*tail=*/0, out);
}

void hm_blobfmt_free(char* buf) { std::free(buf); }

}  // extern "C"
