// Honest CPU baseline for the north-star benchmark.
//
// A faithful, multithreaded C++ implementation of the reference's
// per-series / per-window query hot loop — ChunkedRateFunction over
// sorted timestamp vectors with counter correction and Prometheus
// extrapolation, reduced with sum by (group)  (reference:
// query/src/main/scala/filodb/query/exec/rangefn/RateFunctions.scala:140-207,
// exec/AggrOverRangeVectors.scala:161-277,
// jmh/src/main/scala/filodb.jmh/QueryInMemoryBenchmark.scala:45-249).
//
// The JVM publishes no absolute numbers and no JVM exists in this
// environment, so this -O3 C++ loop is the stand-in for the
// JVM's iterator path: same algorithm (binary search per window, one pass
// per series), same data, scaled across hardware threads the way the
// reference's query scheduler spreads range vectors across its pool.
//
// Semantics match the per-series NumPy loop of tests/test_baseline.py
// (same correction and extrapolation formulas).

#include <algorithm>
#include <cmath>
#include <cstdint>
#include <cstring>
#include <thread>
#include <vector>

namespace {

// One series: compact finite samples, apply counter correction, then emit
// the extrapolated rate for every window into a thread-private [G,T] sum.
void series_rate(const int64_t* ts, const double* vals, size_t nrows,
                 const int64_t* steps, size_t nsteps, int64_t window_ms,
                 int32_t group, size_t nsteps_stride, double* out,
                 double* cnt, int64_t* t_buf, double* v_buf) {
  size_t n = 0;
  for (size_t i = 0; i < nrows; ++i) {
    if (std::isfinite(vals[i])) {
      t_buf[n] = ts[i];
      v_buf[n] = vals[i];
      ++n;
    }
  }
  if (n < 2) return;
  // counter correction: running sum of drops, added back (prefix scan)
  double corr = 0.0;
  double prev = v_buf[0];
  for (size_t i = 1; i < n; ++i) {
    double cur = v_buf[i];
    if (cur < prev) corr += prev - cur;
    prev = cur;
    v_buf[i] = cur + corr;
  }
  double* orow = out + static_cast<size_t>(group) * nsteps_stride;
  double* crow = cnt + static_cast<size_t>(group) * nsteps_stride;
  for (size_t j = 0; j < nsteps; ++j) {
    const int64_t st = steps[j];
    const int64_t ws = st - window_ms;
    // (ws, st] window; timestamps sorted: binary search both bounds
    const int64_t* tb = t_buf;
    const int64_t* lo_p = std::upper_bound(tb, tb + n, ws);
    const int64_t* hi_p = std::upper_bound(lo_p, tb + n, st);
    const size_t lo = static_cast<size_t>(lo_p - tb);
    const size_t hi = static_cast<size_t>(hi_p - tb);
    if (hi - lo < 2) continue;
    const int64_t t1 = t_buf[lo], t2 = t_buf[hi - 1];
    if (t2 == t1) continue;
    const double delta = v_buf[hi - 1] - v_buf[lo];
    const double nw = static_cast<double>(hi - lo);
    const double avg_dur = static_cast<double>(t2 - t1) / (nw - 1.0);
    double ext_start, ext_end;
    if (static_cast<double>(t1 - ws) <= avg_dur * 1.1)
      ext_start = std::min(static_cast<double>(ws) + avg_dur / 2.0,
                           static_cast<double>(t1));
    else
      ext_start = static_cast<double>(t1) - avg_dur / 2.0;
    if (static_cast<double>(st - t2) <= avg_dur * 1.1)
      ext_end = std::max(static_cast<double>(st) - avg_dur / 2.0,
                         static_cast<double>(t2));
    else
      ext_end = static_cast<double>(t2) + avg_dur / 2.0;
    const double rate = delta * ((ext_end - ext_start) /
                                 static_cast<double>(t2 - t1)) /
                        (static_cast<double>(window_ms) / 1000.0);
    orow[j] += rate;
    crow[j] += 1.0;
  }
}

}  // namespace

extern "C" {

int baseline_hw_threads() {
  unsigned n = std::thread::hardware_concurrency();
  return n ? static_cast<int>(n) : 1;
}

// ts/vals: [S, R] row-major (one series per row; NaN-padded vals).
// ids: [S] group id in [0, G). steps: [T] window end timestamps (ms).
// out/cnt: [G, T] caller-zeroed. Returns 0, or -1 on bad args.
int baseline_rate_sum(const int64_t* ts, const double* vals, size_t S,
                      size_t R, const int32_t* ids, size_t G,
                      const int64_t* steps, size_t T, int64_t window_ms,
                      double* out, double* cnt, int nthreads) {
  if (!ts || !vals || !ids || !steps || !out || !cnt || G == 0) return -1;
  for (size_t s = 0; s < S; ++s)
    if (ids[s] < 0 || static_cast<size_t>(ids[s]) >= G) return -1;
  if (nthreads <= 0) nthreads = baseline_hw_threads();
  const size_t nt = std::min<size_t>(static_cast<size_t>(nthreads),
                                     std::max<size_t>(S, 1));

  std::vector<std::vector<double>> priv_out(nt), priv_cnt(nt);
  std::vector<std::thread> threads;
  threads.reserve(nt);
  const size_t per = (S + nt - 1) / nt;
  for (size_t t = 0; t < nt; ++t) {
    priv_out[t].assign(G * T, 0.0);
    priv_cnt[t].assign(G * T, 0.0);
    const size_t s0 = t * per, s1 = std::min(S, s0 + per);
    threads.emplace_back([=, &priv_out, &priv_cnt]() {
      std::vector<int64_t> t_buf(R);
      std::vector<double> v_buf(R);
      double* po = priv_out[t].data();
      double* pc = priv_cnt[t].data();
      for (size_t s = s0; s < s1; ++s)
        series_rate(ts + s * R, vals + s * R, R, steps, T, window_ms,
                    ids[s], T, po, pc, t_buf.data(), v_buf.data());
    });
  }
  for (auto& th : threads) th.join();
  for (size_t t = 0; t < nt; ++t)
    for (size_t i = 0; i < G * T; ++i) {
      out[i] += priv_out[t][i];
      cnt[i] += priv_cnt[t][i];
    }
  return 0;
}

// sum_over_time variant (no correction/extrapolation): per window, sum of
// samples in (st-window, st] (reference: AggrOverTimeFunctions.scala
// SumOverTime).
int baseline_sum_over_time(const int64_t* ts, const double* vals, size_t S,
                           size_t R, const int32_t* ids, size_t G,
                           const int64_t* steps, size_t T,
                           int64_t window_ms, double* out, double* cnt,
                           int nthreads) {
  if (!ts || !vals || !ids || !steps || !out || !cnt || G == 0) return -1;
  for (size_t s = 0; s < S; ++s)
    if (ids[s] < 0 || static_cast<size_t>(ids[s]) >= G) return -1;
  if (nthreads <= 0) nthreads = baseline_hw_threads();
  const size_t nt = std::min<size_t>(static_cast<size_t>(nthreads),
                                     std::max<size_t>(S, 1));
  std::vector<std::vector<double>> priv_out(nt), priv_cnt(nt);
  std::vector<std::thread> threads;
  const size_t per = (S + nt - 1) / nt;
  for (size_t t = 0; t < nt; ++t) {
    priv_out[t].assign(G * T, 0.0);
    priv_cnt[t].assign(G * T, 0.0);
    const size_t s0 = t * per, s1 = std::min(S, s0 + per);
    threads.emplace_back([=, &priv_out, &priv_cnt]() {
      std::vector<int64_t> t_buf(R);
      std::vector<double> v_buf(R);
      double* po = priv_out[t].data();
      double* pc = priv_cnt[t].data();
      for (size_t s = s0; s < s1; ++s) {
        const int64_t* trow = ts + s * R;
        const double* vrow = vals + s * R;
        size_t n = 0;
        for (size_t i = 0; i < R; ++i)
          if (std::isfinite(vrow[i])) {
            t_buf[n] = trow[i];
            v_buf[n] = vrow[i];
            ++n;
          }
        if (!n) continue;
        double* orow = po + static_cast<size_t>(ids[s]) * T;
        double* crow = pc + static_cast<size_t>(ids[s]) * T;
        const int64_t* tb = t_buf.data();
        for (size_t j = 0; j < T; ++j) {
          const int64_t st = steps[j];
          const int64_t* lo_p = std::upper_bound(tb, tb + n, st - window_ms);
          const int64_t* hi_p = std::upper_bound(lo_p, tb + n, st);
          if (lo_p == hi_p) continue;
          double acc = 0.0;
          for (const int64_t* p = lo_p; p != hi_p; ++p)
            acc += v_buf[p - tb];
          orow[j] += acc;
          crow[j] += 1.0;
        }
      }
    });
  }
  for (auto& th : threads) th.join();
  for (size_t t = 0; t < nt; ++t)
    for (size_t i = 0; i < G * T; ++i) {
      out[i] += priv_out[t][i];
      cnt[i] += priv_cnt[t][i];
    }
  return 0;
}

}  // extern "C"
