// Host RGB -> BT.601 studio-range YUV 4:2:0 (utils/hostcolor.py), one fused
// pass over row bands: Y, the 2x2 mean and both chroma samples of a pair of
// rows in one sweep, bands on a small persistent thread pool.
//
// The bytes are those of the three cv2 calls the Python road makes
// (tests/test_hostcolor_bands.py holds both roads to each other):
//   Y      cv2.cvtColor(COLOR_RGB2YUV_I420): 20-bit fixed point,
//          (269484 R + 528482 G + 102760 B + (16 << 20) + (1 << 19)) >> 20
//   mean   cv2.resize(INTER_AREA) by exactly 2: (a + b + c + d + 2) >> 2
//   Cb/Cr  cv2.transform with a 2x4 matrix on 8-bit input: the matrix as
//          float32, s = m[3]; s += m[0]*R; s += m[1]*G; s += m[2]*B in
//          float32 with NO fused multiply-add (the build passes
//          -ffp-contract=off), rounded half to even, saturated.

#include <algorithm>
#include <atomic>
#include <condition_variable>
#include <cstdint>
#include <cstring>
#include <functional>
#include <memory>
#include <mutex>
#include <thread>

namespace {

constexpr int kWorkers = 7;       // + the caller: utils/hostcolor._MAX_BANDS
constexpr int kChunk = 1024;      // pixels a sweep: the scratch stays in L1

// Persistent workers (a frame is converted 30-60 times a second: creating
// and joining threads a frame would be a tenth of the pass).  One job at a
// time: concurrent callers (two sessions of one process, both with the GIL
// released) queue on job_m_.  Workers are detached and the singleton is
// leaked, as native/cabac.cpp's pool is and for its reason.
class BandPool {
 public:
  static BandPool& instance() {
    static BandPool* p = new BandPool();
    return *p;
  }

  // fn(i) for i in [0, n); the caller takes indices too.
  void run(int n, const std::function<void(int)>& fn) {
    if (n <= 1) {
      for (int i = 0; i < n; ++i) fn(i);
      return;
    }
    std::lock_guard<std::mutex> job_lk(job_m_);
    auto job = std::make_shared<Job>();
    job->fn = &fn;
    job->total = n;
    job->remaining = n;
    {
      std::lock_guard<std::mutex> lk(m_);
      for (; workers_ < kWorkers; ++workers_)
        std::thread([this] { worker(); }).detach();
      job_ = job;
      ++gen_;
    }
    cv_.notify_all();
    work(*job);
    std::unique_lock<std::mutex> lk(m_);
    done_cv_.wait(lk, [&] { return job->remaining == 0; });
    job_ = nullptr;
  }

 private:
  struct Job {
    const std::function<void(int)>* fn = nullptr;
    std::atomic<int> next{0};
    int total = 0;
    int remaining = 0;      // under m_
  };

  void work(Job& job) {
    for (;;) {
      int i = job.next.fetch_add(1);
      if (i >= job.total) return;
      (*job.fn)(i);
      std::lock_guard<std::mutex> lk(m_);
      if (--job.remaining == 0) done_cv_.notify_all();
    }
  }

  void worker() {
    uint64_t seen = 0;
    std::unique_lock<std::mutex> lk(m_);
    for (;;) {
      cv_.wait(lk, [&] { return gen_ != seen; });
      seen = gen_;
      std::shared_ptr<Job> job = job_;
      lk.unlock();
      if (job) work(*job);
      lk.lock();
    }
  }

  std::mutex job_m_;
  std::mutex m_;
  std::condition_variable cv_, done_cv_;
  std::shared_ptr<Job> job_;
  uint64_t gen_ = 0;
  int workers_ = 0;
};

// One row's luma from planar samples.
inline void luma_row(const uint8_t* r, const uint8_t* g, const uint8_t* b,
                     int n, uint8_t* y) {
  for (int x = 0; x < n; ++x)
    y[x] = (uint8_t)((269484 * (int)r[x] + 528482 * (int)g[x] +
                      102760 * (int)b[x] + (16 << 20) + (1 << 19)) >> 20);
}

// s rounded half to even and saturated, for |s| < 2^22: adding 1.5 * 2^23
// leaves the integer in the low mantissa bits (the default rounding mode
// does the rounding; no fast-math, so the compiler keeps the sum).
inline uint8_t round_sat(float s) {
  float t = s + 12582912.0f;
  int32_t i;
  std::memcpy(&i, &t, sizeof i);
  i = (i & 0x7fffff) - 0x400000;
  return (uint8_t)std::min(std::max(i, 0), 255);
}

// Rows r0..r1 (even) of the picture into y, u, v.
void convert_band(const uint8_t* rgb, int64_t w, uint8_t* y, int64_t y_stride,
                  uint8_t* u, uint8_t* v, int64_t c_stride, const float* m,
                  int64_t r0, int64_t r1) {
  uint8_t pr[2][kChunk], pg[2][kChunk], pb[2][kChunk];
  for (int64_t r = r0; r < r1; r += 2) {
    for (int64_t x0 = 0; x0 < w; x0 += kChunk) {
      const int n = (int)std::min<int64_t>(kChunk, w - x0);   // even
      for (int k = 0; k < 2; ++k) {
        const uint8_t* src = rgb + 3 * ((r + k) * w + x0);
        for (int x = 0; x < n; ++x) {
          pr[k][x] = src[3 * x];
          pg[k][x] = src[3 * x + 1];
          pb[k][x] = src[3 * x + 2];
        }
        luma_row(pr[k], pg[k], pb[k], n, y + (r + k) * y_stride + x0);
      }
      uint8_t* ur = u + (r / 2) * c_stride + x0 / 2;
      uint8_t* vr = v + (r / 2) * c_stride + x0 / 2;
      for (int x = 0; x < n / 2; ++x) {
        const float R = (float)((pr[0][2 * x] + pr[0][2 * x + 1] +
                                 pr[1][2 * x] + pr[1][2 * x + 1] + 2) >> 2);
        const float G = (float)((pg[0][2 * x] + pg[0][2 * x + 1] +
                                 pg[1][2 * x] + pg[1][2 * x + 1] + 2) >> 2);
        const float B = (float)((pb[0][2 * x] + pb[0][2 * x + 1] +
                                 pb[1][2 * x] + pb[1][2 * x + 1] + 2) >> 2);
        float s = m[3];
        s += m[0] * R;
        s += m[1] * G;
        s += m[2] * B;
        ur[x] = round_sat(s);
        s = m[7];
        s += m[4] * R;
        s += m[5] * G;
        s += m[6] * B;
        vr[x] = round_sat(s);
      }
    }
  }
}

}  // namespace

extern "C" {

int32_t tpudesktop_colour_abi_version() { return 1; }

// rgb: h x w x 3 bytes, contiguous, h and w even.  y: rows y_stride apart;
// u, v: rows c_stride apart.  m: the 2x4 chroma matrix, row-major float32.
// bands: row bands to cut, on even rows (at most a band a pair of rows).
void rgb_to_yuv420_bands(const uint8_t* rgb, int64_t h, int64_t w,
                         uint8_t* y, int64_t y_stride, uint8_t* u, uint8_t* v,
                         int64_t c_stride, const float* m, int32_t bands) {
  const int64_t pairs = h / 2;
  const int n = (int)std::max<int64_t>(1, std::min<int64_t>(bands, pairs));
  BandPool::instance().run(n, [&](int i) {
    convert_band(rgb, w, y, y_stride, u, v, c_stride, m,
                 2 * (i * pairs / n), 2 * ((i + 1) * pairs / n));
  });
}

}  // extern "C"
