// Native entropy-coding stage for the TPU desktop-streaming codecs.
//
// This is the host-side sequential tail of the encode path (SURVEY.md §7
// hard part #1): the transform/quant/zigzag stages run on TPU, then the
// quantized coefficient tensors land here for bit packing.  The reference
// container had this inside NVENC silicon / libx264 (Dockerfile:210); our
// equivalent is first-party C++ compiled at install time (g++ -O3) and
// loaded via ctypes.  The Python implementations in bitstream/ are the
// behavioral reference: tests assert byte-identical output.
//
// Exported C ABI (see native/lib.py for the ctypes bindings):
//   jpeg_component_histogram  : per-component DC/AC symbol histograms
//   jpeg_encode_scan          : interleaved 4:2:0 MCU scan emission
//   h264_emulation_prevention : Annex-B EPB escaping of one RBSP
//   h264_annexb_rows          : a frame's row slices as Annex-B NALs, one
//                               call (start code, NAL header, the row's
//                               CABAC slice header where the host builds
//                               it, EPB escaping)

#include <cstdint>
#include <cstring>

namespace {

// ---------------------------------------------------------------------------
// MSB-first bit writer with optional JPEG 0xFF00 byte stuffing.
// ---------------------------------------------------------------------------
struct BitWriter {
  uint8_t* out;
  int64_t cap;
  int64_t pos = 0;        // bytes written
  uint64_t acc = 0;       // bit accumulator
  int nbits = 0;          // bits in accumulator
  bool jpeg_stuffing;
  bool overflow = false;

  BitWriter(uint8_t* out_, int64_t cap_, bool stuff)
      : out(out_), cap(cap_), jpeg_stuffing(stuff) {}

  inline void put_byte(uint8_t b) {
    if (pos >= cap) { overflow = true; return; }
    out[pos++] = b;
    if (jpeg_stuffing && b == 0xFF) {
      if (pos >= cap) { overflow = true; return; }
      out[pos++] = 0x00;
    }
  }

  inline void write(uint32_t value, int n) {
    if (n == 0) return;
    acc = (acc << n) | (value & ((n >= 32) ? 0xFFFFFFFFu : ((1u << n) - 1)));
    nbits += n;
    while (nbits >= 8) {
      nbits -= 8;
      put_byte((uint8_t)((acc >> nbits) & 0xFF));
    }
    acc &= (nbits >= 64) ? ~0ull : ((1ull << nbits) - 1);
  }

  inline void pad_to_byte(int pad_bit) {
    if (nbits % 8) {
      int n = 8 - nbits % 8;
      write(pad_bit ? ((1u << n) - 1) : 0, n);
    }
  }
};

inline int size_category(int32_t v) {
  uint32_t av = v < 0 ? (uint32_t)(-(int64_t)v) : (uint32_t)v;
  return av == 0 ? 0 : 32 - __builtin_clz(av);
}

// Huffman table on the wire for the C side: codes + lengths per symbol.
struct HuffTable {
  const uint32_t* codes;
  const uint8_t* lens;
};

// Encode one zigzagged 64-coeff block.  Returns new DC predictor.
inline int32_t encode_block(BitWriter& bw, const int32_t* zz, int32_t prev_dc,
                            const HuffTable& dc, const HuffTable& ac) {
  int32_t diff = zz[0] - prev_dc;
  int s = size_category(diff);
  uint32_t amp = diff >= 0 ? (uint32_t)diff : (uint32_t)(diff + (1 << s) - 1);
  bw.write(dc.codes[s], dc.lens[s]);
  bw.write(amp, s);

  int run = 0;
  int last_nz = 0;
  for (int k = 63; k >= 1; --k) {
    if (zz[k] != 0) { last_nz = k; break; }
  }
  for (int k = 1; k <= last_nz; ++k) {
    int32_t v = zz[k];
    if (v == 0) { ++run; continue; }
    while (run >= 16) {
      bw.write(ac.codes[0xF0], ac.lens[0xF0]);
      run -= 16;
    }
    int sz = size_category(v);
    uint32_t a = v >= 0 ? (uint32_t)v : (uint32_t)(v + (1 << sz) - 1);
    bw.write(ac.codes[(run << 4) | sz], ac.lens[(run << 4) | sz]);
    bw.write(a, sz);
    run = 0;
  }
  if (last_nz < 63) bw.write(ac.codes[0x00], ac.lens[0x00]);
  return zz[0];
}

// Emulation prevention (spec §7.4.1.1) of n bytes into out from pos on;
// `zeros` is the run of 0x00 that ends the bytes escaped so far, so that
// one NAL's RBSP may come in pieces.  Returns the new pos, -1 past out_cap.
// Entropy-coded bytes hold a zero in 256: outside a run of zeros the bytes
// up to the next one are copied whole.
inline int64_t escape_into(const uint8_t* in, int64_t n, uint8_t* out,
                           int64_t pos, int64_t out_cap, int& zeros) {
  int64_t i = 0;
  while (i < n) {
    if (zeros == 0) {
      const void* z = memchr(in + i, 0, n - i);
      int64_t run = z ? (const uint8_t*)z - (in + i) + 1 : n - i;
      if (run > out_cap - pos) return -1;
      memcpy(out + pos, in + i, run);
      pos += run;
      i += run;
      zeros = z ? 1 : 0;
      continue;
    }
    uint8_t b = in[i++];
    if (zeros >= 2 && b <= 3) {
      if (pos >= out_cap) return -1;
      out[pos++] = 3;
      zeros = 0;
    }
    if (pos >= out_cap) return -1;
    out[pos++] = b;
    zeros = (b == 0) ? zeros + 1 : 0;
  }
  return pos;
}

}  // namespace

extern "C" {

// Histogram DC-size and AC run/size symbols for one component.
// blocks: (nblk, 64) int32 zigzagged; dc_hist: int64[17]; ac_hist: int64[256].
void jpeg_component_histogram(const int32_t* blocks, int64_t nblk,
                              int64_t* dc_hist, int64_t* ac_hist) {
  int32_t prev_dc = 0;
  for (int64_t b = 0; b < nblk; ++b) {
    const int32_t* zz = blocks + b * 64;
    dc_hist[size_category(zz[0] - prev_dc)]++;
    prev_dc = zz[0];
    int last_nz = 0;
    for (int k = 63; k >= 1; --k) {
      if (zz[k] != 0) { last_nz = k; break; }
    }
    int run = 0;
    for (int k = 1; k <= last_nz; ++k) {
      if (zz[k] == 0) { ++run; continue; }
      while (run >= 16) { ac_hist[0xF0]++; run -= 16; }
      ac_hist[(run << 4) | size_category(zz[k])]++;
      run = 0;
    }
    if (last_nz < 63) ac_hist[0x00]++;
  }
}

// Emit the interleaved 4:2:0 scan: per MCU 4 luma blocks then Cb then Cr.
//   y:  (nmcu*4, 64)   cb, cr: (nmcu, 64)
//   *_codes: uint32[256], *_lens: uint8[256] (DC tables use entries 0..16)
// Returns bytes written, or -1 on output overflow.
int64_t jpeg_encode_scan(const int32_t* y, const int32_t* cb, const int32_t* cr,
                         int64_t nmcu,
                         const uint32_t* dc_codes_l, const uint8_t* dc_lens_l,
                         const uint32_t* ac_codes_l, const uint8_t* ac_lens_l,
                         const uint32_t* dc_codes_c, const uint8_t* dc_lens_c,
                         const uint32_t* ac_codes_c, const uint8_t* ac_lens_c,
                         uint8_t* out, int64_t out_cap) {
  BitWriter bw(out, out_cap, /*stuff=*/true);
  HuffTable dcl{dc_codes_l, dc_lens_l}, acl{ac_codes_l, ac_lens_l};
  HuffTable dcc{dc_codes_c, dc_lens_c}, acc{ac_codes_c, ac_lens_c};
  int32_t prev_y = 0, prev_cb = 0, prev_cr = 0;
  for (int64_t m = 0; m < nmcu; ++m) {
    for (int s = 0; s < 4; ++s)
      prev_y = encode_block(bw, y + (m * 4 + s) * 64, prev_y, dcl, acl);
    prev_cb = encode_block(bw, cb + m * 64, prev_cb, dcc, acc);
    prev_cr = encode_block(bw, cr + m * 64, prev_cr, dcc, acc);
  }
  bw.pad_to_byte(1);
  if (bw.overflow) return -1;
  return bw.pos;
}

// H.264 emulation prevention (spec §7.4.1.1): insert 0x03 after any
// 0x00 0x00 followed by a byte <= 0x03.  Worst case out = in * 3/2.
// Returns bytes written, or -1 if out_cap too small.
int64_t h264_emulation_prevention(const uint8_t* in, int64_t n,
                                  uint8_t* out, int64_t out_cap) {
  int zeros = 0;
  return escape_into(in, n, out, 0, out_cap, zeros);
}

// All row slices of a frame as Annex-B NAL units, in one call: per row the
// start code, the NAL header byte, the slice header where the host builds
// it, and the EPB-escaped RBSP (the escape state starts anew at every NAL
// and runs on from the header's bytes into the payload's).  Byte for byte
// bitstream/h264.py:nal_unit over every row.
//   src, src_len  : the buffer the rows' RBSP bytes lie in
//   row_off/len   : int64[rows], each row's bytes within src
//   nal_header    : (nal_ref_idc << 5) | nal_unit_type
//   hdr_tail, hdr_tail_nbits (0: the rows carry their slice headers
//                   already, the CAVLC road): the slice header after
//                   first_mb_in_slice, right-aligned; row r's header is
//                   ue(r * mb_step) + the tail + cabac_alignment_one_bits
// Returns bytes written, -1 if out_cap is too small, -2 if a row lies
// outside src.
int64_t h264_annexb_rows(const uint8_t* src, int64_t src_len,
                         const int64_t* row_off, const int64_t* row_len,
                         int64_t rows, int32_t nal_header, int64_t mb_step,
                         uint64_t hdr_tail, int32_t hdr_tail_nbits,
                         uint8_t* out, int64_t out_cap) {
  int64_t pos = 0;
  for (int64_t r = 0; r < rows; ++r) {
    if (row_off[r] < 0 || row_len[r] < 0 || row_off[r] > src_len - row_len[r])
      return -2;
    if (pos + 5 > out_cap) return -1;
    out[pos++] = 0; out[pos++] = 0; out[pos++] = 0; out[pos++] = 1;
    out[pos++] = (uint8_t)nal_header;
    int zeros = 0;
    if (hdr_tail_nbits > 0) {
      uint8_t hdr[24];               // ue() of 32 bits + 64 of tail: 16 bytes
      BitWriter bw(hdr, sizeof hdr, /*stuff=*/false);
      uint32_t code = (uint32_t)(r * mb_step) + 1;      // ue(first_mb)
      int nb = 32 - __builtin_clz(code);
      bw.write(0, nb - 1);
      bw.write(code, nb);
      if (hdr_tail_nbits > 32)
        bw.write((uint32_t)(hdr_tail >> 32), hdr_tail_nbits - 32);
      bw.write((uint32_t)hdr_tail,
               hdr_tail_nbits > 32 ? 32 : hdr_tail_nbits);
      bw.pad_to_byte(1);
      pos = escape_into(hdr, bw.pos, out, pos, out_cap, zeros);
      if (pos < 0) return -1;
    }
    pos = escape_into(src + row_off[r], row_len[r], out, pos, out_cap, zeros);
    if (pos < 0) return -1;
  }
  return pos;
}

// Simple ABI sanity probe used by the loader.
int32_t tpudesktop_entropy_abi_version() { return 1; }

}  // extern "C"
