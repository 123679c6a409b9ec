// Host-side device-buffer packer — the C-ABI shim of the north star
// (BASELINE.json: "The Zig side packs variable-length trie paths and node
// RLP into padded device buffers"; the reference's analogous native glue is
// src/glue.c). Pads variable-length payloads with keccak multi-rate padding
// and lays them out as the fixed-shape (B, C, 136-byte) chunk buffer the
// device keccak kernel (phant_tpu/ops/keccak_jax.py) consumes.

#include <cstdint>
#include <cstring>
#include <cstddef>

namespace {
constexpr size_t kRate = 136;
}

extern "C" {

// Pack payload i = in[offsets[i] .. offsets[i]+lens[i]) into
// out[i * max_chunks * kRate ...], keccak-padded into nchunks[i] rate blocks.
// out must be zero-initialised to B * max_chunks * kRate bytes by the caller
// (numpy allocates it zeroed). Returns 0 on success, -1 if any payload
// overflows the bucket bound.
int phant_pack_keccak(const uint8_t* in, const uint64_t* offsets,
                      const uint32_t* lens, size_t n, size_t max_chunks,
                      uint8_t* out, int32_t* nchunks) {
  const size_t row = max_chunks * kRate;
  for (size_t i = 0; i < n; ++i) {
    const size_t len = lens[i];
    const size_t k = len / kRate + 1;  // padding always adds >= 1 bit
    if (k > max_chunks) return -1;
    nchunks[i] = static_cast<int32_t>(k);
    uint8_t* dst = out + i * row;
    std::memcpy(dst, in + offsets[i], len);
    dst[len] ^= 0x01;
    dst[k * kRate - 1] ^= 0x80;
  }
  return 0;
}

// Lay payload i out as row i of out (row_bytes a row), zero past its length
// and with NO keccak padding: the row form the device-resident witness table
// hashes and parses (phant_tpu/ops/witness_jax.py pack_node_rows). out must
// be zero-initialised to rows * row_bytes by the caller. Returns 0 on
// success, -1 if a payload does not leave the row one free byte (the
// device pads inside the row).
int phant_pack_rows(const uint8_t* in, const uint64_t* offsets,
                    const uint32_t* lens, size_t n, size_t row_bytes,
                    uint8_t* out) {
  for (size_t i = 0; i < n; ++i) {
    if (lens[i] >= row_bytes) return -1;
    std::memcpy(out + i * row_bytes, in + offsets[i], lens[i]);
  }
  return 0;
}

// --- witness child-ref scanner ---------------------------------------------
// Finds the byte offsets (into the witness blob) of every child hash
// reference inside each RLP trie node: the 32-byte string children of a
// branch node (items 0..15), the child of an extension node (2-item node
// whose hex-prefix flag has the leaf bit 0x20 clear), recursing into
// embedded (<32B) child structures. Leaf values and branch values are NOT
// references. Host-side complement of the device linkage verdict
// (phant_tpu/ops/witness_jax.py witness_verify_linked); the reference's
// analogous node walk is src/mpt/mpt.zig:47-119 (it computes roots only).

namespace {

// One RLP item at *pos (absolute into d, item must end by `end`).
// kind: 0 = string, 1 = list; [*ps, *pe) = payload span. Returns false on
// malformed input.
bool rlp_item(const uint8_t* d, size_t end, size_t* pos, int* kind,
              size_t* ps, size_t* pe) {
  if (*pos >= end) return false;
  const uint8_t b = d[*pos];
  size_t l, s;
  if (b < 0x80) {
    *kind = 0;
    *ps = *pos;
    *pe = *pos + 1;
    *pos += 1;
    return true;
  }
  if (b < 0xb8) {
    l = b - 0x80;
    s = *pos + 1;
    *kind = 0;
  } else if (b < 0xc0) {
    const size_t ll = b - 0xb7;
    if (*pos + 1 + ll > end) return false;
    l = 0;
    for (size_t i = 0; i < ll; ++i) l = (l << 8) | d[*pos + 1 + i];
    s = *pos + 1 + ll;
    *kind = 0;
  } else if (b < 0xf8) {
    l = b - 0xc0;
    s = *pos + 1;
    *kind = 1;
  } else {
    const size_t ll = b - 0xf7;
    if (*pos + 1 + ll > end) return false;
    l = 0;
    for (size_t i = 0; i < ll; ++i) l = (l << 8) | d[*pos + 1 + i];
    s = *pos + 1 + ll;
    *kind = 1;
  }
  if (l > end || s + l > end) return false;
  *ps = s;
  *pe = s + l;
  *pos = s + l;
  return true;
}

// If a leaf's value payload [s, e) is account-shaped RLP — a list of
// exactly four strings whose 3rd and 4th are 32 bytes (nonce, balance,
// storage_root, code_hash) — return the absolute offset of the storage
// root, else -1. The storage root is a commitment the leaf carries, so a
// witness's storage-trie nodes link through it. Malformed input is simply
// "not an account" (no error): leaf values are opaque in general.
long account_storage_root_off(const uint8_t* d, size_t s, size_t e) {
  size_t pos = s;
  int kind;
  size_t ps, pe;
  if (!rlp_item(d, e, &pos, &kind, &ps, &pe) || kind != 1 || pos != e)
    return -1;
  size_t ips[4], ipe[4];
  int n = 0;
  size_t p = ps;
  while (p < pe) {
    if (n >= 4) return -1;
    int k;
    if (!rlp_item(d, pe, &p, &k, &ips[n], &ipe[n]) || k != 0) return -1;
    ++n;
  }
  if (n != 4 || ipe[2] - ips[2] != 32 || ipe[3] - ips[3] != 32) return -1;
  return static_cast<long>(ips[2]);
}

// Scan a node's list payload [s, e) for child refs; returns the updated ref
// count, or -1 on malformed input / capacity overflow.
long scan_node_list(const uint8_t* d, size_t s, size_t e, int64_t* out_off,
                    int32_t* out_node, long cap, long cnt, int32_t node,
                    int depth) {
  if (depth > 64) return -1;
  int kinds[17];
  size_t pss[17], pes[17];
  int nitems = 0;
  size_t pos = s;
  while (pos < e) {
    if (nitems >= 17) return -1;
    if (!rlp_item(d, e, &pos, &kinds[nitems], &pss[nitems], &pes[nitems]))
      return -1;
    ++nitems;
  }
  if (nitems == 17) {
    for (int i = 0; i < 16; ++i) {
      if (kinds[i] == 0 && pes[i] - pss[i] == 32) {
        if (cnt >= cap) return -1;
        out_off[cnt] = static_cast<int64_t>(pss[i]);
        out_node[cnt] = node;
        ++cnt;
      } else if (kinds[i] == 1 && pes[i] > pss[i]) {
        cnt = scan_node_list(d, pss[i], pes[i], out_off, out_node, cap, cnt,
                             node, depth + 1);
        if (cnt < 0) return -1;
      }
    }
  } else if (nitems == 2) {
    if (pes[0] == pss[0]) return -1;  // hex-prefix path is never empty
    const bool is_leaf = (d[pss[0]] & 0x20) != 0;
    if (!is_leaf) {
      if (kinds[1] == 0 && pes[1] - pss[1] == 32) {
        if (cnt >= cap) return -1;
        out_off[cnt] = static_cast<int64_t>(pss[1]);
        out_node[cnt] = node;
        ++cnt;
      } else if (kinds[1] == 1) {
        cnt = scan_node_list(d, pss[1], pes[1], out_off, out_node, cap, cnt,
                             node, depth + 1);
        if (cnt < 0) return -1;
      }
    } else if (kinds[1] == 0) {
      const long sr = account_storage_root_off(d, pss[1], pes[1]);
      if (sr >= 0) {
        if (cnt >= cap) return -1;
        out_off[cnt] = sr;
        out_node[cnt] = node;
        ++cnt;
      }
    }
  }
  // other item counts: not a trie node shape — contributes no refs
  return cnt;
}

}  // namespace

// Scan n nodes (node i = blob[offsets[i] .. +lens[i])) for child hash refs.
// Writes each ref's absolute blob offset and owning node index; returns the
// ref count, or -1 on malformed RLP / capacity overflow.
long phant_scan_refs(const uint8_t* blob, const uint64_t* offsets,
                     const uint32_t* lens, size_t n, int64_t* out_off,
                     int32_t* out_node, size_t cap) {
  long cnt = 0;
  for (size_t i = 0; i < n; ++i) {
    const size_t s = offsets[i];
    const size_t e = s + lens[i];
    size_t pos = s;
    int kind;
    size_t ps, pe;
    if (!rlp_item(blob, e, &pos, &kind, &ps, &pe) || kind != 1 || pos != e)
      return -1;
    cnt = scan_node_list(blob, ps, pe, out_off, out_node,
                         static_cast<long>(cap), cnt, static_cast<int32_t>(i),
                         0);
    if (cnt < 0) return -1;
  }
  return cnt;
}

}  // extern "C"
