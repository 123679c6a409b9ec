// Native EVM bytecode interpreter core (Shanghai revision).
//
// The reference executes bytecode in C++ too: the evmone interpreter behind
// the EVMC C ABI, with the client providing a host-interface vtable over its
// StateDB (reference: src/blockchain/vm.zig:33-558, build.zig:116-127). This
// file is the equivalent native core for this framework, written from
// scratch: a C ABI (`phant_evm_execute`) takes a host vtable of function
// pointers (state access, logs, nested call/create, native/evm.h) that the
// CPython extension's EvmHost (native/pyext.cc) implements over the Python
// StateDB — mirroring how the reference's Zig host backs evmone's 14
// callbacks. Semantics are differential-tested
// opcode-for-opcode against the Python interpreter
// (phant_tpu/evm/interpreter.py) on the execution-spec-test fixtures.
//
// Notes:
// - u256 is 4x64-bit limbs (little-endian limb order) with __uint128
//   products; div/mod are bit-serial (exactness over speed; DIV is cold).
// - Exceptional halts consume all frame gas (status kFail, gas_left 0);
//   REVERT preserves remaining gas (status kRevert).
// - The EVM stack lives on the heap (1024 u256 = 32 KiB) so depth-1024
//   call chains do not overflow the C stack.

#include <cstdint>
#include <cstring>
#include <cstddef>
#include <vector>

extern "C" void phant_keccak256(const uint8_t* in, size_t len, uint8_t* out);

namespace {

// ---------------------------------------------------------------------------
// u256
// ---------------------------------------------------------------------------

struct U256 {
  uint64_t w[4];  // w[0] = least significant
};

inline U256 u_zero() { return U256{{0, 0, 0, 0}}; }

inline U256 u_from64(uint64_t v) { return U256{{v, 0, 0, 0}}; }

inline bool u_is_zero(const U256& a) {
  return (a.w[0] | a.w[1] | a.w[2] | a.w[3]) == 0;
}

inline int u_cmp(const U256& a, const U256& b) {
  for (int i = 3; i >= 0; --i) {
    if (a.w[i] < b.w[i]) return -1;
    if (a.w[i] > b.w[i]) return 1;
  }
  return 0;
}

inline U256 u_add(const U256& a, const U256& b) {
  U256 r;
  unsigned __int128 c = 0;
  for (int i = 0; i < 4; ++i) {
    c += (unsigned __int128)a.w[i] + b.w[i];
    r.w[i] = (uint64_t)c;
    c >>= 64;
  }
  return r;
}

inline U256 u_sub(const U256& a, const U256& b) {
  U256 r;
  unsigned __int128 borrow = 0;
  for (int i = 0; i < 4; ++i) {
    unsigned __int128 d =
        (unsigned __int128)a.w[i] - b.w[i] - (uint64_t)borrow;
    r.w[i] = (uint64_t)d;
    borrow = (d >> 64) ? 1 : 0;
  }
  return r;
}

inline U256 u_mul(const U256& a, const U256& b) {  // low 256 bits
  uint64_t r[4] = {0, 0, 0, 0};
  for (int i = 0; i < 4; ++i) {
    unsigned __int128 carry = 0;
    for (int j = 0; i + j < 4; ++j) {
      carry += (unsigned __int128)a.w[i] * b.w[j] + r[i + j];
      r[i + j] = (uint64_t)carry;
      carry >>= 64;
    }
  }
  return U256{{r[0], r[1], r[2], r[3]}};
}

inline void u_mul_full(const U256& a, const U256& b, uint64_t out[8]) {
  std::memset(out, 0, 8 * sizeof(uint64_t));
  for (int i = 0; i < 4; ++i) {
    unsigned __int128 carry = 0;
    for (int j = 0; j < 4; ++j) {
      carry += (unsigned __int128)a.w[i] * b.w[j] + out[i + j];
      out[i + j] = (uint64_t)carry;
      carry >>= 64;
    }
    out[i + 4] = (uint64_t)carry;
  }
}

inline int u_bit(const uint64_t* words, int i) {
  return (words[i >> 6] >> (i & 63)) & 1;
}

inline int u_bitlen(const U256& a) {
  for (int i = 3; i >= 0; --i) {
    if (a.w[i]) return 64 * i + 64 - __builtin_clzll(a.w[i]);
  }
  return 0;
}

// words[nwords] mod m (bit-serial); m != 0.
U256 u_mod_words(const uint64_t* words, int nwords, const U256& m) {
  U256 r = u_zero();
  for (int i = 64 * nwords - 1; i >= 0; --i) {
    uint64_t top = r.w[3] >> 63;
    r.w[3] = (r.w[3] << 1) | (r.w[2] >> 63);
    r.w[2] = (r.w[2] << 1) | (r.w[1] >> 63);
    r.w[1] = (r.w[1] << 1) | (r.w[0] >> 63);
    r.w[0] = (r.w[0] << 1) | (uint64_t)u_bit(words, i);
    if (top || u_cmp(r, m) >= 0) r = u_sub(r, m);
  }
  return r;
}

// a / b and a % b; b != 0. The remainder shift can carry past bit 255 when
// b >= 2^255, so the shifted-out top bit forces a subtraction (the wrapped
// subtraction is still exact: 2r+bit <= 2b-1 < 2^257).
void u_divmod(const U256& a, const U256& b, U256* q, U256* r) {
  *q = u_zero();
  *r = u_zero();
  for (int i = 255; i >= 0; --i) {
    uint64_t top = r->w[3] >> 63;
    r->w[3] = (r->w[3] << 1) | (r->w[2] >> 63);
    r->w[2] = (r->w[2] << 1) | (r->w[1] >> 63);
    r->w[1] = (r->w[1] << 1) | (r->w[0] >> 63);
    r->w[0] = (r->w[0] << 1) | (uint64_t)u_bit(a.w, i);
    if (top || u_cmp(*r, b) >= 0) {
      *r = u_sub(*r, b);
      q->w[i >> 6] |= 1ULL << (i & 63);
    }
  }
}

inline bool u_sign(const U256& a) { return a.w[3] >> 63; }

inline U256 u_neg(const U256& a) { return u_sub(u_zero(), a); }

inline U256 u_abs(const U256& a) { return u_sign(a) ? u_neg(a) : a; }

// true if the value fits in uint64 (all high limbs zero)
inline bool u_fits64(const U256& a, uint64_t* out) {
  if (a.w[1] | a.w[2] | a.w[3]) return false;
  *out = a.w[0];
  return true;
}

inline void u_to_be(const U256& a, uint8_t out[32]) {
  for (int i = 0; i < 4; ++i) {
    uint64_t v = a.w[3 - i];
    for (int j = 0; j < 8; ++j) out[8 * i + j] = (uint8_t)(v >> (56 - 8 * j));
  }
}

inline U256 u_from_be(const uint8_t in[32]) {
  U256 r;
  for (int i = 0; i < 4; ++i) {
    uint64_t v = 0;
    for (int j = 0; j < 8; ++j) v = (v << 8) | in[8 * i + j];
    r.w[3 - i] = v;
  }
  return r;
}

inline U256 u_from_addr(const uint8_t addr[20]) {
  uint8_t be[32];
  std::memset(be, 0, 12);
  std::memcpy(be + 12, addr, 20);
  return u_from_be(be);
}

inline void u_to_addr(const U256& a, uint8_t out[20]) {
  uint8_t be[32];
  u_to_be(a, be);
  std::memcpy(out, be + 12, 20);
}

// ---------------------------------------------------------------------------
// C ABI structs: native/evm.h (shared with the embedder, native/pyext.cc)
// ---------------------------------------------------------------------------

}  // namespace

#include "evm.h"

namespace {

// ---------------------------------------------------------------------------
// gas schedule (Shanghai; mirrors phant_tpu/evm/gas.py and, transitively,
// reference src/blockchain/params.zig:5-39)
// ---------------------------------------------------------------------------

constexpr int64_t kColdAccount = 2600, kWarmAccount = 100;
constexpr int64_t kColdSload = 2100, kWarmSload = 100;
constexpr int64_t kSstoreSet = 20000, kSstoreReset = 2900, kSstoreSentry = 2300;
constexpr int64_t kSstoreClearsRefund = 4800;
constexpr int64_t kCreateGas = 32000, kCodeDepositPerByte = 200;
constexpr int64_t kMaxCodeSize = 0x6000, kMaxInitcodeSize = 2 * kMaxCodeSize;
constexpr int64_t kInitcodeWordCost = 2;
constexpr int64_t kCallValueGas = 9000, kCallStipend = 2300, kNewAccountGas = 25000;
constexpr int64_t kKeccakGas = 30, kKeccakWordGas = 6, kCopyWordGas = 3;
constexpr int64_t kLogGas = 375, kLogTopicGas = 375, kLogDataGas = 8;
constexpr int64_t kExpGas = 10, kExpByteGas = 50;
constexpr int64_t kSelfdestructGas = 5000;
constexpr int64_t kMemoryGas = 3, kQuadDiv = 512;

inline int64_t mem_cost(uint64_t size_bytes) {
  uint64_t words = (size_bytes + 31) / 32;
  return (int64_t)(kMemoryGas * words + (words * words) / kQuadDiv);
}

inline int64_t copy_cost_words(uint64_t len) {
  return kCopyWordGas * (int64_t)((len + 31) / 32);
}

enum class Halt { kNone, kStop, kReturn, kRevert, kFail };

struct Interp {
  const PhantHost* host;
  const PhantTxContext* txc;
  const PhantMsg* msg;
  const uint8_t* code;
  uint64_t code_len;
  uint8_t self_addr[20];  // frame.address = storage context

  std::vector<U256> stack;
  std::vector<uint8_t> mem;
  std::vector<uint8_t> retdata;  // child return data buffer
  std::vector<uint8_t> out;      // RETURN / REVERT payload
  std::vector<uint8_t> jumpdests;  // bitmap
  uint64_t pc = 0;
  int64_t gas = 0;

  Interp(const PhantHost* h, const PhantTxContext* t, const PhantMsg* m,
         const uint8_t* c, uint64_t clen)
      : host(h), txc(t), msg(m), code(c), code_len(clen) {
    stack.reserve(64);
    std::memcpy(self_addr, m->target, 20);
    gas = m->gas;
    jumpdests.assign((clen + 7) / 8, 0);
    for (uint64_t i = 0; i < clen; ++i) {
      uint8_t op = code[i];
      if (op == 0x5B) jumpdests[i >> 3] |= (uint8_t)(1 << (i & 7));
      if (op >= 0x60 && op <= 0x7F) i += op - 0x5F;
    }
  }

  bool is_jumpdest(uint64_t i) const {
    return i < code_len && (jumpdests[i >> 3] >> (i & 7)) & 1;
  }

  bool use_gas(int64_t amount) {
    if (amount < 0 || gas < amount) return false;
    gas -= amount;
    return true;
  }

  bool push(const U256& v) {
    if (stack.size() >= 1024) return false;
    stack.push_back(v);
    return true;
  }

  bool pop(U256* v) {
    if (stack.empty()) return false;
    *v = stack.back();
    stack.pop_back();
    return true;
  }

  // charge + grow memory to cover [off, off+size); size==0 is free
  bool expand(const U256& off_u, const U256& size_u) {
    if (u_is_zero(size_u)) return true;
    uint64_t off, size;
    if (!u_fits64(off_u, &off) || !u_fits64(size_u, &size)) return false;
    if (off > (1ULL << 32) || size > (1ULL << 32)) return false;
    uint64_t new_size = off + size;
    if (new_size <= mem.size()) return true;
    uint64_t new_words = (new_size + 31) / 32;
    if (!use_gas(mem_cost(new_words * 32) - mem_cost(mem.size()))) return false;
    mem.resize(new_words * 32, 0);
    return true;
  }

  void mread(uint64_t off, uint64_t size, std::vector<uint8_t>* dst) {
    dst->assign(size, 0);
    if (size && off + size <= mem.size())
      std::memcpy(dst->data(), mem.data() + off, size);
  }

  Halt run();
};

// saturating word-count cost for possibly-huge u256 sizes: any non-u64 size
// exceeds all gas, which reads as "out of gas" exactly like the Python side
inline bool size_cost(const U256& size_u, int64_t per_word, int64_t* out) {
  uint64_t size;
  if (!u_fits64(size_u, &size) || size > (1ULL << 40)) return false;
  *out = per_word * (int64_t)((size + 31) / 32);
  return true;
}

#define POP1(a) \
  U256 a;       \
  if (!pop(&a)) return Halt::kFail;
#define POP2(a, b) POP1(a) POP1(b)
#define POP3(a, b, c) POP2(a, b) POP1(c)
#define GAS(n) \
  if (!use_gas(n)) return Halt::kFail;
#define PUSH(v) \
  if (!push(v)) return Halt::kFail;

Halt Interp::run() {
  while (pc < code_len) {
    uint8_t op = code[pc];
    if (host->trace)
      host->trace(host->ctx, pc, (int32_t)op, gas, msg->depth,
                  (int32_t)stack.size());
    ++pc;

    // PUSH1..PUSH32
    if (op >= 0x60 && op <= 0x7F) {
      GAS(3);
      int width = op - 0x5F;
      uint8_t be[32];
      std::memset(be, 0, 32);
      uint64_t avail = pc < code_len ? code_len - pc : 0;
      uint64_t take = (uint64_t)width < avail ? (uint64_t)width : avail;
      // value is the immediate left-aligned to `width`, zero-extended past
      // the end of code, interpreted big-endian
      std::memcpy(be + 32 - width, code + pc, take);
      PUSH(u_from_be(be));
      pc += width;
      continue;
    }
    // DUP1..DUP16
    if (op >= 0x80 && op <= 0x8F) {
      GAS(3);
      size_t i = op - 0x7F;
      if (stack.size() < i) return Halt::kFail;
      PUSH(stack[stack.size() - i]);
      continue;
    }
    // SWAP1..SWAP16
    if (op >= 0x90 && op <= 0x9F) {
      GAS(3);
      size_t i = op - 0x8F;
      if (stack.size() < i + 1) return Halt::kFail;
      std::swap(stack[stack.size() - 1], stack[stack.size() - 1 - i]);
      continue;
    }

    switch (op) {
      case 0x00:  // STOP
        return Halt::kStop;

      case 0x01: {  // ADD
        GAS(3);
        POP2(a, b);
        PUSH(u_add(a, b));
        break;
      }
      case 0x02: {  // MUL
        GAS(5);
        POP2(a, b);
        PUSH(u_mul(a, b));
        break;
      }
      case 0x03: {  // SUB
        GAS(3);
        POP2(a, b);
        PUSH(u_sub(a, b));
        break;
      }
      case 0x04: {  // DIV
        GAS(5);
        POP2(a, b);
        if (u_is_zero(b)) {
          PUSH(u_zero());
        } else {
          U256 q, r;
          u_divmod(a, b, &q, &r);
          PUSH(q);
        }
        break;
      }
      case 0x05: {  // SDIV
        GAS(5);
        POP2(a, b);
        if (u_is_zero(b)) {
          PUSH(u_zero());
        } else {
          U256 q, r;
          u_divmod(u_abs(a), u_abs(b), &q, &r);
          PUSH(u_sign(a) != u_sign(b) ? u_neg(q) : q);
        }
        break;
      }
      case 0x06: {  // MOD
        GAS(5);
        POP2(a, b);
        if (u_is_zero(b)) {
          PUSH(u_zero());
        } else {
          U256 q, r;
          u_divmod(a, b, &q, &r);
          PUSH(r);
        }
        break;
      }
      case 0x07: {  // SMOD
        GAS(5);
        POP2(a, b);
        if (u_is_zero(b)) {
          PUSH(u_zero());
        } else {
          U256 q, r;
          u_divmod(u_abs(a), u_abs(b), &q, &r);
          PUSH(u_sign(a) ? u_neg(r) : r);
        }
        break;
      }
      case 0x08: {  // ADDMOD
        GAS(8);
        POP3(a, b, m);
        if (u_is_zero(m)) {
          PUSH(u_zero());
        } else {
          uint64_t wide[5];
          unsigned __int128 c = 0;
          for (int i = 0; i < 4; ++i) {
            c += (unsigned __int128)a.w[i] + b.w[i];
            wide[i] = (uint64_t)c;
            c >>= 64;
          }
          wide[4] = (uint64_t)c;
          PUSH(u_mod_words(wide, 5, m));
        }
        break;
      }
      case 0x09: {  // MULMOD
        GAS(8);
        POP3(a, b, m);
        if (u_is_zero(m)) {
          PUSH(u_zero());
        } else {
          uint64_t wide[8];
          u_mul_full(a, b, wide);
          PUSH(u_mod_words(wide, 8, m));
        }
        break;
      }
      case 0x0A: {  // EXP
        POP2(base, exp);
        int byte_len = (u_bitlen(exp) + 7) / 8;
        GAS(kExpGas + kExpByteGas * byte_len);
        U256 acc = u_from64(1);
        for (int i = u_bitlen(exp) - 1; i >= 0; --i) {
          acc = u_mul(acc, acc);
          if (u_bit(exp.w, i)) acc = u_mul(acc, base);
        }
        PUSH(acc);
        break;
      }
      case 0x0B: {  // SIGNEXTEND
        GAS(5);
        POP2(k, v);
        uint64_t kk;
        if (u_fits64(k, &kk) && kk < 31) {
          int bit = 8 * (int)(kk + 1) - 1;
          bool set = u_bit(v.w, bit);
          for (int i = bit + 1; i < 256; ++i) {
            if (set)
              v.w[i >> 6] |= 1ULL << (i & 63);
            else
              v.w[i >> 6] &= ~(1ULL << (i & 63));
          }
        }
        PUSH(v);
        break;
      }

      case 0x10: {  // LT
        GAS(3);
        POP2(a, b);
        PUSH(u_from64(u_cmp(a, b) < 0));
        break;
      }
      case 0x11: {  // GT
        GAS(3);
        POP2(a, b);
        PUSH(u_from64(u_cmp(a, b) > 0));
        break;
      }
      case 0x12: {  // SLT
        GAS(3);
        POP2(a, b);
        bool sa = u_sign(a), sb = u_sign(b);
        int c = u_cmp(a, b);
        PUSH(u_from64(sa != sb ? sa : c < 0));
        break;
      }
      case 0x13: {  // SGT
        GAS(3);
        POP2(a, b);
        bool sa = u_sign(a), sb = u_sign(b);
        int c = u_cmp(a, b);
        PUSH(u_from64(sa != sb ? sb : c > 0));
        break;
      }
      case 0x14: {  // EQ
        GAS(3);
        POP2(a, b);
        PUSH(u_from64(u_cmp(a, b) == 0));
        break;
      }
      case 0x15: {  // ISZERO
        GAS(3);
        POP1(a);
        PUSH(u_from64(u_is_zero(a)));
        break;
      }
      case 0x16: {  // AND
        GAS(3);
        POP2(a, b);
        for (int i = 0; i < 4; ++i) a.w[i] &= b.w[i];
        PUSH(a);
        break;
      }
      case 0x17: {  // OR
        GAS(3);
        POP2(a, b);
        for (int i = 0; i < 4; ++i) a.w[i] |= b.w[i];
        PUSH(a);
        break;
      }
      case 0x18: {  // XOR
        GAS(3);
        POP2(a, b);
        for (int i = 0; i < 4; ++i) a.w[i] ^= b.w[i];
        PUSH(a);
        break;
      }
      case 0x19: {  // NOT
        GAS(3);
        POP1(a);
        for (int i = 0; i < 4; ++i) a.w[i] = ~a.w[i];
        PUSH(a);
        break;
      }
      case 0x1A: {  // BYTE
        GAS(3);
        POP2(i_u, v);
        uint64_t i;
        if (u_fits64(i_u, &i) && i < 32) {
          uint8_t be[32];
          u_to_be(v, be);
          PUSH(u_from64(be[i]));
        } else {
          PUSH(u_zero());
        }
        break;
      }
      case 0x1B: {  // SHL
        GAS(3);
        POP2(sh_u, v);
        uint64_t sh;
        if (!u_fits64(sh_u, &sh) || sh >= 256) {
          PUSH(u_zero());
        } else {
          U256 r = u_zero();
          int limb = (int)(sh / 64), bits = (int)(sh % 64);
          for (int i = 3; i >= 0; --i) {
            uint64_t lo = (i - limb) >= 0 ? v.w[i - limb] : 0;
            uint64_t lo2 = (i - limb - 1) >= 0 ? v.w[i - limb - 1] : 0;
            r.w[i] = bits ? (lo << bits) | (lo2 >> (64 - bits)) : lo;
          }
          PUSH(r);
        }
        break;
      }
      case 0x1C: {  // SHR
        GAS(3);
        POP2(sh_u, v);
        uint64_t sh;
        if (!u_fits64(sh_u, &sh) || sh >= 256) {
          PUSH(u_zero());
        } else {
          U256 r = u_zero();
          int limb = (int)(sh / 64), bits = (int)(sh % 64);
          for (int i = 0; i < 4; ++i) {
            uint64_t hi = (i + limb) < 4 ? v.w[i + limb] : 0;
            uint64_t hi2 = (i + limb + 1) < 4 ? v.w[i + limb + 1] : 0;
            r.w[i] = bits ? (hi >> bits) | (hi2 << (64 - bits)) : hi;
          }
          PUSH(r);
        }
        break;
      }
      case 0x1D: {  // SAR
        GAS(3);
        POP2(sh_u, v);
        bool neg = u_sign(v);
        uint64_t sh;
        if (!u_fits64(sh_u, &sh) || sh >= 256) {
          U256 ones{{~0ULL, ~0ULL, ~0ULL, ~0ULL}};
          PUSH(neg ? ones : u_zero());
        } else {
          U256 r;
          int limb = (int)(sh / 64), bits = (int)(sh % 64);
          for (int i = 0; i < 4; ++i) {
            uint64_t hi = (i + limb) < 4 ? v.w[i + limb] : (neg ? ~0ULL : 0);
            uint64_t hi2 =
                (i + limb + 1) < 4 ? v.w[i + limb + 1] : (neg ? ~0ULL : 0);
            r.w[i] = bits ? (hi >> bits) | (hi2 << (64 - bits)) : hi;
          }
          PUSH(r);
        }
        break;
      }

      case 0x20: {  // KECCAK256
        POP2(off_u, size_u);
        int64_t words;
        if (!size_cost(size_u, kKeccakWordGas, &words)) return Halt::kFail;
        GAS(kKeccakGas + words);
        if (!expand(off_u, size_u)) return Halt::kFail;
        uint64_t off = 0, size = 0;
        u_fits64(off_u, &off);
        u_fits64(size_u, &size);
        uint8_t digest[32];
        phant_keccak256(size ? mem.data() + off : digest, size, digest);
        PUSH(u_from_be(digest));
        break;
      }

      case 0x30:  // ADDRESS
        GAS(2);
        PUSH(u_from_addr(self_addr));
        break;
      case 0x31: {  // BALANCE
        POP1(a_u);
        uint8_t addr[20];
        u_to_addr(a_u, addr);
        int warm = host->access_account(host->ctx, addr);
        GAS(warm ? kWarmAccount : kColdAccount);
        uint8_t bal[32];
        host->get_balance(host->ctx, addr, bal);
        PUSH(u_from_be(bal));
        break;
      }
      case 0x32:  // ORIGIN
        GAS(2);
        PUSH(u_from_addr(txc->origin));
        break;
      case 0x33:  // CALLER
        GAS(2);
        PUSH(u_from_addr(msg->caller));
        break;
      case 0x34:  // CALLVALUE
        GAS(2);
        PUSH(u_from_be(msg->value));
        break;
      case 0x35: {  // CALLDATALOAD
        GAS(3);
        POP1(i_u);
        uint64_t i;
        if (!u_fits64(i_u, &i) || i >= msg->data_len) {
          PUSH(u_zero());
        } else {
          uint8_t be[32];
          std::memset(be, 0, 32);
          uint64_t take = msg->data_len - i < 32 ? msg->data_len - i : 32;
          std::memcpy(be, msg->data + i, take);
          PUSH(u_from_be(be));
        }
        break;
      }
      case 0x36:  // CALLDATASIZE
        GAS(2);
        PUSH(u_from64(msg->data_len));
        break;
      case 0x37: {  // CALLDATACOPY
        POP3(dst_u, src_u, size_u);
        int64_t cost;
        if (!size_cost(size_u, kCopyWordGas, &cost)) return Halt::kFail;
        GAS(3 + cost);
        if (!expand(dst_u, size_u)) return Halt::kFail;
        uint64_t dst = 0, src = 0, size = 0;
        u_fits64(dst_u, &dst);
        u_fits64(size_u, &size);
        bool src_ok = u_fits64(src_u, &src);
        if (size) {
          // in-range prefix copied, remainder zero-filled (no src+i wrap)
          uint64_t avail =
              (src_ok && src < msg->data_len) ? msg->data_len - src : 0;
          uint64_t take = avail < size ? avail : size;
          if (take) std::memcpy(mem.data() + dst, msg->data + src, take);
          std::memset(mem.data() + dst + take, 0, size - take);
        }
        break;
      }
      case 0x38:  // CODESIZE
        GAS(2);
        PUSH(u_from64(code_len));
        break;
      case 0x39: {  // CODECOPY
        POP3(dst_u, src_u, size_u);
        int64_t cost;
        if (!size_cost(size_u, kCopyWordGas, &cost)) return Halt::kFail;
        GAS(3 + cost);
        if (!expand(dst_u, size_u)) return Halt::kFail;
        uint64_t dst = 0, src = 0, size = 0;
        u_fits64(dst_u, &dst);
        u_fits64(size_u, &size);
        bool src_ok = u_fits64(src_u, &src);
        if (size) {
          uint64_t avail = (src_ok && src < code_len) ? code_len - src : 0;
          uint64_t take = avail < size ? avail : size;
          if (take) std::memcpy(mem.data() + dst, code + src, take);
          std::memset(mem.data() + dst + take, 0, size - take);
        }
        break;
      }
      case 0x3A:  // GASPRICE
        GAS(2);
        PUSH(u_from_be(txc->gas_price));
        break;
      case 0x3B: {  // EXTCODESIZE
        POP1(a_u);
        uint8_t addr[20];
        u_to_addr(a_u, addr);
        int warm = host->access_account(host->ctx, addr);
        GAS(warm ? kWarmAccount : kColdAccount);
        PUSH(u_from64(host->get_code_size(host->ctx, addr)));
        break;
      }
      case 0x3C: {  // EXTCODECOPY
        POP1(a_u);
        POP3(dst_u, src_u, size_u);
        uint8_t addr[20];
        u_to_addr(a_u, addr);
        int warm = host->access_account(host->ctx, addr);
        int64_t cost;
        if (!size_cost(size_u, kCopyWordGas, &cost)) return Halt::kFail;
        GAS((warm ? kWarmAccount : kColdAccount) + cost);
        if (!expand(dst_u, size_u)) return Halt::kFail;
        uint64_t dst = 0, src = 0, size = 0;
        u_fits64(dst_u, &dst);
        u_fits64(size_u, &size);
        uint64_t ext_len = host->get_code_size(host->ctx, addr);
        bool src_ok = u_fits64(src_u, &src);
        if (size) {
          // zero-fill then copy the in-range slice (Python pads with zeros)
          std::memset(mem.data() + dst, 0, size);
          if (src_ok && src < ext_len) {
            uint64_t take = ext_len - src < size ? ext_len - src : size;
            host->copy_code(host->ctx, addr, src, mem.data() + dst, take);
          }
        }
        break;
      }
      case 0x3D:  // RETURNDATASIZE
        GAS(2);
        PUSH(u_from64(retdata.size()));
        break;
      case 0x3E: {  // RETURNDATACOPY
        POP3(dst_u, src_u, size_u);
        int64_t cost;
        if (!size_cost(size_u, kCopyWordGas, &cost)) return Halt::kFail;
        GAS(3 + cost);
        uint64_t src = 0, size = 0;
        u_fits64(size_u, &size);
        // overflow-safe bounds check: out-of-bounds is an exceptional halt
        if (!u_fits64(src_u, &src) || size > retdata.size() ||
            src > retdata.size() - size)
          return Halt::kFail;
        if (!expand(dst_u, size_u)) return Halt::kFail;
        uint64_t dst = 0;
        u_fits64(dst_u, &dst);
        if (size) std::memcpy(mem.data() + dst, retdata.data() + src, size);
        break;
      }
      case 0x3F: {  // EXTCODEHASH
        POP1(a_u);
        uint8_t addr[20];
        u_to_addr(a_u, addr);
        int warm = host->access_account(host->ctx, addr);
        GAS(warm ? kWarmAccount : kColdAccount);
        if (host->is_empty(host->ctx, addr)) {
          PUSH(u_zero());
        } else {
          uint8_t h[32];
          host->get_code_hash(host->ctx, addr, h);
          PUSH(u_from_be(h));
        }
        break;
      }

      case 0x40: {  // BLOCKHASH
        GAS(20);
        POP1(n_u);
        uint64_t n;
        uint64_t cur = txc->block_number;
        if (!u_fits64(n_u, &n) || n >= cur || cur - n > 256) {
          PUSH(u_zero());
        } else {
          uint8_t h[32];
          host->get_block_hash(host->ctx, n, h);
          PUSH(u_from_be(h));
        }
        break;
      }
      case 0x41:  // COINBASE
        GAS(2);
        PUSH(u_from_addr(txc->coinbase));
        break;
      case 0x42:  // TIMESTAMP
        GAS(2);
        PUSH(u_from64(txc->timestamp));
        break;
      case 0x43:  // NUMBER
        GAS(2);
        PUSH(u_from64(txc->block_number));
        break;
      case 0x44:  // PREVRANDAO
        GAS(2);
        PUSH(u_from_be(txc->prev_randao));
        break;
      case 0x45:  // GASLIMIT
        GAS(2);
        PUSH(u_from64(txc->gas_limit));
        break;
      case 0x46:  // CHAINID
        GAS(2);
        PUSH(u_from64(txc->chain_id));
        break;
      case 0x47: {  // SELFBALANCE
        GAS(5);
        uint8_t bal[32];
        host->get_balance(host->ctx, self_addr, bal);
        PUSH(u_from_be(bal));
        break;
      }
      case 0x48:  // BASEFEE
        GAS(2);
        PUSH(u_from_be(txc->base_fee));
        break;
      case 0x49: {  // BLOBHASH (EIP-4844, Cancun)
        if (txc->revision < 1) return Halt::kFail;
        GAS(3);
        POP1(idx_u);
        uint64_t idx;
        if (u_fits64(idx_u, &idx) && idx < txc->n_blob_hashes &&
            txc->blob_hashes != nullptr) {
          PUSH(u_from_be(txc->blob_hashes + 32 * idx));
        } else {
          PUSH(u_zero());
        }
        break;
      }
      case 0x4A:  // BLOBBASEFEE (EIP-7516, Cancun)
        if (txc->revision < 1) return Halt::kFail;
        GAS(2);
        PUSH(u_from_be(txc->blob_base_fee));
        break;

      case 0x50: {  // POP
        GAS(2);
        POP1(v);
        (void)v;
        break;
      }
      case 0x51: {  // MLOAD
        POP1(off_u);
        GAS(3);
        if (!expand(off_u, u_from64(32))) return Halt::kFail;
        uint64_t off = 0;
        u_fits64(off_u, &off);
        uint8_t be[32];
        std::memcpy(be, mem.data() + off, 32);
        PUSH(u_from_be(be));
        break;
      }
      case 0x52: {  // MSTORE
        POP2(off_u, val);
        GAS(3);
        if (!expand(off_u, u_from64(32))) return Halt::kFail;
        uint64_t off = 0;
        u_fits64(off_u, &off);
        u_to_be(val, mem.data() + off);
        break;
      }
      case 0x53: {  // MSTORE8
        POP2(off_u, val);
        GAS(3);
        if (!expand(off_u, u_from64(1))) return Halt::kFail;
        uint64_t off = 0;
        u_fits64(off_u, &off);
        mem[off] = (uint8_t)(val.w[0] & 0xFF);
        break;
      }
      case 0x54: {  // SLOAD
        POP1(slot);
        uint8_t key[32];
        u_to_be(slot, key);
        int warm = host->access_storage(host->ctx, self_addr, key);
        GAS(warm ? kWarmSload : kColdSload);
        uint8_t val[32];
        host->get_storage(host->ctx, self_addr, key, val);
        PUSH(u_from_be(val));
        break;
      }
      case 0x55: {  // SSTORE (EIP-2200 + 2929 + 3529 lattice)
        if (msg->is_static) return Halt::kFail;
        if (gas <= kSstoreSentry) return Halt::kFail;
        POP2(slot, new_v);
        uint8_t key[32];
        u_to_be(slot, key);
        int64_t cost = 0;
        if (!host->access_storage(host->ctx, self_addr, key)) cost += kColdSload;
        uint8_t cur_b[32], orig_b[32];
        host->get_storage(host->ctx, self_addr, key, cur_b);
        host->get_original_storage(host->ctx, self_addr, key, orig_b);
        U256 cur = u_from_be(cur_b), orig = u_from_be(orig_b);
        bool cur_eq_new = u_cmp(cur, new_v) == 0;
        bool cur_eq_orig = u_cmp(cur, orig) == 0;
        if (cur_eq_new) {
          cost += kWarmSload;
        } else if (cur_eq_orig) {
          cost += u_is_zero(orig) ? kSstoreSet : kSstoreReset;
        } else {
          cost += kWarmSload;
        }
        GAS(cost);
        if (!cur_eq_new) {
          if (cur_eq_orig) {
            if (!u_is_zero(orig) && u_is_zero(new_v))
              host->add_refund(host->ctx, kSstoreClearsRefund);
          } else {
            if (!u_is_zero(orig)) {
              if (u_is_zero(cur))
                host->add_refund(host->ctx, -kSstoreClearsRefund);
              else if (u_is_zero(new_v))
                host->add_refund(host->ctx, kSstoreClearsRefund);
            }
            if (u_cmp(new_v, orig) == 0) {
              host->add_refund(host->ctx, u_is_zero(orig)
                                              ? kSstoreSet - kWarmSload
                                              : kSstoreReset - kWarmSload);
            }
          }
          uint8_t nv[32];
          u_to_be(new_v, nv);
          host->set_storage(host->ctx, self_addr, key, nv);
        }
        break;
      }
      case 0x56: {  // JUMP
        GAS(8);
        POP1(dst_u);
        uint64_t dst;
        if (!u_fits64(dst_u, &dst) || !is_jumpdest(dst)) return Halt::kFail;
        pc = dst;
        break;
      }
      case 0x57: {  // JUMPI
        GAS(10);
        POP2(dst_u, cond);
        if (!u_is_zero(cond)) {
          uint64_t dst;
          if (!u_fits64(dst_u, &dst) || !is_jumpdest(dst)) return Halt::kFail;
          pc = dst;
        }
        break;
      }
      case 0x58:  // PC
        GAS(2);
        PUSH(u_from64(pc - 1));
        break;
      case 0x59:  // MSIZE
        GAS(2);
        PUSH(u_from64(mem.size()));
        break;
      case 0x5A:  // GAS
        GAS(2);
        PUSH(u_from64((uint64_t)gas));
        break;
      case 0x5B:  // JUMPDEST
        GAS(1);
        break;
      case 0x5C: {  // TLOAD (EIP-1153, Cancun)
        if (txc->revision < 1) return Halt::kFail;
        GAS(kWarmSload);
        POP1(slot);
        uint8_t key[32], val[32];
        u_to_be(slot, key);
        host->get_transient(host->ctx, self_addr, key, val);
        PUSH(u_from_be(val));
        break;
      }
      case 0x5D: {  // TSTORE (EIP-1153, Cancun)
        if (txc->revision < 1) return Halt::kFail;
        if (msg->is_static) return Halt::kFail;
        GAS(kWarmSload);
        POP2(slot, val_u);
        uint8_t key[32], val[32];
        u_to_be(slot, key);
        u_to_be(val_u, val);
        host->set_transient(host->ctx, self_addr, key, val);
        break;
      }
      case 0x5E: {  // MCOPY (EIP-5656, Cancun)
        if (txc->revision < 1) return Halt::kFail;
        POP3(dst_u, src_u, size_u);
        int64_t words_cost;
        if (!size_cost(size_u, kCopyWordGas, &words_cost)) return Halt::kFail;
        GAS(3 + words_cost);
        if (!u_is_zero(size_u)) {
          // one expansion covering both ranges (charge on the larger end)
          const U256& far = u_cmp(dst_u, src_u) >= 0 ? dst_u : src_u;
          if (!expand(far, size_u)) return Halt::kFail;
          uint64_t dst = 0, src = 0, size = 0;
          u_fits64(dst_u, &dst);
          u_fits64(src_u, &src);
          u_fits64(size_u, &size);
          std::memmove(mem.data() + dst, mem.data() + src, size);
        }
        break;
      }
      case 0x5F:  // PUSH0 (EIP-3855, Shanghai)
        GAS(2);
        PUSH(u_zero());
        break;

      case 0xA0:
      case 0xA1:
      case 0xA2:
      case 0xA3:
      case 0xA4: {  // LOG0..LOG4
        if (msg->is_static) return Halt::kFail;
        int ntopics = op - 0xA0;
        POP2(off_u, size_u);
        uint8_t topics[4 * 32];
        for (int i = 0; i < ntopics; ++i) {
          POP1(t);
          u_to_be(t, topics + 32 * i);
        }
        uint64_t size = 0;
        int64_t data_gas;
        if (!u_fits64(size_u, &size) || size > (1ULL << 40)) return Halt::kFail;
        data_gas = kLogDataGas * (int64_t)size;
        GAS(kLogGas + kLogTopicGas * ntopics + data_gas);
        if (!expand(off_u, size_u)) return Halt::kFail;
        uint64_t off = 0;
        u_fits64(off_u, &off);
        host->emit_log(host->ctx, self_addr, size ? mem.data() + off : nullptr,
                       size, topics, ntopics);
        break;
      }

      case 0xF0:    // CREATE
      case 0xF5: {  // CREATE2
        bool is_c2 = op == 0xF5;
        if (msg->is_static) return Halt::kFail;
        POP3(value, off_u, size_u);
        U256 salt = u_zero();
        if (is_c2) {
          POP1(s);
          salt = s;
        }
        uint64_t size = 0;
        if (!u_fits64(size_u, &size) || (int64_t)size > kMaxInitcodeSize)
          return Halt::kFail;  // EIP-3860
        int64_t words = (int64_t)((size + 31) / 32);
        GAS(kCreateGas +
            (kInitcodeWordCost + (is_c2 ? kKeccakWordGas : 0)) * words);
        if (!expand(off_u, size_u)) return Halt::kFail;
        uint64_t off = 0;
        u_fits64(off_u, &off);
        std::vector<uint8_t> init;
        mread(off, size, &init);
        retdata.clear();
        uint8_t bal[32];
        host->get_balance(host->ctx, self_addr, bal);
        if (u_cmp(value, u_from_be(bal)) > 0) {
          PUSH(u_zero());
          break;
        }
        int64_t child_gas = gas - gas / 64;  // EIP-150
        gas -= child_gas;
        PhantMsg cmsg;
        std::memset(&cmsg, 0, sizeof(cmsg));
        cmsg.kind = is_c2 ? PHANT_CREATE2 : PHANT_CREATE;
        cmsg.is_static = 0;
        cmsg.depth = msg->depth + 1;
        cmsg.gas = child_gas;
        std::memcpy(cmsg.caller, self_addr, 20);
        u_to_be(value, cmsg.value);
        cmsg.data = init.data();
        cmsg.data_len = init.size();
        u_to_be(salt, cmsg.salt);
        PhantResult cres;
        std::memset(&cres, 0, sizeof(cres));
        host->call(host->ctx, &cmsg, &cres);
        gas += cres.gas_left;
        if (cres.status == 0) {
          PUSH(u_from_addr(cres.create_address));
        } else {
          if (cres.status == 1 && cres.output_len)
            retdata.assign(cres.output, cres.output + cres.output_len);
          PUSH(u_zero());
        }
        break;
      }

      case 0xF1:    // CALL
      case 0xF2:    // CALLCODE
      case 0xF4:    // DELEGATECALL
      case 0xFA: {  // STATICCALL
        POP2(gas_req, addr_u);
        U256 value = u_zero();
        if (op == 0xF1 || op == 0xF2) {
          POP1(v);
          value = v;
        }
        POP2(in_off, in_size);
        POP2(ret_off, ret_size);
        uint8_t addr[20];
        u_to_addr(addr_u, addr);
        if (op == 0xF1 && !u_is_zero(value) && msg->is_static)
          return Halt::kFail;
        int warm = host->access_account(host->ctx, addr);
        GAS(warm ? kWarmAccount : kColdAccount);
        // EIP-7702: a delegated code target charges the delegate's
        // warm/cold access to THIS instruction, before the 63/64 split
        GAS(host->delegate_access_cost(host->ctx, addr));
        if (!expand(in_off, in_size)) return Halt::kFail;
        if (!expand(ret_off, ret_size)) return Halt::kFail;
        int64_t extra = 0;
        if (!u_is_zero(value)) {
          extra += kCallValueGas;
          if (op == 0xF1 && host->is_empty(host->ctx, addr))
            extra += kNewAccountGas;
        }
        GAS(extra);
        int64_t cap = gas - gas / 64;  // EIP-150
        uint64_t req64;
        int64_t child_gas =
            (u_fits64(gas_req, &req64) && (int64_t)req64 >= 0 &&
             (int64_t)req64 < cap)
                ? (int64_t)req64
                : cap;
        GAS(child_gas);
        if (!u_is_zero(value)) child_gas += kCallStipend;

        uint64_t ioff = 0, isize = 0, roff = 0, rsize = 0;
        u_fits64(in_off, &ioff);
        u_fits64(in_size, &isize);
        u_fits64(ret_off, &roff);
        u_fits64(ret_size, &rsize);
        std::vector<uint8_t> args;
        mread(ioff, isize, &args);
        retdata.clear();

        if (!u_is_zero(value) && (op == 0xF1 || op == 0xF2)) {
          uint8_t bal[32];
          host->get_balance(host->ctx, self_addr, bal);
          if (u_cmp(u_from_be(bal), value) < 0) {
            gas += child_gas;
            PUSH(u_zero());
            break;
          }
        }

        PhantMsg cmsg;
        std::memset(&cmsg, 0, sizeof(cmsg));
        cmsg.depth = msg->depth + 1;
        cmsg.gas = child_gas;
        cmsg.data = args.data();
        cmsg.data_len = args.size();
        if (op == 0xF1) {  // CALL
          cmsg.kind = PHANT_CALL;
          cmsg.is_static = msg->is_static;
          std::memcpy(cmsg.caller, self_addr, 20);
          std::memcpy(cmsg.target, addr, 20);
          std::memcpy(cmsg.code_address, addr, 20);
          u_to_be(value, cmsg.value);
        } else if (op == 0xF2) {  // CALLCODE: run addr's code in our context
          cmsg.kind = PHANT_CALLCODE;
          cmsg.is_static = msg->is_static;
          std::memcpy(cmsg.caller, self_addr, 20);
          std::memcpy(cmsg.target, self_addr, 20);
          std::memcpy(cmsg.code_address, addr, 20);
          u_to_be(value, cmsg.value);
        } else if (op == 0xF4) {  // DELEGATECALL: keep caller + value
          cmsg.kind = PHANT_DELEGATECALL;
          cmsg.is_static = msg->is_static;
          std::memcpy(cmsg.caller, msg->caller, 20);
          std::memcpy(cmsg.target, self_addr, 20);
          std::memcpy(cmsg.code_address, addr, 20);
          std::memcpy(cmsg.value, msg->value, 32);
        } else {  // STATICCALL
          cmsg.kind = PHANT_STATICCALL;
          cmsg.is_static = 1;
          std::memcpy(cmsg.caller, self_addr, 20);
          std::memcpy(cmsg.target, addr, 20);
          std::memcpy(cmsg.code_address, addr, 20);
        }
        PhantResult cres;
        std::memset(&cres, 0, sizeof(cres));
        host->call(host->ctx, &cmsg, &cres);
        if (cres.output_len)
          retdata.assign(cres.output, cres.output + cres.output_len);
        gas += cres.gas_left;
        if (rsize && cres.output_len) {
          uint64_t take = cres.output_len < rsize ? cres.output_len : rsize;
          std::memcpy(mem.data() + roff, cres.output, take);
        }
        PUSH(u_from64(cres.status == 0));
        break;
      }

      case 0xF3: {  // RETURN
        POP2(off_u, size_u);
        if (!expand(off_u, size_u)) return Halt::kFail;
        uint64_t off = 0, size = 0;
        u_fits64(off_u, &off);
        u_fits64(size_u, &size);
        mread(off, size, &out);
        return Halt::kReturn;
      }
      case 0xFD: {  // REVERT
        POP2(off_u, size_u);
        if (!expand(off_u, size_u)) return Halt::kFail;
        uint64_t off = 0, size = 0;
        u_fits64(off_u, &off);
        u_fits64(size_u, &size);
        mread(off, size, &out);
        return Halt::kRevert;
      }
      case 0xFE:  // INVALID
        return Halt::kFail;
      case 0xFF: {  // SELFDESTRUCT
        if (msg->is_static) return Halt::kFail;
        POP1(b_u);
        uint8_t beneficiary[20];
        u_to_addr(b_u, beneficiary);
        GAS(kSelfdestructGas);
        if (!host->access_account(host->ctx, beneficiary)) {
          GAS(kColdAccount);
        }
        uint8_t bal[32];
        host->get_balance(host->ctx, self_addr, bal);
        if (!u_is_zero(u_from_be(bal)) &&
            host->is_empty(host->ctx, beneficiary)) {
          GAS(kNewAccountGas);
        }
        host->selfdestruct(host->ctx, self_addr, beneficiary);
        return Halt::kStop;
      }

      default:
        return Halt::kFail;  // unknown opcode
    }
  }
  return Halt::kStop;  // ran off the end of code
}

}  // namespace

extern "C" {

// Execute one frame of bytecode. The host has already done snapshotting,
// value transfer, and precompile dispatch (exactly the split the reference
// has between its Zig host and evmone). Returns result->status.
// result->output is heap-allocated when non-null; free with phant_evm_free.
int32_t phant_evm_execute(const PhantHost* host, const PhantTxContext* txc,
                          const PhantMsg* msg, const uint8_t* code,
                          uint64_t code_len, PhantResult* result) {
  Interp in(host, txc, msg, code, code_len);
  Halt halt = in.run();
  result->output = nullptr;
  result->output_len = 0;
  std::memset(result->create_address, 0, 20);
  switch (halt) {
    case Halt::kStop:
      result->status = 0;
      result->gas_left = in.gas;
      break;
    case Halt::kReturn:
    case Halt::kRevert: {
      result->status = halt == Halt::kReturn ? 0 : 1;
      result->gas_left = in.gas;
      if (!in.out.empty()) {
        uint8_t* buf = new uint8_t[in.out.size()];
        std::memcpy(buf, in.out.data(), in.out.size());
        result->output = buf;
        result->output_len = in.out.size();
      }
      break;
    }
    default:
      result->status = 2;  // exceptional halt: all gas consumed
      result->gas_left = 0;
      break;
  }
  return result->status;
}

void phant_evm_free(const uint8_t* ptr) { delete[] ptr; }

}  // extern "C"
