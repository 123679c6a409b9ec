/* Plain Keccak-256 (the original padding 0x01, as Ethereum uses it), for
 * the benchmark's reference and generator only. Compiled at run time by
 * keccak.py with the system's C compiler; nothing of the program is used.
 */
#include <stddef.h>
#include <stdint.h>
#include <string.h>

static const uint64_t RC[24] = {
    0x0000000000000001ULL, 0x0000000000008082ULL, 0x800000000000808aULL,
    0x8000000080008000ULL, 0x000000000000808bULL, 0x0000000080000001ULL,
    0x8000000080008081ULL, 0x8000000000008009ULL, 0x000000000000008aULL,
    0x0000000000000088ULL, 0x0000000080008009ULL, 0x000000008000000aULL,
    0x000000008000808bULL, 0x800000000000008bULL, 0x8000000000008089ULL,
    0x8000000000008003ULL, 0x8000000000008002ULL, 0x8000000000000080ULL,
    0x000000000000800aULL, 0x800000008000000aULL, 0x8000000080008081ULL,
    0x8000000000008080ULL, 0x0000000080000001ULL, 0x8000000080008008ULL};
static const int ROT[24] = {1,  3,  6,  10, 15, 21, 28, 36, 45, 55, 2,  14,
                            27, 41, 56, 8,  25, 43, 62, 18, 39, 61, 20, 44};
static const int PIL[24] = {10, 7,  11, 17, 18, 3, 5,  16, 8,  21, 24, 4,
                            15, 23, 19, 13, 12, 2, 20, 14, 22, 9,  6,  1};

static void keccakf(uint64_t s[25]) {
  uint64_t bc[5], t;
  for (int r = 0; r < 24; r++) {
    for (int i = 0; i < 5; i++)
      bc[i] = s[i] ^ s[i + 5] ^ s[i + 10] ^ s[i + 15] ^ s[i + 20];
    for (int i = 0; i < 5; i++) {
      t = bc[(i + 4) % 5] ^ ((bc[(i + 1) % 5] << 1) | (bc[(i + 1) % 5] >> 63));
      for (int j = 0; j < 25; j += 5) s[j + i] ^= t;
    }
    t = s[1];
    for (int i = 0; i < 24; i++) {
      int j = PIL[i];
      bc[0] = s[j];
      s[j] = (t << ROT[i]) | (t >> (64 - ROT[i]));
      t = bc[0];
    }
    for (int j = 0; j < 25; j += 5) {
      for (int i = 0; i < 5; i++) bc[i] = s[j + i];
      for (int i = 0; i < 5; i++)
        s[j + i] ^= (~bc[(i + 1) % 5]) & bc[(i + 2) % 5];
    }
    s[0] ^= RC[r];
  }
}

void keccak256(const uint8_t *in, size_t len, uint8_t *out) {
  uint64_t s[25];
  uint8_t block[136];
  memset(s, 0, sizeof s);
  while (len >= 136) {
    for (int i = 0; i < 17; i++) {
      uint64_t w;
      memcpy(&w, in + 8 * i, 8);
      s[i] ^= w;
    }
    keccakf(s);
    in += 136;
    len -= 136;
  }
  memset(block, 0, sizeof block);
  memcpy(block, in, len);
  block[len] ^= 0x01;
  block[135] ^= 0x80;
  for (int i = 0; i < 17; i++) {
    uint64_t w;
    memcpy(&w, block + 8 * i, 8);
    s[i] ^= w;
  }
  keccakf(s);
  memcpy(out, s, 32);
}

/* n messages laid end to end in `in`, message i at offsets[i]..offsets[i+1] */
void keccak256_many(const uint8_t *in, const int64_t *offsets, size_t n,
                    uint8_t *out) {
  for (size_t i = 0; i < n; i++)
    keccak256(in + offsets[i], (size_t)(offsets[i + 1] - offsets[i]),
              out + 32 * i);
}
