// C ABI of the native EVM core (native/evm.cc): the structs a frame is
// described by and the host vtable the embedder fills. One definition for
// the core and for its embedder, the CPython extension's host binding
// (native/pyext.cc).

#pragma once

#include <cstdint>

extern "C" {

struct PhantTxContext {
  uint8_t origin[20];
  uint8_t coinbase[20];
  uint64_t block_number;
  uint64_t timestamp;
  uint64_t gas_limit;
  uint64_t chain_id;
  uint8_t gas_price[32];
  uint8_t prev_randao[32];
  uint8_t base_fee[32];
  // EVM revision: 0 = Shanghai, 1 = Cancun, 2 = Prague — Cancun opcode
  // gates check `revision >= 1` so Prague inherits them; EIP-7702
  // delegation resolves host-side in the shared _call_inner, so this core
  // needs no Prague-specific opcodes. (The reference hardcodes
  // EVMC_SHANGHAI, src/blockchain/vm.zig:472; this core fork-dispatches)
  uint64_t revision;
  uint8_t blob_base_fee[32];          // EIP-7516
  const uint8_t* blob_hashes;         // EIP-4844: n x 32 bytes, may be null
  uint64_t n_blob_hashes;
};

// kinds for PhantMsg / the host `call` callback
enum PhantCallKind : int32_t {
  PHANT_CALL = 0,
  PHANT_CALLCODE = 1,
  PHANT_DELEGATECALL = 2,
  PHANT_STATICCALL = 3,
  PHANT_CREATE = 4,
  PHANT_CREATE2 = 5,
};

struct PhantMsg {
  int32_t kind;
  int32_t is_static;
  int32_t depth;
  int64_t gas;
  uint8_t caller[20];    // msg.sender inside the child
  uint8_t target[20];    // storage/balance context of the child
  uint8_t code_address[20];  // where the code comes from (CALLCODE/DELEGATE)
  uint8_t value[32];
  const uint8_t* data;
  uint64_t data_len;
  uint8_t salt[32];  // CREATE2
};

struct PhantResult {
  int32_t status;  // 0 success, 1 revert, 2 failure
  int64_t gas_left;
  const uint8_t* output;  // owned by the host (callback) or by phant (entry)
  uint64_t output_len;
  uint8_t create_address[20];
};

// Host vtable: the Python StateDB side of the interface (the reference's
// equivalent is the 14-entry EVMC host_interface at vm.zig:40-55).
struct PhantHost {
  void* ctx;
  int32_t (*access_account)(void*, const uint8_t addr[20]);  // 1 if was warm
  int32_t (*access_storage)(void*, const uint8_t addr[20], const uint8_t key[32]);
  void (*get_storage)(void*, const uint8_t addr[20], const uint8_t key[32], uint8_t out[32]);
  void (*get_original_storage)(void*, const uint8_t addr[20], const uint8_t key[32], uint8_t out[32]);
  void (*set_storage)(void*, const uint8_t addr[20], const uint8_t key[32], const uint8_t val[32]);
  void (*get_balance)(void*, const uint8_t addr[20], uint8_t out[32]);
  uint64_t (*get_code_size)(void*, const uint8_t addr[20]);
  void (*copy_code)(void*, const uint8_t addr[20], uint64_t offset, uint8_t* out, uint64_t size);
  void (*get_code_hash)(void*, const uint8_t addr[20], uint8_t out[32]);
  int32_t (*is_empty)(void*, const uint8_t addr[20]);
  void (*get_block_hash)(void*, uint64_t number, uint8_t out[32]);
  void (*emit_log)(void*, const uint8_t addr[20], const uint8_t* data, uint64_t len,
                   const uint8_t* topics, int32_t ntopics);
  void (*add_refund)(void*, int64_t delta);
  void (*selfdestruct)(void*, const uint8_t addr[20], const uint8_t beneficiary[20]);
  void (*call)(void*, const PhantMsg* msg, PhantResult* result);
  // EIP-1153 transient storage (Cancun); appended so pre-Cancun embedders'
  // vtable layout is a strict prefix
  void (*get_transient)(void*, const uint8_t addr[20], const uint8_t key[32], uint8_t out[32]);
  void (*set_transient)(void*, const uint8_t addr[20], const uint8_t key[32], const uint8_t val[32]);
  // optional per-instruction tracer (NULL = tracing off, zero overhead
  // beyond one branch). The reference compiles evmone's tracing.cpp into
  // its binary but never installs a tracer (build.zig:118, SURVEY §5);
  // this is the equivalent debugging surface, actually wired up.
  void (*trace)(void*, uint64_t pc, int32_t op, int64_t gas, int32_t depth,
                int32_t stack_size);
  // EIP-7702 (Prague): extra CALL-family charge when the code target is a
  // delegated account — warms the delegate host-side and returns its
  // warm/cold access cost (0 when not delegated / pre-Prague). Appended
  // last so older vtable layouts stay a strict prefix.
  int64_t (*delegate_access_cost)(void*, const uint8_t addr[20]);
};

// Execute one frame of bytecode (native/evm.cc). result->output is
// heap-allocated when non-null; free with phant_evm_free.
int32_t phant_evm_execute(const PhantHost* host, const PhantTxContext* txc,
                          const PhantMsg* msg, const uint8_t* code,
                          uint64_t code_len, PhantResult* result);
void phant_evm_free(const uint8_t* ptr);

}  // extern "C"
