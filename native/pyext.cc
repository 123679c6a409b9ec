// CPython extension driver for the native witness-engine core
// (native/engine.cc). The ctypes interface hands the core contiguous
// numpy buffers, which costs a b"".join + fromiter per batch (~30us/block
// at mainnet witness shapes — half the steady-state budget). This module
// walks the witness list structure directly with the CPython API and
// feeds the core scattered PyBytes pointers, so the Python side of
// verify_batch is two calls and zero copies.
//
// Two protocols share the walk/commit machinery:
//
// Classic (one batch at a time, mirrors WitnessEngine._verify_native):
//   scan(witnesses)  -> (novel: list[bytes], miss: int, total: int)
//                       witnesses = sequence of (root32, sequence[bytes]);
//                       batch state (node ptrs, rows, block bounds, roots)
//                       is retained on the engine object, and the
//                       witnesses object is INCREF'd so the pointers stay
//                       alive until finish()/the next scan().
//   [caller hashes the novel nodes on its routed backend]
//   finish(digests)  -> bytes verdicts (1 byte per block, 0/1);
//                       digests = b"".join of 32B digests for scan's
//                       novel list, or None when nothing was novel.
//   flush()          -> drop the interned generation (eviction).
//   nodes/digests()  -> interned counts (eviction policy + stats RPC).
//
// Pipelined (WitnessEngine.begin_batch/resolve_batch, PR 5): batch state
// lives in a standalone Batch object so several scanned batches can be
// outstanding at once — batch N+1 scans (executor thread, pack stage)
// while batch N hashes/commits (resolve worker). A node novel in two
// outstanding batches is interned twice (a benign duplicate row: both
// rows carry the same digest refid, so verdicts are unaffected); flushes
// are the caller's responsibility to order around outstanding batches
// (WitnessEngine defers eviction while handles are in flight).
//   scan_begin(witnesses)        -> (Batch, novel, miss, total)
//   finish_batch(Batch, digests) -> verdict bytes
//   finish_batch_native(Batch)   -> verdict bytes (in-C keccak)
//
// The pure-C stages (scan loop, commit, verdict, in-C hashing) release
// the GIL: the whole point of the pipelined protocol is that the resolve
// worker's C time runs concurrently with the executor's Python time.
// Engine-level exclusion of table mutation is WitnessEngine._lock.
//
// The module also carries the trie-node encoder (second half of this
// file): one RLP writer under the host walk of a trie (encode_subtree),
// under the hash-plan builder's templates (encode_node) and, being the
// same bytes, under rlp.encode. It holds the GIL throughout: every step
// reads Python objects.
//
// And the scalar hash, keccak256(data) -> bytes, under
// phant_tpu.crypto.keccak.keccak256 (the end of this file): the GIL held
// for a short input, released for a long one.

#define PY_SSIZE_T_CLEAN
#include <Python.h>

#include <time.h>

#include <atomic>
#include <cstdint>
#include <cstdlib>
#include <cstring>
#include <initializer_list>
#include <vector>

#include "evm.h"

extern "C" {
void* phant_engine_new();
void phant_engine_free(void*);
void phant_engine_flush(void*);
uint64_t phant_engine_nodes(void*);
uint64_t phant_engine_digests(void*);
int phant_engine_scan_ptrs(void*, const uint8_t* const*, const uint32_t*,
                           uint64_t, int64_t*, uint32_t*, uint64_t*);
int64_t phant_engine_commit_ptrs(void*, const uint8_t* const*,
                                 const uint32_t*, uint64_t, int64_t*,
                                 const uint32_t*, uint64_t, const uint8_t*);
int64_t phant_engine_commit_hash_ptrs(void*, const uint8_t* const*,
                                      const uint32_t*, uint64_t, int64_t*,
                                      const uint32_t*, uint64_t);
int phant_engine_verdict(void*, const int64_t*, const uint64_t*, uint64_t,
                         const uint8_t*, uint8_t*);
void phant_keccak256_ptrs_fast(const uint8_t* const*, const uint32_t*,
                               uint64_t, uint8_t*);
void phant_keccak256(const uint8_t*, size_t, uint8_t*);
}

namespace {

// The places where this module gives the interpreter lock away around pure
// C work, and two process-wide clocks a place: the nanoseconds the work ran
// unlocked (from the release to the work's end), and the nanoseconds from
// the work's end until the lock was back. The second is the one wait for
// the lock the program can MEASURE rather than infer from wall less CPU;
// utils/native reads both at every /metrics exposition (lock_clocks()).
enum LockSite {
  kSiteScan,
  kSiteVerdict,
  kSiteCommit,
  kSiteCommitHash,
  kSiteHash,
  kSiteFinishCommit,
  kSiteKeccak,
  kLockSites
};
const char* const kLockSiteNames[kLockSites] = {
    "scan",   "verdict", "commit", "commit_hash", "hash", "finish_commit",
    "keccak"};
std::atomic<uint64_t> g_unlocked_ns[kLockSites];
std::atomic<uint64_t> g_retake_ns[kLockSites];

inline uint64_t mono_ns() {
  timespec ts;
  clock_gettime(CLOCK_MONOTONIC, &ts);
  return static_cast<uint64_t>(ts.tv_sec) * 1000000000ull +
         static_cast<uint64_t>(ts.tv_nsec);
}

// Py_BEGIN/END_ALLOW_THREADS as a scope, clocked: the lock is released at
// construction and taken back when the scope (the native work) ends.
class Unlocked {
 public:
  explicit Unlocked(LockSite site)
      : site_(site), save_(PyEval_SaveThread()), released_(mono_ns()) {}
  ~Unlocked() {
    const uint64_t done = mono_ns();
    PyEval_RestoreThread(save_);
    const uint64_t back = mono_ns();
    g_unlocked_ns[site_].fetch_add(done - released_, std::memory_order_relaxed);
    g_retake_ns[site_].fetch_add(back - done, std::memory_order_relaxed);
  }
  Unlocked(const Unlocked&) = delete;
  Unlocked& operator=(const Unlocked&) = delete;

 private:
  const LockSite site_;
  PyThreadState* const save_;
  const uint64_t released_;
};

// One scanned batch: node pointers (pinned via `keep`), scan rows, block
// bounds, roots. Owned inline by the engine (classic protocol) or by a
// Batch object (pipelined protocol).
struct BatchState {
  std::vector<PyObject*> node_objs;  // borrowed (owned via `keep`)
  std::vector<const uint8_t*> ptrs;
  std::vector<uint32_t> lens;
  std::vector<int64_t> rows;
  std::vector<uint32_t> novel_idx;
  std::vector<uint64_t> block_offs;
  std::vector<uint8_t> roots;
  std::vector<uint8_t> digests;  // 32B/novel, filled by hash_batch()
  uint64_t n_novel = 0;
  PyObject* keep = nullptr;  // the witnesses object (pins node bytes)
};

void batch_clear(BatchState* bs) {
  bs->n_novel = 0;
  Py_CLEAR(bs->keep);
}

struct EngineObject {
  PyObject_HEAD
  void* eng;
  BatchState* batch;  // classic-protocol slot, valid between scan/finish
  int have_batch;
};

void Engine_dealloc(EngineObject* self) {
  if (self->eng) phant_engine_free(self->eng);
  if (self->batch) {
    batch_clear(self->batch);
    delete self->batch;
  }
  Py_TYPE(self)->tp_free(reinterpret_cast<PyObject*>(self));
}

PyObject* Engine_new(PyTypeObject* type, PyObject*, PyObject*) {
  EngineObject* self =
      reinterpret_cast<EngineObject*>(type->tp_alloc(type, 0));
  if (!self) return nullptr;
  self->eng = phant_engine_new();
  self->batch = new BatchState();
  self->have_batch = 0;
  return reinterpret_cast<PyObject*>(self);
}

void clear_batch(EngineObject* self) {
  self->have_batch = 0;
  batch_clear(self->batch);
}

// Walk `witnesses` into `bs` (ptrs/lens/block_offs/roots + keep), run the
// C hit-scan, and build the novel list. Returns the (novel, miss, total)
// tuple, or nullptr with an exception set (bs left cleared).
PyObject* scan_into(EngineObject* self, PyObject* witnesses, BatchState* bs) {
  batch_clear(bs);
  // `keep` pins every container whose items back a stored pointer: the
  // materialized outer sequence plus each block's materialized node
  // sequence (PySequence_Fast returns the list/tuple itself, or a fresh
  // list for lazy inputs — either way it owns the bytes objects).
  PyObject* keep = PyList_New(0);
  if (!keep) return nullptr;
  PyObject* wseq = PySequence_Fast(witnesses, "witnesses must be a sequence");
  if (!wseq || PyList_Append(keep, wseq) < 0) {
    Py_XDECREF(wseq);
    Py_DECREF(keep);
    return nullptr;
  }
  Py_DECREF(wseq);  // owned by `keep` now
  const Py_ssize_t n_blocks = PySequence_Fast_GET_SIZE(wseq);
  auto& ptrs = bs->ptrs;
  auto& node_objs = bs->node_objs;
  auto& lens = bs->lens;
  auto& boffs = bs->block_offs;
  auto& roots = bs->roots;
  ptrs.clear();
  node_objs.clear();
  lens.clear();
  boffs.clear();
  roots.clear();
  boffs.push_back(0);
  roots.reserve(32 * n_blocks);
  for (Py_ssize_t b = 0; b < n_blocks; ++b) {
    PyObject* pair = PySequence_Fast_GET_ITEM(wseq, b);  // borrowed
    PyObject* root_obj;
    PyObject* nodes_obj;
    PyObject* p2 = nullptr;
    if (PyTuple_Check(pair) && PyTuple_GET_SIZE(pair) == 2) {
      root_obj = PyTuple_GET_ITEM(pair, 0);
      nodes_obj = PyTuple_GET_ITEM(pair, 1);
    } else {
      p2 = PySequence_Fast(pair, "witness must be (root, nodes)");
      if (!p2 || PySequence_Fast_GET_SIZE(p2) != 2 ||
          PyList_Append(keep, p2) < 0) {
        Py_XDECREF(p2);
        Py_DECREF(keep);
        if (!PyErr_Occurred())
          PyErr_SetString(PyExc_ValueError, "witness must be (root, nodes)");
        return nullptr;
      }
      root_obj = PySequence_Fast_GET_ITEM(p2, 0);
      nodes_obj = PySequence_Fast_GET_ITEM(p2, 1);
      Py_DECREF(p2);  // owned by `keep`
    }
    char* rbuf;
    Py_ssize_t rlen;
    if (PyBytes_AsStringAndSize(root_obj, &rbuf, &rlen) < 0 || rlen != 32) {
      Py_DECREF(keep);
      if (!PyErr_Occurred())
        PyErr_SetString(PyExc_ValueError, "root must be 32 bytes");
      return nullptr;
    }
    roots.insert(roots.end(), rbuf, rbuf + 32);
    PyObject* nseq = PySequence_Fast(nodes_obj, "nodes must be a sequence");
    if (!nseq || PyList_Append(keep, nseq) < 0) {
      Py_XDECREF(nseq);
      Py_DECREF(keep);
      return nullptr;
    }
    Py_DECREF(nseq);  // owned by `keep`
    const Py_ssize_t n_nodes = PySequence_Fast_GET_SIZE(nseq);
    for (Py_ssize_t i = 0; i < n_nodes; ++i) {
      PyObject* node = PySequence_Fast_GET_ITEM(nseq, i);  // borrowed
      char* buf;
      Py_ssize_t blen;
      if (PyBytes_AsStringAndSize(node, &buf, &blen) < 0) {
        Py_DECREF(keep);
        return nullptr;
      }
      ptrs.push_back(reinterpret_cast<const uint8_t*>(buf));
      node_objs.push_back(node);  // borrowed; pinned via `keep`
      lens.push_back(static_cast<uint32_t>(blen));
    }
    boffs.push_back(ptrs.size());
  }
  // roots vector backs the verdict call; node ptrs live until finish
  bs->keep = keep;

  const uint64_t n = ptrs.size();
  bs->rows.resize(n);
  bs->novel_idx.resize(n ? n : 1);
  uint64_t counts[2] = {0, 0};
  // pure C from here: the scan loop touches only the pinned buffers
  {
    Unlocked unlocked(kSiteScan);
    phant_engine_scan_ptrs(self->eng, ptrs.data(), lens.data(), n,
                           bs->rows.data(), bs->novel_idx.data(), counts);
  }
  bs->n_novel = counts[1];

  // the novel list shares the existing bytes objects (no copies) — they
  // are alive via `keep` and the INCREF here
  PyObject* novel = PyList_New(static_cast<Py_ssize_t>(counts[1]));
  if (!novel) {
    batch_clear(bs);  // don't leave a half-built batch retained on OOM
    return nullptr;
  }
  for (uint64_t k = 0; k < counts[1]; ++k) {
    PyObject* nb = node_objs[bs->novel_idx[k]];
    Py_INCREF(nb);
    PyList_SET_ITEM(novel, static_cast<Py_ssize_t>(k), nb);
  }
  PyObject* ret = Py_BuildValue("(NKK)", novel, (unsigned long long)counts[0],
                                (unsigned long long)n);
  if (!ret) {
    // "N" args are consumed by Py_BuildValue even on failure (CPython
    // modsupport.c releases them so they don't leak) — only the batch
    // state needs unwinding here, a DECREF would double-release `novel`
    batch_clear(bs);
  }
  return ret;
}

// scan(witnesses) -> (novel list, miss, total) — classic protocol
PyObject* Engine_scan(EngineObject* self, PyObject* witnesses) {
  clear_batch(self);
  PyObject* ret = scan_into(self, witnesses, self->batch);
  if (ret) self->have_batch = 1;
  return ret;
}

// Per-block verdicts over a batch state (GIL released around the C join).
PyObject* batch_verdict(EngineObject* self, BatchState* bs) {
  const uint64_t n_blocks = bs->block_offs.size() - 1;
  PyObject* out = PyBytes_FromStringAndSize(nullptr,
                                            static_cast<Py_ssize_t>(n_blocks));
  if (!out) return nullptr;
  uint8_t* obuf = reinterpret_cast<uint8_t*>(PyBytes_AS_STRING(out));
  {
    Unlocked unlocked(kSiteVerdict);
    phant_engine_verdict(self->eng, bs->rows.data(), bs->block_offs.data(),
                         n_blocks, bs->roots.data(), obuf);
  }
  return out;
}

// Shared tail of both classic finish paths: verdicts + batch reset.
PyObject* verdict_and_clear(EngineObject* self) {
  PyObject* out = batch_verdict(self, self->batch);
  clear_batch(self);
  return out;
}

// Commit a batch's novel nodes with caller digests (GIL released).
// Returns 0, or -1 with an exception set.
int batch_commit(EngineObject* self, BatchState* bs, PyObject* digests_obj) {
  if (!bs->n_novel) return 0;
  char* dbuf;
  Py_ssize_t dlen;
  if (digests_obj == Py_None ||
      PyBytes_AsStringAndSize(digests_obj, &dbuf, &dlen) < 0) {
    if (!PyErr_Occurred())
      PyErr_SetString(PyExc_ValueError, "novel nodes need digests");
    return -1;
  }
  if (static_cast<uint64_t>(dlen) != 32 * bs->n_novel) {
    PyErr_SetString(PyExc_ValueError, "digests must be 32B per novel node");
    return -1;
  }
  {
    Unlocked unlocked(kSiteCommit);
    phant_engine_commit_ptrs(self->eng, bs->ptrs.data(), bs->lens.data(),
                             bs->ptrs.size(), bs->rows.data(),
                             bs->novel_idx.data(), bs->n_novel,
                             reinterpret_cast<const uint8_t*>(dbuf));
  }
  return 0;
}

// Commit with in-C keccak of the novel nodes (GIL released: the commit
// touches only raw pointers pinned by `keep` — a big novel batch, tens of
// MB of keccak at startup/post-eviction, must not stall the Engine API's
// other serving threads).
void batch_commit_native(EngineObject* self, BatchState* bs) {
  if (!bs->n_novel) return;
  {
    Unlocked unlocked(kSiteCommitHash);
    phant_engine_commit_hash_ptrs(self->eng, bs->ptrs.data(), bs->lens.data(),
                                  bs->ptrs.size(), bs->rows.data(),
                                  bs->novel_idx.data(), bs->n_novel);
  }
}

// finish_native() -> verdict bytes; novel nodes are hashed IN C through
// the fast keccak batch — the zero-Python-round-trip path the engine
// takes when the routed hashing backend is the host.
PyObject* Engine_finish_native(EngineObject* self, PyObject*) {
  if (!self->have_batch) {
    PyErr_SetString(PyExc_RuntimeError, "finish_native() without a batch");
    return nullptr;
  }
  batch_commit_native(self, self->batch);
  return verdict_and_clear(self);
}

// finish(digests_or_None) -> verdict bytes (one 0/1 byte per block)
PyObject* Engine_finish(EngineObject* self, PyObject* digests_obj) {
  if (!self->have_batch) {
    PyErr_SetString(PyExc_RuntimeError, "finish() without a scanned batch");
    return nullptr;
  }
  if (batch_commit(self, self->batch, digests_obj) < 0) return nullptr;
  return verdict_and_clear(self);
}

// --- pipelined protocol ----------------------------------------------------

extern PyTypeObject BatchType;

struct BatchObject {
  PyObject_HEAD
  EngineObject* owner;  // strong ref: a live batch pins its engine
  BatchState* bs;
  int finished;
};

void Batch_dealloc(BatchObject* self) {
  if (self->bs) {
    batch_clear(self->bs);
    delete self->bs;
  }
  Py_CLEAR(self->owner);
  Py_TYPE(self)->tp_free(reinterpret_cast<PyObject*>(self));
}

PyObject* Batch_n_novel(BatchObject* self, PyObject*) {
  return PyLong_FromUnsignedLongLong(self->bs ? self->bs->n_novel : 0);
}

PyMethodDef Batch_methods[] = {
    {"n_novel", reinterpret_cast<PyCFunction>(Batch_n_novel), METH_NOARGS,
     "novel first occurrences in this batch"},
    {nullptr, nullptr, 0, nullptr},
};

// scan_begin(witnesses) -> (Batch, novel, miss, total). Unlike scan(),
// the batch state lives in the returned Batch object, so any number of
// scanned batches can be outstanding (pipelining). Batches may finish in
// ANY order — the tables are append-only, rows encode their own novel
// indices, and a node novel in two outstanding batches commits a benign
// duplicate row whichever lands first.
PyObject* Engine_scan_begin(EngineObject* self, PyObject* witnesses) {
  BatchObject* batch = PyObject_New(BatchObject, &BatchType);
  if (!batch) return nullptr;
  Py_INCREF(self);
  batch->owner = self;
  batch->bs = new BatchState();
  batch->finished = 0;
  PyObject* scanned = scan_into(self, witnesses, batch->bs);
  if (!scanned) {
    Py_DECREF(batch);
    return nullptr;
  }
  // (novel, miss, total) -> (Batch, novel, miss, total)
  PyObject* ret = PyTuple_New(4);
  if (!ret) {
    Py_DECREF(batch);
    Py_DECREF(scanned);
    return nullptr;
  }
  PyTuple_SET_ITEM(ret, 0, reinterpret_cast<PyObject*>(batch));
  for (int i = 0; i < 3; ++i) {
    PyObject* item = PyTuple_GET_ITEM(scanned, i);
    Py_INCREF(item);
    PyTuple_SET_ITEM(ret, i + 1, item);
  }
  Py_DECREF(scanned);
  return ret;
}

BatchObject* checked_batch(EngineObject* self, PyObject* arg) {
  if (!PyObject_TypeCheck(arg, &BatchType)) {
    PyErr_SetString(PyExc_TypeError, "expected a Batch from scan_begin()");
    return nullptr;
  }
  BatchObject* batch = reinterpret_cast<BatchObject*>(arg);
  if (batch->owner != self) {
    PyErr_SetString(PyExc_ValueError, "batch belongs to a different engine");
    return nullptr;
  }
  if (batch->finished) {
    PyErr_SetString(PyExc_RuntimeError, "batch already finished");
    return nullptr;
  }
  return batch;
}

PyObject* batch_finish_tail(BatchObject* batch, PyObject* out) {
  batch->finished = 1;
  batch_clear(batch->bs);  // release the pinned witnesses promptly
  return out;
}

// hash_batch(batch): keccak the batch's novel nodes into batch-local
// digest storage — touches NO engine table, so callers run it WITHOUT
// the engine lock (GIL released too): the resolve worker hashes batch N
// here while the executor's scan_begin(N+1) probes the tables under the
// lock. finish_batch(batch, None) then commits with the stored digests.
PyObject* Engine_hash_batch(EngineObject* self, PyObject* arg) {
  BatchObject* batch = checked_batch(self, arg);
  if (!batch) return nullptr;
  BatchState* bs = batch->bs;
  if (bs->n_novel) {
    bs->digests.resize(32 * bs->n_novel);
    // batch-local ptr/len scratch (the Engine's scratch vectors belong
    // to lock-holding calls; this one deliberately runs outside it)
    std::vector<const uint8_t*> nptrs(bs->n_novel);
    std::vector<uint32_t> nlens(bs->n_novel);
    for (uint64_t k = 0; k < bs->n_novel; ++k) {
      nptrs[k] = bs->ptrs[bs->novel_idx[k]];
      nlens[k] = bs->lens[bs->novel_idx[k]];
    }
    {
      Unlocked unlocked(kSiteHash);
      phant_keccak256_ptrs_fast(nptrs.data(), nlens.data(), bs->n_novel,
                                bs->digests.data());
    }
  }
  Py_RETURN_NONE;
}

// finish_batch(batch, digests_or_None) -> verdict bytes. None is valid
// when the batch had no novel nodes OR hash_batch() already filled the
// batch-local digests.
PyObject* Engine_finish_batch(EngineObject* self, PyObject* args) {
  PyObject* batch_obj;
  PyObject* digests_obj;
  if (!PyArg_ParseTuple(args, "OO", &batch_obj, &digests_obj)) return nullptr;
  BatchObject* batch = checked_batch(self, batch_obj);
  if (!batch) return nullptr;
  BatchState* bs = batch->bs;
  if (digests_obj == Py_None && bs->n_novel &&
      bs->digests.size() == 32 * bs->n_novel) {
    {
      Unlocked unlocked(kSiteFinishCommit);
      phant_engine_commit_ptrs(self->eng, bs->ptrs.data(), bs->lens.data(),
                               bs->ptrs.size(), bs->rows.data(),
                               bs->novel_idx.data(), bs->n_novel,
                               bs->digests.data());
    }
  } else if (batch_commit(self, bs, digests_obj) < 0) {
    return nullptr;
  }
  return batch_finish_tail(batch, batch_verdict(self, batch->bs));
}

// finish_batch_native(batch) -> verdict bytes (in-C keccak of the novels)
PyObject* Engine_finish_batch_native(EngineObject* self, PyObject* arg) {
  BatchObject* batch = checked_batch(self, arg);
  if (!batch) return nullptr;
  batch_commit_native(self, batch->bs);
  return batch_finish_tail(batch, batch_verdict(self, batch->bs));
}

PyObject* Engine_flush(EngineObject* self, PyObject*) {
  clear_batch(self);
  phant_engine_flush(self->eng);
  Py_RETURN_NONE;
}

PyObject* Engine_nodes(EngineObject* self, PyObject*) {
  return PyLong_FromUnsignedLongLong(phant_engine_nodes(self->eng));
}

PyObject* Engine_digests(EngineObject* self, PyObject*) {
  return PyLong_FromUnsignedLongLong(phant_engine_digests(self->eng));
}

PyMethodDef Engine_methods[] = {
    {"scan", reinterpret_cast<PyCFunction>(Engine_scan), METH_O,
     "scan(witnesses) -> (novel, miss, total)"},
    {"finish", reinterpret_cast<PyCFunction>(Engine_finish), METH_O,
     "finish(digests|None) -> verdict bytes"},
    {"finish_native", reinterpret_cast<PyCFunction>(Engine_finish_native),
     METH_NOARGS, "finish with in-C keccak of the novel nodes"},
    {"scan_begin", reinterpret_cast<PyCFunction>(Engine_scan_begin), METH_O,
     "scan_begin(witnesses) -> (Batch, novel, miss, total)"},
    {"hash_batch", reinterpret_cast<PyCFunction>(Engine_hash_batch), METH_O,
     "keccak the batch's novel nodes into batch-local digests (no "
     "engine-table access: safe without the engine lock)"},
    {"finish_batch", reinterpret_cast<PyCFunction>(Engine_finish_batch),
     METH_VARARGS, "finish_batch(batch, digests|None) -> verdict bytes"},
    {"finish_batch_native",
     reinterpret_cast<PyCFunction>(Engine_finish_batch_native), METH_O,
     "finish_batch(batch) with in-C keccak of the novel nodes"},
    {"flush", reinterpret_cast<PyCFunction>(Engine_flush), METH_NOARGS,
     "drop the interned generation"},
    {"nodes", reinterpret_cast<PyCFunction>(Engine_nodes), METH_NOARGS,
     "interned node count"},
    {"digests", reinterpret_cast<PyCFunction>(Engine_digests), METH_NOARGS,
     "interned digest count"},
    {nullptr, nullptr, 0, nullptr},
};

PyTypeObject EngineType = {
    PyVarObject_HEAD_INIT(nullptr, 0)
    "phant_engine_ext.Engine",           /* tp_name */
    sizeof(EngineObject),                /* tp_basicsize */
};

PyTypeObject BatchType = {
    PyVarObject_HEAD_INIT(nullptr, 0)
    "phant_engine_ext.Batch",            /* tp_name */
    sizeof(BatchObject),                 /* tp_basicsize */
};


// --- the trie-node encoder ---------------------------------------------------
//
// One RLP writer. An item is bytes (bytearray and memoryview too), an int
// (its minimal big-endian bytes, as rlp.encode has it), a list or tuple
// of items and, in the top list of a template alone, a hole (32 zero
// bytes under the header 0xA0) or a value hole (prefix + 32 zero bytes +
// suffix as ONE string item). Anything else is a TypeError, as it is in
// phant_tpu/rlp.py, the oracle this is tested against byte for byte.

// A list's header goes in front of a payload whose length is known only
// once it is written: every list leaves kHeaderRoom bytes free, writes
// its payload behind them and moves it up against the header.
constexpr size_t kHeaderRoom = 9;
constexpr int kMaxDepth = 200;  // of nested lists; Python's own limit is lower
constexpr int kMaxTrieDepth = 1024;  // of a walk: no key has as many digits

struct Out {
  uint8_t* data = nullptr;
  size_t len = 0;
  size_t cap = 0;
  ~Out() { std::free(data); }

  uint8_t* grow(size_t n) {  // n more bytes at the end, or nullptr
    if (len + n > cap) {
      size_t want = cap ? cap * 2 : 1024;
      while (want < len + n) want *= 2;
      uint8_t* p = static_cast<uint8_t*>(std::realloc(data, want));
      if (!p) {
        PyErr_NoMemory();
        return nullptr;
      }
      data = p;
      cap = want;
    }
    uint8_t* at = data + len;
    len += n;
    return at;
  }
};

// Header of a string (base 0x80) or list (base 0xC0) of `n` payload
// bytes, written to `to` (room for kHeaderRoom); returns its length.
size_t write_header(uint8_t* to, size_t n, uint8_t base) {
  if (n <= 55) {
    to[0] = static_cast<uint8_t>(base + n);
    return 1;
  }
  size_t ll = 0;
  for (size_t v = n; v; v >>= 8) ++ll;
  to[0] = static_cast<uint8_t>(base + 55 + ll);
  for (size_t i = 0; i < ll; ++i)
    to[1 + i] = static_cast<uint8_t>(n >> (8 * (ll - 1 - i)));
  return 1 + ll;
}

bool put_string(Out* out, const uint8_t* p, size_t n) {
  if (n == 1 && p[0] < 0x80) {
    uint8_t* at = out->grow(1);
    if (!at) return false;
    at[0] = p[0];
    return true;
  }
  uint8_t head[kHeaderRoom];
  const size_t h = write_header(head, n, 0x80);
  uint8_t* at = out->grow(h + n);
  if (!at) return false;
  std::memcpy(at, head, h);
  if (n) std::memcpy(at + h, p, n);
  return true;
}

// A bytes object as a string item; `raw` may be null (an error is set).
bool put_bytes(Out* out, PyObject* raw) {
  return raw &&
         put_string(out, reinterpret_cast<uint8_t*>(PyBytes_AS_STRING(raw)),
                    static_cast<size_t>(PyBytes_GET_SIZE(raw)));
}

struct Holes {
  PyObject* hole;        // identity marks a hole; nullptr: none known
  PyObject* value_hole;  // exact type of a value hole; nullptr: none
  std::vector<size_t> at;  // byte offset of each hole in `Out`
};

bool put_int(Out* out, PyObject* item) {
  const unsigned long long v = PyLong_AsUnsignedLongLong(item);
  if (v == static_cast<unsigned long long>(-1) && PyErr_Occurred()) {
    if (!PyErr_ExceptionMatches(PyExc_OverflowError)) return false;
    PyErr_Clear();
    PyObject* zero = PyLong_FromLong(0);
    if (!zero) return false;
    const int neg = PyObject_RichCompareBool(item, zero, Py_LT);
    Py_DECREF(zero);
    if (neg < 0) return false;
    if (neg) {
      PyErr_SetString(PyExc_ValueError, "cannot RLP-encode negative integer");
      return false;
    }
    // wider than 64 bits: Python lays the bytes out
    PyObject* bits = PyObject_CallMethod(item, "bit_length", nullptr);
    if (!bits) return false;
    const size_t nbytes = (PyLong_AsSize_t(bits) + 7) / 8;
    Py_DECREF(bits);
    PyObject* raw = PyObject_CallMethod(item, "to_bytes", "ns",
                                        static_cast<Py_ssize_t>(nbytes), "big");
    const bool ok = put_bytes(out, raw);
    Py_XDECREF(raw);
    return ok;
  }
  uint8_t be[8];
  size_t n = 0;
  for (unsigned long long w = v; w; w >>= 8) ++n;
  for (size_t i = 0; i < n; ++i)
    be[i] = static_cast<uint8_t>(v >> (8 * (n - 1 - i)));
  return put_string(out, be, n);
}

// prefix + 32 zero bytes + suffix as ONE string item; the hole is the zeros.
bool put_value_hole(Out* out, PyObject* item, Holes* holes) {
  PyObject* prefix = PyObject_GetAttrString(item, "prefix");
  PyObject* suffix = prefix ? PyObject_GetAttrString(item, "suffix") : nullptr;
  bool ok = false;
  if (suffix && !(PyBytes_Check(prefix) && PyBytes_Check(suffix))) {
    PyErr_SetString(PyExc_TypeError, "a value hole's prefix and suffix are bytes");
  } else if (suffix) {
    const size_t np = static_cast<size_t>(PyBytes_GET_SIZE(prefix));
    const size_t ns = static_cast<size_t>(PyBytes_GET_SIZE(suffix));
    uint8_t head[kHeaderRoom];
    const size_t h = write_header(head, np + 32 + ns, 0x80);
    uint8_t* at = out->grow(h + np + 32 + ns);
    if (at) {
      std::memcpy(at, head, h);
      std::memcpy(at + h, PyBytes_AS_STRING(prefix), np);
      std::memset(at + h + np, 0, 32);
      std::memcpy(at + h + np + 32, PyBytes_AS_STRING(suffix), ns);
      holes->at.push_back(out->len - ns - 32);
      ok = true;
    }
  }
  Py_XDECREF(prefix);
  Py_XDECREF(suffix);
  return ok;
}

bool put_item(Out* out, PyObject* item, Holes* holes, int depth);

constexpr size_t kFailed = static_cast<size_t>(-1);

// The items of `seq` (a list or tuple) as one RLP list, written behind
// kHeaderRoom free bytes with the header up against the payload: returns
// where the list's encoding starts, or kFailed. `holes` is for the items
// of a template's top list.
size_t put_list(Out* out, PyObject* seq, Holes* holes, int depth) {
  if (depth > kMaxDepth) {
    PyErr_SetString(PyExc_RecursionError, "RLP nesting too deep");
    return kFailed;
  }
  const size_t start = out->len;
  if (!out->grow(kHeaderRoom)) return kFailed;
  // an item's encoding can run Python code (an int subclass's to_bytes,
  // a buffer's bytes()), so the size is read anew and the item is held
  for (Py_ssize_t i = 0; i < PySequence_Fast_GET_SIZE(seq); ++i) {
    PyObject* item = PySequence_Fast_GET_ITEM(seq, i);
    Py_INCREF(item);
    const bool ok = put_item(out, item, holes, depth + 1);
    Py_DECREF(item);
    if (!ok) return kFailed;
  }
  uint8_t head[kHeaderRoom];
  const size_t h = write_header(head, out->len - start - kHeaderRoom, 0xC0);
  const size_t from = start + kHeaderRoom - h;
  std::memcpy(out->data + from, head, h);
  return from;
}

bool put_item(Out* out, PyObject* item, Holes* holes, int depth) {
  if (PyBytes_Check(item)) return put_bytes(out, item);
  if (PyList_Check(item) || PyTuple_Check(item)) {
    // a nested list closes the gap its header room left
    const size_t start = out->len;
    const size_t from = put_list(out, item, nullptr, depth);
    if (from == kFailed) return false;
    std::memmove(out->data + start, out->data + from, out->len - from);
    out->len -= from - start;
    return true;
  }
  if (holes && item == holes->hole) {
    uint8_t* at = out->grow(33);
    if (!at) return false;
    at[0] = 0xA0;
    std::memset(at + 1, 0, 32);
    holes->at.push_back(out->len - 32);
    return true;
  }
  if (holes && reinterpret_cast<PyObject*>(Py_TYPE(item)) == holes->value_hole)
    return put_value_hole(out, item, holes);
  if (PyLong_Check(item)) return put_int(out, item);
  if (PyByteArray_Check(item) || PyMemoryView_Check(item)) {
    PyObject* raw = PyBytes_FromObject(item);
    const bool ok = put_bytes(out, raw);
    Py_XDECREF(raw);
    return ok;
  }
  PyErr_Format(PyExc_TypeError, "cannot RLP-encode %.200s",
               Py_TYPE(item)->tp_name);
  return false;
}

// Where the encoding of the top item lies in `out`.
struct Span {
  size_t start;
  size_t len;
};

bool encode_top(Out* out, PyObject* item, Holes* holes, Span* span) {
  if (PyList_Check(item) || PyTuple_Check(item)) {
    span->start = put_list(out, item, holes, 0);
    if (span->start == kFailed) return false;
  } else {
    span->start = 0;
    if (!put_item(out, item, nullptr, 0)) return false;
  }
  span->len = out->len - span->start;
  return true;
}

// rlp_encode(item) -> bytes: phant_tpu.rlp.encode's bytes.
PyObject* ext_rlp_encode(PyObject*, PyObject* item) {
  Out out;
  Span span;
  if (!encode_top(&out, item, nullptr, &span)) return nullptr;
  return PyBytes_FromStringAndSize(
      reinterpret_cast<char*>(out.data + span.start),
      static_cast<Py_ssize_t>(span.len));
}

// encode_node(hole, value_hole_type, items) -> (bytes, [hole offsets]):
// the RLP list of `items` with every hole zeroed, and where each hole's
// 32 bytes start, in the order met. The items come last so that a caller
// binds its two sentinels once (functools.partial).
PyObject* ext_encode_node(PyObject*, PyObject* const* args, Py_ssize_t nargs) {
  if (nargs != 3) {
    PyErr_SetString(PyExc_TypeError,
                    "encode_node(hole, value_hole_type, items)");
    return nullptr;
  }
  PyObject* seq = PySequence_Fast(args[2], "a node's items are a sequence");
  if (!seq) return nullptr;
  Holes holes{args[0] == Py_None ? nullptr : args[0],
              args[1] == Py_None ? nullptr : args[1],
              {}};
  Out out;
  Span span;
  PyObject* ret = nullptr;
  if (encode_top(&out, seq, &holes, &span)) {
    PyObject* offs = PyList_New(static_cast<Py_ssize_t>(holes.at.size()));
    if (offs) {
      for (size_t i = 0; i < holes.at.size(); ++i) {
        PyObject* v = PyLong_FromSize_t(holes.at[i] - span.start);
        if (!v) {
          Py_CLEAR(offs);
          break;
        }
        PyList_SET_ITEM(offs, static_cast<Py_ssize_t>(i), v);
      }
    }
    if (offs) {
      PyObject* enc = PyBytes_FromStringAndSize(
          reinterpret_cast<char*>(out.data + span.start),
          static_cast<Py_ssize_t>(span.len));
      if (enc) ret = PyTuple_Pack(2, enc, offs);
      Py_XDECREF(enc);
      Py_DECREF(offs);
    }
  }
  Py_DECREF(seq);
  return ret;
}

// Yellow-paper hex-prefix of a sequence of nibbles (mpt.encode_hex_prefix).
PyObject* hex_prefix(PyObject* path, bool is_leaf) {
  PyObject* seq = PySequence_Fast(path, "a node's path is a sequence of nibbles");
  if (!seq) return nullptr;
  const Py_ssize_t n = PySequence_Fast_GET_SIZE(seq);
  PyObject* raw = PyBytes_FromStringAndSize(nullptr, 1 + n / 2);
  if (!raw) {
    Py_DECREF(seq);
    return nullptr;
  }
  uint8_t* to = reinterpret_cast<uint8_t*>(PyBytes_AS_STRING(raw));
  std::memset(to, 0, static_cast<size_t>(1 + n / 2));
  to[0] = is_leaf ? 0x20 : 0x00;
  // an odd path's first nibble shares the flag's byte; the rest pair up
  Py_ssize_t at = 1;  // index of the next half byte of `to`, in nibbles
  if (n % 2) to[0] |= 0x10; else at = 2;
  for (Py_ssize_t i = 0; i < n; ++i, ++at) {
    const long v = PyLong_AsLong(PySequence_Fast_GET_ITEM(seq, i));
    if (v < 0 || v > 15) {
      if (!PyErr_Occurred())
        PyErr_SetString(PyExc_ValueError, "a nibble lies in 0..15");
      Py_DECREF(seq);
      Py_DECREF(raw);
      return nullptr;
    }
    to[at / 2] |= static_cast<uint8_t>(at % 2 ? v : v << 4);
  }
  Py_DECREF(seq);
  return raw;
}

// hex_prefix(nibbles, is_leaf) -> bytes
PyObject* ext_hex_prefix(PyObject*, PyObject* const* args, Py_ssize_t nargs) {
  if (nargs != 2) {
    PyErr_SetString(PyExc_TypeError, "hex_prefix(nibbles, is_leaf)");
    return nullptr;
  }
  const int leaf = PyObject_IsTrue(args[1]);
  if (leaf < 0) return nullptr;
  return hex_prefix(args[0], leaf != 0);
}

// The host walk of a trie: every node below `node` that `cache` does not
// hold is encoded, children first, and its (structure, encoding,
// reference) kept in `cache` under id(node), Trie._enc_cache's contract.
// The reference is what the node's parent holds of it: its structure
// where its encoding is shorter than `embed_below` bytes, else the
// keccak-256 of the encoding, computed once, where the entry is built
// (`hashes` tallies those); a clean child of a dirty parent is read from
// its entry and never hashed again. A node of none of the three kinds
// enters as its `.digest` (an unwitnessed subtree), never encoded.
struct Walk {
  PyObject* cache;
  Py_ssize_t hashes;  // digests this walk computed
  PyObject* path_enc;  // callable(path, is_leaf), or nullptr: hex-prefix
  Py_ssize_t embed_below;
  PyTypeObject* leaf;
  PyTypeObject* extension;
  PyTypeObject* branch;
  PyObject* empty;  // b""
};

PyObject* walk_node(Walk* w, PyObject* node, int depth);

PyObject* walk_path(Walk* w, PyObject* node, bool is_leaf) {
  PyObject* path = PyObject_GetAttrString(node, "path");
  if (!path) return nullptr;
  PyObject* enc =
      w->path_enc
          ? PyObject_CallFunctionObjArgs(w->path_enc, path,
                                         is_leaf ? Py_True : Py_False, nullptr)
          : hex_prefix(path, is_leaf);
  Py_DECREF(path);
  return enc;
}

// What a parent holds of `child`: a new reference.
PyObject* walk_ref(Walk* w, PyObject* child, int depth) {
  PyTypeObject* t = Py_TYPE(child);
  if (t != w->leaf && t != w->extension && t != w->branch) {
    PyObject* digest = PyObject_GetAttrString(child, "digest");
    if (!digest && PyErr_ExceptionMatches(PyExc_AttributeError)) {
      PyErr_Clear();
      PyErr_Format(PyExc_TypeError, "cannot encode a trie node of type %.200s",
                   t->tp_name);
    }
    return digest;
  }
  PyObject* entry = walk_node(w, child, depth);
  if (!entry) return nullptr;
  PyObject* ref = PyTuple_GET_ITEM(entry, 2);
  Py_INCREF(ref);
  Py_DECREF(entry);
  return ref;
}

// The node's items as a new list, its children's references resolved.
PyObject* walk_structure(Walk* w, PyObject* node, int depth) {
  PyTypeObject* t = Py_TYPE(node);
  if (t == w->branch) {
    PyObject* children = PyObject_GetAttrString(node, "children");
    if (!children) return nullptr;
    PyObject* seq = PySequence_Fast(children, "a branch's children are a sequence");
    Py_DECREF(children);
    if (!seq) return nullptr;
    const Py_ssize_t n = PySequence_Fast_GET_SIZE(seq);
    PyObject* items = PyList_New(n + 1);
    for (Py_ssize_t i = 0; items && i < n; ++i) {
      PyObject* child = PySequence_Fast_GET_ITEM(seq, i);
      PyObject* ref;
      if (child == Py_None) {
        ref = w->empty;
        Py_INCREF(ref);
      } else {
        ref = walk_ref(w, child, depth);
      }
      if (!ref) Py_CLEAR(items); else PyList_SET_ITEM(items, i, ref);
    }
    Py_DECREF(seq);
    if (!items) return nullptr;
    PyObject* value = PyObject_GetAttrString(node, "value");
    if (!value) {
      Py_DECREF(items);
      return nullptr;
    }
    if (value == Py_None) {
      Py_DECREF(value);
      value = w->empty;
      Py_INCREF(value);
    }
    PyList_SET_ITEM(items, n, value);
    return items;
  }
  const bool is_leaf = t == w->leaf;
  PyObject* path = walk_path(w, node, is_leaf);
  if (!path) return nullptr;
  PyObject* second;
  if (is_leaf) {
    second = PyObject_GetAttrString(node, "value");
  } else {
    PyObject* child = PyObject_GetAttrString(node, "child");
    second = child ? walk_ref(w, child, depth) : nullptr;
    Py_XDECREF(child);
  }
  PyObject* items = second ? PyList_New(2) : nullptr;
  if (!items) {
    Py_DECREF(path);
    Py_XDECREF(second);
    return nullptr;
  }
  PyList_SET_ITEM(items, 0, path);
  PyList_SET_ITEM(items, 1, second);
  return items;
}

// What the parent of a node with this structure and encoding holds of
// it (a new reference): the one place of the walk that hashes.
PyObject* walk_reference(Walk* w, PyObject* structure, PyObject* enc) {
  if (PyBytes_GET_SIZE(enc) < w->embed_below) {
    Py_INCREF(structure);
    return structure;
  }
  PyObject* ref = PyBytes_FromStringAndSize(nullptr, 32);
  if (!ref) return nullptr;
  phant_keccak256(reinterpret_cast<uint8_t*>(PyBytes_AS_STRING(enc)),
                  static_cast<size_t>(PyBytes_GET_SIZE(enc)),
                  reinterpret_cast<uint8_t*>(PyBytes_AS_STRING(ref)));
  ++w->hashes;
  return ref;
}

// (structure, encoding, reference) of a leaf, extension or branch: a new
// reference to the tuple the cache holds.
PyObject* walk_node(Walk* w, PyObject* node, int depth) {
  if (depth > kMaxTrieDepth) {
    PyErr_SetString(PyExc_RecursionError, "trie deeper than any key");
    return nullptr;
  }
  PyObject* key = PyLong_FromVoidPtr(node);
  if (!key) return nullptr;
  PyObject* entry = PyDict_GetItemWithError(w->cache, key);  // borrowed
  if (entry) {
    Py_DECREF(key);
    if (!PyTuple_CheckExact(entry) || PyTuple_GET_SIZE(entry) != 3) {
      PyErr_SetString(PyExc_TypeError,
                      "a memo entry is (structure, encoding, reference)");
      return nullptr;
    }
    Py_INCREF(entry);
    return entry;
  }
  PyObject* structure = PyErr_Occurred() ? nullptr : walk_structure(w, node, depth + 1);
  PyObject* enc = structure ? ext_rlp_encode(nullptr, structure) : nullptr;
  PyObject* ref = enc ? walk_reference(w, structure, enc) : nullptr;
  entry = ref ? PyTuple_Pack(3, structure, enc, ref) : nullptr;
  Py_XDECREF(structure);
  Py_XDECREF(enc);
  Py_XDECREF(ref);
  if (entry && PyDict_SetItem(w->cache, key, entry) < 0) Py_CLEAR(entry);
  Py_DECREF(key);
  return entry;
}

// encode_subtree(node, cache, path_enc, embed_below, (Leaf, Extension,
// Branch)) -> ((structure, encoding, reference) of `node`, digests
// computed), the cache filled below it
PyObject* ext_encode_subtree(PyObject*, PyObject* const* args, Py_ssize_t nargs) {
  if (nargs != 5 || !PyDict_Check(args[1]) || !PyTuple_Check(args[4]) ||
      PyTuple_GET_SIZE(args[4]) != 3) {
    PyErr_SetString(PyExc_TypeError,
                    "encode_subtree(node, cache: dict, path_enc, embed_below, "
                    "(Leaf, Extension, Branch))");
    return nullptr;
  }
  Walk w;
  w.cache = args[1];
  w.hashes = 0;
  w.path_enc = args[2] == Py_None ? nullptr : args[2];
  w.embed_below = PyLong_AsSsize_t(args[3]);
  if (w.embed_below == -1 && PyErr_Occurred()) return nullptr;
  w.leaf = reinterpret_cast<PyTypeObject*>(PyTuple_GET_ITEM(args[4], 0));
  w.extension = reinterpret_cast<PyTypeObject*>(PyTuple_GET_ITEM(args[4], 1));
  w.branch = reinterpret_cast<PyTypeObject*>(PyTuple_GET_ITEM(args[4], 2));
  PyTypeObject* t = Py_TYPE(args[0]);
  if (t != w.leaf && t != w.extension && t != w.branch) {
    PyErr_Format(PyExc_TypeError, "cannot encode a trie node of type %.200s",
                 t->tp_name);
    return nullptr;
  }
  w.empty = PyBytes_FromStringAndSize("", 0);
  if (!w.empty) return nullptr;
  PyObject* entry = walk_node(&w, args[0], 0);
  Py_DECREF(w.empty);
  return entry ? Py_BuildValue("(Nn)", entry, w.hashes) : nullptr;
}

// --- the EVM's host binding --------------------------------------------------
//
// native/evm.cc runs one frame of bytecode against a vtable of host
// callbacks (native/evm.h). EvmHost IS that host, over a Python StateDB:
// the callbacks turn the VM's 20- and 32-byte arguments into bytes and
// ints, call the state object's methods by interned name and write the
// answers into the VM's buffers. One EvmHost serves a block: what the
// block fixes (state, coinbase, number, ...) is set when it is built,
// what a transaction fixes (origin, gas price, blob hashes, the Evm whose
// _nested_call/_nested_create the `call` callback re-enters, its tracer)
// at every outermost execute(). Nested frames come back in through
// execute() at any depth. The interpreter lock is held from a frame's
// entry to its return: every callback needs it.
//
// A Python error in a callback cannot unwind through the VM's frames: it
// is parked in `pending`, every later callback answers a default without
// touching Python, and execute() raises it once the VM has returned (at
// every depth: an inner frame's raise travels through the Python frames
// between and is parked again by the outer `call` callback).

struct EvmHostObject {
  PyObject_HEAD
  PhantHost host;
  PhantTxContext txc;
  PyObject* state;
  PyObject* block_hash_fn;  // number -> 32 bytes, or None
  // the host side's Python helpers, for the callbacks that are more than
  // one StateDB method (phant_tpu/evm/native_vm.py hands them over)
  PyObject* visible_code;       // (evm, addr) -> bytes
  PyObject* visible_code_hash;  // (evm, addr) -> 32 bytes | None
  PyObject* delegate_cost;      // (evm, addr) -> int
  PyObject* selfdestruct;       // (state, addr, beneficiary)
  PyObject* nested;  // (evm, kind, ...) -> (status, gas_left, output, created)
  PyObject* log_type;           // Log(address, topics, data)
  // of the transaction whose frames are on the stack (active > 0)
  PyObject* evm;
  PyObject* tracer;
  PyObject* blob_hashes;  // the joined hashes txc.blob_hashes points into
  // A child's output, alive until the VM has copied it: the VM does so
  // straight after the `call` callback returns, before any other
  // callback can run (native/evm.cc, the CREATE and CALL cases), and the
  // slot is written at that callback's very end, so one slot is enough
  // under any nesting.
  PyObject* last_output;
  PyObject* pending;  // the first exception a callback met
  int active;         // frames of the VM on the stack
  unsigned long long frames;  // frames run since frames_run() last asked
};

struct HostNames {
  PyObject *access_address, *access_storage_key, *get_storage,
      *get_original_storage, *set_storage, *get_balance, *is_empty, *add_log,
      *add_refund, *get_transient, *set_transient, *env, *origin, *gas_price,
      *blob_hashes, *blob_base_fee, *tracer, *join;
};
HostNames names;

bool intern_host_names() {
  struct {
    PyObject** slot;
    const char* text;
  } all[] = {
      {&names.access_address, "access_address"},
      {&names.access_storage_key, "access_storage_key"},
      {&names.get_storage, "get_storage"},
      {&names.get_original_storage, "get_original_storage"},
      {&names.set_storage, "set_storage"},
      {&names.get_balance, "get_balance"},
      {&names.is_empty, "is_empty"},
      {&names.add_log, "add_log"},
      {&names.add_refund, "add_refund"},
      {&names.get_transient, "get_transient"},
      {&names.set_transient, "set_transient"},
      {&names.env, "env"},
      {&names.origin, "origin"},
      {&names.gas_price, "gas_price"},
      {&names.blob_hashes, "blob_hashes"},
      {&names.blob_base_fee, "blob_base_fee"},
      {&names.tracer, "tracer"},
      {&names.join, "join"},
  };
  for (auto& n : all) {
    *n.slot = PyUnicode_InternFromString(n.text);
    if (!*n.slot) return false;
  }
  return true;
}

inline EvmHostObject* host_of(void* ctx) {
  return static_cast<EvmHostObject*>(ctx);
}

#if PY_VERSION_HEX < 0x030C0000
PyObject* PyErr_GetRaisedException() {
  PyObject *type, *value, *tb;
  PyErr_Fetch(&type, &value, &tb);
  PyErr_NormalizeException(&type, &value, &tb);
  if (tb) PyException_SetTraceback(value, tb);
  Py_XDECREF(type);
  Py_XDECREF(tb);
  return value;
}

void PyErr_SetRaisedException(PyObject* exc) {
  PyErr_SetObject(reinterpret_cast<PyObject*>(Py_TYPE(exc)), exc);
  Py_DECREF(exc);
}
#endif

// Park the raised exception (the first one wins) and clear the indicator.
void host_park(EvmHostObject* h) {
  PyObject* exc = PyErr_GetRaisedException();
  if (h->pending)
    Py_XDECREF(exc);
  else
    h->pending = exc;
}

inline const char* chars(const uint8_t* p) {
  return reinterpret_cast<const char*>(p);
}

inline PyObject* addr_bytes(const uint8_t* addr) {
  return PyBytes_FromStringAndSize(chars(addr), 20);
}

inline PyObject* word_int(const uint8_t* word) {
  return _PyLong_FromByteArray(word, 32, /*little_endian=*/0, /*signed=*/0);
}

// A Python int into a 32-byte big-endian word; -1 with an error set where
// it is no int, negative or wider.
int int_word(PyObject* value, uint8_t out[32]) {
  if (!PyLong_Check(value)) {
    PyErr_SetString(PyExc_TypeError, "the EVM host wants an int");
    return -1;
  }
#if PY_VERSION_HEX >= 0x030D0000
  return _PyLong_AsByteArray(reinterpret_cast<PyLongObject*>(value), out, 32,
                             0, 0, 1);
#else
  return _PyLong_AsByteArray(reinterpret_cast<PyLongObject*>(value), out, 32,
                             0, 0);
#endif
}

// target.name(args...), or target(args...) where name is null, with every
// argument's reference taken over; a null argument (an allocation that
// failed) or a raising call is parked and gives null. With an exception
// parked already nothing of Python runs: null at once.
PyObject* host_call(EvmHostObject* h, PyObject* target, PyObject* name,
                    std::initializer_list<PyObject*> owned) {
  PyObject* args[4] = {target};
  size_t n = 1;
  bool whole = !h->pending;
  for (PyObject* a : owned) {
    args[n++] = a;
    whole = whole && a;
  }
  PyObject* r = nullptr;
  if (whole)
    r = name ? PyObject_VectorcallMethod(
                   name, args, n | PY_VECTORCALL_ARGUMENTS_OFFSET, nullptr)
             : PyObject_Vectorcall(
                   target, args + 1,
                   (n - 1) | PY_VECTORCALL_ARGUMENTS_OFFSET, nullptr);
  for (PyObject* a : owned) Py_XDECREF(a);
  if (!r && !h->pending) host_park(h);
  return r;
}

// The answer as the VM's int32 flag; 0 where the call was parked.
int32_t host_flag(EvmHostObject* h, PyObject* r) {
  if (!r) return 0;
  const int v = PyObject_IsTrue(r);
  Py_DECREF(r);
  if (v < 0) {
    host_park(h);
    return 0;
  }
  return v;
}

// The answer as a 32-byte word into `out`; zeros where the call was parked.
void host_word(EvmHostObject* h, PyObject* r, uint8_t out[32]) {
  std::memset(out, 0, 32);
  if (!r) return;
  if (int_word(r, out) < 0) {
    host_park(h);
    std::memset(out, 0, 32);
  }
  Py_DECREF(r);
}

int32_t cb_access_account(void* ctx, const uint8_t* addr) {
  EvmHostObject* h = host_of(ctx);
  return host_flag(
      h, host_call(h, h->state, names.access_address, {addr_bytes(addr)}));
}

int32_t cb_access_storage(void* ctx, const uint8_t* addr, const uint8_t* key) {
  EvmHostObject* h = host_of(ctx);
  return host_flag(h, host_call(h, h->state, names.access_storage_key,
                                {addr_bytes(addr), word_int(key)}));
}

void host_load(void* ctx, PyObject* name, const uint8_t* addr,
               const uint8_t* key, uint8_t* out) {
  EvmHostObject* h = host_of(ctx);
  host_word(h, host_call(h, h->state, name, {addr_bytes(addr), word_int(key)}),
            out);
}

void host_store(void* ctx, PyObject* name, const uint8_t* addr,
                const uint8_t* key, const uint8_t* val) {
  EvmHostObject* h = host_of(ctx);
  Py_XDECREF(host_call(h, h->state, name,
                       {addr_bytes(addr), word_int(key), word_int(val)}));
}

void cb_get_storage(void* ctx, const uint8_t* addr, const uint8_t* key,
                    uint8_t* out) {
  host_load(ctx, names.get_storage, addr, key, out);
}

void cb_get_original_storage(void* ctx, const uint8_t* addr,
                             const uint8_t* key, uint8_t* out) {
  host_load(ctx, names.get_original_storage, addr, key, out);
}

void cb_set_storage(void* ctx, const uint8_t* addr, const uint8_t* key,
                    const uint8_t* val) {
  host_store(ctx, names.set_storage, addr, key, val);
}

void cb_get_transient(void* ctx, const uint8_t* addr, const uint8_t* key,
                      uint8_t* out) {
  host_load(ctx, names.get_transient, addr, key, out);
}

void cb_set_transient(void* ctx, const uint8_t* addr, const uint8_t* key,
                      const uint8_t* val) {
  host_store(ctx, names.set_transient, addr, key, val);
}

void cb_get_balance(void* ctx, const uint8_t* addr, uint8_t* out) {
  EvmHostObject* h = host_of(ctx);
  host_word(h, host_call(h, h->state, names.get_balance, {addr_bytes(addr)}),
            out);
}

int32_t cb_is_empty(void* ctx, const uint8_t* addr) {
  EvmHostObject* h = host_of(ctx);
  return host_flag(h,
                   host_call(h, h->state, names.is_empty, {addr_bytes(addr)}));
}

// helper(evm, addr) as bytes, or null with the failure parked.
PyObject* host_code_bytes(EvmHostObject* h, PyObject* helper,
                          const uint8_t* addr, bool none_ok) {
  Py_INCREF(h->evm);
  PyObject* r = host_call(h, helper, nullptr, {h->evm, addr_bytes(addr)});
  if (!r) return nullptr;
  if (PyBytes_Check(r) || (none_ok && r == Py_None)) return r;
  Py_DECREF(r);
  PyErr_SetString(PyExc_TypeError, "the EVM host wants code as bytes");
  host_park(h);
  return nullptr;
}

uint64_t cb_get_code_size(void* ctx, const uint8_t* addr) {
  EvmHostObject* h = host_of(ctx);
  PyObject* code = host_code_bytes(h, h->visible_code, addr, false);
  if (!code) return 0;
  const uint64_t n = static_cast<uint64_t>(PyBytes_GET_SIZE(code));
  Py_DECREF(code);
  return n;
}

void cb_copy_code(void* ctx, const uint8_t* addr, uint64_t offset,
                  uint8_t* out, uint64_t size) {
  EvmHostObject* h = host_of(ctx);
  PyObject* code = host_code_bytes(h, h->visible_code, addr, false);
  if (!code) return;
  const uint64_t n = static_cast<uint64_t>(PyBytes_GET_SIZE(code));
  if (offset < n) {
    const uint64_t take = size < n - offset ? size : n - offset;
    std::memcpy(out, PyBytes_AS_STRING(code) + offset, take);
  }
  Py_DECREF(code);
}

void cb_get_code_hash(void* ctx, const uint8_t* addr, uint8_t* out) {
  EvmHostObject* h = host_of(ctx);
  std::memset(out, 0, 32);
  PyObject* hash = host_code_bytes(h, h->visible_code_hash, addr, true);
  if (!hash) return;
  if (hash != Py_None && PyBytes_GET_SIZE(hash) == 32)
    std::memcpy(out, PyBytes_AS_STRING(hash), 32);
  Py_DECREF(hash);
}

int64_t cb_delegate_access_cost(void* ctx, const uint8_t* addr) {
  EvmHostObject* h = host_of(ctx);
  Py_INCREF(h->evm);
  PyObject* r =
      host_call(h, h->delegate_cost, nullptr, {h->evm, addr_bytes(addr)});
  if (!r) return 0;
  const long long cost = PyLong_AsLongLong(r);
  Py_DECREF(r);
  if (cost == -1 && PyErr_Occurred()) {
    host_park(h);
    return 0;
  }
  return cost;
}

void cb_get_block_hash(void* ctx, uint64_t number, uint8_t* out) {
  EvmHostObject* h = host_of(ctx);
  std::memset(out, 0, 32);
  if (h->block_hash_fn == Py_None) return;
  PyObject* r = host_call(h, h->block_hash_fn, nullptr,
                          {PyLong_FromUnsignedLongLong(number)});
  if (!r) return;
  if (PyBytes_Check(r) && PyBytes_GET_SIZE(r) == 32) {
    std::memcpy(out, PyBytes_AS_STRING(r), 32);
  } else {
    PyErr_SetString(PyExc_TypeError, "a block hash is 32 bytes");
    host_park(h);
  }
  Py_DECREF(r);
}

void cb_emit_log(void* ctx, const uint8_t* addr, const uint8_t* data,
                 uint64_t len, const uint8_t* topics, int32_t ntopics) {
  EvmHostObject* h = host_of(ctx);
  PyObject* tops = PyTuple_New(ntopics);
  for (int32_t i = 0; tops && i < ntopics; ++i) {
    PyObject* t = PyBytes_FromStringAndSize(chars(topics) + 32 * i, 32);
    if (!t) {
      Py_CLEAR(tops);
      break;
    }
    PyTuple_SET_ITEM(tops, i, t);
  }
  PyObject* payload =
      PyBytes_FromStringAndSize(chars(data), static_cast<Py_ssize_t>(len));
  PyObject* log = host_call(h, h->log_type, nullptr,
                            {addr_bytes(addr), tops, payload});
  if (log) Py_XDECREF(host_call(h, h->state, names.add_log, {log}));
}

void cb_add_refund(void* ctx, int64_t delta) {
  EvmHostObject* h = host_of(ctx);
  Py_XDECREF(
      host_call(h, h->state, names.add_refund, {PyLong_FromLongLong(delta)}));
}

void cb_selfdestruct(void* ctx, const uint8_t* addr,
                     const uint8_t* beneficiary) {
  EvmHostObject* h = host_of(ctx);
  Py_INCREF(h->state);
  Py_XDECREF(host_call(h, h->selfdestruct, nullptr,
                       {h->state, addr_bytes(addr), addr_bytes(beneficiary)}));
}

void cb_trace(void* ctx, uint64_t pc, int32_t op, int64_t gas, int32_t depth,
              int32_t stack_size) {
  EvmHostObject* h = host_of(ctx);
  if (h->pending) return;
  PyObject* r = PyObject_CallFunction(h->tracer, "KiLii",
                                      static_cast<unsigned long long>(pc), op,
                                      static_cast<long long>(gas), depth,
                                      stack_size);
  if (!r) host_park(h);
  Py_XDECREF(r);
}

// A nested CALL*/CREATE*: the Python side builds the Message and re-enters
// Evm._nested_call/_nested_create, which come back into execute() for the
// child's frame. A host-side failure reads as a failed call to the VM and
// is raised by the execute() under it.
void cb_call(void* ctx, const PhantMsg* m, PhantResult* res) {
  EvmHostObject* h = host_of(ctx);
  res->status = 2;
  res->gas_left = 0;
  res->output = nullptr;
  res->output_len = 0;
  std::memset(res->create_address, 0, 20);
  if (h->pending) return;
  PyObject* value = word_int(m->value);
  if (!value) {
    host_park(h);
    return;
  }
  PyObject* r = PyObject_CallFunction(
      h->nested, "OiiiLy#y#y#Ny#y#", h->evm, static_cast<int>(m->kind),
      static_cast<int>(m->is_static), static_cast<int>(m->depth),
      static_cast<long long>(m->gas), chars(m->caller), Py_ssize_t{20},
      chars(m->target), Py_ssize_t{20}, chars(m->code_address), Py_ssize_t{20},
      value, m->data_len ? chars(m->data) : "",
      static_cast<Py_ssize_t>(m->data_len), chars(m->salt), Py_ssize_t{32});
  if (!r) {
    host_park(h);
    return;
  }
  int status;
  long long gas_left;
  PyObject *output, *created;
  if (!PyArg_ParseTuple(r, "iLSO", &status, &gas_left, &output, &created) ||
      (created != Py_None &&
       !(PyBytes_Check(created) && PyBytes_GET_SIZE(created) == 20))) {
    if (!PyErr_Occurred())
      PyErr_SetString(PyExc_TypeError, "a created address is 20 bytes");
    host_park(h);
    Py_DECREF(r);
    return;
  }
  res->status = status;
  res->gas_left = gas_left;
  if (PyBytes_GET_SIZE(output)) {
    res->output = reinterpret_cast<const uint8_t*>(PyBytes_AS_STRING(output));
    res->output_len = static_cast<uint64_t>(PyBytes_GET_SIZE(output));
    Py_INCREF(output);
    Py_XSETREF(h->last_output, output);
  }
  if (created != Py_None)
    std::memcpy(res->create_address, PyBytes_AS_STRING(created), 20);
  Py_DECREF(r);
}

// What a transaction fixes, read off the Evm whose outermost frame enters.
int host_bind_tx(EvmHostObject* h, PyObject* evm) {
  PyObject* env = PyObject_GetAttr(evm, names.env);
  if (!env) return -1;
  PyObject* origin = PyObject_GetAttr(env, names.origin);
  PyObject* gas_price = PyObject_GetAttr(env, names.gas_price);
  PyObject* blob_fee = PyObject_GetAttr(env, names.blob_base_fee);
  PyObject* hashes = PyObject_GetAttr(env, names.blob_hashes);
  PyObject* tracer = PyObject_GetAttr(evm, names.tracer);
  Py_DECREF(env);
  PyObject* joined = nullptr;
  int rc = -1;
  if (origin && gas_price && blob_fee && hashes && tracer) {
    if (!PyBytes_Check(origin) || PyBytes_GET_SIZE(origin) != 20) {
      PyErr_SetString(PyExc_TypeError, "env.origin is 20 bytes");
    } else if (int_word(gas_price, h->txc.gas_price) == 0 &&
               int_word(blob_fee, h->txc.blob_base_fee) == 0) {
      PyObject* empty = PyBytes_FromStringAndSize(nullptr, 0);
      if (empty && PyTuple_CheckExact(hashes) && !PyTuple_GET_SIZE(hashes)) {
        joined = empty;  // every transaction but a blob transaction
      } else if (empty) {
        joined = PyObject_CallMethodOneArg(empty, names.join, hashes);
        Py_DECREF(empty);
      }
      if (joined && (!PyBytes_Check(joined) ||
                     PyBytes_GET_SIZE(joined) % 32 != 0)) {
        PyErr_SetString(PyExc_ValueError, "a blob hash is 32 bytes");
        Py_CLEAR(joined);
      }
      if (joined) rc = 0;
    }
  }
  if (rc == 0) {
    std::memcpy(h->txc.origin, PyBytes_AS_STRING(origin), 20);
    const Py_ssize_t n = PyBytes_GET_SIZE(joined);
    h->txc.blob_hashes =
        n ? reinterpret_cast<const uint8_t*>(PyBytes_AS_STRING(joined))
          : nullptr;
    h->txc.n_blob_hashes = static_cast<uint64_t>(n / 32);
    Py_XSETREF(h->blob_hashes, joined);
    Py_INCREF(evm);
    Py_XSETREF(h->evm, evm);
    if (tracer == Py_None) {
      Py_CLEAR(h->tracer);
      h->host.trace = nullptr;  // the VM's loop pays one branch
    } else {
      Py_INCREF(tracer);
      Py_XSETREF(h->tracer, tracer);
      h->host.trace = cb_trace;
    }
  }
  Py_XDECREF(origin);
  Py_XDECREF(gas_price);
  Py_XDECREF(blob_fee);
  Py_XDECREF(hashes);
  Py_XDECREF(tracer);
  return rc;
}

// The transaction's objects go when its outermost frame has returned: a
// binding at rest refers to nothing that refers back to it.
void host_unbind_tx(EvmHostObject* h) {
  h->txc.blob_hashes = nullptr;
  h->txc.n_blob_hashes = 0;
  h->host.trace = nullptr;
  Py_CLEAR(h->evm);
  Py_CLEAR(h->tracer);
  Py_CLEAR(h->blob_hashes);
  Py_CLEAR(h->last_output);
}

// execute(evm, code, caller, address, value, data, gas, depth, is_static)
//   -> (status, gas_left, output): one frame, 0 success / 1 revert / 2 failure
PyObject* EvmHost_execute(EvmHostObject* self, PyObject* const* args,
                          Py_ssize_t nargs) {
  if (nargs != 9) {
    PyErr_SetString(PyExc_TypeError,
                    "execute(evm, code, caller, address, value, data, gas, "
                    "depth, is_static)");
    return nullptr;
  }
  PyObject *evm = args[0], *code = args[1], *caller = args[2],
           *address = args[3], *data = args[5];
  if (!PyBytes_Check(code) || !PyBytes_Check(data) || !PyBytes_Check(caller) ||
      PyBytes_GET_SIZE(caller) != 20 || !PyBytes_Check(address) ||
      PyBytes_GET_SIZE(address) != 20) {
    PyErr_SetString(PyExc_TypeError,
                    "code and data are bytes, caller and address 20 bytes");
    return nullptr;
  }
  PhantMsg msg;
  std::memset(&msg, 0, sizeof(msg));
  msg.kind = PHANT_CALL;
  if (int_word(args[4], msg.value) < 0) return nullptr;
  msg.gas = PyLong_AsLongLong(args[6]);
  msg.depth = static_cast<int32_t>(PyLong_AsLong(args[7]));
  const int is_static = PyObject_IsTrue(args[8]);
  if (PyErr_Occurred()) return nullptr;
  msg.is_static = is_static;
  std::memcpy(msg.caller, PyBytes_AS_STRING(caller), 20);
  std::memcpy(msg.target, PyBytes_AS_STRING(address), 20);
  msg.data_len = static_cast<uint64_t>(PyBytes_GET_SIZE(data));
  msg.data = msg.data_len
                 ? reinterpret_cast<const uint8_t*>(PyBytes_AS_STRING(data))
                 : nullptr;

  const bool outermost = self->active == 0;
  if (outermost) {
    if (host_bind_tx(self, evm) < 0) return nullptr;
  } else if (evm != self->evm) {
    PyErr_SetString(PyExc_RuntimeError,
                    "the EVM host was re-entered by another Evm");
    return nullptr;
  }
  PhantResult res;
  std::memset(&res, 0, sizeof(res));
  const Py_ssize_t code_len = PyBytes_GET_SIZE(code);
  ++self->active;
  ++self->frames;
  // code and data are the caller's arguments: alive for the whole call
  phant_evm_execute(
      &self->host, &self->txc, &msg,
      code_len ? reinterpret_cast<const uint8_t*>(PyBytes_AS_STRING(code))
               : nullptr,
      static_cast<uint64_t>(code_len), &res);
  --self->active;
  PyObject* output = PyBytes_FromStringAndSize(
      chars(res.output), static_cast<Py_ssize_t>(res.output_len));
  if (res.output) phant_evm_free(res.output);
  PyObject* pending = self->pending;
  self->pending = nullptr;
  if (outermost) host_unbind_tx(self);
  if (pending) {
    Py_XDECREF(output);
    PyErr_SetRaisedException(pending);
    return nullptr;
  }
  if (!output) return nullptr;
  return Py_BuildValue("(iLN)", static_cast<int>(res.status),
                       static_cast<long long>(res.gas_left), output);
}

// frames_run() -> frames of the VM run since the last call (nested too)
PyObject* EvmHost_frames_run(EvmHostObject* self, PyObject*) {
  const unsigned long long n = self->frames;
  self->frames = 0;
  return PyLong_FromUnsignedLongLong(n);
}

int EvmHost_traverse(EvmHostObject* self, visitproc visit, void* arg) {
  Py_VISIT(self->state);
  Py_VISIT(self->block_hash_fn);
  Py_VISIT(self->visible_code);
  Py_VISIT(self->visible_code_hash);
  Py_VISIT(self->delegate_cost);
  Py_VISIT(self->selfdestruct);
  Py_VISIT(self->nested);
  Py_VISIT(self->log_type);
  Py_VISIT(self->evm);
  Py_VISIT(self->tracer);
  Py_VISIT(self->pending);
  return 0;
}

int EvmHost_clear(EvmHostObject* self) {
  Py_CLEAR(self->state);
  Py_CLEAR(self->block_hash_fn);
  Py_CLEAR(self->visible_code);
  Py_CLEAR(self->visible_code_hash);
  Py_CLEAR(self->delegate_cost);
  Py_CLEAR(self->selfdestruct);
  Py_CLEAR(self->nested);
  Py_CLEAR(self->log_type);
  Py_CLEAR(self->pending);
  host_unbind_tx(self);
  return 0;
}

void EvmHost_dealloc(EvmHostObject* self) {
  PyObject_GC_UnTrack(self);
  EvmHost_clear(self);
  Py_TYPE(self)->tp_free(reinterpret_cast<PyObject*>(self));
}

// EvmHost(state, coinbase, block_number, timestamp, gas_limit, chain_id,
//         prev_randao, base_fee, revision, block_hash_fn, helpers)
// helpers = (visible_code, visible_code_hash, delegation_access_cost,
//            selfdestruct, nested, Log)
PyObject* EvmHost_new(PyTypeObject* type, PyObject* args, PyObject* kwds) {
  if (kwds && PyDict_GET_SIZE(kwds)) {
    PyErr_SetString(PyExc_TypeError, "EvmHost takes no keyword arguments");
    return nullptr;
  }
  PyObject *state, *base_fee, *block_hash_fn;
  PyObject* helpers[6];
  const char *coinbase, *randao;
  Py_ssize_t coinbase_len, randao_len;
  unsigned long long number, timestamp, gas_limit, chain_id, revision;
  if (!PyArg_ParseTuple(args, "Oy#KKKKy#OKO(OOOOOO)", &state, &coinbase,
                        &coinbase_len, &number, &timestamp, &gas_limit,
                        &chain_id, &randao, &randao_len, &base_fee, &revision,
                        &block_hash_fn, &helpers[0], &helpers[1], &helpers[2],
                        &helpers[3], &helpers[4], &helpers[5]))
    return nullptr;
  if (coinbase_len != 20 || randao_len != 32) {
    PyErr_SetString(PyExc_ValueError,
                    "coinbase is 20 bytes and prev_randao 32");
    return nullptr;
  }
  EvmHostObject* self =
      reinterpret_cast<EvmHostObject*>(type->tp_alloc(type, 0));
  if (!self) return nullptr;  // tp_alloc zeroes the object
  if (int_word(base_fee, self->txc.base_fee) < 0) {
    Py_DECREF(self);
    return nullptr;
  }
  std::memcpy(self->txc.coinbase, coinbase, 20);
  std::memcpy(self->txc.prev_randao, randao, 32);
  self->txc.block_number = number;
  self->txc.timestamp = timestamp;
  self->txc.gas_limit = gas_limit;
  self->txc.chain_id = chain_id;
  self->txc.revision = revision;
  PyObject** slots[] = {&self->visible_code, &self->visible_code_hash,
                        &self->delegate_cost, &self->selfdestruct,
                        &self->nested,       &self->log_type};
  for (int i = 0; i < 6; ++i) {
    Py_INCREF(helpers[i]);
    *slots[i] = helpers[i];
  }
  Py_INCREF(state);
  self->state = state;
  Py_INCREF(block_hash_fn);
  self->block_hash_fn = block_hash_fn;
  PhantHost* host = &self->host;
  host->ctx = self;
  host->access_account = cb_access_account;
  host->access_storage = cb_access_storage;
  host->get_storage = cb_get_storage;
  host->get_original_storage = cb_get_original_storage;
  host->set_storage = cb_set_storage;
  host->get_balance = cb_get_balance;
  host->get_code_size = cb_get_code_size;
  host->copy_code = cb_copy_code;
  host->get_code_hash = cb_get_code_hash;
  host->is_empty = cb_is_empty;
  host->get_block_hash = cb_get_block_hash;
  host->emit_log = cb_emit_log;
  host->add_refund = cb_add_refund;
  host->selfdestruct = cb_selfdestruct;
  host->call = cb_call;
  host->get_transient = cb_get_transient;
  host->set_transient = cb_set_transient;
  host->trace = nullptr;  // set with a transaction's tracer
  host->delegate_access_cost = cb_delegate_access_cost;
  return reinterpret_cast<PyObject*>(self);
}

PyMethodDef EvmHost_methods[] = {
    {"execute", reinterpret_cast<PyCFunction>(EvmHost_execute), METH_FASTCALL,
     "execute(evm, code, caller, address, value, data, gas, depth, is_static)"
     " -> (status, gas_left, output)"},
    {"frames_run", reinterpret_cast<PyCFunction>(EvmHost_frames_run),
     METH_NOARGS, "frames of the VM run since the last call, nested ones too"},
    {nullptr, nullptr, 0, nullptr},
};

PyTypeObject EvmHostType = {
    PyVarObject_HEAD_INIT(nullptr, 0)
    "phant_engine_ext.EvmHost",          /* tp_name */
    sizeof(EvmHostObject),               /* tp_basicsize */
};

// lock_clocks() -> {site: (unlocked_seconds, retake_seconds)}
PyObject* ext_lock_clocks(PyObject*, PyObject*) {
  PyObject* out = PyDict_New();
  if (!out) return nullptr;
  for (int i = 0; i < kLockSites; ++i) {
    PyObject* pair = Py_BuildValue(
        "(dd)", g_unlocked_ns[i].load(std::memory_order_relaxed) / 1e9,
        g_retake_ns[i].load(std::memory_order_relaxed) / 1e9);
    if (!pair || PyDict_SetItemString(out, kLockSiteNames[i], pair) < 0) {
      Py_XDECREF(pair);
      Py_DECREF(out);
      return nullptr;
    }
    Py_DECREF(pair);
  }
  return out;
}

// The scalar hash. An input under this many bytes is hashed with the
// interpreter lock HELD, a longer one with it released: the native hash
// runs at about 300 MB/s (phant_tpu/backend.py NATIVE_HASH_BPS), so 4 KiB
// are 14 us of it, and a hand-over of the lock that another thread waits
// for costs the caller tens of microseconds to get it back (PERF.md
// section 7 p): below the threshold a release costs more than it buys,
// above it (contract code of 24 KB, a blob transaction's encoding) a
// caller that held on would stall every other thread for the hash's length.
constexpr Py_ssize_t kKeccakUnlockBytes = 4096;
// calls by lock discipline, [0] held and [1] released; counted with the
// lock held, so plain words
uint64_t g_keccak_calls[2];

// keccak256(data) -> the 32-byte digest of any contiguous buffer
PyObject* ext_keccak256(PyObject*, PyObject* arg) {
  Py_buffer view;
  if (PyObject_GetBuffer(arg, &view, PyBUF_SIMPLE) < 0) return nullptr;
  PyObject* out = PyBytes_FromStringAndSize(nullptr, 32);
  if (out) {
    const uint8_t* data = static_cast<const uint8_t*>(view.buf);
    uint8_t* digest = reinterpret_cast<uint8_t*>(PyBytes_AS_STRING(out));
    const size_t len = static_cast<size_t>(view.len);
    if (view.len < kKeccakUnlockBytes) {
      ++g_keccak_calls[0];
      phant_keccak256(data, len, digest);
    } else {
      ++g_keccak_calls[1];
      // the view keeps a bytearray from resizing under the hash
      Unlocked unlocked(kSiteKeccak);
      phant_keccak256(data, len, digest);
    }
  }
  PyBuffer_Release(&view);
  return out;
}

// keccak_calls() -> {"held": n, "released": n}
PyObject* ext_keccak_calls(PyObject*, PyObject*) {
  return Py_BuildValue("{s:K,s:K}", "held",
                       static_cast<unsigned long long>(g_keccak_calls[0]),
                       "released",
                       static_cast<unsigned long long>(g_keccak_calls[1]));
}

PyMethodDef module_methods[] = {
    {"lock_clocks", ext_lock_clocks, METH_NOARGS,
     "lock_clocks() -> {site: (seconds run unlocked, seconds waited to take "
     "the interpreter lock back)}"},
    {"keccak256", ext_keccak256, METH_O,
     "keccak256(data) -> bytes: the lock held under KECCAK_UNLOCK_BYTES, "
     "released from there up"},
    {"keccak_calls", ext_keccak_calls, METH_NOARGS,
     "keccak_calls() -> {'held': n, 'released': n}: keccak256 calls by what "
     "they did with the interpreter lock"},
    {"rlp_encode", ext_rlp_encode, METH_O, "rlp_encode(item) -> bytes"},
    {"encode_node", reinterpret_cast<PyCFunction>(ext_encode_node),
     METH_FASTCALL,
     "encode_node(hole, value_hole_type, items) -> (bytes, [hole offsets])"},
    {"hex_prefix", reinterpret_cast<PyCFunction>(ext_hex_prefix), METH_FASTCALL,
     "hex_prefix(nibbles, is_leaf) -> bytes"},
    {"encode_subtree", reinterpret_cast<PyCFunction>(ext_encode_subtree),
     METH_FASTCALL,
     "encode_subtree(node, cache, path_enc, embed_below, node_types) -> "
     "((structure, encoding, reference), digests computed)"},
    {nullptr, nullptr, 0, nullptr},
};

PyModuleDef moduledef = {
    PyModuleDef_HEAD_INIT,
    "phant_engine_ext",
    "CPython driver for the native witness-engine core, the trie-node "
    "encoder, the EVM's host binding and the scalar keccak256",
    -1,
    module_methods,
};

}  // namespace

extern "C" PyObject* PyInit_phant_engine_ext() {
  EngineType.tp_dealloc = reinterpret_cast<destructor>(Engine_dealloc);
  EngineType.tp_flags = Py_TPFLAGS_DEFAULT;
  EngineType.tp_methods = Engine_methods;
  EngineType.tp_new = Engine_new;
  if (PyType_Ready(&EngineType) < 0) return nullptr;
  BatchType.tp_dealloc = reinterpret_cast<destructor>(Batch_dealloc);
  BatchType.tp_flags = Py_TPFLAGS_DEFAULT;
  BatchType.tp_methods = Batch_methods;
  // Batch objects are created only by scan_begin(); no tp_new exposed
  if (PyType_Ready(&BatchType) < 0) return nullptr;
  EvmHostType.tp_dealloc = reinterpret_cast<destructor>(EvmHost_dealloc);
  EvmHostType.tp_flags = Py_TPFLAGS_DEFAULT | Py_TPFLAGS_HAVE_GC;
  EvmHostType.tp_traverse = reinterpret_cast<traverseproc>(EvmHost_traverse);
  EvmHostType.tp_clear = reinterpret_cast<inquiry>(EvmHost_clear);
  EvmHostType.tp_methods = EvmHost_methods;
  EvmHostType.tp_new = EvmHost_new;
  if (PyType_Ready(&EvmHostType) < 0) return nullptr;
  PyObject* m = PyModule_Create(&moduledef);
  if (!m) return nullptr;
  Py_INCREF(&EngineType);
  if (PyModule_AddObject(m, "Engine",
                         reinterpret_cast<PyObject*>(&EngineType)) < 0) {
    Py_DECREF(&EngineType);
    Py_DECREF(m);
    return nullptr;
  }
  if (!intern_host_names()) {
    Py_DECREF(m);
    return nullptr;
  }
  Py_INCREF(&EvmHostType);
  if (PyModule_AddObject(m, "EvmHost",
                         reinterpret_cast<PyObject*>(&EvmHostType)) < 0) {
    Py_DECREF(&EvmHostType);
    Py_DECREF(m);
    return nullptr;
  }
  if (PyModule_AddIntConstant(m, "KECCAK_UNLOCK_BYTES", kKeccakUnlockBytes) <
      0) {
    Py_DECREF(m);
    return nullptr;
  }
  return m;
}
