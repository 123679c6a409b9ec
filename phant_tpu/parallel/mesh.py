"""Multi-chip scaling: device meshes, sharded kernels, multi-host init.

The reference is single-process and has no distributed backend (SURVEY §2:
its only network surface is the HTTP Engine API, reference:
src/main.zig:143-149). This framework's scale-out axis is data parallelism
over blocks/nodes/signatures: a `jax.sharding.Mesh` with one `dp` axis,
`shard_map`-ped kernels whose per-shard partial results are combined with
XLA collectives over ICI (within a slice) / DCN (across slices), and
`jax.distributed` for multi-host process groups — the TPU-native
equivalent of a NCCL/MPI backend.

Tested on a virtual 8-device CPU mesh (tests/test_parallel.py); the driver
dry-runs the same path via __graft_entry__.dryrun_multichip.
"""

from __future__ import annotations

import functools
from typing import Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from phant_tpu.crypto.keccak import RATE
from phant_tpu.ops.witness_jax import (
    WITNESS_MAX_CHUNKS,
    _digests_from_rows,
    _extract_ref_positions,
    _gather_node_rows,
    _gather_refs,
    _ref_words_from_rows,
    _row_words,
    linked_verdict,
    witness_digests,
)

shard_map = jax.shard_map

import contextlib
import threading

# serializes the cache-off window below: the config flip is
# process-global, so concurrent sharded compiles must take turns. A
# single-device compile racing the window at worst skips one persistent-
# cache write (benign; its in-memory executable is unaffected) — there is
# no corruption mode, which is what makes the sharded path default-safe
# in threaded servers.
_CACHE_TOGGLE_LOCK = threading.RLock()

# AOT-compiled sharded executables, keyed by (kernel, mesh devices, static
# params, input shapes/dtypes). Every sharded entry point below builds a
# FRESH closure; jitting it per call would mean (a) a full re-trace on
# every dispatched batch under mesh-sharded SERVING and (b) the
# process-global cache-off window toggling around every one of them. The
# memo compiles once per key (inside the window) via the AOT path
# (jit().lower().compile()); steady-state calls hit the compiled
# executable directly and never touch the cache config again.
# MeshExecutorPool pre-warms the serving kernels at start
# (prewarm_sharded), so a serving process pays its windows at boot, not
# mid-traffic.
_EXEC_CACHE: dict = {}
_EXEC_LOCK = threading.Lock()


def _mesh_key(mesh: "Mesh") -> tuple:
    return (mesh.axis_names, tuple(d.id for d in mesh.devices.flat))


def _arg_key(args) -> tuple:
    return tuple((tuple(a.shape), str(a.dtype)) for a in args)


def _compiled_call(key: tuple, build, args):
    """Run `jax.jit(build())` AOT-compiled and memoized under `key`.

    `args` must already be device_put with the shardings the traceable
    expects — the lowered executable bakes them in, and the memo key
    carries the mesh device ids + input shapes/dtypes so a shape or mesh
    change compiles a fresh executable. The whole miss path (including
    the compile) runs under _EXEC_LOCK: first-compiles are serialized by
    the cache-toggle lock anyway, and a lock-free read of the shared dict
    would be exactly the unlocked-shared-state hazard phantlint's LOCK
    rule exists to catch."""
    with _EXEC_LOCK:
        fn = _EXEC_CACHE.get(key)
        if fn is None:
            with _no_compile_cache():
                fn = jax.jit(build()).lower(*args).compile()
            _EXEC_CACHE[key] = fn
    return fn(*args)


@contextlib.contextmanager
def _no_compile_cache():
    """Serializing SOME multi-device (shard_map) executables still
    SEGFAULTS jax 0.9.0 in the persistent compilation cache's write path
    (`compilation_cache.put_executable_and_time`; the sharded GLV
    ecrecover, deleted in PR 30, did in PR 24's whole-suite run, while the
    sharded witness programs were written and re-read fine; whether the
    window can close is for a four-chip run to settle), so every sharded
    compile below runs with the cache SWITCHED OFF —
    `jax_enable_compilation_cache`, the directory is left alone.
    Single-device kernels keep the cache."""
    from jax.experimental.compilation_cache import compilation_cache

    with _CACHE_TOGGLE_LOCK:
        if not jax.config.jax_enable_compilation_cache:
            yield
            return
        jax.config.update("jax_enable_compilation_cache", False)
        compilation_cache.reset_cache()  # the on/off decision is memoized
        try:
            yield
        finally:
            jax.config.update("jax_enable_compilation_cache", True)
            compilation_cache.reset_cache()


def make_mesh(n_devices: Optional[int] = None, axis: str = "dp") -> Mesh:
    """1-D device mesh over the first n (default: all) local devices."""
    devices = jax.devices()
    if n_devices is not None:
        if len(devices) < n_devices:
            raise RuntimeError(
                f"need {n_devices} devices but jax sees {len(devices)} "
                f"({devices[0].platform}); set JAX_PLATFORMS=cpu and "
                f"--xla_force_host_platform_device_count for a virtual mesh"
            )
        devices = devices[:n_devices]
    # jax.devices() yields Device HANDLES, not device arrays — no data
    # moves here (HOSTSYNC's taint heuristic cannot tell the difference)
    return Mesh(np.array(devices), axis_names=(axis,))  # phantlint: disable=HOSTSYNC — device handles, not arrays


def init_distributed(
    coordinator_address: Optional[str] = None,
    num_processes: Optional[int] = None,
    process_id: Optional[int] = None,
) -> None:
    """Join a multi-host process group (the NCCL/MPI-equivalent bootstrap):
    after this, jax.devices() spans every host's chips and the collectives
    in the sharded kernels ride ICI/DCN. No-op arguments let TPU pods
    auto-detect their topology."""
    jax.distributed.initialize(
        coordinator_address=coordinator_address,
        num_processes=num_processes,
        process_id=process_id,
    )


# ---------------------------------------------------------------------------
# sharded witness verification (dp over the node axis)
# ---------------------------------------------------------------------------


def witness_verify_fused_sharded(
    mesh: Mesh,
    blob,
    meta16,
    roots,
    *,
    max_chunks: int = WITNESS_MAX_CHUNKS,
    n_blocks: Optional[int] = None,
):
    """The flagship fused kernel (on-device RLP ref extraction,
    phant_tpu/ops/witness_jax.py witness_verify_fused) with the node axis
    sharded over `dp`. Each shard gathers its node rows from the replicated
    blob, hashes them, and parses its own nodes' child refs on device; node
    lengths are all_gather-ed once for the global offset prefix-sum, and the
    per-shard ref slices are all_gather-ed for the linkage join (a node's
    parent may sit on any shard — these are the collectives that ride ICI).
    Per-block partials combine with pmax (root hit) / pmin (all linked).

    The node axis must be divisible by the mesh size (pack_witness_fused
    pads to powers of two)."""
    if n_blocks is None:
        n_blocks = int(roots.shape[0])
    axis = mesh.axis_names[0]

    def build():
        @functools.partial(
            shard_map,
            mesh=mesh,
            in_specs=(P(), P(None, axis), P()),
            out_specs=P(),
        )
        def inner(blob_s, meta_s, roots_s):
            lens_l = meta_s[0].astype(jnp.int32)
            block_l = meta_s[1].astype(jnp.int32)
            nloc = lens_l.shape[0]
            lens_all = jax.lax.all_gather(lens_l, axis, axis=0, tiled=True)
            off_all = jnp.cumsum(lens_all) - lens_all  # exclusive global offsets
            i = jax.lax.axis_index(axis)
            offsets_l = jax.lax.dynamic_slice(off_all, (i * nloc,), (nloc,))
            words = _row_words(
                _gather_node_rows(blob_s, offsets_l, lens_l, max_chunks * RATE)
            )
            digests = _digests_from_rows(words, lens_l, max_chunks=max_chunks)
            ref_pos = _extract_ref_positions(words, lens_l)
            refs_l = _ref_words_from_rows(words, ref_pos).reshape(-1, 8)
            live_l = (ref_pos >= 0).reshape(-1)
            rblock_l = jnp.broadcast_to(block_l[:, None], ref_pos.shape).reshape(-1)
            refs = jax.lax.all_gather(refs_l, axis, axis=0, tiled=True)
            ref_block = jax.lax.all_gather(rblock_l, axis, axis=0, tiled=True)
            ref_live = jax.lax.all_gather(live_l, axis, axis=0, tiled=True)
            root_hit, all_ok = linked_verdict(
                digests, lens_l, block_l, refs, ref_block, ref_live, roots_s, n_blocks
            )
            return jnp.stack(
                [jax.lax.pmax(root_hit, axis), jax.lax.pmin(all_ok, axis)]
            )

        return inner

    repl = NamedSharding(mesh, P())
    col = NamedSharding(mesh, P(None, axis))
    args = (
        jax.device_put(jnp.asarray(blob), repl),
        jax.device_put(jnp.asarray(meta16), col),
        jax.device_put(jnp.asarray(roots), repl),
    )
    key = ("fused", _mesh_key(mesh), max_chunks, n_blocks) + _arg_key(args)
    out = _compiled_call(key, build, args)
    return (out[0] > 0) & (out[1] > 0)


def witness_verify_linked_sharded(
    mesh: Mesh,
    blob,
    meta,
    ref_meta,
    roots,
    *,
    max_chunks: int = WITNESS_MAX_CHUNKS,
    n_blocks: Optional[int] = None,
):
    """Full (linked) multiproof verification with BOTH the node axis and the
    ref axis sharded over `dp`. Each shard hashes its nodes and gathers its
    slice of child refs from the replicated blob; the ref slices are then
    `all_gather`-ed over the mesh (a small array — this is the collective
    that rides ICI) because a node's parent may sit on any shard. Per-block
    partials combine with pmax (root hit) / pmin (all nodes linked).

    Node and ref axes must be divisible by the mesh size (pack_witness pads
    both to powers of two).
    """
    if n_blocks is None:
        n_blocks = int(roots.shape[0])
    axis = mesh.axis_names[0]

    def build():
        @functools.partial(
            shard_map,
            mesh=mesh,
            in_specs=(P(), P(None, axis), P(None, axis), P()),
            out_specs=P(),
        )
        def inner(blob_s, meta_s, ref_s, roots_s):
            offsets, lens, block_id = meta_s[0], meta_s[1], meta_s[2]
            digests = witness_digests(blob_s, offsets, lens, max_chunks=max_chunks)
            refs_local = _gather_refs(blob_s, ref_s[0])
            refs = jax.lax.all_gather(refs_local, axis, axis=0, tiled=True)
            ref_block = jax.lax.all_gather(ref_s[1], axis, axis=0, tiled=True)
            ref_live = jax.lax.all_gather(ref_s[0] >= 0, axis, axis=0, tiled=True)
            root_hit, all_ok = linked_verdict(
                digests, lens, block_id, refs, ref_block, ref_live, roots_s, n_blocks
            )
            return jnp.stack([jax.lax.pmax(root_hit, axis), jax.lax.pmin(all_ok, axis)])

        return inner

    repl = NamedSharding(mesh, P())
    col = NamedSharding(mesh, P(None, axis))
    args = (
        jax.device_put(jnp.asarray(blob), repl),
        jax.device_put(jnp.asarray(meta), col),
        jax.device_put(jnp.asarray(ref_meta), col),
        jax.device_put(jnp.asarray(roots), repl),
    )
    key = ("linked", _mesh_key(mesh), max_chunks, n_blocks) + _arg_key(args)
    out = _compiled_call(key, build, args)
    return (out[0] > 0) & (out[1] > 0)


def witness_digests_sharded(mesh: Mesh, blob, offsets, lens, *, max_chunks: int = WITNESS_MAX_CHUNKS):
    """The witness engine's novel-batch keccak (ops/witness_engine.py
    _hash_batch_device) with the NODE axis sharded over `dp`: the blob is
    replicated, each shard hashes its slice of nodes, outputs stay sharded
    (no collective — hashing is embarrassingly parallel; the engine's
    linkage join runs on host integers). This is the steady-state
    multi-chip path: novel nodes per block are few, so one mesh dispatch
    hashes a whole prefetch window's novelty.

    The node axis must be divisible by the mesh size (callers pad to
    powers of two)."""
    axis = mesh.axis_names[0]

    def build():
        @functools.partial(
            shard_map,
            mesh=mesh,
            in_specs=(P(), P(axis), P(axis)),
            out_specs=P(axis),
        )
        def inner(blob_s, off_s, lens_s):
            return witness_digests(blob_s, off_s, lens_s, max_chunks=max_chunks)

        return inner

    repl = NamedSharding(mesh, P())
    col = NamedSharding(mesh, P(axis))
    args = (
        jax.device_put(jnp.asarray(blob), repl),
        jax.device_put(jnp.asarray(offsets), col),
        jax.device_put(jnp.asarray(lens), col),
    )
    key = ("digests", _mesh_key(mesh), max_chunks) + _arg_key(args)
    return _compiled_call(key, build, args)


# ---------------------------------------------------------------------------
# sharded ecrecover (dp over the signature axis)
# ---------------------------------------------------------------------------


def ecrecover_sharded(mesh: Mesh, e, r, s, parity):
    """Batched ecrecover with the signature axis sharded over `dp`. Each
    shard runs the full fused kernel on its slice; outputs shard the same
    way (no collective needed — recovery is embarrassingly parallel).

    Batch size must be divisible by the mesh size (ecrecover_batch buckets
    to powers of two, so any power-of-two mesh divides it).
    """
    from phant_tpu.ops.secp256k1_jax import ecrecover_kernel

    axis = mesh.axis_names[0]

    def build():
        @functools.partial(
            shard_map,
            mesh=mesh,
            in_specs=(P(axis), P(axis), P(axis), P(axis)),
            out_specs=(P(axis), P(axis)),
        )
        def inner(e_s, r_s, s_s, p_s):
            return ecrecover_kernel(e_s, r_s, s_s, p_s)

        return inner

    shard = NamedSharding(mesh, P(axis))
    # four FIXED kernel arguments, not a data axis — each upload is one
    # sharded array carrying the whole batch
    args = [jax.device_put(jnp.asarray(v), shard) for v in (e, r, s, parity)]  # phantlint: disable=JNPHOSTLOOP — fixed argument tuple, not per-element
    key = ("ecrecover", _mesh_key(mesh)) + _arg_key(args)
    return _compiled_call(key, build, args)


# ---------------------------------------------------------------------------
# serving prewarm
# ---------------------------------------------------------------------------


def prewarm_sharded(
    mesh: Mesh, *, max_chunks: int = WITNESS_MAX_CHUNKS, n_blocks: int = 8
) -> int:
    """Compile the serving-path sharded executables once, at startup.

    MeshExecutorPool calls this when the mesh serving path comes up so the
    first served batch doesn't pay a multi-second cold shard_map compile
    mid-traffic, and so the compile-cache-off windows (_no_compile_cache —
    a process-global config toggle) fire at BOOT, where no single-device
    compile is racing them. Production shapes that differ from the prewarm
    shapes still compile once each on first hit (bucketing keeps that set
    small); what the executable memo guarantees is that STEADY-STATE
    sharded dispatches never toggle the cache at all.
    Returns the number of executables compiled (0 when both were already
    warm)."""
    n = int(mesh.devices.size)
    before = len(_EXEC_CACHE)
    # tiny all-pad shapes: verdicts are meaningless (and ignored) — the
    # point is the compile, and pad rows (len 0) are a layout every kernel
    # already handles
    B = 2 * n
    blob = np.zeros(
        1 << (B * 64 + max_chunks * RATE - 1).bit_length(), np.uint8
    )
    offsets = np.zeros(B, np.int32)
    lens = np.zeros(B, np.int32)
    # one-shot boot prewarm: the forced syncs below ARE the point (not on
    # any hot path phantlint HOSTSYNC scopes to)
    np.asarray(witness_digests_sharded(mesh, blob, offsets, lens, max_chunks=max_chunks))
    meta16 = np.zeros((2, B), np.uint16)
    roots = np.zeros((n_blocks, 8), np.uint32)
    np.asarray(
        witness_verify_fused_sharded(
            mesh, blob, meta16, roots, max_chunks=max_chunks, n_blocks=n_blocks
        )
    )
    return len(_EXEC_CACHE) - before
