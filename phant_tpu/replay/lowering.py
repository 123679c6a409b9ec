"""Segment-level root lowering: K block states hashed in ONE dispatch.

The serving root lane coalesces *across concurrent requests*
(ops/root_engine.py); historical replay has no concurrency to borrow —
its batch axis is the segment itself. A segment's per-block state tries
differ only in leaf *values* whenever no account was born or died and no
RLP field changed width, so consecutive blocks' HashPlans share one
level layout and vmap through `_hash_plans_batched` (ops/mpt_jax.py) as
a single fused device program. This module owns that lowering:

  * `group_segment_plans` splits a segment's plans into maximal
    structure-sharing runs (`plans_share_structure`) — an account
    birth/death or a width change simply ends the run, it never fails
    the segment;
  * `lower_segment_plans` dispatches every multi-plan run as one
    batched device call and defers singletons/unplannable blocks to the
    host walk — pure enqueue, no device sync (phantlint HOSTSYNC scopes
    this function: a reintroduced `.item()` in the megabatch loop is a
    gate-red regression);
  * `resolve_segment_roots` is the one honest sync point, reading all
    runs back after the EVM has moved on to the next segment.

Env: `PHANT_REPLAY_ROOT` (`0`/`host` pins the host walk, `1`/`device`
forces batched device dispatch — tests and the XLA-CPU proxy; under `auto`
deferred roots go to the device exactly when the device route exists, the
same shape as PHANT_BATCHED_SIG/PHANT_BATCHED_ROOT, and the engine's root
MODE keeps the host walk: `auto_root_mode`).
"""

from __future__ import annotations

import os
from typing import Callable, List, Optional, Sequence, Tuple

import numpy as np

from phant_tpu.ops.mpt_jax import (
    MPT_MAX_CHUNKS,
    HashPlan,
    _hash_plans_batched,
    execute_plan_host,
    plans_share_structure,
)


def device_roots_wanted() -> bool:
    """Route deferred segment roots to the batched device executor?
    Same 0/1/auto shape as stateless._batched_sig_wanted: the env pin is
    for tests and the XLA-CPU proxy, auto keys on a live device."""
    env = os.environ.get("PHANT_REPLAY_ROOT", "auto")
    if env in ("0", "off", "host", ""):
        return False
    if env in ("1", "device"):
        return True
    from phant_tpu.backend import crypto_backend, jax_device_ok

    return crypto_backend() == "tpu" and jax_device_ok()


def auto_root_mode() -> Tuple[str, Optional[str]]:
    """What root mode `auto` takes: (mode, a sentence where a live device
    was passed over, else None). `PHANT_REPLAY_ROOT=1|device` is an explicit
    request for deferred roots, as `--root defer` is. Otherwise the host
    walk, on a live device too: a deferred plan is
    `build_hash_plan(state.flush_root_trie())`, the WHOLE retained trie laid
    out once a block (a million nodes at a genesis of 2^20 accounts), where
    the walk re-encodes the dirty paths alone. A planner over what a block
    dirtied is what will make `auto` defer; it brings its condition here."""
    if os.environ.get("PHANT_REPLAY_ROOT", "auto") in ("1", "device"):
        return "defer", None
    if device_roots_wanted():
        return "host", (
            "host walk: the state retains its trie, and a deferred plan would "
            "lay out the whole of it once a block (--root defer asks for that)"
        )
    return "host", None


def group_segment_plans(
    plans: Sequence[Optional[HashPlan]],
) -> List[Tuple[int, int]]:
    """Maximal [start, end) runs of consecutive structure-sharing plans.
    A None plan (embedded/oversized nodes — build_hash_plan declined) is
    always a singleton run; runs never merge across it."""
    runs: List[Tuple[int, int]] = []
    i = 0
    while i < len(plans):
        j = i + 1
        while (
            j < len(plans)
            and plans[i] is not None
            and plans[j] is not None
            and plans_share_structure(plans[i], plans[j])
        ):
            j += 1
        runs.append((i, j))
        i = j
    return runs


def lower_segment_plans(plans: Sequence[Optional[HashPlan]]) -> List[tuple]:
    """Dispatch a segment's per-block root plans: every run of >= 2
    structure-sharing plans becomes ONE vmapped `_hash_plans_batched`
    device program (K roots, one host->device round trip); singletons
    and unplannable blocks defer to the host walk at resolve time (the
    per-root RTT is exactly what the offload gate rejects at K=1).

    Returns opaque handles for `resolve_segment_roots`. This function is
    pure enqueue — it must never synchronize on device values (HOSTSYNC
    gate); the readback lives in resolve, after the EVM has moved on."""
    import jax.numpy as jnp

    handles: List[tuple] = []
    if not plans:
        return handles
    device_ok = device_roots_wanted()
    for i, j in group_segment_plans(plans):
        run = list(plans[i:j])
        if device_ok and run[0] is not None and (j - i) >= 2:
            blobs = jnp.asarray(np.stack([p.blob for p in run]))  # phantlint: disable=JNPHOSTLOOP — ONE stacked upload per structure-run (the merge is the point); runs per segment are bounded by plan-shape diversity, not block count
            # per-LEVEL metadata uploads, bounded by trie depth — the
            # node axis ships in the one stacked blob above
            levels_d = tuple(
                tuple(jnp.asarray(a) for a in lvl) for lvl in run[0].levels  # phantlint: disable=JNPHOSTLOOP — bounded per-level metadata upload
            )
            out = _hash_plans_batched(blobs, levels_d, max_chunks=MPT_MAX_CHUNKS)
            handles.append(("device", i, j, out))
        else:
            handles.append(("host", i, j, run))
    return handles


def resolve_segment_roots(
    handles: Sequence[tuple],
    fallbacks: Optional[Sequence[Optional[Callable[[], bytes]]]] = None,
) -> Tuple[List[Optional[bytes]], dict]:
    """Materialize every lowered run's roots, in block order.

    `fallbacks[k]` supplies the root for an unplannable block k (the
    replay engine captures `trie.root_hash` thunks at flush time). The
    device readback here is the segment's product — the one deliberate
    sync per segment, not an accidental one."""
    roots: List[Optional[bytes]] = []
    stats = {"device_groups": 0, "device_roots": 0, "host_roots": 0}
    for kind, i, j, payload in handles:
        if kind == "device":
            arr = np.asarray(payload, dtype="<u4")  # phantlint: disable=HOSTSYNC — segment root readback is the product
            for k in range(arr.shape[0]):
                roots.append(arr[k].tobytes())
            stats["device_groups"] += 1
            stats["device_roots"] += j - i
        else:
            for k, p in enumerate(payload, start=i):
                if p is not None:
                    roots.append(execute_plan_host(p))
                elif fallbacks is not None and fallbacks[k] is not None:
                    roots.append(fallbacks[k]())
                else:
                    roots.append(None)
                stats["host_roots"] += 1
    return roots, stats
