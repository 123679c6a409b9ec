"""Device-side MPT root recomputation (level-by-level keccak).

Recomputes a Merkle Patricia Trie root with every keccak256 on device: the
host walks the built trie once and emits a *hash plan* — per-level RLP node
templates with 32-byte holes where child digests belong — and the device
then alternates (scatter child digests into the blob) -> (batched keccak of
the level) until the root digest falls out. Host->device traffic is the
template blob once plus tiny per-level index arrays; all hashing (the hot
~90% of CPU root computation) happens on the chip.

This is BASELINE.md metric #2 (state-root recompute): the reference computes
roots serially on CPU (reference: src/mpt/mpt.zig:38-119, keccak per node)
and skips state-root verification entirely (reference:
src/blockchain/blockchain.zig:83-85).

Scope: tries whose nodes all RLP-encode to >= 32 bytes (true for the secure
state trie — account leaves are ~70B — and for receipt/tx tries of real
blocks). Tries with embedded (<32B) nodes fall back to the CPU walk.

Two executors share the plans. Replay and `trie_root_device` run one plan
(or K of one structure) level by level, the levels unrolled and the plan's
own sizes in the jit key (`_hash_plan_fused`, `_hash_plans_batched`). The
serving root lane merges a batch's plans into strips on a rung of
PLAN_LADDER and walks them in one loop (`merge_plans`,
`_hash_plan_outputs`): its jit key is the rung, never a block's sizes.
"""

from __future__ import annotations

import functools
from dataclasses import dataclass
from typing import Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from phant_tpu import rlp
from phant_tpu.crypto.keccak import RATE
from phant_tpu.mpt.mpt import (
    BranchNode,
    EMPTY_TRIE_ROOT,
    ExtensionNode,
    LeafNode,
    Trie,
    count_node_encodings,
    encode_hex_prefix,
)
from phant_tpu.utils.native import load_engine_ext
from phant_tpu.ops.witness_jax import _pow2ceil as _pow2, witness_digests

# state-trie branch nodes are <= 17*33 + 2 bytes; 5 rate chunks cover 676B
MPT_MAX_CHUNKS = 5

_HOLE = object()  # placeholder for a child digest in a node template


def _list_header(payload_len: int) -> bytes:
    if payload_len < 56:
        return bytes([0xC0 + payload_len])
    ll = payload_len.to_bytes((payload_len.bit_length() + 7) // 8, "big")
    return bytes([0xF7 + len(ll)]) + ll


def str_header(payload_len: int) -> bytes:
    """RLP string header for a payload of `payload_len` >= 2 bytes (the
    single-byte encodings below 0x80 never apply to the >=33-byte account
    leaf values this is used for)."""
    if payload_len < 56:
        return bytes([0x80 + payload_len])
    ll = payload_len.to_bytes((payload_len.bit_length() + 7) // 8, "big")
    return bytes([0xB7 + len(ll)]) + ll


class _ValueHole:
    """A leaf VALUE carrying an embedded 32-byte hole: RLP-encodes as one
    string item `prefix + <32 zero bytes> + suffix`, with the hole's byte
    offset reported like a child-ref hole. This is how the fused post-root
    plan wires an account leaf to its storage trie's root digest — the
    storage root is a hole INSIDE the leaf's account-RLP value
    (stateless.WitnessStateDB.post_root_plan)."""

    __slots__ = ("prefix", "suffix")

    def __init__(self, prefix: bytes, suffix: bytes):
        self.prefix = prefix
        self.suffix = suffix


def _template_encoder():
    """items -> (template, hole offsets): one call of the extension's node
    encoder where the program has it, else the Python encoder below."""
    ext = load_engine_ext()
    if ext is None:
        return _encode_template_python
    return functools.partial(ext.encode_node, _HOLE, _ValueHole)


def _encode_template(items) -> Tuple[bytes, List[int]]:
    """RLP-encode a node whose child refs are 32-byte holes; returns the
    encoding (holes zeroed) and each hole's byte offset (in encounter
    order — standalone `_HOLE` items and `_ValueHole` inner holes alike)."""
    return _template_encoder()(items)


def _encode_template_python(items) -> Tuple[bytes, List[int]]:
    """`_encode_template` without the extension, and its oracle."""
    payload = bytearray()
    holes: List[int] = []
    for it in items:
        if it is _HOLE:
            payload.append(0xA0)  # RLP string header for 32 bytes
            holes.append(len(payload))
            payload += b"\x00" * 32
        elif isinstance(it, _ValueHole):
            total = len(it.prefix) + 32 + len(it.suffix)
            payload += str_header(total)
            payload += it.prefix
            holes.append(len(payload))
            payload += b"\x00" * 32
            payload += it.suffix
        else:
            payload += rlp.encode_python(it)
    header = _list_header(len(payload))
    return bytes(header) + bytes(payload), [h + len(header) for h in holes]


@dataclass
class HashPlan:
    """Per-level device layout for one (or one fused set of) trie(s).

    The plan is value-complete but hash-free: templates carry zeroed 32-byte
    holes where child digests go, so executing the plan re-derives EVERY
    node digest from raw bytes — caching a plan caches packing work, never
    hashes. `device_args` holds the plan's arrays already resident on the
    device (populated on first execution), so repeated roots of an unchanged
    trie transfer nothing but the 32-byte result.

    `out_rows` lists the digest rows (in the PADDED per-level row space)
    the caller wants back — the fused post-root plans read back each
    storage root plus the account root; None means just the root."""

    blob: np.ndarray  # (L,) uint8 — all templates + gather/scatter slack
    # per level: offsets (n,), lens (n,), hole_pos (h,), hole_child (h,)
    levels: List[Tuple[np.ndarray, np.ndarray, np.ndarray, np.ndarray]]
    n_nodes: int  # total real nodes
    root_pos: int  # row of the root digest in the global digest buffer
    device_args: Optional[tuple] = None  # (blob_d, levels_d) jax arrays
    out_rows: Optional[np.ndarray] = None  # (R,) int32 padded-space rows
    used: int = 0  # template bytes at the blob's head (0 = not recorded)


class PlanBuilder:
    """Shared post-order template walker behind `build_hash_plan` (full
    tries) and the PartialTrie post-root planner (stateless.py).

    Two extensions over the original full-trie walk make witness-shaped
    (partial) tries plannable:

      * a node exposing a `.digest` attribute (an unwitnessed HashNode
        subtree) contributes its digest to the parent template as a
        CONSTANT — no entry, no hashing: the untouched subtrees of a
        witness enter the level blob as literal bytes;
      * a LeafNode registered in `value_holes` encodes its value as a
        `_ValueHole` — 32 zero bytes wired to another planned entry's
        digest row — which is how one fused plan covers account AND
        storage tries (the storage root is a hole in the account leaf).

    `try_subtree` visits one trie with rollback: a subtree containing an
    embedded (<32 B) or oversized node unwinds cleanly so the caller can
    fall back to the host walk for THAT trie only.

    Scheme hooks (phant_tpu/commitment/): `_path_enc` encodes a leaf/
    extension path into its template (hex-prefix here; bit-prefix for the
    binary scheme's BinaryPlanBuilder) and `_min_template` carries the
    embedded-node rule (32 for hexary MPT — a <32 B encoding would have
    been embedded in its parent, so a digest-per-node plan would be wrong;
    0 for schemes that ALWAYS reference children by digest). Everything
    else — level layout, hole wiring, `finish`, `merge_plans`, the device
    executors — is scheme-independent: a HashPlan is just templates with
    32-byte holes at byte offsets."""

    #: leaf/extension path encoding (hexary default: hex-prefix)
    _path_enc = staticmethod(encode_hex_prefix)
    #: smallest plannable template (the hexary embedded-node rule)
    _min_template = 32

    def __init__(self):
        # the encoders are chosen once a builder, not once a node
        self._template = _template_encoder()
        ext = load_engine_ext()
        if ext is not None and self._path_enc is encode_hex_prefix:
            self._path_enc = ext.hex_prefix
        # (level, template, [(hole_off, child_gi)])
        self.entries: List[Tuple[int, bytes, List[Tuple[int, int]]]] = []
        self._index: Dict[int, int] = {}
        self._order: List[int] = []  # node ids, parallel to entries
        self.too_small = False
        # id(LeafNode) -> (value_prefix, value_suffix, child_gi,
        # child_level): the fused account+storage wiring
        self.value_holes: Dict[int, Tuple[bytes, bytes, int, int]] = {}

    def visit(self, node) -> Tuple[Optional[int], int, Optional[bytes]]:
        """(entry_gi, level, const_digest). `const_digest` is set (and gi
        is None, level 0) for digest-only nodes."""
        dg = getattr(node, "digest", None)
        if dg is not None:
            return None, 0, dg
        nid = id(node)
        if nid in self._index:
            gi = self._index[nid]
            return gi, self.entries[gi][0], None
        if isinstance(node, LeafNode):
            vh = self.value_holes.get(nid)
            if vh is not None:
                prefix, suffix, child_gi, child_level = vh
                template, holes = self._template(
                    [self._path_enc(node.path, True), _ValueHole(prefix, suffix)]
                )
                level = child_level + 1
                hole_refs: List[Tuple[int, int]] = [(holes[0], child_gi)]
            else:
                template, _holes = self._template(
                    [self._path_enc(node.path, True), node.value]
                )
                level = 0
                hole_refs = []
        elif isinstance(node, ExtensionNode):
            ci, clvl, cdg = self.visit(node.child)
            if cdg is not None:
                template, _holes = self._template(
                    [self._path_enc(node.path, False), cdg]
                )
                level = 0
                hole_refs = []
            else:
                template, holes = self._template(
                    [self._path_enc(node.path, False), _HOLE]
                )
                level = clvl + 1
                hole_refs = [(holes[0], ci)]
        else:  # BranchNode
            items: List = []
            child_order: List[int] = []
            level = -1
            for child in node.children:
                if child is None:
                    items.append(b"")
                    continue
                # a witness's edge, most of a dirty branch's children,
                # is told here and spared the call
                cdg = getattr(child, "digest", None)
                if cdg is not None:
                    items.append(cdg)  # constant 32-byte digest ref
                    continue
                ci, clvl, _none = self.visit(child)
                items.append(_HOLE)
                child_order.append(ci)
                if clvl > level:
                    level = clvl
            items.append(node.value if node.value is not None else b"")
            template, holes = self._template(items)
            level += 1  # -1 (all-constant children) -> level 0
            hole_refs = list(zip(holes, child_order))
        if len(template) < self._min_template:
            self.too_small = True
        if len(template) > MPT_MAX_CHUNKS * RATE - 1:
            self.too_small = True  # oversized node: CPU path
        gi = len(self.entries)
        self.entries.append((level, template, hole_refs))
        self._index[nid] = gi
        self._order.append(nid)
        return gi, level, None

    def try_subtree(self, node) -> Optional[Tuple[int, int]]:
        """Visit one trie root; (gi, level), or None with the builder
        rolled back when the subtree is unplannable (embedded/oversized
        node, or a digest-only root)."""
        mark = len(self.entries)
        saved = self.too_small
        self.too_small = False
        gi, level, const = self.visit(node)
        if self.too_small or const is not None:
            del self.entries[mark:]
            for nid in self._order[mark:]:
                self._index.pop(nid, None)
            del self._order[mark:]
            self.too_small = saved
            return None
        self.too_small = saved
        return gi, level

    def finish(
        self, root_gi: int, out_gis: Sequence[int] = ()
    ) -> Optional[HashPlan]:
        """Lay the visited entries into the per-level device layout.
        `out_gis` selects extra entries whose digest rows the caller wants
        read back (`HashPlan.out_rows`; the root row is appended last)."""
        if self.too_small or not self.entries:
            return None
        entries = self.entries
        n = len(entries)
        count_node_encodings(n)
        templates = [template for _lvl, template, _holes in entries]
        lens = np.fromiter(map(len, templates), np.int64, n)
        offsets = np.cumsum(lens) - lens
        pos = int(lens.sum())
        # pow2-pad the blob so repeated roots of similar tries hit a small
        # set of compiled shapes (the slack doubles as scatter scratch)
        blob = np.zeros(_pow2(pos + MPT_MAX_CHUNKS * RATE), np.uint8)
        blob[:pos] = np.frombuffer(b"".join(templates), np.uint8)

        level_of = np.fromiter((lvl for lvl, _t, _h in entries), np.int64, n)
        per_level = np.bincount(level_of)
        # the root is the unique max-level node (level(parent) >
        # level(child) for every edge — including the value-hole edges —
        # and all planned nodes descend from the root)
        assert per_level[-1] == 1 and level_of[root_gi] == len(per_level) - 1
        # digest rows are laid out level by level, each level padded to a
        # power of two — remap must use the PADDED cumulative position,
        # since that is where the fused executor writes each level's rows
        padded = np.array([_pow2(int(c)) for c in per_level], np.int64)
        # a level's entries in visit order, one level after another
        by_level = np.argsort(level_of, kind="stable")
        first = np.cumsum(per_level) - per_level  # each level's start there
        rank = np.arange(n) - np.repeat(first, per_level)
        remap = np.empty(n, np.int64)
        remap[by_level] = np.repeat(np.cumsum(padded) - padded, per_level) + rank

        # every hole of the plan in (entry, encounter) order, then the
        # same grouping by its entry's level
        holes_of = np.fromiter((len(h) for _l, _t, h in entries), np.int64, n)
        holes = np.array(
            [ref for _l, _t, refs in entries for ref in refs], np.int64
        ).reshape(-1, 2)
        owner = np.repeat(np.arange(n), holes_of)
        hole_at = offsets[owner] + holes[:, 0]
        hole_to = remap[holes[:, 1]]
        hole_level = level_of[owner]
        holes_by_level = np.argsort(hole_level, kind="stable")
        hole_ends = np.cumsum(np.bincount(hole_level, minlength=len(per_level)))
        hole_starts = np.concatenate(([0], hole_ends[:-1]))

        levels = []
        scratch = len(blob) - 32  # scatter target for hole padding rows
        for lvl, real in enumerate(per_level):
            idxs = by_level[first[lvl] : first[lvl] + real]
            off = np.zeros(padded[lvl], np.int32)
            ln = np.zeros(padded[lvl], np.int32)
            off[:real] = offsets[idxs]
            ln[:real] = lens[idxs]
            mine = holes_by_level[hole_starts[lvl] : hole_ends[lvl]]
            hpad = _pow2(len(mine)) if len(mine) else 1
            hole_pos = np.full(hpad, scratch, np.int32)
            hole_child = np.zeros(hpad, np.int32)
            hole_pos[: len(mine)] = hole_at[mine]
            hole_child[: len(mine)] = hole_to[mine]
            levels.append((off, ln, hole_pos, hole_child))
        out_rows = None
        if out_gis:
            out_rows = remap[np.asarray(out_gis, np.int64)].astype(np.int32)
        return HashPlan(
            blob=blob,
            levels=levels,
            n_nodes=n,
            root_pos=int(remap[root_gi]),
            out_rows=out_rows,
            used=pos,
        )


def plan_payload_bytes(plan: HashPlan) -> int:
    """Total template bytes of one plan — the shippable payload weighed by
    the offload gate (ops/root_engine.py) and the scheduler's root-job
    byte accounting; the pow2 blob padding is slack, not payload. ONE
    definition so the two can never drift."""
    return int(sum(int(ln.sum()) for _o, ln, _h, _c in plan.levels))


def build_hash_plan(trie: Trie) -> Optional[HashPlan]:
    """Walk the trie into a HashPlan, or None when any node encodes < 32B
    (embedded-node rule: those tries take the CPU path)."""
    if trie.root is None:
        return None
    builder = PlanBuilder()
    res = builder.try_subtree(trie.root)
    if res is None:
        return None
    return builder.finish(res[0])


# ---------------------------------------------------------------------------
# the served layout: strips on a ladder
# ---------------------------------------------------------------------------

#: rows hashed per step of the served program, and the child digests one
#: step may scatter. A branch has at most 17 holes, so any row fits a strip.
STRIP_ROWS = 128
STRIP_HOLES = 256


@dataclass(frozen=True)
class Rung:
    """One compiled shape of the served root program: strips the index
    arrays hold, blob bytes, digest rows read back. The three grow
    together, so the program's shapes are the rungs and nothing else."""

    steps: int
    blob: int
    outs: int


#: THE shape set of `_hash_plan_outputs` (PERF.md, fault 0a): a lone
#: 225-tx block under a 2^20 genesis is 20 strips, 0.5 MB and 17 digests
#: and sits well inside rung 0; 128 such requests coalesced fill rung 7.
#: A batch over the top rung is hashed on the host.
PLAN_LADDER: Tuple[Rung, ...] = tuple(
    Rung(32 << k, (1 << 20) << k, 64 << k) for k in range(8)
)


@dataclass
class StripPlan:
    """K HashPlans merged into the served program's layout: the rows of
    every level cut into strips of STRIP_ROWS (a strip never spans two
    levels, so a strip's children all lie in earlier strips), the strips
    stacked into arrays of the rung's shape. The program walks the first
    `n_steps` strips and no more: the rest of the rung is never hashed."""

    blob: np.ndarray  # (rung.blob,) uint8
    off: np.ndarray  # (steps, STRIP_ROWS) int32
    ln: np.ndarray  # (steps, STRIP_ROWS) int32, 0 = pad row
    hole_pos: np.ndarray  # (steps, STRIP_HOLES) int32
    hole_child: np.ndarray  # (steps, STRIP_HOLES) int32 digest rows
    out_rows: np.ndarray  # (rung.outs,) int32, the last repeated as padding
    n_steps: int
    n_nodes: int
    used: int  # template bytes, packed from the blob's head
    rung: int  # index into PLAN_LADDER: with the device, the jit key


def _cut_strips(holes_of_row: np.ndarray) -> np.ndarray:
    """Strip number (from 0) of each row of one level: STRIP_ROWS rows a
    strip, fewer where their holes would pass STRIP_HOLES."""
    n = len(holes_of_row)
    strip = np.arange(n) // STRIP_ROWS
    if np.bincount(strip, weights=holes_of_row).max() <= STRIP_HOLES:
        return strip
    k = rows = holes = 0
    for i, h in enumerate(holes_of_row.tolist()):
        if rows == STRIP_ROWS or holes + h > STRIP_HOLES:
            k, rows, holes = k + 1, 0, 0
        strip[i] = k
        rows += 1
        holes += h
    return strip


def merge_plans(
    plans: Sequence[HashPlan], lease=None
) -> Tuple[Optional[StripPlan], List[np.ndarray]]:
    """K independent HashPlans fused into ONE device plan on a rung of
    PLAN_LADDER — the cross-request coalescing behind the serving
    post-root path (ops/root_engine.py): level l of the merged plan is the
    concatenation of every input plan's level l, cut into strips, so one
    dispatch hashes all K requests' dirty subtrees. Row and hole indices
    are remapped into the strips' row space; the templates are packed
    head to tail and the blob's last 32 bytes take the pad holes.

    Returns (merged plan, per-input-plan merged out rows — same order as
    each plan's own out_rows, defaulting to [root]); the plan is None
    where the batch is over the ladder's top rung. `lease(n)` hands in a
    pooled buffer of n bytes, zero wherever no earlier merge wrote (the
    serving staging lease); omitted, a fresh buffer is allocated."""
    useds = [p.used or len(p.blob) for p in plans]
    shifts = np.cumsum([0] + useds)
    pos = int(shifts[-1])

    # local padded-row -> merged row maps (pad rows map to 0; only pad
    # holes reference them and those are dropped below)
    local_maps = [
        np.zeros(sum(len(off) for off, _l, _p, _c in p.levels), np.int64)
        for p in plans
    ]
    local_starts = [
        np.cumsum([0] + [len(off) for off, _l, _p, _c in p.levels]) for p in plans
    ]
    rows_at: List[np.ndarray] = []  # per level: merged row of each real row
    offs: List[np.ndarray] = []
    lns: List[np.ndarray] = []
    holes_at: List[np.ndarray] = []  # per level: flat slot of each real hole
    hps: List[np.ndarray] = []
    hcs: List[np.ndarray] = []
    step = 0
    for lvl in range(max(len(p.levels) for p in plans)):
        l_off, l_ln, l_hp, l_hc, l_hrow, l_plan = [], [], [], [], [], []
        n = 0
        for pi, p in enumerate(plans):
            if lvl >= len(p.levels):
                continue
            off, ln, hp, hc = p.levels[lvl]
            n_real = int(np.count_nonzero(ln))
            l_plan.append((pi, n, n_real))
            l_off.append(off[:n_real] + shifts[pi])
            l_ln.append(ln[:n_real])
            # real holes only: pad holes point at the plan's own scratch
            real_h = hp != (len(p.blob) - 32)
            if real_h.any():
                l_hp.append(hp[real_h] + shifts[pi])
                # children live at strictly lower levels, already mapped
                l_hc.append(local_maps[pi][hc[real_h]])
                # holes are listed row by row, and a level's rows lie in
                # the blob in their own order
                l_hrow.append(
                    n + np.searchsorted(off[:n_real], hp[real_h], side="right") - 1
                )
            n += n_real
        if not n:
            continue
        hrow = np.concatenate(l_hrow) if l_hrow else np.zeros(0, np.int64)
        strip = _cut_strips(np.bincount(hrow, minlength=n))
        first = np.searchsorted(strip, np.arange(strip[-1] + 1))
        rows = (step + strip) * STRIP_ROWS + np.arange(n) - first[strip]
        for pi, at, n_real in l_plan:
            s0 = local_starts[pi][lvl]
            local_maps[pi][s0 : s0 + n_real] = rows[at : at + n_real]
        rows_at.append(rows)
        offs += l_off
        lns += l_ln
        if len(hrow):
            hstrip = strip[hrow]
            hfirst = np.searchsorted(hstrip, np.arange(strip[-1] + 1))
            holes_at.append(
                (step + hstrip) * STRIP_HOLES + np.arange(len(hrow)) - hfirst[hstrip]
            )
            hps += l_hp
            hcs += l_hc
        step += int(strip[-1]) + 1

    outs: List[np.ndarray] = []
    for pi, p in enumerate(plans):
        rows = (
            p.out_rows
            if p.out_rows is not None
            else np.asarray([p.root_pos], np.int32)
        )
        outs.append(local_maps[pi][rows].astype(np.int32))
    out_rows = np.concatenate(outs)
    need = pos + MPT_MAX_CHUNKS * RATE + 32
    rung = next(
        (
            k
            for k, r in enumerate(PLAN_LADDER)
            if r.steps >= step and r.blob >= need and r.outs >= len(out_rows)
        ),
        None,
    )
    if rung is None:
        return None, outs
    r = PLAN_LADDER[rung]
    blob = lease(r.blob) if lease is not None else np.zeros(r.blob, np.uint8)
    for p, sp, used in zip(plans, shifts, useds):
        blob[sp : sp + used] = p.blob[:used]
    off = np.zeros((r.steps, STRIP_ROWS), np.int32)
    ln = np.zeros((r.steps, STRIP_ROWS), np.int32)
    at = np.concatenate(rows_at)
    off.reshape(-1)[at] = np.concatenate(offs)
    ln.reshape(-1)[at] = np.concatenate(lns)
    hole_pos = np.full((r.steps, STRIP_HOLES), r.blob - 32, np.int32)
    hole_child = np.zeros((r.steps, STRIP_HOLES), np.int32)
    if holes_at:
        at = np.concatenate(holes_at)
        hole_pos.reshape(-1)[at] = np.concatenate(hps)
        hole_child.reshape(-1)[at] = np.concatenate(hcs)
    padded = np.full(r.outs, out_rows[-1], np.int32)
    padded[: len(out_rows)] = out_rows
    merged = StripPlan(
        blob=blob,
        off=off,
        ln=ln,
        hole_pos=hole_pos,
        hole_child=hole_child,
        out_rows=padded,
        n_steps=step,
        n_nodes=sum(p.n_nodes for p in plans),
        used=pos,
        rung=rung,
    )
    return merged, outs


# ---------------------------------------------------------------------------
# device executor
# ---------------------------------------------------------------------------


def plan_digests_host(plan: HashPlan) -> np.ndarray:
    """CPU mirror of the fused device executor: recompute EVERY node digest
    from the plan's templates (scatter child digests into the holes, batch
    keccak each level through the native library). This is the honest CPU
    baseline for the device state-root path — identical inputs, identical
    recompute-all-hashes semantics, best available host implementation
    (no RLP re-encoding, one keccak FFI batch per level). Returns the full
    (total_pad, 32) u8 digest buffer in the padded row space."""
    from phant_tpu.crypto.keccak import keccak256
    from phant_tpu.utils.native import load_native

    native = load_native()
    blob = plan.blob.copy()
    total_pad = sum(len(off) for off, _l, _p, _c in plan.levels)
    digests = np.zeros((total_pad, 32), np.uint8)
    out_start = 0
    pos32 = np.arange(32)
    for off, ln, hole_pos, hole_child in plan.levels:
        child = digests[hole_child]  # (H, 32)
        blob[hole_pos[:, None] + pos32[None, :]] = child
        payloads = [
            blob[off[k] : off[k] + ln[k]].tobytes() for k in range(len(off))
        ]
        if native is not None:
            hashed = native.keccak256_batch_fast(payloads)
        else:
            hashed = [keccak256(p) for p in payloads]
        digests[out_start : out_start + len(off)] = [
            np.frombuffer(h, np.uint8) for h in hashed
        ]
        out_start += len(off)
    return digests


def execute_plan_host(plan: HashPlan) -> bytes:
    """Host plan execution returning the root digest (see
    plan_digests_host)."""
    return plan_digests_host(plan)[plan.root_pos].tobytes()


def execute_plan_outputs_host(plan: HashPlan) -> List[bytes]:
    """Host plan execution returning the `out_rows` digests (root-only
    when the plan has none) — the CPU twin of `_hash_plan_outputs`."""
    digests = plan_digests_host(plan)
    rows = (
        plan.out_rows
        if plan.out_rows is not None
        else np.asarray([plan.root_pos], np.int64)
    )
    return [digests[int(r)].tobytes() for r in rows]


def _plan_digests_body(blob, levels, *, max_chunks: int):
    """Execute a whole HashPlan in ONE device program: for each level
    (statically unrolled; shapes are the jit cache key) scatter the child
    digests into the template holes, hash the level with the batched keccak
    kernel, and append to the digest buffer. One dispatch replaces the
    per-level round trips of the old executor — on a high-latency link that
    is the difference between ~1x and ~{levels}x RTT per root. Returns the
    full (total_pad, 8) u32 digest buffer."""
    total_pad = sum(off.shape[0] for off, _l, _p, _c in levels)
    digests = jnp.zeros((total_pad, 8), jnp.uint32)
    shifts = jnp.arange(4, dtype=jnp.uint32) * 8
    pos32 = jnp.arange(32, dtype=jnp.int32)
    out_start = 0
    for off, ln, hole_pos, hole_child in levels:
        d = digests[hole_child]  # (H, 8)
        dbytes = ((d[:, :, None] >> shifts[None, None, :]) & 0xFF).astype(jnp.uint8)
        flat = hole_pos[:, None] + pos32[None, :]
        blob = blob.at[flat.reshape(-1)].set(dbytes.reshape(-1))
        level_digests = witness_digests(blob, off, ln, max_chunks=max_chunks)
        digests = jax.lax.dynamic_update_slice(
            digests, level_digests, (out_start, 0)
        )
        out_start += off.shape[0]
    return digests


def _hash_plan_body(blob, levels, *, max_chunks: int):
    """(8,) u32 root digest words (the root is the unique max-level node,
    laid out last by PlanBuilder.finish). Unjitted body so
    `_hash_plans_batched` can vmap it over a batch of blobs; the scalar
    entry point `_hash_plan_fused` wraps it in jit."""
    return _plan_digests_body(blob, levels, max_chunks=max_chunks)[-1]


@functools.partial(jax.jit, static_argnames=("max_chunks",))
def _hash_plan_outputs(
    blob, off, ln, hole_pos, hole_child, n_steps, out_rows, *, max_chunks: int
):
    """A StripPlan executed in ONE device program, returning only the
    requested digest rows — the serving post-root executor
    (ops/root_engine.py): one dispatch hashes a MERGED multi-request plan
    and reads back each request's storage roots + account root ((R, 8)
    u32), nothing else. One loop over the first `n_steps` strips (a run
    time number: the rung's empty strips cost nothing): scatter the
    strip's child digests into its templates' holes, hash its rows, keep
    the digests. The jit cache key is the rung's shapes (PLAN_LADDER)."""
    steps, rows = off.shape
    shifts = jnp.arange(4, dtype=jnp.uint32) * 8
    pos32 = jnp.arange(32, dtype=jnp.int32)

    def strip(t, carry):
        blob, digests = carry
        d = digests[hole_child[t]]  # (H, 8)
        dbytes = ((d[:, :, None] >> shifts[None, None, :]) & 0xFF).astype(jnp.uint8)
        flat = hole_pos[t][:, None] + pos32[None, :]
        blob = blob.at[flat.reshape(-1)].set(dbytes.reshape(-1))
        hashed = witness_digests(blob, off[t], ln[t], max_chunks=max_chunks)
        return blob, jax.lax.dynamic_update_slice(digests, hashed, (t * rows, 0))

    digests = jnp.zeros((steps * rows, 8), jnp.uint32)
    _blob, digests = jax.lax.fori_loop(0, n_steps, strip, (blob, digests))
    return digests[out_rows]


_hash_plan_fused = functools.partial(jax.jit, static_argnames=("max_chunks",))(
    _hash_plan_body
)


@functools.partial(jax.jit, static_argnames=("max_chunks",))
def _hash_plans_batched(blobs, levels, *, max_chunks: int):
    """K state roots in ONE dispatch: vmap the fused plan executor over a
    (K, L) batch of template blobs sharing one level layout. This is the
    production shape for block replay — K consecutive block states of the
    same account trie differ only in leaf *values*, so the structural plan
    (offsets/holes) is shared and only the blobs vary. Amortizes the
    host->device round trip over K roots (the per-root RTT is what the
    offload gate rejects at K=1 on a slow host<->device link)."""
    return jax.vmap(
        lambda b: _hash_plan_body(b, levels, max_chunks=max_chunks)
    )(blobs)


def plans_share_structure(a: HashPlan, b: HashPlan) -> bool:
    """True when two plans have identical level layouts (offsets, lengths,
    hole positions, hole children) and blob sizes — the precondition for
    vmapping them through one `_hash_plans_batched` dispatch. Consecutive
    block states of the same account trie share structure whenever only
    fixed-width leaf values changed; an account birth/death or a
    variable-width RLP growth breaks the run. The replay segment lowerer
    (phant_tpu/replay/lowering.py) uses this to group a segment's
    per-block plans into maximal batchable runs instead of failing the
    whole segment on the first mismatch."""
    if len(a.blob) != len(b.blob) or len(a.levels) != len(b.levels):
        return False
    for (o1, l1, h1, c1), (o2, l2, h2, c2) in zip(a.levels, b.levels):
        if (
            o1.shape != o2.shape
            or not np.array_equal(o1, o2)
            or not np.array_equal(l1, l2)
            or not np.array_equal(h1, h2)
            or not np.array_equal(c1, c2)
        ):
            return False
    return True


def trie_roots_device_batched(plans: List[HashPlan]) -> List[bytes]:
    """Roots for K same-structure plans (identical level layouts, differing
    blobs) in one fused device dispatch. Raises ValueError if the plans'
    layouts differ (callers batch consecutive block states, which share
    structure by construction when leaf values are fixed-width)."""
    if not plans:
        return []
    ref = plans[0]
    for p in plans[1:]:
        if not plans_share_structure(p, ref):
            raise ValueError("batched plans must share structure")
    blobs = jnp.asarray(np.stack([p.blob for p in plans]))
    # per-LEVEL metadata uploads, bounded by trie depth (~8 tiny arrays) —
    # not a data-axis loop; the node axis itself ships in the one blob above
    levels_d = tuple(tuple(jnp.asarray(a) for a in lvl) for lvl in ref.levels)  # phantlint: disable=JNPHOSTLOOP — bounded per-level metadata upload
    roots = _hash_plans_batched(blobs, levels_d, max_chunks=MPT_MAX_CHUNKS)
    arr = np.asarray(roots, dtype="<u4")
    return [arr[k].tobytes() for k in range(arr.shape[0])]


def trie_root_device(trie: Trie, plan: Optional[HashPlan] = None) -> bytes:
    """Trie root with all keccak hashing on device in a single fused
    dispatch; CPU fallback for tries with embedded nodes.

    Plans are cached on the trie per mutation epoch (phant_tpu/mpt/mpt.py
    bumps `_epoch` on put/delete): an unchanged trie re-executes the full
    hash pipeline from device-resident templates — every digest is
    recomputed on device each call, only the host packing is reused."""
    if trie.root is None:
        return EMPTY_TRIE_ROOT
    if plan is None:
        epoch = getattr(trie, "_epoch", None)
        cached = getattr(trie, "_device_plan", None)
        if cached is not None and epoch is not None and cached[0] == epoch:
            plan = cached[1]
        else:
            plan = build_hash_plan(trie)
            if plan is not None and epoch is not None:
                trie._device_plan = (epoch, plan)
    if plan is None:
        return trie.root_hash()

    if plan.device_args is None:
        # memoized ONCE per plan; bounded by trie depth like the batched twin
        levels_d = tuple(
            tuple(jnp.asarray(a) for a in lvl) for lvl in plan.levels  # phantlint: disable=JNPHOSTLOOP — bounded per-level metadata upload
        )
        plan.device_args = (jnp.asarray(plan.blob), levels_d)
    blob_d, levels_d = plan.device_args
    assert plan.root_pos == sum(len(off) for off, _l, _p, _c in plan.levels) - 1
    root_words = _hash_plan_fused(blob_d, levels_d, max_chunks=MPT_MAX_CHUNKS)
    # the 32-byte root is the product — this readback is the function's
    # contract, not an accidental sync
    return np.asarray(root_words, dtype="<u4").tobytes()  # phantlint: disable=HOSTSYNC — root readback is the product
