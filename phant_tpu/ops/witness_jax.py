"""Fused block-witness verification on device.

The device program receives exactly the bytes a stateless client receives —
the concatenated RLP witness nodes (blob) plus tiny metadata — and does
everything else on device: unpack each node from the blob (gather),
keccak-pad it, hash it with the batched keccak kernel, and reduce a
per-block verdict (does some node hash to the block's expected root?).
Host->device traffic is therefore the witness itself, not a padded layout
(~4x smaller, and no host-side packing loop at all).

Reference scope: the keccak/MPT hot loop (src/crypto/hasher.zig:4-17,
src/mpt/mpt.zig:38-119); the batching axis and the on-device verdict are
this framework's addition per the north star (BASELINE.json).
"""

from __future__ import annotations

import functools
from typing import List, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from phant_tpu.crypto.keccak import RATE
from phant_tpu.ops.keccak_jax import keccak256_chunked_auto
from phant_tpu.utils.rungs import pow2ceil as _pow2ceil

# Bucket bound for witness nodes: RLP trie nodes are <= 576B (BASELINE.md),
# and 576 < 5 * 136. Shared by __graft_entry__.py / tests.
WITNESS_MAX_CHUNKS = 5

CHUNK_WORDS = RATE // 4  # u32 words of one rate chunk of a node row


def _gather_node_rows(blob, offsets, lens, row: int):
    """(B, row) uint8 — each node's bytes sliced out of the blob, zeroed
    past its length."""
    pos = jnp.arange(row, dtype=jnp.int32)[None, :]  # (1, row)
    idx = offsets[:, None] + pos  # (B, row)
    data = jnp.take(blob, idx, mode="clip")
    return jnp.where(pos < lens[:, None], data, jnp.uint8(0))


def _row_words(data):
    """(B, row // 4) u32 little-endian words of (B, row) u8 node rows: the
    ROW FORM every per-node function below takes. A blob-form program packs
    the rows it gathered once; the resident update is handed this form by
    the host (`pack_node_rows`) and never sees a byte-granular array."""
    b = data.reshape(data.shape[0], -1, 4).astype(jnp.uint32)
    return b[..., 0] | (b[..., 1] << 8) | (b[..., 2] << 16) | (b[..., 3] << 24)


def _digests_from_rows(words, lens, *, max_chunks: int):
    """Keccak-pad node rows (u32 words, zero past each node's length) and
    hash them (shared by the meta, fused and resident programs, so each
    hashes the same rows it parses)."""
    wpos = jnp.arange(max_chunks * CHUNK_WORDS, dtype=jnp.int32)[None, :]
    # keccak multi-rate padding: 0x01 after the payload, 0x80 at the end of
    # the last rate block, each XORed into the word that holds its byte
    nchunks = lens // RATE + 1
    pad01 = jnp.uint32(1) << (8 * (lens & 3)).astype(jnp.uint32)
    padded = (
        words
        ^ jnp.where(wpos == (lens >> 2)[:, None], pad01[:, None], jnp.uint32(0))
        ^ jnp.where(
            wpos == (nchunks * CHUNK_WORDS - 1)[:, None],
            jnp.uint32(0x80000000),
            jnp.uint32(0),
        )
    )
    return keccak256_chunked_auto(
        padded.reshape(words.shape[0], max_chunks, CHUNK_WORDS),
        nchunks,
        max_chunks=max_chunks,
    )


@functools.partial(jax.jit, static_argnames=("max_chunks",))
def witness_digests(
    blob: jax.Array,
    offsets: jax.Array,
    lens: jax.Array,
    *,
    max_chunks: int,
) -> jax.Array:
    """Hash every node sliced out of `blob` on device.

    Args:
      blob: (L,) uint8 — concatenated node payloads, L >= max offset+len and
        padded with at least max_chunks*RATE trailing zeros (gather slack).
      offsets: (B,) int32 — start of node i in blob.
      lens: (B,) int32 — byte length of node i (0 = padding row).
      max_chunks: static bucket bound (rate chunks per node).

    Returns:
      (B, 8) uint32 digests (little-endian words).
    """
    words = _row_words(_gather_node_rows(blob, offsets, lens, max_chunks * RATE))
    return _digests_from_rows(words, lens, max_chunks=max_chunks)


# ---------------------------------------------------------------------------
# linked (full multiproof) verification
# ---------------------------------------------------------------------------


def _gather_refs(blob, ref_off):
    """(M, 8) u32 little-endian words of the 32-byte refs at `ref_off`."""
    idx = jnp.maximum(ref_off, 0)[:, None] + jnp.arange(32, dtype=jnp.int32)[None, :]
    b = jnp.take(blob, idx, mode="clip").astype(jnp.uint32).reshape(-1, 8, 4)
    return b[..., 0] | (b[..., 1] << 8) | (b[..., 2] << 16) | (b[..., 3] << 24)


# sentinel block id for pad refs: matches nothing. A plain int (NOT a jnp
# array) so importing this module for its host-side helpers never triggers
# jax backend initialization.
_DEAD_BLOCK = 2**30


def _referenced(digests, block_id, refs, ref_block, ref_live):
    """(N,) bool: node i's digest appears among its own block's child refs.

    Exact 256-bit equality (soundness: a truncated fingerprint would let an
    adversary link a foreign node with a crafted collision), computed as a
    sort-join instead of an (N, M) compare matrix: stack refs and digests as
    rows keyed by (block, 8 digest words), order them lexicographically, mark
    equal-key runs, and flag a digest row iff its run contains a live ref.
    O((N+M) log(N+M)) work vs O(N*M*8) for the matrix — at mainnet shapes the
    matrix would rival the keccak cost itself.

    The lexicographic order is nine STABLE single-key sorts, least
    significant key first, as ONE `lax.sort` op inside a scan: the TPU
    compiler's time for a sort grows with its operand and key count (the
    former 11-operand 9-key `lax.sort` took 513 s to compile for a v5e at
    1024 nodes and 1055 s at 8192; this form takes ~20 s, PR 24 rehearsal).
    Its run time against the single sort's is not measured: the old form
    could not be compiled within a chip call."""
    N = digests.shape[0]
    M = refs.shape[0]
    block = jnp.concatenate(
        [
            jnp.where(ref_live, ref_block, jnp.int32(_DEAD_BLOCK)),
            block_id.astype(jnp.int32),
        ]
    )
    words = [jnp.concatenate([refs[:, k], digests[:, k]]) for k in range(8)]
    is_digest = jnp.concatenate(
        [jnp.zeros((M,), jnp.uint32), jnp.ones((N,), jnp.uint32)]
    )
    src = jnp.concatenate(
        [jnp.full((M,), N, jnp.uint32), jnp.arange(N, dtype=jnp.uint32)]
    )
    rows = jnp.stack([block.astype(jnp.uint32), *words, is_digest, src])
    # identity permutation derived from the input so it inherits the
    # input's varying-manual-axes under shard_map (a fresh arange would be
    # replicated and break the scan carry typing)
    perm0 = jnp.cumsum(jnp.ones_like(block)) - 1

    def by_key(perm, key):
        _k, perm = jax.lax.sort((key[perm], perm), num_keys=1, is_stable=True)
        return perm, None

    perm, _ = jax.lax.scan(by_key, perm0, rows[8::-1])  # w7 .. w0, block
    sb, *sw, stag, ssrc = rows[:, perm]
    eq_prev = sb[1:] == sb[:-1]
    for w in sw:
        eq_prev = eq_prev & (w[1:] == w[:-1])
    eq_prev = jnp.concatenate([jnp.zeros((1,), bool), eq_prev])
    run_id = jnp.cumsum((~eq_prev).astype(jnp.int32)) - 1
    live_ref_row = (stag == 0) & (sb < _DEAD_BLOCK)
    run_has_ref = (
        jnp.zeros((N + M,), jnp.int32).at[run_id].max(live_ref_row.astype(jnp.int32))
    )
    row_ref = run_has_ref[run_id] > 0
    # scatter digest rows' flags back to node order (ref rows dump to slot N)
    out = (
        jnp.zeros((N + 1,), jnp.int32)
        .at[jnp.where(stag == 1, ssrc, jnp.uint32(N))]
        .max(row_ref.astype(jnp.int32))
    )
    return out[:N] > 0


def linked_verdict(digests, lens, block_id, refs, ref_block, ref_live, roots, n_blocks: int):
    """Per-block (root_hit, all_linked) partials as int32 arrays.

    A block verifies iff some node hashes to its root AND every node is
    either that root or hash-referenced by another witness node of the same
    block. Hash references are acyclic (a cycle would be a keccak collision),
    so this is exactly 'the witness is a connected subtree rooted at the
    claimed root' — the real multiproof verdict, not just root membership.
    Shared between the single-chip kernel and the dp-sharded path (which
    combines partials with pmax/pmin over the mesh)."""
    valid = lens > 0
    is_root = jnp.all(digests == roots[block_id], axis=1) & valid
    referenced = _referenced(digests, block_id, refs, ref_block, ref_live)
    ok_node = (~valid) | is_root | referenced
    root_hit = (
        jnp.zeros((n_blocks,), jnp.int32).at[block_id].max(is_root.astype(jnp.int32))
    )
    all_ok = (
        jnp.ones((n_blocks,), jnp.int32)
        .at[jnp.where(valid, block_id, 0)]
        .min(jnp.where(valid, ok_node, True).astype(jnp.int32))
    )
    return root_hit, all_ok


@functools.partial(jax.jit, static_argnames=("max_chunks", "n_blocks"))
def witness_verify_linked(
    blob: jax.Array,
    meta: jax.Array,
    ref_meta: jax.Array,
    roots: jax.Array,
    *,
    max_chunks: int,
    n_blocks: int,
) -> jax.Array:
    """Full multiproof witness verification on device.

    meta: (3, B) int32 — (offsets, lens, block_id) per node (0-len = pad).
    ref_meta: (2, R) int32 — (blob offset, block_id) of every 32-byte child
      hash reference inside the witness nodes (host-scanned, -1 offset = pad).
    roots: (n_blocks, 8) uint32.

    Returns (n_blocks,) bool. Unlike plain root membership,
    a block passes only if its nodes form a connected subtree rooted at the
    expected root — a witness with a broken parent->child link is rejected.
    """
    offsets, lens, block_id = meta[0], meta[1], meta[2]
    digests = witness_digests(blob, offsets, lens, max_chunks=max_chunks)
    refs = _gather_refs(blob, ref_meta[0])
    root_hit, all_ok = linked_verdict(
        digests, lens, block_id, refs, ref_meta[1], ref_meta[0] >= 0, roots, n_blocks
    )
    return (root_hit > 0) & (all_ok > 0)


# ---------------------------------------------------------------------------
# fused verification with ON-DEVICE ref extraction
#
# The RLP child-hash references of a trie node are recoverable from at most
# 17 top-level item-header decodes (all vectorizable gathers):
#   - a 17-item node (branch) references its 32-byte-string children
#     (slots 0..15); embedded (<32B) children cannot themselves contain a
#     33-byte hash reference, so no recursion is ever needed;
#   - a 2-item node is an extension (item1 if a 32-byte string) or a leaf,
#     whose account-shaped value commits a storage root at a fixed offset
#     behind 4 more header decodes.
# Running this on device removes the ref_meta transfer (~8 bytes per ref,
# the second-largest h2d stream after the blob itself) AND the host-side
# native ref scan; the host ships the raw witness plus 4 bytes per node.
# Mirrors native/packer.cc phant_scan_refs / scan_refs_py bit-for-bit
# (differential-tested) except that malformed nodes mark themselves ref-less
# (failing verification) instead of raising.
# ---------------------------------------------------------------------------


def _bytes_at(words, pos):
    """(B,) u32 holding the four bytes of each node row at byte positions
    pos .. pos+3, lowest first; bytes past the row's end read 0 (as a row
    reads past its node's length). Two dense compare-select-sum passes over
    the row's words and a funnel shift: no gather, whatever the position."""
    q = pos >> 2
    wpos = jnp.arange(words.shape[1], dtype=jnp.int32)[None, :]
    zero = jnp.uint32(0)
    w0 = jnp.sum(jnp.where(wpos == q[:, None], words, zero), axis=1, dtype=jnp.uint32)
    w1 = jnp.sum(
        jnp.where(wpos == q[:, None] + 1, words, zero), axis=1, dtype=jnp.uint32
    )
    sh = (8 * (pos & 3)).astype(jnp.uint32)
    return jnp.where(sh == 0, w0, (w0 >> sh) | (w1 << (32 - sh)))


def _take_at(words, idx):
    """(B,) byte of each node row at per-node byte position idx."""
    return (_bytes_at(words, idx) & 0xFF).astype(jnp.int32)


def _decode_rlp_header(words, pos):
    """Vectorized RLP item-header decode at per-node byte position `pos`.

    Returns (payload_start, payload_len, next_pos, ok, is_list, is_ref)
    where is_ref flags exactly the 0xa0 header (32-byte string). Length-of-
    length > 2 cannot occur in <=679B nodes and flags not-ok."""
    head = _bytes_at(words, pos)
    b0 = (head & 0xFF).astype(jnp.int32)
    b1 = ((head >> 8) & 0xFF).astype(jnp.int32)
    b2 = ((head >> 16) & 0xFF).astype(jnp.int32)
    single = b0 < 0x80
    short_str = (b0 >= 0x80) & (b0 <= 0xB7)
    long_str = (b0 >= 0xB8) & (b0 <= 0xBF)
    short_list = (b0 >= 0xC0) & (b0 <= 0xF7)
    long_list = b0 >= 0xF8
    lnl = jnp.where(long_str, b0 - 0xB7, jnp.where(long_list, b0 - 0xF7, 0))
    long_len = jnp.where(lnl == 1, b1, (b1 << 8) | b2)
    plen = jnp.where(
        single,
        1,
        jnp.where(
            short_str, b0 - 0x80, jnp.where(short_list, b0 - 0xC0, long_len)
        ),
    )
    ps = jnp.where(single, pos, pos + 1 + lnl)
    return ps, plen, ps + plen, lnl <= 2, short_list | long_list, b0 == 0xA0


def _extract_ref_positions(words, lens):
    """(B, 17) int32 node-relative offsets of every child hash reference
    (-1 = no ref in that slot). Slots 0..15 are branch children; slot 16 is
    the extension child or the account-leaf storage root."""
    end = lens.astype(jnp.int32)
    zero = jnp.zeros_like(end)
    ps0, _plen0, pe0, ok0, islist0, _ = _decode_rlp_header(words, zero)
    bad = ~(ok0 & islist0 & (pe0 == end) & (end > 0))

    pos = ps0
    item_ps = []
    item_pe = []
    item_ref = []
    item_valid = []
    for _k in range(17):
        ps, _plen, nxt, ok, is_list, is_ref = _decode_rlp_header(words, pos)
        valid = (pos < end) & ~bad
        overrun = valid & (~ok | (nxt > end))
        bad = bad | overrun
        valid = valid & ~overrun
        item_ps.append(jnp.where(valid, ps, 0))
        item_pe.append(jnp.where(valid, nxt, 0))
        item_ref.append(valid & is_ref & ~is_list)
        item_valid.append(valid)
        pos = jnp.where(valid, nxt, pos)
    bad = bad | (pos != end)  # 18+ items, or trailing garbage

    n_items = sum(v.astype(jnp.int32) for v in item_valid)
    is_branch = (n_items == 17) & ~bad
    is_pair = (n_items == 2) & ~bad

    # branch: slots 0..15 that are 32-byte strings
    branch_refs = [
        jnp.where(is_branch & item_ref[k], item_ps[k], -1) for k in range(16)
    ]

    # pair: hex-prefix flag byte of item 0 (empty path = malformed)
    p0 = _take_at(words, item_ps[0])
    nonempty0 = (item_pe[0] - item_ps[0]) > 0
    is_ext = is_pair & nonempty0 & ((p0 & 0x20) == 0)
    is_leaf = is_pair & nonempty0 & ((p0 & 0x20) != 0)
    ext_ref = jnp.where(is_ext & item_ref[1], item_ps[1], -1)

    # leaf: item1 must be a string whose content is a 4-string account list
    # with 32-byte items 2 and 3 (mirrors _account_storage_root_off)
    v_ps, v_pe = item_ps[1], item_pe[1]
    l_ps, _lp, l_pe, l_ok, l_islist, _ = _decode_rlp_header(words, v_ps)
    acct = is_leaf & ~item_ref[1] & l_ok & l_islist & (l_pe == v_pe)
    q_ps, _qp, q_pe, q_ok, q_islist, _ = _decode_rlp_header(words, l_ps)  # nonce
    acct = acct & q_ok & ~q_islist & (q_pe <= l_pe)
    r_ps, _rp, r_pe, r_ok, r_islist, _ = _decode_rlp_header(words, q_pe)  # balance
    acct = acct & r_ok & ~r_islist & (r_pe <= l_pe)
    acct = (
        acct
        & (_take_at(words, r_pe) == 0xA0)
        & (_take_at(words, r_pe + 33) == 0xA0)
        & (r_pe + 66 == l_pe)
    )
    leaf_ref = jnp.where(acct, r_pe + 1, -1)

    slot16 = jnp.where(is_ext, ext_ref, leaf_ref)
    return jnp.stack(branch_refs + [slot16], axis=1)


def _ref_words_from_rows(words, ref_pos):
    """(B, 17, 8) u32 LE words of the 32-byte refs at node-relative byte
    positions ref_pos; a dead slot (ref_pos < 0) reads all zero.

    A ref at byte p lies in the nine row words from p >> 2 on. They are
    brought to the front by a barrel shifter over the row's words — one
    select between two static slices per bit of the word offset, the
    window narrowing as the remaining shift does — and a funnel shift by
    the byte remainder makes the eight ref words: dense moves whose unit
    is the row, where a byte gather moved 17 x 32 single bytes a node."""
    B, W = words.shape
    live = ref_pos >= 0
    p = jnp.where(live, ref_pos, 0)
    q = (p >> 2)[:, :, None]
    top = 1
    while 2 * top < W:
        top *= 2
    x = jnp.pad(words, ((0, 0), (0, max(0, 2 * top + 8 - W))))[:, None, :]
    bit = top
    while bit:
        keep = bit + 8  # the shifts still to come are below `bit`, then 9 words
        x = jnp.where((q & bit) != 0, x[..., bit : bit + keep], x[..., :keep])
        bit //= 2
    sh = (8 * (p & 3)).astype(jnp.uint32)[:, :, None]
    lo, hi = x[..., :8], x[..., 1:9]
    out = jnp.where(sh == 0, lo, (lo >> sh) | (hi << (32 - sh)))
    return jnp.where(live[:, :, None], out, jnp.uint32(0))


def node_row_features(words, lens, *, max_chunks: int):
    """(digests, ref_words, ref_live) of node rows in the row form: (B,
    max_chunks * 34) u32 words, one node a row, zero past its length
    (`lens`; 0 = a pad row). The per-node features the device-resident
    intern table persists (ops/witness_resident.py): digest (B, 8), the
    up-to-17 child-hash reference words (B, 17, 8; dead slots zero) and
    which ref slots are live (B, 17). Composes inside jit. The hashing and
    the ref parse are the functions `witness_verify_fused` runs inline, on
    the same bytes: the two can never diverge on ref semantics (malformed
    nodes are ref-less on both)."""
    digests = _digests_from_rows(words, lens, max_chunks=max_chunks)
    ref_pos = _extract_ref_positions(words, lens)
    refs = _ref_words_from_rows(words, ref_pos)
    ref_live = (ref_pos >= 0) & (lens[:, None] > 0)
    return digests, refs, ref_live


def witness_node_features(blob, offsets, lens, *, max_chunks: int):
    """`node_row_features` of every node sliced out of `blob` on the
    device: the BLOB form, one byte gather a row position
    (`_gather_node_rows`). The resident update left it in PR 31 (the host
    lays the rows out, `pack_node_rows`); it stays as the form the row
    form is tested against, and for callers that hold a blob."""
    words = _row_words(_gather_node_rows(blob, offsets, lens, max_chunks * RATE))
    return node_row_features(words, lens, max_chunks=max_chunks)


@functools.partial(jax.jit, static_argnames=("max_chunks", "n_blocks"))
def witness_verify_fused(
    blob: jax.Array,
    meta16: jax.Array,
    roots: jax.Array,
    *,
    max_chunks: int,
    n_blocks: int,
) -> jax.Array:
    """Full linked multiproof verification from the raw witness alone.

    meta16: (2, B) uint16 — (len, block_id) per node, in blob order (0-len =
      pad). Offsets are an on-device exclusive cumsum: the blob IS the
      concatenation of the nodes. Child references are parsed out of the
      node bytes on device (_extract_ref_positions) — host->device traffic
      is the witness bytes + 4 bytes per node, nothing else.

    Semantics identical to witness_verify_linked: a block verifies iff its
    nodes form a connected subtree rooted at its expected root.
    """
    lens = meta16[0].astype(jnp.int32)
    block_id = meta16[1].astype(jnp.int32)
    offsets = jnp.cumsum(lens) - lens  # exclusive
    words = _row_words(_gather_node_rows(blob, offsets, lens, max_chunks * RATE))
    digests = _digests_from_rows(words, lens, max_chunks=max_chunks)
    ref_pos = _extract_ref_positions(words, lens)
    refs = _ref_words_from_rows(words, ref_pos).reshape(-1, 8)
    ref_live = (ref_pos >= 0).reshape(-1)
    ref_block = jnp.broadcast_to(block_id[:, None], ref_pos.shape).reshape(-1)
    root_hit, all_ok = linked_verdict(
        digests, lens, block_id, refs, ref_block, ref_live, roots, n_blocks
    )
    return (root_hit > 0) & (all_ok > 0)


def pack_witness_fused(
    node_lists: Sequence[Sequence[bytes]],
    max_chunks: int,
    pad_nodes_to: int | None = None,
    min_pad: int = 1,
) -> Tuple[np.ndarray, np.ndarray]:
    """(blob, meta16) for `witness_verify_fused`: the concatenated witness
    bytes plus (2, B) uint16 (len, block_id) rows — no offsets, no host ref
    scan. The cheapest possible host-side layout (~4 bytes/node of metadata
    vs 12 + 8/ref for the explicit-refs path)."""
    parts: List[bytes] = [n for nodes in node_lists for n in nodes]
    B = len(parts)
    counts = np.fromiter(
        (len(nodes) for nodes in node_lists), np.int64, len(node_lists)
    )
    lens_arr = np.fromiter((len(n) for n in parts), np.int64, B)
    if len(node_lists) > 0xFFFF:
        raise ValueError("block_id exceeds uint16; split the batch")
    if B and (lens_arr // RATE + 1 > max_chunks).any():
        raise ValueError(
            f"node of {int(lens_arr.max())}B exceeds bucket bound {max_chunks}"
        )
    if int(lens_arr.sum()) >= 2**31:
        raise ValueError("witness blob exceeds int32 offset range; split the batch")
    target = pad_nodes_to
    if target is None:
        target = _pow2ceil(max(B, min_pad))
    if B > target:
        raise ValueError(f"{B} nodes exceed pad_nodes_to={target}")
    meta16 = np.zeros((2, target), np.uint16)
    meta16[0, :B] = lens_arr
    meta16[1, :B] = np.repeat(
        np.arange(len(node_lists), dtype=np.uint16), counts
    )
    blob = np.frombuffer(
        b"".join(parts) + b"\x00" * (max_chunks * RATE), dtype=np.uint8
    )
    return blob, meta16


# ---------------------------------------------------------------------------
# host-side layout
# ---------------------------------------------------------------------------


def pack_witness_blob(
    node_lists: Sequence[Sequence[bytes]], max_chunks: int, pad_nodes_to: int | None = None
) -> Tuple[np.ndarray, np.ndarray]:
    """Concatenate per-block node lists into (blob, meta) where meta is the
    (3, B) int32 array of (offsets, lens, block_id) rows.

    The blob gets max_chunks*RATE trailing zeros of gather slack; the node
    axis is padded to `pad_nodes_to` (default: next power of two) with
    zero-length rows so repeated calls reuse a small set of compiled shapes.
    """
    parts: List[bytes] = [n for nodes in node_lists for n in nodes]
    B = len(parts)
    counts = np.fromiter((len(nodes) for nodes in node_lists), np.int64, len(node_lists))
    lens_arr = np.fromiter((len(n) for n in parts), np.int32, B)
    if int(lens_arr.sum()) >= 2**31:
        raise ValueError("witness blob exceeds int32 offset range; split the batch")
    if B and (lens_arr // RATE + 1 > max_chunks).any():
        worst = int(lens_arr.max())
        raise ValueError(f"node of {worst}B exceeds bucket bound {max_chunks}")
    target = pad_nodes_to
    if target is None:
        target = 1
        while target < max(B, 1):
            target *= 2
    if B > target:
        raise ValueError(f"{B} nodes exceed pad_nodes_to={target}")
    meta = np.zeros((3, target), np.int32)
    if B > 1:
        np.cumsum(lens_arr[:-1], out=meta[0, 1:B])
    meta[1, :B] = lens_arr
    meta[2, :B] = np.repeat(np.arange(len(node_lists), dtype=np.int32), counts)
    blob = np.frombuffer(b"".join(parts) + b"\x00" * (max_chunks * RATE), dtype=np.uint8)
    return blob, meta


def _pack_rows_np(nodes: Sequence[bytes], rows: int, row_bytes: int) -> np.ndarray:
    """numpy twin of native `pack_rows` (one flat scatter of the joined
    bytes): the form where the native library is absent."""
    lens = np.fromiter(map(len, nodes), np.int64, len(nodes))
    if len(nodes) and int(lens.max()) >= row_bytes:
        raise ValueError(f"payload fills its row of {row_bytes} bytes")
    buf = np.zeros(rows * row_bytes, np.uint8)
    total = int(lens.sum())
    if total:
        # byte j of the join lands at its row's start plus its offset in
        # the node: row_start - node_start, repeated over the node's bytes
        shift = np.arange(len(nodes), dtype=np.int64) * row_bytes - (
            np.cumsum(lens) - lens
        )
        buf[np.arange(total, dtype=np.int64) + np.repeat(shift, lens)] = (
            np.frombuffer(b"".join(nodes), np.uint8)
        )
    return buf.reshape(rows, row_bytes)


def pack_node_rows(
    nodes: Sequence[bytes], max_chunks: int, pad_rows_to: int | None = None
) -> Tuple[np.ndarray, np.ndarray]:
    """(words, lens) — the ROW FORM of `nodes` for `node_row_features`:
    words (R, max_chunks * 34) u32, node i the little-endian words of row i,
    zero past its length; lens (R,) int32, 0 in the pad rows. R is
    `pad_rows_to` (default: the next power of two). One native memcpy loop
    (native/packer.cc phant_pack_rows), numpy where the library is absent.
    The upload is R * max_chunks * 136 bytes whatever the nodes hold: the
    padding the blob form gathered on the device is laid out on the host."""
    from phant_tpu.utils.native import load_native

    row_bytes = max_chunks * RATE
    rows = _pow2ceil(len(nodes)) if pad_rows_to is None else pad_rows_to
    if len(nodes) > rows:
        raise ValueError(f"{len(nodes)} nodes exceed pad_rows_to={rows}")
    native = load_native()
    if native is not None:
        buf = native.pack_rows(nodes, rows, row_bytes)
    else:
        buf = _pack_rows_np(nodes, rows, row_bytes)
    lens = np.zeros(rows, np.int32)
    lens[: len(nodes)] = np.fromiter(map(len, nodes), np.int32, len(nodes))
    return buf.view("<u4"), lens


def roots_to_words(roots: Sequence[bytes]) -> np.ndarray:
    """(NB, 8) u32 little-endian view of 32-byte root hashes."""
    return np.stack([np.frombuffer(r, dtype="<u4") for r in roots])


# --- child-ref extraction (host) ------------------------------------------


def _rlp_item_bounds(data, end: int, pos: int):
    """(kind, payload_start, payload_end, next_pos); kind 0=str, 1=list.
    Mirrors the native scanner (native/packer.cc phant_scan_refs)."""
    b = data[pos]
    if b < 0x80:
        return 0, pos, pos + 1, pos + 1
    if b < 0xB8:
        l, s, kind = b - 0x80, pos + 1, 0
    elif b < 0xC0:
        ll = b - 0xB7
        l = int.from_bytes(bytes(data[pos + 1 : pos + 1 + ll]), "big")
        s, kind = pos + 1 + ll, 0
    elif b < 0xF8:
        l, s, kind = b - 0xC0, pos + 1, 1
    else:
        ll = b - 0xF7
        l = int.from_bytes(bytes(data[pos + 1 : pos + 1 + ll]), "big")
        s, kind = pos + 1 + ll, 1
    if s + l > end:
        raise ValueError("malformed RLP in witness node")
    return kind, s, s + l, s + l


def _scan_list_refs(data, s: int, e: int, out: List[int], depth: int = 0) -> None:
    if depth > 64:
        raise ValueError("RLP nesting too deep")
    items = []
    pos = s
    while pos < e:
        kind, ps, pe, pos = _rlp_item_bounds(data, e, pos)
        items.append((kind, ps, pe))
        if len(items) > 17:
            raise ValueError("not a trie node")
    if len(items) == 17:
        for kind, ps, pe in items[:16]:
            if kind == 0 and pe - ps == 32:
                out.append(ps)
            elif kind == 1 and pe > ps:
                _scan_list_refs(data, ps, pe, out, depth + 1)
    elif len(items) == 2:
        kind0, p0s, p0e = items[0]
        if p0e == p0s:
            raise ValueError("empty hex-prefix path")
        if not (data[p0s] & 0x20):  # extension (leaf bit clear)
            kind, ps, pe = items[1]
            if kind == 0 and pe - ps == 32:
                out.append(ps)
            elif kind == 1:
                _scan_list_refs(data, ps, pe, out, depth + 1)
        else:  # leaf: an account-shaped value commits its storage root
            kind, ps, pe = items[1]
            if kind == 0:
                sr = _account_storage_root_off(data, ps, pe)
                if sr >= 0:
                    out.append(sr)


def _account_storage_root_off(data, s: int, e: int) -> int:
    """Absolute offset of the storage root inside an account-shaped leaf
    value (a 4-string RLP list with 32-byte items 2 and 3), else -1.
    Mirrors native/packer.cc account_storage_root_off."""
    try:
        kind, ps, pe, nxt = _rlp_item_bounds(data, e, s)
    except ValueError:
        return -1
    if kind != 1 or nxt != e:
        return -1
    spans = []
    pos = ps
    while pos < pe:
        try:
            k, ips, ipe, pos = _rlp_item_bounds(data, pe, pos)
        except ValueError:
            return -1
        if k != 0 or len(spans) >= 4:
            return -1
        spans.append((ips, ipe))
    if len(spans) != 4:
        return -1
    if spans[2][1] - spans[2][0] != 32 or spans[3][1] - spans[3][0] != 32:
        return -1
    return spans[2][0]


def scan_refs_py(blob, offsets, lens) -> Tuple[np.ndarray, np.ndarray]:
    """Pure-Python fallback for NativeLib.scan_refs: absolute blob offsets of
    every child hash reference, with the owning node index."""
    ref_off: List[int] = []
    ref_node: List[int] = []
    mv = memoryview(blob) if isinstance(blob, (bytes, bytearray)) else blob
    for i in range(len(offsets)):
        s, e = int(offsets[i]), int(offsets[i]) + int(lens[i])
        kind, ps, pe, pos = _rlp_item_bounds(mv, e, s)
        if kind != 1 or pos != e:
            raise ValueError("witness node is not a single RLP list")
        before = len(ref_off)
        _scan_list_refs(mv, ps, pe, ref_off)
        ref_node.extend([i] * (len(ref_off) - before))
    return np.asarray(ref_off, np.int64), np.asarray(ref_node, np.int32)


def pack_witness(
    node_lists: Sequence[Sequence[bytes]],
    max_chunks: int,
    pad_nodes_to: int | None = None,
    pad_refs_to: int | None = None,
    min_pad: int = 1,
) -> Tuple[np.ndarray, np.ndarray, np.ndarray]:
    """(blob, meta, ref_meta) for `witness_verify_linked`: the blob/meta of
    `pack_witness_blob` plus the (2, R) int32 (ref offset, ref block) rows of
    every child hash reference (native scanner when available, Python
    fallback otherwise). Pad rows carry offset -1. `min_pad` floors both
    padded axes (power-of-two mesh divisibility)."""
    from phant_tpu.utils.native import load_native

    if pad_nodes_to is None and min_pad > 1:
        total = sum(len(nodes) for nodes in node_lists)
        pad_nodes_to = _pow2ceil(max(total, min_pad))
    blob, meta = pack_witness_blob(node_lists, max_chunks, pad_nodes_to)
    counts = [len(nodes) for nodes in node_lists]
    B = sum(counts)
    offsets = meta[0][:B].astype(np.uint64)
    lens = meta[1][:B].astype(np.uint32)
    native = load_native()
    if native is not None:
        ref_off, ref_node = native.scan_refs(blob, offsets, lens)
    else:
        ref_off, ref_node = scan_refs_py(blob, offsets, lens)
    ref_block = meta[2][:B][ref_node]
    R = len(ref_off)
    target = pad_refs_to
    if target is None:
        target = _pow2ceil(max(R, min_pad))
    if R > target:
        raise ValueError(f"{R} refs exceed pad_refs_to={target}")
    ref_meta = np.full((2, target), -1, np.int32)
    ref_meta[0, :R] = ref_off.astype(np.int32)
    ref_meta[1, :R] = ref_block
    return blob, meta, ref_meta
