"""Pluggable commitment schemes: how world state is committed.

The verification stack was built around ONE commitment scheme — the
hexary keccak Merkle Patricia Trie — but nothing in its hot layers
actually depends on hexary RLP semantics:

  * the witness engine (ops/witness_engine.py, all three cores), the
    fused device kernel (ops/witness_jax.py) and the device-resident
    intern table (ops/witness_resident.py) verify "these nodes form a
    connected subtree rooted at the claimed digest" over any node bytes
    whose child references the ref scanners can see;
  * the hash-plan executors (ops/mpt_jax.py HashPlan / merge_plans,
    ops/root_engine.py, the scheduler's root lane) hash "templates with
    32-byte holes at byte offsets" — they never look inside a template;
  * the trie STRUCTURE algorithms (mpt/mpt.py insert/delete/collapse)
    are radix-generic over `children[digit]`.

This package makes that seam explicit. A `CommitmentScheme` bundles the
scheme-specific pieces — key digitization, node codec, partial-trie
construction from a witness, hash-plan lowering, witness generation —
behind one object, and everything scheme-dependent in stateless.py /
spec/runner.py resolves through it. Two backends ship:

  * `mpt` (commitment/mpt_scheme.py): the paper's hexary keccak MPT,
    byte-identical to the pre-plugin code path (the default);
  * `binary` (commitment/binary.py): fixed-shape 2-ary keccak Merkle
    nodes with bit-level path compression a la MHOT (PAPERS.md
    2606.11736) — the scheme three of the five related papers argue is
    the stateless endgame (2504.14069: binary dominates hexary on
    witness bytes).

THE REF-TRANSPARENCY CONTRACT (what lets a new scheme ride the whole
existing stack unmodified): a scheme's node encoding must be a single
RLP list in which every child reference appears where the shared ref
scanners (_scan_list_refs / native packer.cc / the device
_extract_ref_positions — all differential-tested identical) already
look: 32-byte string children of a 17-item list, the 32-byte second
item of a 2-item list whose first item's 0x20 bit is clear, or the
storage root inside an account-shaped leaf value. Schemes that speak
this contract get all three engine cores, the fused kernel, the
resident table, the serving scheduler and the mesh lanes for free;
a scheme that cannot (e.g. a non-keccak Verkle commitment) plugs in
below the same interface but must bring its own verifier route.

Selection: `PHANT_COMMITMENT` (the `--commitment={mpt,binary}` CLI
flag sets it) picks the process-wide active scheme; library callers can
pass an explicit scheme to `WitnessStateDB` / `execute_stateless`.
"""

from __future__ import annotations

import os
from typing import Dict, List, Mapping, Tuple


def account_leaf_value(
    nonce: int, balance: int, storage_root: bytes, code_hash: bytes
) -> bytes:
    """THE account leaf VALUE encoding — rlp([nonce, balance,
    storage_root, code_hash]). One copy, shared by every scheme
    (CommitmentScheme.account_leaf), the hexary state builders
    (state/root.py) and the stateless write-back path
    (stateless.WitnessStateDB): the value encoding is the state MODEL,
    and a divergence between the producers would be a silent root
    split."""
    from phant_tpu import rlp

    return rlp.encode(
        [rlp.encode_uint(nonce), rlp.encode_uint(balance), storage_root, code_hash]
    )


class CommitmentScheme:
    """One way of committing world state to a 32-byte root.

    Subclasses supply trie construction (full and witness-backed),
    hash-plan lowering, and witness generation. The account/storage KEY
    derivation (keccak(address) / keccak(slot_be32)) and the account
    leaf VALUE encoding (rlp([nonce, balance, storage_root, code_hash]))
    are deliberately shared across schemes — they are part of the state
    MODEL, not of how the tree commits to it — which is also what makes
    the account-leaf storage-root ref visible to the shared scanners.
    """

    #: registry key and the `--commitment` flag value
    name: str = "abstract"
    #: root of the empty trie (keccak(rlp(b"")) for both keccak schemes)
    empty_root: bytes = b""

    # -- tries ---------------------------------------------------------------

    def fresh_trie(self):
        """An empty buildable trie of this scheme."""
        raise NotImplementedError

    def partial_trie(self, root_digest: bytes, db: Dict[bytes, bytes]):
        """A witness-backed partial trie (unwitnessed subtrees opaque);
        raises StatelessError when the witness misses the root."""
        raise NotImplementedError

    def plan_builder(self):
        """A PlanBuilder lowering this scheme's dirty nodes into a
        HashPlan (ops/mpt_jax.py) for the batched root lane."""
        raise NotImplementedError

    # -- state commitment ----------------------------------------------------

    def build_storage_trie(self, storage: Mapping[int, int]):
        trie = self.fresh_trie()
        from phant_tpu.crypto.keccak import keccak256
        from phant_tpu import rlp

        for slot, value in storage.items():
            if value == 0:
                continue
            trie.put(
                keccak256(slot.to_bytes(32, "big")),
                rlp.encode(rlp.encode_uint(value)),
            )
        return trie

    def account_leaf(self, account) -> bytes:
        return account_leaf_value(
            account.nonce,
            account.balance,
            self.build_storage_trie(account.storage).root_hash(),
            account.code_hash(),
        )

    def build_state_trie(self, accounts: Mapping[bytes, object]):
        """address -> account trie, skipping EIP-161-empty accounts
        (same account-model semantics for every scheme)."""
        from phant_tpu.crypto.keccak import keccak256

        trie = self.fresh_trie()
        for address, account in accounts.items():
            if account.is_empty() and not account.storage:
                continue
            trie.put(keccak256(address), self.account_leaf(account))
        return trie

    def state_root_of(self, accounts: Mapping[bytes, object]) -> bytes:
        return self.build_state_trie(accounts).root_hash()

    # -- witnesses -----------------------------------------------------------

    def collect_nodes(self, trie, nodes: Dict[bytes, None]) -> None:
        """Add every witness-shippable node encoding of `trie` to `nodes`
        (an ordered set). Scheme-specific: the hexary scheme skips
        embedded (<32 B) nodes, the binary scheme ships every node."""
        raise NotImplementedError

    def proof_nodes(self, trie, key: bytes) -> List[bytes]:
        """The witness nodes proving `key`'s presence/absence: the node
        encodings along the lookup path (sibling digests are embedded in
        the path nodes themselves for both keccak schemes)."""
        raise NotImplementedError

    def witness_of_state(self, accounts: Mapping[bytes, object]) -> Tuple[
        bytes, List[bytes], List[bytes]
    ]:
        """(state_root, nodes, codes): the FULL state (accounts + storage
        subtrees) as a witness — the provably-sufficient witness the spec
        runner executes against (phant_tpu/spec/runner.py)."""
        from phant_tpu.utils.trace import metrics

        nodes: Dict[bytes, None] = {}
        codes: Dict[bytes, None] = {}
        for acct in accounts.values():
            if acct.code:
                codes[acct.code] = None
            if any(v for v in acct.storage.values()):
                self.collect_nodes(self.build_storage_trie(acct.storage), nodes)
        trie = self.build_state_trie(accounts)
        self.collect_nodes(trie, nodes)
        metrics.count(
            "commitment.witness_nodes", len(nodes), scheme=self.name
        )
        return trie.root_hash(), list(nodes), list(codes)


# ---------------------------------------------------------------------------
# registry
# ---------------------------------------------------------------------------

_SCHEMES: Dict[str, CommitmentScheme] = {}


def register_scheme(scheme: CommitmentScheme) -> CommitmentScheme:
    _SCHEMES[scheme.name] = scheme
    return scheme


def scheme_names() -> Tuple[str, ...]:
    _load_builtin()
    return tuple(sorted(_SCHEMES))


def get_scheme(name: str) -> CommitmentScheme:
    _load_builtin()
    try:
        return _SCHEMES[name]
    except KeyError:
        raise ValueError(
            f"unknown commitment scheme {name!r} (have: {sorted(_SCHEMES)})"
        ) from None


def active_scheme() -> CommitmentScheme:
    """The process-wide scheme: PHANT_COMMITMENT (default `mpt` — the
    paper's hexary keccak MPT, byte-identical to the pre-plugin path).
    Read per call so tests/CLI can flip it without import-order games;
    the env read is a dict lookup, nowhere near any hot loop (states are
    constructed once per request)."""
    return get_scheme(os.environ.get("PHANT_COMMITMENT", "mpt") or "mpt")


def _load_builtin() -> None:
    if "mpt" not in _SCHEMES:
        from phant_tpu.commitment import mpt_scheme  # noqa: F401  (registers)
    if "binary" not in _SCHEMES:
        from phant_tpu.commitment import binary  # noqa: F401  (registers)
