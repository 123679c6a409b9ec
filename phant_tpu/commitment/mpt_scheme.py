"""The default commitment scheme: the paper's hexary keccak MPT.

Pure delegation to the pre-plugin machinery — mpt/mpt.py tries,
stateless.PartialTrie, ops/mpt_jax.PlanBuilder, state/root.py builders,
spec-runner witness collection — so the default path stays byte-identical
to the code before the commitment/ refactor (pinned by every existing
suite running unmodified)."""

from __future__ import annotations

from typing import Dict, List, Mapping

from phant_tpu.commitment import CommitmentScheme, register_scheme
from phant_tpu.mpt.mpt import EMPTY_TRIE_ROOT, BranchNode, ExtensionNode, Trie


class MptScheme(CommitmentScheme):
    name = "mpt"
    empty_root = EMPTY_TRIE_ROOT

    def fresh_trie(self) -> Trie:
        return Trie()

    def partial_trie(self, root_digest: bytes, db: Dict[bytes, bytes]):
        from phant_tpu.stateless import PartialTrie

        return PartialTrie(root_digest, db)

    def plan_builder(self):
        from phant_tpu.ops.mpt_jax import PlanBuilder

        return PlanBuilder()

    # -- state commitment: the state/root.py builders verbatim --------------

    def build_storage_trie(self, storage: Mapping[int, int]) -> Trie:
        from phant_tpu.state.root import build_storage_trie

        return build_storage_trie(storage)

    def account_leaf(self, account) -> bytes:
        from phant_tpu.state.root import account_leaf

        return account_leaf(account)

    def build_state_trie(self, accounts) -> Trie:
        from phant_tpu.state.root import build_state_trie

        return build_state_trie(accounts)

    def state_root_of(self, accounts) -> bytes:
        from phant_tpu.state.root import state_root

        return state_root(accounts)

    # -- witnesses -----------------------------------------------------------

    def collect_nodes(self, trie: Trie, nodes: Dict[bytes, None]) -> None:
        """Every >=32 B node encoding (embedded nodes travel inside their
        parents; the root ships regardless) — exactly the spec runner's
        pre-plugin collection."""
        if trie.root is None:
            return

        def walk(node):
            enc = trie.node_encoding(node)[1]
            if len(enc) >= 32 or node is trie.root:
                nodes[enc] = None
            if isinstance(node, ExtensionNode):
                walk(node.child)
            elif isinstance(node, BranchNode):
                for child in node.children:
                    if child is not None:
                        walk(child)

        walk(trie.root)

    def proof_nodes(self, trie: Trie, key: bytes) -> List[bytes]:
        from phant_tpu.mpt.proof import generate_proof

        return generate_proof(trie, key)


register_scheme(MptScheme())
