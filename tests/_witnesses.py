"""Synthetic witnesses the device-path tests share."""

import numpy as np

from phant_tpu import rlp
from phant_tpu.crypto.keccak import keccak256
from phant_tpu.mpt.mpt import Trie
from phant_tpu.mpt.proof import generate_proof


def build_witnesses(n_blocks=10, picks=4, trie_n=128, seed=5):
    """(root, [(root, nodes)] per block): `picks` account proofs a block
    over one `trie_n`-leaf trie, nodes deduplicated in first-seen order."""
    rng = np.random.default_rng(seed)
    trie = Trie()
    keys = []
    for _ in range(trie_n):
        k = keccak256(rng.bytes(20))
        trie.put(k, rlp.encode([rlp.encode_uint(1), rng.bytes(8)]))
        keys.append(k)
    root = trie.root_hash()
    r = np.random.default_rng(seed + 4)
    wits = []
    for _ in range(n_blocks):
        idx = r.choice(len(keys), size=picks, replace=False)
        nodes = {}
        for i in idx:
            for n in generate_proof(trie, keys[i]):
                nodes[n] = None
        wits.append((root, list(nodes.keys())))
    return root, wits
