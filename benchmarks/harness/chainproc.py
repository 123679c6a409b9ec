"""The chain is made in a process of its own (no jax there), beside the
server's set-up, and its blocks come back one by one, each with the request
body a consensus client would POST for it (encoded here, not in the
server's process)."""

from __future__ import annotations


def make(pipe, build_dir: str, seed: int, params: dict, n_blocks: int) -> None:
    from reference import keccak
    from reference.chain import Chain

    keccak.load(build_dir)
    chain = Chain(seed, params)
    pipe.send(("genesis", chain.genesis))
    for i in range(n_blocks):
        chain.extend(1)
        block = chain.blocks[-1]
        pipe.send(("block", block, block.body(i + 1)))
