"""`chainproc.make` over `reference/chain_basefee.py`: the chain of blocks
at the gas limit, each with the request body a consensus client would POST
for it. A thread of this process does the sending, as in
`chainproc_holders.py`: a body here is 5.9 MB and a pipe holds 64 KB, so a
`send` on the generator's own thread would stand still until the server's
process, busy with its warm-up, had read it all."""

from __future__ import annotations


def make(pipe, build_dir: str, seed: int, params: dict, n_blocks: int) -> None:
    import queue
    import threading

    from reference import keccak
    from reference.chain_basefee import Chain

    keccak.load(build_dir)
    chain = Chain(seed, params)
    out: queue.SimpleQueue = queue.SimpleQueue()

    def send():
        while (item := out.get()) is not None:
            pipe.send(item)

    sender = threading.Thread(target=send, name="chain-send")
    sender.start()
    try:
        out.put(("genesis", chain.genesis))
        for i in range(n_blocks):
            chain.extend(1)
            block = chain.blocks[-1]
            out.put(("block", block, block.body(i + 1)))
    finally:
        out.put(None)
        sender.join()
