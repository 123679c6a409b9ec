"""`chainproc.make` for a deployment that holds the state whole: the
genesis goes out with its key holders (who was funded, with what: a header
does not say), and a block without a request body, which nobody posts. The
chain's process still imports nothing of the program."""

from __future__ import annotations


def holders_of(chain) -> dict:
    """The genesis's key holders, as `reference.chain.Chain` made them from
    its seed: the funded senders, the cold accounts (one blob of 20-byte
    addresses, in the order that numbers their balances), the contracts and
    their code."""
    from reference.chain import COUNTER_CODE

    return {
        "pool": list(chain.pool),
        "cold": b"".join(chain.cold),
        "contracts": list(chain.contracts),
        "code": COUNTER_CODE,
    }


def make(pipe, build_dir: str, seed: int, params: dict, n_blocks: int) -> None:
    """The genesis, then the blocks one by one. A thread of this process
    does the sending: a block is half a megabyte and a pipe holds 64 KB, so
    a `send` on the generator's own thread stands still until the other
    side's feeder thread has had the interpreter lock eight times, beside a
    set-up that holds it; the generator never waits for the reader."""
    import queue
    import threading

    from reference import keccak
    from reference.chain import Chain

    keccak.load(build_dir)
    chain = Chain(seed, params)
    out: queue.SimpleQueue = queue.SimpleQueue()

    def send():
        while (item := out.get()) is not None:
            pipe.send(item)

    sender = threading.Thread(target=send, name="chain-send")
    sender.start()
    try:
        out.put(("genesis", chain.genesis, holders_of(chain)))
        for _ in range(n_blocks):
            chain.extend(1)
            out.put(("block", chain.blocks[-1]))
    finally:
        out.put(None)
        sender.join()
