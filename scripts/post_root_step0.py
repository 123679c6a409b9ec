#!/usr/bin/env python3
"""Where the post-root's host milliseconds go: walk and plan, split.

    python3 scripts/post_root_step0.py [--genesis-log2 16] [--reps 9] [--tree DIR]
    python3 scripts/post_root_step0.py --retained 24 [--genesis-log2 20] [--tree DIR]

**Served** (the default). One block of the benchmark's own chain
(`benchmarks/reference/chain.py`, the traffic of
`benchmarks/traffic/lone.json`: 225 txs) goes through
`engine_api.handle_request` with `stateless.compute_post_root` replaced, so
that everything up to the post-root runs as served and the post-root is
`state_root()` (the host walk) or `post_root_plan()` (the plan's host half)
under a clock, `--reps` times each, medians in ms:

  walk  structural = `state_root()` less its `root_hash` calls (the puts, the
        keys' keccaks, the leaf values); hash = the walk's fresh encodings
        through the scalar C keccak again, in one call; encode = the
        `root_hash` calls less hash (the encoder and whatever recursion
        surrounds it).
  plan  structural = `post_root_plan()` less `try_subtree` and `finish`;
        encode = the template encoder's calls; visit = `try_subtree` less
        encode; lay-out = `finish`.

**Retained** (`--retained BLOCKS`). The replay cell's chain (the deployment
of `benchmarks/configs/replay-mpt-1chip.json`, the mix of
`benchmarks/traffic/seg32.json`) is put into the program's types
(`benchmarks/harness/fixture_of_chain.py`) and run block by block through
`Blockchain.run_block` on a `StateDB` of the whole genesis, as the replay's
run loop does, with no lane and no device beside it. Each block's
`StateDB.state_root()` is split, medians over the blocks in ms:

  structural    `flush_root_trie()` less the `root_hash` calls inside it: the
                dirty accounts' puts and deletes (`bytes_to_nibbles`,
                `_insert`, `_evict`), their keys' keccaks, the leaf values,
                the storage tries' puts
  storage_root  `root_hash` of the dirty accounts' storage tries
  state_root    `root_hash` of the state trie: one walk over its dirty paths

and beside them what the walks counted a block (`mpt.node_encodings`,
`mpt.ref_hashes`), the seconds of the first root (which hashes every node
once) and the process's resident set before it, after it and after the last
block. 2^20 accounts take 90 s and 3 GB; 2^14 ten seconds.

`--tree` is the checkout to measure (default: this one), so that one process
a tree compares a parent (`git archive` into a directory) with a change.
Host CPU figures of the machine it runs on: never a device number.
"""

from __future__ import annotations

import argparse
import json
import os
import statistics
import sys
import time
from pathlib import Path


def timed(fn, key: str, spent: dict):
    """`fn`, with its seconds added to `spent[key]`."""

    def wrapper(*a, **k):
        t0 = time.perf_counter()
        try:
            return fn(*a, **k)
        finally:
            spent[key] = spent.get(key, 0.0) + time.perf_counter() - t0

    return wrapper


def served(args, tree: Path) -> dict:
    from reference.chain import Chain

    mix = json.loads((tree / "benchmarks/traffic/lone.json").read_text())["chain"]
    chain = Chain(
        35,
        {"genesis_log2": args.genesis_log2, "sender_pool": 2048, "contracts": 16, **mix},
    )
    chain.extend(2)
    request = json.loads(chain.blocks[1].body(2))

    import phant_tpu.stateless as stateless
    from phant_tpu.__main__ import make_genesis_parent_header
    from phant_tpu.blockchain.chain import Blockchain
    from phant_tpu.blockchain.fork import fork_for
    from phant_tpu.config import ChainConfig
    from phant_tpu.engine_api import handle_request
    from phant_tpu.ops import mpt_jax
    from phant_tpu.state.statedb import StateDB
    from phant_tpu.utils.native import load_native

    native = load_native()
    config = ChainConfig.from_chain_id(1)
    state = StateDB({})
    node = Blockchain(
        chain_id=1,
        state=state,
        parent_header=make_genesis_parent_header(),
        fork=fork_for(config, state, 0, int(time.time())),
        config=config,
    )

    spent: dict = {}
    stateless.PartialTrie.root_hash = timed(stateless.PartialTrie.root_hash, "root_hash", spent)
    builder = mpt_jax.PlanBuilder
    builder.try_subtree = timed(builder.try_subtree, "try_subtree", spent)
    builder.finish = timed(builder.finish, "finish", spent)
    init = builder.__init__

    def bound(self):  # the template encoder is bound a builder
        init(self)
        self._template = timed(self._template, "template", spent)

    builder.__init__ = bound

    rows = {"walk": [], "plan": []}
    mode = ["walk"]

    def post_root(db):
        spent.clear()
        if mode[0] == "walk":
            t0 = time.perf_counter()
            root = db.state_root()
            total = time.perf_counter() - t0
            fresh = [
                entry[1]
                for trie in (db._trie, *db._storage_ptries.values())
                for entry in trie._enc_cache.values()
            ]
            t0 = time.perf_counter()
            native.keccak256_batch(fresh)
            hashed = time.perf_counter() - t0
            rooted = spent.get("root_hash", 0.0)
            rows["walk"].append((total, total - rooted, rooted - hashed, hashed, len(fresh)))
            return root
        t0 = time.perf_counter()
        prp = db.post_root_plan()
        total = time.perf_counter() - t0
        visit, lay, enc = (spent.get(k, 0.0) for k in ("try_subtree", "finish", "template"))
        rows["plan"].append((total, total - visit - lay, enc, visit - enc, lay, prp.plan.n_nodes))
        db._repair_pending(prp.patches)  # the request ends on the host
        return db.state_root()

    stateless.compute_post_root = post_root
    for _ in range(args.reps):
        for mode[0] in ("walk", "plan"):
            code, reply = handle_request(node, request)
            if code != 200 or reply["result"]["status"] != "VALID":
                raise SystemExit(f"the block was not verified: {reply}")

    def med(kind, i):
        return round(statistics.median(r[i] for r in rows[kind]) * 1e3, 2)

    return {
        "reps": args.reps,
        "walk_ms": {
            "total": med("walk", 0), "structural": med("walk", 1),
            "encode": med("walk", 2), "hash": med("walk", 3),
            "nodes": rows["walk"][0][4],
        },
        "plan_ms": {
            "total": med("plan", 0), "structural": med("plan", 1),
            "encode": med("plan", 2), "visit": med("plan", 3),
            "lay_out": med("plan", 4), "nodes": rows["plan"][0][5],
        },
    }  # fmt: skip


def rss_mb() -> float:
    with open("/proc/self/status") as f:
        for line in f:
            if line.startswith("VmRSS:"):
                return int(line.split()[1]) / 1024
    return 0.0


def retained(args, tree: Path) -> dict:
    from harness import fixture_of_chain as foc
    from harness.chainproc_holders import holders_of
    from reference.chain import Chain

    config = json.loads((tree / "benchmarks/configs/replay-mpt-1chip.json").read_text())
    mix = json.loads((tree / "benchmarks/traffic/seg32.json").read_text())["chain"]
    chain = Chain(
        43,
        {
            "genesis_log2": args.genesis_log2,
            "sender_pool": min(config["sender_pool"], 1 << (args.genesis_log2 - 1)),
            "contracts": config["contracts"],
            **mix,
        },
    )
    chain.extend(args.retained)
    fix = foc.fixture_of(chain.genesis, holders_of(chain), chain.blocks)

    from phant_tpu.backend import set_evm_backend
    from phant_tpu.mpt.mpt import Trie
    from phant_tpu.state.statedb import StateDB
    from phant_tpu.utils.trace import metrics

    set_evm_backend("native")
    node = fix.fresh_chain()
    rss = {"state_built": rss_mb()}
    t0 = time.perf_counter()
    if node.state.state_root() != chain.genesis.state_root:
        raise SystemExit("the program's root of the genesis is not the reference's")
    first_root_s = time.perf_counter() - t0
    rss["first_root"] = rss_mb()

    spent: dict = {}
    Trie.root_hash = timed(Trie.root_hash, "root_hash", spent)
    StateDB.flush_root_trie = timed(StateDB.flush_root_trie, "flush", spent)
    StateDB.state_root = timed(StateDB.state_root, "total", spent)

    def counts() -> tuple:
        c = metrics.snapshot()["counters"]
        return tuple(
            sum(v for k, v in c.items() if k.startswith(f"mpt.{name}{{"))
            for name in ("node_encodings", "ref_hashes")
        )

    rows = []
    for block in fix.blocks:
        spent.clear()
        before = counts()
        node.run_block(block)
        encoded, hashed = (b - a for a, b in zip(before, counts()))
        # flush's own root_hash calls are the storage tries'; state_root()
        # adds the state trie's after the flush
        walk = spent["total"] - spent["flush"]
        storage = spent["root_hash"] - walk
        rows.append((spent["total"], spent["flush"] - storage, storage, walk, encoded, hashed))
    if node.state.state_root() != chain.blocks[-1].header.state_root:
        raise SystemExit("the head's root is not the reference's")
    rss["last_block"] = rss_mb()

    def med(i):
        return statistics.median(r[i] for r in rows)

    return {
        "blocks": len(rows),
        "first_root_s": round(first_root_s, 2),
        "rss_mb": {k: round(v) for k, v in rss.items()},
        "median_ms_a_block": {
            name: round(med(i) * 1e3, 3)
            for i, name in enumerate(("root", "structural", "storage_root", "state_root"))
        },
        "least_root_ms": round(min(r[0] for r in rows) * 1e3, 3),
        "node_encodings_a_block": med(4),
        "ref_hashes_a_block": med(5),
    }


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--genesis-log2", type=int, default=16)
    ap.add_argument("--reps", type=int, default=9)
    ap.add_argument("--retained", type=int, default=0, metavar="BLOCKS")
    ap.add_argument("--tree", default=str(Path(__file__).resolve().parents[1]))
    args = ap.parse_args()
    tree = Path(args.tree).resolve()
    sys.path.insert(0, str(tree))
    sys.path.insert(0, str(tree / "benchmarks"))
    os.environ.setdefault("JAX_PLATFORMS", "cpu")

    from reference import keccak as ref_keccak

    ref_keccak.load(tree / "build" / "bench")
    out = retained(args, tree) if args.retained else served(args, tree)
    print(
        json.dumps(
            {"tree": str(tree), "genesis_log2": args.genesis_log2, "cpus": os.cpu_count(), **out}
        )
    )
    return 0


if __name__ == "__main__":
    sys.exit(main())
