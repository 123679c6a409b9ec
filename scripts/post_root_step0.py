#!/usr/bin/env python3
"""Where the post-root's host milliseconds go: walk and plan, split.

    python3 scripts/post_root_step0.py [--genesis-log2 16] [--reps 9] [--tree DIR]

One block of the benchmark's own chain (`benchmarks/reference/chain.py`, the
traffic of `benchmarks/traffic/lone.json`: 225 txs) goes through
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


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--genesis-log2", type=int, default=16)
    ap.add_argument("--reps", type=int, default=9)
    ap.add_argument("--tree", default=str(Path(__file__).resolve().parents[1]))
    args = ap.parse_args()
    tree = Path(args.tree).resolve()
    sys.path.insert(0, str(tree))
    sys.path.insert(0, str(tree / "benchmarks"))
    os.environ.setdefault("JAX_PLATFORMS", "cpu")

    from reference import keccak as ref_keccak
    from reference.chain import Chain

    ref_keccak.load(tree / "build" / "bench")
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

    def timed(fn, key):
        def wrapper(*a, **k):
            t0 = time.perf_counter()
            try:
                return fn(*a, **k)
            finally:
                spent[key] = spent.get(key, 0.0) + time.perf_counter() - t0

        return wrapper

    stateless.PartialTrie.root_hash = timed(stateless.PartialTrie.root_hash, "root_hash")
    builder = mpt_jax.PlanBuilder
    builder.try_subtree = timed(builder.try_subtree, "try_subtree")
    builder.finish = timed(builder.finish, "finish")
    if hasattr(mpt_jax, "_template_encoder"):  # the encoder is bound a builder
        init = builder.__init__

        def bound(self):
            init(self)
            self._template = timed(self._template, "template")

        builder.__init__ = bound
    else:  # a tree from before PR 35
        mpt_jax._encode_template = timed(mpt_jax._encode_template, "template")

    rows = {"walk": [], "plan": []}
    mode = ["walk"]

    def post_root(db):
        spent.clear()
        if mode[0] == "walk":
            t0 = time.perf_counter()
            root = db.state_root()
            total = time.perf_counter() - t0
            fresh = [
                pair[1]
                for trie in (db._trie, *db._storage_ptries.values())
                for pair in trie._enc_cache.values()
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

    out = {
        "tree": str(tree),
        "genesis_log2": args.genesis_log2,
        "reps": args.reps,
        "cpus": os.cpu_count(),
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
    print(json.dumps(out))
    return 0


if __name__ == "__main__":
    sys.exit(main())
