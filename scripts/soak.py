#!/usr/bin/env python
"""Scheduler soak smoke: hammer a live Engine API server from threads.

`make soak` / scripts/check.sh run this after the pytest groups as the
serving subsystem's end-to-end gate: a real `EngineAPIServer` (CPU
backend, ephemeral port) takes a few hundred concurrent requests from a
small thread pool — state-mutating newPayloads (the scheduler's serial
lane), stateless verifications (the batching lane), read-only RPCs, and
`/healthz`/`/metrics` scrapes — then shuts down gracefully.

Pass criteria (exit 1 otherwise):
  * every request completes at the HTTP layer (no transport errors);
  * exactly ONE newPayload lands VALID (serialization held: the N-1
    replays are INVALID, never double-applied) and the chain advanced
    exactly once;
  * every stateless verification returns VALID with the expected root,
    and at least one engine batch coalesced >1 requests;
  * the scheduler sheds nothing (queue sized for the load: rejected == 0)
    and its executor is still alive at the end;
  * shutdown drains cleanly (no queued work abandoned, the scheduler
    slot is released).

A fourth phase (`_qos_phase`, PR 6) runs a short fixed-seed
scripts/loadgen.py sweep — open-loop Poisson arrivals with bursts, a 10:1
backfill:head tenant mix, slow-loris clients — and asserts the QoS
contract from the server's own telemetry: zero serial-lane sheds,
nonzero adaptive-wait adjustments, no tenant starved under overload, and
every loris connection closed by the socket deadline.

A second phase (`_crash_phase`) INDUCES one executor crash in a
throwaway server — a poisoned engine under a real HTTP
executeStatelessPayloadV1 — and asserts the obs postmortem contract:
  * pre-crash, `GET /debug/flight` serves the ring with the request's
    admit/batch records;
  * the crash writes a well-formed JSON dump under build/flight/ whose
    records include the `sched.executor_crash` event AND the crashing
    batch's trace ids (joinable to the HTTP X-Phant-Trace header);
  * `/healthz` flips to 503 and the flip writes its own dump.

A replay phase (`_replay_phase`, PR 18) drives a witnessed fixture
chain through the segment-pipelined ReplayEngine against a live
scheduler: byte-identity with serial `run_blocks` on the healthy lanes,
then an induced mid-segment sig-dispatch crash that must degrade
stage-by-stage (stage-named `replay.segment_crash`, -32052, final root
unchanged).

The final phase (`_sanitizer_phase`, PR 17) re-runs a depth-2 pipelined
scheduler under threaded submit pressure with the phantsan lockset race
sanitizer (phant_tpu/analysis/sanitizer.py) enabled: instrumented lock
proxies + per-field lockset tracking, Eraser-style. Any two-stack race
report fails the soak.
"""

from __future__ import annotations

import os
import sys
import urllib.request
from concurrent.futures import ThreadPoolExecutor

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

os.environ.setdefault("JAX_PLATFORMS", "cpu")


def _get(base: str, path: str):
    with urllib.request.urlopen(base + path, timeout=60) as resp:
        return resp.status, resp.read()


def main() -> int:
    threads = int(os.environ.get("PHANT_SOAK_THREADS", "8"))
    rounds = int(os.environ.get("PHANT_SOAK_ROUNDS", "12"))

    # deferred imports: JAX_PLATFORMS must be pinned first
    from phant_tpu.config import ChainId
    from phant_tpu.blockchain.chain import Blockchain
    from phant_tpu.engine_api.server import EngineAPIServer
    from phant_tpu.serving import SchedulerConfig, active_scheduler
    from phant_tpu.state.statedb import StateDB
    from phant_tpu.__main__ import make_genesis_parent_header

    sys.path.insert(
        0,
        os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))), "tests"),
    )
    # _post too: one JSON-RPC client shape shared with the test suite
    from test_serving import _post, _stateless_request, _valid_payload_json

    chain = Blockchain(
        chain_id=int(ChainId.Testing),
        state=StateDB(),
        parent_header=make_genesis_parent_header(),
        verify_state_root=False,
    )
    stateless_chain, stateless_rpc, want_root = _stateless_request()
    new_payload_rpc = {
        "jsonrpc": "2.0",
        "id": 1,
        "method": "engine_newPayloadV2",
        "params": [_valid_payload_json()],
    }
    version_rpc = {"jsonrpc": "2.0", "id": 2, "method": "engine_getClientVersionV1", "params": []}

    # ONE server, ONE scheduler: the newPayload chain serves the serial
    # lane; stateless requests carry their own self-contained pre-state so
    # they ride the same server regardless of its resident chain state —
    # but executeStateless resolves parent/config through the bound chain,
    # so bind the stateless-parent chain and let newPayload mutate it.
    del chain
    server = EngineAPIServer(
        stateless_chain,
        host="127.0.0.1",
        port=0,
        sched_config=SchedulerConfig(max_batch=32, max_wait_ms=20.0, queue_depth=1024),
    )
    server.serve_in_background()
    base = f"http://127.0.0.1:{server.port}"
    failures: list = []
    valid_newpayloads = 0
    stateless_ok = 0
    total = 0

    def one_round(r: int) -> list:
        out = []
        out.append(("newPayload", _post(base, new_payload_rpc)))
        out.append(("stateless", _post(base, stateless_rpc)))
        out.append(("version", _post(base, version_rpc)))
        out.append(("healthz", _get(base, "/healthz")))
        if r % 3 == 0:
            out.append(("metrics", _get(base, "/metrics")))
        return out

    try:
        with ThreadPoolExecutor(max_workers=threads) as pool:
            for results in pool.map(one_round, range(threads * rounds)):
                for kind, (code, body) in results:
                    total += 1
                    if kind == "newPayload":
                        if code != 200:
                            failures.append(f"newPayload HTTP {code}: {body}")
                        elif body["result"]["status"] == "VALID":
                            valid_newpayloads += 1
                        elif body["result"]["status"] != "INVALID":
                            failures.append(f"newPayload odd status: {body}")
                    elif kind == "stateless":
                        if code != 200 or body["result"]["status"] != "VALID":
                            failures.append(f"stateless failed ({code}): {body}")
                        elif body["result"]["stateRoot"] != want_root:
                            failures.append(f"stateless wrong root: {body}")
                        else:
                            stateless_ok += 1
                    elif code != 200:
                        failures.append(f"{kind} HTTP {code}")
        st = server.scheduler.stats_snapshot()
        state = server.scheduler.state()
    finally:
        server.shutdown()

    n_rounds = threads * rounds
    if valid_newpayloads != 1:
        failures.append(f"{valid_newpayloads} VALID newPayloads (want exactly 1)")
    if stateless_ok != n_rounds:
        failures.append(f"{stateless_ok}/{n_rounds} stateless VALID")
    if st["rejected"] != 0:
        failures.append(f"scheduler shed {st['rejected']} requests under a sized queue")
    if st["coalesced"] < 2:
        failures.append(f"no coalesced batches under {threads}-way load: {st}")
    if not state["executor_alive"]:
        failures.append(f"executor dead at end: {state}")
    if active_scheduler() is not None:
        failures.append("scheduler slot not released after shutdown")

    print(
        f"[soak] {total} requests over {threads} threads: "
        f"1 VALID newPayload + {n_rounds - 1} serialized replays, "
        f"{stateless_ok} stateless VALID, scheduler stats {st}"
    )
    if failures:
        for f in failures:
            print(f"[soak] FAIL: {f}", file=sys.stderr)
        return 1
    print("[soak] green: no errors, clean drain")
    rc = _crash_phase()
    if rc:
        return rc
    rc = _pipeline_phase()
    if rc:
        return rc
    rc = _post_root_phase()
    if rc:
        return rc
    rc = _sender_lane_phase()
    if rc:
        return rc
    rc = _replay_phase()
    if rc:
        return rc
    rc = _commitment_phase()
    if rc:
        return rc
    rc = _slo_phase()
    if rc:
        return rc
    rc = _timeline_phase()
    if rc:
        return rc
    rc = _qos_phase()
    if rc:
        return rc
    return _sanitizer_phase()


def _crash_phase() -> int:
    """Induce one executor crash in a throwaway server; assert the flight
    recorder leaves a joinable postmortem (the obs acceptance criterion)."""
    import json
    import urllib.error

    from phant_tpu.engine_api.server import EngineAPIServer
    from phant_tpu.serving import SchedulerConfig, VerificationScheduler

    from test_serving import _post, _stateless_request

    class _PoisonedEngine:
        def verify_batch(self, witnesses):
            raise RuntimeError("soak-induced crash")

    flight_dir = os.environ.get(
        "PHANT_FLIGHT_DIR",
        os.path.join(
            os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
            "build",
            "flight",
        ),
    )
    os.makedirs(flight_dir, exist_ok=True)
    before = set(os.listdir(flight_dir))

    stateless_chain, stateless_rpc, _root = _stateless_request()
    sched = VerificationScheduler(
        engine=_PoisonedEngine(),
        config=SchedulerConfig(max_batch=8, max_wait_ms=10.0),
    )
    server = EngineAPIServer(
        stateless_chain, host="127.0.0.1", port=0, scheduler=sched
    )
    server.serve_in_background()
    base = f"http://127.0.0.1:{server.port}"
    failures: list = []
    try:
        # pre-crash: the live ring is readable over HTTP
        code, body = _get(base, "/debug/flight")
        if code != 200:
            failures.append(f"/debug/flight pre-crash HTTP {code}")
        # the crash: a real stateless request whose witness check routes
        # through the poisoned engine on the executor thread
        code, body = _post(base, stateless_rpc)
        if code != 503 or body.get("error", {}).get("code") != -32052:
            failures.append(f"induced crash reply unexpected: {code} {body}")
        # healthz flips 503 (and dumps on the flip)
        try:
            _get(base, "/healthz")
            failures.append("healthz stayed 200 after executor crash")
        except urllib.error.HTTPError as e:
            if e.code != 503:
                failures.append(f"healthz HTTP {e.code}, want 503")
    finally:
        server.shutdown()
        sched.shutdown()

    new_dumps = sorted(set(os.listdir(flight_dir)) - before)
    crash_dumps = [d for d in new_dumps if "executor_crash" in d]
    if not crash_dumps:
        failures.append(f"no executor_crash flight dump written ({new_dumps})")
    else:
        with open(os.path.join(flight_dir, crash_dumps[0])) as f:
            dump = json.load(f)  # must be well-formed JSON
        kinds = [r.get("kind") for r in dump.get("records", [])]
        crash = [
            r for r in dump["records"] if r.get("kind") == "sched.executor_crash"
        ]
        if not crash:
            failures.append(f"dump lacks sched.executor_crash record: {kinds}")
        elif not any(crash[0].get("crashed_trace_ids") or []):
            failures.append(f"crash record carries no trace ids: {crash[0]}")
        if "sched.batch_start" not in kinds:
            failures.append(f"dump lacks the crashing batch's start record: {kinds}")
    if not any("healthz_503" in d for d in new_dumps):
        failures.append(f"no healthz_503 flip dump written ({new_dumps})")

    if failures:
        for f in failures:
            print(f"[soak] FAIL (crash phase): {f}", file=sys.stderr)
        return 1
    print(
        f"[soak] crash phase green: {len(new_dumps)} flight dump(s), "
        f"postmortem names the crashing batch ({crash_dumps[0]})"
    )
    return 0


def _pipeline_phase() -> int:
    """Pipelined-execution soak (PR 5): the same witness span at pipeline
    depth 2 vs depth 1 must produce byte-identical verdicts offline, and
    an induced RESOLVE-stage crash at depth 2 must fail exactly the
    in-flight handles (-32052) while the already-resolved batches keep
    their VALID verdicts and the crash dump names the resolve stage."""
    import json

    from phant_tpu.obs.flight import flight
    from phant_tpu.ops.witness_engine import WitnessEngine
    from phant_tpu.serving import (
        SchedulerConfig,
        SchedulerDown,
        VerificationScheduler,
    )

    from test_serving import _witness_set

    failures: list = []
    wits = _witness_set(128, trie_size=512, picks=8, seed=11)

    outs = {}
    for depth in (1, 2):
        eng = WitnessEngine()
        with VerificationScheduler(
            engine=eng,
            config=SchedulerConfig(
                max_batch=16, max_wait_ms=10.0, queue_depth=4096,
                pipeline_depth=depth,
            ),
        ) as s:
            outs[depth] = s.verify_many(wits)
            st = s.stats_snapshot()
            if depth == 2 and st["pipelined_batches"] < 1:
                failures.append(f"depth-2 soak never pipelined: {st}")
        # explicit release between passes: a fresh engine per depth
        # re-seeds the HOST tables, but a device-resident table's arrays
        # would linger until GC — the depth-2 pass must not run against
        # a box still holding depth-1's device memory
        eng.reset()
    if not (outs[1] == outs[2]).all() or not outs[1].all():
        failures.append("depth-2 verdicts diverge from depth-1")

    class _PoisonedResolve:
        """Healthy until ARMED (after the pre-crash futures complete, so
        the phase is immune to how many batches the assembler formed),
        then the next resolve dies — the wedged-readback failure mode."""

        def __init__(self):
            self._eng = WitnessEngine()
            self.armed = False

        def verify_batch(self, w):
            return self._eng.verify_batch(w)

        def begin_batch(self, w):
            return self._eng.begin_batch(w)

        def abandon_batch(self, h):
            self._eng.abandon_batch(h)

        def resolve_batch(self, h):
            if self.armed:
                raise RuntimeError("soak-induced resolve crash")
            return self._eng.resolve_batch(h)

    flight_dir = os.environ.get(
        "PHANT_FLIGHT_DIR",
        os.path.join(
            os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
            "build",
            "flight",
        ),
    )
    before = set(os.listdir(flight_dir)) if os.path.isdir(flight_dir) else set()
    poisoned = _PoisonedResolve()
    s = VerificationScheduler(
        engine=poisoned,
        config=SchedulerConfig(max_batch=8, max_wait_ms=5.0, pipeline_depth=2),
    )
    try:
        first = [s.submit_witness(*w) for w in wits[:8]]
        if not all(f.result(timeout=30) for f in first):
            failures.append("pre-crash batch not VALID")
        poisoned.armed = True
        second = [s.submit_witness(*w) for w in wits[8:16]]
        for f in second:
            try:
                f.result(timeout=30)
                failures.append("in-flight handle survived resolve crash")
            except SchedulerDown as e:
                if e.code != -32052:
                    failures.append(f"wrong down code: {e.code}")
        if not all(f.result(timeout=1) for f in first):
            failures.append("already-resolved verdicts lost after crash")
    finally:
        s.shutdown()
    new_dumps = sorted(set(os.listdir(flight_dir)) - before)
    crash_dumps = [d for d in new_dumps if "executor_crash" in d]
    if not crash_dumps:
        failures.append(f"no resolve-crash flight dump ({new_dumps})")
    else:
        with open(os.path.join(flight_dir, crash_dumps[-1])) as f:
            dump = json.load(f)
        crashes = [
            r for r in dump.get("records", [])
            if r.get("kind") == "sched.executor_crash"
        ]
        if not crashes or crashes[-1].get("stage") != "resolve":
            failures.append(
                f"crash dump does not name the resolve stage: "
                f"{crashes[-1] if crashes else None}"
            )

    # prefetch-stage drill (PR 9): the 4th stage dies mid-decode — only
    # in-flight work fails (-32052) and the dump names the PREFETCH stage
    class _PoisonedPrefetch(_PoisonedResolve):
        def prefetch_batch(self, w):
            if self.armed:
                raise RuntimeError("soak-induced prefetch crash")
            return self._eng.prefetch_batch(w)

        def begin_batch(self, w, prefetch=None):
            return self._eng.begin_batch(w, prefetch=prefetch)

        def resolve_batch(self, h):
            return self._eng.resolve_batch(h)

    before = set(os.listdir(flight_dir)) if os.path.isdir(flight_dir) else set()
    poisoned = _PoisonedPrefetch()
    s = VerificationScheduler(
        engine=poisoned,
        config=SchedulerConfig(
            max_batch=8, max_wait_ms=5.0, pipeline_depth=2, prefetch=True
        ),
    )
    try:
        first = [s.submit_witness(*w) for w in wits[16:24]]
        if not all(f.result(timeout=30) for f in first):
            failures.append("pre-crash batch not VALID (prefetch drill)")
        poisoned.armed = True
        second = [s.submit_witness(*w) for w in wits[24:32]]
        for f in second:
            try:
                f.result(timeout=30)
                failures.append("in-flight handle survived prefetch crash")
            except SchedulerDown as e:
                if e.code != -32052:
                    failures.append(f"wrong down code (prefetch): {e.code}")
        if not all(f.result(timeout=1) for f in first):
            failures.append("resolved verdicts lost after prefetch crash")
    finally:
        s.shutdown()
    new_dumps = sorted(set(os.listdir(flight_dir)) - before)
    crash_dumps = [d for d in new_dumps if "executor_crash" in d]
    if not crash_dumps:
        failures.append(f"no prefetch-crash flight dump ({new_dumps})")
    else:
        with open(os.path.join(flight_dir, crash_dumps[-1])) as f:
            dump = json.load(f)
        crashes = [
            r for r in dump.get("records", [])
            if r.get("kind") == "sched.executor_crash"
        ]
        if not crashes or crashes[-1].get("stage") != "prefetch":
            failures.append(
                f"crash dump does not name the prefetch stage: "
                f"{crashes[-1] if crashes else None}"
            )

    if failures:
        for f in failures:
            print(f"[soak] FAIL (pipeline phase): {f}", file=sys.stderr)
        return 1
    print(
        "[soak] pipeline phase green: depth-2 byte-identical, resolve- and "
        "prefetch-stage crashes fail only in-flight handles and name "
        "their stages"
    )
    return 0


def _commitment_phase() -> int:
    """Binary-backend soak (PR 12): a binary-Merkle witness span through
    the depth-2 scheduler must produce verdicts byte-identical to the
    direct engine oracle (corrupt blocks included — the engine is
    scheme-blind by the ref-transparency contract), and an induced crash
    under binary traffic must fail only in-flight requests with -32052
    plus a stage-named flight dump."""
    import json

    from phant_tpu.commitment import get_scheme
    from phant_tpu.crypto.keccak import keccak256
    from phant_tpu.ops.witness_engine import WitnessEngine
    from phant_tpu.serving import (
        SchedulerConfig,
        SchedulerDown,
        VerificationScheduler,
    )
    from phant_tpu.types.account import Account

    failures: list = []
    scheme = get_scheme("binary")
    accounts = {
        bytes([i % 250 + 1]) * 20: Account(
            nonce=i % 4,
            balance=i * 10**13 + 5,
            storage=({j: j * 3 + 1 for j in range(1, 7)} if i % 9 == 0 else {}),
        )
        for i in range(1, 160)
    }
    root, nodes, _codes = scheme.witness_of_state(accounts)
    wits = []
    for k in range(48):
        if k % 8 == 3:  # byte-flip corruption
            bad = list(nodes)
            bad[k % len(nodes)] = bad[k % len(nodes)][:-1] + bytes(
                [bad[k % len(nodes)][-1] ^ 1]
            )
            wits.append((root, bad))
        elif k % 8 == 6:  # wrong root
            wits.append((bytes([k + 1]) * 32, list(nodes)))
        else:
            wits.append((root, list(nodes)))

    oracle_eng = WitnessEngine()
    oracle = [bool(v) for v in oracle_eng.verify_batch(wits)]
    if not any(oracle) or all(oracle):
        failures.append("binary span lost its accept/reject mix")
    with VerificationScheduler(
        engine=WitnessEngine(),
        config=SchedulerConfig(
            max_batch=16, max_wait_ms=10.0, queue_depth=4096, pipeline_depth=2
        ),
    ) as s:
        got = [bool(v) for v in s.verify_many(wits)]
    if got != oracle:
        failures.append("scheduler verdicts diverge from the binary oracle")

    # induced crash under binary traffic: only in-flight work dies (-32052)
    class _Poisoned:
        def __init__(self):
            self._eng = WitnessEngine()
            self.armed = False

        def verify_batch(self, w):
            return self._eng.verify_batch(w)

        def begin_batch(self, w, prefetch=None):
            return self._eng.begin_batch(w)

        def abandon_batch(self, h):
            self._eng.abandon_batch(h)

        def resolve_batch(self, h):
            if self.armed:
                raise RuntimeError("soak-induced binary resolve crash")
            return self._eng.resolve_batch(h)

    flight_dir = os.environ.get(
        "PHANT_FLIGHT_DIR",
        os.path.join(
            os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
            "build",
            "flight",
        ),
    )
    before = set(os.listdir(flight_dir)) if os.path.isdir(flight_dir) else set()
    good = [w for w, ok in zip(wits, oracle) if ok]
    poisoned = _Poisoned()
    s = VerificationScheduler(
        engine=poisoned,
        config=SchedulerConfig(max_batch=8, max_wait_ms=5.0, pipeline_depth=2),
    )
    try:
        first = [s.submit_witness(*w) for w in good[:8]]
        if not all(f.result(timeout=30) for f in first):
            failures.append("pre-crash binary batch not VALID")
        poisoned.armed = True
        second = [s.submit_witness(*w) for w in good[8:16]]
        for f in second:
            try:
                f.result(timeout=30)
                failures.append("in-flight binary request survived the crash")
            except SchedulerDown as e:
                if e.code != -32052:
                    failures.append(f"wrong down code (binary): {e.code}")
        if not all(f.result(timeout=1) for f in first):
            failures.append("resolved binary verdicts lost after the crash")
    finally:
        s.shutdown()
    new_dumps = sorted(set(os.listdir(flight_dir)) - before)
    crash_dumps = [d for d in new_dumps if "executor_crash" in d]
    if not crash_dumps:
        failures.append(f"no binary-crash flight dump ({new_dumps})")
    else:
        with open(os.path.join(flight_dir, crash_dumps[-1])) as f:
            dump = json.load(f)
        crashes = [
            r
            for r in dump.get("records", [])
            if r.get("kind") == "sched.executor_crash"
        ]
        if not crashes or not crashes[-1].get("stage"):
            failures.append(
                f"binary crash dump carries no stage: "
                f"{crashes[-1] if crashes else None}"
            )

    if failures:
        for f in failures:
            print(f"[soak] FAIL (commitment phase): {f}", file=sys.stderr)
        return 1
    print(
        "[soak] commitment phase green: binary span byte-identical through "
        "the depth-2 scheduler, induced crash failed only in-flight "
        "requests with -32052 and a stage-named dump"
    )
    return 0


def _post_root_phase() -> int:
    """Batched post-root soak (PR 11): the same request set through the
    scheduler's root lane at pipeline depth 2 on the forced-device
    (XLA-CPU proxy) route must be byte-identical to the host
    `state_root()` oracle, and an induced ROOT-DISPATCH crash must fail
    only in-flight requests with -32052 while leaving a stage-named
    flight dump."""
    import json

    from phant_tpu.backend import set_crypto_backend
    from phant_tpu.ops.root_engine import RootEngine
    from phant_tpu.serving import (
        SchedulerConfig,
        SchedulerDown,
        VerificationScheduler,
    )
    from phant_tpu.ops._cache import enable_compilation_cache

    from test_post_root import _request_set

    enable_compilation_cache()  # warm from the pytest groups' persistent cache
    failures: list = []
    os.environ["PHANT_ALLOW_JAX_CPU"] = "1"
    set_crypto_backend("tpu")
    try:
        hosts, prps, dbs = _request_set()
        with VerificationScheduler(
            config=SchedulerConfig(
                max_batch=8,
                max_wait_ms=10.0,
                pipeline_depth=2,
                root_engine_factory=lambda: RootEngine(device_floor=0),
            ),
        ) as s:
            outs = s.root_many([p.plan for p in prps])
            st = s.stats_snapshot()
        for prp, db, out, want in zip(prps, dbs, outs, hosts):
            if db.apply_post_root(prp, out) != want:
                failures.append("batched post root diverged from the oracle")
        if st["root_batches"] < 1:
            failures.append(f"root lane never batched: {st}")
    finally:
        set_crypto_backend("cpu")

    class _PoisonedRoot(RootEngine):
        armed = False

        def begin_batch(self, plans, prefetch=None):
            if _PoisonedRoot.armed:
                raise RuntimeError("soak-induced root dispatch crash")
            return super().begin_batch(plans, prefetch=prefetch)

    flight_dir = os.environ.get(
        "PHANT_FLIGHT_DIR",
        os.path.join(
            os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
            "build",
            "flight",
        ),
    )
    os.makedirs(flight_dir, exist_ok=True)
    before = set(os.listdir(flight_dir))
    _PoisonedRoot.armed = False
    hosts, prps, dbs = _request_set()
    s = VerificationScheduler(
        config=SchedulerConfig(
            max_batch=8,
            max_wait_ms=5.0,
            pipeline_depth=2,
            root_engine_factory=_PoisonedRoot,
        ),
    )
    try:
        first = [s.submit_root(p.plan) for p in prps[:2]]
        pre = [f.result(timeout=60) for f in first]
        _PoisonedRoot.armed = True
        second = [s.submit_root(p.plan) for p in prps[2:]]
        for f in second:
            try:
                f.result(timeout=60)
                failures.append("in-flight root survived the dispatch crash")
            except SchedulerDown as e:
                if e.code != -32052:
                    failures.append(f"wrong down code (root): {e.code}")
        if [f.result(timeout=1) for f in first] != pre:
            failures.append("already-resolved root digests lost after crash")
    finally:
        s.shutdown()
    new_dumps = sorted(set(os.listdir(flight_dir)) - before)
    crash_dumps = [d for d in new_dumps if "executor_crash" in d]
    if not crash_dumps:
        failures.append(f"no root-crash flight dump ({new_dumps})")
    else:
        with open(os.path.join(flight_dir, crash_dumps[-1])) as f:
            dump = json.load(f)
        crashes = [
            r
            for r in dump.get("records", [])
            if r.get("kind") == "sched.executor_crash"
        ]
        if not crashes or crashes[-1].get("stage") not in (
            "pack",
            "dispatch",
            "prefetch",
        ):
            failures.append(
                f"root-crash dump does not name a dispatch-side stage: "
                f"{crashes[-1] if crashes else None}"
            )

    if failures:
        for f in failures:
            print(f"[soak] FAIL (post-root phase): {f}", file=sys.stderr)
        return 1
    print(
        "[soak] post-root phase green: depth-2 batched roots byte-identical, "
        "induced root-dispatch crash fails only in-flight with a "
        "stage-named dump"
    )
    return 0


def _sender_lane_phase() -> int:
    """Coalesced sender recovery soak (PR 14): the same request set
    through the scheduler's sig lane at pipeline depth 2 on the
    forced-device (XLA-CPU proxy) route must be byte-identical to the
    `recover_senders_async(force_cpu=True)` oracle — invalid-signature
    and pre-EIP-155 blocks included — and an induced SIG-DISPATCH crash
    must fail only in-flight requests with -32052 while leaving a
    stage-named flight dump."""
    import json

    from phant_tpu.backend import set_crypto_backend
    from phant_tpu.ops.sig_engine import SigEngine
    from phant_tpu.serving import (
        SchedulerConfig,
        SchedulerDown,
        VerificationScheduler,
    )
    from phant_tpu.ops._cache import enable_compilation_cache

    from test_sender_lane import _request_set

    enable_compilation_cache()  # warm from the pytest groups' persistent cache
    failures: list = []
    os.environ["PHANT_ALLOW_JAX_CPU"] = "1"
    set_crypto_backend("tpu")
    try:
        oracles, rows_list = _request_set()
        with VerificationScheduler(
            config=SchedulerConfig(
                max_batch=8,
                max_wait_ms=10.0,
                pipeline_depth=2,
                sig_engine_factory=lambda: SigEngine(device_floor=0),
            ),
        ) as s:
            outs = s.sig_many(rows_list)
            st = s.stats_snapshot()
        for got, want in zip(outs, oracles):
            if got != want:
                failures.append("sig-lane senders diverged from the oracle")
        if st["sig_batches"] < 1:
            failures.append(f"sig lane never batched: {st}")
    finally:
        set_crypto_backend("cpu")

    class _PoisonedSig(SigEngine):
        armed = False

        def begin_batch(self, rows_list, prefetch=None):
            if _PoisonedSig.armed:
                raise RuntimeError("soak-induced sig dispatch crash")
            return super().begin_batch(rows_list, prefetch=prefetch)

    flight_dir = os.environ.get(
        "PHANT_FLIGHT_DIR",
        os.path.join(
            os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
            "build",
            "flight",
        ),
    )
    os.makedirs(flight_dir, exist_ok=True)
    before = set(os.listdir(flight_dir))
    _PoisonedSig.armed = False
    oracles, rows_list = _request_set()
    s = VerificationScheduler(
        config=SchedulerConfig(
            max_batch=8,
            max_wait_ms=5.0,
            pipeline_depth=2,
            sig_engine_factory=_PoisonedSig,
        ),
    )
    try:
        first = [s.submit_sig(r) for r in rows_list[:2]]
        pre = [f.result(timeout=60) for f in first]
        _PoisonedSig.armed = True
        second = [s.submit_sig(r) for r in rows_list[2:]]
        for f in second:
            try:
                f.result(timeout=60)
                failures.append("in-flight sig job survived the dispatch crash")
            except SchedulerDown as e:
                if e.code != -32052:
                    failures.append(f"wrong down code (sig): {e.code}")
        if [f.result(timeout=1) for f in first] != pre:
            failures.append("already-resolved senders lost after crash")
    finally:
        s.shutdown()
    new_dumps = sorted(set(os.listdir(flight_dir)) - before)
    crash_dumps = [d for d in new_dumps if "executor_crash" in d]
    if not crash_dumps:
        failures.append(f"no sig-crash flight dump ({new_dumps})")
    else:
        with open(os.path.join(flight_dir, crash_dumps[-1])) as f:
            dump = json.load(f)
        crashes = [
            r
            for r in dump.get("records", [])
            if r.get("kind") == "sched.executor_crash"
        ]
        if not crashes or crashes[-1].get("stage") not in (
            "pack",
            "dispatch",
            "prefetch",
        ):
            failures.append(
                f"sig-crash dump does not name a dispatch-side stage: "
                f"{crashes[-1] if crashes else None}"
            )

    if failures:
        for f in failures:
            print(f"[soak] FAIL (sender-lane phase): {f}", file=sys.stderr)
        return 1
    print(
        "[soak] sender-lane phase green: depth-2 merged senders "
        "byte-identical (invalid-sig + pre-EIP-155 blocks included), "
        "induced sig-dispatch crash fails only in-flight with a "
        "stage-named dump"
    )
    return 0


def _replay_phase() -> int:
    """Historical replay soak (PR 18): a witnessed fixture chain through
    the segment pipeline against a live depth-2 scheduler (sig + witness
    lanes up) must land byte-identical to serial `run_blocks` with every
    segment's merged ecrecover on the sig lane; then an induced
    MID-SEGMENT sig-dispatch crash must degrade stage-by-stage — the
    replay still completes on its local megabatch fallbacks, the final
    state root does not change by a byte, and the flight recorder
    carries stage-named `replay.segment_crash` records with the
    scheduler's -32052 alongside the executor's own crash dump."""
    import json

    from phant_tpu import serving
    from phant_tpu.obs.flight import flight
    from phant_tpu.ops.sig_engine import SigEngine
    from phant_tpu.ops.witness_engine import WitnessEngine
    from phant_tpu.replay import (
        ReplayEngine,
        attach_witnesses,
        build_synthetic_chain,
    )
    from phant_tpu.replay.engine import (
        STAGE_DISPATCH,
        STAGE_PACK,
        STAGE_PREFETCH,
        STAGE_RESOLVE,
    )

    failures: list = []
    stages = (STAGE_PREFETCH, STAGE_PACK, STAGE_DISPATCH, STAGE_RESOLVE)
    prev_sig = os.environ.get("PHANT_BATCHED_SIG")
    os.environ["PHANT_BATCHED_SIG"] = "1"
    try:
        fix = attach_witnesses(build_synthetic_chain(12, 3))
        serial = fix.fresh_chain()
        serial.run_blocks(fix.blocks)
        want_root = serial.state.state_root()

        def _sched(make_sig):
            return serving.VerificationScheduler(
                engine=WitnessEngine(),
                config=serving.SchedulerConfig(
                    max_batch=16,
                    max_wait_ms=20.0,
                    pipeline_depth=2,
                    sig_engine_factory=make_sig,
                ),
            )

        # healthy leg: byte-identity with every segment on the lanes
        s = _sched(lambda: SigEngine(device_floor=0))
        serving.install(s)
        try:
            rep = ReplayEngine(segment_blocks=5, pipeline_depth=2).run(
                fix.fresh_chain(), fix.blocks, witnesses=fix.witnesses
            )
            st = s.stats_snapshot()
        finally:
            serving.uninstall(s)
            s.shutdown()
        if not rep.ok or rep.final_state_root != want_root:
            failures.append("segment replay diverged from serial run_blocks")
        if rep.stats["lane_sig_segments"] != rep.stats["segments"]:
            failures.append(f"segment(s) skipped the sig lane: {rep.stats}")
        if st["sig_batches"] < 1 or st["requests"] < 12:
            failures.append(f"replay never rode the scheduler lanes: {st}")

        # crash leg: the sig lane's dispatch dies mid-segment
        class _PoisonedSig(SigEngine):
            armed = True

            def begin_batch(self, rows_list, prefetch=None):
                if _PoisonedSig.armed:
                    raise RuntimeError("soak-induced replay sig crash")
                return super().begin_batch(rows_list, prefetch=prefetch)

            def sig_many(self, rows_list):
                if _PoisonedSig.armed:
                    raise RuntimeError("soak-induced replay sig crash")
                return super().sig_many(rows_list)

        flight_dir = os.environ.get(
            "PHANT_FLIGHT_DIR",
            os.path.join(
                os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
                "build",
                "flight",
            ),
        )
        os.makedirs(flight_dir, exist_ok=True)
        dumps_before = set(os.listdir(flight_dir))
        before = len(flight.records())
        s = _sched(_PoisonedSig)
        serving.install(s)
        try:
            rep = ReplayEngine(segment_blocks=5, pipeline_depth=2).run(
                fix.fresh_chain(), fix.blocks, witnesses=fix.witnesses
            )
        finally:
            serving.uninstall(s)
            s.shutdown()
            _PoisonedSig.armed = False
        if not rep.ok or rep.final_state_root != want_root:
            failures.append("degraded replay changed the final state root")
        recs = flight.records()[before:]
        crashes = [
            r for r in recs if r.get("kind") == "replay.segment_crash"
        ]
        if not crashes:
            failures.append("no replay.segment_crash flight record")
        else:
            if not all(c.get("stage") in stages for c in crashes):
                failures.append(f"segment crash lacks a stage name: {crashes}")
            if not any(c.get("code") == -32052 for c in crashes):
                failures.append(f"no -32052 on the segment crash: {crashes}")
        crash_dumps = [
            d
            for d in sorted(set(os.listdir(flight_dir)) - dumps_before)
            if "executor_crash" in d
        ]
        if not crash_dumps:
            failures.append("no executor_crash flight dump from the sig lane")
        else:
            with open(os.path.join(flight_dir, crash_dumps[-1])) as f:
                dump = json.load(f)  # must be well-formed JSON
            if not any(
                r.get("kind") == "sched.executor_crash"
                for r in dump.get("records", [])
            ):
                failures.append("sig-lane dump lacks the executor crash record")
    finally:
        if prev_sig is None:
            os.environ.pop("PHANT_BATCHED_SIG", None)
        else:
            os.environ["PHANT_BATCHED_SIG"] = prev_sig

    if failures:
        for f in failures:
            print(f"[soak] FAIL (replay phase): {f}", file=sys.stderr)
        return 1
    print(
        f"[soak] replay phase green: {rep.stats['segments']}-segment replay "
        "byte-identical to serial on the lanes, induced mid-segment sig "
        f"crash degraded stage-by-stage ({len(crashes)} segment-crash "
        "records, root unchanged)"
    )
    return 0


def _slo_phase() -> int:
    """SLO exemplar capture under live traffic (PR 15): the soak's mixed
    request shape against a server whose `--slo-budget-ms` is
    deliberately impossible (0.01ms — every request violates). Asserts:
    violations are COUNTED (`obs.slow_captures{trigger=wall}`),
    exemplars LAND in /debug/slow over real HTTP with stage-named
    critical-path phases and the full span tree, and the stall watchdog
    stays QUIET throughout — slow is an SLO event, not a wedged
    executor, and conflating them would bury the real stall signal."""
    from phant_tpu.engine_api.server import EngineAPIServer
    from phant_tpu.obs import critpath
    from phant_tpu.obs.flight import flight
    from phant_tpu.serving import SchedulerConfig
    from phant_tpu.utils.trace import metrics

    from test_serving import _post, _stateless_request

    failures: list = []
    n_requests = int(os.environ.get("PHANT_SOAK_SLO_REQUESTS", "12"))
    os.environ["PHANT_SLO_BUDGET_MS"] = "0.01"
    critpath.slow.clear()
    seq_before = (flight.records() or [{}])[-1].get("seq", 0)
    counters_before = metrics.snapshot()["counters"]
    slow_before = sum(
        v
        for k, v in counters_before.items()
        if k.startswith("obs.slow_captures")
    )
    try:
        stateless_chain, stateless_rpc, _want_root = _stateless_request()
        server = EngineAPIServer(
            stateless_chain,
            host="127.0.0.1",
            port=0,
            sched_config=SchedulerConfig(
                max_batch=8, max_wait_ms=5.0, queue_depth=256
            ),
        )
        server.serve_in_background()
        base = f"http://127.0.0.1:{server.port}"
        try:
            with ThreadPoolExecutor(max_workers=4) as pool:
                for code, body in pool.map(
                    lambda _i: _post(base, stateless_rpc), range(n_requests)
                ):
                    if code != 200 or body["result"]["status"] != "VALID":
                        failures.append(f"stateless failed ({code}): {body}")
            import json

            code, raw = _get(base, "/debug/slow")
            if code != 200:
                failures.append(f"/debug/slow HTTP {code}")
                slow_body = {"records": []}
            else:
                slow_body = json.loads(raw)
        finally:
            server.shutdown()
    finally:
        os.environ.pop("PHANT_SLO_BUDGET_MS", None)
        critpath.refresh_from_env()

    counters_after = metrics.snapshot()["counters"]
    slow_after = sum(
        v
        for k, v in counters_after.items()
        if k.startswith("obs.slow_captures")
    )
    if slow_after - slow_before < n_requests:
        failures.append(
            f"slow captures undercounted: {slow_after - slow_before} < "
            f"{n_requests} violating requests"
        )
    records = slow_body.get("records", [])
    if not records:
        failures.append("no exemplars in /debug/slow under a 0.01ms budget")
    for rec in records[-3:]:
        if rec.get("kind") != "obs.slow_capture":
            failures.append(f"unexpected slow-ring record kind: {rec.get('kind')}")
            continue
        breakdown = rec.get("breakdown_ms") or {}
        bad = [ph for ph in breakdown if ph not in critpath.PHASES]
        if bad or not breakdown:
            failures.append(
                f"exemplar breakdown not stage-named: {sorted(breakdown)}"
            )
        sp = rec.get("span") or {}
        if sp.get("span") != "verify_block" or "phases" not in sp:
            failures.append(f"exemplar lacks the full span tree: {sp.get('span')}")
    # slow != stalled: the watchdog's deadline allowance (30s) was never
    # threatened by an SLO budget of 0.01ms — any stall record here means
    # the two signals got conflated
    stalls = [
        r
        for r in flight.records()
        if r.get("kind") == "sched.stall" and r.get("seq", 0) > seq_before
    ]
    if stalls:
        failures.append(f"watchdog fired on merely-slow traffic: {stalls}")

    if failures:
        for f in failures:
            print(f"[soak] FAIL (slo phase): {f}", file=sys.stderr)
        return 1
    print(
        f"[soak] slo phase green: {slow_after - slow_before} violations "
        f"counted, {len(records)} exemplars in /debug/slow with stage-named "
        "phases, watchdog quiet"
    )
    return 0


def _timeline_phase() -> int:
    """Unified timeline export under live traffic (PR 16): a mixed-load
    HTTP run against a server whose SLO budget is deliberately impossible
    (every request violates) must export, over real HTTP, a PARSEABLE
    Chrome-trace timeline whose kept-set contains the induced SLO
    violators (`reason=slo`) with request AND lane tracks present and
    every flow begin paired with its end; a second, throwaway poisoned
    server's -32052 crash request must land in the kept-set with
    `reason=error`; and the stall watchdog stays QUIET throughout."""
    import json

    from phant_tpu.engine_api.server import EngineAPIServer
    from phant_tpu.obs import critpath, timeline
    from phant_tpu.obs.flight import flight
    from phant_tpu.serving import SchedulerConfig, VerificationScheduler

    from test_serving import _post, _stateless_request

    failures: list = []
    n_requests = int(os.environ.get("PHANT_SOAK_TIMELINE_REQUESTS", "12"))
    os.environ["PHANT_SLO_BUDGET_MS"] = "0.01"
    seq_before = (flight.records() or [{}])[-1].get("seq", 0)
    timeline.reset()
    try:
        stateless_chain, stateless_rpc, _want_root = _stateless_request()
        server = EngineAPIServer(
            stateless_chain,
            host="127.0.0.1",
            port=0,
            sched_config=SchedulerConfig(
                max_batch=8, max_wait_ms=5.0, queue_depth=256
            ),
        )
        server.serve_in_background()
        base = f"http://127.0.0.1:{server.port}"
        try:
            with ThreadPoolExecutor(max_workers=4) as pool:
                for code, body in pool.map(
                    lambda _i: _post(base, stateless_rpc), range(n_requests)
                ):
                    if code != 200 or body["result"]["status"] != "VALID":
                        failures.append(f"stateless failed ({code}): {body}")
            code, raw = _get(base, "/debug/timeline?window=300")
            if code != 200:
                failures.append(f"/debug/timeline HTTP {code}")
                payload = {"traceEvents": [], "metadata": {}}
            else:
                payload = json.loads(raw)  # must be well-formed JSON
        finally:
            server.shutdown()
    finally:
        os.environ.pop("PHANT_SLO_BUDGET_MS", None)
        critpath.refresh_from_env()

    events = payload.get("traceEvents", [])
    kept = payload.get("metadata", {}).get("kept", {})
    if kept.get("slo", 0) < n_requests:
        failures.append(
            f"kept-set misses the induced SLO violators: {kept} "
            f"(want slo >= {n_requests})"
        )
    slo_slices = [
        e
        for e in events
        if e.get("ph") == "X"
        and e.get("cat") == "request"
        and e.get("args", {}).get("reason") == "slo"
    ]
    if len(slo_slices) < 1:
        failures.append("no reason=slo request slice in the exported timeline")
    proc_names = {
        e["args"]["name"]
        for e in events
        if e.get("ph") == "M" and e.get("name") == "process_name"
    }
    if not {"requests", "lanes"} <= proc_names:
        failures.append(f"track families missing from export: {proc_names}")
    s_ids = {e["id"] for e in events if e.get("ph") == "s"}
    f_ids = {e["id"] for e in events if e.get("ph") == "f"}
    if s_ids != f_ids:
        failures.append(f"unpaired flow events: {s_ids ^ f_ids}")
    if not s_ids:
        failures.append("no request->batch flow arrows in the exported timeline")

    # crash request lands in the kept-set with reason=error: a throwaway
    # poisoned server (same shape as _crash_phase, no dump assertions)
    class _PoisonedEngine:
        def verify_batch(self, witnesses):
            raise RuntimeError("soak-induced timeline crash")

    timeline.reset()
    stateless_chain, stateless_rpc, _root = _stateless_request()
    sched = VerificationScheduler(
        engine=_PoisonedEngine(),
        config=SchedulerConfig(max_batch=8, max_wait_ms=10.0),
    )
    server = EngineAPIServer(
        stateless_chain, host="127.0.0.1", port=0, scheduler=sched
    )
    server.serve_in_background()
    base = f"http://127.0.0.1:{server.port}"
    try:
        code, body = _post(base, stateless_rpc)
        if code != 503 or body.get("error", {}).get("code") != -32052:
            failures.append(f"induced crash reply unexpected: {code} {body}")
        code, raw = _get(base, "/debug/timeline?window=300")
        if code != 200:
            failures.append(f"/debug/timeline post-crash HTTP {code}")
            payload = {"traceEvents": [], "metadata": {}}
        else:
            payload = json.loads(raw)
    finally:
        server.shutdown()
        sched.shutdown()
    kept = payload.get("metadata", {}).get("kept", {})
    if kept.get("error", 0) < 1:
        failures.append(f"crash request not in the kept-set: {kept}")
    crash_slices = [
        e
        for e in payload.get("traceEvents", [])
        if e.get("ph") == "X"
        and e.get("cat") == "request"
        and e.get("args", {}).get("reason") == "error"
    ]
    if not crash_slices:
        failures.append("no reason=error request slice after the crash")

    # slow/crashed != stalled: the watchdog must not have fired
    stalls = [
        r
        for r in flight.records()
        if r.get("kind") == "sched.stall" and r.get("seq", 0) > seq_before
    ]
    if stalls:
        failures.append(f"watchdog fired during the timeline phase: {stalls}")

    if failures:
        for f in failures:
            print(f"[soak] FAIL (timeline phase): {f}", file=sys.stderr)
        return 1
    print(
        f"[soak] timeline phase green: {len(slo_slices)} SLO violators + "
        f"the crash request in the kept-set, {len(s_ids)} flow arrows "
        "paired, tracks present, watchdog quiet"
    )
    return 0


def _qos_phase() -> int:
    """Multi-tenant QoS under real overload (the PR 6 gate): a short
    fixed-seed scripts/loadgen.py run — open-loop Poisson arrivals with
    bursts, 10:1 backfill:head tenant mix, slow-loris clients — against a
    live EngineAPIServer. Asserts, from the server's own flight recorder
    and metrics: the serial mutation lane was NEVER shed, the adaptive
    batching policy actually adjusted the assembly wait, no tenant
    starved during the overload point, and every slow-loris connection
    was closed by the socket deadline. <=60s total
    (PHANT_SOAK_LOADGEN_SECONDS per load point, default 5)."""
    import loadgen

    seconds = float(os.environ.get("PHANT_SOAK_LOADGEN_SECONDS", "5"))
    result = loadgen.run_profile(
        seed=6,
        duration_s=seconds,
        multipliers=(0.5, 1.0, 2.0),
        slow_loris=2,
        loris_timeout_s=1.5,
        log=lambda msg: print(f"[soak] qos: {msg}", file=sys.stderr),
    )
    checks = result["checks"]
    failures: list = []
    if checks["serial_lane_sheds"] != 0:
        failures.append(
            f"serial mutation lane shed {checks['serial_lane_sheds']} jobs "
            "(the documented shed order forbids it)"
        )
    if checks["adaptive_wait_adjustments"] <= 0:
        failures.append("adaptive batching never adjusted the assembly wait")
    if not checks["no_starvation"]:
        failures.append(f"tenant(s) starved under overload: {checks['starved_tenants']}")
    if checks["loris_all_closed"] is False:
        failures.append(
            f"slow-loris connections outlived the socket deadline: {result}"
        )
    if failures:
        for f in failures:
            print(f"[soak] FAIL (qos phase): {f}", file=sys.stderr)
        return 1
    overload = max(result["points"], key=lambda p: p["multiplier"])
    print(
        f"[soak] qos phase green: {len(result['points'])}-point sweep, overload "
        f"tput {overload['tput_rps']} rps / shed {overload['shed_rate']:.0%}, "
        f"head p99 {overload.get('head_p99_ms')}ms, "
        f"{checks['adaptive_wait_adjustments']} adaptive-wait adjustments, "
        f"no starvation, loris closed"
    )
    return 0


def _sanitizer_phase() -> int:
    """Lockset-sanitized serving soak (PR 17): phantsan — the Eraser-style
    race detector in phant_tpu/analysis/sanitizer.py — watches a depth-2
    pipelined scheduler under multi-threaded submit pressure with
    instrumented lock proxies and per-field lockset tracking. ANY race
    report (two-stack, field-level) fails the phase: the sanitizer's
    perturbation of lock timing is exactly the stress the pytest groups
    can't apply, and it has already caught real resolve-before-count and
    lazy-init races in this scheduler.

    Only VerificationScheduler is registered here (NOT the obs
    singletons): lock proxies wrap Lock()/RLock() calls made AFTER
    enable(), and flight/metrics built their real locks at module import
    — tracking them now would report their correctly-locked accesses as
    unprotected. The pytest sanitizer session (PHANT_SANITIZE=1, enabled
    at conftest import before anything else) covers those classes."""
    from phant_tpu.analysis import sanitizer
    from phant_tpu.ops.witness_engine import WitnessEngine

    from test_serving import _witness_set

    failures: list = []
    # enable BEFORE constructing the scheduler: only locks created after
    # enable() are proxies, and field tracking needs the class registered
    # before the instance starts writing
    sanitizer.enable()
    from phant_tpu.serving.scheduler import VerificationScheduler

    sanitizer.register_shared_class(VerificationScheduler)
    try:
        from phant_tpu.serving import SchedulerConfig

        wits = _witness_set(96, trie_size=512, picks=8, seed=23)
        with VerificationScheduler(
            engine=WitnessEngine(),
            config=SchedulerConfig(
                max_batch=8, max_wait_ms=5.0, queue_depth=4096,
                pipeline_depth=2,
            ),
        ) as s:
            with ThreadPoolExecutor(max_workers=6) as pool:
                outs = list(
                    pool.map(
                        lambda w: s.submit_witness(*w).result(timeout=120),
                        wits,
                    )
                )
            st = s.stats_snapshot()
        if not all(outs):
            failures.append(f"sanitized verdicts not all VALID: {sum(outs)}/{len(outs)}")
        if st["pipelined_batches"] < 1:
            failures.append(f"sanitized soak never pipelined: {st}")
    finally:
        reports = sanitizer.drain_reports()
        sanitizer.unregister(VerificationScheduler)
        sanitizer.disable()
    for r in reports:
        failures.append("phantsan race report:\n" + r.format())
    if failures:
        for f in failures:
            print(f"[soak] FAIL (sanitizer phase): {f}", file=sys.stderr)
        return 1
    print(
        f"[soak] sanitizer phase green: {len(wits)} sanitized verifications "
        f"over 6 threads at depth 2, {st['pipelined_batches']} pipelined "
        "batches, zero race reports"
    )
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
