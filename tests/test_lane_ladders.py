"""Every served device program is launched on a declared rung (PR 34).

`ecrecover_kernel` and the resident table's verdict, update and gather
programs were keyed on the size of the wave that reached them, and which
requests share a wave is up to a 5 ms assembly window (PERF.md section 7,
fault 0c). Pinned here, on the XLA-CPU proxy: the rung functions are closed
under any wave and leave a lone request where it was; a wave above a
(test-sized) top rung goes out as several launches and answers as one
launch and as the host route do, a bad signature and a tampered node in the
second chunk refused for their own reason; and the deployment of the
benchmark's `serve-mpt-shared-1chip` configuration, four clients in step on
one server, against the plain reference.
"""

from __future__ import annotations

import json
import os
import sys
from pathlib import Path

import numpy as np
import pytest

os.environ.setdefault("JAX_PLATFORMS", "cpu")

from phant_tpu.backend import set_crypto_backend
from phant_tpu.crypto.keccak import keccak256
from phant_tpu.crypto.secp256k1 import N, pubkey_of, sign
from phant_tpu.utils import rungs
from phant_tpu.utils.trace import _labels_key, metrics

from _witnesses import build_witnesses

BENCH = Path(__file__).resolve().parents[1] / "benchmarks"


@pytest.fixture
def tpu_backend(monkeypatch):
    """The device routes on the XLA-CPU proxy, residency forced."""
    monkeypatch.setenv("PHANT_ALLOW_JAX_CPU", "1")
    monkeypatch.setenv("PHANT_RESIDENT", "1")
    set_crypto_backend("tpu")
    yield
    set_crypto_backend("cpu")


def _counter(name: str, **labels) -> int:
    return metrics.snapshot()["counters"].get(_labels_key(name, labels), 0)


# -- (i) closure --------------------------------------------------------------


#: a request of the deployment's shape: 225 signatures, 1,400-1,580 nodes
LONE_SIGS, LONE_NODES = 225, 1500


def test_a_lone_request_lands_where_it_did():
    from phant_tpu.ops.secp256k1_jax import SIG_LADDER
    from phant_tpu.ops.witness_resident import ROW_LADDER, VERDICT_LADDER, verdict_rung

    assert rungs.launches(SIG_LADDER, LONE_SIGS) == [256]
    for nodes in (1400, LONE_NODES, 1580):
        assert verdict_rung(nodes, 1) == (2048, 1)
        assert rungs.launches(ROW_LADDER, nodes) == [2048]
    assert SIG_LADDER == (256,)  # one rung: the first request builds the only shape
    # a wave's three rungs, and ONE block's at the gas limit (PR 44)
    assert VERDICT_LADDER == ((2048, 1), (4096, 2), (8192, 4), (16384, 1))
    assert ROW_LADDER == (2048,)  # one rung, as the signatures': the boot's seconds


#: one block FULL of transfers between distinct accounts (1,428 senders,
#: 1,428 recipients, the coinbase) under a 2^20 genesis: 9,900-10,050 nodes
GAS_LIMIT_NODES = 10050


@pytest.mark.parametrize("nodes", [8193, GAS_LIMIT_NODES, 16384])
def test_one_block_at_the_gas_limit_sits_on_the_lone_blocks_rung(nodes):
    """Above a wave's widest rung one block takes (16,384, 1), a member of
    the ladder (what `lanes.oversize_launches` counts is a shape that is
    not), alone or between the blocks of a wave, which are cut as before."""
    from phant_tpu.ops.witness_resident import (
        VERDICT_LADDER,
        _verdict_launches,
        verdict_rows,
        verdict_rung,
    )

    assert verdict_rung(nodes, 1) == (16384, 1) and (16384, 1) in VERDICT_LADDER
    assert verdict_rung(nodes, 2) is None  # no wave is cut that wide
    assert _verdict_launches([nodes]) == [(0, 1, (16384, 1))]
    assert verdict_rows([nodes]) == 16384
    assert _verdict_launches([LONE_NODES, nodes, LONE_NODES, LONE_NODES]) == [
        (0, 1, (2048, 1)),
        (1, 2, (16384, 1)),
        (2, 4, (4096, 2)),
    ]


def test_one_block_above_the_lone_blocks_rung_is_still_oversize():
    from phant_tpu.ops.witness_resident import VERDICT_LADDER, _verdict_launches, verdict_rung

    assert verdict_rung(16385, 1) is None
    assert _verdict_launches([16385]) == [(0, 1, (32768, 1))]
    assert (32768, 1) not in VERDICT_LADDER


@pytest.mark.parametrize(
    "wave,cuts",
    [
        (1, [(0, 1, (2048, 1))]),
        (2, [(0, 2, (4096, 2))]),
        (3, [(0, 3, (8192, 4))]),
        (4, [(0, 4, (8192, 4))]),
        (16, [(0, 4, (8192, 4)), (4, 8, (8192, 4)), (8, 12, (8192, 4)), (12, 16, (8192, 4))]),
    ],
)
def test_a_wave_is_cut_where_it_was_before_the_lone_blocks_rung(wave, cuts):
    """The cuts of e003592 (PR 43), whose ladder ended at (8,192, 4): a wave
    of usual blocks is never put on the lone block's rung, five blocks of
    1,500 nodes (7,500 rows) are still cut at four."""
    from phant_tpu.ops.witness_resident import _verdict_launches, _wave_rung

    assert _wave_rung() == (8192, 4)
    assert _verdict_launches([LONE_NODES] * wave) == cuts


@pytest.mark.parametrize("wave", range(1, 10))
def test_a_wave_of_whole_requests_stays_on_the_ladders(wave):
    """Waves of 1..9 requests of the deployment's shape: every launch is a
    member of its declared ladder and together they hold the wave."""
    from phant_tpu.ops.secp256k1_jax import SIG_LADDER
    from phant_tpu.ops.witness_resident import (
        ROW_LADDER,
        VERDICT_LADDER,
        _verdict_launches,
    )

    sigs = rungs.launches(SIG_LADDER, wave * LONE_SIGS)
    assert set(sigs) <= set(SIG_LADDER) and sum(sigs) >= wave * LONE_SIGS
    assert sum(sigs) - wave * LONE_SIGS < SIG_LADDER[-1]  # pads less than a launch
    rows = rungs.launches(ROW_LADDER, wave * LONE_NODES)
    assert set(rows) <= set(ROW_LADDER) and sum(rows) >= wave * LONE_NODES
    cuts = _verdict_launches([LONE_NODES] * wave)
    assert [lo for lo, _hi, _s in cuts] == [0] + [hi for _lo, hi, _s in cuts[:-1]]
    assert cuts[-1][1] == wave
    for lo, hi, shape in cuts:
        assert shape in VERDICT_LADDER
        assert shape[0] >= (hi - lo) * LONE_NODES and shape[1] >= hi - lo
    if wave == 3:
        assert cuts == [(0, 3, (8192, 4))]  # one index: three blocks take the wave's widest rung


@pytest.mark.parametrize("seed", range(8))
def test_random_counts_stay_on_the_ladders(seed):
    """Random signature counts, novel-node counts and waves of random
    blocks up to 4 x the wave's rung: closed, whole blocks a launch, in
    order; one block above the wave's rung stands alone on the lone block's,
    and only one above that leaves the ladder."""
    from phant_tpu.ops.secp256k1_jax import SIG_LADDER
    from phant_tpu.ops.witness_resident import (
        ROW_LADDER,
        VERDICT_LADDER,
        _verdict_launches,
        _wave_rung,
        verdict_rows,
    )

    rng = np.random.default_rng([seed, 0x1ADDE4])
    for ladder in (SIG_LADDER, ROW_LADDER):
        for n in rng.integers(0, 4 * ladder[-1] + 1, 50).tolist():
            got = rungs.launches(ladder, n)
            assert set(got) <= set(ladder) and n <= sum(got) < n + ladder[-1] + ladder[0]
            assert got[:-1] == [ladder[-1]] * (len(got) - 1)
    top_rows, top_blocks = _wave_rung()
    lone_rows = max(rows for rows, _blocks in VERDICT_LADDER)
    for _ in range(20):
        counts = rng.integers(1, 4000, int(rng.integers(1, 40))).tolist()
        if seed % 2:
            counts[int(rng.integers(len(counts)))] = int(rng.integers(top_rows + 1, 4 * top_rows))
        cuts = _verdict_launches(counts)
        assert [c[0] for c in cuts] == [0] + [c[1] for c in cuts[:-1]]
        assert cuts[-1][1] == len(counts)
        for lo, hi, (rows, blocks) in cuts:
            n = sum(counts[lo:hi])
            assert rows >= n and blocks >= hi - lo and hi - lo <= top_blocks
            if (rows, blocks) not in VERDICT_LADDER:
                assert hi - lo == 1 and n > lone_rows and rows == rungs.pow2ceil(n)
            elif n > top_rows:
                assert hi - lo == 1 and (rows, blocks) == (lone_rows, 1)
        assert verdict_rows(counts) == sum(c[2][0] for c in cuts)


def test_rung_of_refuses_what_the_caller_should_have_split():
    assert rungs.rung_of((4, 8), 5) == 8
    with pytest.raises(ValueError, match="top rung"):
        rungs.rung_of((4, 8), 9)
    assert [rungs.pow2ceil(n) for n in (0, 1, 2, 3, 1024, 1025)] == [1, 1, 2, 4, 1024, 2048]


def test_small_signature_rungs_are_the_cpus_alone(monkeypatch):
    import jax

    from phant_tpu.ops import secp256k1_jax as sj

    assert sj.sig_ladder() == sj.SIG_SMALL_RUNGS + sj.SIG_LADDER  # this suite runs on the CPU
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert sj.sig_ladder() == sj.SIG_LADDER
    assert rungs.launches(sj.sig_ladder(), 3) == [256]  # what the boot built, never less


# -- (ii) split launches ------------------------------------------------------


def _signatures(n: int, seed: int):
    rng = np.random.default_rng(seed)
    msgs, rs, ss, recids, want = [], [], [], [], []
    for i in range(n):
        key = int.from_bytes(rng.bytes(32), "big") % N or 1
        msg = keccak256(rng.bytes(40 + i))
        r, s, parity = sign(msg, key)
        msgs.append(msg)
        rs.append(r)
        ss.append(s)
        recids.append(parity)
        want.append(keccak256(pubkey_of(key)[1:])[12:])
    return msgs, rs, ss, recids, want


def test_signatures_above_the_top_rung_go_out_as_launches_of_it(monkeypatch):
    """A ladder cut to (32,): the 40 signatures of two requests, merged by
    the sig engine, are two launches (12 s each on XLA-CPU, hence no more),
    joined so that each request sees the senders its keys give and the
    host route recovers, a bad signature in the second launch None and its
    neighbours untouched."""
    from phant_tpu.ops import secp256k1_jax as sj
    from phant_tpu.ops.sig_engine import SigEngine
    from phant_tpu.signer.signer import SigRows

    monkeypatch.setattr(sj, "SIG_SMALL_RUNGS", ())
    monkeypatch.setattr(sj, "SIG_LADDER", (32,))
    msgs, rs, ss, recids, want = _signatures(40, 34)
    rs[35] = 0  # invalid: r = 0, in the second launch
    want[35] = None
    rows = [
        SigRows(msgs[a:b], rs[a:b], ss[a:b], recids[a:b], set())
        for a, b in ((0, 20), (20, 40))
    ]
    split0 = _counter("lanes.split_launches", program="ecrecover")
    on32 = _counter("lanes.launches", program="ecrecover", rung="32")
    real0, pad0 = _counter("sig.rows", kind="real"), _counter("sig.rows", kind="pad")
    eng = SigEngine(device_floor=0)
    set_crypto_backend("tpu")
    try:
        got = eng.sig_many(rows)
    finally:
        set_crypto_backend("cpu")
    assert eng.stats["device_batches"] == 1
    assert got == [want[:20], want[20:]]
    assert _counter("lanes.split_launches", program="ecrecover") == split0 + 2
    assert _counter("lanes.launches", program="ecrecover", rung="32") == on32 + 2
    assert _counter("sig.rows", kind="real") == real0 + 40
    assert _counter("sig.rows", kind="pad") == pad0 + 24
    assert SigEngine(device_floor=1 << 30).sig_many(rows) == got


def _tamper(wits: list, block: int) -> list:
    """`wits` with one byte of one node of `block` flipped."""
    root, nodes = wits[block]
    victim = max(nodes, key=len)
    flipped = victim[:1] + bytes([victim[1] ^ 1]) + victim[2:]
    out = list(wits)
    out[block] = (root, [flipped if n == victim else n for n in nodes])
    return out


def test_a_witness_wave_above_the_top_rung_is_cut_between_blocks(tpu_backend, monkeypatch):
    """Ten blocks on ladders cut to test size go out as five verdict
    launches of two blocks and four update launches; verdicts and digests
    are those of one launch and of the host route, and the block with a
    tampered node, which lies in the fourth launch, is refused alone."""
    from phant_tpu.ops import witness_resident as wr
    from phant_tpu.ops.witness_engine import WitnessEngine

    _root, wits = build_witnesses(n_blocks=10)
    wits = _tamper(wits, 7)
    set_crypto_backend("cpu")
    want = np.asarray(WitnessEngine(resident=False).verify_batch(wits))
    set_crypto_backend("tpu")
    assert want.tolist() == [True] * 7 + [False] + [True] * 2
    novel = list(dict.fromkeys(n for _r, nodes in wits for n in nodes))
    assert max(len(nodes) for _r, nodes in wits) <= 32 < len(novel) <= 128

    def run(verdict_ladder, row_ladder):
        monkeypatch.setattr(wr, "VERDICT_LADDER", verdict_ladder)
        monkeypatch.setattr(wr, "ROW_LADDER", row_ladder)
        was = {
            p: _counter("lanes.split_launches", program=p)
            for p in ("verdict", "update", "gather")
        }
        table = wr.ResidentTable(max_cap=1024, start_cap=1024)
        verdicts, digests = table.dispatch(wits, novel).resolve()
        split = {p: _counter("lanes.split_launches", program=p) - n for p, n in was.items()}
        return verdicts, digests, split

    one_v, one_d, one_split = run(((512, 16),), (128,))
    cut_v, cut_d, cut_split = run(((64, 2),), (32,))
    assert one_split == {"verdict": 0, "update": 0, "gather": 0}
    n_rows = -(-len(novel) // 32)
    assert cut_split == {"verdict": 5, "update": n_rows, "gather": n_rows}
    assert one_v.tolist() == cut_v.tolist() == want.tolist()
    assert one_d == cut_d == [keccak256(n) for n in novel]
    # and through the engine, whose host tables commit from those digests
    eng = WitnessEngine(resident=True, resident_cap=1024)
    assert np.asarray(eng.verify_batch(wits)).tolist() == want.tolist()
    assert np.asarray(eng.verify_batch(wits)).tolist() == want.tolist()  # nothing novel


def test_one_block_above_the_top_rung_keeps_a_shape_of_its_own(tpu_backend, monkeypatch):
    from phant_tpu.ops import witness_resident as wr

    monkeypatch.setattr(wr, "VERDICT_LADDER", ((4, 1), (8, 2)))
    monkeypatch.setattr(wr, "ROW_LADDER", (32,))
    _root, wits = build_witnesses(n_blocks=3)
    assert all(len(nodes) > 8 for _r, nodes in wits)
    over0 = _counter("lanes.oversize_launches", program="verdict")
    table = wr.ResidentTable(max_cap=1024, start_cap=1024)
    verdicts, _d = table.dispatch(wits, []).resolve()
    assert verdicts.tolist() == [True] * 3
    assert _counter("lanes.oversize_launches", program="verdict") == over0 + 3


def test_one_block_on_the_lone_blocks_rung_is_launched_alone_and_counted_nowhere(tpu_backend, monkeypatch):
    """The same three blocks, each wider than the wave's rung, on a ladder
    that has a rung for one such block: three launches of it, the verdicts
    of the host route (the block with a tampered node refused alone), and
    `lanes.oversize_launches` where it stood."""
    from phant_tpu.ops import witness_resident as wr

    monkeypatch.setattr(wr, "VERDICT_LADDER", ((4, 1), (8, 2), (64, 1)))
    monkeypatch.setattr(wr, "ROW_LADDER", (32,))
    _root, wits = build_witnesses(n_blocks=3)
    assert all(8 < len(nodes) <= 64 for _r, nodes in wits)
    over0 = _counter("lanes.oversize_launches", program="verdict")
    on64 = _counter("lanes.launches", program="verdict", rung="64x1")
    table = wr.ResidentTable(max_cap=1024, start_cap=1024)
    verdicts, _d = table.dispatch(_tamper(wits, 1), []).resolve()
    assert verdicts.tolist() == [True, False, True]
    assert _counter("lanes.launches", program="verdict", rung="64x1") == on64 + 3
    assert _counter("lanes.oversize_launches", program="verdict") == over0


# -- (iii) the deployment against the plain reference ---------------------------


@pytest.fixture
def bench_path():
    sys.path.insert(0, str(BENCH))
    yield
    sys.path.remove(str(BENCH))


def test_four_clients_in_step_against_the_plain_reference(tpu_backend, bench_path, tmp_path, monkeypatch):
    """`serve-mpt-shared-1chip` at test size: the bare command's server on
    the XLA-CPU proxy (root lane forced as the rehearsal forces it), four
    clients posting each block of a seeded reference chain in step. Every
    answer VALID with the reference's root, some waves coalesced, every
    launch on a declared rung, the five altered bodies refused."""
    from drivers.serve import PROBES
    from harness.clients import in_step
    from reference import keccak
    from reference.chain import Chain, hx

    from phant_tpu.__main__ import build_parser, build_server
    from phant_tpu.ops.secp256k1_jax import sig_ladder
    from phant_tpu.ops.witness_resident import ROW_LADDER, VERDICT_LADDER

    monkeypatch.setenv("PHANT_BATCHED_ROOT", "1")
    monkeypatch.setenv("PHANT_ROOT_DEVICE_FLOOR", "0")
    keccak.load(tmp_path)
    chain = Chain(
        3400000034,
        dict(genesis_log2=10, sender_pool=300, contracts=4, zipf_s=1.0, transfers_per_block=4,
             calls_per_block=2, cold_recipient_share=0.5, slots_per_contract=64),
    )  # fmt: skip
    chain.extend(3)
    bodies = {i: b.body(i + 1) for i, b in enumerate(chain.blocks)}
    for j, (what, *_rest) in enumerate(PROBES):
        bodies[-j - 1] = chain.blocks[2].body_altered(what, j + 1)
    config = json.loads((BENCH / "configs" / "serve-mpt-shared-1chip.json").read_text())
    traffic = json.loads((BENCH / "traffic" / "heads4.json").read_text())
    assert "env" not in config and traffic["mode"] == "in_step" and traffic["clients"] == 4
    coalesced0 = _counter("sched.coalesced_requests")
    programs = ("ecrecover", "verdict", "update", "gather")
    seen = {p: rungs.shapes_of(p) for p in programs}  # other tests cut the ladders
    server = build_server(build_parser().parse_args(config["argv"]))
    server.serve_in_background()
    try:
        plans = [list(range(len(chain.blocks)))] * traffic["clients"]
        _o, _c, _x, records = in_step.run("127.0.0.1", server.port, bodies, plans, None, traffic)
        _o, _c, _x, probes = in_step.run(
            "127.0.0.1", server.port, bodies, [[-j - 1 for j in range(len(PROBES))]], None, traffic
        )
    finally:
        server.shutdown()
    assert len(records) == 4 * len(chain.blocks)
    for _who, idx, _t0, _t1, code, reply in records:
        result = json.loads(reply)["result"]
        assert code == 200 and result["status"] == "VALID", (idx, reply[:300])
        assert result["stateRoot"] == hx(chain.blocks[idx].header.state_root), idx
    assert _counter("sched.coalesced_requests") > coalesced0
    for _who, k, _t0, _t1, code, reply in probes:
        what, _number, any_of, none_of = PROBES[-k - 1]
        result = json.loads(reply)["result"]
        err = (result.get("validationError") or "").lower()
        assert code == 200 and result["status"] == "INVALID", (what, reply[:300])
        assert not any_of or any(w in err for w in any_of), (what, err)
        assert not any(w in err for w in none_of), (what, err)
    met = {p: {s[0] for s in rungs.shapes_of(p) - seen[p]} for p in programs}
    assert met["ecrecover"] <= set(sig_ladder())
    assert met["verdict"] <= set(VERDICT_LADDER)
    assert met["update"] <= set(ROW_LADDER) and met["gather"] <= set(ROW_LADDER)


def test_the_boot_builds_every_rung_and_a_wave_builds_none(tpu_backend, monkeypatch):
    """What a server's start runs on an accelerator (`prewarm_lanes`), on
    ladders cut to test size: every rung of the table's programs is built
    on the engine's own table, which stays empty; the waves served after
    it build nothing. On the CPU the boot only exports the family."""
    from phant_tpu import serving as srv
    from phant_tpu.ops import witness_resident as wr
    from phant_tpu.ops.witness_engine import WitnessEngine
    from phant_tpu.serving.scheduler import SchedulerConfig, VerificationScheduler

    monkeypatch.setattr(wr, "VERDICT_LADDER", ((32, 1), (128, 4), (256, 1)))
    monkeypatch.setattr(wr, "ROW_LADDER", (16, 128))
    eng = WitnessEngine(resident=True, resident_cap=512)
    _root, wits = build_witnesses(n_blocks=6)
    with VerificationScheduler(engine=eng, config=SchedulerConfig()) as s:
        srv.boot_lanes(s)  # this suite runs on the CPU: nothing is built
        assert eng.resident_table() is None
        gauges = metrics.snapshot()["gauges"]
        assert all(f'lanes.program_shapes{{program="{p}"}}' in gauges for p in srv.LANE_PROGRAMS)
        seen = {p: rungs.shapes_of(p) for p in srv.LANE_PROGRAMS}
        assert s.prewarm_lanes() == 2 * 2 + 3
        table = eng.resident_table()
        assert table is not None and table.rows() == 0
        built = {p: {sh[0] for sh in rungs.shapes_of(p) - seen[p]} for p in srv.LANE_PROGRAMS}
        assert built["verdict"] == {(32, 1), (128, 4), (256, 1)}  # the lone block's rung too
        assert built["update"] == built["gather"] == {16, 128}
        assert built["ecrecover"] == set()  # one rung, left to the first request
        sizes = [f._cache_size() for f in (table._update_fn, table._verdict_fn, table._gather_fn)]
        at_boot = {p: rungs.shapes_of(p) for p in srv.LANE_PROGRAMS}
        for wave in (wits[:1], wits[1:3], wits[3:6]):
            assert list(s.verify_many(wave)) == [True] * len(wave)
        assert list(s.verify_many(_tamper(wits, 4))) == [True] * 4 + [False, True]
        assert table.rows() > 0
        assert sizes == [f._cache_size() for f in (table._update_fn, table._verdict_fn, table._gather_fn)]
        assert at_boot == {p: rungs.shapes_of(p) for p in srv.LANE_PROGRAMS}


def test_the_boots_build_is_over_before_the_port_answers(tpu_backend, monkeypatch):
    """On an accelerator the lanes' programs are built in the server's
    constructor, as the root lane's are: `build_server` returns when the
    build is over, on the thread that called it, and only then does a
    request get an answer (here a made-up build, with jax's backend said
    to be an accelerator's). A build that fails fails the constructor."""
    import threading
    import urllib.request

    from phant_tpu import serving
    from phant_tpu.engine_api import server as srv
    from phant_tpu.serving.scheduler import VerificationScheduler

    calls = []

    def build(self):
        calls.append(threading.current_thread() is threading.main_thread())
        return 6

    monkeypatch.setattr(VerificationScheduler, "prewarm_lanes", build)
    monkeypatch.setattr(serving, "on_cpu", lambda: False)
    monkeypatch.setattr(srv, "_boot_root_lane", lambda: None)
    from phant_tpu.__main__ import build_parser, build_server

    argv = build_parser().parse_args(["--crypto_backend=tpu", "--engine_api_port", "0"])
    metrics.gauge_set("lanes.prewarm_seconds", -1.0)
    server = build_server(argv)
    try:
        assert calls == [True]
        assert metrics.snapshot()["gauges"]["lanes.prewarm_seconds"] >= 0
        server.serve_in_background()
        url = f"http://127.0.0.1:{server.port}"
        with urllib.request.urlopen(url + "/metrics", timeout=10) as r:
            assert "phant_lanes_program_shapes" in r.read().decode()
        body = json.dumps({"jsonrpc": "2.0", "id": 1, "method": "engine_exchangeCapabilities", "params": [[]]})
        req = urllib.request.Request(url, body.encode(), {"Content-Type": "application/json"})
        with urllib.request.urlopen(req, timeout=30) as r:
            assert json.loads(r.read())["id"] == 1
    finally:
        server.shutdown()

    def broken(self):
        raise RuntimeError("no such rung")

    monkeypatch.setattr(VerificationScheduler, "prewarm_lanes", broken)
    with pytest.raises(RuntimeError, match="no such rung"):
        build_server(argv)
