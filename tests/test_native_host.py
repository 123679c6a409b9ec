"""The native VM's host binding (native/pyext.cc `EvmHost`, behind
evm/native_vm.BlockHost): every callback of the table answers as the Python
interpreter does, nested frames re-enter it, a host-side error is raised once
the C++ stack has unwound, one binding serves a block's transactions, and
nothing of it outlives the block."""

import gc
import threading
import weakref

import pytest

from phant_tpu.backend import set_evm_backend
from phant_tpu.crypto.keccak import keccak256
from phant_tpu.evm import gas as G
from phant_tpu.evm.interpreter import Evm, create2_address, create_address
from phant_tpu.evm.message import (
    REVISION_CANCUN,
    REVISION_PRAGUE,
    REVISION_SHANGHAI,
    Environment,
    Message,
)
from phant_tpu.evm.native_vm import BlockHost, native_available
from phant_tpu.state.statedb import StateDB
from phant_tpu.types.account import Account
from phant_tpu.utils.trace import metrics

SENDER = b"\x10" * 20
A = b"\xaa" * 20  # the contract a message enters
B = b"\xbb" * 20
C = b"\xcc" * 20
LIB = b"\x1b" * 20
RICH = b"\x51" * 20
NOBODY = b"\x0e" * 20  # not in the state
DELEGATED = b"\xde" * 20  # 0xef0100 || LIB

OPS = {
    "STOP": 0x00, "ADD": 0x01, "KECCAK256": 0x20, "ADDRESS": 0x30,
    "BALANCE": 0x31, "ORIGIN": 0x32, "CALLER": 0x33, "CALLVALUE": 0x34,
    "CALLDATALOAD": 0x35, "GASPRICE": 0x3A, "EXTCODESIZE": 0x3B,
    "EXTCODECOPY": 0x3C, "RETURNDATASIZE": 0x3D, "RETURNDATACOPY": 0x3E,
    "EXTCODEHASH": 0x3F, "BLOCKHASH": 0x40, "COINBASE": 0x41,
    "TIMESTAMP": 0x42, "NUMBER": 0x43, "PREVRANDAO": 0x44, "GASLIMIT": 0x45,
    "CHAINID": 0x46, "SELFBALANCE": 0x47, "BASEFEE": 0x48, "BLOBHASH": 0x49,
    "BLOBBASEFEE": 0x4A, "POP": 0x50, "MLOAD": 0x51, "MSTORE": 0x52,
    "SLOAD": 0x54, "SSTORE": 0x55, "GAS": 0x5A, "TLOAD": 0x5C, "TSTORE": 0x5D,
    "DUP1": 0x80, "LOG0": 0xA0, "LOG1": 0xA1, "LOG2": 0xA2, "LOG3": 0xA3,
    "LOG4": 0xA4, "CREATE": 0xF0, "CALL": 0xF1, "CALLCODE": 0xF2,
    "RETURN": 0xF3, "DELEGATECALL": 0xF4, "CREATE2": 0xF5, "STATICCALL": 0xFA,
    "REVERT": 0xFD, "SELFDESTRUCT": 0xFF,
}  # fmt: skip


def asm(*parts) -> bytes:
    """An opcode by name, an int as its shortest PUSH, bytes as one PUSH."""
    out = b""
    for part in parts:
        if isinstance(part, str):
            out += bytes([OPS[part]])
            continue
        raw = part if isinstance(part, bytes) else part.to_bytes(max(1, (part.bit_length() + 7) // 8), "big")
        assert 1 <= len(raw) <= 32
        out += bytes([0x5F + len(raw)]) + raw
    return out


def returning(*words: bytes) -> bytes:
    """Each fragment leaves one word on the stack; all are returned."""
    code = b"".join(word + asm(32 * i, "MSTORE") for i, word in enumerate(words))
    return code + asm(32 * len(words), 0, "RETURN")


def call(kind: str, to: bytes, *, value: int = 0, args=(0, 0), ret=(0, 0), gas=None) -> bytes:
    """One CALL-family instruction; leaves its success flag on the stack."""
    code = asm(ret[1], ret[0], args[1], args[0])
    if kind in ("CALL", "CALLCODE"):
        code += asm(value)
    return code + asm(to) + (asm("GAS") if gas is None else asm(gas)) + asm(kind)


def words(data: bytes):
    return [int.from_bytes(data[i : i + 32], "big") for i in range(0, len(data), 32)]


@pytest.fixture(autouse=True)
def _native_or_skip():
    if not native_available():
        pytest.skip("native toolchain unavailable")
    yield
    set_evm_backend("python")


def block_hash(number: int) -> bytes:
    return keccak256(number.to_bytes(8, "big"))


def environment(state, **over) -> Environment:
    fields = dict(
        state=state, origin=SENDER, coinbase=b"\xc0" * 20, block_number=300,
        gas_limit=30_000_000, gas_price=17, timestamp=1_700_000_000,
        prev_randao=b"\x5a" * 32, base_fee=7, chain_id=1,
        block_hash_fn=block_hash, revision=REVISION_SHANGHAI,
    )  # fmt: skip
    fields.update(over)
    return Environment(**fields)


def seen(state: StateDB, result) -> dict:
    """Everything a frame can leave behind, in a comparable form."""
    return {
        "result": (result.success, result.gas_left, result.output, result.create_address, result.is_revert),
        "accounts": {
            addr: (acct.nonce, acct.balance, acct.code, sorted((k, v) for k, v in acct.storage.items() if v))
            for addr, acct in state.accounts.items()
        },
        "logs": [(log.address, log.topics, log.data) for log in state.logs],
        "refund": state.refund,
        "selfdestructs": sorted(state.selfdestructs),
        "warm": (sorted(state.accessed_addresses), sorted(state.accessed_storage_keys)),
        "touched": sorted(state.touched),
        "created": sorted(state.created),
        "transient": sorted(state.transient.items()),
    }


def run(backend: str, accounts: dict, *, env=None, gas=1_000_000, value=0, data=b"", target=A):
    set_evm_backend(backend)
    state = StateDB({addr: acct.copy() for addr, acct in accounts.items()})
    state.start_tx()
    state.access_address(SENDER)
    if target is not None:
        state.access_address(target)
    else:
        state.increment_nonce(SENDER)  # as the transaction's processing does
    evm = Evm(environment(state, **(env or {})))
    result = evm.execute_message(Message(caller=SENDER, target=target, value=value, data=data, gas=gas))
    return seen(state, result)


def both(accounts: dict, **kw) -> dict:
    """The same message under both backends: equal in everything it left."""
    python = run("python", accounts, **kw)
    native = run("native", accounts, **kw)
    assert native == python
    return native


def base_accounts(code: bytes, more=None) -> dict:
    accounts = {
        SENDER: Account(balance=10**18),
        A: Account(code=code, balance=1_000),
        LIB: Account(code=returning(asm(42)) + b"\x00" * 7, balance=5),
        RICH: Account(balance=123_456_789),
        DELEGATED: Account(code=G.DELEGATION_PREFIX + LIB, nonce=1),
    }
    accounts.update(more or {})
    return accounts


# --- every callback of the table, against the interpreter -------------------

RUNTIME = asm(7, 0, "SSTORE")  # what the created contracts hold
INIT = asm(3, 0, "SSTORE") + asm(RUNTIME, 0, "MSTORE") + asm(len(RUNTIME), 32 - len(RUNTIME), "RETURN")
INIT_REVERTS = asm(0xBAD, 0, "MSTORE", 32, 0, "REVERT")


INIT_AT = 0x300  # where a creator keeps its init code: clear of what it returns


def store_init(init: bytes) -> bytes:
    """The init code into memory (it is under 32 bytes: one word)."""
    assert len(init) <= 32
    return asm(init.ljust(32, b"\x00"), INIT_AT, "MSTORE")


CALLBACK_CASES = {
    # access_storage + get_storage: cold, then warm, then an empty slot
    "sload": dict(code=returning(asm(5, "SLOAD"), asm(5, "SLOAD"), asm(6, "SLOAD")), storage={5: 77}),
    # set_storage, get_original_storage, add_refund both ways: clear a set
    # slot, restore it, clear it again; set an empty slot and empty it again
    "sstore_refund": dict(
        code=asm(0, 0, "SSTORE", 1, 0, "SSTORE", 0, 0, "SSTORE", 5, 1, "SSTORE", 0, 1, "SSTORE", 9, 2, "SSTORE"),
        storage={0: 1, 2: 4},
    ),
    # access_account + get_balance: a cold account, itself, nobody
    "balance": dict(code=returning(asm(RICH, "BALANCE"), asm("SELFBALANCE"), asm(NOBODY, "BALANCE"), asm(RICH, "BALANCE"))),
    # get_code_size, copy_code (past the code's end), get_code_hash, is_empty
    "extcode": dict(
        code=returning(
            asm(40, 3, 0x200, LIB, "EXTCODECOPY", 0x200, "MLOAD"),
            asm(0x220, "MLOAD"),
            asm(LIB, "EXTCODESIZE"),
            asm(LIB, "EXTCODEHASH"),
            asm(NOBODY, "EXTCODESIZE"),
            asm(NOBODY, "EXTCODEHASH"),
            asm(RICH, "EXTCODEHASH"),
        )
    ),
    # EIP-7702: EXTCODE* of a delegated account see the marker alone
    "extcode_delegated": dict(
        code=returning(
            asm(DELEGATED, "EXTCODESIZE"),
            asm(DELEGATED, "EXTCODEHASH"),
            asm(32, 0, 0x200, DELEGATED, "EXTCODECOPY", 0x200, "MLOAD"),
        ),
        env=dict(revision=REVISION_PRAGUE),
    ),
    # get_block_hash: the newest, the oldest in reach, one too old, itself, 2^64 + 5
    "blockhash": dict(
        code=returning(*(asm(n, "BLOCKHASH") for n in (299, 44, 43, 300, 2**64 + 5))),
    ),
    # emit_log: LOG0..LOG4, with and without data
    "logs": dict(
        code=asm(b"\xd1" * 32, 0, "MSTORE")
        + asm(32, 0, "LOG0")
        + asm(b"\x01" * 32, 5, 3, "LOG1")  # 5 bytes from 3
        + asm(b"\x02" * 32, b"\x01" * 32, 0, 0, "LOG2")
        + asm(b"\x03" * 32, b"\x02" * 32, b"\x01" * 32, 64, 0, "LOG3")
        + asm(b"\x04" * 32, b"\x03" * 32, b"\x02" * 32, b"\x01" * 32, 1, 31, "LOG4"),
    ),
    # get_transient / set_transient
    "transient": dict(
        code=asm(9, 1, "TSTORE") + returning(asm(1, "TLOAD"), asm(2, "TLOAD")),
        env=dict(revision=REVISION_CANCUN),
    ),
    # selfdestruct: to a cold account that does not exist yet
    "selfdestruct": dict(code=asm(NOBODY, "SELFDESTRUCT")),
    "selfdestruct_cancun": dict(code=asm(RICH, "SELFDESTRUCT"), env=dict(revision=REVISION_CANCUN)),
    # delegate_access_cost: a CALL through a delegated account pays for the
    # delegate, cold the first time and warm the second
    "delegated_call_cost": dict(
        code=returning(
            call("CALL", DELEGATED, ret=(0x200, 32)),
            asm("GAS"),
            call("STATICCALL", DELEGATED, ret=(0x220, 32)),
            asm("GAS"),
            asm(0x200, "MLOAD"),
            asm(0x220, "MLOAD"),
        ),
        env=dict(revision=REVISION_PRAGUE),
    ),
    # the transaction's and the block's context
    "context": dict(
        code=returning(
            *(asm(name) for name in ("ORIGIN", "GASPRICE", "COINBASE", "TIMESTAMP", "NUMBER", "PREVRANDAO", "GASLIMIT", "CHAINID", "BASEFEE", "BLOBBASEFEE", "CALLER", "CALLVALUE", "ADDRESS")),
            *(asm(i, "BLOBHASH") for i in range(3)),
        ),
        env=dict(revision=REVISION_CANCUN, blob_hashes=(b"\x01" + b"\xb1" * 31, b"\x01" + b"\xb2" * 31), blob_base_fee=2**70 + 3, chain_id=2**40 + 1),
        value=77,
    ),
    # call: value to nobody (is_empty), a precompile, a contract; the child's output
    "call_kinds": dict(
        code=asm(b"\x77" * 32, 0x300, "MSTORE")
        + returning(
            call("CALL", NOBODY, value=5),
            call("CALL", b"\x00" * 19 + b"\x04", args=(0x300, 32), ret=(0x200, 32)),
            asm(0x200, "MLOAD"),
            call("STATICCALL", LIB, ret=(0x220, 32)),
            asm(0x220, "MLOAD"),
            call("CALLCODE", LIB, value=1, ret=(0x240, 32)),
            call("DELEGATECALL", LIB, ret=(0x260, 16)),
            asm(0x260, "MLOAD"),
            asm("RETURNDATASIZE"),
            call("CALL", RICH, value=10**9),  # more than it has
        ),
    ),
    # a static frame may not write: the child's SSTORE fails, the parent goes on
    "static_violation": dict(
        code=returning(call("STATICCALL", B), asm("GAS"), asm(1, "SLOAD")),
        more={B: Account(code=asm(1, 1, "SSTORE"))},
    ),
    # call with CREATE and CREATE2: the address, the init code's writes, the deposit
    "create": dict(
        code=store_init(INIT)
        + returning(
            asm(len(INIT), INIT_AT, 0, "CREATE"),
            asm(0x5A17, len(INIT), INIT_AT, 1, "CREATE2"),
            asm(0x5A17, len(INIT), INIT_AT, 1, "CREATE2"),  # a collision
            asm("RETURNDATASIZE"),
        ),
    ),
    "create_reverts": dict(
        code=store_init(INIT_REVERTS)
        + returning(
            asm(len(INIT_REVERTS), INIT_AT, 0, "CREATE"),
            asm("RETURNDATASIZE"),
            asm(32, 0, 0x200, "RETURNDATACOPY", 0x200, "MLOAD"),
        ),
    ),
}


@pytest.mark.parametrize("case", sorted(CALLBACK_CASES))
def test_callback_answers_as_the_interpreter(case):
    spec = CALLBACK_CASES[case]
    accounts = base_accounts(spec["code"], spec.get("more"))
    accounts[A].storage.update(spec.get("storage", {}))
    got = both(accounts, env=spec.get("env"), value=spec.get("value", 0))
    assert got["result"][0], got["result"]  # every case's outer frame succeeds
    out = words(got["result"][2])
    # and what the interpreter said is what the opcode means
    if case == "sload":
        assert out == [77, 77, 0]
    elif case == "sstore_refund":
        assert got["accounts"][A][3] == [(2, 9)] and got["refund"] > 0
    elif case == "balance":
        assert out == [123_456_789, 1_000, 0, 123_456_789]
    elif case == "extcode":
        lib = accounts[LIB].code
        assert out[0] == int.from_bytes(lib[3:35].ljust(32, b"\x00"), "big")
        assert out[2:] == [len(lib), int.from_bytes(keccak256(lib), "big"), 0, 0, int.from_bytes(keccak256(b""), "big")]
    elif case == "extcode_delegated":
        assert out == [2, int.from_bytes(G.DELEGATION_MARKER_HASH, "big"), int.from_bytes(G.DELEGATION_MARKER.ljust(32, b"\x00"), "big")]
    elif case == "blockhash":
        assert out == [int.from_bytes(block_hash(299), "big"), int.from_bytes(block_hash(44), "big"), 0, 0, 0]
    elif case == "logs":
        assert [len(topics) for _a, topics, _d in got["logs"]] == [0, 1, 2, 3, 4]
        assert [len(data) for _a, _t, data in got["logs"]] == [32, 5, 0, 64, 1]
    elif case == "transient":
        assert out == [9, 0] and got["transient"] == [((A, 1), 9)]
    elif case == "selfdestruct":
        assert got["selfdestructs"] == [A] and got["accounts"][NOBODY][1] == 1_000
    elif case == "delegated_call_cost":
        assert out[0] == out[2] == 1 and out[4] == out[5] == 42
        assert LIB in got["warm"][0]
    elif case == "context":
        assert out[:3] == [int.from_bytes(SENDER, "big"), 17, int.from_bytes(b"\xc0" * 20, "big")]
        assert out[7] == 2**40 + 1 and out[9] == 2**70 + 3 and out[11] == 77
        assert out[13:] == [int.from_bytes(b"\x01" + b"\xb1" * 31, "big"), int.from_bytes(b"\x01" + b"\xb2" * 31, "big"), 0]
    elif case == "call_kinds":
        assert out == [1, 1, int.from_bytes(b"\x77" * 32, "big"), 1, 42, 1, 1, 42 >> 128, 32, 0]
        assert got["accounts"][NOBODY][1] == 5
    elif case == "static_violation":
        assert out[0] == 0 and out[2] == 0 and B in got["accounts"] and not got["accounts"][B][3]
    elif case == "create":
        first, second = create_address(A, 0), create2_address(A, (0x5A17).to_bytes(32, "big"), INIT)
        assert out == [int.from_bytes(first, "big"), int.from_bytes(second, "big"), 0, 0]
        assert got["accounts"][first] == (1, 0, RUNTIME, [(0, 3)])
        assert got["accounts"][second] == (1, 1, RUNTIME, [(0, 3)])
        assert got["accounts"][A][0] == 3  # a failed CREATE2 spends a nonce too
    elif case == "create_reverts":
        assert out == [0, 32, 0xBAD] and got["accounts"][A][0] == 1


# --- nested frames: the journal and the output buffers under re-entry --------


def _chain_of_three(kind: str) -> dict:
    """A -> B -> C three frames deep; C writes, logs and returns a word, B
    writes, takes C's word and REVERTS with it and one of its own, A writes
    and returns B's revert data with B's flag."""
    def nested(to):
        return call(kind, to, ret=(0, 32), gas=100_000)

    code_c = asm(3, 0, "SSTORE", b"\x0c" * 32, 0, 0, "LOG1") + returning(asm(0xC0FFEE))
    code_b = asm(2, 0, "SSTORE") + nested(C) + asm("POP", 0xB0B, 32, "MSTORE", 64, 0, "REVERT")
    code_a = asm(1, 0, "SSTORE") + nested(B) + asm(
        0x40, "MSTORE", "RETURNDATASIZE", 0x60, "MSTORE", "RETURNDATASIZE", 0, 0, "RETURNDATACOPY", 1, 1, "SSTORE", 0x80, 0, "RETURN"
    )  # fmt: skip
    return base_accounts(code_a, {B: Account(code=code_b, balance=9), C: Account(code=code_c)})


@pytest.mark.parametrize("kind", ["CALL", "DELEGATECALL", "CALLCODE", "STATICCALL"])
def test_three_frames_deep_with_a_revert_in_the_middle(kind):
    got = both(_chain_of_three(kind))
    ok, _gas, output, _created, _revert = got["result"]
    assert ok
    if kind == "STATICCALL":  # B's first write fails the frame: no data, no flag
        assert words(output) == [0, 0, 0, 0]
    else:
        assert words(output) == [0xC0FFEE, 0xB0B, 0, 64]
    # B's and C's writes and C's log went with B's revert, A's stayed
    assert got["accounts"][A][3] == [(0, 1), (1, 1)]
    assert not got["accounts"][B][3] and not got["accounts"][C][3]
    assert got["logs"] == []


def test_a_create2_in_the_middle_frame_is_rolled_back():
    """B creates a contract whose init code writes, then reverts with the
    address it was given: the account is gone, the address is the derived
    one, and A reads it from B's revert data."""
    code_b = store_init(INIT) + asm(0x5A17, len(INIT), INIT_AT, 0, "CREATE2") + asm(
        "DUP1", "EXTCODESIZE", 32, "MSTORE", 0, "MSTORE", 64, 0, "REVERT"
    )  # fmt: skip
    code_a = call("CALL", B) + asm(0x40, "MSTORE", 64, 0, 0, "RETURNDATACOPY", 0x60, 0, "RETURN")
    got = both(base_accounts(code_a, {B: Account(code=code_b, nonce=1)}))
    created = create2_address(B, (0x5A17).to_bytes(32, "big"), INIT)
    assert words(got["result"][2]) == [int.from_bytes(created, "big"), len(RUNTIME), 0]
    assert created not in got["accounts"] and got["accounts"][B][0] == 1


def test_a_call_chain_reaches_the_depth_limit_on_a_server_thread():
    """A contract that counts and calls itself runs to EVM depth 1024 on a
    thread of the default stack size (a handler's), as under the
    interpreter: 1,025 frames, each re-entered through the binding."""
    code = asm(0, "SLOAD", 1, "ADD", 0, "SSTORE") + call("CALL", A) + asm("STOP")
    box = {}

    def work():
        try:
            box["got"] = both(base_accounts(code), gas=10**13)
        except BaseException as e:  # the test's thread reports it
            box["error"] = e

    thread = threading.Thread(target=work)
    thread.start()
    thread.join(timeout=120)
    assert not thread.is_alive() and "error" not in box, box.get("error")
    assert box["got"]["accounts"][A][3] == [(0, 1025)]


# --- a host-side error is raised once the C++ stack has unwound --------------


@pytest.mark.parametrize("method,depth", [("get_storage", 0), ("get_storage", 2), ("access_address", 1), ("add_log", 2), ("get_balance", 0)])
def test_a_host_error_is_raised_after_the_frames_unwound(method, depth):
    """`method` of the state raises while a frame `depth` calls down runs it:
    the error comes out of execute_message as itself (not as a failed call),
    and the same block's binding runs the next transaction."""
    leaf = asm(0, "SLOAD", "POP", b"\x01" * 32, 0, 0, "LOG1", RICH, "BALANCE", "STOP")
    chain = [A, B, C]
    accounts = base_accounts(b"")
    for i, addr in enumerate(chain):
        code = leaf if i >= depth else call("CALL", chain[i + 1]) + asm("STOP")
        accounts[addr] = Account(code=code)
    set_evm_backend("native")
    state = StateDB(accounts)
    state.start_tx()
    host = BlockHost()
    real = getattr(state, method)
    calls = []

    def boom(*args):
        calls.append(args)
        raise RuntimeError(f"boom in {method}")

    def guarded(*args):  # only the leaf's own use of the method raises
        if method == "access_address" and args[0] != RICH:
            return real(*args)
        return boom(*args)

    setattr(state, method, guarded)
    message = Message(caller=SENDER, target=A, value=0, data=b"", gas=500_000)
    with pytest.raises(RuntimeError, match=f"boom in {method}"):
        Evm(environment(state), host).execute_message(message)
    assert len(calls) == 1  # every later callback answered without Python
    delattr(state, method)
    state.start_tx()
    again = Evm(environment(state), host).execute_message(message)
    assert again.success
    host.close()


def test_a_bad_answer_from_the_state_is_an_error_not_a_value():
    """A state method answering what no word can hold is raised, as the
    ctypes table's `to_bytes` raised it."""
    set_evm_backend("native")
    state = StateDB(base_accounts(returning(asm(0, "SLOAD"))))
    state.start_tx()
    state.get_storage = lambda addr, slot: -1
    with pytest.raises(OverflowError):
        Evm(environment(state)).execute_message(Message(caller=SENDER, target=A, value=0, data=b"", gas=100_000))


# --- one binding a block: what a transaction fixes is its own ----------------

CONTEXT_CODE = returning(asm("ORIGIN"), asm("GASPRICE"), asm(0, "BLOBHASH"), asm("BLOBBASEFEE"), asm("NUMBER"))


def _transactions():
    return [
        dict(origin=b"\x01" * 20, gas_price=11, blob_hashes=(b"\x01" + b"\xaa" * 31,), blob_base_fee=3),
        dict(origin=b"\x02" * 20, gas_price=2**200, blob_hashes=(), blob_base_fee=3),
        dict(origin=b"\x03" * 20, gas_price=0, blob_hashes=(b"\x01" + b"\xcc" * 31, b"\x01" + b"\xdd" * 31), blob_base_fee=3),
    ]


def test_transactions_of_one_block_read_their_own_context():
    set_evm_backend("native")
    state = StateDB(base_accounts(CONTEXT_CODE))
    host = BlockHost()
    bindings = metrics.snapshot()["counters"].get("evm.host_bindings", 0)
    for tx in _transactions():
        state.start_tx()
        env = environment(state, revision=REVISION_CANCUN, **tx)
        result = Evm(env, host).execute_message(Message(caller=tx["origin"], target=A, value=0, data=b"", gas=100_000))
        first_hash = int.from_bytes(tx["blob_hashes"][0], "big") if tx["blob_hashes"] else 0
        assert words(result.output) == [int.from_bytes(tx["origin"], "big"), tx["gas_price"], first_hash, 3, 300]
    host.close()
    assert metrics.snapshot()["counters"]["evm.host_bindings"] == bindings + 1


def test_a_tracer_on_one_transaction_of_a_block_and_not_on_the_next():
    """`Evm.tracer` is the transaction's: set on the first and third of three
    that share a binding, their streams are the interpreter's, and the
    second, untraced, runs with the VM's trace slot empty."""
    accounts = _chain_of_three("CALL")

    def block(backend):
        set_evm_backend(backend)
        state = StateDB({addr: acct.copy() for addr, acct in accounts.items()})
        host = BlockHost() if backend == "native" else None
        streams = []
        for traced in (True, False, True):
            state.start_tx()
            evm = Evm(environment(state), host)
            steps = []
            if traced:
                evm.tracer = lambda pc, op, gas, depth, size: steps.append((pc, op, gas, depth, size))
            result = evm.execute_message(Message(caller=SENDER, target=A, value=0, data=b"", gas=400_000))
            streams.append((steps, result.output, result.gas_left))
        if host is not None:
            host.close()
        return streams

    native, python = block("native"), block("python")
    assert native == python
    assert len(native[0][0]) > 40 and native[1][0] == [] and native[2][0]
    assert {depth for _pc, _op, _gas, depth, _size in native[0][0]} == {0, 1, 2}


def test_a_raising_tracer_is_raised_too():
    set_evm_backend("native")
    state = StateDB(base_accounts(asm(1, 2, "ADD", "STOP")))
    state.start_tx()
    evm = Evm(environment(state))

    def tracer(pc, op, gas, depth, size):
        if pc == 2:
            raise KeyError("tracer")

    evm.tracer = tracer
    with pytest.raises(KeyError, match="tracer"):
        evm.execute_message(Message(caller=SENDER, target=A, value=0, data=b"", gas=100_000))


# --- nothing of the binding outlives the block --------------------------------


def _alive(*names):
    return [o for o in gc.get_objects() if type(o).__name__ in names]


def test_after_a_block_no_object_of_the_binding_is_left(reference_block):
    """Under tenure a cycle through the binding would hold the block's whole
    witness state until a deep collection (PR 27's finding). With the
    collector OFF: a served block's binding, its BlockHost and its state are
    gone by reference count when the request has been answered, and a
    collection afterwards finds nothing of them."""
    from test_post_root import _serve_reference_block

    _block, request_json = reference_block
    set_evm_backend("native")
    states = []

    def post_root(db):
        states.append(weakref.ref(db))
        return db.state_root()

    gc.collect()
    gc.disable()
    try:
        reply = _serve_reference_block(request_json, post_root)
        assert reply["result"]["status"] == "VALID", reply
        assert _alive("EvmHost", "BlockHost") == []
        assert states[0]() is None  # the witness state died with the request
    finally:
        gc.enable()
    gc.collect()
    assert _alive("EvmHost", "BlockHost") == []


def test_a_lone_message_leaves_no_binding_behind():
    set_evm_backend("native")
    gc.collect()
    gc.disable()
    try:
        state = StateDB(_chain_of_three("CALL"))
        state.start_tx()
        evm = Evm(environment(state))
        watch = weakref.ref(state)
        assert evm.execute_message(Message(caller=SENDER, target=A, value=0, data=b"", gas=400_000)).success
        assert evm.host is None and _alive("EvmHost", "BlockHost") == []
        del state, evm
        assert watch() is None
    finally:
        gc.enable()


# --- the counters that say it engaged ----------------------------------------


def _evm_counters():
    counters = metrics.snapshot()["counters"]
    return {k: v for k, v in counters.items() if k.startswith("evm.")}


@pytest.mark.parametrize("backend", ["native", "python"])
def test_a_served_block_counts_its_frames_and_one_binding(reference_block, backend):
    """`evm.native_frames{binding="ext"}` grows by the block's frames (its 20
    calls reach code once each) and `evm.host_bindings` by ONE under the
    native backend; under the interpreter neither moves; no other series of
    the family exists."""
    from test_post_root import _serve_reference_block

    _block, request_json = reference_block
    set_evm_backend(backend)
    before = _evm_counters()
    reply = _serve_reference_block(request_json, lambda db: db.state_root())
    assert reply["result"]["status"] == "VALID", reply
    after = _evm_counters()
    grown = {k: after[k] - before.get(k, 0) for k in after if after[k] != before.get(k, 0)}
    if backend == "native":
        assert grown == {'evm.native_frames{binding="ext"}': 20, "evm.host_bindings": 1}
    else:
        assert grown == {}
    assert set(after) <= {'evm.native_frames{binding="ext"}', "evm.host_bindings"}


def test_a_served_block_hashes_through_the_extension_and_never_through_ctypes(reference_block, monkeypatch):
    """The scalar `keccak256` of a served request is the extension's, the
    interpreter lock held: a patched `NativeLib.keccak256` is never entered
    and `native.keccak_calls{lock=held}` grows by at least a hash a
    transaction (the signing hashes alone are that many), `released` by
    none. With the extension masked from the hash in this test the same
    block answers the same through the `ctypes` library."""
    from phant_tpu.crypto import keccak
    from phant_tpu.utils import native
    from test_post_root import _serve_reference_block

    block, request_json = reference_block
    if native.load_ext() is None or native.load_native() is None:
        pytest.skip("no toolchain: neither binding can be built here")
    assert keccak._ext_keccak256 is native.load_ext().keccak256
    set_evm_backend("native")
    entered = []
    real = native.NativeLib.keccak256

    def counting(self, data):
        entered.append(len(data))
        return real(self, data)

    monkeypatch.setattr(native.NativeLib, "keccak256", counting)
    before = native.keccak_calls()
    reply = _serve_reference_block(request_json, lambda db: db.state_root())
    after = native.keccak_calls()
    assert reply["result"]["status"] == "VALID", reply
    assert reply["result"]["stateRoot"] == request_json["params"][0]["stateRoot"]
    assert entered == []
    assert after["held"] - before["held"] >= len(request_json["params"][0]["transactions"]) == 60
    assert after["released"] == before["released"]

    monkeypatch.setattr(keccak, "_ext_keccak256", None)
    masked = _serve_reference_block(request_json, lambda db: db.state_root())
    assert masked == reply
    assert len(entered) >= 60 and native.keccak_calls() == after


def test_the_counters_are_on_the_metrics_page():
    set_evm_backend("native")
    both(_chain_of_three("CALL"))
    text = metrics.prometheus_text()
    assert 'phant_evm_native_frames_total{binding="ext"}' in text
    assert "phant_evm_host_bindings_total" in text and 'binding="ctypes"' not in text
