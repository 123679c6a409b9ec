"""ctypes bridge to the native C++ EVM core (native/evm.cc).

Architecture mirror of the reference: evmone (C++) executes bytecode while
the client provides a host vtable over its StateDB (reference:
src/blockchain/vm.zig:40-55 installs 14 host callbacks; nested calls
re-enter the interpreter through the host, vm.zig:382-522). Here the host
side is this module: every callback closes over the Python `Evm`/`StateDB`,
and nested CALL*/CREATE* ops route back through `Evm._nested_call` /
`_nested_create`, which re-enter the C++ core for child frames.

Enabled via `--evm_backend=native` (phant_tpu.backend); falls back to the
pure-Python interpreter when the toolchain is unavailable.
"""

from __future__ import annotations

import ctypes as ct
import threading
from typing import Optional

from phant_tpu.evm import gas as G
from phant_tpu.evm.interpreter import _visible_code, delegation_access_cost
from phant_tpu.evm.message import ExecResult, Message
from phant_tpu.types.receipt import Log

_ADDR = ct.c_uint8 * 20
_B32 = ct.c_uint8 * 32

KIND_CALL, KIND_CALLCODE, KIND_DELEGATECALL, KIND_STATICCALL = 0, 1, 2, 3
KIND_CREATE, KIND_CREATE2 = 4, 5


class PhantTxContext(ct.Structure):
    _fields_ = [
        ("origin", _ADDR),
        ("coinbase", _ADDR),
        ("block_number", ct.c_uint64),
        ("timestamp", ct.c_uint64),
        ("gas_limit", ct.c_uint64),
        ("chain_id", ct.c_uint64),
        ("gas_price", _B32),
        ("prev_randao", _B32),
        ("base_fee", _B32),
        # Cancun extensions (must mirror native/evm.cc PhantTxContext)
        ("revision", ct.c_uint64),
        ("blob_base_fee", _B32),
        ("blob_hashes", ct.POINTER(ct.c_uint8)),
        ("n_blob_hashes", ct.c_uint64),
    ]


class PhantMsg(ct.Structure):
    _fields_ = [
        ("kind", ct.c_int32),
        ("is_static", ct.c_int32),
        ("depth", ct.c_int32),
        ("gas", ct.c_int64),
        ("caller", _ADDR),
        ("target", _ADDR),
        ("code_address", _ADDR),
        ("value", _B32),
        ("data", ct.POINTER(ct.c_uint8)),
        ("data_len", ct.c_uint64),
        ("salt", _B32),
    ]


class PhantResult(ct.Structure):
    _fields_ = [
        ("status", ct.c_int32),
        ("gas_left", ct.c_int64),
        ("output", ct.POINTER(ct.c_uint8)),
        ("output_len", ct.c_uint64),
        ("create_address", _ADDR),
    ]


_CB = {
    "access_account": ct.CFUNCTYPE(ct.c_int32, ct.c_void_p, ct.POINTER(ct.c_uint8)),
    "access_storage": ct.CFUNCTYPE(
        ct.c_int32, ct.c_void_p, ct.POINTER(ct.c_uint8), ct.POINTER(ct.c_uint8)
    ),
    "get_storage": ct.CFUNCTYPE(
        None, ct.c_void_p, ct.POINTER(ct.c_uint8), ct.POINTER(ct.c_uint8),
        ct.POINTER(ct.c_uint8),
    ),
    "get_original_storage": ct.CFUNCTYPE(
        None, ct.c_void_p, ct.POINTER(ct.c_uint8), ct.POINTER(ct.c_uint8),
        ct.POINTER(ct.c_uint8),
    ),
    "set_storage": ct.CFUNCTYPE(
        None, ct.c_void_p, ct.POINTER(ct.c_uint8), ct.POINTER(ct.c_uint8),
        ct.POINTER(ct.c_uint8),
    ),
    "get_balance": ct.CFUNCTYPE(
        None, ct.c_void_p, ct.POINTER(ct.c_uint8), ct.POINTER(ct.c_uint8)
    ),
    "get_code_size": ct.CFUNCTYPE(ct.c_uint64, ct.c_void_p, ct.POINTER(ct.c_uint8)),
    "copy_code": ct.CFUNCTYPE(
        None, ct.c_void_p, ct.POINTER(ct.c_uint8), ct.c_uint64,
        ct.POINTER(ct.c_uint8), ct.c_uint64,
    ),
    "get_code_hash": ct.CFUNCTYPE(
        None, ct.c_void_p, ct.POINTER(ct.c_uint8), ct.POINTER(ct.c_uint8)
    ),
    "is_empty": ct.CFUNCTYPE(ct.c_int32, ct.c_void_p, ct.POINTER(ct.c_uint8)),
    "get_block_hash": ct.CFUNCTYPE(
        None, ct.c_void_p, ct.c_uint64, ct.POINTER(ct.c_uint8)
    ),
    "emit_log": ct.CFUNCTYPE(
        None, ct.c_void_p, ct.POINTER(ct.c_uint8), ct.POINTER(ct.c_uint8),
        ct.c_uint64, ct.POINTER(ct.c_uint8), ct.c_int32,
    ),
    "add_refund": ct.CFUNCTYPE(None, ct.c_void_p, ct.c_int64),
    "selfdestruct": ct.CFUNCTYPE(
        None, ct.c_void_p, ct.POINTER(ct.c_uint8), ct.POINTER(ct.c_uint8)
    ),
    "call": ct.CFUNCTYPE(
        None, ct.c_void_p, ct.POINTER(PhantMsg), ct.POINTER(PhantResult)
    ),
    # EIP-1153 transient storage (Cancun); appended after `call` to keep
    # the vtable layout a strict prefix of the pre-Cancun one
    "get_transient": ct.CFUNCTYPE(
        None, ct.c_void_p, ct.POINTER(ct.c_uint8), ct.POINTER(ct.c_uint8),
        ct.POINTER(ct.c_uint8),
    ),
    "set_transient": ct.CFUNCTYPE(
        None, ct.c_void_p, ct.POINTER(ct.c_uint8), ct.POINTER(ct.c_uint8),
        ct.POINTER(ct.c_uint8),
    ),
    # optional per-instruction tracer (installed only when Evm.tracer is
    # set; NULL otherwise so the C loop pays one predictable branch)
    "trace": ct.CFUNCTYPE(
        None, ct.c_void_p, ct.c_uint64, ct.c_int32, ct.c_int64, ct.c_int32,
        ct.c_int32,
    ),
    # EIP-7702 (Prague): extra CALL-family charge for delegated code
    # targets; appended LAST to keep older vtable layouts a strict prefix
    "delegate_access_cost": ct.CFUNCTYPE(
        ct.c_int64, ct.c_void_p, ct.POINTER(ct.c_uint8)
    ),
}


class PhantHost(ct.Structure):
    _fields_ = [("ctx", ct.c_void_p)] + [(name, fn) for name, fn in _CB.items()]


def _bytes20(p) -> bytes:
    return ct.string_at(p, 20)


def _bytes32_int(p) -> int:
    return int.from_bytes(ct.string_at(p, 32), "big")


def _write32(dst, value: int) -> None:
    ct.memmove(dst, value.to_bytes(32, "big"), 32)


_lib = None
_lib_failed = False
_load_lock = threading.Lock()


def _load():
    global _lib, _lib_failed
    if _lib is not None or _lib_failed:
        return _lib
    # lock-serialized (phantlint LOCK): two request threads racing the
    # argtypes/restype setup would mutate shared ctypes function objects
    # mid-call. Acquisition order is _load_lock -> native._lock (inside
    # load_native); nothing takes them in reverse.
    with _load_lock:
        return _load_locked()


def _load_locked():
    global _lib, _lib_failed
    if _lib is not None or _lib_failed:
        return _lib
    from phant_tpu.utils.native import load_native

    native = load_native()
    if native is None:
        _lib_failed = True
        return None
    lib = native._lib
    lib.phant_evm_execute.argtypes = [
        ct.POINTER(PhantHost), ct.POINTER(PhantTxContext), ct.POINTER(PhantMsg),
        ct.POINTER(ct.c_uint8), ct.c_uint64, ct.POINTER(PhantResult),
    ]
    lib.phant_evm_execute.restype = ct.c_int32
    lib.phant_evm_free.argtypes = [ct.POINTER(ct.c_uint8)]
    lib.phant_evm_free.restype = None
    _lib = lib
    return _lib


class NativeSession:
    """Host vtable bound to one Evm instance (one per Environment)."""

    def __init__(self, evm):
        self.evm = evm
        self.state = evm.state
        env = evm.env
        self.txc = PhantTxContext()
        ct.memmove(self.txc.origin, env.origin, 20)
        ct.memmove(self.txc.coinbase, env.coinbase, 20)
        self.txc.block_number = env.block_number
        self.txc.timestamp = env.timestamp
        self.txc.gas_limit = env.gas_limit
        self.txc.chain_id = env.chain_id
        ct.memmove(self.txc.gas_price, env.gas_price.to_bytes(32, "big"), 32)
        ct.memmove(self.txc.prev_randao, env.prev_randao, 32)
        ct.memmove(self.txc.base_fee, env.base_fee.to_bytes(32, "big"), 32)
        self.txc.revision = env.revision
        ct.memmove(
            self.txc.blob_base_fee, env.blob_base_fee.to_bytes(32, "big"), 32
        )
        if env.blob_hashes:
            raw = b"".join(env.blob_hashes)
            self._blob_buf = ct.create_string_buffer(raw, len(raw))
            self.txc.blob_hashes = ct.cast(
                self._blob_buf, ct.POINTER(ct.c_uint8)
            )
            self.txc.n_blob_hashes = len(env.blob_hashes)
        else:
            self.txc.blob_hashes = None
            self.txc.n_blob_hashes = 0

        # single-slot holder for the child-output buffer crossing the C
        # boundary: the C++ caller copies it immediately after host->call
        # returns, so only the most recent buffer must stay alive
        self._last_output = None
        self._pending_exc: Optional[BaseException] = None
        self._cbs = {}  # prevent GC of CFUNCTYPE trampolines
        self.host = PhantHost()
        self.host.ctx = None
        # int-returning callbacks need an explicit safe default; void ones
        # return None regardless
        int_cbs = {
            "access_account",
            "access_storage",
            "get_code_size",
            "is_empty",
            "delegate_access_cost",
        }
        for name in _CB:
            if name == "trace" and getattr(evm, "tracer", None) is None:
                # leave the vtable slot NULL: the C loop skips tracing
                setattr(self.host, name, _CB[name]())
                continue
            raw = getattr(self, "_cb_" + name)
            guarded = self._guard(raw, 0 if name in int_cbs else None)
            cb = _CB[name](guarded)
            self._cbs[name] = cb
            setattr(self.host, name, cb)

    def close(self) -> None:
        """Break the cycles this session closes (Evm <-> session, session ->
        trampoline -> bound callback -> session) once its transaction is
        over: nothing calls through the vtable any more."""
        self._cbs = self.host = self.evm = self.state = None

    def _guard(self, fn, default):
        """No exception may unwind through the C frame: ctypes would swallow
        it and C++ would keep running on garbage. Stash the first error and
        re-raise it from execute() once the C++ stack has unwound."""

        def wrapped(*args):
            try:
                return fn(*args)
            except BaseException as e:
                if self._pending_exc is None:
                    self._pending_exc = e
                return default

        return wrapped

    # --- state callbacks (the reference's EVMOneHost equivalents) ---------

    def _cb_access_account(self, _ctx, addr) -> int:
        return 1 if self.state.access_address(_bytes20(addr)) else 0

    def _cb_access_storage(self, _ctx, addr, key) -> int:
        return 1 if self.state.access_storage_key(_bytes20(addr), _bytes32_int(key)) else 0

    def _cb_get_storage(self, _ctx, addr, key, out) -> None:
        _write32(out, self.state.get_storage(_bytes20(addr), _bytes32_int(key)))

    def _cb_get_original_storage(self, _ctx, addr, key, out) -> None:
        _write32(out, self.state.get_original_storage(_bytes20(addr), _bytes32_int(key)))

    def _cb_set_storage(self, _ctx, addr, key, val) -> None:
        self.state.set_storage(_bytes20(addr), _bytes32_int(key), _bytes32_int(val))

    def _cb_get_balance(self, _ctx, addr, out) -> None:
        _write32(out, self.state.get_balance(_bytes20(addr)))

    def _cb_get_code_size(self, _ctx, addr) -> int:
        return len(_visible_code(self.evm, _bytes20(addr)))

    def _cb_copy_code(self, _ctx, addr, offset, out, size) -> None:
        code = _visible_code(self.evm, _bytes20(addr))
        chunk = code[offset : offset + size]
        if chunk:
            ct.memmove(out, chunk, len(chunk))

    def _cb_get_code_hash(self, _ctx, addr, out) -> None:
        address = _bytes20(addr)
        acct = self.state.get_account(address)
        if acct is None:
            ct.memmove(out, b"\x00" * 32, 32)
            return
        code = _visible_code(self.evm, address)
        if code == G.DELEGATION_MARKER:  # delegated: hash of the marker
            ct.memmove(out, G.DELEGATION_MARKER_HASH, 32)
        else:
            ct.memmove(out, acct.code_hash(), 32)

    def _cb_delegate_access_cost(self, _ctx, addr) -> int:
        return delegation_access_cost(self.evm, _bytes20(addr))

    def _cb_is_empty(self, _ctx, addr) -> int:
        return 1 if self.state.is_empty(_bytes20(addr)) else 0

    def _cb_get_block_hash(self, _ctx, number, out) -> None:
        ct.memmove(out, self.evm.env.get_block_hash(number), 32)

    def _cb_emit_log(self, _ctx, addr, data, data_len, topics, ntopics) -> None:
        payload = ct.string_at(data, data_len) if data_len else b""
        tops = tuple(
            ct.string_at(ct.addressof(topics.contents) + 32 * i, 32)
            for i in range(ntopics)
        )
        self.state.add_log(Log(address=_bytes20(addr), topics=tops, data=payload))

    def _cb_add_refund(self, _ctx, delta) -> None:
        self.state.add_refund(delta)

    def _cb_get_transient(self, _ctx, addr, key, out) -> None:
        _write32(out, self.state.get_transient(_bytes20(addr), _bytes32_int(key)))

    def _cb_set_transient(self, _ctx, addr, key, val) -> None:
        self.state.set_transient(
            _bytes20(addr), _bytes32_int(key), _bytes32_int(val)
        )

    def _cb_trace(self, _ctx, pc, op, gas, depth, stack_size) -> None:
        self.evm.tracer(pc, op, gas, depth, stack_size)

    def _cb_selfdestruct(self, _ctx, addr, beneficiary) -> None:
        # state effects of SELFDESTRUCT (interpreter.py _selfdestruct)
        a, b = _bytes20(addr), _bytes20(beneficiary)
        balance = self.state.get_balance(a)
        self.state.add_balance(b, balance)
        self.state.set_balance(a, 0)
        self.state.touch(b)
        self.state.mark_selfdestruct(a)

    # --- nested call/create: re-enters Evm, which re-enters C++ -----------

    def _cb_call(self, _ctx, msg_p, res_p) -> None:
        from phant_tpu.evm.interpreter import create2_address, create_address

        m = msg_p.contents
        res = res_p.contents
        if self._pending_exc is not None:
            # a host callback already failed: abort fast, don't run children
            res.status = 2
            res.gas_left = 0
            res.output = None
            res.output_len = 0
            return
        data = ct.string_at(m.data, m.data_len) if m.data_len else b""
        kind = m.kind
        caller = bytes(m.caller)
        try:
            if kind in (KIND_CREATE, KIND_CREATE2):
                msg = Message(
                    caller=caller, target=None,
                    value=_bytes32_int(m.value), data=data, gas=m.gas,
                    is_static=False, depth=m.depth,
                )
                if kind == KIND_CREATE2:
                    addr = create2_address(caller, bytes(m.salt), data)
                else:
                    addr = create_address(caller, self.state.get_nonce(caller))
                result = self.evm._nested_create(msg, addr)
            else:
                msg = Message(
                    caller=caller,
                    target=bytes(m.target),
                    value=_bytes32_int(m.value),
                    data=data,
                    gas=m.gas,
                    is_static=bool(m.is_static),
                    depth=m.depth,
                    code_address=(
                        bytes(m.code_address)
                        if kind in (KIND_CALLCODE, KIND_DELEGATECALL)
                        else None
                    ),
                    transfers_value=kind != KIND_DELEGATECALL,
                )
                result = self.evm._nested_call(msg)
        except BaseException as e:  # must never unwind through the C frame
            # stash and re-raise from NativeSession.execute once the C++
            # stack has unwound — a host-side bug must not be mistaken for
            # an in-EVM call failure (the first/innermost error wins)
            if self._pending_exc is None:
                self._pending_exc = e
            res.status = 2
            res.gas_left = 0
            res.output = None
            res.output_len = 0
            return

        res.status = 0 if result.success else (1 if result.is_revert else 2)
        res.gas_left = result.gas_left
        if result.output:
            buf = ct.create_string_buffer(result.output, len(result.output))
            self._last_output = buf
            res.output = ct.cast(buf, ct.POINTER(ct.c_uint8))
            res.output_len = len(result.output)
        else:
            res.output = None
            res.output_len = 0
        if result.create_address:
            ct.memmove(res.create_address, result.create_address, 20)

    # --- frame execution ---------------------------------------------------

    def execute(self, code: bytes, msg: Message, address: bytes) -> ExecResult:
        lib = _load()
        assert lib is not None
        cmsg = PhantMsg()
        cmsg.kind = KIND_CALL
        cmsg.is_static = 1 if msg.is_static else 0
        cmsg.depth = msg.depth
        cmsg.gas = msg.gas
        ct.memmove(cmsg.caller, msg.caller, 20)
        ct.memmove(cmsg.target, address, 20)
        ct.memmove(cmsg.value, msg.value.to_bytes(32, "big"), 32)
        if msg.data:
            data_buf = ct.create_string_buffer(msg.data, len(msg.data))
            cmsg.data = ct.cast(data_buf, ct.POINTER(ct.c_uint8))
        else:
            cmsg.data = None
        cmsg.data_len = len(msg.data)

        res = PhantResult()
        lib.phant_evm_execute(
            ct.byref(self.host), ct.byref(self.txc), ct.byref(cmsg),
            ct.cast(code, ct.POINTER(ct.c_uint8)) if code else None,
            len(code), ct.byref(res),
        )
        output = ct.string_at(res.output, res.output_len) if res.output_len else b""
        if res.output:
            lib.phant_evm_free(res.output)
        if self._pending_exc is not None:
            exc = self._pending_exc
            self._pending_exc = None
            raise exc
        if res.status == 0:
            return ExecResult(True, res.gas_left, output)
        if res.status == 1:
            return ExecResult(False, res.gas_left, output, error="revert")
        return ExecResult(False, 0, error="native evm failure")


def native_available() -> bool:
    return _load() is not None


def execute_native(evm, code: bytes, msg: Message, address: bytes) -> Optional[ExecResult]:
    """Run one frame natively; None if the native lib is unavailable."""
    if _load() is None:
        return None
    session = getattr(evm, "_native_session", None)
    if session is None:
        session = NativeSession(evm)
        evm._native_session = session
    return session.execute(code, msg, address)
