"""EVM bytecode interpreter, Shanghai revision.

The reference embeds evmone (C++) behind the EVMC ABI and implements the
host side over its StateDB (reference: src/blockchain/vm.zig:33-558). This
framework owns a from-scratch interpreter with the same observable
semantics: full Shanghai opcode set, EIP-2929 warm/cold accounting,
EIP-2200/3529 SSTORE lattice (reference: vm.zig:180-264 implements the same
lattice through EVMC storage-status codes), EIP-150 63/64 forwarding,
CREATE/CREATE2 with EIP-3860/3541/170 rules, and static-call protection.

Layout: `Evm.execute_message` is the reference's processMessageCall
(vm.zig:67-124); `Evm._call` is the recursive host `call` (vm.zig:382-522)
using journal snapshots instead of the reference's full deep clone.
"""

from __future__ import annotations

from dataclasses import dataclass, field
from typing import Dict, List, Optional, Set, Tuple

from phant_tpu.crypto.keccak import keccak256
from phant_tpu.evm import gas as G
from phant_tpu.evm.message import (
    Environment,
    EVMError,
    ExecResult,
    Message,
    REVISION_CANCUN,
    REVISION_PRAGUE,
)
from phant_tpu.evm.precompiles import active_precompiles
from phant_tpu.types.receipt import Log
from phant_tpu import rlp

U256 = (1 << 256) - 1
SIGN_BIT = 1 << 255

# Nested EVM calls cost ~6 Python frames per EVM depth; MAX_CALL_DEPTH=1024
# needs ~6200 frames. Raise the interpreter limit once, with headroom.
import sys

if sys.getrecursionlimit() < 20_000:
    sys.setrecursionlimit(20_000)


def create_address(sender: bytes, nonce: int) -> bytes:
    """CREATE: keccak(rlp([sender, nonce]))[12:]
    (reference: src/common/contract.zig:8-24)."""
    return keccak256(rlp.encode([sender, rlp.encode_uint(nonce)]))[12:]


def create2_address(sender: bytes, salt: bytes, init_code: bytes) -> bytes:
    """CREATE2: keccak(0xff ‖ sender ‖ salt ‖ keccak(init))[12:]
    (reference: src/common/contract.zig:26-40)."""
    return keccak256(b"\xff" + sender + salt + keccak256(init_code))[12:]


def valid_jumpdests(code: bytes) -> Set[int]:
    dests = set()
    i, n = 0, len(code)
    while i < n:
        op = code[i]
        if op == 0x5B:
            dests.add(i)
        if 0x60 <= op <= 0x7F:  # PUSH1..PUSH32
            i += op - 0x5F
        i += 1
    return dests


@dataclass
class Frame:
    msg: Message
    code: bytes
    gas: int
    address: bytes  # executing address (storage/balance context)
    stack: List[int] = field(default_factory=list)
    memory: bytearray = field(default_factory=bytearray)
    pc: int = 0
    return_data: bytes = b""
    jumpdests: Set[int] = field(default_factory=set)

    def push(self, v: int) -> None:
        if len(self.stack) >= 1024:
            raise EVMError("stack overflow")
        self.stack.append(v)

    def pop(self) -> int:
        if not self.stack:
            raise EVMError("stack underflow")
        return self.stack.pop()

    def use_gas(self, amount: int) -> None:
        if self.gas < amount:
            raise EVMError("out of gas")
        self.gas -= amount

    def expand_memory(self, offset: int, size: int) -> None:
        """Charge and grow memory to cover [offset, offset+size)."""
        if size == 0:
            return
        if offset > 2**32 or size > 2**32:
            raise EVMError("out of gas")  # absurd offsets: cost overflows
        new_size = offset + size
        cur = len(self.memory)
        if new_size <= cur:
            return
        new_words = (new_size + 31) // 32
        self.use_gas(G.memory_cost(new_words * 32) - G.memory_cost(cur))
        self.memory.extend(b"\x00" * (new_words * 32 - cur))

    def mread(self, offset: int, size: int) -> bytes:
        if size == 0:
            return b""
        return bytes(self.memory[offset : offset + size])

    def mwrite(self, offset: int, data: bytes) -> None:
        if data:
            self.memory[offset : offset + len(data)] = data


def _to_signed(x: int) -> int:
    return x - (1 << 256) if x & SIGN_BIT else x


def _to_unsigned(x: int) -> int:
    return x & U256


def _addr_to_int(addr: bytes) -> int:
    return int.from_bytes(addr, "big")


def _int_to_addr(v: int) -> bytes:
    return (v & ((1 << 160) - 1)).to_bytes(20, "big")


class Evm:
    """One EVM instance bound to an Environment (reference: vm.zig:33-65)."""

    def __init__(self, env: Environment, host=None):
        self.env = env
        self.state = env.state
        # the native VM's host binding (evm/native_vm.BlockHost): the one a
        # block's transactions share, handed in by Blockchain.run_block;
        # None = this Evm makes its own at its first native frame
        self.host = host
        self._own_host = None
        # optional per-instruction tracer: fn(pc, op, gas, depth, stack_size).
        # Same hook shape on both backends (native/evm.cc PhantHost.trace),
        # so a fixture divergence is localized by diffing the two traces.
        self.tracer = None

    # ------------------------------------------------------------------
    # top level (reference: VM.processMessageCall vm.zig:67-124)
    # ------------------------------------------------------------------

    def execute_message(self, msg: Message) -> ExecResult:
        try:
            if msg.target is None:
                nonce = self.state.get_nonce(msg.caller)
                # top-level create: sender nonce was already bumped by tx
                # processing, so the address derives from nonce-1
                addr = create_address(msg.caller, nonce - 1)
                return self._create(msg, addr)
            return self._call_inner(msg)
        finally:
            # a binding this Evm made for itself ends with its message (one
            # a block shares ends with the block): the state it holds dies
            # by reference count and waits for no collector
            own, self._own_host = self._own_host, None
            if own is not None:
                self.host = None
                own.close()

    # ------------------------------------------------------------------
    # call path (reference: EVMOneHost.call vm.zig:382-522)
    # ------------------------------------------------------------------

    def _call_inner(self, msg: Message) -> ExecResult:
        state = self.state
        snapshot = state.snapshot()  # journal mark (reference deep-clones)
        target = msg.target
        assert target is not None
        code_addr = msg.code_address if msg.code_address is not None else target

        state.touch(target)
        # value transfer (reference: vm.zig:444-466); DELEGATECALL carries the
        # parent's value for CALLVALUE but moves no funds
        if msg.value and msg.transfers_value:
            if state.get_balance(msg.caller) < msg.value:
                return ExecResult(False, msg.gas, error="insufficient balance")
            state.sub_balance(msg.caller, msg.value)
            state.add_balance(target, msg.value)

        precompiles = active_precompiles(self.env.revision)
        if code_addr in precompiles:
            result = precompiles[code_addr](msg.data, msg.gas)
            if not result.success:
                state.revert_to(snapshot)
            return result

        code = state.get_code(code_addr)
        # EIP-7702 delegation: 0xef0100‖address executes the delegate's
        # code in the account's own context. Resolved ONE level (a chain
        # of designators executes the raw designator bytes, which halt on
        # 0xEF). The gas for the delegate's access is the CALLER's cost
        # (delegation_access_cost in the CALL family / free warm-add at
        # the tx top level) — resolution here is charge-free. This is the
        # single code-fetch point for both backends (the native core's
        # nested calls re-enter here via the host `call` callback), so
        # delegation behaves identically everywhere. INVARIANT: every
        # entry path into execute_message must have already charged AND
        # warmed the delegate (chain.py tx top level; CALL family via
        # delegation_access_cost) — a new entry path that skips that gets
        # a silent free warm-add here.
        if self.env.revision >= REVISION_PRAGUE and G.is_delegation_designator(
            code
        ):
            delegate = G.delegation_target(code)
            state.access_address(delegate)  # idempotent (already warmed)
            delegated = state.get_code(delegate)
            if not G.is_delegation_designator(delegated):
                code = delegated
        if not code:
            return ExecResult(True, msg.gas)

        result = self._execute_code(code, msg, target)
        if not result.success:
            state.revert_to(snapshot)
        return result

    # ------------------------------------------------------------------
    # create path (reference: vm.zig:478-516 + contract deposit rules)
    # ------------------------------------------------------------------

    def _create(self, msg: Message, addr: bytes) -> ExecResult:
        state = self.state
        if state.get_balance(msg.caller) < msg.value:
            return ExecResult(False, msg.gas, error="insufficient balance")

        # address collision (existing code or nonce) burns the gas
        existing = state.get_account(addr)
        if existing is not None and (existing.code or existing.nonce):
            return ExecResult(False, 0, error="create collision")

        snapshot = state.snapshot()
        state.access_address(addr)
        acct = state.create_account(addr)
        state.mark_created(addr)
        state.set_nonce(addr, 1)  # EIP-161
        state.touch(addr)
        if msg.value:
            state.sub_balance(msg.caller, msg.value)
            state.add_balance(addr, msg.value)

        # init code runs with empty calldata
        init_msg = Message(
            caller=msg.caller, target=addr, value=msg.value, data=b"",
            gas=msg.gas, is_static=msg.is_static, depth=msg.depth,
        )
        result = self._execute_code(msg.data, init_msg, addr)
        if not result.success:
            state.revert_to(snapshot)
            result.create_address = None
            return result

        deposit_code = result.output
        # EIP-3541: new code must not start with 0xEF (reference: vm.zig:496-500)
        if deposit_code[:1] == b"\xef":
            state.revert_to(snapshot)
            return ExecResult(False, 0, error="EF code prefix")
        # EIP-170 max code size (reference: vm.zig:501-505)
        if len(deposit_code) > G.MAX_CODE_SIZE:
            state.revert_to(snapshot)
            return ExecResult(False, 0, error="code too large")
        deposit_gas = len(deposit_code) * G.CODE_DEPOSIT_PER_BYTE
        if result.gas_left < deposit_gas:
            state.revert_to(snapshot)
            return ExecResult(False, 0, error="out of gas (deposit)")
        result.gas_left -= deposit_gas
        state.set_code(addr, deposit_code)
        return ExecResult(True, result.gas_left, b"", create_address=addr)

    # ------------------------------------------------------------------
    # interpreter loop
    # ------------------------------------------------------------------

    def _execute_code(self, code: bytes, msg: Message, address: bytes) -> ExecResult:
        """Run one frame of bytecode on the selected EVM backend: the C++
        core (native/evm.cc, mirroring the reference's evmone-behind-EVMC
        split) or this module's Python interpreter."""
        from phant_tpu.backend import evm_backend

        if evm_backend() == "native":
            host = self.host
            if host is None:
                from phant_tpu.evm.native_vm import BlockHost

                host = self.host = self._own_host = BlockHost()
            result = host.execute(self, code, msg, address)
            if result is not None:
                return result  # None: toolchain unavailable, fall through
        frame = Frame(
            msg=msg, code=code, gas=msg.gas, address=address,
            jumpdests=valid_jumpdests(code),
        )
        return self._run(frame)

    def _run(self, frame: Frame) -> ExecResult:
        try:
            return self._run_unsafe(frame)
        except RecursionError:
            # ~6 Python frames per EVM depth; the limit below makes legal
            # depth-1024 chains fit, so reaching here is exceptional
            return ExecResult(False, 0, error="python recursion limit")
        except EVMError as e:
            if e.reason == "revert-op":
                return ExecResult(False, frame.gas, frame.return_data, error="revert")
            return ExecResult(False, 0, error=e.reason)

    def _run_unsafe(self, frame: Frame) -> ExecResult:
        stack = frame.stack
        state = self.state
        env = self.env
        code = frame.code
        n = len(code)
        tracer = self.tracer
        while frame.pc < n:
            op = code[frame.pc]
            if tracer is not None:
                tracer(frame.pc, op, frame.gas, frame.msg.depth, len(stack))
            frame.pc += 1
            # ---- push family (most common) ----
            if 0x60 <= op <= 0x7F:
                width = op - 0x5F
                frame.use_gas(3)
                imm = code[frame.pc : frame.pc + width]
                if len(imm) < width:  # code is zero-extended past its end
                    imm = imm.ljust(width, b"\x00")
                frame.push(int.from_bytes(imm, "big"))
                frame.pc += width
                continue
            if 0x80 <= op <= 0x8F:  # DUP1..16
                frame.use_gas(3)
                i = op - 0x7F
                if len(stack) < i:
                    raise EVMError("stack underflow")
                frame.push(stack[-i])
                continue
            if 0x90 <= op <= 0x9F:  # SWAP1..16
                frame.use_gas(3)
                i = op - 0x8F
                if len(stack) < i + 1:
                    raise EVMError("stack underflow")
                stack[-1], stack[-i - 1] = stack[-i - 1], stack[-1]
                continue

            handler = _DISPATCH.get(op)
            if handler is None:
                raise EVMError(f"invalid opcode 0x{op:02x}")
            result = handler(self, frame)
            if result is not None:
                return result
        return ExecResult(True, frame.gas)

    # ------------------------------------------------------------------
    # nested call/create from opcodes
    # ------------------------------------------------------------------

    def _nested_call(self, msg: Message) -> ExecResult:
        if msg.depth > G.MAX_CALL_DEPTH:
            return ExecResult(False, msg.gas, error="call depth exceeded")
        return self._call_inner(msg)

    def _nested_create(self, msg: Message, addr: bytes) -> ExecResult:
        if msg.depth > G.MAX_CALL_DEPTH:
            return ExecResult(False, msg.gas, error="call depth exceeded")
        nonce = self.state.get_nonce(msg.caller)
        if nonce >= 2**64 - 1:
            return ExecResult(False, msg.gas, error="nonce overflow")
        self.state.increment_nonce(msg.caller)
        return self._create(msg, addr)


# ===========================================================================
# opcode handlers — each returns None to continue or an ExecResult to halt
# ===========================================================================

_DISPATCH: Dict[int, object] = {}


def op(code: int, base_gas: int = 0):
    def deco(fn):
        if base_gas:
            def wrapped(evm, frame, _fn=fn, _g=base_gas):
                frame.use_gas(_g)
                return _fn(evm, frame)
            _DISPATCH[code] = wrapped
        else:
            _DISPATCH[code] = fn
        return fn
    return deco


# ---- 0x00s: control / arithmetic ----


@op(0x00)
def _stop(evm, frame):
    return ExecResult(True, frame.gas)


@op(0x01, 3)
def _add(evm, frame):
    frame.push((frame.pop() + frame.pop()) & U256)


@op(0x02, 5)
def _mul(evm, frame):
    frame.push((frame.pop() * frame.pop()) & U256)


@op(0x03, 3)
def _sub(evm, frame):
    a, b = frame.pop(), frame.pop()
    frame.push((a - b) & U256)


@op(0x04, 5)
def _div(evm, frame):
    a, b = frame.pop(), frame.pop()
    frame.push(a // b if b else 0)


@op(0x05, 5)
def _sdiv(evm, frame):
    a, b = _to_signed(frame.pop()), _to_signed(frame.pop())
    if b == 0:
        frame.push(0)
    else:
        q = abs(a) // abs(b)
        frame.push(_to_unsigned(-q if (a < 0) != (b < 0) else q))


@op(0x06, 5)
def _mod(evm, frame):
    a, b = frame.pop(), frame.pop()
    frame.push(a % b if b else 0)


@op(0x07, 5)
def _smod(evm, frame):
    a, b = _to_signed(frame.pop()), _to_signed(frame.pop())
    if b == 0:
        frame.push(0)
    else:
        r = abs(a) % abs(b)
        frame.push(_to_unsigned(-r if a < 0 else r))


@op(0x08, 8)
def _addmod(evm, frame):
    a, b, m = frame.pop(), frame.pop(), frame.pop()
    frame.push((a + b) % m if m else 0)


@op(0x09, 8)
def _mulmod(evm, frame):
    a, b, m = frame.pop(), frame.pop(), frame.pop()
    frame.push((a * b) % m if m else 0)


@op(0x0A)
def _exp(evm, frame):
    base, exp = frame.pop(), frame.pop()
    byte_len = (exp.bit_length() + 7) // 8
    frame.use_gas(G.EXP_GAS + G.EXP_BYTE_GAS * byte_len)
    frame.push(pow(base, exp, 1 << 256))


@op(0x0B, 5)
def _signextend(evm, frame):
    k, v = frame.pop(), frame.pop()
    if k < 31:
        bit = 8 * (k + 1) - 1
        if v & (1 << bit):
            v |= U256 ^ ((1 << (bit + 1)) - 1)
        else:
            v &= (1 << (bit + 1)) - 1
    frame.push(v)


# ---- 0x10s: comparison / bitwise ----


@op(0x10, 3)
def _lt(evm, frame):
    frame.push(1 if frame.pop() < frame.pop() else 0)


@op(0x11, 3)
def _gt(evm, frame):
    frame.push(1 if frame.pop() > frame.pop() else 0)


@op(0x12, 3)
def _slt(evm, frame):
    frame.push(1 if _to_signed(frame.pop()) < _to_signed(frame.pop()) else 0)


@op(0x13, 3)
def _sgt(evm, frame):
    frame.push(1 if _to_signed(frame.pop()) > _to_signed(frame.pop()) else 0)


@op(0x14, 3)
def _eq(evm, frame):
    frame.push(1 if frame.pop() == frame.pop() else 0)


@op(0x15, 3)
def _iszero(evm, frame):
    frame.push(1 if frame.pop() == 0 else 0)


@op(0x16, 3)
def _and(evm, frame):
    frame.push(frame.pop() & frame.pop())


@op(0x17, 3)
def _or(evm, frame):
    frame.push(frame.pop() | frame.pop())


@op(0x18, 3)
def _xor(evm, frame):
    frame.push(frame.pop() ^ frame.pop())


@op(0x19, 3)
def _not(evm, frame):
    frame.push(frame.pop() ^ U256)


@op(0x1A, 3)
def _byte(evm, frame):
    i, v = frame.pop(), frame.pop()
    frame.push((v >> (8 * (31 - i))) & 0xFF if i < 32 else 0)


@op(0x1B, 3)
def _shl(evm, frame):
    shift, v = frame.pop(), frame.pop()
    frame.push((v << shift) & U256 if shift < 256 else 0)


@op(0x1C, 3)
def _shr(evm, frame):
    shift, v = frame.pop(), frame.pop()
    frame.push(v >> shift if shift < 256 else 0)


@op(0x1D, 3)
def _sar(evm, frame):
    shift, v = frame.pop(), _to_signed(frame.pop())
    if shift >= 256:
        frame.push(U256 if v < 0 else 0)
    else:
        frame.push(_to_unsigned(v >> shift))


# ---- 0x20: keccak ----


@op(0x20)
def _keccak256(evm, frame):
    offset, size = frame.pop(), frame.pop()
    frame.use_gas(G.KECCAK256_GAS + G.KECCAK256_WORD_GAS * ((size + 31) // 32))
    frame.expand_memory(offset, size)
    frame.push(int.from_bytes(keccak256(frame.mread(offset, size)), "big"))


# ---- 0x30s: environment ----


@op(0x30, 2)
def _address(evm, frame):
    frame.push(_addr_to_int(frame.address))


@op(0x31)
def _balance(evm, frame):
    addr = _int_to_addr(frame.pop())
    warm = evm.state.access_address(addr)
    frame.use_gas(G.WARM_ACCOUNT_ACCESS if warm else G.COLD_ACCOUNT_ACCESS)
    frame.push(evm.state.get_balance(addr))


@op(0x32, 2)
def _origin(evm, frame):
    frame.push(_addr_to_int(evm.env.origin))


@op(0x33, 2)
def _caller(evm, frame):
    frame.push(_addr_to_int(frame.msg.caller))


@op(0x34, 2)
def _callvalue(evm, frame):
    frame.push(frame.msg.value)


@op(0x35, 3)
def _calldataload(evm, frame):
    i = frame.pop()
    data = frame.msg.data
    frame.push(int.from_bytes(data[i : i + 32].ljust(32, b"\x00"), "big") if i < len(data) else 0)


@op(0x36, 2)
def _calldatasize(evm, frame):
    frame.push(len(frame.msg.data))


@op(0x37)
def _calldatacopy(evm, frame):
    dest, src, size = frame.pop(), frame.pop(), frame.pop()
    frame.use_gas(3 + G.copy_cost(size))
    frame.expand_memory(dest, size)
    data = frame.msg.data[src : src + size] if src < len(frame.msg.data) else b""
    frame.mwrite(dest, data.ljust(size, b"\x00"))


@op(0x38, 2)
def _codesize(evm, frame):
    frame.push(len(frame.code))


@op(0x39)
def _codecopy(evm, frame):
    dest, src, size = frame.pop(), frame.pop(), frame.pop()
    frame.use_gas(3 + G.copy_cost(size))
    frame.expand_memory(dest, size)
    data = frame.code[src : src + size] if src < len(frame.code) else b""
    frame.mwrite(dest, data.ljust(size, b"\x00"))


@op(0x3A, 2)
def _gasprice(evm, frame):
    frame.push(evm.env.gas_price)



def _visible_code(evm, addr: bytes) -> bytes:
    """Code as seen by the EXTCODE* instructions: a delegated account
    (EIP-7702 designator 0xef0100‖address) exposes only the 2-byte marker
    0xef01 — the delegate address is deliberately opaque to contracts."""
    code = evm.state.get_code(addr)
    if evm.env.revision >= REVISION_PRAGUE and G.is_delegation_designator(code):
        return G.DELEGATION_MARKER
    return code


def visible_code_hash(evm, addr: bytes):
    """EXTCODEHASH semantics shared by both backends: None for an empty
    account (the opcode pushes 0), the precomputed marker hash for a
    delegated account, the stored code hash otherwise."""
    if evm.state.is_empty(addr):
        return None
    if _visible_code(evm, addr) == G.DELEGATION_MARKER:
        return G.DELEGATION_MARKER_HASH
    return evm.state.get_account(addr).code_hash()


def selfdestruct_effects(state, address: bytes, beneficiary: bytes) -> None:
    """What SELFDESTRUCT does to the state once its gas is paid; shared by
    both backends (the native core through its host's `selfdestruct`)."""
    state.add_balance(beneficiary, state.get_balance(address))
    state.set_balance(address, 0)
    state.touch(beneficiary)
    state.mark_selfdestruct(address)


def delegation_access_cost(evm, code_addr: bytes) -> int:
    """EIP-7702 surcharge for calling through a delegated account: warms
    the delegate and returns its warm/cold access cost (0 when the target
    is not delegated or pre-Prague). Shared by both backends' CALL-family
    gas accounting — the python opcodes directly, the native core via the
    delegate_access_cost host callback."""
    if evm.env.revision < REVISION_PRAGUE:
        return 0
    code = evm.state.get_code(code_addr)
    if not G.is_delegation_designator(code):
        return 0
    warm = evm.state.access_address(G.delegation_target(code))
    return G.WARM_ACCOUNT_ACCESS if warm else G.COLD_ACCOUNT_ACCESS


@op(0x3B)
def _extcodesize(evm, frame):
    addr = _int_to_addr(frame.pop())
    warm = evm.state.access_address(addr)
    frame.use_gas(G.WARM_ACCOUNT_ACCESS if warm else G.COLD_ACCOUNT_ACCESS)
    frame.push(len(_visible_code(evm, addr)))


@op(0x3C)
def _extcodecopy(evm, frame):
    addr = _int_to_addr(frame.pop())
    dest, src, size = frame.pop(), frame.pop(), frame.pop()
    warm = evm.state.access_address(addr)
    frame.use_gas((G.WARM_ACCOUNT_ACCESS if warm else G.COLD_ACCOUNT_ACCESS) + G.copy_cost(size))
    frame.expand_memory(dest, size)
    ext = _visible_code(evm, addr)
    data = ext[src : src + size] if src < len(ext) else b""
    frame.mwrite(dest, data.ljust(size, b"\x00"))


@op(0x3D, 2)
def _returndatasize(evm, frame):
    frame.push(len(frame.return_data))


@op(0x3E)
def _returndatacopy(evm, frame):
    dest, src, size = frame.pop(), frame.pop(), frame.pop()
    frame.use_gas(3 + G.copy_cost(size))
    if src + size > len(frame.return_data):
        raise EVMError("returndata out of bounds")
    frame.expand_memory(dest, size)
    frame.mwrite(dest, frame.return_data[src : src + size])


@op(0x3F)
def _extcodehash(evm, frame):
    addr = _int_to_addr(frame.pop())
    warm = evm.state.access_address(addr)
    frame.use_gas(G.WARM_ACCOUNT_ACCESS if warm else G.COLD_ACCOUNT_ACCESS)
    h = visible_code_hash(evm, addr)
    frame.push(0 if h is None else int.from_bytes(h, "big"))


# ---- 0x40s: block ----


@op(0x40, 20)
def _blockhash(evm, frame):
    number = frame.pop()
    current = evm.env.block_number
    if number >= current or current - number > 256:
        frame.push(0)
    else:
        frame.push(int.from_bytes(evm.env.get_block_hash(number), "big"))


@op(0x41, 2)
def _coinbase(evm, frame):
    frame.push(_addr_to_int(evm.env.coinbase))


@op(0x42, 2)
def _timestamp(evm, frame):
    frame.push(evm.env.timestamp)


@op(0x43, 2)
def _number(evm, frame):
    frame.push(evm.env.block_number)


@op(0x44, 2)
def _prevrandao(evm, frame):
    frame.push(int.from_bytes(evm.env.prev_randao, "big"))


@op(0x45, 2)
def _gaslimit(evm, frame):
    frame.push(evm.env.gas_limit)


@op(0x46, 2)
def _chainid(evm, frame):
    frame.push(evm.env.chain_id)


@op(0x47, 5)
def _selfbalance(evm, frame):
    frame.push(evm.state.get_balance(frame.address))


@op(0x48, 2)
def _basefee(evm, frame):
    frame.push(evm.env.base_fee)


def _require_cancun(evm) -> None:
    """Cancun opcodes are invalid bytes under earlier revisions — fork
    dispatch the reference TODO-pins away (src/blockchain/vm.zig:472)."""
    if evm.env.revision < REVISION_CANCUN:
        raise EVMError("invalid opcode (pre-Cancun)")


@op(0x49)
def _blobhash(evm, frame):
    """EIP-4844 BLOBHASH: tx's i-th blob versioned hash, else 0."""
    _require_cancun(evm)
    frame.use_gas(G.BLOBHASH_GAS)
    i = frame.pop()
    hashes = evm.env.blob_hashes
    frame.push(int.from_bytes(hashes[i], "big") if i < len(hashes) else 0)


@op(0x4A)
def _blobbasefee(evm, frame):
    """EIP-7516 BLOBBASEFEE: the block's blob base fee."""
    _require_cancun(evm)
    frame.use_gas(G.BLOBBASEFEE_GAS)
    frame.push(evm.env.blob_base_fee)


# ---- 0x50s: stack/memory/storage/flow ----


@op(0x50, 2)
def _pop_op(evm, frame):
    frame.pop()


@op(0x51)
def _mload(evm, frame):
    offset = frame.pop()
    frame.use_gas(3)
    frame.expand_memory(offset, 32)
    frame.push(int.from_bytes(frame.mread(offset, 32), "big"))


@op(0x52)
def _mstore(evm, frame):
    offset, value = frame.pop(), frame.pop()
    frame.use_gas(3)
    frame.expand_memory(offset, 32)
    frame.mwrite(offset, value.to_bytes(32, "big"))


@op(0x53)
def _mstore8(evm, frame):
    offset, value = frame.pop(), frame.pop()
    frame.use_gas(3)
    frame.expand_memory(offset, 1)
    frame.memory[offset] = value & 0xFF


@op(0x54)
def _sload(evm, frame):
    slot = frame.pop()
    warm = evm.state.access_storage_key(frame.address, slot)
    frame.use_gas(G.WARM_SLOAD if warm else G.COLD_SLOAD)
    frame.push(evm.state.get_storage(frame.address, slot))


@op(0x55)
def _sstore(evm, frame):
    if frame.msg.is_static:
        raise EVMError("static call state change")
    # EIP-2200 sentry (reference lattice: vm.zig:192-254)
    if frame.gas <= G.SSTORE_SENTRY:
        raise EVMError("out of gas")
    slot, new = frame.pop(), frame.pop()
    state = evm.state
    addr = frame.address
    cost = 0
    if not state.access_storage_key(addr, slot):
        cost += G.COLD_SLOAD
    current = state.get_storage(addr, slot)
    original = state.get_original_storage(addr, slot)
    if current == new:
        cost += G.WARM_SLOAD
    elif current == original:
        cost += G.SSTORE_SET if original == 0 else G.SSTORE_RESET
    else:
        cost += G.WARM_SLOAD
    frame.use_gas(cost)
    # refunds (EIP-3529)
    if current != new:
        if current == original:
            if original != 0 and new == 0:
                state.add_refund(G.SSTORE_CLEARS_REFUND)
        else:
            if original != 0:
                if current == 0:
                    state.add_refund(-G.SSTORE_CLEARS_REFUND)
                elif new == 0:
                    state.add_refund(G.SSTORE_CLEARS_REFUND)
            if new == original:
                if original == 0:
                    state.add_refund(G.SSTORE_SET - G.WARM_SLOAD)
                else:
                    state.add_refund(G.SSTORE_RESET - G.WARM_SLOAD)
        state.set_storage(addr, slot, new)


@op(0x56, 8)
def _jump(evm, frame):
    dest = frame.pop()
    if dest not in frame.jumpdests:
        raise EVMError("invalid jump")
    frame.pc = dest  # land on the JUMPDEST, which charges its own 1 gas


@op(0x57, 10)
def _jumpi(evm, frame):
    dest, cond = frame.pop(), frame.pop()
    if cond:
        if dest not in frame.jumpdests:
            raise EVMError("invalid jump")
        frame.pc = dest


@op(0x58, 2)
def _pc(evm, frame):
    frame.push(frame.pc - 1)


@op(0x59, 2)
def _msize(evm, frame):
    frame.push(len(frame.memory))


@op(0x5A, 2)
def _gas(evm, frame):
    frame.push(frame.gas)


@op(0x5B, 1)
def _jumpdest(evm, frame):
    pass


@op(0x5C)
def _tload(evm, frame):
    """EIP-1153 TLOAD (Cancun): transient storage read, flat warm cost."""
    _require_cancun(evm)
    frame.use_gas(G.TLOAD_GAS)
    slot = frame.pop()
    frame.push(evm.state.get_transient(frame.address, slot))


@op(0x5D)
def _tstore(evm, frame):
    """EIP-1153 TSTORE (Cancun): journaled for reverts, cleared per tx."""
    _require_cancun(evm)
    if frame.msg.is_static:
        raise EVMError("static call state change")
    frame.use_gas(G.TSTORE_GAS)
    slot, value = frame.pop(), frame.pop()
    evm.state.set_transient(frame.address, slot, value)


@op(0x5E)
def _mcopy(evm, frame):
    """EIP-5656 MCOPY (Cancun): memory-to-memory copy, overlap-safe."""
    _require_cancun(evm)
    dest, src, size = frame.pop(), frame.pop(), frame.pop()
    frame.use_gas(3 + G.copy_cost(size))
    if size:
        # one expansion covering both ranges (charged on the larger end)
        frame.expand_memory(max(dest, src), size)
        data = frame.mread(src, size)
        frame.mwrite(dest, data)


@op(0x5F, 2)
def _push0(evm, frame):
    """EIP-3855 (Shanghai)."""
    frame.push(0)


# ---- 0xA0s: logs ----


def _log(evm, frame, topic_count: int):
    if frame.msg.is_static:
        raise EVMError("static call state change")
    offset, size = frame.pop(), frame.pop()
    topics = tuple(frame.pop().to_bytes(32, "big") for _ in range(topic_count))
    frame.use_gas(G.LOG_GAS + G.LOG_TOPIC_GAS * topic_count + G.LOG_DATA_GAS * size)
    frame.expand_memory(offset, size)
    evm.state.add_log(Log(address=frame.address, topics=topics, data=frame.mread(offset, size)))


for _i in range(5):
    _DISPATCH[0xA0 + _i] = (lambda i: lambda evm, frame: _log(evm, frame, i))(_i)


# ---- 0xF0s: calls / create / halt ----


@op(0xF0)
def _create_op(evm, frame):
    if frame.msg.is_static:
        raise EVMError("static call state change")
    value, offset, size = frame.pop(), frame.pop(), frame.pop()
    if size > G.MAX_INITCODE_SIZE:  # EIP-3860
        raise EVMError("initcode too large")
    frame.use_gas(G.CREATE_GAS + G.INITCODE_WORD_COST * ((size + 31) // 32))
    frame.expand_memory(offset, size)
    init_code = frame.mread(offset, size)
    frame.return_data = b""
    if value > evm.state.get_balance(frame.address):
        frame.push(0)
        return
    gas_for_child = frame.gas - frame.gas // 64  # EIP-150
    frame.gas -= gas_for_child
    addr = create_address(frame.address, evm.state.get_nonce(frame.address))
    msg = Message(
        caller=frame.address, target=None, value=value, data=init_code,
        gas=gas_for_child, is_static=False, depth=frame.msg.depth + 1,
    )
    result = evm._nested_create(msg, addr)
    frame.gas += result.gas_left
    if result.success:
        frame.push(_addr_to_int(result.create_address))
    else:
        if result.is_revert:
            frame.return_data = result.output
        frame.push(0)


@op(0xF5)
def _create2_op(evm, frame):
    if frame.msg.is_static:
        raise EVMError("static call state change")
    value, offset, size, salt = frame.pop(), frame.pop(), frame.pop(), frame.pop()
    if size > G.MAX_INITCODE_SIZE:
        raise EVMError("initcode too large")
    words = (size + 31) // 32
    frame.use_gas(G.CREATE_GAS + (G.INITCODE_WORD_COST + G.KECCAK256_WORD_GAS) * words)
    frame.expand_memory(offset, size)
    init_code = frame.mread(offset, size)
    frame.return_data = b""
    if value > evm.state.get_balance(frame.address):
        frame.push(0)
        return
    gas_for_child = frame.gas - frame.gas // 64
    frame.gas -= gas_for_child
    addr = create2_address(frame.address, salt.to_bytes(32, "big"), init_code)
    msg = Message(
        caller=frame.address, target=None, value=value, data=init_code,
        gas=gas_for_child, is_static=False, depth=frame.msg.depth + 1,
    )
    result = evm._nested_create(msg, addr)
    frame.gas += result.gas_left
    if result.success:
        frame.push(_addr_to_int(result.create_address))
    else:
        if result.is_revert:
            frame.return_data = result.output
        frame.push(0)


def _call_family(evm, frame, kind: str):
    gas_req = frame.pop()
    addr = _int_to_addr(frame.pop())
    if kind in ("call", "callcode"):
        value = frame.pop()
    else:
        value = 0
    in_off, in_size, ret_off, ret_size = frame.pop(), frame.pop(), frame.pop(), frame.pop()

    if kind == "call" and value and frame.msg.is_static:
        raise EVMError("static call state change")

    warm = evm.state.access_address(addr)
    access_cost = G.WARM_ACCOUNT_ACCESS if warm else G.COLD_ACCOUNT_ACCESS
    frame.use_gas(access_cost)
    # EIP-7702: a delegated code target charges the delegate's warm/cold
    # access to THIS instruction (caller side, before the 63/64 split)
    frame.use_gas(delegation_access_cost(evm, addr))
    frame.expand_memory(in_off, in_size)
    frame.expand_memory(ret_off, ret_size)

    extra = 0
    if value:
        extra += G.CALL_VALUE_GAS
        if kind == "call" and evm.state.is_empty(addr):
            extra += G.NEW_ACCOUNT_GAS
    frame.use_gas(extra)

    gas_for_child = min(gas_req, frame.gas - frame.gas // 64)  # EIP-150
    frame.use_gas(gas_for_child)
    if value:
        gas_for_child += G.CALL_STIPEND

    args = frame.mread(in_off, in_size)
    frame.return_data = b""

    if value and kind in ("call", "callcode") and evm.state.get_balance(frame.address) < value:
        frame.gas += gas_for_child
        frame.push(0)
        return

    if kind == "call":
        msg = Message(
            caller=frame.address, target=addr, value=value, data=args,
            gas=gas_for_child, is_static=frame.msg.is_static,
            depth=frame.msg.depth + 1,
        )
    elif kind == "callcode":
        msg = Message(
            caller=frame.address, target=frame.address, value=value, data=args,
            gas=gas_for_child, is_static=frame.msg.is_static,
            depth=frame.msg.depth + 1, code_address=addr,
        )
    elif kind == "delegatecall":
        msg = Message(
            caller=frame.msg.caller, target=frame.address, value=frame.msg.value,
            data=args, gas=gas_for_child, is_static=frame.msg.is_static,
            depth=frame.msg.depth + 1, code_address=addr, transfers_value=False,
        )
    else:  # staticcall
        msg = Message(
            caller=frame.address, target=addr, value=0, data=args,
            gas=gas_for_child, is_static=True, depth=frame.msg.depth + 1,
        )
    result = evm._nested_call(msg)
    frame.return_data = result.output
    frame.gas += result.gas_left
    if ret_size and result.output:
        frame.mwrite(ret_off, result.output[:ret_size])
    frame.push(1 if result.success else 0)


@op(0xF1)
def _call_op(evm, frame):
    _call_family(evm, frame, "call")


@op(0xF2)
def _callcode_op(evm, frame):
    _call_family(evm, frame, "callcode")


@op(0xF4)
def _delegatecall_op(evm, frame):
    _call_family(evm, frame, "delegatecall")


@op(0xFA)
def _staticcall_op(evm, frame):
    _call_family(evm, frame, "staticcall")


@op(0xF3)
def _return(evm, frame):
    offset, size = frame.pop(), frame.pop()
    frame.expand_memory(offset, size)
    return ExecResult(True, frame.gas, frame.mread(offset, size))


@op(0xFD)
def _revert(evm, frame):
    offset, size = frame.pop(), frame.pop()
    frame.expand_memory(offset, size)
    frame.return_data = frame.mread(offset, size)
    raise EVMError("revert-op")


@op(0xFE)
def _invalid(evm, frame):
    raise EVMError("designated invalid opcode")


@op(0xFF)
def _selfdestruct(evm, frame):
    if frame.msg.is_static:
        raise EVMError("static call state change")
    beneficiary = _int_to_addr(frame.pop())
    frame.use_gas(G.SELFDESTRUCT_GAS)
    if not evm.state.access_address(beneficiary):
        frame.use_gas(G.COLD_ACCOUNT_ACCESS)
    balance = evm.state.get_balance(frame.address)
    if balance and evm.state.is_empty(beneficiary):
        frame.use_gas(G.NEW_ACCOUNT_GAS)
    selfdestruct_effects(evm.state, frame.address, beneficiary)
    return ExecResult(True, frame.gas)
