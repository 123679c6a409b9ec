"""The native C++ EVM core (native/evm.cc) behind its host binding.

Architecture mirror of the reference: evmone (C++) executes bytecode while
the client provides a host vtable over its StateDB (reference:
src/blockchain/vm.zig:40-55 installs 14 host callbacks; nested calls
re-enter the interpreter through the host, vm.zig:382-522). Here the host
IS native code too: `EvmHost` of the CPython extension (native/pyext.cc,
built by `utils/native.load_ext` from pyext.cc + engine.cc + keccak.cc +
evm.cc) fills the VM's 19-entry vtable with C++ callbacks that call the
Python `StateDB`'s methods by name and write the answers into the VM's
buffers, holding the interpreter lock from a frame's entry to its return.
Nested CALL*/CREATE* ops route back through `Evm._nested_call` /
`_nested_create` (`_nested` below), which re-enter the binding for the
child's frame.

One binding serves a BLOCK (`BlockHost`): `Blockchain.run_block` hands the
same one to every transaction's `Evm` and closes it with the block; an
`Evm` that got none (a test's lone message) makes its own and closes it
when its message ends. What the block fixes is set when the binding is
built, what a transaction fixes at each outermost frame.

Enabled via `--evm_backend=native` (phant_tpu.backend). The extension needs
g++ and Python's headers; where it does not load (no toolchain,
PHANT_NO_NATIVE) the pure-Python interpreter runs the frame. There is no
second native path and nothing to choose.
"""

from __future__ import annotations

from typing import Optional

from phant_tpu.evm.interpreter import (
    _visible_code,
    create2_address,
    create_address,
    delegation_access_cost,
    selfdestruct_effects,
    visible_code_hash,
)
from phant_tpu.evm.message import ExecResult, Message
from phant_tpu.types.receipt import Log
from phant_tpu.utils.native import load_ext
from phant_tpu.utils.trace import metrics

# PhantCallKind of native/evm.h
KIND_CALL, KIND_CALLCODE, KIND_DELEGATECALL, KIND_STATICCALL = 0, 1, 2, 3
KIND_CREATE, KIND_CREATE2 = 4, 5


def _nested(
    evm, kind, is_static, depth, gas, caller, target, code_address, value,
    data, salt,
):  # fmt: skip
    """The `call` callback's Python half: the child's Message, then the Evm,
    which re-enters the binding for the child's frame. Answers
    (status, gas_left, output, created address | None)."""
    if kind in (KIND_CREATE, KIND_CREATE2):
        msg = Message(
            caller=caller, target=None, value=value, data=data, gas=gas,
            is_static=False, depth=depth,
        )  # fmt: skip
        if kind == KIND_CREATE2:
            addr = create2_address(caller, salt, data)
        else:
            addr = create_address(caller, evm.state.get_nonce(caller))
        result = evm._nested_create(msg, addr)
    else:
        msg = Message(
            caller=caller,
            target=target,
            value=value,
            data=data,
            gas=gas,
            is_static=bool(is_static),
            depth=depth,
            code_address=(
                code_address if kind in (KIND_CALLCODE, KIND_DELEGATECALL) else None
            ),
            transfers_value=kind != KIND_DELEGATECALL,
        )
        result = evm._nested_call(msg)
    status = 0 if result.success else (1 if result.is_revert else 2)
    return status, result.gas_left, result.output, result.create_address


_HELPERS = (
    _visible_code,
    visible_code_hash,
    delegation_access_cost,
    selfdestruct_effects,
    _nested,
    Log,
)


def native_available() -> bool:
    return load_ext() is not None


class BlockHost:
    """The native VM's host binding for the frames of one block: built at
    the first frame that reaches code (a block of plain transfers builds
    none), shared by the block's transactions, dropped by `close`. At rest
    the binding refers to the state and the block's constants and to
    nothing that refers back, so closing frees it by reference count."""

    __slots__ = ("_binding",)

    def __init__(self):
        self._binding = None

    def execute(self, evm, code: bytes, msg: Message, address: bytes) -> Optional[ExecResult]:
        """Run one frame natively; None if the extension is unavailable."""
        binding = self._binding
        if binding is None:
            ext = load_ext()
            if ext is None:
                return None
            env = evm.env
            binding = self._binding = ext.EvmHost(
                evm.state, env.coinbase, env.block_number, env.timestamp,
                env.gas_limit, env.chain_id, env.prev_randao, env.base_fee,
                env.revision, env.block_hash_fn, _HELPERS,
            )  # fmt: skip
            metrics.count("evm.host_bindings")
        status, gas_left, output = binding.execute(
            evm, code, msg.caller, address, msg.value, msg.data, msg.gas,
            msg.depth, msg.is_static,
        )  # fmt: skip
        if status == 0:
            return ExecResult(True, gas_left, output)
        if status == 1:
            return ExecResult(False, gas_left, output, error="revert")
        return ExecResult(False, 0, error="native evm failure")

    def close(self) -> None:
        binding, self._binding = self._binding, None
        if binding is not None:
            metrics.count("evm.native_frames", binding.frames_run(), binding="ext")
