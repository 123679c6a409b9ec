"""RLP (Recursive Length Prefix) encoding/decoding.

A from-scratch implementation of Ethereum's canonical serialization format
(yellow-paper appendix B). The reference client consumes RLP through the
external `zig-rlp` dependency (reference: build.zig.zon:5-8, used throughout
src/types/*.zig); here it is a first-class module because every hot-loop
input (MPT node bodies, tx signing payloads, header hashes) is RLP, and the
TPU packer (phant_tpu/ops) needs byte-exact control over node encodings.

Items are `bytes` or (recursively) lists of items. Integers are encoded
big-endian with no leading zeros via :func:`encode_uint`.
"""

from __future__ import annotations

import functools
from typing import Iterable, List, Tuple, Union

RLPItem = Union[bytes, List["RLPItem"]]

__all__ = [
    "encode",
    "encode_python",
    "encode_uint",
    "decode",
    "decode_uint",
    "RLPItem",
    "DecodeError",
]


class DecodeError(ValueError):
    """Raised on malformed or non-canonical RLP input."""


def encode_uint(value: int) -> bytes:
    """Minimal big-endian byte encoding of a non-negative integer (0 -> b'')."""
    if value < 0:
        raise ValueError("cannot RLP-encode negative integer")
    if value == 0:
        return b""
    return value.to_bytes((value.bit_length() + 7) // 8, "big")


def decode_uint(data: bytes) -> int:
    if data[:1] == b"\x00":
        raise DecodeError("non-canonical integer (leading zero)")
    return int.from_bytes(data, "big")


def _encode_length(length: int, short_offset: int) -> bytes:
    if length <= 55:
        return bytes([short_offset + length])
    length_bytes = encode_uint(length)
    return bytes([short_offset + 55 + len(length_bytes)]) + length_bytes


@functools.lru_cache(maxsize=1)
def _native_encoder():
    """The extension's encoder (native/pyext.cc `rlp_encode`), or None
    where the process runs without the extension; asked once a process."""
    from phant_tpu.utils.native import load_engine_ext

    ext = load_engine_ext()
    return ext.rlp_encode if ext is not None else None


def encode(item: RLPItem) -> bytes:
    native = _native_encoder()
    if native is not None:
        return native(item)
    return encode_python(item)


def encode_python(item: RLPItem) -> bytes:
    """`encode` without the extension, and the oracle of its encoders."""
    if isinstance(item, (bytes, bytearray, memoryview)):
        data = bytes(item)
        if len(data) == 1 and data[0] < 0x80:
            return data
        return _encode_length(len(data), 0x80) + data
    if isinstance(item, (list, tuple)):
        payload = b"".join(encode_python(sub) for sub in item)
        return _encode_length(len(payload), 0xC0) + payload
    if isinstance(item, int):
        # Convenience: ints encode as their minimal big-endian bytes.
        return encode_python(encode_uint(item))
    raise TypeError(f"cannot RLP-encode {type(item).__name__}")


_MAX_DEPTH = 64  # nesting cap; untrusted input must not drive Python recursion


def _decode_at(data: bytes, pos: int, depth: int = 0) -> Tuple[RLPItem, int]:
    if depth > _MAX_DEPTH:
        raise DecodeError("RLP nesting too deep")
    if pos >= len(data):
        raise DecodeError("unexpected end of input")
    prefix = data[pos]
    if prefix < 0x80:  # single byte
        return bytes([prefix]), pos + 1
    if prefix <= 0xB7:  # short string
        length = prefix - 0x80
        end = pos + 1 + length
        if end > len(data):
            raise DecodeError("string extends past end of input")
        payload = data[pos + 1 : end]
        if length == 1 and payload[0] < 0x80:
            raise DecodeError("non-canonical single byte")
        return payload, end
    if prefix <= 0xBF:  # long string
        lenlen = prefix - 0xB7
        if pos + 1 + lenlen > len(data):
            raise DecodeError("length extends past end of input")
        length_bytes = data[pos + 1 : pos + 1 + lenlen]
        if length_bytes[:1] == b"\x00":
            raise DecodeError("non-canonical length (leading zero)")
        length = int.from_bytes(length_bytes, "big")
        if length <= 55:
            raise DecodeError("non-canonical length (should be short form)")
        start = pos + 1 + lenlen
        end = start + length
        if end > len(data):
            raise DecodeError("string extends past end of input")
        return data[start:end], end
    if prefix <= 0xF7:  # short list
        length = prefix - 0xC0
        end = pos + 1 + length
        if end > len(data):
            raise DecodeError("list extends past end of input")
        return _decode_list_payload(data, pos + 1, end, depth), end
    # long list
    lenlen = prefix - 0xF7
    if pos + 1 + lenlen > len(data):
        raise DecodeError("length extends past end of input")
    length_bytes = data[pos + 1 : pos + 1 + lenlen]
    if length_bytes[:1] == b"\x00":
        raise DecodeError("non-canonical length (leading zero)")
    length = int.from_bytes(length_bytes, "big")
    if length <= 55:
        raise DecodeError("non-canonical length (should be short form)")
    start = pos + 1 + lenlen
    end = start + length
    if end > len(data):
        raise DecodeError("list extends past end of input")
    return _decode_list_payload(data, start, end, depth), end


def _decode_list_payload(data: bytes, start: int, end: int, depth: int) -> List[RLPItem]:
    items: List[RLPItem] = []
    pos = start
    while pos < end:
        item, pos = _decode_at(data, pos, depth + 1)
        if pos > end:
            raise DecodeError("item extends past end of list")
        items.append(item)
    return items


def decode(data: bytes, *, strict: bool = True) -> RLPItem:
    """Decode a single RLP item; with strict=True, trailing bytes are an error."""
    item, consumed = _decode_at(bytes(data), 0)
    if strict and consumed != len(data):
        raise DecodeError(f"trailing bytes after RLP item ({len(data) - consumed})")
    return item


def decode_prefix(data: bytes) -> Tuple[RLPItem, int]:
    """Decode the first RLP item, returning (item, bytes_consumed)."""
    return _decode_at(bytes(data), 0)
