"""RLP, as the yellow paper's appendix B has it. Items are bytes or lists."""

from __future__ import annotations


def uint(n: int) -> bytes:
    """The big-endian bytes of a scalar, with no leading zero (0 is empty)."""
    return n.to_bytes((n.bit_length() + 7) // 8, "big")


def _length(n: int, offset: int) -> bytes:
    if n < 56:
        return bytes([offset + n])
    raw = uint(n)
    return bytes([offset + 55 + len(raw)]) + raw


def encode(item) -> bytes:
    if isinstance(item, bytes):
        n = len(item)
        if n == 1 and item[0] < 0x80:
            return item
        if n < 56:
            return bytes((0x80 + n,)) + item
        return _length(n, 0x80) + item
    payload = b"".join([encode(x) for x in item])
    return _length(len(payload), 0xC0) + payload


def _decode_at(data: bytes, pos: int):
    first = data[pos]
    if first < 0x80:
        return data[pos : pos + 1], pos + 1
    if first < 0xB8:
        end = pos + 1 + first - 0x80
        return data[pos + 1 : end], end
    if first < 0xC0:
        ll = first - 0xB7
        n = int.from_bytes(data[pos + 1 : pos + 1 + ll], "big")
        start = pos + 1 + ll
        return data[start : start + n], start + n
    if first < 0xF8:
        start, n = pos + 1, first - 0xC0
    else:
        ll = first - 0xF7
        n = int.from_bytes(data[pos + 1 : pos + 1 + ll], "big")
        start = pos + 1 + ll
    end, out = start + n, []
    while start < end:
        item, start = _decode_at(data, start)
        out.append(item)
    return out, end


def decode(data: bytes):
    item, end = _decode_at(data, 0)
    if end != len(data):
        raise ValueError("trailing bytes after an RLP item")
    return item
