"""Canonical precommit sign bytes, written from the wire format alone.

proto/tendermint/types/canonical.proto, CanonicalVote:
  1 type (varint)            2 height (sfixed64)     3 round (sfixed64)
  4 block_id (message; omitted when nil)   5 timestamp (message, always)
  6 chain_id (string)
framed as protoio.MarshalDelimited (uvarint length prefix). CanonicalBlockID
is {1 hash, 2 part_set_header{1 total, 2 hash}} with the nested header always
emitted. Zero-valued scalars are omitted (proto3).

This file imports nothing of the program: the generator signs these bytes and
the reference verifies them, so a program whose own encoding drifted would
reject every honest lane.
"""

from __future__ import annotations

PRECOMMIT = 2


def _uvarint(n: int) -> bytes:
    out = bytearray()
    while True:
        b = n & 0x7F
        n >>= 7
        if n:
            out.append(b | 0x80)
        else:
            out.append(b)
            return bytes(out)


def _varint_field(field: int, value: int) -> bytes:
    if value == 0:
        return b""
    return _uvarint(field << 3) + _uvarint(value & 0xFFFFFFFFFFFFFFFF)


def _sfixed64_field(field: int, value: int) -> bytes:
    if value == 0:
        return b""
    return _uvarint(field << 3 | 1) + (value & 0xFFFFFFFFFFFFFFFF).to_bytes(
        8, "little"
    )


def _bytes_field(field: int, value: bytes, always: bool = False) -> bytes:
    if not value and not always:
        return b""
    return _uvarint(field << 3 | 2) + _uvarint(len(value)) + value


def vote_template(
    chain_id: str, height: int, round_: int, block_hash: bytes,
    psh_total: int, psh_hash: bytes,
) -> tuple[bytes, bytes]:
    """(prefix, suffix) around the one field that differs per validator
    of a commit: the timestamp."""
    psh = _varint_field(1, psh_total) + _bytes_field(2, psh_hash)
    block_id = _bytes_field(1, block_hash) + _bytes_field(2, psh, always=True)
    prefix = (
        _varint_field(1, PRECOMMIT)
        + _sfixed64_field(2, height)
        + _sfixed64_field(3, round_)
        + _bytes_field(4, block_id)
    )
    return prefix, _bytes_field(6, chain_id.encode())


def vote_sign_bytes(template: tuple[bytes, bytes], timestamp_ns: int) -> bytes:
    seconds, nanos = divmod(timestamp_ns, 1_000_000_000)
    ts = _varint_field(1, seconds) + _varint_field(2, nanos)
    body = template[0] + _bytes_field(5, ts, always=True) + template[1]
    return _uvarint(len(body)) + body
