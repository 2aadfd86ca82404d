"""Deterministic protobuf-wire-compatible encoder.

The consensus-critical byte strings in this framework (canonical vote
sign-bytes, header field encodings, hashes) are produced by this module. It
implements the subset of the protobuf wire format needed for canonical
encodings — varint, fixed64/sfixed64, and length-delimited fields — with
strictly deterministic output (fields emitted in ascending tag order, default
values omitted, no unknown fields).

The reference builds its canonical sign-bytes from gogoproto-generated
marshalling (reference types/canonical.go:56, sfixed64 height/round); this
module provides the same determinism guarantees without a codegen step.
"""

from __future__ import annotations

import struct

WIRE_VARINT = 0
WIRE_FIXED64 = 1
WIRE_BYTES = 2
WIRE_FIXED32 = 5


def _uvarint_loop(value: int) -> bytes:
    out = bytearray()
    while value > 0x7F:
        out.append((value & 0x7F) | 0x80)
        value >>= 7
    out.append(value)
    return bytes(out)


# Every constant of the wire format is made once, here: the one-byte
# varints (lengths under 128, flags, small counts) and the tags of field
# numbers 0..31 under all wire types. A 150-validator commit asks for
# the same few hundreds of times over.
_B1 = tuple(bytes((i,)) for i in range(0x80))
_TAGS = tuple(_uvarint_loop(i) for i in range(32 << 3))
_pack_Q = struct.Struct("<Q").pack
_U64 = (1 << 64) - 1


def uvarint(value: int) -> bytes:
    """Encode an unsigned integer as a protobuf base-128 varint."""
    if 0 <= value <= 0x7F:
        return _B1[value]
    if value < 0:
        raise ValueError("uvarint requires a non-negative value")
    return _uvarint_loop(value)


def varint(value: int) -> bytes:
    """An int64 as a varint value: a negative number is its 10-byte two's
    complement (what proto does with a negative int64)."""
    if 0 <= value <= 0x7F:
        return _B1[value]
    return _uvarint_loop(value & _U64 if value < 0 else value)


#: an sfixed64 value (what sfixed64_field writes after its tag)
sfixed64 = struct.Struct("<q").pack


def svarint(value: int) -> bytes:
    """Zigzag-encoded signed varint."""
    return uvarint((value << 1) ^ (value >> 63) if value < 0 else value << 1)


def tag(field_number: int, wire_type: int) -> bytes:
    key = (field_number << 3) | wire_type
    if 0 <= key < len(_TAGS):
        return _TAGS[key]
    return uvarint(key)


_KIND_WIRE_TYPE = {
    "varint": WIRE_VARINT,
    "sfixed64": WIRE_FIXED64,
    "fixed64": WIRE_FIXED64,
    "bytes": WIRE_BYTES,
    "message": WIRE_BYTES,
}


def field_tag(field_number: int, kind: str) -> bytes:
    """The tag of a field, for an encoder of many elements that makes it
    once, as a module constant, and writes `TAG + value` itself. `kind`
    is the name the wire-schema lockfile gives the field's helper
    ("varint", "sfixed64", "fixed64", "bytes", "message"): tmtlint reads
    the constant's definition and locks the encoder that uses it like one
    that calls the helper."""
    return tag(field_number, _KIND_WIRE_TYPE[kind])


def varint_field(field_number: int, value: int) -> bytes:
    """Varint field; 0 is omitted (proto3 default-elision)."""
    if value == 0:
        return b""
    return tag(field_number, WIRE_VARINT) + varint(value)


def bool_field(field_number: int, value: bool) -> bytes:
    return varint_field(field_number, 1 if value else 0)


def sfixed64_field(field_number: int, value: int) -> bytes:
    if value == 0:
        return b""
    return tag(field_number, WIRE_FIXED64) + sfixed64(value)


def fixed64_field(field_number: int, value: int) -> bytes:
    if value == 0:
        return b""
    return tag(field_number, WIRE_FIXED64) + _pack_Q(value)


def bytes_field(field_number: int, value: bytes) -> bytes:
    """Length-delimited field; empty bytes are omitted."""
    if not value:
        return b""
    return tag(field_number, WIRE_BYTES) + uvarint(len(value)) + value


def string_field(field_number: int, value: str) -> bytes:
    return bytes_field(field_number, value.encode("utf-8"))


def message_field(field_number: int, encoded: bytes) -> bytes:
    """Embedded message field. Unlike bytes_field, an empty message is still
    emitted when explicitly requested (callers pass None to omit)."""
    return tag(field_number, WIRE_BYTES) + uvarint(len(encoded)) + encoded


def len_prefixed(encoded: bytes) -> bytes:
    """Length-delimit a full message (framing used for streams and hashing)."""
    return uvarint(len(encoded)) + encoded


def check_repeat(items, bound: int, what: str) -> None:
    """Clamp a repeated-field collection at decode. Wire frames arrive
    from untrusted peers (and durable bytes see chaos bit-rot), so a
    corrupt repeat count must raise, never allocate — the shared
    checker every decode loop calls with its module's named ``MAX_*``
    bound (the tmtlint wire-bounds rule recognizes the call as the
    clamp)."""
    if len(items) > bound:
        raise ValueError(f"wire frame repeats {what} beyond {bound}")


class Reader:
    """Minimal wire-format reader for decoding our own encodings."""

    def __init__(self, data: bytes):
        self.data = data
        self.pos = 0

    def eof(self) -> bool:
        return self.pos >= len(self.data)

    def read_uvarint(self) -> int:
        shift = 0
        result = 0
        while True:
            if self.pos >= len(self.data):
                raise ValueError("truncated varint")
            b = self.data[self.pos]
            self.pos += 1
            result |= (b & 0x7F) << shift
            if not b & 0x80:
                return result
            shift += 7
            if shift > 70:
                raise ValueError("varint too long")

    def read_tag(self) -> tuple[int, int]:
        v = self.read_uvarint()
        return v >> 3, v & 0x7

    def read_fixed64(self) -> int:
        if self.pos + 8 > len(self.data):
            raise ValueError("truncated fixed64")
        (v,) = struct.unpack_from("<Q", self.data, self.pos)
        self.pos += 8
        return v

    def read_sfixed64(self) -> int:
        if self.pos + 8 > len(self.data):
            raise ValueError("truncated sfixed64")
        (v,) = struct.unpack_from("<q", self.data, self.pos)
        self.pos += 8
        return v

    def read_bytes(self) -> bytes:
        n = self.read_uvarint()
        if self.pos + n > len(self.data):
            raise ValueError("truncated bytes")
        v = self.data[self.pos : self.pos + n]
        self.pos += n
        return v

    def read_string(self) -> str:
        return self.read_bytes().decode("utf-8")

    def skip(self, wire_type: int) -> None:
        if wire_type == WIRE_VARINT:
            self.read_uvarint()
        elif wire_type == WIRE_FIXED64:
            self.pos += 8
        elif wire_type == WIRE_BYTES:
            self.read_bytes()
        elif wire_type == WIRE_FIXED32:
            self.pos += 4
        else:
            raise ValueError(f"unknown wire type {wire_type}")
