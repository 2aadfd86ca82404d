"""Crypto core: key/signature interfaces and batch-verifier dispatch.

Mirrors the surface of the reference's crypto package (reference
crypto/crypto.go:22-54): `PubKey`, `PrivKey`, and the two-method
`BatchVerifier` (`add`, `verify`) that the whole commit-verification funnel
gates on. The TPU implementation registers behind the same interface
(crypto/tpu/), so consensus, block-sync, state-sync, and the light client are
agnostic to where verification executes.
"""

from __future__ import annotations

import abc

from ..libs import protoenc as pe


class PubKey(abc.ABC):
    TYPE: str = ""

    @abc.abstractmethod
    def bytes(self) -> bytes: ...

    @abc.abstractmethod
    def verify_signature(self, msg: bytes, sig: bytes) -> bool: ...

    def address(self) -> bytes:
        """SHA-256(key bytes)[:20], derived on first use and kept on the
        key: a key's bytes are set once, and validator sets share key
        objects across copies, so a set pays one derivation a key."""
        addr = self.__dict__.get("_address")
        if addr is None:
            from .hashes import address

            addr = self.__dict__["_address"] = address(self.bytes())
        return addr

    def __eq__(self, other) -> bool:
        return (
            isinstance(other, PubKey)
            and self.TYPE == other.TYPE
            and self.bytes() == other.bytes()
        )

    def __hash__(self) -> int:
        return hash((self.TYPE, self.bytes()))

    def __repr__(self) -> str:
        return f"PubKey{{{self.TYPE}:{self.bytes().hex()[:16]}…}}"


class PrivKey(abc.ABC):
    TYPE: str = ""

    @abc.abstractmethod
    def bytes(self) -> bytes: ...

    @abc.abstractmethod
    def sign(self, msg: bytes) -> bytes: ...

    @abc.abstractmethod
    def pub_key(self) -> PubKey: ...


class BatchVerifier(abc.ABC):
    """Accumulate (pubkey, msg, sig) triples, verify them in one shot.

    `verify` returns (all_ok, per_item_validity) — the same contract as the
    reference (crypto/crypto.go:46-54)."""

    @abc.abstractmethod
    def add(self, pub_key: PubKey, msg: bytes, sig: bytes) -> None: ...

    @abc.abstractmethod
    def verify(self) -> tuple[bool, list[bool]]: ...


# registry: key type name -> (pubkey codec, batch verifier factory)
_PUBKEY_DECODERS: dict[str, callable] = {}


def register_pubkey_type(type_name: str, decoder) -> None:
    _PUBKEY_DECODERS[type_name] = decoder


#: builtin key-type modules, lazily imported on first decode
_BUILTIN_KEY_MODULES = {
    "ed25519": "ed25519",
    "secp256k1": "secp256k1",
    "sr25519": "sr25519",
    "bls12381": "bls",
}


def pubkey_from_type_and_bytes(type_name: str, data: bytes) -> PubKey:
    if type_name not in _PUBKEY_DECODERS and type_name in _BUILTIN_KEY_MODULES:
        # decoders register at module import; pull in the builtin module
        # for a known type on first use (a genesis doc with secp256k1
        # validators must decode without the caller pre-importing it)
        import importlib

        importlib.import_module(f".{_BUILTIN_KEY_MODULES[type_name]}", __name__)
    try:
        dec = _PUBKEY_DECODERS[type_name]
    except KeyError:
        raise ValueError(f"unknown pubkey type {type_name!r}") from None
    return dec(data)


# The reference's tendermint.crypto.PublicKey proto oneof field numbers
# (proto/tendermint/crypto/keys.proto:13-17) — consensus-critical: the
# validator-set hash merkles SimpleValidator encodings built on this.
# bls12381 is a framework extension on the next free field number.
PUBKEY_PROTO_FIELD = {"ed25519": 1, "secp256k1": 2, "sr25519": 3, "bls12381": 4}
_PUBKEY_PROTO_TYPE = {v: k for k, v in PUBKEY_PROTO_FIELD.items()}


def pubkey_to_proto(pub: PubKey) -> bytes:
    """Serialize as the reference's PublicKey oneof message — byte-exact
    (frozen against the reference's MBT vectors, tests/test_light_mbt.py)."""
    return pe.bytes_field(PUBKEY_PROTO_FIELD[pub.TYPE], pub.bytes())


def pubkey_from_proto(data: bytes) -> PubKey:
    r = pe.Reader(data)
    f, wt = r.read_tag()
    try:
        type_name = _PUBKEY_PROTO_TYPE[f]
    except KeyError:
        raise ValueError(f"unknown PublicKey oneof field {f}") from None
    return pubkey_from_type_and_bytes(type_name, r.read_bytes())
