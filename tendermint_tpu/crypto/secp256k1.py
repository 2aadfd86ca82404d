"""secp256k1 ECDSA key types (analog of reference crypto/secp256k1).

Signatures are 64-byte compact (r||s, 32 bytes each, big-endian) with the
low-S malleability rule enforced on both sign and verify, matching the
reference (crypto/secp256k1/secp256k1_nocgo.go:21-48). Public keys are
33-byte compressed SEC1. Like the reference, secp256k1 has no batch kernel
(`crypto.batch.supports_batch_verifier` is False for it): in a commit or a
commit range its rows take the AdaptiveBatchVerifier's host lane — one
`verify_signature` a row on the host pool, beside the Edwards rows' device
dispatch — so a mixed validator set keeps its ed25519 majority in the range
batch (a secp256k1 device kernel is a later milestone, see BASELINE.md
config 4 and PERF.md §7).

Verification calls the system's OpenSSL (`libcrypto`) through `ctypes`, one
`ECDSA_verify` a signature with the parsed key kept per thread: a `ctypes`
call drops the GIL for its length, which `cryptography`'s binding does not
(its verify holds the GIL from end to end, so a pool of threads verifies no
faster than one: PERF.md §6, PR 32) — the host lane's threads therefore run
side by side. Where no `libcrypto` with the curve is found, verification
goes through `cryptography`, same verdicts, one thread's worth of speed.

When the OpenSSL-backed `cryptography` package is absent the module degrades
to the pure-Python RFC 6979 implementation in softcrypto.py (deterministic
nonces on both paths, so signatures are stable either way)."""

from __future__ import annotations

import ctypes
import ctypes.util
import secrets
import threading

try:
    from cryptography.exceptions import InvalidSignature
    from cryptography.hazmat.primitives import hashes as crypto_hashes
    from cryptography.hazmat.primitives.asymmetric import ec
    from cryptography.hazmat.primitives.asymmetric.utils import (
        Prehashed,
        decode_dss_signature,
        encode_dss_signature,
    )
    from cryptography.hazmat.primitives.serialization import (
        Encoding,
        PublicFormat,
    )

    _HAVE_OPENSSL = True
except ImportError:  # degraded path: pure-Python ECDSA (softcrypto)
    _HAVE_OPENSSL = False

from . import PrivKey, PubKey, register_pubkey_type
from . import softcrypto
from .hashes import sha256

KEY_TYPE = "secp256k1"
PUBKEY_SIZE = 33
PRIVKEY_SIZE = 32
SIGNATURE_SIZE = 64

# curve order
N = 0xFFFFFFFFFFFFFFFFFFFFFFFFFFFFFFFEBAAEDCE6AF48A03BBFD25E8CD0364141
HALF_N = N // 2


#: OpenSSL's number for the curve (obj_mac.h NID_secp256k1)
_NID_SECP256K1 = 714
#: parsed keys a thread keeps before it frees them all and starts again
_KEY_CACHE_MAX = 1024


def _load_libcrypto():
    """The system's libcrypto with the calls verification needs declared,
    or None where there is none or it lacks the curve."""
    try:
        lib = ctypes.CDLL(ctypes.util.find_library("crypto") or "libcrypto.so.3")
        vp = ctypes.c_void_p
        lib.EC_KEY_new_by_curve_name.restype = vp
        lib.EC_KEY_new_by_curve_name.argtypes = [ctypes.c_int]
        lib.EC_KEY_oct2key.restype = ctypes.c_int
        lib.EC_KEY_oct2key.argtypes = [vp, ctypes.c_char_p, ctypes.c_size_t, vp]
        lib.EC_KEY_free.restype = None
        lib.EC_KEY_free.argtypes = [vp]
        lib.ECDSA_verify.restype = ctypes.c_int
        lib.ECDSA_verify.argtypes = [
            ctypes.c_int, ctypes.c_char_p, ctypes.c_int, ctypes.c_char_p, ctypes.c_int, vp
        ]
        lib.ERR_clear_error.restype = None
        key = lib.EC_KEY_new_by_curve_name(_NID_SECP256K1)
        if not key:
            return None
        lib.EC_KEY_free(key)
        return lib
    except (OSError, AttributeError):
        return None


#: loaded with the module, before any pool thread can ask for it
_LIBCRYPTO = _load_libcrypto()
_parsed = threading.local()


def _ec_key(lib, data: bytes):
    """This thread's EC_KEY for a 33-byte compressed point (None where the
    bytes are no point of the curve). Per thread, so that no OpenSSL
    object is ever shared between threads."""
    keys = _parsed.__dict__.setdefault("keys", {})
    key = keys.get(data)
    if key is None and data not in keys:
        if len(keys) >= _KEY_CACHE_MAX:
            for old in keys.values():
                if old:
                    lib.EC_KEY_free(old)
            keys.clear()
        key = lib.EC_KEY_new_by_curve_name(_NID_SECP256K1)
        if not lib.EC_KEY_oct2key(key, data, len(data), None):
            lib.EC_KEY_free(key)
            lib.ERR_clear_error()
            key = None
        keys[data] = key
    return key


def _der_int(b: bytes) -> bytes:
    b = b.lstrip(b"\0")
    if b[0] & 0x80:
        b = b"\0" + b
    return b"\x02" + bytes((len(b),)) + b


def _der_signature(sig: bytes) -> bytes:
    """r||s (each 32 bytes, neither zero) as the DER SEQUENCE of two
    INTEGERs that OpenSSL's ECDSA_verify takes."""
    body = _der_int(sig[:32]) + _der_int(sig[32:])
    return b"\x30" + bytes((len(body),)) + body


class Secp256k1PubKey(PubKey):
    TYPE = KEY_TYPE

    def __init__(self, data: bytes):
        if len(data) != PUBKEY_SIZE:
            raise ValueError(f"secp256k1 pubkey must be {PUBKEY_SIZE} bytes")
        self._bytes = bytes(data)

    def bytes(self) -> bytes:
        return self._bytes

    def verify_signature(self, msg: bytes, sig: bytes) -> bool:
        if len(sig) != SIGNATURE_SIZE:
            return False
        r = int.from_bytes(sig[:32], "big")
        s = int.from_bytes(sig[32:], "big")
        if not (0 < r < N and 0 < s <= HALF_N):  # reject high-S (malleability)
            return False
        lib = _LIBCRYPTO
        if lib is not None:
            key = _ec_key(lib, self._bytes)
            if key is None:
                return False
            der = _der_signature(sig)
            if lib.ECDSA_verify(0, sha256(msg), 32, der, len(der), key) == 1:
                return True
            lib.ERR_clear_error()
            return False
        if not _HAVE_OPENSSL:
            return softcrypto.secp256k1_verify(self._bytes, sha256(msg), r, s)
        try:
            pub = ec.EllipticCurvePublicKey.from_encoded_point(
                ec.SECP256K1(), self._bytes
            )
            pub.verify(
                encode_dss_signature(r, s),
                sha256(msg),
                ec.ECDSA(Prehashed(crypto_hashes.SHA256())),
            )
            return True
        except (InvalidSignature, ValueError):
            return False


class Secp256k1PrivKey(PrivKey):
    TYPE = KEY_TYPE

    def __init__(self, data: bytes):
        if len(data) != PRIVKEY_SIZE:
            raise ValueError(f"secp256k1 privkey must be {PRIVKEY_SIZE} bytes")
        self._bytes = bytes(data)
        self._d = int.from_bytes(data, "big")
        if _HAVE_OPENSSL:
            self._sk = ec.derive_private_key(self._d, ec.SECP256K1())
            self._pub = self._sk.public_key().public_bytes(
                Encoding.X962, PublicFormat.CompressedPoint
            )
        else:
            self._sk = None
            self._pub = softcrypto.secp256k1_pub(self._d)

    @classmethod
    def generate(cls) -> "Secp256k1PrivKey":
        while True:
            d = secrets.token_bytes(PRIVKEY_SIZE)
            v = int.from_bytes(d, "big")
            if 0 < v < N:
                return cls(d)

    def bytes(self) -> bytes:
        return self._bytes

    def sign(self, msg: bytes) -> bytes:
        if self._sk is not None:
            der = self._sk.sign(
                sha256(msg), ec.ECDSA(Prehashed(crypto_hashes.SHA256()))
            )
            r, s = decode_dss_signature(der)
        else:
            r, s = softcrypto.secp256k1_sign(self._d, sha256(msg))
        if s > HALF_N:
            s = N - s
        return r.to_bytes(32, "big") + s.to_bytes(32, "big")

    def pub_key(self) -> Secp256k1PubKey:
        return Secp256k1PubKey(self._pub)


register_pubkey_type(KEY_TYPE, Secp256k1PubKey)
