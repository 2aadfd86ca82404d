"""secp256k1 verification gives one verdict whichever back end computes it:
the system's libcrypto through ctypes (the path the host lane's threads run
side by side), `cryptography`, and the pure-Python softcrypto."""

import hashlib
import threading
from concurrent.futures import ThreadPoolExecutor

import pytest

from tendermint_tpu.crypto import secp256k1 as m

pytestmark = pytest.mark.skipif(
    m._LIBCRYPTO is None or not m._HAVE_OPENSSL,
    reason="needs both the system libcrypto and cryptography",
)

N = m.N


def _key(i: int) -> m.Secp256k1PrivKey:
    return m.Secp256k1PrivKey(hashlib.sha256(b"backend-key-%d" % i).digest())


def _flip(b: bytes, bit: int) -> bytes:
    out = bytearray(b)
    out[bit // 8] ^= 1 << (bit % 8)
    return bytes(out)


def _short_r_signature(sk) -> tuple[bytes, bytes]:
    """(msg, sig) whose r starts with a zero byte: DER drops it."""
    for i in range(20000):
        msg = b"short-r-%d" % i
        sig = sk.sign(msg)
        if sig[0] == 0:
            return msg, sig
    raise AssertionError("no signature with a short r in 20000 tries")


def _cases():
    sk, other = _key(1), _key(2)
    pk = sk.pub_key().bytes()
    msg = b"a canonical vote's sign-bytes, more or less" * 3
    sig = sk.sign(msg)
    r, s = sig[:32], sig[32:]
    s_int = int.from_bytes(s, "big")
    short_msg, short_sig = _short_r_signature(sk)
    on_curve_x = pk[1:]
    # an x with no point on the curve: x^3 + 7 is no square mod p
    p = 2**256 - 2**32 - 977
    x = 5
    while pow((x**3 + 7) % p, (p - 1) // 2, p) == 1:
        x += 1
    off_curve = b"\x02" + x.to_bytes(32, "big")
    return {
        "valid": (pk, msg, sig, True),
        "valid-short-r": (pk, short_msg, short_sig, True),
        "short-r-other-msg": (pk, msg, short_sig, False),
        "msg-changed": (pk, msg + b"x", sig, False),
        "r-bit-0": (pk, msg, _flip(sig, 0), False),
        "r-bit-255": (pk, msg, _flip(sig, 255), False),
        "s-bit-0": (pk, msg, _flip(sig, 256), False),
        "s-bit-200": (pk, msg, _flip(sig, 456), False),
        "other-key": (other.pub_key().bytes(), msg, sig, False),
        "other-parity": (bytes((pk[0] ^ 1,)) + on_curve_x, msg, sig, False),
        "high-s": (pk, msg, r + (N - s_int).to_bytes(32, "big"), False),
        "r-zero": (pk, msg, bytes(32) + s, False),
        "s-zero": (pk, msg, r + bytes(32), False),
        "r-is-n": (pk, msg, N.to_bytes(32, "big") + s, False),
        "r-all-ones": (pk, msg, b"\xff" * 32 + s, False),
        "sig-short": (pk, msg, sig[:63], False),
        "sig-long": (pk, msg, sig + b"\0", False),
        "key-off-curve": (off_curve, msg, sig, False),
        "key-prefix-04": (b"\x04" + on_curve_x, msg, sig, False),
        "key-prefix-00": (b"\x00" + on_curve_x, msg, sig, False),
        "key-prefix-06": (b"\x06" + on_curve_x, msg, sig, False),
        "key-x-is-p": (b"\x02" + p.to_bytes(32, "big"), msg, sig, False),
        "empty-msg": (pk, b"", sk.sign(b""), True),
    }


CASES = _cases()


def _verdicts(monkeypatch, pk: bytes, msg: bytes, sig: bytes) -> dict:
    key = m.Secp256k1PubKey(pk)
    out = {"libcrypto": key.verify_signature(msg, sig)}
    monkeypatch.setattr(m, "_LIBCRYPTO", None)
    out["cryptography"] = key.verify_signature(msg, sig)
    monkeypatch.setattr(m, "_HAVE_OPENSSL", False)
    out["softcrypto"] = key.verify_signature(msg, sig)
    return out


@pytest.mark.parametrize("name", sorted(CASES))
def test_every_backend_gives_the_same_verdict(monkeypatch, name):
    pk, msg, sig, want = CASES[name]
    assert _verdicts(monkeypatch, pk, msg, sig) == {
        "libcrypto": want, "cryptography": want, "softcrypto": want}


@pytest.mark.parametrize("seed", range(8))
def test_backends_agree_on_seeded_signatures_and_single_bit_flips(monkeypatch, seed):
    """32 signatures a seed, each sound and with one seeded bit flipped."""
    rows = []
    for i in range(32):
        sk = _key(100 * seed + i % 5)
        msg = hashlib.sha256(b"m-%d-%d" % (seed, i)).digest() * (1 + i % 4)
        sig = sk.sign(msg)
        bit = int.from_bytes(hashlib.sha256(b"bit-%d-%d" % (seed, i)).digest()[:2], "big") % 512
        rows.append((sk.pub_key(), msg, sig))
        rows.append((sk.pub_key(), msg, _flip(sig, bit)))
    fast = [pk.verify_signature(msg, sig) for pk, msg, sig in rows]
    monkeypatch.setattr(m, "_LIBCRYPTO", None)
    slow = [pk.verify_signature(msg, sig) for pk, msg, sig in rows]
    assert fast == slow
    assert fast[0::2] == [True] * 32 and not any(fast[1::2])


@pytest.mark.parametrize("r,s", [
    (1, 1), (0x7F, 0x80), (0x80, 0x7F), (N - 1, N // 2), (2**255, 2**254),
    (2**248 - 1, 2**8), (2**200 + 5, 2**100 + 3),
])
def test_der_signature_is_cryptographys_encoding(r, s):
    from cryptography.hazmat.primitives.asymmetric.utils import encode_dss_signature

    sig = r.to_bytes(32, "big") + s.to_bytes(32, "big")
    assert m._der_signature(sig) == encode_dss_signature(r, s)


def test_threads_verify_side_by_side_with_their_own_parsed_keys():
    """Eight threads over the same 40 keys: every verdict right, and each
    thread parsed its own copy of a key (no OpenSSL object is shared)."""
    sks = [_key(500 + i) for i in range(40)]
    rows = []
    for i in range(400):
        sk = sks[i % 40]
        msg = b"row-%d" % i
        sig = sk.sign(msg)
        rows.append((sk.pub_key(), msg, sig if i % 7 else _flip(sig, 300), bool(i % 7)))
    handles: dict[int, dict] = {}
    started = threading.Barrier(8)

    def work(part):
        started.wait()
        out = [pk.verify_signature(msg, sig) for pk, msg, sig, _ in part]
        handles[threading.get_ident()] = dict(m._parsed.keys)
        return out

    with ThreadPoolExecutor(8) as pool:
        parts = [rows[50 * i : 50 * (i + 1)] for i in range(8)]
        got = list(pool.map(work, parts))
    for part, verdicts in zip(parts, got):
        assert verdicts == [want for *_, want in part]
    assert len(handles) == 8
    seen: set[int] = set()
    for keys in handles.values():
        assert len(keys) == 40 and all(keys.values())
        assert seen.isdisjoint(keys.values())
        seen.update(keys.values())


def test_the_parsed_key_cache_is_bounded(monkeypatch):
    monkeypatch.setattr(m, "_KEY_CACHE_MAX", 4)

    def work():
        sizes = []
        for i in range(11):
            sk = _key(900 + i)
            assert sk.pub_key().verify_signature(b"x", sk.sign(b"x"))
            sizes.append(len(m._parsed.keys))
        return sizes

    with ThreadPoolExecutor(1) as pool:
        assert pool.submit(work).result() == [1, 2, 3, 4, 1, 2, 3, 4, 1, 2, 3]
