"""The host prep as it stood before PR 28, kept as the oracle (as PR 26
kept the loop encoder): `resolve_ed25519`, `prepare_batch_eq` and
`prepare_resolved` letter for letter from the parent's
`tendermint_tpu/crypto/tpu/verify.py` — one object and half a dozen
Python passes a signature. `tests/test_host_prep.py` holds the program's
column-wise prep byte-equal to what these return for the same random
bytes. Nothing in the program imports this."""

from __future__ import annotations

import hashlib

import numpy as np

L = 2**252 + 27742317777372353535851937790883648493

_MIN_BUCKET = 64


class ResolvedSig:
    """A signature reduced to the Edwards-form check
    [8](s·B − k·A − R) == O — the common shape both key types share.
    ed25519: k = SHA-512(R ‖ A ‖ msg) mod L; sr25519: k is the Merlin
    transcript challenge and A/R are the ristretto coset representatives
    re-encoded in ed25519 compressed form."""

    __slots__ = ("a", "r", "s", "k")

    def __init__(self, a: bytes, r: bytes, s: int, k: int):
        self.a = a
        self.r = r
        self.s = s
        self.k = k


def resolve_ed25519(pub: bytes, msg: bytes, sig: bytes) -> ResolvedSig | None:
    """None = malformed (wrong sizes or non-canonical s ≥ L)."""
    if len(pub) != 32 or len(sig) != 64:
        return None
    r, s = sig[:32], sig[32:]
    s_int = int.from_bytes(s, "little")
    if s_int >= L:
        return None
    k = int.from_bytes(hashlib.sha512(r + pub + msg).digest(), "little") % L
    return ResolvedSig(pub, r, s_int, k)


def prepare_resolved(entries: list[ResolvedSig | None], pad_to: int = 0):
    """ResolvedSig list -> per-signature kernel inputs (None entries and
    padding rows stay invalid)."""
    n = len(entries)
    m = max(pad_to, n)
    a_np = np.zeros((m, 32), np.uint8)
    r_np = np.zeros((m, 32), np.uint8)
    s_np = np.zeros((m, 32), np.uint8)
    h_np = np.zeros((m, 32), np.uint8)
    s_valid = np.zeros(m, bool)
    for i, e in enumerate(entries):
        if e is None:
            continue
        s_valid[i] = True
        a_np[i] = np.frombuffer(e.a, np.uint8)
        r_np[i] = np.frombuffer(e.r, np.uint8)
        s_np[i] = np.frombuffer(e.s.to_bytes(32, "little"), np.uint8)
        h_np[i] = np.frombuffer(e.k.to_bytes(32, "little"), np.uint8)

    def to_digits(b: np.ndarray) -> np.ndarray:
        """(N,32) bytes -> (N,64) radix-16 little-endian digits."""
        d = np.empty((b.shape[0], 64), np.int32)
        d[:, 0::2] = b & 0xF
        d[:, 1::2] = b >> 4
        return d

    return (
        a_np.astype(np.int32),
        r_np.astype(np.int32),
        to_digits(s_np),
        to_digits(h_np),
        s_valid,
    )


def _group_bucket(g: int) -> int:
    """Pad the unique-key count so the A-side MSM length (G + 1 base-point
    row) lands on a power of two ≥ 64 — stable compile shapes, and the
    MSM's blocked prefix scan needs divisibility."""
    b = _MIN_BUCKET
    while b < g + 1:
        b *= 2
    return b - 1


def prepare_batch_eq(entries: list[ResolvedSig | None], pad_to: int = 0):
    """Host prep for the batch-equation kernel. pad_to ≥ len(entries)
    pads the signature axis with inert rows (digits 0, s_valid False);
    the unique-key axis is padded to a group bucket. Returns (ua_bytes,
    r_bytes, ga_digits, r_digits, zs_digits, s_valid, gidx) numpy arrays
    shaped for `_kernel_eq`."""
    import os as _os

    n = len(entries)
    m = max(pad_to, n)
    r_np = np.zeros((m, 32), np.uint8)
    r_sc = np.zeros((m, 16), np.uint8)  # z bytes
    s_valid = np.zeros(m, bool)
    gidx = np.zeros(m, np.int32)
    group_of: dict[bytes, int] = {}
    ua: list[bytes] = []
    coeffs: list[int] = []  # per-group Σ z·k mod L
    zs = 0
    rnd = _os.urandom(16 * n)
    for i, e in enumerate(entries):
        if e is None:
            continue
        gi = group_of.get(e.a)
        if gi is None:
            gi = group_of[e.a] = len(ua)
            ua.append(e.a)
            coeffs.append(0)
        gidx[i] = gi
        s_valid[i] = True
        r_np[i] = np.frombuffer(e.r, np.uint8)
        # z ∈ [1, 2^128): |1 excludes zero (a zero coefficient would drop
        # the signature from the equation entirely)
        z = int.from_bytes(rnd[16 * i : 16 * i + 16], "little") | 1
        r_sc[i] = np.frombuffer(z.to_bytes(16, "little"), np.uint8)
        # accumulate WITHOUT reducing: one mod per group at the end beats
        # a 384-bit modular reduction per signature
        coeffs[gi] += z * e.k
        zs += z * e.s
    gb = _group_bucket(len(ua))
    ua_np = np.zeros((gb, 32), np.uint8)
    ga_sc = np.zeros((gb, 32), np.uint8)
    for gi, (key, c) in enumerate(zip(ua, coeffs)):
        ua_np[gi] = np.frombuffer(key, np.uint8)
        ga_sc[gi] = np.frombuffer((c % L).to_bytes(32, "little"), np.uint8)
    zs_digits = np.frombuffer((zs % L).to_bytes(32, "little"), np.uint8).reshape(32, 1)
    return (
        ua_np,  # uint8 throughout: the kernel casts on-device, the
        r_np,  # host->device copy moves 4x fewer bytes
        np.ascontiguousarray(ga_sc.T),  # (32, gb)
        np.ascontiguousarray(r_sc.T),  # (16, m)
        zs_digits,
        s_valid,
        gidx,
    )
