"""The plain reference for a committee that mixes key types: what
`reference.py` says a commit and a quorum are, with every signature
verified under ITS OWN key's scheme.

It imports the standard library, `cryptography` and `reference.py` (its
canonical-vote encoder and `CommitData`: plain data, nothing of the
program), and nothing of tendermint_tpu. A public key says its scheme by
its length, as on the wire:

    32 bytes   ed25519    OpenSSL's Ed25519 verify (strict RFC 8032; equal
                          to ZIP-215 on honestly made and bit-flipped
                          signatures, which are all the benchmark makes)
    33 bytes   secp256k1  compressed SEC1 point; ECDSA over SHA-256 of the
                          sign-bytes, the signature 64 bytes r || s
                          big-endian, parsed by hand: 0 < r < n and
                          0 < s <= n/2 (the reference's low-S rule, written
                          out here — OpenSSL alone would take a high s)

`commit_verdict` keeps `reference.commit_verdict`'s first three answers
and adds the signatures checked by scheme, which is what tells a verifier
that skipped a lane, or ran one twice, from a sound one.
"""

from __future__ import annotations

import os
import pickle
import subprocess
import sys
from concurrent.futures import ThreadPoolExecutor
from fractions import Fraction

from cryptography.exceptions import InvalidSignature
from cryptography.hazmat.primitives import hashes
from cryptography.hazmat.primitives.asymmetric import ec
from cryptography.hazmat.primitives.asymmetric.ed25519 import Ed25519PublicKey
from cryptography.hazmat.primitives.asymmetric.utils import encode_dss_signature

from benchmark.reference import FLAG_COMMIT, QUORUM, CommitData, canonical_vote_bytes

ED25519 = "ed25519"
SECP256K1 = "secp256k1"
SCHEME_BY_KEY_SIZE = {32: ED25519, 33: SECP256K1}

#: the order of secp256k1's base point
SECP256K1_N = 0xFFFFFFFFFFFFFFFFFFFFFFFFFFFFFFFEBAAEDCE6AF48A03BBFD25E8CD0364141

_ECDSA_SHA256 = ec.ECDSA(hashes.SHA256())
_KEYS: dict[bytes, object] = {}


def scheme_of(raw_key: bytes) -> str:
    return SCHEME_BY_KEY_SIZE[len(raw_key)]


def _key(raw: bytes):
    """OpenSSL key objects, made once per public key."""
    k = _KEYS.get(raw)
    if k is None:
        if scheme_of(raw) == ED25519:
            k = Ed25519PublicKey.from_public_bytes(raw)
        else:
            k = ec.EllipticCurvePublicKey.from_encoded_point(ec.SECP256K1(), raw)
        _KEYS[raw] = k
    return k


def signature_ok(raw_key: bytes, msg: bytes, sig: bytes) -> bool:
    """One signature under its key's scheme."""
    try:
        if scheme_of(raw_key) == ED25519:
            _key(raw_key).verify(sig, msg)
            return True
        if len(sig) != 64:
            return False
        r = int.from_bytes(sig[:32], "big")
        s = int.from_bytes(sig[32:], "big")
        if not 0 < r < SECP256K1_N or not 0 < s <= SECP256K1_N // 2:
            return False
        _key(raw_key).verify(encode_dss_signature(r, s), msg, _ECDSA_SHA256)
        return True
    except (InvalidSignature, ValueError):
        return False


def commit_verdict(c: CommitData, quorum: Fraction = QUORUM) -> tuple[bool, int, int, dict]:
    """(accepted, signatures checked, index of the first bad one or -1,
    signatures checked by scheme). Light semantics, as
    `reference.commit_verdict`: the for-block signatures in index order,
    each one verified, until MORE than `quorum` of the total power has
    signed exactly this block ID."""
    needed = sum(c.powers) * quorum.numerator // quorum.denominator
    tallied = checked = 0
    by_scheme = {ED25519: 0, SECP256K1: 0}
    for idx, (flag, ts, sig) in enumerate(c.sigs):
        if flag != FLAG_COMMIT:
            continue
        msg = canonical_vote_bytes(
            c.chain_id, c.height, c.round, c.block_hash, c.parts_total,
            c.parts_hash, ts,
        )
        checked += 1
        by_scheme[scheme_of(c.pubkeys[idx])] += 1
        if not signature_ok(c.pubkeys[idx], msg, sig):
            return False, checked, idx, by_scheme
        tallied += c.powers[idx]
        if tallied > needed:
            return True, checked, -1, by_scheme
    return False, checked, -1, by_scheme


def commit_verdicts(commits: list[CommitData], quorum: Fraction = QUORUM,
                    workers: int = 8) -> list[tuple[bool, int, int, dict]]:
    """commit_verdict over many commits, shared out to a few child
    processes as `reference.commit_verdicts` does (the OpenSSL binding
    holds the GIL): each runs THIS module (`-m`, from the checkout's root,
    so that `benchmark.reference` is found) and imports the standard
    library and `cryptography` alone, never jax. All of them have ended
    when this returns; if one fails, the work is done here instead."""
    workers = min(workers, os.cpu_count() or 1, len(commits) // 16)
    if workers <= 1:
        return [commit_verdict(c, quorum) for c in commits]
    step = -(-len(commits) // workers)
    jobs = [pickle.dumps(([tuple(c.__dict__.values()) for c in commits[i:i + step]], quorum))
            for i in range(0, len(commits), step)]
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    procs = [subprocess.Popen([sys.executable, "-m", "benchmark.reference_mixed"], cwd=root,
                              stdin=subprocess.PIPE, stdout=subprocess.PIPE)
             for _ in jobs]
    try:
        with ThreadPoolExecutor(max_workers=len(procs)) as pool:
            outs = list(pool.map(lambda pj: pj[0].communicate(pj[1], timeout=600)[0],
                                 zip(procs, jobs)))
        if any(p.returncode != 0 for p in procs):
            raise RuntimeError("a reference worker failed")
        return [v for out in outs for v in pickle.loads(out)]
    except (OSError, RuntimeError, subprocess.SubprocessError, pickle.PickleError, EOFError):
        return [commit_verdict(c, quorum) for c in commits]
    finally:
        for p in procs:
            if p.poll() is None:
                p.kill()
            p.wait()


def _worker() -> None:
    """A child of commit_verdicts: one pickled (commits, quorum) on stdin,
    the pickled verdicts on stdout. Only bytes this program wrote are
    unpickled."""
    rows, quorum = pickle.loads(sys.stdin.buffer.read())
    sys.stdout.buffer.write(
        pickle.dumps([commit_verdict(CommitData(*row), quorum) for row in rows]))
    sys.stdout.buffer.flush()


if __name__ == "__main__":
    _worker()
