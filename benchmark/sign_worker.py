#!/usr/bin/env python3
"""One worker of `fixtures_mixedfull.SignerPool`: a process that holds
private keys and signs what it is sent, so that a commit's rows are signed on
several cores (`cryptography`'s sign keeps the GIL from end to end: a pool of
threads signs no faster than one).

stdin and stdout carry `harness.write_part`'s framing (eight bytes of length,
then a pickle). The first part is a list of (key type, private key bytes),
ed25519 or secp256k1; every later one a list of (key index, sign-bytes),
answered by the list of signatures in that order, each the program's own
`Ed25519PrivKey.sign` / `Secp256k1PrivKey.sign`.
The worker ends at the end of its stdin — which comes when the process that
started it closes the pipe OR dies, however it dies: nobody else holds the
writing end.

    python3 benchmark/sign_worker.py   (started by SignerPool alone)
"""

from __future__ import annotations

import os
import pickle
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from benchmark.harness import PART_HEADER, write_part  # noqa: E402


def read_part(inp):
    """The next part of `inp` (a buffered pipe), unpickled; None at its end."""
    head = inp.read(PART_HEADER)
    if len(head) < PART_HEADER:
        return None
    # only bytes the process at the pipe's other end wrote are unpickled: the
    # pool that started this worker, or a worker the pool started
    return pickle.loads(inp.read(int.from_bytes(head, "big")))


def main() -> int:
    from tendermint_tpu.crypto.ed25519 import Ed25519PrivKey
    from tendermint_tpu.crypto.secp256k1 import Secp256k1PrivKey

    inp, out = sys.stdin.buffer, sys.stdout.buffer
    sys.stdout = sys.stderr  # nothing but the answers on the pipe
    by_type = {k.TYPE: k for k in (Ed25519PrivKey, Secp256k1PrivKey)}
    keys = [by_type[key_type](data) for key_type, data in read_part(inp)]
    while (rows := read_part(inp)) is not None:
        write_part(out, [keys[i].sign(msg) for i, msg in rows])
    return 0


if __name__ == "__main__":
    sys.exit(main())
