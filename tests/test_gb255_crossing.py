"""A dispatch's distinct keys pick its program (`crypto/tpu/verify.py`
`_group_bucket`): up to 63, 127 or 255 key rows beside the base point. A
committee of 150 whose set changes keeps a <= 512-row hub dispatch at 101-103
distinct keys (gb127, the static cell's shape); the crossing needs MORE THAN
127 distinct keys in ONE dispatch. Here it is crossed: 130 keys, one
signature each, go out at bucket 256 / gb255 — and the kernel answers as the
host verifier does, on honest signatures and on a flipped bit."""

import numpy as np

from tendermint_tpu.crypto.ed25519 import Ed25519PubKey
from tendermint_tpu.crypto.tpu import verify as V
from tendermint_tpu.testing import det_priv_keys


def test_more_than_127_distinct_keys_take_gb255_and_answer_as_the_host_does():
    assert [V._group_bucket(g) for g in (63, 64, 101, 103, 127, 128, 150)] == [
        63, 127, 127, 127, 127, 255, 255]
    keys = det_priv_keys(130, seed=b"gb255")
    items = [(k.pub_key().bytes(), b"vote-%d" % i, k.sign(b"vote-%d" % i))
             for i, k in enumerate(keys)]
    p, m, s = items[77]
    items[77] = (p, m, s[:7] + bytes([s[7] ^ 0x10]) + s[8:])
    rows = V.resolve_rows(items)
    operands = V.prepare_batch_eq(rows, pad_to=V._bucket(len(rows)))
    assert operands[0].shape == (255, 32) and operands[1].shape == (256, 32)
    host = [Ed25519PubKey(p).verify_signature(m, s) for p, m, s in items]
    assert host.count(False) == 1 and host[77] is False
    assert np.asarray(V.verify_batch_eq(items)).tolist() == host
