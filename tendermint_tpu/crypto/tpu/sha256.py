"""Batched SHA-256 on the JAX backend — the HashHub's device kernel.

One kernel call hashes a whole bucket of independent messages: the
compression function is pure 32-bit bitwise/add arithmetic, so it
vectorizes over the batch axis on the VPU the same way the ed25519
batch-equation kernel vectorizes group arithmetic (PAPERS.md
arXiv:2407.03511 measures exactly this formulation; zkSpeed makes the
same batched-hash bet for Poseidon). Merkle work is naturally uniform —
`0x01||left||right` inner nodes are 65 bytes (2 blocks) and leaf
messages cluster by size — which is what makes fixed-shape buckets pay.

Shape discipline (same as tpu/verify): a
kernel call is keyed by (block_bucket, batch_bucket) — messages are
host-padded to a power-of-two block count and the batch to the bucket
ladder, so the set of XLA compilations is small and rides the
persistent compile cache. Mixed block counts inside one call are
handled with a per-message active mask (a message stops absorbing
blocks once its padded length is consumed), so a bucket never splits
by exact size.

All arithmetic is uint32 — no 64-bit emulation anywhere on the TPU
path (the message bit-length is the only 64-bit quantity and it is
composed from two 32-bit words on the host).

Routing is opt-in (TMTPU_HASH_TPU=1) exactly like the BLS pairing
kernel: host OpenSSL SHA-256 is extremely fast per call, so the device
only wins on wide batches and the cold compile must never be paid
implicitly on a CPU image. crypto/hash_hub owns the breaker and the
hashlib fallback; this module just computes or raises.
"""

from __future__ import annotations

import os
import threading

import numpy as np

#: bucket ladder for the batch axis (messages per kernel call)
_MIN_BUCKET = 16
_MAX_BUCKET = 4096
#: largest padded block count the kernel unrolls (8 blocks = 512 bytes
#: of padded message, i.e. host messages up to 503 bytes). Longer
#: messages (64 KiB block parts) are bandwidth-bound single hashes —
#: the host path keeps them.
_MAX_BLOCKS = 8

_H0 = (
    0x6A09E667, 0xBB67AE85, 0x3C6EF372, 0xA54FF53A,
    0x510E527F, 0x9B05688C, 0x1F83D9AB, 0x5BE0CD19,
)
_K = (
    0x428A2F98, 0x71374491, 0xB5C0FBCF, 0xE9B5DBA5, 0x3956C25B, 0x59F111F1,
    0x923F82A4, 0xAB1C5ED5, 0xD807AA98, 0x12835B01, 0x243185BE, 0x550C7DC3,
    0x72BE5D74, 0x80DEB1FE, 0x9BDC06A7, 0xC19BF174, 0xE49B69C1, 0xEFBE4786,
    0x0FC19DC6, 0x240CA1CC, 0x2DE92C6F, 0x4A7484AA, 0x5CB0A9DC, 0x76F988DA,
    0x983E5152, 0xA831C66D, 0xB00327C8, 0xBF597FC7, 0xC6E00BF3, 0xD5A79147,
    0x06CA6351, 0x14292967, 0x27B70A85, 0x2E1B2138, 0x4D2C6DFC, 0x53380D13,
    0x650A7354, 0x766A0ABB, 0x81C2C92E, 0x92722C85, 0xA2BFE8A1, 0xA81A664B,
    0xC24B8B70, 0xC76C51A3, 0xD192E819, 0xD6990624, 0xF40E3585, 0x106AA070,
    0x19A4C116, 0x1E376C08, 0x2748774C, 0x34B0BCB5, 0x391C0CB3, 0x4ED8AA4A,
    0x5B9CCA4F, 0x682E6FF3, 0x748F82EE, 0x78A5636F, 0x84C87814, 0x8CC70208,
    0x90BEFFFA, 0xA4506CEB, 0xBEF9A3F7, 0xC67178F2,
)

#: (block_bucket, batch_bucket) -> jitted kernel; exact-shape keyed so
#: every call after the first is a cache hit (persistent XLA cache
#: makes the first one cheap across processes too)
_kernels: dict[tuple[int, int], object] = {}
_kernels_lock = threading.Lock()


def device_enabled() -> bool:
    """The SHA-256 device path is opt-in (see module docstring)."""
    return os.environ.get("TMTPU_HASH_TPU") == "1"


def max_device_bytes() -> int:
    """Largest message the kernel accepts (padding included in the
    _MAX_BLOCKS unroll): 64*_MAX_BLOCKS bytes minus the 0x80 terminator
    and the 8-byte length word."""
    return 64 * _MAX_BLOCKS - 9


def batch_bucket(n: int) -> int:
    """Power-of-two batch bucket (the tpu/verify ladder shape)."""
    b = _MIN_BUCKET
    while b < n:
        b *= 2
    return min(b, _MAX_BUCKET)


def block_bucket(nblocks: int) -> int:
    """Power-of-two padded-block bucket, capped at _MAX_BLOCKS."""
    b = 1
    while b < nblocks:
        b *= 2
    return b


def _padded_blocks(length: int) -> int:
    """Blocks the standard SHA-256 padding of a `length`-byte message
    occupies (0x80 terminator + 64-bit big-endian bit length)."""
    return (length + 8) // 64 + 1


def _make_kernel(t_bucket: int):
    """Build the jitted batch kernel for one block bucket. The batch
    axis stays dynamic to JAX but calls are always bucket-padded, so
    XLA sees one shape per (t_bucket, batch_bucket) pair."""
    import jax
    import jax.numpy as jnp

    k_consts = jnp.asarray(_K, jnp.uint32)

    def rotr(x, n):
        return (x >> np.uint32(n)) | (x << np.uint32(32 - n))

    def compress(state, w16):
        # The 48 schedule steps and the 64 rounds are ROLLED loops, not
        # unrolled Python: every round's eight words feed several later
        # expressions, and as one straight-line graph XLA's fusion
        # re-derives shared producers per consumer — on the CPU backend
        # of JAX 0.9.0 the compiled program then runs effectively
        # forever (the compile itself takes seconds). A loop carry is
        # materialized once per iteration, which bounds the work.
        batch = w16.shape[0]
        w0 = jnp.zeros((64, batch), jnp.uint32).at[:16].set(w16.T)

        def sched(j, w):
            w15, w2 = w[j - 15], w[j - 2]
            s0 = rotr(w15, 7) ^ rotr(w15, 18) ^ (w15 >> np.uint32(3))
            s1 = rotr(w2, 17) ^ rotr(w2, 19) ^ (w2 >> np.uint32(10))
            return w.at[j].set(w[j - 16] + s0 + w[j - 7] + s1)

        w = jax.lax.fori_loop(16, 64, sched, w0)

        def round_(j, v):
            a, b, c, d, e, f, g, h = v
            s1 = rotr(e, 6) ^ rotr(e, 11) ^ rotr(e, 25)
            ch = (e & f) ^ (~e & g)
            t1 = h + s1 + ch + k_consts[j] + w[j]
            s0 = rotr(a, 2) ^ rotr(a, 13) ^ rotr(a, 22)
            maj = (a & b) ^ (a & c) ^ (b & c)
            t2 = s0 + maj
            return (t1 + t2, a, b, c, d + t1, e, f, g)

        v = jax.lax.fori_loop(
            0, 64, round_, tuple(state[:, i] for i in range(8))
        )
        return jnp.stack([v[i] + state[:, i] for i in range(8)], axis=1)

    def kernel(blocks, nblk):
        # blocks: (batch, t_bucket, 16) uint32; nblk: (batch,) uint32.
        # A message absorbs block t only while t < its padded block
        # count — the mask is what lets one bucket mix message sizes.
        batch = blocks.shape[0]
        state = jnp.broadcast_to(
            jnp.asarray(_H0, jnp.uint32), (batch, 8)
        )
        for t in range(t_bucket):
            new = compress(state, blocks[:, t, :])
            state = jnp.where((nblk > np.uint32(t))[:, None], new, state)
        return state

    return jax.jit(kernel)


def _get_kernel(t_bucket: int, b_bucket: int):
    with _kernels_lock:
        fn = _kernels.get((t_bucket, b_bucket))
        if fn is None:
            from .verify import _ensure_compile_cache

            _ensure_compile_cache()
            fn = _make_kernel(t_bucket)
            _kernels[(t_bucket, b_bucket)] = fn
        return fn


def prepare_hash_batch(
    msgs: list[bytes], *, pad_to: int, block_pad: int
) -> tuple[np.ndarray, np.ndarray]:
    """Host prep: pack messages (standard SHA-256 padding applied) into
    a (pad_to, block_pad, 16) big-endian uint32 word array plus the
    per-message padded block counts. Both pads must be bucket shapes —
    the dispatch core asserts, same discipline as prepare_batch_eq."""
    raw = np.zeros((pad_to, block_pad * 64), np.uint8)
    nblk = np.zeros((pad_to,), np.uint32)
    for i, m in enumerate(msgs):
        length = len(m)
        nb = _padded_blocks(length)
        end = nb * 64
        if length:
            raw[i, :length] = np.frombuffer(m, np.uint8)
        raw[i, length] = 0x80
        raw[i, end - 8 : end] = np.frombuffer(
            (length * 8).to_bytes(8, "big"), np.uint8
        )
        nblk[i] = nb
    words = raw.reshape(pad_to, block_pad, 16, 4).astype(np.uint32)
    packed = (
        (words[..., 0] << 24) | (words[..., 1] << 16)
        | (words[..., 2] << 8) | words[..., 3]
    )
    return packed, nblk


def sha256_device(msgs: list[bytes]) -> list[bytes]:
    """Hash every message in one (or a few) bucket-shaped kernel calls.

    Raises on any backend/kernel error — the HashHub wraps this in the
    shared breaker and re-hashes on the host, so callers never see a
    device failure. Messages longer than `max_device_bytes()` are a
    caller bug (the hub routes those to the host before dispatch)."""
    import time as _time

    if not msgs:
        return []
    limit = max_device_bytes()
    nb_max = 1
    for m in msgs:
        if len(m) > limit:
            raise ValueError(
                f"message of {len(m)} bytes exceeds the device unroll "
                f"({limit} bytes) — host path required"
            )
        nb = _padded_blocks(len(m))
        if nb > nb_max:
            nb_max = nb
    t_bucket = block_bucket(nb_max)
    out: list[bytes] = []
    for lo in range(0, len(msgs), _MAX_BUCKET):
        chunk = msgs[lo : lo + _MAX_BUCKET]
        b_bucket = batch_bucket(len(chunk))
        assert b_bucket >= len(chunk) and b_bucket & (b_bucket - 1) == 0
        key = (t_bucket, b_bucket)
        cold = key not in _kernels
        fn = _get_kernel(t_bucket, b_bucket)
        packed, nblk = prepare_hash_batch(
            chunk, pad_to=b_bucket, block_pad=t_bucket
        )
        t0 = _time.monotonic()
        state = np.asarray(fn(packed, nblk))
        if cold:
            # classify the first-call compile against the persistent
            # cache, same telemetry the verify kernels feed
            from .. import backend_telemetry as bt

            bt.record_compile(
                f"sha256-{t_bucket}x{b_bucket}", _time.monotonic() - t0
            )
        digests = state[: len(chunk)].astype(">u4").tobytes()
        out.extend(
            digests[i * 32 : (i + 1) * 32] for i in range(len(chunk))
        )
    return out


def warmup(*, blocks: int = 2, batch: int = _MIN_BUCKET) -> None:
    """Compile the given bucket shape ahead of use (the hub's probe
    calls this so the first real dispatch is warm)."""
    sha256_device([b"\x01" * 65] * min(batch, _MAX_BUCKET))
    if blocks != 2:
        n = min(blocks, _MAX_BLOCKS) * 64 - 9
        sha256_device([b"\x02" * n] * min(batch, _MAX_BUCKET))
