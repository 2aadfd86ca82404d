"""Batched ed25519 verification: host preparation + the JAX kernel + the
`BatchVerifier` implementation that plugs into crypto/batch.py.

Pipeline (mirrors the reference's split of responsibilities in
types/validation.go:152 — sign-bytes stay host-side, group math is the
kernel):

  host:   parse signatures, canonical-range-check s < L, hash
          k = SHA-512(R ‖ A ‖ msg) (one Python pass a signature,
          `resolve_rows`), fold the batch equation's scalars and unpack
          them to digits (`prepare_batch_eq`, column-wise) — a chunk at a
          time, inside the dispatch loop
  device: decompress A and R, joint double-scalar mult s·B - k·A,
          cofactored identity check  [8](s·B - k·A - R) == O
  host:   per-signature validity bitmap (the `[]bool` of the reference's
          BatchVerifier.Verify, crypto/crypto.go:53)

Batches are padded to power-of-two buckets (floor 64) so XLA compiles a
handful of shapes; multi-chip runs shard the batch axis over a Mesh data
axis — verification is pure data parallelism, so the only collective is the
implicit all-gather of the validity bitmap.
"""

from __future__ import annotations

import hashlib
import os
import threading
from contextlib import contextmanager
from functools import partial
from math import gcd as _gcd
from typing import NamedTuple

import numpy as np

from ...libs import trace
from .. import BatchVerifier, PubKey

L = 2**252 + 27742317777372353535851937790883648493

_MIN_BUCKET = 64


def _install_trace_annotator() -> None:
    """Put the flight recorder's spans on the profiler's clock
    (libs/trace.set_annotator): from here on an entered span also
    enters a `jax.profiler.TraceAnnotation` called
    `tm.<subsystem>.<name>`. Called where this module has loaded jax
    anyway — libs/trace itself never imports it."""
    if not trace.annotator_installed():
        import jax

        trace.set_annotator(jax.profiler.TraceAnnotation)


def backend_ready() -> bool:
    try:
        import jax

        ready = len(jax.devices()) > 0
    except Exception:
        return False
    _install_trace_annotator()
    return ready


def _kernel(a_bytes, r_bytes, s_digits, h_digits, s_valid):
    """The device computation. All inputs int32; shapes:
    a_bytes/r_bytes (B,32), s_digits/h_digits (B,64) radix-16 little-endian
    digits, s_valid (B,) bool.

    A and R are decompressed in ONE stacked call (batch 2B): the square
    root is a ~254-multiply dependency chain, so halving the number of
    decompress instances both shrinks the graph and doubles the SIMD
    width through the longest serial section."""
    import jax
    import jax.numpy as jnp

    from . import curve

    # named scopes are metadata on the compiled program's operations (a
    # device trace reads the kernel by phase); the program is the same
    stacked, ok = curve.decompress(jnp.concatenate([a_bytes, r_bytes], axis=0))
    n = a_bytes.shape[0]
    A = curve.Point(*(c[:n] for c in stacked))
    R = curve.Point(*(c[n:] for c in stacked))
    a_ok, r_ok = ok[:n], ok[n:]
    with jax.named_scope("scalar_mul"):
        v = curve.scalar_mul_double(s_digits, h_digits, curve.point_neg(A))  # sB - kA
    with jax.named_scope("finish"):
        w = curve.point_add(v, curve.point_neg(R))  # sB - kA - R
        eq_ok = curve.is_identity(curve.mul_by_cofactor(w))
        return a_ok & r_ok & eq_ok & s_valid


def _kernel_eq(ua_bytes, r_bytes, ga_digits, r_digits, zs_digits, s_valid, gidx):
    """Randomized linear-combination batch verification (the reference's
    actual batch algorithm, crypto/ed25519/ed25519.go:225 via
    curve25519-voi): ONE multi-scalar multiplication

        [8]( zs·B − Σ_g c_g·A_g − Σ zᵢ·Rᵢ ) == O

    with zs = Σ zᵢ·sᵢ mod L and zᵢ random 128-bit coefficients sampled
    per call on the host. Scalars on A and R may be reduced mod L even
    though those points can carry torsion (ZIP-215): the final ×8 kills
    every torsion component, so only the prime-order part — where mod-L
    reduction is exact — survives.

    A-side GROUPING: consensus batches repeat public keys (150 validators
    sign every one of dozens of block-sync commits), and the equation is
    linear in the points — so the host collapses Σᵢ zᵢkᵢ·Aᵢ to
    Σ_g c_g·A_g with c_g = Σ_{i: Aᵢ=A_g} zᵢkᵢ mod L over the G unique
    keys. The 32-window A-side MSM then runs over G+1 rows instead of N
    (54 commits × 150 validators: 8100 → 151), and only G unique keys are
    decompressed. Worst case (all keys distinct) degrades to exactly the
    ungrouped shape.

    Inputs: ua_bytes (G,32) unique compressed keys; r_bytes (N,32);
    ga_digits (32,G) radix-256 digits of c_g; r_digits (16,N) digits of
    zᵢ; zs_digits (32,1); s_valid (N,) bool (s < L, well-formed);
    gidx (N,) int32 mapping each signature to its key group.
    Format-invalid entries arrive with zeroed digits; decompression
    failures are masked to the identity in-kernel, so neither perturbs
    the sum. Returns (ok_bitmap (N,), eq_ok ()): on eq_ok the bitmap IS
    the per-signature answer; on failure the caller falls back to the
    per-signature kernel for attribution (historical block-sync batches
    are ~always all-valid, so the one-MSM happy path dominates).
    """
    import jax
    import jax.numpy as jnp

    from . import curve, msm
    from .curve import Point

    # operands arrive as uint8 (host->device transfer is 4x smaller);
    # all arithmetic runs in int32
    ua_bytes, r_bytes, ga_digits, r_digits, zs_digits = (
        x.astype(jnp.int32)
        for x in (ua_bytes, r_bytes, ga_digits, r_digits, zs_digits)
    )
    g = ua_bytes.shape[0]
    stacked, ok = curve.decompress(jnp.concatenate([ua_bytes, r_bytes], axis=0))
    A = Point(*(c[:g] for c in stacked))
    R = Point(*(c[g:] for c in stacked))
    a_ok, r_ok = ok[:g], ok[g:]
    r_use = r_ok & s_valid
    ok_bitmap = jnp.take(a_ok, gidx) & r_use

    Am = curve.point_select(a_ok, curve.point_neg(A), curve.identity((g,)))
    Rm = curve.point_select(
        r_use, curve.point_neg(R), curve.identity((r_bytes.shape[0],))
    )

    # A-group MSM carries the base point as one extra row (scalar zs)
    bpt = curve.base_point(())
    ga = Point(
        *(jnp.concatenate([c, b[None]], axis=0) for c, b in zip(Am, bpt))
    )
    ga_digits = jnp.concatenate([ga_digits, zs_digits], axis=1)

    # the kernel's phases by name (decompress is named in curve.py): a
    # device trace lays its time to them; metadata only, same program
    with jax.named_scope("msm_keys"):
        keys_sum = msm.msm(ga, ga_digits)
    with jax.named_scope("msm_sigs"):
        sigs_sum = msm.msm(Rm, r_digits)
    with jax.named_scope("finish"):
        acc = curve.point_add(keys_sum, sigs_sum)
        eq_ok = curve.is_identity(curve.mul_by_cofactor(acc))
    return ok_bitmap, eq_ok


_jitted_kernel = None
_jitted_kernel_eq = None
#: device-id tuple -> (sharded eq kernel, sharded per-sig kernel). Keyed
#: by the EXACT device set, not the count: after a per-device breaker
#: trip the surviving mesh is a different set of chips and must not
#: reuse a kernel pinned to the dead one.
_sharded_kernels: dict[tuple, object] = {}
_cache_ready = False


#: The one place this checkout persists XLA compilations when nothing
#: outside placed the cache: fixed (the directory is part of the cache
#: key — a moved path never hits) and inside the checkout (never ~, a
#: temp name, a pid or a time). Listed in .gitignore.
COMPILE_CACHE_DIR = os.path.join(
    os.path.dirname(
        os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
    ),
    ".jax_cache",
)


def _ensure_compile_cache() -> None:
    """Persist XLA compilations to disk — the verification kernels cost
    seconds (CPU) to minutes (TPU) to compile per batch bucket; the cache
    makes that a one-time cost across processes — and, behind it, choose
    the kernels' formulation (`_choose_formulation`: on a TPU each Pallas
    kernel proven once against the host's known answer). Every way to a
    jitted kernel passes here first, so no production program is traced
    before that proof is over.

    Placement: when JAX_COMPILATION_CACHE_DIR is set, JAX already reads
    it and this code sets NO directory at all; otherwise the fixed
    in-checkout COMPILE_CACHE_DIR. A directory that cannot be created
    raises — a cold multi-minute start every time is not a default to
    slide into silently."""
    global _cache_ready
    if _cache_ready:
        return
    import jax

    _install_trace_annotator()
    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        os.makedirs(COMPILE_CACHE_DIR, exist_ok=True)
        jax.config.update("jax_compilation_cache_dir", COMPILE_CACHE_DIR)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 1.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    _choose_formulation()
    _cache_ready = True


#: Filled by _choose_formulation on a TPU: `chosen` ("pallas" | "xla"), so
#: a run records which family of programs it traces. A Pallas kernel that
#: failed to compile or gave a wrong answer leaves `error` (multiply /
#: power stage) or `scan_error` here AND counts in
#: backend_telemetry.BACKEND["pallas_probe_errors"] — chip_smoke.py
#: refuses a run that has either.
field_mul_probe: dict = {}

#: windows of the self-test's MSM: two, so that the vmap batching rule
#: hands the fused scan a real batch axis (production runs 16 and 32)
_SELF_TEST_WINDOWS = 2

#: the self-test's stages in the order they are proven, each with the key
#: of `field_mul_probe` its failure is recorded under
_SELF_TEST_STAGES = (
    ("mul", "error"), ("pow22523", "error"), ("scan_blocks", "scan_error")
)


def _probe_failed(key: str, e: Exception) -> None:
    """A Pallas kernel failed its self-test on a real TPU. The XLA
    formulations are still a device path, so production keeps going on
    them — but loudly: recorded, counted, WARNING."""
    import logging

    from .. import backend_telemetry as bt

    field_mul_probe.setdefault(key, repr(e))
    bt.BACKEND["pallas_probe_errors"] += 1
    logging.getLogger("crypto.tpu").warning(
        "pallas self-test stage %r failed on the TPU (%r); every kernel "
        "stays on its XLA formulation", key, e,
    )


def _choose_formulation() -> None:
    """The one decision of which formulation the kernels are traced in,
    made once from the platform: on a TPU the Pallas kernels (VMEM field
    multiply, fused pow22523, fused in-block MSM scan — field.set_pallas),
    anywhere else the portable XLA ones, with nothing run.

    Before anything trusts the Pallas kernels — before any production
    program is traced, and so before the probe can call the device
    available — each is proven once in this process against a KNOWN
    ANSWER the host computes over Python integers (`_known_answer`): one
    small device program a kernel, in the Pallas formulation only, no XLA
    twin beside it (a kernel Mosaic refuses raises here, not in the first
    commit; a fault the two formulations shared would have passed a twin
    and does not pass the integers). ANY failure puts the one switch back
    off, skips the stages after it and is loud (`_probe_failed`): the
    all-XLA family, which every CPU test run exercises — never a mixed
    one. Nothing is timed, nothing is kept on disk, no stage is skipped
    on any start."""
    import jax

    from . import field as F

    if jax.default_backend() != "tpu":
        return
    # benchmark/metrics/setup_probe_ab_s.py reads this span by name;
    # `programs` = device programs the proof traced, `stages` = kernels
    # proven (3 and 3 on a sound chip)
    with trace.span("backend", "pallas_ab") as sp:
        F.set_pallas(True)
        programs = stages = 0
        for name, key in _SELF_TEST_STAGES:
            try:
                case = _known_answer(name)  # host work only
                programs += 1
                case.check(np.asarray(jax.jit(case.program)(*case.operands)))
            except Exception as e:  # noqa: BLE001 — surfaced by _probe_failed
                _probe_failed(key, e)
                F.set_pallas(False)
                break
            stages += 1
        proven = stages == len(_SELF_TEST_STAGES)
        field_mul_probe["chosen"] = "pallas" if proven else "xla"
        sp.set(chosen=field_mul_probe["chosen"], programs=programs, stages=stages)


class _KnownAnswer(NamedTuple):
    """One stage of the self-test: ONE device program over `operands`
    (numpy), traced in whatever formulation the switch then selects, and
    the host's judgement of what it returned — `check(out)` raises on a
    wrong answer."""

    program: object
    operands: tuple
    check: object


def _limb_ints(name: str, got: np.ndarray) -> list[int]:
    """Limb rows from the device, (..., 32) -> the integers mod p they
    represent, in C order. Holds the module invariant of field.py on the
    way (every limb in [0, 2^9)): the next multiply's exactness rests on
    it, and no comparison of reduced values would see it. Host integers
    only: each limb's low byte and its carry bit are read as two
    little-endian numbers."""
    from .field import LIMBS, P_INT

    if got.shape[-1] != LIMBS or got.min() < 0 or got.max() >= 512:
        raise RuntimeError(f"pallas {name}: shape {got.shape} or a limb outside [0, 2^9)")
    lo, hi = (got & 0xFF).astype(np.uint8).tobytes(), (got >> 8).astype(np.uint8).tobytes()
    from_bytes = int.from_bytes
    return [
        (from_bytes(lo[i : i + LIMBS], "little") + (from_bytes(hi[i : i + LIMBS], "little") << 8))
        % P_INT
        for i in range(0, len(lo), LIMBS)
    ]


def _check_limbs(name: str, want: list[int], got: np.ndarray) -> None:
    """`got` must represent the integers `want`, row by row."""
    ints = _limb_ints(name, got)
    bad = [i for i, (g, w) in enumerate(zip(ints, want)) if g != w]
    if bad or len(ints) != len(want):
        raise RuntimeError(
            f"pallas {name} mismatch against the host's integers in "
            f"{len(bad)} of {len(want)} rows (first: {bad[:1]}; {len(ints)} came back)"
        )


def _known_answer(name: str) -> _KnownAnswer:
    """The three readers of the switch — field.mul, field.pow22523 and
    msm._block_prefixes — each on the smallest operand that takes the
    production path, with the answer the HOST computes for it over Python
    integers (exact, and independent of the limb code, the carry passes
    and `field.canonical`, which both device formulations share).

    `mul`, `pow22523`: lane tiles of limb rows over the whole range the
    module invariant admits ([0, 2^9); row 0 every limb at the bound, and
    for the power rows of 0 and 1): a·b mod p and z^(2^252 − 3) mod p.
    The multiply takes one tile plainly (decompression's call) and two
    under vmap (the batching rule every multiply of the MSM's windows goes
    through); the power is only ever called plainly.

    `scan_blocks`: the in-block prefix scan under vmap over two windows
    (production: 16 and 32), each of TILE blocks — the `g % TILE` gate of
    msm._block_prefixes. Its operands are REAL curve points, consecutive
    multiples n·B by repeated host addition (extended coordinates, Z ≠ 1;
    the cached form (Y−X, Y+X, 2dT, 2Z) in integers too), and the answer
    is every one of the windows' 2·TILE·_BLOCK prefixes by the host's own
    addition, compared projectively with the extended coordinate's
    relation T·Z = X·Y. On the curve the group law fixes each prefix
    whatever the formulas; random limb "points" (the twins' operand) only
    ever worked twin against twin. NOT the MSM around the scan: the sort,
    the boundary gather, the tree and the fold are the same XLA code in
    both families and every CPU test runs them, while lowering them costs
    six times the scan's own program at every start (PERF.md §6, PR 36).

    The program is a fresh lambda a call, so it traces anew in the
    formulation of the moment."""
    import jax
    import jax.numpy as jnp

    from .. import ed25519_math as em
    from . import field as F
    from . import msm as msm_mod
    from . import pallas_field
    from .curve import CachedPoint, Point

    rng = np.random.default_rng(0)
    tile, p = pallas_field.TILE, F.P_INT

    def limb_rows():
        rows = rng.integers(0, 512, (3, tile, F.LIMBS), dtype=np.int32)
        rows[:, 0] = 511
        return rows

    if name == "mul":
        a, b = limb_rows(), limb_rows()
        want = [x * y % p for x, y in zip(_limb_ints(name, a), _limb_ints(name, b))]

        def program(x, y):
            plain = F.mul(x[0], y[0])
            return jnp.concatenate([plain[None], jax.vmap(F.mul)(x[1:], y[1:])])

        return _KnownAnswer(program, (a, b), partial(_check_limbs, name, want))
    if name == "pow22523":
        z = limb_rows()[0]
        z[1], z[2] = 0, F.ONE
        want = [pow(v, 2**252 - 3, p) for v in _limb_ints(name, z)]
        return _KnownAnswer(lambda x: F.pow22523(x), (z,), partial(_check_limbs, name, want))
    assert name == "scan_blocks", name

    block, windows = msm_mod._BLOCK, _SELF_TEST_WINDOWS
    pts = [em.scalar_mul_base(7)]
    for _ in range(windows * tile * block - 1):
        pts.append(pts[-1].add(em.BASE))
    want = []  # (window, block, position): the prefix inside the block
    for i, pt in enumerate(pts):
        want.append(pt if i % block == 0 else want[-1].add(pt))

    def limbs(values, *shape):
        raw = b"".join(v.to_bytes(32, "little") for v in values)
        return np.frombuffer(raw, np.uint8).reshape(*shape, F.LIMBS).astype(np.int32)

    grid = (windows, tile, block)
    first = tuple(limbs([getattr(pt, c) for pt in pts], *grid)[:, :, 0] for c in "XYZT")
    cached = (
        [(pt.Y - pt.X) % p for pt in pts], [(pt.Y + pt.X) % p for pt in pts],
        [2 * F.D_INT * pt.T % p for pt in pts], [2 * pt.Z % p for pt in pts],
    )  # curve.to_cached, in integers
    rest = tuple(np.moveaxis(limbs(c, *grid)[:, :, 1:], 2, 1) for c in cached)

    def check(got: np.ndarray) -> None:
        n = tile * block
        if got.shape != (windows, 4, tile, block, F.LIMBS):
            raise RuntimeError(f"pallas {name}: shape {got.shape}")
        ints = _limb_ints(name, got)
        bad = 0
        for w in range(windows):
            coords = (ints[(4 * w + c) * n : (4 * w + c + 1) * n] for c in range(4))
            for x, y, z, t, h in zip(*coords, want[w * n : (w + 1) * n]):
                bad += z == 0 or (t * z - x * y) % p != 0 or not h.equals(em.Point(x, y, z, t))
        if bad:
            raise RuntimeError(
                f"pallas {name} mismatch: {bad} of {windows * n} in-block prefixes "
                "are not the host's sums of the same multiples of the base point"
            )

    def scan(f, r):
        return msm_mod._block_prefixes(Point(*f), CachedPoint(*r))[0]

    def program(f, r):
        return jnp.stack(jax.vmap(scan)(f, r), axis=1)

    return _KnownAnswer(program, (first, rest), check)


def _get_kernel():
    global _jitted_kernel
    if _jitted_kernel is None:
        import jax

        _ensure_compile_cache()
        _jitted_kernel = jax.jit(_kernel)
    return _jitted_kernel


def _get_kernel_eq():
    global _jitted_kernel_eq
    if _jitted_kernel_eq is None:
        import jax

        _ensure_compile_cache()
        _jitted_kernel_eq = jax.jit(_kernel_eq)
    return _jitted_kernel_eq


def warmup(
    bucket: int | None = None, *, groups: int | None = None, fallback: bool = False
) -> None:
    """Compile + execute the batch-equation kernel once at the floor
    bucket size so the first real batch pays neither backend init nor
    compile (the persistent compile cache makes this fast after the
    first-ever process). `groups` warms the grouped A-side at the bucket
    that many unique keys land on (a 150-validator set needs gb=255 —
    a different static shape than the all-padding gb=63); fallback=True
    also warms the per-signature attribution kernel (only exercised by
    bad batches)."""
    g = groups or 1
    n = max(bucket or _MIN_BUCKET, _bucket(g))  # ≥1 signature per key
    # warm the plan dispatch SELECTS for every batch that pads to this
    # rung (_plan_shape: one plan per padded shape) — on a multi-device
    # host a big rung is the sharded programs', and warming the
    # single-device jit would leave the real first batch to compile inline
    sel = _select_kernels(n, 1)
    # distinct dummy keys pin the unique-key count; they need not
    # decompress (shape is what compiles), but must be format-valid
    entries: list[ResolvedSig | None] = [
        ResolvedSig(i.to_bytes(4, "little") + b"\x00" * 28, b"\x01" + b"\x00" * 31, 0, 0)
        for i in range(g)
    ] + [None] * (n - g)
    sel.kernel_eq(*prepare_batch_eq(entries, pad_to=sel.bucket))
    if fallback:
        sel.kernel_sig(*prepare_resolved([None] * n, pad_to=sel.bucket))


def make_sharded_kernel(mesh, axis: str = "data"):
    """Shard the per-signature kernel over `axis` of `mesh`. Every
    operand carries the batch dimension and every row is independent, so
    each device runs `_kernel` on its own rows under shard_map with zero
    cross-chip communication. (A plain jit with batch in_shardings is NOT
    that: `_kernel` stacks A and R along the batch axis for one
    decompress call, and the SPMD partitioner re-distributes the
    concatenated rows across chips — 16 collective-permutes and 3
    all-to-alls on a v5e:2x2.)"""
    import jax
    from jax import shard_map
    from jax.sharding import PartitionSpec as P

    _ensure_compile_cache()

    # a name of its own: a device trace shows `jit__kernel_sharded`
    # beside the single-device `jit__kernel`
    def _kernel_sharded(a_bytes, r_bytes, s_digits, h_digits, s_valid):
        return _kernel(a_bytes, r_bytes, s_digits, h_digits, s_valid)

    return jax.jit(
        # check_vma=False: the scalar-mult scan seeds its carry with
        # mesh-invariant constants and makes it row-varying in the body,
        # and the Pallas formulations carry no varying-axes annotation
        shard_map(
            _kernel_sharded, mesh=mesh, in_specs=(P(axis),) * 5,
            out_specs=P(axis), check_vma=False,
        )
    )


def _reduce_partials(partial_pts):
    """Fold the per-device partial points into one. The device count is
    static at trace time and tiny (≤ the mesh size), so a degraded
    non-power-of-two mesh (8 → 7 after a breaker trip) folds with an
    unrolled chain of point_adds instead of the power-of-two tree."""
    from . import curve, msm
    from .curve import Point

    n_dev = partial_pts.x.shape[0]
    if n_dev & (n_dev - 1) == 0:
        return msm._tree_reduce_points(partial_pts, axis=0)
    total = Point(*(c[0] for c in partial_pts))
    for k in range(1, n_dev):
        total = curve.point_add(total, Point(*(c[k] for c in partial_pts)))
    return total


def _sigs_partial(r_bytes, r_digits, s_valid):
    """The R-side of the batch equation over the rows given: the point
    −Σ zᵢ·Rᵢ stacked as (4, 32) limbs, and the rows that entered it
    (decompressed and well-formed). On a mesh each device calls this on
    its own shard; the partials of all shards, folded
    (`_reduce_partials`), are the single-device sum over all rows."""
    import jax
    import jax.numpy as jnp

    from . import curve, msm

    r_bytes = r_bytes.astype(jnp.int32)
    r_digits = r_digits.astype(jnp.int32)
    R, r_ok = curve.decompress(r_bytes)
    r_use = r_ok & s_valid
    Rm = curve.point_select(
        r_use, curve.point_neg(R), curve.identity((r_bytes.shape[0],))
    )
    with jax.named_scope("msm_sigs"):
        return jnp.stack(list(msm.msm(Rm, r_digits))), r_use


def make_sharded_kernel_eq(mesh, axis: str = "data"):
    """Multi-chip batch-equation verification, ONE shard_map over the
    whole kernel: R-point decompression and the 16-window R-side MSM —
    the bulk of the work after A-side grouping — are data-parallel over
    the signature shard on each device (zero communication); each device
    reduces its shard to ONE partial point, and the only collective in
    the whole kernel is the explicit all-gather of those n_dev partials
    (a few KB over ICI). The epilogue — decompress the G unique keys, the
    small grouped A-side MSM (G+1 rows incl. the zs·B base-point term),
    the cofactored identity check — is computed identically on every
    device from replicated operands.

    Why everything sits inside the shard_map: with the Pallas
    formulations switched on (real TPU only) the epilogue holds Mosaic
    kernels, and XLA refuses to auto-partition those ("wrap the call in
    a shard_map") — under a bare jit on a mesh the program does not
    compile at all; and left to the SPMD partitioner the gather became
    two dozen tiny collective-permutes / all-to-alls / all-reduces.

    Call with (ua_bytes, r_bytes, ga_digits, r_digits, zs_digits,
    s_valid, gidx); the signature-axis length must divide evenly by the
    mesh axis size.
    """
    import jax
    import jax.numpy as jnp
    from jax import shard_map
    from jax.sharding import PartitionSpec as P

    from . import curve, msm
    from .curve import Point

    _ensure_compile_cache()

    # the name the program carries in a device trace
    # (`jit__kernel_eq_sharded`), and three phases by named scope: `shard`
    # (this chip's rows), `gather` (the one collective and the fold of the
    # partials), `epilogue` (what every chip repeats). Metadata only.
    def _kernel_eq_sharded(
        ua_bytes, r_bytes, ga_digits, r_digits, zs_digits, s_valid, gidx
    ):
        with jax.named_scope("shard"):
            # this device's signature shard -> one partial point (4, 32)
            part, r_use = _sigs_partial(r_bytes, r_digits, s_valid)
        with jax.named_scope("gather"):
            parts = jax.lax.all_gather(part, axis)  # (n_dev, 4, 32) everywhere
            total = _reduce_partials(Point(*(parts[:, i] for i in range(4))))
        with jax.named_scope("epilogue"):
            # replicated: unique-key decompression + grouped A MSM
            ua_bytes = ua_bytes.astype(jnp.int32)
            ga_digits = ga_digits.astype(jnp.int32)
            zs_digits = zs_digits.astype(jnp.int32)
            g = ua_bytes.shape[0]
            A, a_ok = curve.decompress(ua_bytes)
            Am = curve.point_select(a_ok, curve.point_neg(A), curve.identity((g,)))
            bpt = curve.base_point(())
            ga = Point(
                *(jnp.concatenate([c, b[None]], axis=0) for c, b in zip(Am, bpt))
            )
            gd = jnp.concatenate([ga_digits, zs_digits], axis=1)
            with jax.named_scope("msm_keys"):
                keys_sum = msm.msm(ga, gd)
            with jax.named_scope("finish"):
                acc = curve.point_add(total, keys_sum)
                eq_ok = curve.is_identity(curve.mul_by_cofactor(acc))
            ok_bitmap = jnp.take(a_ok, gidx) & r_use
        return ok_bitmap, eq_ok

    rep, rows = P(), P(axis)
    # check_vma=False: Pallas out_shapes carry no varying-axes annotation
    # and the scans seed their carries with mesh-invariant constants; the
    # replicated eq_ok output is replicated by construction (every device
    # computes it from the gathered partials and replicated operands)
    return jax.jit(
        shard_map(
            _kernel_eq_sharded,
            mesh=mesh,
            in_specs=(rep, rows, rep, P(None, axis), rep, rows, rows),
            out_specs=(rows, rep),
            check_vma=False,
        )
    )


class ResolvedSig(NamedTuple):
    """A signature reduced to the Edwards-form check
    [8](s·B − k·A − R) == O — the common shape both key types share.
    ed25519: k = SHA-512(R ‖ A ‖ msg), as `resolve_rows` leaves it: the
    512-bit integer, NOT yet reduced mod L (the batch equation reduces
    once per key group, the per-signature kernel's prep when it runs);
    sr25519: k is the Merlin transcript challenge and A/R are the
    ristretto coset representatives re-encoded in ed25519 compressed
    form. The hot loop builds plain `(a, r, s, k)` tuples; this is the
    name tests and the sr25519 path construct them by."""

    a: bytes
    r: bytes
    s: int
    k: int


def resolve_rows(items, host_rows: list | None = None, base: int = 0) -> list:
    """The hashing half of the host's work on a signature (the folding
    half is `prepare_batch_eq`'s loop; nothing else touches a signature
    between a caller's `add` and the kernel's operands): (key, msg, sig)
    triples -> rows `(a, r, s, k)`, None for a malformed one (wrong
    sizes, non-canonical s ≥ L) — an inert row whose verdict is False.
    Per ed25519 signature only what no array operation can do: one
    SHA-512, two `int.from_bytes`, the s < L test; no reduction of k
    (see ResolvedSig), no object, no call but the hash's. A key is a
    PubKey object (dispatch on TYPE: sr25519 goes through `resolve`) or —
    `verify_batch_eq`'s raw triples — the 32 key bytes of an ed25519 key.
    Any other key type is None here, and its row index (`base` + position)
    is appended to `host_rows` for the caller to verify on the host."""
    rows: list = []
    add = rows.append
    sha512, from_bytes = hashlib.sha512, int.from_bytes
    for pk, msg, sig in items:
        kind = getattr(pk, "TYPE", None)
        if kind == "ed25519":
            pub = pk.bytes()
        elif kind is None:
            pub = pk
        else:
            if kind != "sr25519" and host_rows is not None:
                host_rows.append(base + len(rows))
            add(resolve(pk, msg, sig))
            continue
        if len(sig) != 64 or len(pub) != 32:
            add(None)
            continue
        s = from_bytes(sig[32:], "little")
        if s >= L:
            add(None)
            continue
        r = sig[:32]
        add((pub, r, s, from_bytes(sha512(r + pub + msg).digest(), "little")))
    return rows


def resolve_ed25519(pub: bytes, msg: bytes, sig: bytes) -> ResolvedSig | None:
    """None = malformed (wrong sizes or non-canonical s ≥ L)."""
    row = resolve_rows(((pub, msg, sig),))[0]
    return None if row is None else ResolvedSig(*row)


def resolve_sr25519(pub: bytes, msg: bytes, sig: bytes) -> ResolvedSig | None:
    from .. import sr25519

    triple = sr25519.to_edwards_triple(pub, msg, sig)
    if triple is None:
        return None
    a_ed, r_ed, k = triple
    s_clear = bytearray(sig[32:])
    s_clear[31] &= 0x7F
    s_int = int.from_bytes(bytes(s_clear), "little")
    if s_int >= L:
        return None
    return ResolvedSig(a_ed, r_ed, s_int, k)


def resolve(pub_key, msg: bytes, sig: bytes) -> ResolvedSig | None:
    """Dispatch on the PubKey object's TYPE, one signature: what
    `resolve_rows` calls for every key that is not ed25519 (and what a
    caller with a single triple may)."""
    if pub_key.TYPE == "ed25519":
        return resolve_ed25519(pub_key.bytes(), msg, sig)
    if pub_key.TYPE == "sr25519":
        return resolve_sr25519(pub_key.bytes(), msg, sig)
    return None


def prepare_batch(items: list[tuple[bytes, bytes, bytes]], pad_to: int = 0):
    """Host-side prep for the per-signature kernel. items: (pubkey32,
    msg, sig64) ed25519 triples; pad_to pads to the bucket shape (inert
    rows). Returns numpy arrays
    (a_bytes, r_bytes, s_digits, h_digits, s_valid)."""
    return prepare_resolved(resolve_rows(items), pad_to=pad_to)


def _rows_u8(chunks: list, n: int, m: int, width: int) -> np.ndarray:
    """n byte strings of `width` bytes -> an (m, width) uint8 array, the
    rows past n zero: ONE frombuffer over the joined bytes."""
    out = np.zeros((m, width), np.uint8)
    if n:
        out[:n] = np.frombuffer(b"".join(chunks), np.uint8).reshape(n, width)
    return out


def prepare_resolved(entries: list[ResolvedSig | None], pad_to: int = 0):
    """Resolved rows -> per-signature kernel inputs (None entries and
    padding rows stay invalid). The rare path (a failed batch equation,
    a warm-up): k is reduced mod L HERE, per signature."""
    n = len(entries)
    m = max(pad_to, n)
    zero = bytes(32)
    a_col, r_col, s_col, h_col = [], [], [], []
    s_valid = np.zeros(m, bool)
    s_valid[:n] = True
    for i, e in enumerate(entries):
        if e is None:
            s_valid[i] = False
            e = (zero, zero, 0, 0)
        a, r, s, k = e
        a_col.append(a)
        r_col.append(r)
        s_col.append(s.to_bytes(32, "little"))
        h_col.append((k % L).to_bytes(32, "little"))

    def to_digits(b: np.ndarray) -> np.ndarray:
        """(N,32) bytes -> (N,64) radix-16 little-endian digits."""
        d = np.empty((b.shape[0], 64), np.int32)
        d[:, 0::2] = b & 0xF
        d[:, 1::2] = b >> 4
        return d

    return (
        _rows_u8(a_col, n, m, 32).astype(np.int32),
        _rows_u8(r_col, n, m, 32).astype(np.int32),
        to_digits(_rows_u8(s_col, n, m, 32)),
        to_digits(_rows_u8(h_col, n, m, 32)),
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


def prepare_batch_eq(
    entries: list[ResolvedSig | None], pad_to: int = 0, groups_to: int = 0
):
    """Host prep for the batch-equation kernel. pad_to ≥ len(entries)
    pads the signature axis with inert rows (digits 0, s_valid False);
    the unique-key axis is padded to a group bucket, the keys' own or
    `groups_to` if that is wider (inert key rows: no signature row
    points at them). Returns (ua_bytes,
    r_bytes, ga_digits, r_digits, zs_digits, s_valid, gidx) numpy arrays
    shaped for `_kernel_eq`.

    Per row the loop does what no array operation can — a dict probe for
    the key group and one bigint multiply-add each into that group's
    Σ z·k and into Σ z·s; every array is built column-wise from joined
    bytes. The coefficients z are the 16·n bytes of ONE os.urandom call
    with bit 0 set (z ∈ [1, 2^128): a zero coefficient would drop the
    signature from the equation entirely): the array the kernel gets IS
    the randomness, and the loop's z is read from the same bytes."""
    n = len(entries)
    m = max(pad_to, n)
    z_np = np.zeros((m, 16), np.uint8)
    z_np[:n] = np.frombuffer(os.urandom(16 * n), np.uint8).reshape(n, 16)
    z_np[:n, 0] |= 1
    zero = bytes(32)
    group_of: dict[bytes, int] = {}
    coeffs: list[int] = []  # per-group Σ z·k, reduced mod L once below
    zs = 0
    g_col, r_col, bad = [], [], []
    lo_col, hi_col = z_np[:n].view("<u8").T.tolist()  # z's two 64-bit halves
    for e, lo, hi in zip(entries, lo_col, hi_col):
        if e is None:
            bad.append(len(g_col))
            g_col.append(0)
            r_col.append(zero)
            continue
        a, r, s, k = e
        try:
            gi = group_of[a]
        except KeyError:
            gi = group_of[a] = len(coeffs)
            coeffs.append(0)
        g_col.append(gi)
        r_col.append(r)
        # accumulate WITHOUT reducing — neither z·k (k itself may be the
        # unreduced 512-bit hash) nor the sums: one mod per group at the
        # end beats a modular reduction per signature
        z = hi << 64 | lo
        coeffs[gi] += z * k
        zs += z * s
    s_valid = np.zeros(m, bool)
    s_valid[:n] = True
    gidx = np.zeros(m, np.int32)
    gidx[:n] = g_col
    if bad:
        s_valid[bad] = False
        z_np[bad] = 0
    g = len(coeffs)
    gb = max(_group_bucket(g), groups_to)
    ga_sc = _rows_u8([(c % L).to_bytes(32, "little") for c in coeffs], g, gb, 32)
    zs_digits = np.frombuffer((zs % L).to_bytes(32, "little"), np.uint8).reshape(32, 1)
    return (
        _rows_u8(list(group_of), g, gb, 32),  # uint8 throughout: the kernel
        _rows_u8(r_col, n, m, 32),  # casts on-device, the copy moves 4x fewer bytes
        np.ascontiguousarray(ga_sc.T),  # (32, gb)
        np.ascontiguousarray(z_np.T),  # (16, m)
        zs_digits,
        s_valid,
        gidx,
    )


def _shard_devices() -> list:
    """The devices the sharded kernels may span right now: the mesh
    health registry's active set (per-device breakers, recovery probes —
    crypto/tpu/mesh.py). TMTPU_NO_SHARDED=1 pins the single-device
    path; TMTPU_MESH_MAX_DEVICES caps the mesh inside the registry."""
    if os.environ.get("TMTPU_NO_SHARDED"):
        return []
    try:
        from . import mesh as mesh_mod

        devs = mesh_mod.device_list()
    except Exception:  # noqa: BLE001 — backend not up yet
        return []
    return devs if len(devs) > 1 else []


def _shard_device_count() -> int:
    """Active mesh size (1 = single-device dispatch)."""
    return max(1, len(_shard_devices()))


def _get_sharded(devices: list):
    """(batch-equation kernel, per-signature fallback kernel) jitted over
    a 1-D mesh of exactly `devices`; cached per device set."""
    key = tuple(d.id for d in devices)
    kernels = _sharded_kernels.get(key)
    if kernels is None:
        from jax.sharding import Mesh

        mesh = Mesh(np.asarray(devices), ("data",))
        kernels = (make_sharded_kernel_eq(mesh), make_sharded_kernel(mesh))
        _sharded_kernels[key] = kernels
    return kernels


#: Largest single-kernel batch: bigger ranges are split into chunks of
#: this size. Bounds XLA compile shapes AND pipelines naturally — chunk
#: k+1's host prep runs while chunk k executes (async dispatch); the
#: bitmaps are only synced after every chunk is in flight.
_MAX_BUCKET = int(os.environ.get("TMTPU_MAX_BUCKET", "8192"))

#: Distinct keys the full-chunk program is compiled for at start-up
#: (`crypto/batch._probe_tpu`'s last warm-up: this many keys at
#: `_MAX_BUCKET` rows, both kernels): a 150-validator set. A batch sent at
#: the whole chunk (`TPUBatchVerifier.whole_chunk`) is padded to ITS group
#: bucket too where its keys fit, so it runs the one program every start
#: has warm — its own (gb127 for the 101 keys a 150-validator range stops
#: at) would be a second 8,192-row program, compiled in the middle of a
#: catch-up for ≈ 3% of a kernel
_CHUNK_GROUPS = 150


class _Selection:
    """One dispatch plan: the kernels, the padded bucket shape, the pad
    multiple it was bucketed with, and the device set (None = single)."""

    __slots__ = ("kernel_eq", "kernel_sig", "bucket", "multiple", "devices")

    def __init__(self, kernel_eq, kernel_sig, bucket, multiple, devices):
        self.kernel_eq = kernel_eq
        self.kernel_sig = kernel_sig
        self.bucket = bucket
        self.multiple = multiple
        self.devices = devices


#: Padded rows from which a dispatch is sharded over the mesh. Timed on a
#: v5e 2x2 host (PERF.md §6, PR 27), wall ms a dispatch, one chip / four:
#: 256 rows 14.5 / 17.1, 512: 15.0 / 17.3, 2,048: 17.1 / 18.0, 8,192:
#: 24.0 / 20.0. Under the gate the kernel is the MSMs' serial parts (fold,
#: buckets), which chips do not divide, and the four-way transfer and the
#: collective cost more than the rows they spread.
_SHARD_MIN_ROWS = 8192


def _plan_shape(n: int, pad_multiple: int, n_dev: int) -> tuple[bool, int, int]:
    """(sharded, bucket, pad multiple) for an n-entry chunk on `n_dev`
    active devices — a function of the PADDED shape alone: every raw
    count that pads to one rung of the ladder gets one plan, and that
    rung itself (what `warmup` is given) gets the same one. So a shape
    start-up warmed is a shape dispatch selects, and the reverse. The
    rung is the ladder's rounded up to `pad_multiple`: a batch the hub
    sends at the whole chunk (`TPUBatchVerifier.whole_chunk`) is planned
    as the chunk-sized batch it runs as."""
    rung = _bucket(n, pad_multiple)
    sharded = n_dev > 1 and (
        os.environ.get("TMTPU_FORCE_SHARDED") == "1" or rung >= _SHARD_MIN_ROWS
    )
    mult = pad_multiple * n_dev // _gcd(pad_multiple, n_dev) if sharded else pad_multiple
    return sharded, _bucket(rung, mult), mult


def _select_kernels(n: int, pad_multiple: int) -> _Selection:
    """THE dispatch plan for an n-entry chunk, for `warmup` and
    `_dispatch_and_collect` alike (and so for the cut-off measurement,
    which goes through dispatch): `_plan_shape` on the active mesh."""
    devices = _shard_devices()
    sharded, bucket, mult = _plan_shape(n, pad_multiple, len(devices))
    if sharded:
        kernel_eq, kernel_sig = _get_sharded(devices)
        return _Selection(kernel_eq, kernel_sig, bucket, mult, devices)
    return _Selection(_get_kernel_eq(), _get_kernel(), bucket, mult, None)


def _is_warm_bucket(m: int, multiple: int = 1) -> bool:
    """True when `m` is a shape the bucket ladder can produce — some
    power-of-two ≥ _MIN_BUCKET rounded up to `multiple`. Dispatch
    asserts this on every chunk: any other shape would be an inline
    cold XLA compile on the hot path (the ROADMAP's 20–83 s warmup
    cliffs), which must instead route through pad-to-bucket or the CPU
    fallback."""
    if m < _MIN_BUCKET:
        return False
    multiple = max(1, multiple)
    b = _MIN_BUCKET
    while True:
        rounded = ((b + multiple - 1) // multiple) * multiple
        if rounded == m:
            return True
        if rounded > m:
            return False
        b *= 2


def _shard_fill(n_real: int, bucket: int, n_dev: int) -> list[int]:
    """Real (non-padding) signatures landing on each device's contiguous
    shard of a `bucket`-row batch — the per-device occupancy record."""
    s = bucket // n_dev
    return [max(0, min(s, n_real - k * s)) for k in range(n_dev)]


#: per-thread record of the last dispatch this thread ran: route-adjacent
#: diagnostics for the VerifyHub's hub.dispatch spans (devices + shard
#: fill). Thread-local for the same reason as AdaptiveBatchVerifier's
#: last_route — concurrent verifiers must not misattribute each other.
_dispatch_local = threading.local()


def last_dispatch_info() -> dict | None:
    """{devices: [...], shards: [...]} of this thread's last sharded
    dispatch, or None when it ran single-device."""
    return getattr(_dispatch_local, "info", None)


class _InFlightWork:
    """One registration of `while_in_flight`: the callable, whether a
    dispatch loop has run it, and what it raised."""

    __slots__ = ("fn", "ran", "error")

    def __init__(self, fn):
        self.fn = fn
        self.ran = False
        self.error: Exception | None = None


@contextmanager
def while_in_flight(fn):
    """Hand the dispatch loop host work to do while its chunks are on the
    device: inside the `with`, the first `_dispatch_and_collect` THIS
    thread runs calls `fn()` once, after its last chunk's dispatch and
    before its first collect — the one stretch in which the caller would
    otherwise only wait. Kept in `_dispatch_local`, so the registration
    crosses the call chain between a caller and the dispatch without an
    argument, and no other thread (a VerifyHub runner) ever sees it.

    `fn` is work the caller owes anyway. Where no device dispatch goes
    out (the `cpu` route, a host lane, an open breaker) it does not run,
    and the caller does it where it always did; the registration yielded
    says which (`ran`). What `fn` raises is not a device fault: the
    dispatch loop collects as if it had returned, and the error is raised
    here, on the caller's side, when the `with` ends (unless the body
    raised: that error wins)."""
    work = _InFlightWork(fn)
    prev = getattr(_dispatch_local, "work", None)
    _dispatch_local.work = work
    try:
        yield work
    finally:
        _dispatch_local.work = prev
    if work.error is not None:
        raise work.error


def _run_in_flight_work(work: _InFlightWork, chunks: int) -> None:
    """Run a registration, once: a degrade retry re-enters the dispatch
    loop and must not run it again. Whatever it raises is kept for
    `while_in_flight`'s exit and never reaches `crypto/batch`'s device
    guard, which would count it against the breaker and re-verify the
    batch on the host."""
    work.ran = True
    with trace.span("tpu", "fill", chunks=chunks) as sp:
        try:
            work.fn()
        except Exception as e:  # noqa: BLE001 — re-raised at the caller's exit
            work.error = e
        sp.set(ran=work.error is None)


def verify_resolved(
    entries: list[ResolvedSig | None], pad_multiple: int = 1
) -> np.ndarray:
    """Batch-equation verification with per-signature fallback: returns a
    bool bitmap of length len(entries). The happy path (all signatures
    valid) costs one MSM kernel call per ≤_MAX_BUCKET chunk; a failed
    equation falls back to the per-signature kernel for THAT chunk only
    (the reference bisects inside voi; attribution cost only matters on
    the rare bad batch).

    Multi-device: when more than one accelerator is visible and the batch
    pads to at least _SHARD_MIN_ROWS rows, the MSM runs sharded over a
    1-D mesh (one partial point gathered per device — the only
    collective); padding rounds the batch up to a mesh-divisible bucket.
    TMTPU_FORCE_SHARDED=1 drops the size gate (tests);
    TMTPU_NO_SHARDED=1 disables sharding. One interface regardless of
    topology — the reference's crypto/crypto.go:46-54 contract."""
    return _dispatch_and_collect(
        len(entries),
        lambda i, j: entries[i:j],
        pad_multiple,
    )


def _dispatch_and_collect(n: int, get_entries, pad_multiple: int) -> np.ndarray:
    """Chunked dispatch core: get_entries(i, j) materializes the resolved
    rows of one ≤ _MAX_BUCKET chunk, CALLED AS THE LOOP RUNS. Every
    caller that starts from signatures (`verify_batch_eq`, and through it
    the verifier objects of the served path) resolves there, so chunk
    k+1's host work (SHA-512 resolve + bigint prep) runs while chunk k
    executes on the device (async dispatch), and nothing is resolved
    before the first chunk is out; only `verify_resolved`'s callers hand
    in rows resolved beforehand. Every chunk of a multi-chunk batch
    shares ONE compile shape (tail padded to the full chunk size):
    stable shapes beat saving padding rows at the cost of an inline XLA
    compile of a one-off tail bucket. Bitmaps are only synced after
    every chunk is in flight; a failed equation falls back to the
    per-signature kernel for that chunk alone.

    The in-flight phase: between the two loops — every chunk dispatched,
    none collected — the calling thread's `while_in_flight` registration
    runs, under a `tpu.fill` [chunks, ran] span. Only if a chunk really
    went out (a loop whose every dispatch raised has nothing in flight),
    once a registration (a degrade retry re-enters this function), and
    never as a device fault: what it raises waits for the caller.

    Mesh degradation: a sharded chunk that raises (a chip died mid-MSM)
    hands the error to mesh.on_dispatch_failure, which probes every
    device and trips the breakers of the dead ones. When membership
    changed, the chunk re-dispatches recursively on the survivors (the
    recursion re-selects kernels on the degraded mesh, bounded by the
    device count); when no probe failed, the error re-raises and the
    AdaptiveBatchVerifier's CPU fallback takes over — CPU only when the
    mesh cannot make progress at all."""
    if n == 0:
        return np.zeros(0, bool)
    sel = _select_kernels(min(n, _MAX_BUCKET), pad_multiple)
    ids = [d.id for d in sel.devices] if sel.devices is not None else None
    n_dev = len(ids) if ids else 1
    # hot-path shape discipline (see _is_warm_bucket): a non-bucket pad
    # here would compile a cold one-off XLA shape inline
    assert _is_warm_bucket(sel.bucket, sel.multiple), (
        f"dispatch shape {sel.bucket} is not a bucket "
        f"(multiple={sel.multiple}); pad-to-bucket or CPU fallback required"
    )
    _dispatch_local.info = None
    # a plan padded to whole chunks is the start-up's program, keys and all
    groups_to = _group_bucket(_CHUNK_GROUPS) if sel.multiple % _MAX_BUCKET == 0 else 0
    in_flight = []
    for i in range(0, n, _MAX_BUCKET):
        chunk = get_entries(i, min(i + _MAX_BUCKET, n))
        try:
            # the host's share of a dispatch, and the jitted call itself
            # (transfer + enqueue: it returns before the device is done)
            # tmtlint: allow[span-per-item] -- per chunk of <= _MAX_BUCKET signatures
            with trace.span(
                "tpu", "prep", n=len(chunk), bucket=sel.bucket, devices=n_dev
            ) as sp:
                args = prepare_batch_eq(chunk, pad_to=sel.bucket, groups_to=groups_to)
                sp.set(groups=int(args[0].shape[0]))
            # tmtlint: allow[span-per-item] -- per chunk
            with trace.span("tpu", "dispatch", bucket=sel.bucket, devices=n_dev):
                res = sel.kernel_eq(*args)
        except Exception as e:  # noqa: BLE001 — settled at collect time
            res = e
        in_flight.append((chunk, res))
    work = getattr(_dispatch_local, "work", None)
    if work is not None and not work.ran and any(
        not isinstance(res, Exception) for _chunk, res in in_flight
    ):
        _run_in_flight_work(work, len(in_flight))
    outs = []
    shards_total = [0] * len(ids) if ids else None
    retried = False
    for chunk, res in in_flight:
        try:
            if isinstance(res, Exception):
                raise res
            bitmap, eq_ok = res
            # the host blocked on the device: reading eq_ok waits for
            # the chunk's program to end
            # tmtlint: allow[span-per-item] -- per chunk
            with trace.span("tpu", "collect", bucket=sel.bucket, devices=n_dev) as sp:
                eq_ok = bool(eq_ok)
                sp.set(eq_ok=eq_ok)
                if eq_ok:
                    out = np.asarray(bitmap)[: len(chunk)]
            if not eq_ok:
                # tmtlint: allow[span-per-item] -- per chunk whose equation failed
                with trace.span("tpu", "attribute", n=len(chunk), bucket=sel.bucket):
                    out = np.asarray(
                        sel.kernel_sig(*prepare_resolved(chunk, pad_to=sel.bucket))
                    )[: len(chunk)]
            if ids:
                from .. import backend_telemetry as bt

                fill = _shard_fill(len(chunk), sel.bucket, len(ids))
                bt.record_shard_dispatch(ids, fill)
                for k, m in enumerate(fill):
                    shards_total[k] += m
        except Exception as e:  # noqa: BLE001 — device failure mid-batch
            out = _degrade_and_retry(chunk, pad_multiple, e, sel)
            retried = True
        outs.append(out)
    if ids and not retried:
        # a degrade retry stamped the surviving mesh's (smaller) info —
        # keep that; only an all-healthy batch attributes to THIS mesh
        _dispatch_local.info = {"devices": ids, "shards": shards_total}
    return outs[0] if len(outs) == 1 else np.concatenate(outs)


def _degrade_and_retry(
    chunk, pad_multiple: int, exc: Exception, sel: _Selection
) -> np.ndarray:
    """One chunk's dispatch raised. Single-device dispatch has nothing to
    degrade to — re-raise (CPU fallback lives in crypto/batch.py). A
    sharded dispatch consults the mesh registry: if probing attributed
    the failure to specific chips, re-verify THIS chunk on the surviving
    mesh (recursion re-selects kernels, so it lands on N−1 devices, then
    N−2, … then the single-device kernel before the CPU path)."""
    if sel.devices is None:
        raise exc
    from .. import backend_telemetry as bt
    from . import mesh as mesh_mod

    if not mesh_mod.on_dispatch_failure(exc):
        # an EARLIER chunk of this batch may already have tripped the
        # dead chip's breaker (all chunks launch against the same
        # selection before any is collected): retry whenever the active
        # set no longer matches the one this selection was pinned to.
        # Re-raise only when the mesh is genuinely unchanged — a
        # transient/kernel error the CPU fallback should absorb.
        current = [d.id for d in _shard_devices()]
        if current == [d.id for d in sel.devices]:
            raise exc
    bt.BACKEND["degrade_retries"] += 1
    return _dispatch_and_collect(
        len(chunk), lambda i, j: chunk[i:j], pad_multiple
    )


def verify_batch_eq(
    items: list, pad_multiple: int = 1, host_rows: list | None = None
) -> np.ndarray:
    """(key, msg, sig) triples -> bool bitmap: THE resolve-and-prepare
    entrance, for raw ed25519 triples (key = the 32 key bytes: the
    cut-off probe, tools) and for the verifier objects of the served path
    (key = a PubKey: `TPUBatchVerifier.verify`) alike. Nothing is
    resolved up front: each ≤ _MAX_BUCKET chunk is resolved
    (`resolve_rows`, under one `tpu.resolve` [n, chunk] span) inside the
    dispatch loop, right before its prep and its jitted call, so chunk
    k+1's SHA-512s and bigint sums run while chunk k is on the device —
    and a malformed signature in a later chunk is found after the
    earlier ones were dispatched: the bitmap is the same. `host_rows`
    collects the rows of key types the kernel does not take (see
    `resolve_rows`)."""
    def resolved(i: int, j: int) -> list:
        with trace.span("tpu", "resolve", n=j - i, chunk=i // _MAX_BUCKET):
            return resolve_rows(items[i:j], host_rows, i)

    return _dispatch_and_collect(len(items), resolved, pad_multiple)


def _bucket(n: int, multiple: int = 1) -> int:
    b = _MIN_BUCKET
    while b < n:
        b *= 2
    if b % multiple:
        b = ((b + multiple - 1) // multiple) * multiple
    return b


def verify_batch(
    items: list[tuple[bytes, bytes, bytes]], kernel=None, pad_multiple: int = 1
) -> np.ndarray:
    """Verify (pubkey, msg, sig) triples; returns a bool bitmap of length
    len(items). Pads to a bucket size to bound XLA compilations."""
    n = len(items)
    if n == 0:
        return np.zeros(0, bool)
    b = _bucket(n, pad_multiple)
    fn = kernel or _get_kernel()
    out = np.asarray(fn(*prepare_batch(items, pad_to=b)))
    return out[:n]


class TPUBatchVerifier(BatchVerifier):
    """BatchVerifier backed by the JAX batch-equation kernel (the
    reference's interface, crypto/crypto.go:46-54). ed25519 AND sr25519
    share the kernel — both reduce to [8](s·B − k·A − R) == O on the same
    curve (see ResolvedSig). Other key types (secp256k1) degrade to host
    verification so mixed validator sets still produce a complete bitmap.

    `add` only keeps the triple: resolving is `verify_batch_eq`'s, chunk
    by chunk inside the dispatch loop. `add_many` is the bulk hand-over
    beside the reference's two calls (one list extend, no call a
    signature)."""

    def __init__(self):
        self._items: list[tuple[PubKey, bytes, bytes]] = []
        #: run at the full chunk shape — `_MAX_BUCKET` rows, the group
        #: bucket of `_CHUNK_GROUPS` keys — whatever the row count (see
        #: `AdaptiveBatchVerifier.whole_chunk`)
        self.whole_chunk = False

    def add(self, pub_key: PubKey, msg: bytes, sig: bytes) -> None:
        self._items.append((pub_key, msg, sig))

    def add_many(self, items) -> None:
        self._items.extend(items)

    def verify(self) -> tuple[bool, list[bool]]:
        items = self._items
        host_rows: list[int] = []
        results = verify_batch_eq(
            items, _MAX_BUCKET if self.whole_chunk else 1, host_rows
        ).tolist()
        for i in host_rows:
            pk, msg, sig = items[i]
            results[i] = pk.verify_signature(msg, sig)
        return all(results) and bool(results), results
