"""Compile the main path's kernels for a DESCRIBED TPU (v5e:2x2) — nothing
runs, no chip needed: the TPU compiler refuses here what it would refuse on
the chip (unaligned slices, too much VMEM, a kernel that cannot lower).

All cases live in this ONE file and describe the topology inside a fixture:
the worker that gets this file is the only process that loads the TPU
library (see /opt/skills/guides/on-chip-measurement, section 2). The
persistent compile cache is off around them — an executable for a described
device cannot be read back without one.

`pallas_field.pow22523` at (8192, 32) took ~4 minutes to compile while its
254 squarings were unrolled; with the long runs rolled (`lax.fori_loop`) it
is ~3 s and sits in tier-1 with the rest.
"""

import os

import jax
import jax.numpy as jnp
import pytest


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies

    os.environ.setdefault("TPU_LOG_DIR", "disabled")  # else libtpu logs under /tmp
    try:
        return topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — no TPU compiler in this image
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    from jax.sharding import SingleDeviceSharding

    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def no_persistent_cache():
    from jax.experimental.compilation_cache import compilation_cache as cc

    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", prev)
    cc.reset_cache()


def _pallas_mul(S):
    from tendermint_tpu.crypto.tpu import pallas_field

    big = S((8192, 32), jnp.int32)
    return jax.jit(pallas_field.mul), (big, big)


def _pallas_scan_blocks(S):
    from tendermint_tpu.crypto.tpu import pallas_field

    t = pallas_field.TILE
    first = tuple(S((t, 32), jnp.int32) for _ in range(4))
    rest = tuple(S((15, t, 32), jnp.int32) for _ in range(4))
    return jax.jit(lambda f, r: pallas_field.scan_blocks(f, r)), (first, rest)


def _pallas_pow22523(S):
    from tendermint_tpu.crypto.tpu import pallas_field

    return jax.jit(pallas_field.pow22523), (S((8192, 32), jnp.int32),)


def _kernel_sig_256(S):
    """The per-signature attribution kernel at the floor-warm bucket,
    shapes as prepare_resolved builds them."""
    from tendermint_tpu.crypto.tpu import verify

    m = 256
    return jax.jit(verify._kernel), (
        S((m, 32), jnp.int32), S((m, 32), jnp.int32),
        S((m, 64), jnp.int32), S((m, 64), jnp.int32), S((m,), jnp.bool_),
    )


def _kernel_eq_256_g150(S):
    """The batch-equation kernel at the shapes warmup(groups=150) builds
    (prepare_batch_eq: 150 unique keys pad to gb=255)."""
    from tendermint_tpu.crypto.tpu import verify

    m, gb = 256, verify._group_bucket(150)
    return jax.jit(verify._kernel_eq), (
        S((gb, 32), jnp.uint8), S((m, 32), jnp.uint8), S((32, gb), jnp.uint8),
        S((16, m), jnp.uint8), S((32, 1), jnp.uint8), S((m,), jnp.bool_),
        S((m,), jnp.int32),
    )


@pytest.mark.parametrize(
    "build,want_custom_call",
    [
        pytest.param(_pallas_mul, True, id="pallas_field.mul-8192"),
        pytest.param(_pallas_scan_blocks, True, id="pallas_field.scan_blocks-TILE"),
        pytest.param(_kernel_sig_256, False, id="_kernel-256"),
        pytest.param(_kernel_eq_256_g150, False, id="_kernel_eq-256-g150"),
        pytest.param(_pallas_pow22523, True, id="pallas_field.pow22523-8192"),
    ],
)
def test_compiles_for_v5e(one_chip, no_persistent_cache, build, want_custom_call):
    def S(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    fn, args = build(S)
    compiled = fn.lower(*args).compile()
    text = compiled.as_text()
    assert ("tpu_custom_call" in text) == want_custom_call
    mem = compiled.memory_analysis()
    used = (
        mem.temp_size_in_bytes
        + mem.argument_size_in_bytes
        + mem.output_size_in_bytes
        + mem.generated_code_size_in_bytes
    )
    assert used < 16 * 1024**3, f"{used} bytes do not fit one v5e chip"


def test_sharded_eq_kernel_compiles_for_the_2x2_mesh_with_one_all_gather(topo, no_persistent_cache):
    """The four-chip deployment's program (`light150.mesh4`) as the chip
    runs it — the gate's rung, 101 keys, the Pallas formulations ON (what
    a TPU process traces; Mosaic kernels inside the shard_map are what
    XLA refused to partition before PR 22): it compiles for the described
    2x2 mesh under its own name, and the ONLY collective the compiler put
    in is the one all-gather of the partial points."""
    import re

    import numpy as np
    from jax.sharding import Mesh, NamedSharding
    from jax.sharding import PartitionSpec as P

    from tendermint_tpu.crypto.tpu import field, verify

    mesh = Mesh(np.asarray(topo.devices), ("data",))

    def S(shape, dtype, spec):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=NamedSharding(mesh, spec))

    m, gb = verify._SHARD_MIN_ROWS, verify._group_bucket(101)
    assert verify._plan_shape(m, 1, len(topo.devices)) == (True, m, len(topo.devices))
    rep, rows = P(), P("data")
    field.set_pallas(True)
    try:
        compiled = verify.make_sharded_kernel_eq(mesh).lower(
            S((gb, 32), jnp.uint8, rep), S((m, 32), jnp.uint8, rows), S((32, gb), jnp.uint8, rep),
            S((16, m), jnp.uint8, P(None, "data")), S((32, 1), jnp.uint8, rep),
            S((m,), jnp.bool_, rows), S((m,), jnp.int32, rows),
        ).compile()
    finally:
        field.set_pallas(False)
    text = compiled.as_text()
    assert re.search(r"HloModule jit__kernel_eq_sharded\b", text) and "tpu_custom_call" in text
    assert len(re.findall(r"= \S+ all-gather(?:-start)?\(", text)) == 1
    for other in ("all-reduce", "collective-permute", "all-to-all", "reduce-scatter"):
        assert not re.search(rf"= \S+ {other}(?:-start)?\(", text), other
    mem = compiled.memory_analysis()
    assert mem.temp_size_in_bytes + mem.generated_code_size_in_bytes < 16 * 1024**3
