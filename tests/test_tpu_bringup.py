"""Bring-up regressions that only a TPU would otherwise reach:

  * the Pallas A/B probe (`verify._maybe_enable_pallas`) returns early off
    TPU, so no CPU test ever ran its body — a missing import inside it went
    unnoticed and was swallowed on the chip. Here the backend gate is
    patched in-test and the Pallas kernels run in interpret mode at tiny
    widths, so the whole probe executes on the CPU;
  * a Pallas failure on a TPU is loud (recorded, counted, WARNING), not
    INFO-and-carry-on;
  * the compile cache can be placed from outside (JAX_COMPILATION_CACHE_DIR
    set -> the code sets no directory at all; unset -> the fixed in-checkout
    path; an uncreatable directory raises);
  * the device probe records an error after a good attach instead of
    labelling the backend "unknown".
"""

import functools
import logging
import os

import jax
import pytest

from tendermint_tpu.crypto import backend_telemetry as bt
from tendermint_tpu.crypto.tpu import field as F
from tendermint_tpu.crypto.tpu import msm as M
from tendermint_tpu.crypto.tpu import pallas_field as PF
from tendermint_tpu.crypto.tpu import verify as V

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture
def probe_on_cpu(monkeypatch):
    """Drive the probe past its backend gate on the CPU: tiny widths,
    Pallas in interpret mode, switches restored afterwards."""
    monkeypatch.delenv("TMTPU_NO_PALLAS", raising=False)
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    monkeypatch.setattr(V, "_PROBE_WIDTH", 32)
    monkeypatch.setattr(V, "_PROBE_CHAIN", 2)
    monkeypatch.setattr(V, "_PROBE_WINDOWS", 1)
    # 32 points in blocks of 4 -> 8 block lanes == the patched TILE, so
    # msm routes the in-block scan through the Pallas gate (g % TILE == 0)
    monkeypatch.setattr(M, "_BLOCK", 4)
    monkeypatch.setattr(PF, "TILE", 8)
    monkeypatch.setattr(PF, "mul", functools.partial(PF.mul, interpret=True))
    monkeypatch.setattr(
        PF, "scan_blocks", functools.partial(PF.scan_blocks, interpret=True, tile=8)
    )
    # the fused pow22523 kernel is a 254-multiply chain: minutes in
    # interpret mode (its own test is `slow`). The probe's control flow
    # is what this guards, so the XLA chain stands in for it.
    monkeypatch.setattr(PF, "pow22523", jax.jit(F._pow22523_chain))
    monkeypatch.setattr(V, "field_mul_probe", {})
    # which multiply "wins" a wall-clock race between XLA-CPU and the
    # Pallas interpreter is noise, and with the interpreted multiply
    # switched on the scan stage would push every msm multiply through the
    # interpreter under vmap: record the decision instead of applying it
    decided = []
    monkeypatch.setattr(F, "set_pallas", lambda on, **kw: decided.append((on, kw)))
    before = dict(bt.BACKEND)
    yield decided
    M.set_pallas_scan(False)
    bt.BACKEND.update(before)


def test_pallas_probe_runs_clean_past_the_backend_gate(probe_on_cpu):
    V._maybe_enable_pallas()
    probe = V.field_mul_probe
    assert not probe.get("error") and not probe.get("scan_error"), (
        f"error={probe.get('error')} scan_error={probe.get('scan_error')}"
    )
    assert bt.BACKEND["pallas_probe_errors"] == 0
    # every pair was cross-checked, timed and decided
    assert probe["chosen"] in ("gemm", "pallas")
    assert probe["pow_chosen"] in ("xla", "pallas")
    assert probe["scan_chosen"] in ("xla", "pallas")
    assert {"gemm_us", "pallas_us", "scan_xla_ms", "scan_pallas_ms"} <= set(probe)
    assert probe_on_cpu == [
        (probe["chosen"] == "pallas", {"pow_chain": probe["pow_chosen"] == "pallas"})
    ]


def test_pallas_probe_failure_is_loud(probe_on_cpu, monkeypatch, caplog):
    def broken(*_a, **_k):
        raise RuntimeError("mosaic refused the kernel")

    monkeypatch.setattr(PF, "scan_blocks", broken)
    with caplog.at_level(logging.WARNING, logger="crypto.tpu"):
        V._maybe_enable_pallas()
    assert "mosaic refused" in V.field_mul_probe["scan_error"]
    assert "error" not in V.field_mul_probe  # mul/pow stage still decided
    assert bt.BACKEND["pallas_probe_errors"] == 1
    assert any(
        r.levelno == logging.WARNING and "scan_error" in r.getMessage()
        for r in caplog.records
    )
    assert not M._USE_PALLAS_SCAN  # the failed formulation stays off


def test_pallas_probe_is_a_noop_off_tpu(monkeypatch):
    monkeypatch.setattr(V, "field_mul_probe", {})
    V._maybe_enable_pallas()  # conftest: the CPU backend
    assert V.field_mul_probe == {}


@pytest.fixture
def cache_config(monkeypatch):
    """Record what _ensure_compile_cache would set, without setting it."""
    updates = {}
    monkeypatch.setattr(V, "_cache_ready", False)
    monkeypatch.setattr(V, "_maybe_enable_pallas", lambda: None)
    monkeypatch.setattr(jax.config, "update", lambda k, v: updates.__setitem__(k, v))
    return updates


def test_cache_dir_from_env_is_left_to_jax(cache_config, monkeypatch, tmp_path):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path / "placed"))
    made = []
    monkeypatch.setattr(os, "makedirs", lambda *a, **k: made.append(a))
    V._ensure_compile_cache()
    assert "jax_compilation_cache_dir" not in cache_config
    assert not made  # no directory of its own either
    assert cache_config["jax_persistent_cache_min_compile_time_secs"] == 1.0


def test_cache_dir_default_is_fixed_inside_the_checkout(cache_config, monkeypatch):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    monkeypatch.setattr(os, "makedirs", lambda *a, **k: None)
    V._ensure_compile_cache()
    assert cache_config["jax_compilation_cache_dir"] == os.path.join(REPO, ".jax_cache")
    assert V.COMPILE_CACHE_DIR == os.path.join(REPO, ".jax_cache")


def test_uncreatable_cache_dir_is_an_error(cache_config, monkeypatch):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)

    def denied(*_a, **_k):
        raise PermissionError("read-only checkout")

    monkeypatch.setattr(os, "makedirs", denied)
    with pytest.raises(PermissionError):
        V._ensure_compile_cache()
    assert not V._cache_ready


def test_probe_records_an_error_after_a_good_attach(monkeypatch):
    """A backend that attached and then failed a probe step is an error
    the probe records (and the verdict is 'unavailable'), never the label
    "unknown" with the node carrying on."""
    from tendermint_tpu.crypto import batch as cb

    def boom(**_k):
        raise RuntimeError("warmup exploded")

    bt.reset()
    monkeypatch.setattr(V, "warmup", boom)
    monkeypatch.setattr(cb, "_tpu_available", None)
    cb._probe_tpu()
    assert cb._tpu_available is False
    assert bt.BACKEND["probe_errors"] == 1
    assert bt.BACKEND["attach_attempts"] == 1 and bt.BACKEND["attach_failures"] == 0
    assert bt.ACTIVE["kind"] == "cpu"
    bt.reset()


def test_routes_and_host_reverifies_are_counted(monkeypatch):
    """Every batch is counted under the route that served it, and a host
    re-verify after a device error is its own route ("cpu-fallback")."""
    from tendermint_tpu import testing as tt
    from tendermint_tpu.crypto import batch as cb

    vals, keys = tt.make_validator_set(4)
    bid = tt.make_block_id(b"r")
    commit = tt.make_commit("c", 1, 0, bid, vals, keys)
    items = [
        (vals.validators[i].pub_key, commit.vote_sign_bytes("c", i), cs.signature)
        for i, cs in enumerate(commit.signatures)
    ]

    class Dead:
        def add(self, *a):
            pass

        def verify(self):
            raise RuntimeError("chip fell over")

    bt.reset()
    breaker = cb.tpu_breaker()
    monkeypatch.setattr(cb, "MIN_TPU_BATCH", 2)
    monkeypatch.setattr(cb, "_tpu_available", True)
    monkeypatch.setattr(cb.AdaptiveBatchVerifier, "_make_tpu_verifier", lambda self: Dead())
    try:
        bv = cb.AdaptiveBatchVerifier()
        for it in items:
            bv.add(*it)
        ok, bitmap = bv.verify()
        assert ok and all(bitmap) and bv.last_route == "cpu-fallback"
        assert bt.ROUTES == {"cpu-fallback": [1.0, 4.0]}
        assert bt.BACKEND["fallbacks"] == 1
        monkeypatch.setattr(cb, "_tpu_available", False)
        bv = cb.AdaptiveBatchVerifier()
        for it in items:
            bv.add(*it)
        assert bv.verify()[0] and bv.last_route == "cpu"
        assert bt.ROUTES["cpu"] == [1.0, 4.0]
        assert bt.snapshot()["routes"]["cpu-fallback"] == [1.0, 4.0]
    finally:
        breaker.record_success()
        bt.reset()
