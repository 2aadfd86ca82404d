"""Bring-up regressions that only a TPU would otherwise reach:

  * the Pallas self-test (`verify._choose_formulation`) returns early off
    TPU, so no CPU test ever ran its body — a missing import inside it went
    unnoticed and was swallowed on the chip. Here the backend gate is
    patched in-test and the Pallas kernels run in interpret mode at tiny
    widths, so the whole self-test executes on the CPU;
  * the self-test holds each Pallas kernel to a KNOWN ANSWER the host
    computes over Python integers (no XLA twin is traced beside it): the
    answers are themselves held to the XLA formulation the CPU runs, and a
    kernel with one wrong limb fails its own stage;
  * a Pallas failure on a TPU is loud (recorded, counted, WARNING) and
    leaves the ONE formulation switch off, not INFO-and-carry-on;
  * the formulation has one switch: field.mul, field.pow22523 and the
    MSM's block scan all follow `field.set_pallas`, and nothing else;
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
from tendermint_tpu.libs import trace

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _record_calls(monkeypatch, log, name, fn):
    """Put `fn` in pallas_field.<name>'s place, noting every call in `log`."""

    def stand_in(*a, **k):
        log.append(name)
        return fn(*a, **k)

    monkeypatch.setattr(PF, name, stand_in)


@pytest.fixture
def probe_on_cpu(monkeypatch):
    """Drive the self-test past its backend gate on the CPU: tiny widths,
    Pallas in interpret mode, the switch restored afterwards. Yields the
    list of values `field.set_pallas` was called with."""
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    # the self-test sizes its MSM as _BLOCK * TILE points: 32 points in
    # blocks of 4 -> 8 block lanes == the patched TILE, so msm routes the
    # in-block scan through the Pallas gate (g % TILE == 0)
    monkeypatch.setattr(M, "_BLOCK", 4)
    monkeypatch.setattr(PF, "TILE", 8)
    pallas_mul = PF.mul

    def mul(a, b):
        # the self-test's own multiply runs the kernel (interpreted); inside
        # the MSM every multiply would go through the interpreter under vmap
        # — a minute of XLA-CPU compile — so there the GEMM stands in
        if a.shape == b.shape == (PF.TILE, 32):
            return pallas_mul(a, b, interpret=True)
        return F._mul_gemm(a, b)

    monkeypatch.setattr(PF, "mul", mul)
    monkeypatch.setattr(
        PF, "scan_blocks", functools.partial(PF.scan_blocks, interpret=True, tile=8)
    )
    # the fused pow22523 kernel is a 254-multiply chain: minutes in
    # interpret mode (its own test is `slow`). The self-test's control
    # flow is what this guards, so the XLA chain stands in for it.
    monkeypatch.setattr(PF, "pow22523", F._pow22523_chain)
    monkeypatch.setattr(V, "field_mul_probe", {})
    switched = []
    set_pallas = F.set_pallas

    def recording_set_pallas(on):
        switched.append(on)
        set_pallas(on)

    monkeypatch.setattr(F, "set_pallas", recording_set_pallas)
    before = dict(bt.BACKEND)
    yield switched
    set_pallas(False)
    bt.BACKEND.update(before)


STAGES = ["mul", "pow22523", "scan_blocks"]


@pytest.fixture
def recorder():
    was = trace.RECORDER.enabled
    trace.RECORDER.enabled = True
    trace.RECORDER.clear()
    yield trace.RECORDER
    trace.RECORDER.clear()
    trace.RECORDER.enabled = was


def _proof_spans(recorder):
    return [x.get("attrs", {}) for x in recorder.dump()
            if (x["subsystem"], x["name"]) == ("backend", "pallas_ab")]


@pytest.fixture
def traced_programs(monkeypatch):
    """Every `jax.jit` the self-test makes, as the state of the formulation
    switch at that moment: an XLA twin would show as a False."""
    states = []
    jit = jax.jit

    def recording_jit(fn, *a, **k):
        states.append(F._USE_PALLAS)
        return jit(fn, *a, **k)

    monkeypatch.setattr(jax, "jit", recording_jit)
    return states


def _one_wrong_limb(fn):
    """`fn`'s result with the lowest bit of one limb flipped."""

    def wrong(*a, **k):
        out = fn(*a, **k)
        if isinstance(out, tuple):  # scan_blocks: four coordinate arrays
            return (out[0].at[-1, -1, 3].set(out[0][-1, -1, 3] ^ 1),) + tuple(out[1:])
        return out.at[-1, 3].set(out[-1, 3] ^ 1)

    return wrong


def test_pallas_self_test_runs_clean_past_the_backend_gate(
    probe_on_cpu, monkeypatch, recorder, traced_programs
):
    ran = []
    for name in STAGES:
        _record_calls(monkeypatch, ran, name, getattr(PF, name))
    V._choose_formulation()
    probe = V.field_mul_probe
    assert not probe.get("error") and not probe.get("scan_error"), (
        f"error={probe.get('error')} scan_error={probe.get('scan_error')}"
    )
    assert bt.BACKEND["pallas_probe_errors"] == 0
    # all three kernels were reached and held to the host's answer
    assert set(STAGES) <= set(ran)
    assert probe == {"chosen": "pallas"}
    assert probe_on_cpu == [True] and F._USE_PALLAS
    # one span, three programs for three kernels, each traced with the
    # switch ON: no XLA twin beside any of them
    assert _proof_spans(recorder) == [{"chosen": "pallas", "programs": 3, "stages": 3}]
    assert traced_programs == [True, True, True]


def test_pallas_self_test_failure_is_loud(probe_on_cpu, monkeypatch, caplog):
    def broken(*_a, **_k):
        raise RuntimeError("mosaic refused the kernel")

    monkeypatch.setattr(PF, "scan_blocks", broken)
    with caplog.at_level(logging.WARNING, logger="crypto.tpu"):
        V._choose_formulation()
    assert "mosaic refused" in V.field_mul_probe["scan_error"]
    assert "error" not in V.field_mul_probe  # the mul/pow stage passed
    assert V.field_mul_probe["chosen"] == "xla"
    assert bt.BACKEND["pallas_probe_errors"] == 1
    assert any(
        r.levelno == logging.WARNING and "scan_error" in r.getMessage()
        for r in caplog.records
    )
    # ONE switch, and it is off: the all-XLA family, never a mixed one
    assert probe_on_cpu == [True, False] and not F._USE_PALLAS


@pytest.fixture
def tiny_tiles(monkeypatch):
    """The interpret-mode sizes of `probe_on_cpu` without its stand-ins: a
    field tile of 8 lanes, an MSM of 32 points in blocks of 4."""
    monkeypatch.setattr(M, "_BLOCK", 4)
    monkeypatch.setattr(PF, "TILE", 8)


@pytest.mark.parametrize("stage", STAGES)
def test_host_oracle_agrees_with_the_xla_formulation(stage, tiny_tiles):
    """The known answer is itself proven on every PR: the program of each
    stage, traced in the XLA formulation the CPU runs, returns what the
    host's integers say — and a single flipped bit of one limb does not."""
    import numpy as np

    assert not F._USE_PALLAS
    case = V._known_answer(stage)
    out = np.array(jax.jit(case.program)(*case.operands))
    case.check(out)
    out[..., -1, 3] ^= 1
    with pytest.raises(RuntimeError, match=f"pallas {stage} mismatch"):
        case.check(out)
    out[..., -1, 3] ^= 1
    out[..., 0, 0] = 512  # the right value mod p is not enough: the limb bound
    with pytest.raises(RuntimeError, match="limb outside"):
        case.check(out)


@pytest.mark.parametrize("stage", STAGES)
def test_a_wrong_pallas_kernel_fails_its_own_stage(
    stage, probe_on_cpu, monkeypatch, caplog, recorder, traced_programs
):
    """A Pallas kernel that returns ONE wrong limb ends the start on the
    all-XLA family, loudly, at its own stage; the stages after it are not
    run, and still no XLA twin was traced."""
    ran = []
    for name in STAGES:
        fn = getattr(PF, name)
        _record_calls(monkeypatch, ran, name, _one_wrong_limb(fn) if name == stage else fn)
    with caplog.at_level(logging.WARNING, logger="crypto.tpu"):
        V._choose_formulation()
    k = STAGES.index(stage)
    key, other = ("scan_error", "error") if stage == "scan_blocks" else ("error", "scan_error")
    assert f"pallas {stage}" in V.field_mul_probe[key] and other not in V.field_mul_probe
    assert V.field_mul_probe["chosen"] == "xla"
    assert probe_on_cpu == [True, False] and not F._USE_PALLAS
    assert bt.BACKEND["pallas_probe_errors"] == 1
    assert any(
        r.levelno == logging.WARNING and key in r.getMessage() for r in caplog.records
    )
    # later stages skipped: pow22523 and the scan are reached by no stage
    # before their own (the MSM of the scan stage multiplies, so `mul` is)
    assert not {"pow22523", "scan_blocks"} & set(STAGES[k + 1:]) & set(ran)
    assert _proof_spans(recorder) == [{"chosen": "xla", "programs": k + 1, "stages": k}]
    assert traced_programs == [True] * (k + 1)


@pytest.mark.parametrize("stage", STAGES)
def test_proof_span_counts_programs_and_stages(stage, probe_on_cpu, monkeypatch, recorder):
    """`backend.pallas_ab` is entered ONCE whatever happens, with `chosen`,
    `programs` (device programs the proof traced: never more than the three
    Pallas ones) and `stages` (kernels proven) — here with `stage` refused
    by the compiler, which is a program traced and a kernel not proven."""

    def refused(*_a, **_k):
        raise RuntimeError("mosaic refused the kernel")

    monkeypatch.setattr(PF, stage, refused)
    V._choose_formulation()
    (attrs,) = _proof_spans(recorder)
    k = STAGES.index(stage)
    assert attrs == {"chosen": "xla", "programs": k + 1, "stages": k}
    assert attrs["programs"] <= 3


def test_pallas_self_test_is_a_noop_off_tpu(monkeypatch):
    monkeypatch.setattr(V, "field_mul_probe", {})
    V._choose_formulation()  # conftest: the CPU backend
    assert V.field_mul_probe == {} and not F._USE_PALLAS


@pytest.mark.parametrize("on", [True, False])
def test_one_switch_routes_all_three_kernels(on, monkeypatch):
    """field.mul, field.pow22523 and an msm whose blocks fill a TILE reach
    the Pallas kernels when `field.set_pallas` is on and none of them when
    it is off — a fourth reader of a private flag would fail here."""
    from tendermint_tpu.crypto.tpu.curve import Point

    reached = []
    _record_calls(monkeypatch, reached, "mul", F._mul_gemm)
    _record_calls(monkeypatch, reached, "pow22523", F._pow22523_chain)
    _record_calls(
        monkeypatch, reached, "scan_blocks",
        functools.partial(PF.scan_blocks, interpret=True, tile=8),
    )
    monkeypatch.setattr(M, "_BLOCK", 4)
    monkeypatch.setattr(PF, "TILE", 8)
    a = jax.ShapeDtypeStruct((8, 32), jax.numpy.int32)
    pts = Point(*(jax.ShapeDtypeStruct((32, 32), jax.numpy.int32),) * 4)
    digs = jax.ShapeDtypeStruct((2, 32), jax.numpy.int32)

    F.set_pallas(on)
    try:  # the stand-ins record as the readers of the switch trace
        jax.eval_shape(lambda x: F.mul(x, x), a)
        jax.eval_shape(lambda x: F.pow22523(x), a)
        jax.eval_shape(lambda p, d: M.msm(p, d), pts, digs)
    finally:
        F.set_pallas(False)
    assert set(reached) == ({"mul", "pow22523", "scan_blocks"} if on else set())


@pytest.fixture
def cache_config(monkeypatch):
    """Record what _ensure_compile_cache would set, without setting it."""
    updates = {}
    monkeypatch.setattr(V, "_cache_ready", False)
    monkeypatch.setattr(V, "_choose_formulation", lambda: None)
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
