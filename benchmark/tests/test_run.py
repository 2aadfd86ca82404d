"""A whole run, minus the look for a chip, at a tiny size on the CPU: the
drivers' fixture, warm-up, window and comparison as `run.execute` drives
them — sound, and with the timed path broken underneath."""

import json
import os
import subprocess
import sys

import pytest

from benchmark import harness, run
from benchmark.tests import tiny

#: on the host route every device check reads false by design; what a
#: tiny CPU run can show is that every OTHER number compared holds
DEVICE_CHECKS = {"probe_errors", "tpu_route_sigs"}


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return tiny.make_root(str(tmp_path_factory.mktemp("bench")))


def _failed(result):
    return {k for k, c in result["checks"].items() if not c["ok"]}


@pytest.mark.parametrize("cell,metric", [
    ("tinylight.sequential", "light_headers_per_s"),
    ("tinyfull.blocksync", "blocksync_blocks_per_s"),
])
def test_sound_run(root, cell, metric):
    res = run.execute(root, cell, 3000000019, tiny.SECONDS, False, device=tiny.CPU_DEVICE)
    assert list(res)[-1] == "checks" and list(res)[:5] == [
        "correct", "attempted", "failed", "metrics", "device"]
    # the seconds blocked on the fixture child: a number of its own in every line
    assert res["fixture_wait_s"] >= 0
    if cell == "tinyfull.blocksync":
        # the chain outlasts its window with the real cells' room: twice what
        # the window consumed is still left
        used = res["checks"]["blocks_applied"]["value"]
        assert res["chain_left_blocks"] >= 2 * used > 0
        assert res["chain_left_blocks"] + used <= tiny.BLOCKS
    else:
        assert "chain_left_blocks" not in res
    assert _failed(res) == DEVICE_CHECKS and res["correct"] is False
    assert res["metrics"][metric]["value"] > 0 and res["metrics"]["setup_s"]["value"] > 0
    assert res["attempted"] > 0 and res["failed"] == 0
    assert {"platform", "kind", "count", "memory_peak_bytes"} <= set(res["device"])
    json.dumps(res)


@pytest.mark.parametrize("cell", ["tinylight.sequential", "tinyfull.blocksync"])
def test_traced_run_reports_layers_and_leaves_out_what_it_cannot_read(root, cell):
    res = run.execute(root, cell, 3000000023, tiny.SECONDS, True, device=tiny.CPU_DEVICE)
    assert res["fixture_wait_s"] >= 0 and ("chain_left_blocks" in res) == ("blocksync" in cell)
    names = set(res["metrics"])
    suffix = cell.split(".")[1].replace("sequential", "light")
    assert f"inline_compiles.{suffix}" in names and f"device_route_share.{suffix}" in names
    # no device ran an operation: shares of a roofline or of the device's
    # time are left out, never reported as 0
    assert not any(n.startswith(("kernel_", "device_idle")) for n in names)
    assert "breakdown" in res and {"busy_s", "window_s"} <= set(res["device"])
    if cell == "tinylight.sequential":  # the metric only the throw-away cell has
        assert 0 < res["metrics"]["verify_share.tiny"]["value"] <= 100


def test_traced_run_whose_chain_ends_says_so(tmp_path):
    """A chain cut short on purpose: the traced run raises with the cause and
    the workload file's `blocks`, not "the profiler wrote no trace"; the same
    chain untraced closes its window early and reads the one block left that
    no successor's commit vouches for."""
    from benchmark.drivers import blocksync

    short = tiny.make_root(str(tmp_path), blocks=70)
    with pytest.raises(blocksync.ChainEnded, match=r"70 blocks \(`traffic.blocks`"):
        run.execute(short, "tinyfull.blocksync", 3000000043, 30.0, True, device=tiny.CPU_DEVICE)
    res = run.execute(short, "tinyfull.blocksync", 3000000043, 30.0, False,
                      device=tiny.CPU_DEVICE)
    assert res["chain_left_blocks"] == 1 and res["checks"]["blocks_applied"]["value"] == 69


def test_faked_host_reverify_turns_correct_false(root, monkeypatch):
    """The program re-verifies on the host after a device error and hands
    back the same verdicts: only its telemetry tells."""
    from benchmark.drivers import light_sequential as drv
    from tendermint_tpu.crypto import backend_telemetry as bt

    real = drv.window

    def window(*a, **kw):
        w = real(*a, **kw)
        bt.BACKEND["fallbacks"] += 1
        bt.record_route("cpu-fallback", 101)
        return w

    monkeypatch.setattr(drv, "window", window)
    res = run.execute(root, "tinylight.sequential", 3000000029, 0.4, False,
                      device=tiny.CPU_DEVICE)
    assert "host_reverifies" in _failed(res) and res["correct"] is False
    assert res["checks"]["verdict_mismatches"]["ok"]  # the verdicts were right


def _accept_everything(*_a, **_kw):
    return None


def test_light_verify_that_checks_nothing_is_caught(root, monkeypatch):
    """A token altered where it is produced: verify_commit_range accepts
    whatever it is given. Honest traffic reads the same; the signature
    count and the warm-up's corrupted commit tell."""
    from tendermint_tpu.light import verifier

    monkeypatch.setattr(verifier, "verify_commit_range", _accept_everything)
    res = run.execute(root, "tinylight.sequential", 3000000031, 0.4, False,
                      device=tiny.CPU_DEVICE)
    assert {"sigs_verified_minus_needed", "warmup_refusal_height_delta"} <= _failed(res)
    assert res["correct"] is False


def test_blocksync_step_that_leaves_the_state_unchanged_is_caught(root, monkeypatch):
    """The timed path broken underneath: the app's Commit keeps its old
    hash (the step returns its state unchanged)."""
    from tendermint_tpu.abci import kvstore

    monkeypatch.setattr(kvstore, "_state_hash", lambda items: b"\x00" * 32)
    res = run.execute(root, "tinyfull.blocksync", 3000000037, tiny.SECONDS, False,
                      device=tiny.CPU_DEVICE)
    assert res["correct"] is False
    assert _failed(res) - DEVICE_CHECKS  # something other than the route


def test_blocksync_verify_that_checks_nothing_is_caught(root, monkeypatch):
    from tendermint_tpu.blocksync import reactor

    monkeypatch.setattr(reactor, "verify_commit_range", _accept_everything)
    monkeypatch.setattr(reactor, "verify_commit_light", _accept_everything)
    res = run.execute(root, "tinyfull.blocksync", 3000000041, tiny.SECONDS, False,
                      device=tiny.CPU_DEVICE)
    assert {"sigs_asked_minus_needed", "warmup_refusal_faults"} <= _failed(res)


def test_run_refuses_a_machine_without_a_tpu():
    """The real entry, the real BENCHMARK.json: no TPU, so no result line
    and a non-zero exit, before anything is built."""
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, os.path.join(harness.ROOT, "benchmark", "run.py"),
         "--workload", "light150.sequential", "--seed", "1", "--seconds", "1", "--trace", "0"],
        capture_output=True, text=True, timeout=300, env=env, cwd=harness.ROOT)
    assert p.returncode != 0
    assert p.stdout.strip() == ""
    assert "no TPU" in p.stderr
