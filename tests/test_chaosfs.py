"""Chaos-fs storage fault injection + WAL crash repair (libs/chaosfs.py,
consensus/wal.py) and the new chaos-net fault classes (asymmetric
partitions, bandwidth shaping, gray failures, clock skew)."""

import os
import subprocess
import sys

import pytest

from tendermint_tpu.consensus.wal import WAL
from tendermint_tpu.libs.chaos import ChaosConfig, ChaosNetwork
from tendermint_tpu.libs.chaosfs import ChaosDB, ChaosFS, ChaosFSConfig
from tendermint_tpu.libs.clock import ManualClock, SkewedClock
from tendermint_tpu.libs.metrics import STORAGE
from tendermint_tpu.store.db import MemDB

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _fill(wal: WAL, n: int = 5, sync: bool = True) -> list[bytes]:
    payloads = [bytes([i]) * (10 + i) for i in range(n)]
    for p in payloads:
        (wal.write_sync if sync else wal.write)(p)
    return payloads


class TestChaosFSCrashModel:
    def test_fsynced_records_always_survive(self, tmp_path):
        fs = ChaosFS(ChaosFSConfig(seed=1))
        wal = WAL(str(tmp_path / "w"), fs=fs)
        payloads = _fill(wal, 5, sync=True)
        wal.write(b"buffered-not-synced")
        fs.halt()
        wal.close()
        fs.simulate_crash()
        wal2 = WAL(str(tmp_path / "w"), fs=fs)
        # crash at a record boundary: the buffered tail vanishes cleanly
        assert [r.data for r in wal2.iter_records()] == payloads
        assert wal2.last_repair == []
        wal2.close()

    def test_torn_write_repaired_to_last_whole_record(self, tmp_path):
        fs = ChaosFS(ChaosFSConfig(seed=7, torn_write_rate=1.0))
        wal = WAL(str(tmp_path / "w"), fs=fs)
        payloads = _fill(wal, 5, sync=True)
        wal.write(b"torn-away-1")
        wal.write(b"torn-away-2")
        fs.halt()
        wal.close()
        fs.simulate_crash()
        assert fs.faults["torn_write"] == 1
        wal2 = WAL(str(tmp_path / "w"), fs=fs)
        got = [r.data for r in wal2.iter_records()]
        # a partial mid-record tail was rotated aside, whole prefix kept
        assert got == payloads[: len(got)] and len(got) >= 5
        if wal2.last_repair:
            rep = wal2.last_repair[0]
            assert os.path.exists(rep.tail_path)
            assert os.path.getsize(rep.path) == rep.valid_end
            # and the head is appendable again after repair
            wal2.write_sync(b"after-restart")
            assert [r.data for r in wal2.iter_records()][-1] == b"after-restart"
        wal2.close()

    def test_lost_fsync_is_acked_but_not_durable(self, tmp_path):
        fs = ChaosFS(ChaosFSConfig(seed=3, lost_fsync_rate=1.0))
        wal = WAL(str(tmp_path / "w"), fs=fs)
        _fill(wal, 4, sync=True)  # every fsync acked, none durable
        fs.halt()
        wal.close()
        fs.simulate_crash()
        assert fs.faults["lost_fsync"] >= 4
        wal2 = WAL(str(tmp_path / "w"), fs=fs)
        assert list(wal2.iter_records()) == []
        wal2.close()

    def test_enospc_mid_record_rolls_back_partial_frame(self, tmp_path):
        fs = ChaosFS(ChaosFSConfig(seed=1, enospc_at_byte=40))
        wal = WAL(str(tmp_path / "w"), fs=fs)
        with pytest.raises(OSError):
            _fill(wal, 5, sync=True)
        assert fs.faults["enospc"] == 1
        # the partial frame was truncated away inline: no garbage gap,
        # and the trigger is one-shot so the "restarted" WAL can write
        wal.write_sync(b"after-enospc")
        fs.halt()
        wal.close()
        fs.simulate_crash()
        wal2 = WAL(str(tmp_path / "w"), fs=fs)
        recs = [r.data for r in wal2.iter_records()]
        assert recs and recs[-1] == b"after-enospc"
        wal2.close()

    def test_repair_survives_enospc_during_salvage(self, tmp_path):
        """Disk still full at restart: the forensic tail-salvage write
        fails with ENOSPC, but repair degrades (truncate without salvage)
        instead of turning the restart into a startup failure."""
        fs = ChaosFS(ChaosFSConfig(seed=7, torn_write_rate=1.0))
        wal = WAL(str(tmp_path / "w"), fs=fs)
        payloads = _fill(wal, 5, sync=True)
        wal.write(b"torn-away-1")
        wal.write(b"torn-away-2")
        fs.halt()
        wal.close()
        fs.simulate_crash()  # seed 7 tears mid-record (repair will fire)

        fs2 = ChaosFS(ChaosFSConfig(seed=1, enospc_at_byte=0))  # disk full NOW
        wal2 = WAL(str(tmp_path / "w"), fs=fs2)  # must not raise
        assert wal2.last_repair and wal2.last_repair[0].tail_path == ""
        assert not os.path.exists(str(tmp_path / "w" / "wal.corrupt.0"))
        got = [r.data for r in wal2.iter_records()]
        assert got == payloads[: len(got)] and len(got) >= 5
        wal2.write_sync(b"after")  # one-shot ENOSPC already spent
        wal2.close()

    def test_bitrot_detected_and_truncated_with_metric(self, tmp_path):
        fs = ChaosFS(ChaosFSConfig(seed=9))
        wal = WAL(str(tmp_path / "w"), fs=fs)
        payloads = _fill(wal, 6, sync=True)
        wal.close()
        before = STORAGE["wal_corrupt_records"]
        rot = ChaosFS(ChaosFSConfig(seed=2, bitrot_rate=0.3))
        wal2 = WAL.__new__(WAL)  # read through the rotten fs WITHOUT repair
        wal2.dir = str(tmp_path / "w")
        wal2.fs = rot
        wal2._head_path = os.path.join(wal2.dir, "wal")
        wal2._f = None
        import logging

        wal2.logger = logging.getLogger("wal-test")
        got = [r.data for r in wal2.iter_records()]
        # bit-rot either missed (full read) or truncated at the flip —
        # never garbage records, and never silent: the metric moved
        assert got == payloads[: len(got)]
        if len(got) < len(payloads):
            assert rot.faults["bitrot"] >= 1
            assert STORAGE["wal_corrupt_records"] > before

    def test_same_seed_same_crash(self, tmp_path):
        """Bit-reproducibility: two identical op sequences under the same
        seed crash to byte-identical survivors."""
        sizes = []
        for run in range(2):
            fs = ChaosFS(ChaosFSConfig(seed=42, torn_write_rate=0.5, lost_fsync_rate=0.3))
            wal = WAL(str(tmp_path / f"w{run}"), fs=fs)
            _fill(wal, 8, sync=True)
            fs.halt()
            wal.close()
            fs.simulate_crash()
            path = str(tmp_path / f"w{run}" / "wal")
            with open(path, "rb") as f:
                sizes.append(f.read())
        assert sizes[0] == sizes[1]


class TestChaosDB:
    def test_enospc_and_bitrot(self):
        fs = ChaosFS(ChaosFSConfig(seed=5, enospc_rate=1.0))
        db = ChaosDB(fs, MemDB())
        with pytest.raises(OSError):
            db.set(b"k", b"v")
        with pytest.raises(OSError):
            db.write_batch([(b"k", b"v")])
        assert fs.faults["db_enospc"] == 2
        assert db.get(b"k") is None  # batch applied nothing

        fs2 = ChaosFS(ChaosFSConfig(seed=5, bitrot_rate=1.0))
        db2 = ChaosDB(fs2, MemDB())
        db2.set(b"k", b"value")
        assert db2.get(b"k") != b"value"  # exactly one flipped byte
        assert fs2.faults["db_bitrot"] == 1


class TestChaosNetNewFaults:
    def test_asymmetric_partition(self):
        net = ChaosNetwork(ChaosConfig(seed=1))
        net.partition_oneway("a", "b")
        assert net.plan("a", "b", 0).drop  # a→b dies
        assert not net.plan("b", "a", 0).drop  # b→a flows
        assert net.faults["asym_drop"] == 1
        net.heal()
        assert not net.plan("a", "b", 0).drop

    def test_bandwidth_shaping_queue_buildup(self):
        net = ChaosNetwork(ChaosConfig(seed=1, bandwidth_rate=1000.0))
        d1 = net.plan("a", "b", 0, nbytes=500, now=10.0).delay_s
        d2 = net.plan("a", "b", 0, nbytes=500, now=10.0).delay_s
        d3 = net.plan("a", "b", 0, nbytes=500, now=10.0).delay_s
        # each 500B message takes 0.5s on a 1000B/s link; the queue builds
        assert abs(d1 - 0.5) < 1e-9 and abs(d2 - 1.0) < 1e-9 and abs(d3 - 1.5) < 1e-9
        assert net.faults["shaped"] == 2  # msgs 2 and 3 queued behind msg 1
        # another link has its own bucket
        assert abs(net.plan("a", "c", 0, nbytes=500, now=10.0).delay_s - 0.5) < 1e-9

    def test_gray_failure_fixed_delay(self):
        net = ChaosNetwork(ChaosConfig(seed=1))
        net.set_gray("b", delay_ms=150.0)
        p = net.plan("a", "b", 0)
        assert not p.drop and abs(p.delay_s - 0.15) < 1e-9
        assert net.faults["gray_delay"] == 1
        assert net.plan("a", "c", 0).delay_s == 0.0  # only the gray peer crawls

    def test_clock_skew_deterministic_per_node(self):
        net1 = ChaosNetwork(ChaosConfig(seed=11, clock_skew_ms=100.0))
        net2 = ChaosNetwork(ChaosConfig(seed=11, clock_skew_ms=100.0))
        base = ManualClock(1_000_000_000)
        c1 = net1.clock_for("nodeA", base=base)
        # order-independent: hand out B first on the second controller
        net2.clock_for("nodeB", base=base)
        c2 = net2.clock_for("nodeA", base=base)
        assert isinstance(c1, SkewedClock)
        assert c1.offset_ns == c2.offset_ns
        assert abs(c1.offset_ns) <= 100_000_000
        assert c1.now_ns() == 1_000_000_000 + c1.offset_ns
        # different seed → different offset
        c3 = ChaosNetwork(ChaosConfig(seed=12, clock_skew_ms=100.0)).clock_for(
            "nodeA", base=base
        )
        assert c3.offset_ns != c1.offset_ns
        # fault class off → base clock untouched
        off = ChaosNetwork(ChaosConfig(seed=11)).clock_for("nodeA", base=base)
        assert off is base

    def test_clock_drift_scales_timeouts(self):
        net = ChaosNetwork(ChaosConfig(seed=4, clock_drift=0.1))
        c = net.clock_for("nodeA")
        assert c.rate != 1.0 and abs(c.rate - 1.0) <= 0.1
        # a fast clock waits LESS real time for the same nominal duration
        assert abs(c.timeout_s(1_000_000_000) - 1.0 / c.rate) < 1e-9
        # drawn from (seed, node_id): reproducible, order-independent
        assert ChaosNetwork(ChaosConfig(seed=4, clock_drift=0.1)).clock_for(
            "nodeA"
        ).rate == c.rate


def test_fs_callsite_lint_clean():
    """tmtlint's fs rules are the tier-1 guard against storage writes
    sneaking around the injectable chaos-fs layer (the whole package: the
    transitive rule follows helpers in other files)."""
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO, "scripts", "tmtlint"),
         "--rule", "fs-discipline", "--rule", "transitive-fs", "tendermint_tpu"],
        cwd=REPO,
        capture_output=True,
        text=True,
        timeout=60,
    )
    assert proc.returncode == 0, proc.stdout + proc.stderr
