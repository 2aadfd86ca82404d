"""Driver `light_mesh`: `light_sequential`'s light clients on a host whose
verifier spans a mesh of chips.

Fixture, spans, warm-up, window and the comparison with the plain reference
are `light_sequential`'s, unchanged: the same entry
(`light.LightClient.verify_light_block_at_height`, sequential mode, no hub),
the same traffic. What this driver adds is what only a mesh can get wrong,
each an exact check on the program's own per-device counters
(`backend_telemetry.SHARD_SIGS`, `MESH`), read from the window's opening to
the end of the run (the traced stretch included, as the signature counts
of `light_sequential.compare` are):

  mesh_devices_active               the dispatch mesh holds the cell's chips
  chips_without_signatures          no chip of it went without a real signature
  sharded_sigs_minus_range_needed   the mesh carried every signature the
                                    reference needs for the RANGE calls, no
                                    more and no fewer (`needed` without the
                                    sessions' trusted-header commits)

`correct` still never rests on the route of a single 101-signature commit:
those pad under the sharding gate, and the measured CPU/TPU cut-off decides
between the host and the first chip for them.
"""

from __future__ import annotations

from benchmark import harness
from benchmark import reference as ref
from benchmark.drivers import light_sequential as base
from benchmark.harness import Check, say

END_TO_END = base.END_TO_END
build = base.build
install = base.install
warmup = base.warmup
release = base.release


def _shard_sigs() -> dict:
    from tendermint_tpu.crypto import backend_telemetry as bt

    return dict(bt.SHARD_SIGS)


def window(fx, cfg: dict, cell: dict, seconds: float, patches: harness.Patches,
           trace, spans: harness.Spans, on_close=lambda: None):
    """`light_sequential.window`, with the per-device signature counters
    read at its opening and at the end of the run."""
    fx.observed["chips"] = int(cell["chips"])
    fx.observed["shard_sigs_open"] = _shard_sigs()
    w = base.window(fx, cfg, cell, seconds, patches, trace, spans, on_close)
    fx.observed["shard_sigs_end"] = _shard_sigs()
    return w


def range_sigs_needed(fx, w) -> int:
    """Signatures the plain reference needs to pass > 2/3 on the commits
    of every verify_adjacent_chain call the run made (up to a refusal)."""
    heights = sorted({h for _s, a, b, _r in w.calls for h in range(a, b + 1)})
    need = dict(zip(heights, (v[1] for v in ref.commit_verdicts(
        [fx.chain.commit_data(h) for h in heights]))))
    total = 0
    for _s, a, b, refused_at in w.calls:
        for h in range(a, b + 1):
            if refused_at and h > refused_at:
                break
            total += need[h]
    return total


def compare(fx, w, d: dict, spans: harness.Spans) -> tuple[list[Check], int, int]:
    import jax

    from tendermint_tpu.crypto import backend_telemetry as bt

    checks, attempted, failed = base.compare(fx, w, d, spans)
    opened, ended = fx.observed["shard_sigs_open"], fx.observed["shard_sigs_end"]
    moved = {str(dev.id): ended.get(str(dev.id), 0.0) - opened.get(str(dev.id), 0.0)
             for dev in jax.devices()[:fx.observed["chips"]]}
    sharded = sum(ended.values()) - sum(opened.values())
    needed = range_sigs_needed(fx, w)
    say(f"mesh: real signatures by device over the run {moved}; sharded {sharded:.0f}, "
        f"the reference needs {needed} for the range calls")
    checks += [
        Check("mesh_devices_active", bt.MESH["devices_active"], fx.observed["chips"], "min"),
        Check("chips_without_signatures", sum(1 for v in moved.values() if v <= 0), 0),
        Check("sharded_sigs_minus_range_needed", abs(sharded - needed), 0),
    ]
    return checks, attempted, failed
