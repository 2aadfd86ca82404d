"""Configuration tree (reference config/config.go:70). Grows with the
framework; each section mirrors a reference config struct. TOML
load/save lives with the CLI."""

from __future__ import annotations

from dataclasses import dataclass, field

MS = 1_000_000  # ns per millisecond


@dataclass
class ConsensusConfig:
    """Timeouts in nanoseconds (reference config/config.go:1069-1093).
    Per-round growth: timeout = base + delta * round."""

    timeout_propose_ns: int = 3_000 * MS
    timeout_propose_delta_ns: int = 500 * MS
    timeout_prevote_ns: int = 1_000 * MS
    timeout_prevote_delta_ns: int = 500 * MS
    timeout_precommit_ns: int = 1_000 * MS
    timeout_precommit_delta_ns: int = 500 * MS
    timeout_commit_ns: int = 1_000 * MS
    skip_timeout_commit: bool = False
    create_empty_blocks: bool = True
    create_empty_blocks_interval_ns: int = 0
    double_sign_check_height: int = 0
    wal_path: str = "data/cs.wal"
    # two-stage pipelined ingest (consensus/ingest.py): stage 1 verifies
    # incoming vote/proposal signatures CONCURRENTLY through the async
    # VerifyHub API (filling device-sized micro-batches from one node),
    # stage 2 applies in strict arrival order via a reorder buffer.
    # ingest_max_inflight bounds the in-flight verifications per node
    # (backpressure into the reactor beyond it). Env mirrors:
    # TMTPU_INGEST_PIPELINE=0 disables, TMTPU_INGEST_INFLIGHT overrides.
    ingest_pipeline: bool = True
    ingest_max_inflight: int = 64
    # commit wire scheme: "per-sig" stores one signature per validator
    # (any key type, EdDSA-batch verified); "bls-aggregate" folds a
    # BLS validator set's precommits into ONE 96-byte aggregate at
    # commit materialization (O(1) signature bytes per commit, pairing
    # verify). Aggregation silently falls back to per-sig when any
    # participating signer is not bls12381 (mixed sets). Env mirror:
    # TMTPU_COMMIT_SCHEME (wins over TOML).
    commit_scheme: str = "per-sig"

    def propose_timeout_ns(self, round_: int) -> int:
        return self.timeout_propose_ns + self.timeout_propose_delta_ns * round_

    def prevote_timeout_ns(self, round_: int) -> int:
        return self.timeout_prevote_ns + self.timeout_prevote_delta_ns * round_

    def precommit_timeout_ns(self, round_: int) -> int:
        return self.timeout_precommit_ns + self.timeout_precommit_delta_ns * round_

    def commit_time_ns(self, t_ns: int) -> int:
        return t_ns + self.timeout_commit_ns


@dataclass
class MempoolIngressConfig:
    """TxIngress — the staged tx admission pipeline in front of the
    priority mempool (mempool/ingress.py): bounded intake with explicit
    backpressure, envelope signature pre-verification micro-batched
    through the VerifyHub backfill lane, per-sender nonce lanes, and
    deterministic in-order admission. TOML section `[mempool.ingress]`;
    env mirrors (win over TOML, the VerifyHub contract):
    TMTPU_INGRESS_DISABLE=1, TMTPU_INGRESS_DEPTH,
    TMTPU_INGRESS_WORKERS, TMTPU_INGRESS_LANE_DEPTH,
    TMTPU_INGRESS_PARK_MS."""

    enabled: bool = True
    # total occupancy bound from accepted submit to insert/park: a full
    # pipeline rejects-with-busy (shed) instead of buffering unboundedly
    depth: int = 2048
    # concurrent stage-A (parse + signature pre-verify) workers; the
    # reorder buffer restores strict arrival order behind them
    verify_workers: int = 8
    # parked out-of-order txs per sender nonce lane
    nonce_lane_depth: int = 32
    # a nonce gap older than this (injected-clock wall domain) evicts
    # every tx parked behind it
    nonce_park_timeout_ms: float = 3000.0
    # stage-B release slice width: consecutive in-release-order entries
    # whose ABCI CheckTx calls are prefetched concurrently (the
    # `_recheck` shape) before serial in-order admission consumes them.
    # 1 (default) is byte-for-byte the serial semantics; >1 collapses
    # the one-RTT-per-tx cost on remote-socket apps. Env mirror:
    # TMTPU_INGRESS_CHECKTX_BATCH.
    checktx_batch: int = 1


@dataclass
class MempoolConfig:
    """Reference config/config.go:800-860."""

    size: int = 5000
    max_txs_bytes: int = 1024 * 1024 * 1024
    cache_size: int = 10000
    keep_invalid_txs_in_cache: bool = False
    max_tx_bytes: int = 1024 * 1024
    recheck: bool = True
    broadcast: bool = True
    ttl_num_blocks: int = 0
    ttl_duration_ns: int = 0
    # post-commit re-CheckTx batch width: the resident set is re-checked
    # in concurrent slices of this many ABCI calls instead of N
    # sequential round-trips (mempool/pool.py _recheck)
    recheck_batch: int = 64
    # max peers each resident tx is gossiped to (0 = unlimited); the
    # reactor also never echoes a tx back to the peer(s) it arrived from
    gossip_fanout: int = 8
    ingress: MempoolIngressConfig = field(default_factory=MempoolIngressConfig)


@dataclass
class EvidenceConfig:
    """Evidence-related consensus params live in types/params.py; this is
    pool sizing."""

    max_pending: int = 1000


@dataclass
class P2PConfig:
    """Reference config/config.go P2PConfig."""

    laddr: str = "0.0.0.0:26656"
    persistent_peers: str = ""  # comma-separated tcp://id@host:port
    seeds: str = ""  # seed nodes: dialed once for addresses, then drop
    max_connections: int = 16
    # flow-rate limits, bytes/sec per connection (reference
    # config/config.go SendRate/RecvRate, default 5.12 MB/s); 0 = unlimited
    send_rate: int = 5_120_000
    recv_rate: int = 5_120_000


@dataclass
class RPCConfig:
    """Reference config/config.go RPCConfig."""

    laddr: str = "127.0.0.1:26657"
    enable: bool = True
    # serve /debug/pprof/* (reference pprof-laddr, config.go:529) —
    # opt-in: profiling slows the event loop
    pprof: bool = False
    # event-loop liveness watchdog (libs/watchdog.py — the deadlock-
    # detector analog, reference internal/libs/sync/deadlock.go): dump
    # all stacks to <home>/data/debug when the loop wedges past the
    # threshold. Opt-in.
    watchdog: bool = False
    watchdog_threshold_s: float = 5.0


@dataclass
class ChaosNetConfig:
    """Chaos-net fault injection (libs/chaos.py). Off by default; when
    `enabled`, every transport the node constructs is wrapped in the
    seeded fault-injection layer. The same knobs are reachable without a
    config file through TMTPU_CHAOS_* env vars (libs/chaos.py docstring);
    a fixed seed makes a fault schedule reproducible."""

    enabled: bool = False
    seed: int = 0
    drop_rate: float = 0.0  # per-message drop probability
    delay_ms: float = 0.0  # p50 extra latency (exponential tail)
    duplicate_rate: float = 0.0
    reorder_rate: float = 0.0
    corrupt_rate: float = 0.0
    bandwidth_rate: float = 0.0  # per-link cap, bytes/sec (queue buildup)
    gray_delay_ms: float = 0.0  # gray failure: fixed slow-but-alive delay
    clock_skew_ms: float = 0.0  # max |per-validator clock skew|
    clock_drift: float = 0.0  # max |rate error| (timeouts fire early/late)


@dataclass
class ChaosFSConfig:
    """Chaos-fs storage fault injection (libs/chaosfs.py). Off by
    default; when `enabled`, the node's WAL rides the seeded
    fault-injecting FS and the block/state DBs are wrapped in `ChaosDB`.
    Env mirror: TMTPU_CHAOS_FS_* (libs/chaosfs.py docstring)."""

    enabled: bool = False
    seed: int = 0
    torn_write_rate: float = 0.0  # P(crash leaves a partial, mid-record tail)
    torn_offset: int = -1  # fixed tear offset into the un-fsynced tail
    lost_fsync_rate: float = 0.0  # P(fsync acked but not durable)
    enospc_rate: float = 0.0  # P(write fails ENOSPC mid-record)
    enospc_at_byte: int = -1  # arm ENOSPC at an exact cumulative byte
    bitrot_rate: float = 0.0  # P(read returns one flipped byte)


@dataclass
class VerifyHubConfig:
    """VerifyHub — the node-wide micro-batching signature-verification
    scheduler (crypto/verify_hub.py). Same knobs via TMTPU_VERIFYHUB_*
    env vars; TMTPU_VERIFYHUB_DISABLE=1 force-bypasses the hub even when
    `enabled` is true. Mesh knobs ride the TMTPU_MESH_* env family:
    TMTPU_MESH_SCALE=0 pins single-chip batch sizing, and the dispatch
    layer reads TMTPU_MESH_MAX_DEVICES / TMTPU_MESH_BREAKER_RESET /
    TMTPU_MESH_PROBE_TIMEOUT (crypto/tpu/mesh.py)."""

    enabled: bool = True
    # max_batch and window_ms govern LONE requests (a vote, a proposal,
    # an evidence vote): how many of them make a dispatch, per chip, and
    # the ceiling of how long one lingers for company (adaptive below
    # it). A group (verify_many: a commit's signatures, a block-sync
    # range's) is one unit: flushed at once, never split at max_batch,
    # and past max_batch rows dispatched at the verifier's chunk shape
    max_batch: int = 512
    window_ms: float = 2.0
    cache_size: int = 8192  # verified-(pubkey,msg,sig) LRU entries
    # scale batch capacity + adaptive window by the ACTIVE device-mesh
    # size, so an 8-chip mesh is fed 8× batches (and a degraded mesh
    # shrinks them again); TMTPU_MESH_SCALE env overrides
    mesh_scale: bool = True
    # verification sidecar (crypto/verifyd.py): path of a running
    # verifyd daemon's Unix socket. When set, the hub ships its packed
    # cold micro-batches there instead of dispatching locally — N node
    # processes on one host share the daemon's single warm device mesh
    # and compile cache. A daemon crash degrades to inline local
    # verification through a circuit breaker (never a liveness event).
    # Env mirror: TMTPU_VERIFYD_SOCK (wins over TOML).
    verifyd_sock: str = ""


@dataclass
class LightDConfig:
    """LightD — the light-client serving layer (light/fleet.py): one
    verified-hop cache + aggregate hop proofs served to a client fleet.
    Env mirrors win over TOML (the VerifyHub contract):
    TMTPU_LIGHTD_SESSIONS / TMTPU_LIGHTD_PROOF_CACHE /
    TMTPU_LIGHTD_AGG_HOPS=0."""

    #: concurrent verification sessions before arrivals are rejected
    #: with busy (LightDBusyError — the ingress backpressure contract;
    #: cache hits and coalesced same-height joins never shed)
    max_sessions: int = 64
    #: hop proofs kept per LightD, encodings memoized (insertion-evicted)
    proof_cache: int = 1024
    #: fold BLS committees' hop commits into the 96-byte aggregate wire
    #: variant (verified through verify_hub.verify_aggregate — one
    #: pairing per hop); per-sig fallback applies either way for
    #: non-BLS committees
    aggregate_hops: bool = True
    #: sequential (adjacent-chain) verification instead of skipping —
    #: the audit/archival shape; skipping is the serving default
    sequential: bool = False


@dataclass
class BootDConfig:
    """BootD — the mass snapshot-serving layer (statesync/fleet.py):
    bounded concurrent chunk sessions + a shared per-snapshot chunk
    cache in front of the app's snapshot store, plus the manifest loop
    that commits/prunes served snapshots on a height interval off the
    consensus hot path. Env mirrors win over TOML (the VerifyHub
    contract): TMTPU_BOOTD_SESSIONS / TMTPU_BOOTD_CHUNK_CACHE /
    TMTPU_BOOTD_REFRESH_S."""

    #: concurrent chunk-loading sessions before arrivals are rejected
    #: with busy (BootDBusyError — shed is backpressure, not failure;
    #: cache hits and coalesced same-chunk joins never shed)
    max_sessions: int = 32
    #: chunk bytes kept in the shared cache (entries, insertion-evicted):
    #: N concurrent joiners amortize each store read to ONE
    chunk_cache: int = 256
    #: manifest refresh cadence (seconds): how often the serving
    #: manifest re-reads ListSnapshots and prunes dead chunk bytes
    refresh_s: float = 2.0
    #: serve only snapshots whose height is a multiple of this interval
    #: (1 = every snapshot the app took); pruned entries leave the
    #: manifest AND the chunk cache on the next refresh
    snapshot_interval: int = 1
    #: backfilled commits verified per hub batch (the backfill lane
    #: mega-batching window)
    backfill_batch: int = 64


@dataclass
class TraceConfig:
    """Flight-recorder tracing (libs/trace.py): structured spans over
    the verify funnel landing in a bounded per-process ring buffer,
    served at /debug/traces and dumped automatically on wedge/breaker
    trip. Env mirrors win over TOML: TMTPU_TRACE=0 disables,
    TMTPU_TRACE_RING sizes the ring, TMTPU_TRACE_DIR points auto-dumps
    at a directory."""

    enabled: bool = True
    ring_size: int = 4096  # spans kept; oldest dropped when full
    dump_dir: str = ""  # where auto-dumps land; empty = in-memory only


@dataclass
class StateSyncConfig:
    """Reference config statesync section."""

    enable: bool = False
    trust_height: int = 0
    trust_hash: str = ""
    trust_period_ns: int = 7 * 24 * 3600 * 10**9


@dataclass
class BlockSyncConfig:
    enable: bool = True


@dataclass
class Config:
    """The full node config tree (reference config/config.go:70),
    TOML-serialized in <home>/config/config.toml."""

    moniker: str = "node"
    # node mode (reference config BaseConfig.Mode, 0.35): "validator",
    # "full", or "seed" (p2p address-crawler only, node/node.go:490)
    mode: str = "validator"
    proxy_app: str = "kvstore"  # builtin app name (socket ABCI later)
    consensus: ConsensusConfig = field(default_factory=ConsensusConfig)
    mempool: MempoolConfig = field(default_factory=MempoolConfig)
    p2p: P2PConfig = field(default_factory=P2PConfig)
    rpc: RPCConfig = field(default_factory=RPCConfig)
    statesync: StateSyncConfig = field(default_factory=StateSyncConfig)
    blocksync: BlockSyncConfig = field(default_factory=BlockSyncConfig)
    chaos: ChaosNetConfig = field(default_factory=ChaosNetConfig)
    chaos_fs: ChaosFSConfig = field(default_factory=ChaosFSConfig)
    verify_hub: VerifyHubConfig = field(default_factory=VerifyHubConfig)
    lightd: LightDConfig = field(default_factory=LightDConfig)
    bootd: BootDConfig = field(default_factory=BootDConfig)
    trace: TraceConfig = field(default_factory=TraceConfig)


def _section_to_toml(name: str, obj) -> str:
    lines = [f"[{name}]"]
    nested: list[str] = []
    for k, v in obj.__dict__.items():
        if hasattr(v, "__dataclass_fields__"):
            # nested section ([mempool.ingress]) — TOML requires it to
            # come after the parent table's own keys
            nested.append(_section_to_toml(f"{name}.{k}", v))
        elif isinstance(v, bool):
            lines.append(f"{k} = {'true' if v else 'false'}")
        elif isinstance(v, (int, float)):
            lines.append(f"{k} = {v}")
        else:
            lines.append(f'{k} = "{v}"')
    return "\n".join(lines + ([""] if nested else []) + nested)


def config_to_toml(cfg: Config) -> str:
    """Serialize (reference config/toml.go template)."""
    parts = [
        f'moniker = "{cfg.moniker}"',
        f'proxy_app = "{cfg.proxy_app}"',
        "",
        _section_to_toml("consensus", cfg.consensus),
        "",
        _section_to_toml("mempool", cfg.mempool),
        "",
        _section_to_toml("p2p", cfg.p2p),
        "",
        _section_to_toml("rpc", cfg.rpc),
        "",
        _section_to_toml("statesync", cfg.statesync),
        "",
        _section_to_toml("blocksync", cfg.blocksync),
        "",
        _section_to_toml("chaos", cfg.chaos),
        "",
        _section_to_toml("chaos_fs", cfg.chaos_fs),
        "",
        _section_to_toml("verify_hub", cfg.verify_hub),
        "",
        _section_to_toml("lightd", cfg.lightd),
        "",
        _section_to_toml("bootd", cfg.bootd),
        "",
        _section_to_toml("trace", cfg.trace),
        "",
    ]
    return "\n".join(parts)


def config_from_toml(text: str) -> Config:
    try:
        import tomllib  # stdlib from 3.11
    except ModuleNotFoundError:  # 3.10 images: tomli is the same parser
        import tomli as tomllib

    data = tomllib.loads(text)
    cfg = Config()
    cfg.moniker = data.get("moniker", cfg.moniker)
    cfg.proxy_app = data.get("proxy_app", cfg.proxy_app)
    for section, obj in (
        ("consensus", cfg.consensus),
        ("mempool", cfg.mempool),
        ("p2p", cfg.p2p),
        ("rpc", cfg.rpc),
        ("statesync", cfg.statesync),
        ("blocksync", cfg.blocksync),
        ("chaos", cfg.chaos),
        ("chaos_fs", cfg.chaos_fs),
        ("verify_hub", cfg.verify_hub),
        ("lightd", cfg.lightd),
        ("bootd", cfg.bootd),
        ("trace", cfg.trace),
    ):
        _apply_section(obj, data.get(section, {}))
    return cfg


def _apply_section(obj, values: dict) -> None:
    for k, v in values.items():
        if not hasattr(obj, k):
            continue
        cur = getattr(obj, k)
        if isinstance(v, dict) and hasattr(cur, "__dataclass_fields__"):
            _apply_section(cur, v)  # nested table, e.g. [mempool.ingress]
        elif not isinstance(v, dict):
            setattr(obj, k, v)
