"""TOML configuration — weed/util/config.go + command/scaffold.go analog
[VERIFY: mount empty; SURVEY.md §5 "Config/flag system"]: named TOML
files (security.toml, master.toml, filer.toml, shell.toml) searched in
`.`, `~/.seaweedfs_tpu/`, `/etc/seaweedfs_tpu/`; `scaffold` prints
commented templates. Parsing uses stdlib tomllib.

Also the typed WEEDTPU_* environment-variable registry: every env knob
the package reads is declared here ONCE (name, type, default, doc) and
read through `env()`. weedlint's env-registry checker flags any raw
`os.environ`/`os.getenv` read elsewhere in the package, and the README
env-var table is generated from this registry — so the docs, the
defaults, and the code cannot drift apart."""

from __future__ import annotations

import dataclasses
import os
from typing import Any, Callable, Optional

try:  # stdlib on 3.11+; this image runs 3.10
    import tomllib
except ImportError:  # pragma: no cover — version-dependent
    try:
        import tomli as tomllib  # type: ignore[no-redef]
    except ImportError:
        tomllib = None  # type: ignore[assignment] — parse at use time

SEARCH_PATHS = [".", "~/.seaweedfs_tpu", "/etc/seaweedfs_tpu"]


def load_configuration(name: str, required: bool = False) -> dict[str, Any]:
    """Load `<name>.toml` from the search path; {} when absent."""
    fname = name if name.endswith(".toml") else name + ".toml"
    for d in SEARCH_PATHS:
        path = os.path.join(os.path.expanduser(d), fname)
        if os.path.exists(path):
            if tomllib is None:
                # a present config that can't be parsed must FAIL, not be
                # silently ignored — dropping security.toml would disable
                # auth without a trace. Absent configs (the common case)
                # never reach here, so 3.10 servers without TOML configs
                # run fine.
                raise RuntimeError(
                    f"{path} exists but no TOML parser is available "
                    "(python < 3.11 without the tomli package)"
                )
            with open(path, "rb") as f:
                return tomllib.load(f)
    if required:
        raise FileNotFoundError(
            f"{fname} not found in {[os.path.expanduser(d) for d in SEARCH_PATHS]}"
        )
    return {}


def get_nested(conf: dict, dotted: str, default: Any = None) -> Any:
    """conf lookup by 'a.b.c' path."""
    cur: Any = conf
    for part in dotted.split("."):
        if not isinstance(cur, dict) or part not in cur:
            return default
        cur = cur[part]
    return cur


SCAFFOLDS = {
    "security": '''\
# security.toml — put in ., ~/.seaweedfs_tpu/, or /etc/seaweedfs_tpu/
# JWT signing on the volume-server write path. Empty key = auth disabled.

[jwt.signing]
key = ""
expires_after_seconds = 10

# optional separate key gating reads
[jwt.signing.read]
key = ""
expires_after_seconds = 10

[guard]
# IPs allowed to bypass JWT checks
white_list = []

# TLS/mTLS for the gRPC control plane. Setting `ca` turns TLS on for every
# server and client in the process. Generate a throwaway CA + leaf pair with
#   python -c "from seaweedfs_tpu.security.tls import generate_self_signed; \\
#              print(generate_self_signed('./certs'))"
[grpc]
ca = ""
cert = ""
key = ""
require_client_auth = true    # mTLS: peers must present a CA-signed cert
# override_authority = "weedtpu-cluster"   # when certs name the cluster, not each host

# HTTPS on the HTTP data path (volume/filer/s3/webdav/iam servers); uses the
# [grpc] cert material
[https]
enabled = false
''',
    "master": '''\
# master.toml
[master.volume_growth]
copy_1 = 7
copy_2 = 6
copy_3 = 3
copy_other = 1

[master.sequencer]
type = "memory"   # memory | snowflake
''',
    "shell": '''\
# shell.toml
[cluster]
default = "localhost"

[cluster.localhost]
master = "127.0.0.1:9333"
''',
    "filer": '''\
# filer.toml — filer metadata store selection
[memory]
enabled = false

[sqlite]
enabled = true
dbFile = "./filer.db"

# from-scratch embedded log-structured store (the leveldb2-analog):
# append-only CRC-framed log + in-memory index, auto-compaction
[log]
enabled = false
dir = "./filerlog"
''',
}


def scaffold(name: str) -> Optional[str]:
    return SCAFFOLDS.get(name)


# -- WEEDTPU_* environment-variable registry ----------------------------------


@dataclasses.dataclass(frozen=True)
class EnvVar:
    """One declared environment knob. `type` drives parsing (bool accepts
    1/true/yes/on, case-insensitive); `parse` overrides it for knobs with
    extra constraints (clamps, enums) so every call site agrees on the
    same coercion instead of re-implementing it."""

    name: str
    type: type
    default: Any
    doc: str
    parse: Optional[Callable[[str], Any]] = None

    def value(self) -> Any:
        raw = os.environ.get(self.name)
        if raw is None or raw == "":
            return self.default
        if self.parse is not None:
            return self.parse(raw)
        if self.type is bool:
            return raw.strip().lower() in ("1", "true", "yes", "on")
        return self.type(raw)


ENV_REGISTRY: dict[str, EnvVar] = {}


def register_env(
    name: str,
    type_: type,
    default: Any,
    doc: str,
    parse: Optional[Callable[[str], Any]] = None,
) -> EnvVar:
    if not name.startswith("WEEDTPU_"):
        raise ValueError(f"env knob {name!r} must be WEEDTPU_-prefixed")
    prev = ENV_REGISTRY.get(name)
    if prev is not None:
        # `parse` compares by identity: the registry is declared ONCE
        # below, so any re-registration bringing its own parser (even a
        # semantically identical closure) is a second source of truth and
        # must fail loudly rather than silently keep the first parser
        if (prev.type, prev.default) != (type_, default) or prev.parse is not parse:
            raise ValueError(
                f"{name} re-registered with conflicting spec: "
                f"{(prev.type, prev.default, prev.parse)} vs "
                f"{(type_, default, parse)}"
            )
        return prev
    var = EnvVar(name, type_, default, doc, parse)
    ENV_REGISTRY[name] = var
    return var


def env(name: str) -> Any:
    """Parsed value of a REGISTERED env knob (default when unset/empty).
    Unknown names raise — a typo'd knob must fail loudly, not silently
    read as its default forever."""
    var = ENV_REGISTRY.get(name)
    if var is None:
        raise KeyError(f"{name} is not in the WEEDTPU env registry")
    return var.value()


def _clamped_int(minimum: int) -> Callable[[str], int]:
    return lambda raw: max(minimum, int(raw))


def _enum(*allowed: str) -> Callable[[str], str]:
    def parse(raw: str) -> str:
        v = raw.strip().lower()
        if v not in allowed:
            raise ValueError(f"expected one of {allowed}, got {raw!r}")
        return v

    return parse


# The full knob catalog. Declarations live here (not at call sites) so one
# import renders the complete table; call sites look their knob up by name.
register_env(
    "WEEDTPU_PIPELINE_DEPTH", int, 2,
    "Inflight depth of the streaming encode/rebuild pipelines (1 = one "
    "batch overlapped, 2 = double buffering, 3 = triple; clamped to >= 1). "
    "Deeper hides longer device latency at (depth+1) staging buffers of "
    "memory.",
    parse=_clamped_int(1),
)
register_env(
    "WEEDTPU_REBUILD_PREFETCH_BATCHES", int, 2,
    "How many batches ahead of the reading cursor the rebuild pipeline "
    "keeps network-prefetched on remote slab sources (clamped to >= 1).",
    parse=_clamped_int(1),
)
register_env(
    "WEEDTPU_BACKEND", str, "",
    "Operator override of the evidence-based auto backend selection: one "
    "of numpy | native | xorsched | jax | pallas | mesh (empty/auto = "
    "measured decision). Explicit new_encoder(backend=...) callers are "
    "never overridden.",
)
register_env(
    "WEEDTPU_MESH_SHAPE", str, "",
    "dp x sp axis shape of the mesh backend's device mesh, as `DPxSP` "
    "(e.g. `4x2`). Empty/`auto` resolves from the best achievable shape "
    "in committed MULTICHIP_r*.json evidence, falling back to "
    "(devices/2) x 2 (or devices x 1 below 4 devices).",
)
register_env(
    "WEEDTPU_MESH_REBUILD", str, "ring",
    "Distributed-rebuild formulation of the mesh backend: `ring` rotates "
    "one resident survivor block per chip with ppermute (peak per-chip "
    "memory = one block; measured faster), `alltoall` regroups "
    "shard-major survivors to byte-major with one all_to_all. Both are "
    "byte-identical to the single-device decode.",
    parse=_enum("ring", "alltoall"),
)
register_env(
    "WEEDTPU_EVIDENCE_MAX_AGE_DAYS", float, 120.0,
    "Committed on-chip measurement evidence older than this no longer "
    "flips the auto backend away from its conservative XLA default.",
)
register_env(
    "WEEDTPU_DECODE_MATRIX_CACHE", int, 512,
    "LRU cap on cached decode matrices (bounds the GF-elimination keys a "
    "long-lived server with churning shard-loss patterns accumulates).",
)
register_env(
    "WEEDTPU_V", int, 0,
    "glog verbosity level: glog.V(n) call sites with n <= this emit.",
)
register_env(
    "WEEDTPU_WIRE", str, "json",
    "Process-wide RPC wire selection: `proto` flips every unary JSON "
    "method in the pinned schema to binary protobuf; anything else means "
    "JSON. All processes of a cluster must agree.",
    parse=lambda raw: "proto" if raw.strip().lower() == "proto" else "json",
)
register_env(
    "WEEDTPU_BENCH_RPC_DELAY_MS", float, 0.0,
    "Bench-only per-RPC server-side sleep (ms) modeling network RTT on "
    "loopback hosts, so fetch/decode overlap is measurable. 0 = off.",
)
register_env(
    "WEEDTPU_LOCK_OBSERVE", bool, False,
    "Opt-in dynamic lock-order recorder: instruments threading.Lock/RLock "
    "at test-session start, records actual acquisition-order edges, and "
    "fails the run if the observed graph has a cycle (see "
    "seaweedfs_tpu/analysis/lockrec.py).",
)
register_env(
    "WEEDTPU_LOCK_OBSERVE_OUT", str, "",
    "Optional path: the instrumented-lock run dumps the observed "
    "acquisition-order graph here as JSON (edges + acquisition sites).",
)
register_env(
    "WEEDTPU_FS_OBSERVE", str, "",
    "Opt-in filesystem-op recorder (weedsafe dynamic half): the directory "
    "to observe — write/fsync/rename/unlink ops on paths under it are "
    "recorded with creation sites for crash-prefix replay (see "
    "seaweedfs_tpu/analysis/fsrec.py). Empty (default) = off.",
)
register_env(
    "WEEDTPU_FS_OBSERVE_OUT", str, "",
    "Optional path: an observed session dumps its recorded filesystem op "
    "trace here as JSON (op kinds, offsets, payload hex, creation sites).",
)
register_env(
    "WEEDTPU_FSREPLAY_MAX_PREFIXES", int, 48,
    "Crash-prefix replay budget per recorded workload: at most this many "
    "prefixes of the op trace are materialized and driven through the "
    "real resume entrypoints (evenly sampled, endpoints always kept) so "
    "the tier-1 replay gate stays inside its time budget. <=0 = every "
    "prefix.",
)
register_env(
    "WEEDTPU_HEDGE_READS", bool, True,
    "Hedged degraded-read shard fetches: once a survivor fetch has run "
    "past the per-peer EWMA-derived hedge delay, launch ONE backup fetch "
    "against a different holder; first success wins, the loser is "
    "cancelled/drained, and results are asserted byte-identical.",
)
register_env(
    "WEEDTPU_HEDGE_DELAY_MS", float, 0.0,
    "Fixed hedge delay in ms for degraded-read shard fetches; 0 (default) "
    "derives the delay per peer from the live latency EWMA + deviation "
    "tracked in the suspicion registry (TCP-RTO-style).",
)
register_env(
    "WEEDTPU_COALESCE_READS", bool, True,
    "Single-flight coalescing of concurrent degraded decodes of the SAME "
    "(shard, interval): one leader reconstructs, waiters get byte-"
    "identical copies — a hot lost shard costs one decode, not N.",
)
register_env(
    "WEEDTPU_REBUILD_MAX_INFLIGHT", int, 8,
    "Token gate on concurrent VolumeEcShardSlabRead rebuild streams per "
    "volume server (clamped to >= 1). A rebuild storm queues behind the "
    "gate instead of saturating the RPC worker pool and starving "
    "foreground interval reads.",
    parse=_clamped_int(1),
)
register_env(
    "WEEDTPU_REBUILD_YIELD_MS", float, 0.0,
    "Cooperative yield (ms) a rebuild slab stream sleeps between chunks, "
    "ceding the GIL/IO to foreground reads under contention. 0 = off.",
)
register_env(
    "WEEDTPU_TRACE_REPAIR", str, "auto",
    "Trace-repair projections for distributed rebuilds: `on` attempts "
    "projection fetches wherever holders advertise the slab_projection "
    "capability, `off` forces full survivor slabs (and stops "
    "advertising/serving the projection read), `auto` additionally "
    "declines projections when the plan would not move fewer bytes than "
    "the slabs it replaces. Any trace failure mid-rebuild falls back to "
    "full slabs.",
    parse=_enum("on", "off", "auto"),
)
register_env(
    "WEEDTPU_TRACE_CHUNK", int, 4 * 1024 * 1024,
    "Projection-window sub-range size (bytes) a TraceSlabSource fetches "
    "per request — the trace analog of the slab stripe size (clamped to "
    ">= 64 KiB).",
    parse=_clamped_int(64 * 1024),
)
register_env(
    "WEEDTPU_SLAB_FANOUT", int, 4,
    "Striping fan-out of remote slab/projection sources: concurrent "
    "sub-range fetches per source, spread across replica holders by "
    "least-inflight pick so one window aggregates the holders' bandwidth "
    "instead of pinning the first-sorted holder (clamped to >= 1).",
    parse=_clamped_int(1),
)
register_env(
    "WEEDTPU_INLINE_EC", str, "off",
    "Inline-EC ingest (encode-on-write): `on` streams every volume "
    "append through the staging-ring encode pipeline so a sealing volume "
    "is born EC'd (stripe state accumulates per open volume, parity is "
    "encoded incrementally per completed large row, journaled for "
    "crash-resume); `off` (default) keeps EC a warm-storage conversion.",
    parse=_enum("on", "off"),
)
register_env(
    "WEEDTPU_INLINE_EC_SEAL_BYTES", int, 0,
    "Auto-seal threshold for inline-EC ingest: a volume whose .dat "
    "crosses this many bytes is sealed in place (read-only, inline "
    "stripe finalized to .ec00-.ec13/.ecx/.eci, EC volume mounted). "
    "0 = never auto-seal; sealing then happens only via the "
    "VolumeEcShardsGenerate{inline:true} control RPC (ec.encode -inline).",
    parse=_clamped_int(0),
)
register_env(
    "WEEDTPU_INLINE_EC_LARGE_BLOCK", int, 1024 * 1024 * 1024,
    "Large stripe-block size (bytes) the inline-EC ingest builders "
    "encode with; must match the seal-time geometry or the inline state "
    "is discarded for the warm path (clamped to >= 4096).",
    parse=_clamped_int(4096),
)
register_env(
    "WEEDTPU_INLINE_EC_SMALL_BLOCK", int, 1024 * 1024,
    "Small (tail) stripe-block size (bytes) for inline-EC ingest — the "
    "inline sibling of the warm encoder's small_block_size (clamped to "
    ">= 512).",
    parse=_clamped_int(512),
)
register_env(
    "WEEDTPU_INLINE_EC_DELTA", bool, True,
    "Delta parity updates for overwrites landing inside already-encoded "
    "inline stripe rows: parity' = parity XOR G_col*(old XOR new) on just "
    "the touched byte columns (GF-linearity rank-1 update). Off = an "
    "overwrite invalidates the inline state and the seal falls back to "
    "the warm full re-encode.",
)
register_env(
    "WEEDTPU_SCRUB", str, "off",
    "Background shard-integrity scrubber: `on` starts a per-volume-server "
    "scan thread that CRC32-verifies every mounted EC shard against its "
    ".eci record in bounded chunks (rate-capped, riding the rebuild "
    "admission lane), quarantines failures out of serving, and triggers "
    "automatic trace-repair; `off` (default) leaves verification to the "
    "explicit ec.verify command.",
    parse=_enum("on", "off"),
)
register_env(
    "WEEDTPU_SCRUB_RATE_MB", float, 64.0,
    "Scrub read-rate cap in MB/s per volume server (rolling 1 s window); "
    "0 = unthrottled. Keeps a full-disk integrity pass from competing "
    "with foreground reads for disk bandwidth.",
)
register_env(
    "WEEDTPU_SCRUB_CHUNK", int, 4 * 1024 * 1024,
    "Scrub chunk size in bytes — the unit of admission-gated, rate-"
    "metered CRC folding (clamped to >= 64 KiB).",
    parse=_clamped_int(64 * 1024),
)
register_env(
    "WEEDTPU_SCRUB_INTERVAL", float, 30.0,
    "Seconds the scrubber sleeps between full passes over the mounted EC "
    "volumes. The persisted cursor makes an interrupted pass resume "
    "mid-shard across restarts.",
)
register_env(
    "WEEDTPU_SCRUB_CURSOR", str, "",
    "Path of the fsync'd scrub cursor file (scan progress + pending "
    "quarantine entries, resumed across restarts). Empty = "
    "`.scrub_cursor.json` in the server's first storage directory.",
)
register_env(
    "WEEDTPU_SCRUB_REPAIR_BACKOFF", float, 5.0,
    "Base backoff in seconds between repair attempts for one quarantined "
    "shard (doubles per failure, capped at 12x the base) — a stripe "
    "missing too many survivors retries calmly instead of hammering the "
    "master/holders.",
)
register_env(
    "WEEDTPU_SCRUB_MAX_REPAIRS", int, 1,
    "Concurrent automatic shard repairs per volume server (clamped to "
    ">= 1). Each repair is a trace-mode rebuild (or a clean-replica "
    "re-pull) — capping them keeps a corruption burst from becoming a "
    "rebuild storm.",
    parse=_clamped_int(1),
)
register_env(
    "WEEDTPU_CONVERT_BATCH", int, 64 * 1024 * 1024,
    "Device-batch budget (bytes) of the geometry-conversion pipeline — "
    "how much virtual-dat data one staging-ring dispatch covers (clamped "
    "to >= 1 MiB). The conversion analog of the encode pipeline's "
    "max_batch_bytes.",
    parse=_clamped_int(1024 * 1024),
)
register_env(
    "WEEDTPU_CONVERT_JOURNAL_MB", float, 64.0,
    "How many MB of converted output the geometry converter writes "
    "between fsync'd .ecc journal watermarks. Smaller = finer "
    "crash-resume granularity (less re-encoded on restart), larger = "
    "fewer fsyncs. Clamped to > 0.",
    parse=lambda raw: max(0.001, float(raw)),
)
register_env(
    "WEEDTPU_CONVERT_VERIFY", bool, True,
    "Re-read every converted shard FROM DISK and verify it against the "
    "staged .eci CRCs before cut-over retires the old geometry (the "
    "scrub-grade gate: bytes on disk, not bytes in flight, are what the "
    "new geometry will serve). Off skips the extra read pass.",
)
register_env(
    "WEEDTPU_TRACE", str, "on",
    "weedtrace request tracing: `on` (default — designed to be safe to "
    "leave on: allocation-light spans, no I/O, bounded ring) records "
    "context-local span trees on every hot path, propagates trace ids "
    "across RPC metadata / the X-Weedtpu-Trace HTTP header, and serves "
    "them at /debug/traces + `ec.trace`; `off` collapses every trace "
    "call site to a no-op.",
    parse=_enum("on", "off"),
)
register_env(
    "WEEDTPU_TRACE_SAMPLE", float, 1.0,
    "Probability a completed NON-tail trace enters the sampled ring "
    "(error traces and the N slowest per (kind, class) are always "
    "retained regardless). Clamped to [0, 1]; lower it on very hot "
    "fronts to bound serialization-free ring churn.",
    parse=lambda raw: min(1.0, max(0.0, float(raw))),
)
register_env(
    "WEEDTPU_TRACE_RING", int, 256,
    "Capacity of the per-process sampled-trace FIFO (tail-retained "
    "error/slowest traces live in their own bounded structures on top). "
    "Clamped to >= 8.",
    parse=_clamped_int(8),
)
register_env(
    "WEEDTPU_TRACE_SLOWEST", int, 5,
    "How many slowest traces per (kind, class) the ring always retains, "
    "independent of sampling — the tail the p99 is about (clamped to "
    ">= 1).",
    parse=_clamped_int(1),
)
register_env(
    "WEEDTPU_XORSCHED_TILE_KB", int, 4,
    "Width-axis tile of the xorsched executors, in KB per shard: each "
    "tile keeps the whole bit-plane slot frame (inputs + grouped temps + "
    "outputs) cache-resident while the XOR program replays. 4 KB "
    "measures best on the committed BENCH host (L1-sized frame); "
    "clamped to >= 1.",
    parse=_clamped_int(1),
)
register_env(
    "WEEDTPU_XORSCHED_CACHE", int, 64,
    "Entry cap of the compiled XOR-schedule LRU (keyed by matrix bytes "
    "+ tile geometry, like the decode-matrix memo). Compilation is "
    "milliseconds and programs are KBs, so a small cap covers every "
    "live (geometry, erasure-pattern) pair; clamped to >= 1.",
    parse=_clamped_int(1),
)
register_env(
    "WEEDTPU_XORSCHED_THREADS", int, 1,
    "Worker threads of the width-parallel native xorsched executor: the "
    "fused block-diagonal decode flattens to independent (block, "
    "width-tile) tasks, spread across this many threads. 0 means "
    "hardware concurrency (resolved natively); 1 keeps the PR 17 "
    "single-stream path; clamped to >= 0.",
    parse=_clamped_int(0),
)
register_env(
    "WEEDTPU_REPAIR", str, "off",
    "Master-side fleet repair scheduler: `on` enumerates every stripe "
    "left under-replicated by a dead/quarantined holder, ranks by "
    "remaining redundancy (2-missing strictly before 1-missing, ties by "
    "stripe bytes then single-domain exposure), and drives batched "
    "remote rebuilds through the rebuild admission lane; `off` (default) "
    "leaves mass repair to the operator's ec.rebuild.",
    parse=_enum("on", "off"),
)
register_env(
    "WEEDTPU_REPAIR_MAX_INFLIGHT", int, 2,
    "Cluster-wide cap on concurrently-running batched rebuild dispatches "
    "from the fleet repair scheduler (clamped to >= 1) — the scheduler's "
    "own pacing budget on top of each holder's "
    "WEEDTPU_REBUILD_MAX_INFLIGHT admission gate.",
    parse=_clamped_int(1),
)
register_env(
    "WEEDTPU_REPAIR_BATCH", int, 8,
    "How many same-priority-class stripes one repair dispatch may carry "
    "to a single rebuild target (clamped to >= 1). The target fuses "
    "equal missing-signature volumes into shared decode dispatches, so "
    "bigger batches amortize device/staging setup across volumes.",
    parse=_clamped_int(1),
)
register_env(
    "WEEDTPU_REPAIR_SCAN_S", float, 30.0,
    "Seconds between full under-replication scans of the master's EC "
    "registry. Death signals (reaped nodes, shrinking heartbeats, "
    "confirmed peer-unreachable reports) trigger an immediate scan on "
    "top of this cadence.",
)
register_env(
    "WEEDTPU_REPAIR_SETTLE_S", float, 2.0,
    "Correlation window the repair scheduler waits after a death signal "
    "before dispatching: a rack's nodes die together but their heartbeat "
    "silences stagger, and ranking before the dust settles would start "
    "1-missing repairs that should have been 2-missing.",
)
register_env(
    "WEEDTPU_REPAIR_DEAD_S", float, 15.0,
    "Heartbeat-silence age after which a holder that peers ALSO report "
    "unreachable is treated as dead for repair purposes (unreported "
    "holders die at 4x this, bounded below by 60 s, so a long GC pause "
    "alone never triggers a mass rebuild).",
)
register_env(
    "WEEDTPU_REPAIR_BACKOFF", float, 2.0,
    "Base seconds of the per-stripe exponential backoff after a repair "
    "dispatch is refused (503/RESOURCE_EXHAUSTED from the admission "
    "lane) or fails in transport; doubles per failure, capped at 12x.",
)
register_env(
    "WEEDTPU_REPAIR_REPORT_FAILURES", int, 3,
    "Consecutive unreachable-peer failures on the degraded-read/rebuild "
    "paths before a volume server names that peer in its heartbeat's "
    "unreachable_peers report (clamped to >= 1; any success resets the "
    "count).",
    parse=_clamped_int(1),
)
register_env(
    "WEEDTPU_PLACEMENT_MAX_PER_DOMAIN", int, 0,
    "Operator override of the failure-domain placement cap (shards of "
    "one stripe a single rack may hold). 0 (default) = the volume's "
    "parity count m, the largest cap that still survives a whole-domain "
    "loss.",
    parse=_clamped_int(0),
)
register_env(
    "WEEDTPU_INLINE_EC_SPREAD", str, "off",
    "Inline-ingest parity spreading: `on` streams each parity shard's "
    "encoded rows to its placement-planned eventual holder WHILE the "
    "volume is still ingesting, so seal cut-over ships only the small "
    "tail and the owner never hosts all k+m shards; any spread failure "
    "falls back to sealing that shard locally. Requires "
    "WEEDTPU_INLINE_EC=on.",
    parse=_enum("on", "off"),
)
register_env(
    "WEEDTPU_LOOKUP_RETRIES", int, 2,
    "Bounded retries (with decorrelated jitter) of the single-flight "
    "master shard-location lookup leader before it fails its waiters — "
    "one transient master hiccup no longer fails a whole burst of "
    "degraded reads (clamped to >= 0).",
    parse=_clamped_int(0),
)

register_env(
    "WEEDTPU_READ_CACHE_MB", float, 64.0,
    "Byte budget (MiB) of the process-wide decoded-interval read cache: a "
    "hot degraded interval is reconstructed once per epoch, not once per "
    "request (the coalesce leader publishes its decode). 0 disables the "
    "cache entirely (no lookups, no counters). Clamped to >= 0.",
    parse=lambda raw: max(0.0, float(raw)),
)

register_env(
    "WEEDTPU_READ_CACHE_TTL_S", float, 30.0,
    "Age (seconds) after which a cached decoded interval expires and the "
    "next read re-decodes — the 'epoch' of decode-once-per-epoch serving. "
    "0 means entries never expire by age (eviction/invalidation only). "
    "Clamped to >= 0.",
    parse=lambda raw: max(0.0, float(raw)),
)


def env_table_markdown() -> str:
    """The README `WEEDTPU_*` table, generated from the registry."""
    lines = [
        "| Variable | Type | Default | Description |",
        "| --- | --- | --- | --- |",
    ]
    for name in sorted(ENV_REGISTRY):
        var = ENV_REGISTRY[name]
        default = "(empty)" if var.default == "" else f"`{var.default}`"
        doc = " ".join(var.doc.split()).replace("|", "\\|")
        lines.append(
            f"| `{name}` | {var.type.__name__} | {default} | {doc} |"
        )
    return "\n".join(lines) + "\n"
