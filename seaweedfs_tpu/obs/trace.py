"""weedtrace core: context-local spans, trace ids, and the bounded
per-process trace ring with tail-biased retention.

Design constraints, in order:

1. **Safe to leave ON.** The read path takes thousands of spans/second
   under load, so recording must be allocation-light and lock-free on
   the span path: a span is one `__slots__` object appended to its
   parent's list; serialization to dicts happens lazily at snapshot
   time (`/debug/traces`, weedload's scrape), never per request. With
   `WEEDTPU_TRACE=off` the root constructors return a no-op and every
   `span()` call collapses to one ContextVar read. No fsync, no I/O,
   ever — the ring lives and dies with the process.

2. **Tail-biased retention.** A uniform sample would retain exactly the
   traces the p99 is NOT about. The ring always keeps error traces and
   the N slowest per (kind, class); everything else is probabilistically
   sampled (`WEEDTPU_TRACE_SAMPLE`) into a bounded FIFO. Total memory is
   bounded by `WEEDTPU_TRACE_RING` + N x live (kind, class) keys +
   the error buffer.

3. **One id end to end.** Trace ids are minted at the HTTP fronts and
   the shell, ride gRPC invocation metadata (`weedtpu-trace` — request
   METADATA, so the pinned proto contracts are untouched) and the
   `X-Weedtpu-Trace` HTTP header, and come back on the response so a
   client can grep every process's glog lines / trace rings for one
   slow request. A `shell -c` script is one id from the birth of its
   process to its last command (`shell.script`); its tree is handed to
   the master when it ends (`ReportTrace`), so it outlives the child.

Span names are a closed catalog (`SPAN_NAMES`): weedlint's obs-drift
family asserts every `span("...")` call site in the package names a
registered stage and every registered stage is used — dashboards and
the tail-attribution artifact key on these strings, so they must not
drift.
"""

from __future__ import annotations

import bisect
import contextvars
import os
import random
import re
import threading
import time
from typing import Iterator, Optional

from seaweedfs_tpu.utils import config

#: the registered stage catalog — every span()/start()/ensure() name in
#: the package MUST appear here (weedlint: obs-span-undeclared), and
#: every entry must have a call site (obs-span-unused). The tail-
#: attribution artifact and `ec.trace` render these strings verbatim.
SPAN_NAMES: dict[str, str] = {
    "http.read": "volume-server HTTP GET of one needle (the serving path)",
    "http.write": "volume-server HTTP POST/PUT of one needle",
    "master.http": "master HTTP facade route (/dir/assign, /dir/lookup, ...)",
    "shell.script": "one `shell -c` script, from the BIRTH of its process to the end of its last command: the root every command of the script nests under (script= its text, cut to 200 characters); handed to the master by ReportTrace when it ends",
    "shell.start": "first child of shell.script, birth of the process to the first command: interp_ms (birth to the first line of __main__), import_ms (from there until grpc and the shell's own modules are loaded: the command line parsed and security.toml read on the way), connect_ms (CommandEnv, the channel, until the script starts), modules=",
    "shell.command": "one weed-shell command execution (command, modules loaded at its start, rpcs it made; ec.rebuild without -remote: overlapped= gathers that ran beside the rebuild of the volume before; ec.encode: overlapped= cut-overs that began while another volume's was running, ckpt_writes= checkpoint files written); a root in the REPL, a child of shell.script in a -c script",
    "shell.plan": "ec.encode / ec.rebuild before their first state-changing RPC: selection, VolumeList, a VolumeStatus a volume, pick_rebuilder (volumes= planned, rpcs= made)",
    "shell.trace": "the receipt of a ReportTrace on a server whose spans are mirrored into the profiler: one short annotation whose attributes carry the child's tree flat (names, what, t_ns from birth_unix_ns, dur_ns, depth, thread)",
    "rpc.client": "the shell's side of one RPC through CommandEnv.master_call / vs_call (method=, target=; thread= where another thread than the command's made it); as many under a shell.command as its rpcs=",
    "rpc.server": "server side of one gRPC method (method name in attrs)",
    "ec.lookup": "master LookupEcVolume round-trip (shard-location cache miss)",
    "ec.recover": "degraded interval reconstruction, client-facing wall time",
    "ec.gather": "survivor fan-out for one interval (local + remote fetches)",
    "ec.fetch": "one remote shard-interval fetch attempt (primary)",
    "ec.fetch.holder": "one holder attempt inside a fetch's failover ladder",
    "ec.hedge": "backup fetch raced against a slow primary",
    "ec.coalesce.wait": "waiter parked on another read's in-flight decode",
    "ec.decode": "GF decode dispatch (backend + batch width in attrs)",
    "cache.hit": "interval served from the decoded-interval cache (no fan-out)",
    "cache.miss": "decoded-interval cache consulted and empty for this interval",
    "ec.copy": "VolumeEcShardsCopy on the puller: every named file of one source (source, shards, bytes)",
    "ec.copy.file": "one file of a copy: its stream from the source written to `.cpy`, then fsync + rename (ext, bytes); self time is the stream",
    "ec.copy.fsync": "flush + fsync of one copied file, apart from its stream",
    "ec.copy.serve": "VolumeEcShardFileCopy on the source: one file read and streamed out (ext, bytes)",
    "rebuild.run": "one rebuild pipeline: a whole volume's (local or distributed), or a VolumeEcShardsRebuildBatch's over many (batch= volumes, signature_groups=, planned= volumes planned side by side, plan_ms= the RPC's begin to the pipeline's start); ring= says whether its staging ring was reused",
    "rebuild.plan": "one volume of a VolumeEcShardsRebuildBatch planned (volume=; local= true where no survivor crosses the network): a fresh LookupEcVolume (its ec.lookup child), the local files, the shard size, a slab source a survivor; the plans of a batch of several run side by side on the batch's executor, all before the first rebuild.stage",
    "rebuild.stage": "staging-ring fill for one rebuild batch (disk/wire): its lane reads queued, the drain it runs ahead of (nested), the wait for the reads",
    "rebuild.read": "one survivor's slab read into its staging row (child of rebuild.stage; on a lane thread where the source allows)",
    "rebuild.wait": "the calling thread blocked in a join of lane tasks (a batch's reads, the last drain's writes)",
    "rebuild.dispatch": "reconstruct_lazy, or reconstruct_block where a packed batch holds several signature groups (blocks=): device_put (H2D) + the jit call, until it returns; form= says how the slot crossed: on the jax backend exact | as_is (rs_jax.apply_matrix), on the mesh backend mesh-ring | mesh-alltoall | mesh-cols (the program that took it; its mesh.put child is the crossing)",
    "mesh.put": "the mesh backend's half of a *.dispatch before its program: the batch laid out for the mesh on the host (the rebuild's dp column slices, shard-major; a padded tail) and device_put over the devices (mesh= dp x sp, variant= ring | alltoall | cols, devices= the batch lay on)",
    "mesh.restore": "the mesh backend's half of a *.sync after the device has finished: the sharded result fetched from its devices and re-laid as the flat (rows, width) the pipelines write (mesh=, variant=, devices=)",
    "rebuild.drain": "device sync + shard write-out + CRC for one rebuild batch",
    "rebuild.sync": "np.asarray of one batch's decode: device wait + D2H, nothing else (on the mesh backend its own time is the wait for the devices; the way back is its mesh.restore child)",
    "rebuild.write": "one rebuilt shard's bytes of one batch written to its file (on a lane thread)",
    "rebuild.crc": "zlib.crc32 fold over one rebuilt shard's bytes of one batch (on a lane thread)",
    "rebuild.verify": "rebuilt shards' CRC32s checked against the .eci record",
    "encode.run": "one encode pipeline: .dat -> shard files + .eci of one volume (write_ec_files), or of a VolumeEcShardsGenerateBatch's many, their rows packed into the same batches (batch= volumes of the run, volumes= their ids, batches= device dispatches the whole run took, bytes= of .dat); ring= says whether its staging ring was reused",
    "encode.finish": "one volume of an encode run made whole: its 15 files closed, its .eci written and fsynced (on a lane of its own, beside the batches of the volumes after it)",
    "encode.stage": "staging-ring fill for one encode batch: its lane reads queued, the drain it runs ahead of (nested), the wait for the reads",
    "encode.read": "one data shard's slabs of one batch read into its staging row (child of encode.stage; on a lane thread), or all ten on the calling thread where the source is no file",
    "encode.wait": "the calling thread blocked in a join of lane tasks (a batch's reads, its data shards' writes, the last drain's parity writes)",
    "encode.dispatch": "encode_parity_lazy: device_put (H2D) + the jit call, until it returns; form= as on rebuild.dispatch",
    "encode.drain": "device sync + shard write-out + CRC for one encode batch",
    "encode.sync": "np.asarray of one batch's parity: device wait + D2H, nothing else (on the mesh backend as rebuild.sync)",
    "encode.write": "one shard's bytes of one batch written to its file (data under its stage, parity under its drain; on a lane thread)",
    "encode.crc": "zlib.crc32 fold over one shard's bytes of one batch (on a lane thread)",
    "ingest.encode": "inline-EC encode of newly-final large rows (one poll)",
    "ingest.seal": "inline-EC seal finalization of one volume",
    "ingest.spread.commit": "seal-time commit of one pre-spread parity shard",
    "scrub.cycle": "one full background integrity pass over mounted shards",
    "scrub.repair": "one automatic repair attempt of a quarantined shard",
    "convert.run": "one whole-volume geometry conversion",
    "convert.chunk": "one journaled chunk of a geometry conversion",
    "heal.verify": "verify-on-read culprit hunt after a body-CRC failure",
    "ec.ecx": "one volume's sorted index (.ecx) written from its .idx after an encode (VolumeEcShardsGenerate, VolumeEcShardsGenerateBatch)",
    "ec.mount": "an EC volume's local shards found and mounted (VolumeEcShardsMount, a rebuild batch's rebuilt shards, the remount of VolumeEcShardsDelete): files opened, the codec's small-read programs warmed",
    "ec.unmount": "VolumeEcShardsDelete's unmount and unlinks (shards= named)",
    "volume.remove": "VolumeDelete: the volume closed and its files unlinked",
    "vs.heartbeat": "a full-state heartbeat composed after the call began, sent and awaited (heartbeat_once under an RPC: the master must know of a mount or a deletion before the RPC answers); heartbeats leave one at a time, so the span holds the wait for the one on its way; waiters= callers the heartbeat served (above 1: shared)",
}

_ID_RE = re.compile(r"^[0-9a-fA-F][0-9a-fA-F-]{0,63}$")

#: gRPC invocation-metadata key / HTTP header the id rides on
MD_KEY = "weedtpu-trace"
HTTP_HEADER = "X-Weedtpu-Trace"
#: HTTP response header carrying the serving class a read resolved to
#: (healthy / ec_intact / cached / degraded) — weedload classifies
#: per-request latencies from it instead of guessing from topology
READ_CLASS_HEADER = "X-Weedtpu-Read-Class"

_cv: contextvars.ContextVar[Optional["Span"]] = contextvars.ContextVar(
    "weedtpu_trace_span", default=None
)

#: guards only the first-child list publication in Span.add_child
_first_child_lock = threading.Lock()

#: the profiler mirror: None, or a callable (name, attrs) that opens an
#: event on another clock and returns an object whose __exit__ closes it.
#: Called on the recording thread at every RECORDED span's and root's
#: __enter__, closed at its __exit__ — never when tracing is off or no
#: trace is ambient. The chip-owning server installs
#: jax.profiler.TraceAnnotation here at boot (command/servers.py), so a
#: profiler session holds the program's spans beside the device's
#: operations; this module itself imports nothing of jax.
_mirror = None


def set_mirror(opener) -> None:
    """Install (or with None remove) the process's profiler mirror."""
    global _mirror
    _mirror = opener


def enabled() -> bool:
    return config.env("WEEDTPU_TRACE") == "on"


def new_trace_id() -> str:
    return os.urandom(8).hex()


def valid_id(tid) -> Optional[str]:
    """Sanitized inbound trace id, or None (never trust wire input)."""
    if isinstance(tid, str) and _ID_RE.match(tid):
        return tid.lower()
    return None


class _TraceState:
    """Shared per-trace state every span of the tree points at."""

    __slots__ = ("trace_id", "kind", "klass", "wall0", "t0")

    def __init__(self, trace_id: str, kind: str, klass: str, t0: Optional[float] = None):
        self.trace_id = trace_id
        self.kind = kind
        self.klass = klass
        self.wall0 = time.time()
        self.t0 = time.monotonic()
        if t0 is not None:
            # a root that began before anyone could open it (a process's
            # birth): both clocks go back by the same stretch
            self.wall0 -= self.t0 - t0
            self.t0 = t0


class Span:
    __slots__ = ("name", "attrs", "t0", "dur", "children", "error", "trace")

    def __init__(
        self, name: str, attrs: Optional[dict], trace: _TraceState, t0: Optional[float] = None
    ):
        self.name = name
        self.attrs = attrs
        self.t0 = time.monotonic() if t0 is None else t0
        self.dur = 0.0
        self.children: Optional[list] = None
        self.error: Optional[str] = None
        self.trace = trace

    def annotate(self, **kv) -> None:
        if self.attrs is None:
            self.attrs = kv
        else:
            self.attrs.update(kv)

    def add_child(self, child: "Span") -> None:
        # list.append is atomic under the GIL, so the steady state is
        # lock-free — but the FIRST-child check-then-assign is not: two
        # pool workers attaching the first two children concurrently
        # could each publish their own list and lose a span. One shared
        # lock guards only that publication (double-checked).
        if self.children is None:
            with _first_child_lock:
                if self.children is None:
                    self.children = []
        self.children.append(child)

    def to_dict(self) -> dict:
        d = {
            "name": self.name,
            "t_ms": round((self.t0 - self.trace.t0) * 1e3, 3),
            "dur_ms": round(self.dur * 1e3, 3),
        }
        if self.attrs:
            d["attrs"] = {k: v for k, v in self.attrs.items()}
        if self.error:
            d["error"] = self.error
        if self.children:
            d["spans"] = [c.to_dict() for c in self.children]
        return d


class _Completed:
    """One finished trace held in the ring — serialized lazily."""

    __slots__ = ("root", "state", "dur", "error")

    def __init__(self, root: Span, state: _TraceState, error: Optional[str]):
        self.root = root
        self.state = state
        self.dur = root.dur
        self.error = error

    def to_dict(self) -> dict:
        return {
            "trace_id": self.state.trace_id,
            "kind": self.state.kind,
            "class": self.state.klass,
            "start": round(self.state.wall0, 3),
            # the same instant, whole: what joins this root with another
            # process's spans of the same id (ec.trace, command_reduce)
            "unix_ns": int(self.state.wall0 * 1e9),
            "duration_s": round(self.dur, 6),
            "error": self.error,
            "root": self.root.to_dict(),
        }


class TraceRing:
    """Bounded retention of completed traces, tail-biased:

    - every ERROR trace lands in a bounded error buffer (newest win),
    - the `slowest_n` slowest per (kind, class) are always kept,
    - the rest pass a probabilistic sample gate into a bounded FIFO.

    `seed` pins the sampler for deterministic tests; 0 = entropy."""

    def __init__(
        self,
        capacity: Optional[int] = None,
        slowest_n: Optional[int] = None,
        sample: Optional[float] = None,
        seed: Optional[int] = None,
        errors_cap: int = 64,
    ):
        self._lock = threading.Lock()
        self.capacity = int(capacity if capacity is not None else config.env("WEEDTPU_TRACE_RING"))
        self.slowest_n = int(
            slowest_n if slowest_n is not None else config.env("WEEDTPU_TRACE_SLOWEST")
        )
        self._sample = sample
        self.errors_cap = errors_cap
        self._rng = random.Random(seed or None)
        self._sampled: list[_Completed] = []
        self._errors: list[_Completed] = []
        #: (kind, class) -> ascending-by-duration list of _Completed
        self._slowest: dict[tuple[str, str], list[_Completed]] = {}
        self.offered = 0
        self.kept = 0

    def _sample_rate(self) -> float:
        if self._sample is not None:
            return self._sample
        return float(config.env("WEEDTPU_TRACE_SAMPLE"))

    def offer(self, done: _Completed) -> bool:
        kept = False
        with self._lock:
            self.offered += 1
            if done.error is not None:
                self._errors.append(done)
                if len(self._errors) > self.errors_cap:
                    del self._errors[0]
                kept = True
            key = (done.state.kind, done.state.klass)
            row = self._slowest.setdefault(key, [])
            if len(row) < self.slowest_n or done.dur > row[0].dur:
                # insert sorted ascending; the least slow leaves the row for
                # the sample gate, as if it had never been among the slowest
                # (or the first few of a kind, fast ones too, would be lost
                # the moment slower ones came)
                bisect.insort(row, done, key=lambda c: c.dur)
                kept = True
                if len(row) > self.slowest_n:
                    self._sample_in(row.pop(0))
            if not kept:
                kept = self._sample_in(done)
            if kept:
                self.kept += 1
        return kept

    def _sample_in(self, done: _Completed) -> bool:
        """Through the sample gate into the FIFO (the caller holds the lock)."""
        rate = self._sample_rate()
        if rate < 1.0 and self._rng.random() >= rate:
            return False
        self._sampled.append(done)
        if len(self._sampled) > self.capacity:
            del self._sampled[0]
        return True

    def snapshot(
        self,
        kind: Optional[str] = None,
        klass: Optional[str] = None,
        min_duration: float = 0.0,
        limit: int = 100,
    ) -> list[dict]:
        """Serialized retained traces, slowest first, deduped by identity
        (a trace can sit in both the slowest row and the sampled FIFO)."""
        with self._lock:
            all_: list[_Completed] = list(self._sampled) + list(self._errors)
            for row in self._slowest.values():
                all_.extend(row)
        seen: set[int] = set()
        out: list[_Completed] = []
        for c in all_:
            if id(c) in seen:
                continue
            seen.add(id(c))
            if kind and c.state.kind != kind:
                continue
            if klass and c.state.klass != klass:
                continue
            if c.dur < min_duration:
                continue
            out.append(c)
        out.sort(key=lambda c: c.dur, reverse=True)
        return [c.to_dict() for c in out[: max(0, int(limit))]]

    def stats(self) -> dict:
        with self._lock:
            return {
                "offered": self.offered,
                "kept": self.kept,
                "sampled": len(self._sampled),
                "errors": len(self._errors),
                "slowest_keys": len(self._slowest),
            }

    def clear(self) -> None:
        with self._lock:
            self._sampled.clear()
            self._errors.clear()
            self._slowest.clear()
            self.offered = self.kept = 0


#: the per-process ring every finished root lands in
RING = TraceRing()


# -- recording primitives ------------------------------------------------------


class _NullCtx:
    """Shared no-op for disabled tracing / span-outside-trace — one
    allocation for the whole process, not one per call."""

    __slots__ = ()

    def __enter__(self):
        return None

    def __exit__(self, *exc):
        return False


_NULL = _NullCtx()


class span:  # noqa: N801 — reads as a statement: `with span("ec.gather"):`
    """Record one child span under the ambient trace; a no-op (and
    allocation-free beyond this tiny object) when no trace is active."""

    __slots__ = ("_name", "_attrs", "_sp", "_tok", "_mirrored")

    def __init__(self, _name: str, **attrs):
        self._name = _name
        self._attrs = attrs or None
        self._sp = None
        self._tok = None
        self._mirrored = None

    def __enter__(self) -> Optional[Span]:
        parent = _cv.get()
        if parent is None:
            return None
        if _mirror is not None:
            self._mirrored = _mirror(self._name, self._attrs)
        sp = Span(self._name, self._attrs, parent.trace)
        parent.add_child(sp)
        self._sp = sp
        self._tok = _cv.set(sp)
        return sp

    def __exit__(self, et, ev, tb):
        sp = self._sp
        if sp is None:
            return False
        sp.dur = time.monotonic() - sp.t0
        if et is not None and sp.error is None:
            sp.error = et.__name__
        _cv.reset(self._tok)
        if self._mirrored is not None:
            self._mirrored.__exit__(None, None, None)
        return False


class _RootCtx:
    __slots__ = ("_state", "_attrs", "_root", "_tok", "_ring", "_mirrored")

    def __init__(self, state: _TraceState, ring: TraceRing, attrs: Optional[dict] = None):
        self._state = state
        self._attrs = attrs or None
        self._ring = ring
        self._root = None
        self._tok = None
        self._mirrored = None

    def __enter__(self) -> Span:
        if _mirror is not None:
            # unix_ns: any one mirrored root of a profiler session yields the
            # offset between the profiler's clock and the wall clock
            self._mirrored = _mirror(
                self._state.kind,
                {**(self._attrs or {}), "trace_id": self._state.trace_id, "unix_ns": time.time_ns()},
            )
        root = Span(self._state.kind, self._attrs, self._state, self._state.t0)
        self._root = root
        self._tok = _cv.set(root)
        return root

    def __exit__(self, et, ev, tb):
        root = self._root
        root.dur = time.monotonic() - root.t0
        error = None
        if et is not None:
            error = f"{et.__name__}: {ev}"[:200]
            root.error = et.__name__
        _cv.reset(self._tok)
        if self._mirrored is not None:
            self._mirrored.__exit__(None, None, None)
        self._ring.offer(_Completed(root, self._state, error))
        return False


def start(
    kind: str,
    klass: str = "healthy",
    trace_id=None,
    ring: Optional[TraceRing] = None,
    t0: Optional[float] = None,
    **attrs,
):
    """Begin a root trace (the HTTP fronts, the shell, background
    maintenance). `trace_id` adopts a propagated id (sanitized); absent
    or invalid ids mint a fresh one. `t0` back-dates the root to a
    `time.monotonic()` reading of before (a `-c` script's root begins at
    its process's birth); `attrs` are the root span's from its start.
    Returns a context manager yielding the root Span — or a no-op when
    tracing is off."""
    if not enabled():
        return _NULL
    tid = valid_id(trace_id) or new_trace_id()
    return _RootCtx(_TraceState(tid, kind, klass, t0), ring or RING, attrs)


def continue_trace(
    kind: str, trace_id, klass: str = "rpc", ring: Optional[TraceRing] = None, **attrs
):
    """Root trace ONLY when a propagated id arrived — the RPC server
    seam: un-traced callers (heartbeats, bare clients) cost nothing,
    traced callers get their id continued in this process's ring.
    `attrs` are the root span's from its start (the RPC's method), so
    the profiler mirror sees them too."""
    tid = valid_id(trace_id)
    if tid is None or not enabled():
        return _NULL
    return _RootCtx(_TraceState(tid, kind, klass), ring or RING, attrs)


def ensure(kind: str, klass: str = "maint"):
    """A span under the ambient trace when one is active, else a fresh
    root trace — maintenance paths (encode, rebuild, convert, scrub
    repair, seal) are always visible in the ring, and nest correctly
    when an operator's shell trace reached them over RPC. Where the
    ambient span already IS this kind (the RPC opened the run span and
    the pipeline it calls ensures one too), that span is the run: both
    annotate the same one."""
    cur = _cv.get()
    if cur is None:
        return start(kind, klass=klass)
    if cur.name == kind:
        return attach(cur)
    return span(kind)


def record(_name: str, t0: float, t1: float, **attrs) -> None:
    """A span that is over already, `time.monotonic()` readings `t0` to
    `t1`, as a child of the ambient span: what ran before anything could
    open a span around it (`shell.start`)."""
    parent = _cv.get()
    if parent is not None:
        sp = Span(_name, attrs or None, parent.trace, t0)
        sp.dur = t1 - t0
        parent.add_child(sp)


def mark(_name: str, **attrs) -> bool:
    """One short event in the profiler mirror alone, where one is installed
    (no span, no ring): `attrs` are what it is there to carry."""
    if _mirror is None or not enabled():
        return False
    _mirror(_name, attrs).__exit__(None, None, None)
    return True


def current() -> Optional[Span]:
    return _cv.get()


def current_trace_id() -> Optional[str]:
    sp = _cv.get()
    return sp.trace.trace_id if sp is not None else None


def current_class() -> Optional[str]:
    sp = _cv.get()
    return sp.trace.klass if sp is not None else None


def annotate(**kv) -> None:
    sp = _cv.get()
    if sp is not None:
        sp.annotate(**kv)


def set_class(klass: str) -> None:
    """Reclassify the AMBIENT trace (e.g. a read that turned degraded
    mid-flight) — retention and attribution key on the final class."""
    sp = _cv.get()
    if sp is not None:
        sp.trace.klass = klass


class attach:  # noqa: N801 — `with attach(parent):` in worker threads
    """Adopt a span captured in another thread as this thread's ambient
    span — the fetch-pool workers' bridge (ContextVars don't cross
    thread-pool submission)."""

    __slots__ = ("_sp", "_tok")

    def __init__(self, sp: Optional[Span]):
        self._sp = sp
        self._tok = None

    def __enter__(self):
        if self._sp is not None:
            self._tok = _cv.set(self._sp)
        return self._sp

    def __exit__(self, *exc):
        if self._tok is not None:
            _cv.reset(self._tok)
        return False


# -- a trace that outlives its process (the shell child's, ReportTrace) --------

#: spans a handed-over tree may hold; the deepest are cut first
REPORT_MAX_SPANS = 2000
#: bytes its JSON may have (2,000 spans are some 0.4 MB): wire input, kept in a ring
REPORT_MAX_BYTES = 1 << 20
#: what a command's name looks like: it becomes a label of a metric family
_COMMAND_RE = re.compile(r"^[A-Za-z][\w.]{0,47}$")


def process_birth(fallback: float) -> float:
    """When this process was born, as a `time.monotonic()` reading, from the
    kernel: the start time of `/proc/self/stat` (clock ticks after boot:
    10 ms steps, rounded down) against CLOCK_BOOTTIME. Where `/proc` is
    missing: `fallback` (the first line of `__main__`)."""
    mono = time.monotonic()
    try:
        with open("/proc/self/stat", "rb") as f:
            # the fields after the command's name, which may hold spaces
            ticks = int(f.read().rsplit(b")", 1)[1].split()[19])
        age = time.clock_gettime(time.CLOCK_BOOTTIME) - ticks / os.sysconf("SC_CLK_TCK")
        return min(fallback, mono - age)
    except (OSError, ValueError, IndexError, AttributeError):
        return fallback


def _depths(root: dict) -> Iterator[tuple[dict, int]]:
    stack = [(root, 0)]
    while stack:
        sp, depth = stack.pop()
        yield sp, depth
        stack.extend((c, depth + 1) for c in reversed(sp.get("spans", ())))


def cap_spans(root: dict, limit: int = REPORT_MAX_SPANS) -> int:
    """Cut a serialized tree to `limit` spans in place, the deepest first
    (a level goes whole or not at all, but for the one that straddles the
    limit, which keeps its earliest); -> how many were cut."""
    by_depth: dict[int, int] = {}
    for _, depth in _depths(root):
        by_depth[depth] = by_depth.get(depth, 0) + 1
    total = sum(by_depth.values())
    if total <= limit:
        return 0
    kept, room = 0, {}
    for depth in sorted(by_depth):
        room[depth] = max(0, min(by_depth[depth], limit - kept))
        kept += room[depth]
    level, depth = [root], 0
    while level:
        below = []
        for sp in level:
            children = sp.get("spans")
            if not children:
                continue
            take = min(len(children), room.get(depth + 1, 0))
            room[depth + 1] = room.get(depth + 1, 0) - take
            if take:
                sp["spans"] = children[:take]
                below.extend(sp["spans"])
            else:
                del sp["spans"]
        level, depth = below, depth + 1
    return total - kept


def flatten(trace: dict) -> dict:
    """A serialized trace as parallel lists, pre-order, for a profiler
    annotation's attributes (`shell.trace`): `names`, `what` (the attribute
    that tells one span of a name from another: an RPC's method, a command's
    name), `t_ns` after the root's start, `dur_ns`, `depth`, `thread` (0: the
    thread that ran the commands; n: the n-th other thread seen, from the
    spans' `thread=`, inherited by what nests under them), each joined into
    one string, beside `trace_id`, `birth_unix_ns` and `start_ms` (what
    `shell.start` says of itself: interpreter, imports, connect)."""
    names, what, t_ns, dur_ns, depth_of, thread_of = [], [], [], [], [], []
    start_ms = ""
    threads: dict[str, int] = {}
    stack = [(trace["root"], 0, 0)]
    while stack:
        sp, depth, thread = stack.pop()
        attrs = sp.get("attrs") or {}
        if "thread" in attrs:
            thread = threads.setdefault(str(attrs["thread"]), len(threads) + 1)
        if sp["name"] == "shell.start":
            start_ms = ";".join(str(attrs.get(k, 0)) for k in ("interp_ms", "import_ms", "connect_ms"))
        names.append(sp["name"])
        what.append(str(attrs.get("method") or attrs.get("command") or ""))
        t_ns.append(int(round(sp["t_ms"] * 1e6)))
        dur_ns.append(int(round(sp["dur_ms"] * 1e6)))
        depth_of.append(depth)
        thread_of.append(thread)
        stack.extend((c, depth + 1, thread) for c in reversed(sp.get("spans", ())))
    join = lambda xs: ";".join(map(str, xs))  # noqa: E731
    return {
        "trace_id": trace["trace_id"],
        "birth_unix_ns": int(trace.get("birth_unix_ns") or trace.get("unix_ns") or 0),
        "names": join(names), "what": join(what), "t_ns": join(t_ns),
        "dur_ns": join(dur_ns), "depth": join(depth_of), "thread": join(thread_of),
        "start_ms": start_ms,
    }


class _Received:
    """A finished trace another process handed over (`ReportTrace`), held in
    the ring as its own are: the serialized form is all there is of it."""

    __slots__ = ("state", "dur", "error", "_dict")

    def __init__(self, trace: dict):
        self.state = _TraceState(trace["trace_id"], trace["kind"], trace["class"])
        self.dur = float(trace["duration_s"])
        self.error = trace.get("error")
        self._dict = trace

    def to_dict(self) -> dict:
        return self._dict


def offer_received(trace: dict, ring: Optional[TraceRing] = None) -> bool:
    """Offer a handed-over trace (`_Completed.to_dict()`'s shape, checked
    and cut to REPORT_MAX_SPANS here: wire input) to this process's ring,
    as any root of its own. Raises ValueError on a malformed one."""
    try:
        tid = valid_id(trace["trace_id"])
        kind, root = trace["kind"], trace["root"]
        ok = (
            tid is not None and kind in SPAN_NAMES and isinstance(trace["class"], str)
            and isinstance(root, dict) and root["name"] == kind
            and float(trace["duration_s"]) >= 0 and float(root["dur_ms"]) >= 0
        )
    except (KeyError, TypeError, ValueError):
        ok = False
    if not ok:
        raise ValueError("not a serialized trace")
    trace["trace_id"] = tid
    cap_spans(root)
    return (ring or RING).offer(_Received(trace))


def script_phases(trace: dict) -> list[tuple[str, str, float]]:
    """A `shell.script` trace as (command, phase, seconds) rows, what the
    master folds into `weedtpu_shell_command_seconds`: per `shell.command`
    its `plan` (`shell.plan`), its `rpc` (the union of the `rpc.client`
    spans its own thread made outside the plan: what the command WAITED
    for, not what ran beside it) and its `other` (the rest of its wall);
    and the script's `start` (`shell.start`), under the first command,
    the one that waited for it."""
    rows: list[tuple[str, str, float]] = []
    start_ms = sum(c["dur_ms"] for c in trace["root"].get("spans", ()) if c["name"] == "shell.start")
    for cmd in trace["root"].get("spans", ()):
        if cmd["name"] != "shell.command":
            continue
        name = str((cmd.get("attrs") or {}).get("command", ""))
        if not _COMMAND_RE.match(name):
            name = "other"  # wire input: never a label of its own
        if start_ms:
            rows.append((name, "start", start_ms / 1e3))
            start_ms = 0.0
        plan_ms, waited = 0.0, []
        stack = list(cmd.get("spans", ()))
        while stack:
            sp = stack.pop()
            if sp["name"] == "shell.plan":
                plan_ms += sp["dur_ms"]
            elif "thread" in (sp.get("attrs") or {}):
                pass  # another thread's, with all under it
            elif sp["name"] == "rpc.client":
                waited.append((sp["t_ms"], sp["t_ms"] + sp["dur_ms"]))
            else:
                stack.extend(sp.get("spans", ()))
        rpc_ms, end = 0.0, None
        for a, b in sorted(waited):
            if end is None or a > end:
                rpc_ms, end = rpc_ms + (b - a), b
            elif b > end:
                rpc_ms, end = rpc_ms + (b - end), b
        rows += [
            (name, "plan", plan_ms / 1e3),
            (name, "rpc", rpc_ms / 1e3),
            (name, "other", max(0.0, cmd["dur_ms"] - plan_ms - rpc_ms) / 1e3),
        ]
    return rows


# -- the /debug/traces surface -------------------------------------------------


def debug_payload(request_path: str, ring: Optional[TraceRing] = None) -> dict:
    """The `/debug/traces` JSON body for one HTTP request path (query
    string included): filter by `kind`, `class`, `min_ms`, cap with
    `limit`. Shared by the volume-server and master HTTP fronts."""
    import urllib.parse

    q = {
        k: v[0]
        for k, v in urllib.parse.parse_qs(
            urllib.parse.urlparse(request_path).query
        ).items()
    }

    def _f(name: str, default: float) -> float:
        try:
            return float(q.get(name, default))
        except (TypeError, ValueError):
            return default

    ring = ring or RING
    return {
        "enabled": enabled(),
        "stats": ring.stats(),
        "traces": ring.snapshot(
            kind=q.get("kind") or None,
            klass=q.get("class") or None,
            min_duration=_f("min_ms", 0.0) / 1e3,
            limit=int(_f("limit", 100)),
        ),
    }


# -- rendering (ec.trace / tests) ---------------------------------------------


#: how far outside its `rpc.client` span a served root may begin and still be
#: its answer: two processes' wall clocks, each read beside a monotonic one
_JOIN_SLACK_NS = 2_000_000


def identity(trace: dict) -> tuple:
    """What tells one retained trace from another, wherever it was fetched."""
    return trace["trace_id"], trace["kind"], trace.get("unix_ns"), trace["start"], trace["duration_s"]


def render_trace(trace: dict, served: Optional[list] = None) -> str:
    """Human span tree with wall times — the `ec.trace` output format.

    trace=4f1d... http.read class=degraded 812.4ms
      +-   0.1ms   810.9ms ec.recover
      |  +-   0.2ms   540.0ms ec.gather shard=3
      ...

    The root's own attributes (an RPC's method, the shell's command)
    follow its class on the first line. `served`: (server, trace) pairs of
    the same id from the servers' rings; each `rpc.server` root among them
    is printed under the `rpc.client` span that sent it (the same method,
    begun inside the client's span by the wall clock, the target's own ring
    first), marked `@ <server>`, its times after its own start, and is
    taken out of the list: what is left there found no caller."""
    root_attrs = "".join(f" {k}={v}" for k, v in (trace["root"].get("attrs") or {}).items())
    lines = [
        f"trace={trace['trace_id']} {trace['kind']} "
        f"class={trace['class']}{root_attrs} {trace['duration_s'] * 1e3:.1f}ms"
        + (f" ERROR={trace['error']}" if trace.get("error") else "")
    ]

    def answer(sp: dict):
        """The served root that `sp`, an rpc.client span, waited for."""
        attrs = sp.get("attrs") or {}
        t0 = trace.get("unix_ns", trace["start"] * 1e9) + sp["t_ms"] * 1e6
        t1 = t0 + sp["dur_ms"] * 1e6
        found = [
            (server != attrs.get("target"), t["unix_ns"], i)
            for i, (server, t) in enumerate(served)
            if t["kind"] == "rpc.server" and t["trace_id"] == trace["trace_id"]
            and (t["root"].get("attrs") or {}).get("method") == attrs.get("method")
            and t0 - _JOIN_SLACK_NS <= t.get("unix_ns", t["start"] * 1e9) <= t1 + _JOIN_SLACK_NS
        ]
        if not found:
            return None
        got = served[min(found)[2]]
        # servers of one process (tests, `server`) share a ring: one root, once
        served[:] = [pair for pair in served if identity(pair[1]) != identity(got[1])]
        return got

    def walk(sp: dict, depth: int) -> None:
        attrs = " ".join(f"{k}={v}" for k, v in (sp.get("attrs") or {}).items())
        err = f" ERROR={sp['error']}" if sp.get("error") else ""
        lines.append(
            f"{'|  ' * depth}+- {sp['t_ms']:8.1f}ms {sp['dur_ms']:9.1f}ms "
            f"{sp['name']}" + (f" {attrs}" if attrs else "") + err
        )
        if served and sp["name"] == "rpc.client":
            got = answer(sp)
            if got is not None:
                server, t = got
                lines.append(
                    f"{'|  ' * (depth + 1)}@ {server} rpc.server {t['duration_s'] * 1e3:.1f}ms"
                    + (f" ERROR={t['error']}" if t.get("error") else "")
                )
                for c in t["root"].get("spans", ()):
                    walk(c, depth + 2)
        for c in sp.get("spans", ()):
            walk(c, depth + 1)

    for c in trace["root"].get("spans", ()):
        walk(c, 0)
    return "\n".join(lines)


# -- per-stage attribution (slo.py's aggregation input) ------------------------


def attribute_stages(trace: dict) -> dict[str, float]:
    """Per-stage attributed seconds for ONE trace, summing EXACTLY to
    its end-to-end duration.

    Each span's self-time (duration minus its children's) goes to its
    own name; the root's self-time goes to "other". Children that
    overlap in parallel (hedged/fan-out fetches, whose summed durations
    exceed the parent's wall time) are scaled down proportionally so a
    stage can never be attributed more wall time than actually passed —
    the property that makes per-class stage sums comparable against the
    observed e2e latencies."""
    stages: dict[str, float] = {}

    def walk(sp: dict, budget: float, is_root: bool) -> None:
        children = sp.get("spans") or []
        child_sum = sum(c["dur_ms"] for c in children) / 1e3
        scale = 1.0
        if child_sum > budget > 0:
            scale = budget / child_sum
        self_t = max(0.0, budget - child_sum * scale)
        key = "other" if is_root else sp["name"]
        stages[key] = stages.get(key, 0.0) + self_t
        for c in children:
            walk(c, (c["dur_ms"] / 1e3) * scale, False)

    walk(trace["root"], trace["duration_s"], True)
    return stages


def iter_spans(trace: dict) -> Iterator[dict]:
    stack = [trace["root"]]
    while stack:
        sp = stack.pop()
        yield sp
        stack.extend(sp.get("spans", ()))
