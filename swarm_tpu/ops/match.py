"""Device match kernel: response streams → word-slot bits → verdicts.

Pure jnp/XLA (a fused Pallas variant comes later); everything is static
shape, vector ops, gathers from small tables, and a handful of scatters.
Pipeline per batch (design in fingerprints/compile.py docstring):

1. Rolling q-gram hashes of each (stream, case) in use — shifted
   multiply-adds only.
2. Per word-table: Bloom probe every window (2 gathers from a 32 KiB
   bitmap), top-k the surviving windows, binary-search the sorted h1
   groups, then check entry h2 + suffix-gram h1/h2 and position bounds.
   Hits scatter into a word-slot bit vector; all q-gram hits are marked
   *uncertain* (host confirms sparse hits — exactness contract).
3. Tiny slots (1–3 bytes) evaluate by dense shifted compare — exact.
4. Verdict lowering: slot buckets → matcher bits (and/or + negation),
   scalar programs (status/size/len dsl), then op and template
   reductions. Uncertainty propagates alongside values.

The kernel's guarantee: a (row, template) pair whose uncertain bit is
clear has the exact oracle verdict; uncertain pairs carry a superset
signal and only ever need host confirmation when something *fired*.
"""

from __future__ import annotations

import functools
import threading
import warnings
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

# The dispatch path donates the staged per-batch uploads and the
# inter-phase rank plane (DeviceDB._phase_b). The kernel's outputs are
# deliberately tiny packed-bit planes, so XLA usually CANNOT alias a
# donated input into an output and warns about it at compile time —
# that is expected, not a bug: donation here buys early buffer release
# (staged batches free at kernel launch instead of at collect, which
# bounds device footprint with ≥2 batches in flight), not output
# aliasing. Filter exactly that message.
warnings.filterwarnings(
    "ignore", message="Some donated buffers were not usable"
)

from swarm_tpu.fingerprints import compile as fpc
from swarm_tpu.ops import hashing
from swarm_tpu.ops.encoding import STREAMS
from swarm_tpu.telemetry import tracing

# every span (telemetry/tracing.py) of a process that matches on the
# device also reaches a recording JAX profiler session, on the device
# trace's clock
tracing.install_profiler_hook(jax.profiler.TraceAnnotation)


#: max live compiled step functions on the SHARDED matcher (its pjit
#: cache is still bounded per shape). The single-device DeviceDB's
#: executables take the corpus as arguments (docs/DEVICE_MATCH.md) so
#: a shape entry is small and all buckets of a width class share one;
#: its jit cache is only dropped wholesale past 4x this bound (see
#: DeviceDB.dispatch's shape-churn guard). Coarse width buckets
#: (engine width_multiple=512) and 256-row buckets keep the live shape
#: set tiny either way. Override: SWARM_MAX_COMPILED.
import os as _os

MAX_COMPILED = int(_os.environ.get("SWARM_MAX_COMPILED", "8"))


def lru_fetch(cache: dict, key):
    """Get + refresh (move-to-back) — dict order is the LRU order."""
    val = cache.pop(key, None)
    if val is not None:
        cache[key] = val
    return val


def lru_store(cache: dict, key, val, cap: int = 0) -> None:
    while cap and len(cache) >= cap:
        cache.pop(next(iter(cache)))
    cache[key] = val


def fused_plane_widths(db: "fpc.CompiledDB") -> list:
    """Byte widths of the ``full``-mode output planes in fused order:
    t_value, t_unc, op_value, op_unc, m_unc (packed bits), then — only
    when the corpus lowered workflow gate tables — wf_cond_v,
    wf_cond_u, wf_emit_v, wf_emit_u, and finally the 1-byte overflow
    column."""
    # widths mirror eval_verdicts' plane allocations exactly: the
    # template planes are padded to max(NT, 1) there (an all-host-tail
    # corpus still emits one packed byte), the op/matcher planes are not
    nbt = (max(db.num_templates, 1) + 7) >> 3
    nbo = (db.op_src.shape[0] + 7) >> 3
    nbm = (db.m_src.shape[0] + 7) >> 3
    widths = [nbt, nbt, nbo, nbo, nbm]
    wf = getattr(db, "wf", None)
    if wf is not None and wf.num_terms:
        nbc = (wf.num_conds + 7) >> 3
        nbe = (wf.num_emits + 7) >> 3
        widths += [nbc, nbc, nbe, nbe]
    widths.append(1)
    return widths


def fuse_planes(planes, overflow):
    """Producer half of the fused full-mode output: pack the five bit
    planes and append the overflow byte column — ONE device array, one
    host read. Keep in lockstep with split_fused below (shared by both
    backends so producer and consumer live in this module)."""
    parts = [jnp.packbits(p, axis=1) for p in planes]
    parts.append(overflow[:, None].astype(jnp.uint8))
    return jnp.concatenate(parts, axis=1)


def split_fused(db: "fpc.CompiledDB", buf: np.ndarray):
    """Slice one fused host buffer back into the engine's six outputs.

    The ``full`` planes ship as ONE device array (see DeviceDB.match):
    a single device-to-host read instead of six, saving five dispatch
    round-trips per batch.

    The buffer is normalized to C order here: XLA owns the device
    layout and is free to hand back a Fortran-ordered result (observed
    on TPU for corpus-scale plane shapes), and every downstream
    consumer — plane slicing, packbits math, the native sw_ext_resolve
    pass — assumes row-major.
    """
    buf = np.ascontiguousarray(buf)
    outs = []
    off = 0
    for w in fused_plane_widths(db):
        outs.append(buf[:, off : off + w])
        off += w
    if off != buf.shape[1]:
        # producer/consumer drift would otherwise shear every plane
        # after the mismatch silently (op bits read as template bits)
        raise ValueError(
            f"fused buffer is {buf.shape[1]} bytes wide, plane widths "
            f"sum to {off}"
        )
    if len(outs) == 10:
        pt, pu, opv, opu, mu, cv, cu, ev, eu, ovf = outs
        wf = (cv, cu, ev, eu)
    else:
        pt, pu, opv, opu, mu, ovf = outs
        wf = None
    return pt, pu, opv, opu, mu, ovf[:, 0] != 0, wf


_DEV_METRICS: dict = {}


def _device_metrics() -> dict:
    """Lazy device-kernel metric families (kept out of import time so
    oracle-only users never touch the registry). The staging/compaction
    families live in :mod:`swarm_tpu.telemetry.device_export` (created
    at telemetry import so every process's ``/metrics`` renders them);
    this merges both maps."""
    if not _DEV_METRICS:
        from swarm_tpu.telemetry import REGISTRY, device_export

        _DEV_METRICS["compile_seconds"] = REGISTRY.counter(
            "swarm_device_compile_seconds_total",
            "Seconds spent compiling device match executables",
        )
        _DEV_METRICS["compiles"] = REGISTRY.counter(
            "swarm_device_compile_total",
            "Device match dispatches that compiled a new executable",
        )
        _DEV_METRICS["phase_ms"] = REGISTRY.gauge(
            "swarm_device_phase_ms",
            "Device match per-phase milliseconds from the most recent "
            "instrumented batch (DeviceDB.profile_phases)",
            ("phase",),
        )
        _DEV_METRICS["staged_batches"] = device_export.STAGED_BATCHES
        _DEV_METRICS["staged_bytes"] = device_export.STAGED_BYTES
        _DEV_METRICS["donated"] = device_export.DONATED_DISPATCHES
        _DEV_METRICS["compacted"] = device_export.COMPACTED_DISPATCHES
        _DEV_METRICS["survivor_max"] = device_export.SURVIVOR_MAX
        _DEV_METRICS["verify_k"] = device_export.VERIFY_K
    return _DEV_METRICS


def _env_flag(name: str, default: bool) -> bool:
    raw = _os.environ.get(name, "").strip().lower()
    if not raw:
        return default
    return raw not in ("0", "off", "false", "no")


def host_batch_leaves(streams: dict, lengths: dict, status) -> bool:
    """Whether every per-batch input leaf is host numpy — the donation
    precondition shared by :class:`DeviceDB` and the sharded matcher
    (parallel/sharded.py). Caller-owned DEVICE arrays must never be
    donated (the caller may reuse them next call; donation would hand
    it a deleted buffer)."""
    leaves = list(streams.values()) + list(lengths.values()) + [status]
    return all(isinstance(v, np.ndarray) for v in leaves)


class _StagingPool:
    """Per-batch device-upload staging for dispatch.

    Every dispatch uploads fresh ``streams``/``lengths``/``status``
    host arrays; this is the single place that upload happens. With
    ``donate_argnums`` on the consuming kernel (DeviceDB's phase B) the
    staged buffers are handed back to XLA's allocator the moment the
    kernel runs, so the next same-shape upload and the kernel's own
    outputs reuse that memory instead of allocate-upload-free on every
    dispatch; on the non-donated arms the staged set dies with the
    launch, which is the same lifetime the legacy path had. Host
    arrays are always copied on upload (jnp.asarray), so donation can
    never invalidate caller-owned numpy (the engine's recycled encode
    planes keep rotating untouched).

    Accounting only — no aliasing decisions live here: ``uploads`` /
    ``bytes`` back the ``swarm_device_staged_*`` families, updated
    under a lock because dispatch runs on both the scheduler's submit
    thread and the walk-offload worker.
    """

    def __init__(self):
        self.uploads = 0  # guarded-by: _lock
        self.bytes = 0  # guarded-by: _lock
        #: rank planes held by not-yet-launched deferred reductions
        #: (the sharded matcher's double-buffered overlap parks the
        #: per-rank bit planes in a _PendingShard between dispatches —
        #: an extra in-flight plane the pool budget must see)
        self.plane_holds = 0  # guarded-by: _lock
        self.plane_bytes = 0  # guarded-by: _lock
        self._lock = threading.Lock()

    def stage(self, streams: dict, lengths: dict, status):
        """Upload one batch; returns (streams, lengths, status) as
        device arrays (pass-through for already-device inputs) plus
        the staged host byte count for this batch."""
        s_j = {k: jnp.asarray(v) for k, v in streams.items()}
        l_j = {k: jnp.asarray(v) for k, v in lengths.items()}
        st_j = jnp.asarray(status)
        n_bytes = int(
            sum(getattr(v, "nbytes", 0) for v in streams.values())
            + sum(getattr(v, "nbytes", 0) for v in lengths.values())
            + int(getattr(status, "nbytes", 0))
        )
        self.account(n_bytes)
        return s_j, l_j, st_j, n_bytes

    def account(self, n_bytes: int) -> None:
        """Record one staged batch whose upload happened elsewhere (the
        sharded matcher's multi-process path builds global jax.Arrays
        itself — the accounting contract stays in this one place)."""
        with self._lock:
            self.uploads += 1
            self.bytes += n_bytes

    def hold_plane(self, n_bytes: int) -> None:
        """One deferred reduction parked its rank planes (device bytes
        that stay live past their dispatch until the launch flushes)."""
        with self._lock:
            self.plane_holds += 1
            self.plane_bytes += int(n_bytes)

    def release_plane(self, n_bytes: int) -> None:
        with self._lock:
            self.plane_holds -= 1
            self.plane_bytes -= int(n_bytes)


class DeviceDB:
    """CompiledDB uploaded to device + the jitted match kernels.

    The corpus arrays are uploaded ONCE (the argument-layout pytree,
    compile.build_device_layout) and passed to the jitted kernels as
    device-resident arguments on every call. The traced programs are
    corpus-size-free: all width buckets of a shape class share one
    executable per batch shape, compile time no longer scales with the
    corpus, and the persistent XLA cache (utils/xlacache.py) hits
    across corpus refreshes. The corpus arrays are never donated —
    every subsequent call reuses them in place.

    Production dispatch is SPLIT-PHASE with survivor compaction
    (docs/DEVICE_MATCH.md): a standing phase-A executable (stacked
    bloom probe → survivor rank plane + per-batch max survivor count)
    runs first; the host reads back ONE scalar (the max), rounds it up
    the power-of-two bucket ladder (compile.survivor_bucket), and
    launches phase B — candidate extraction, gather-verify, tiny,
    regex, verdict lowering — at that compacted width instead of the
    global candidate budget. Per-batch ``streams``/``lengths``/
    ``status`` go through the dispatch staging pool and are DONATED to
    phase B (with the inter-phase rank plane), so XLA reuses the
    staged buffers for kernel outputs across batches. Both knobs are
    runtime-flippable (``compact`` / ``donate`` attributes; env
    ``SWARM_DEVICE_COMPACT`` / ``SWARM_DEVICE_DONATE``); the fused
    non-donated single-kernel arm is kept as the legacy reference twin
    (the bench's dispatch A/B baseline), bit-identical by construction
    since both routes share one verify + verdict lowering.

    ``compile_seconds`` / ``compile_count`` accumulate the wall time
    and count of DISPATCHES that triggered at least one fresh
    executable (measured at the dispatch boundary — launch + phase-A
    wait included, not phase-B compute).

    Cross-thread hand-off (docs/HOST_WALK.md): with the scheduler's
    walk offload, :meth:`dispatch` runs on the submit thread while the
    walk worker calls :meth:`collect` on an earlier batch's output —
    JAX serializes the device work itself, and the WHOLE compile-spy
    read/launch/read/evict sequence runs under ``_counter_lock`` so
    two dispatching threads can neither lose increments nor
    mis-attribute another thread's compile (the read-before/read-after
    pair is atomic now)."""

    MAX_COMPILED = MAX_COMPILED  # legacy alias (sharded path shares it)

    def __init__(
        self,
        db: fpc.CompiledDB,
        candidate_k: int = 128,
        compact: Optional[bool] = None,
        donate: Optional[bool] = None,
    ):
        self.db = db
        self.candidate_k = candidate_k
        self.compact = (
            _env_flag("SWARM_DEVICE_COMPACT", True)
            if compact is None
            else bool(compact)
        )
        self.donate = (
            _env_flag("SWARM_DEVICE_DONATE", True)
            if donate is None
            else bool(donate)
        )
        self.compile_seconds = 0.0  # guarded-by: _counter_lock
        self.compile_count = 0  # guarded-by: _counter_lock
        #: AOT executable-cache twin of the compile spy (docs/AOT.md):
        #: dispatches that LOADED at least one published executable
        #: instead of compiling it, and the wall time those loads took
        #: at the dispatch boundary — counted distinctly so the
        #: compile-count spy stays honest on the fetch path
        self.fetch_seconds = 0.0  # guarded-by: _counter_lock
        self.fetch_count = 0  # guarded-by: _counter_lock
        #: most recent compacted dispatch: survivor_max / verify_k /
        #: budget (the "phase B launches at survivor size" evidence —
        #: bench and tools/profile_device surface it)
        self.last_compact: dict = {}  # guarded-by: _counter_lock
        self.staging = _StagingPool()
        self._counter_lock = threading.Lock()
        self._aot = None  # AotClient (attach_aot) — None = compile-only
        self._meta = None
        self._arrays = None  # device-resident argument pytree
        self._arrays_np = None  # host twin of _arrays (delta refresh)
        # full flag -> fused jit fn (legacy arm); "A" -> phase A;
        # ("B", full, donate_streams) -> phase B. Writes only under the
        # lock; the double-checked fast-path .get() reads are benign
        # (dict get is atomic, a miss just takes the locked slow path)
        self._fn_cache: dict = {}  # guarded-by: _counter_lock

    # ------------------------------------------------------------------
    def _ensure_layout(self):
        if self._arrays is None:
            meta, arrays_np = fpc.build_device_layout(self.db)
            self._meta = meta
            self._arrays_np = arrays_np
            # upload once; jnp.asarray leaves numpy → device committed
            self._arrays = jax.tree_util.tree_map(jnp.asarray, arrays_np)
        return self._meta, self._arrays

    # -- AOT executable cache (docs/AOT.md) ----------------------------
    def attach_aot(self, client) -> None:
        """Attach an :class:`~swarm_tpu.aot.AotClient`: every
        subsequently built kernel wrapper becomes an
        :class:`~swarm_tpu.aot.AotJit` that fetches published
        executables before compiling (and publishes what it compiles).
        Live wrappers are dropped so the attach takes effect at the
        next dispatch; ``None`` detaches."""
        with self._counter_lock:
            self._aot = client
            self._fn_cache.clear()

    def _trace_salt(self, db=None, meta=None) -> str:
        """Everything the traced programs depend on besides argument
        shapes (the aval signature covers those) and the corpus BYTES
        (corpus-free by the PR 3 argument convention): layout metadata
        and the static ints the kernel closures read off ``db``."""
        if db is None:
            db = self.db
        if meta is None:
            meta, _ = self._ensure_layout()
        return repr(
            (
                meta,
                self.candidate_k,
                db.num_slots,
                db.num_templates,
                int(db.op_src.shape[0]),
                int(db.m_src.shape[0]),
                int(db.rx_seq_always.sum()),
            )
        )

    def _layout_signature(self, db, meta, arrays_np) -> tuple:
        """The full trace signature of one (db, layout) pair: the
        trace salt plus every layout leaf's (path, shape, dtype). Two
        equal signatures lower IDENTICAL programs, so the live
        executables can keep serving across a corpus refresh — the
        corpus rides the arguments (docs/DEVICE_MATCH.md), a verdict
        can only depend on the array CONTENT the next dispatch
        passes."""
        leaves = jax.tree_util.tree_flatten_with_path(arrays_np)[0]
        return (
            self._trace_salt(db, meta),
            tuple(
                (
                    jax.tree_util.keystr(p),
                    tuple(leaf.shape),
                    str(leaf.dtype),
                )
                for p, leaf in leaves
            ),
        )

    def update_layout(self, db_new) -> dict:
        """Zero-downtime corpus refresh (docs/AOT.md): swap in a new
        CompiledDB, uploading ONLY the layout leaves the delta build
        actually changed — a leaf adopted by object identity
        (``compile.build_device_layout_delta``) keeps its existing
        DEVICE array, no H2D transfer. When the trace signature is
        unchanged (shapes and statics equal — e.g. a template EDIT
        that keeps every width), the live executables keep serving
        and the refresh costs only the changed uploads; otherwise the
        wrapper cache drops and the next dispatch compiles or AOT-
        fetches against the new shapes.

        Caller contract: quiesce dispatches first (no batch in
        flight) — the engine's :meth:`~swarm_tpu.ops.engine.
        MatchEngine.refresh_corpus` is the supported entry point."""
        meta_old, _ = self._ensure_layout()
        old_np = self._arrays_np
        meta_new, new_np = fpc.build_device_layout(db_new)
        old_host = {
            jax.tree_util.keystr(p): leaf
            for p, leaf in jax.tree_util.tree_flatten_with_path(old_np)[0]
        }
        old_dev = {
            jax.tree_util.keystr(p): leaf
            for p, leaf in jax.tree_util.tree_flatten_with_path(
                self._arrays
            )[0]
        }
        flat_new, _ = jax.tree_util.tree_flatten_with_path(new_np)
        uploaded = reused = 0
        dev_leaves = []
        for path, leaf in flat_new:
            key = jax.tree_util.keystr(path)
            if old_host.get(key) is leaf and key in old_dev:
                dev_leaves.append(old_dev[key])
                reused += 1
            else:
                dev_leaves.append(jnp.asarray(leaf))
                uploaded += 1
        new_dev = jax.tree_util.tree_unflatten(
            jax.tree_util.tree_structure(new_np), dev_leaves
        )
        keep = self._layout_signature(
            self.db, meta_old, old_np
        ) == self._layout_signature(db_new, meta_new, new_np)
        with self._counter_lock:
            self.db = db_new
            self._meta = meta_new
            self._arrays_np = new_np
            self._arrays = new_dev
            if not keep:
                self._fn_cache.clear()
        return {
            "uploaded_leaves": uploaded,
            "reused_leaves": reused,
            "executables_kept": keep,
        }

    def _wrap_jit(
        self, fun, kernel_id: str, static_argnums=(), donate_argnums=()
    ):
        """``jax.jit`` (no AOT client — today's path, bit-for-bit) or
        the explicitly managed :class:`AotJit` twin."""
        if self._aot is None:
            return jax.jit(
                fun,
                static_argnums=static_argnums,
                donate_argnums=donate_argnums,
            )
        from swarm_tpu.aot.jitcache import AotJit

        return AotJit(
            fun,
            kernel_id=kernel_id,
            salt=self._trace_salt(),
            client=self._aot,
            static_argnums=static_argnums,
            donate_argnums=donate_argnums,
            cap=4 * self.MAX_COMPILED,
        )

    def fetched_executable_count(self, full: bool = True) -> int:
        """Live executables serving this DB that were LOADED from the
        AOT cache instead of compiled — the fetch-path twin of
        :meth:`executable_count` (which counts local compiles only).
        Includes the standing phase-A kernel: a warm-fetch bring-up
        should compile nothing at all."""
        from swarm_tpu.aot.jitcache import fetched_size_of

        n = 0
        for key in (full, ("B", full, True), ("B", full, False), "A"):
            fn = self._fn_cache.get(key)
            if fn is not None:
                n += fetched_size_of(fn)
        return n

    def aot_prewarm(self) -> int:
        """Bring-up fetch (worker/runtime.py): pool every published
        executable for this process's program group so the first
        dispatch of each published shape class loads instead of
        compiling. No-op without an attached client."""
        client = self._aot
        return client.prewarm() if client is not None else 0

    def _budget(self) -> int:
        meta, _ = self._ensure_layout()
        return global_candidate_budget(
            self.candidate_k, len(meta.table_stream)
        )

    def _kernel(self, full: bool):
        """The fused single-kernel arm (legacy reference twin)."""
        # double-checked under _counter_lock: two threads first-touching
        # the same shape class must share ONE jitted wrapper, or each
        # compiles its own twin and the spy double-counts the compile
        fn = self._fn_cache.get(full)
        if fn is not None:
            return fn
        with self._counter_lock:
            fn = self._fn_cache.get(full)
            if fn is None:
                db, k = self.db, self.candidate_k
                meta, _ = self._ensure_layout()

                # jit-captures: db, meta, k, full (host metadata +
                # scalars — trace-static by construction; the corpus
                # rides the `arrays` ARGUMENT, never the closure)
                # name pinned: kernel.match_ms reads jit_kernel (test_program_spans)
                def kernel(arrays, streams, lengths, status):
                    out = _match_impl_args(
                        db, meta, k, arrays, streams, lengths, status,
                        full=full,
                    )
                    if full:
                        # bit-plane outputs ship packed (MSB-first,
                        # np.packbits convention): ~9× less host
                        # transfer — and FUSED into one array so the
                        # host makes exactly one device read
                        # (split_fused slices it back)
                        *planes, overflow = out
                        return fuse_planes(planes, overflow)
                    return out

                fn = self._wrap_jit(kernel, f"dd.fused.full={full}")
                self._fn_cache[full] = fn
        return fn

    def _phase_a(self):
        """Standing phase-A executable: staged streams → survivor rank
        plane, overflow vector, and the batch's max survivor count
        (the ONE scalar the host reads between phases). Built once;
        jit's shape cache serves every width bucket."""
        fn = self._fn_cache.get("A")
        if fn is not None:
            return fn
        with self._counter_lock:
            fn = self._fn_cache.get("A")
            if fn is None:
                meta, _ = self._ensure_layout()
                budget = self._budget()

                # jit-captures: meta, budget (layout metadata + a
                # python int; both trace-static)
                # name pinned: kernel.match_ms reads jit_kernel_a (test_program_spans)
                def kernel_a(arrays, streams, lengths):
                    streams = ensure_all_stream(streams, lengths)
                    ctx = _StreamCtx(streams, lengths)
                    cnt, _cs = prefilter_counts(meta, arrays["tab"], ctx)
                    n_surv = cnt[:, -1]
                    K = max(1, min(budget, cnt.shape[1]))
                    overflow = n_surv > K
                    nmax = jnp.max(jnp.minimum(n_surv, K))
                    return cnt, overflow, nmax

                fn = self._wrap_jit(kernel_a, "dd.A")
                self._fn_cache["A"] = fn
        return fn

    def _phase_b(self, full: bool, donate_streams: bool):
        """Phase-B executable family: survivor extraction at the
        static ladder width ``kc`` + gather-verify + tiny + regex +
        verdict lowering. The staged per-batch uploads and the
        inter-phase rank plane are donated so XLA reuses their buffers
        for outputs (``donate_streams=False`` — caller-owned device
        inputs — still donates the rank plane, which this DB owns)."""
        key = ("B", full, donate_streams)
        fn = self._fn_cache.get(key)
        if fn is not None:
            return fn
        with self._counter_lock:
            fn = self._fn_cache.get(key)
            if fn is not None:
                return fn
            db, k = self.db, self.candidate_k
            meta, _ = self._ensure_layout()

            # jit-captures: db, meta, k, full (same contract as the
            # fused kernel: metadata and scalars only)
            # name pinned: kernel.match_ms reads jit_kernel_b (test_program_spans)
            def kernel_b(kc, arrays, streams, lengths, status, cnt,
                         overflow):
                streams = ensure_all_stream(streams, lengths)
                ctx = _StreamCtx(streams, lengths)
                budget = max(
                    1,
                    min(
                        global_candidate_budget(k, len(meta.table_stream)),
                        cnt.shape[1],
                    ),
                )
                col = compact_candidates(cnt, kc, budget)
                col_starts = _col_starts_of(meta, streams)
                value_bits, uncertain_bits = verify_candidates(
                    meta,
                    arrays["tab"],
                    arrays["slot_bytes"],
                    arrays["slot_len"],
                    ctx,
                    col,
                    col_starts,
                    db.num_slots,
                )
                value_bits = tiny_slot_bits(
                    meta, arrays["tiny_bytes"], arrays["tiny_slot"], ctx,
                    value_bits,
                )
                out = _finish_match(
                    db, meta, arrays, streams, lengths, status,
                    value_bits, uncertain_bits, overflow, full=full,
                )
                if full:
                    *planes, ovf = out
                    return fuse_planes(planes, ovf)
                return out

            donate = (
                (2, 3, 4, 5, 6) if donate_streams else (5, 6)
            )  # streams, lengths, status, cnt, overflow | cnt, overflow
            fn = self._wrap_jit(
                kernel_b,
                f"dd.B.full={full}",
                static_argnums=(0,),
                donate_argnums=donate,
            )
            self._fn_cache[key] = fn
        return fn

    def executable_count(self, full: bool = True) -> int:
        """Live compiled executables serving the ``full``-mode verify
        (the compile-count spy the width-bucket tests use): the phase-B
        family on the compacted path plus the fused legacy arm."""
        n = 0
        for key in (full, ("B", full, True), ("B", full, False)):
            fn = self._fn_cache.get(key)
            if fn is not None and hasattr(fn, "_cache_size"):
                n += int(fn._cache_size())
        return n

    def lowered_text(
        self, streams: dict, lengths: dict, status, full: bool = True
    ) -> str:
        """StableHLO text of the production kernel(s) for these shapes
        — the corpus-constants regression test inspects this. On the
        compacted path this is phase A and phase B concatenated (both
        must be corpus-free); with ``compact`` off, the fused twin."""
        meta, arrays = self._ensure_layout()
        s_j = {k: jnp.asarray(v) for k, v in streams.items()}
        l_j = {k: jnp.asarray(v) for k, v in lengths.items()}
        st_j = jnp.asarray(status)
        if not (self.compact and len(meta.table_stream)):
            fn = self._kernel(full)
            return fn.lower(arrays, s_j, l_j, st_j).as_text()
        fa = self._phase_a()
        # inspection only — lower phase B against shape avatars of
        # phase A's outputs (corpus-freeness holds at every ladder
        # rung, so the smallest one serves) instead of executing the
        # prefilter on device just to render text
        cnt_s, overflow_s, _ = jax.eval_shape(fa, arrays, s_j, l_j)
        kc = fpc.survivor_bucket(0, self._budget())
        fb = self._phase_b(full, self.donate)
        return (
            fa.lower(arrays, s_j, l_j).as_text()
            + "\n"
            + fb.lower(
                kc, arrays, s_j, l_j, st_j, cnt_s, overflow_s
            ).as_text()
        )

    # ------------------------------------------------------------------
    def match(self, streams: dict, lengths: dict, status, full: bool = False):
        """streams: name → uint8 [B, W]; lengths: name → int32 [B].

        Returns (t_value [B, NT] bool, t_uncertain [B, NT] bool,
        overflow [B] bool); with ``full`` the op/matcher planes are
        included: (t_value, t_unc, op_value, op_unc, m_unc, overflow)
        — the engine's sparse-confirmation inputs, packed, and already
        materialized as HOST numpy views of one fused device read
        (split_fused).
        """
        out = self.dispatch(streams, lengths, status, full=full)
        if full:
            return self.collect(out)
        return out

    @staticmethod
    def _all_host(streams: dict, lengths: dict, status) -> bool:
        """Donation precondition — see :func:`host_batch_leaves` (the
        module helper, shared with the sharded matcher); dispatches
        with caller-owned device inputs take the non-donated phase-B
        variant instead."""
        return host_batch_leaves(streams, lengths, status)

    def _spied_launch(self, fns: list, launch):
        """Run ``launch()`` with the compile spy held atomically: the
        cache-size read-before/read-after pair, the counter updates,
        and the shape-churn eviction all happen under ``_counter_lock``
        so concurrent dispatching threads (scheduler submit + walk
        offload, or two engines) can't interleave and lose or
        double-count a compile. The lock does serialize concurrent
        dispatches on one DeviceDB for the duration of ``launch()``
        (incl. the compacted path's phase-A scalar sync) — accepted:
        production has a single dispatching thread per DB (the walk
        offload thread only collects), so the lock is uncontended
        there, and attribution under the 4x eviction cannot be made
        race-free with snapshot-outside-lock reads."""
        import time as _time

        from swarm_tpu.aot.jitcache import fetched_size_of

        spies = [fn for fn in fns if hasattr(fn, "_cache_size")]
        with self._counter_lock:
            n0 = sum(fn._cache_size() for fn in spies)
            f0 = sum(fetched_size_of(fn) for fn in spies)
            t0 = _time.perf_counter()
            out = launch()
            dt = _time.perf_counter() - t0
            grew = sum(fn._cache_size() for fn in spies) - n0
            grew_f = sum(fetched_size_of(fn) for fn in spies) - f0
            if grew_f > 0:
                # a deserialized AOT load is NOT a compile (docs/AOT.md
                # — the fetch-path honesty contract): it gets its own
                # spy pair; a dispatch that fetched one phase and
                # compiled the other counts on both
                self.fetch_seconds += dt
                self.fetch_count += 1
            if grew > 0:
                self.compile_seconds += dt
                self.compile_count += 1
                m = _device_metrics()
                m["compile_seconds"].inc(dt)
                m["compiles"].inc(1)
                # shape-churn guard: jax.jit never evicts entries, so
                # adversarial width/row variety would grow the caches
                # without bound. Executables are corpus-free (small),
                # hence the generous 4x bound; past it the whole cache
                # drops — a rare recompile beats unbounded RSS.
                for fn in spies:
                    if fn._cache_size() > 4 * self.MAX_COMPILED and hasattr(
                        fn, "clear_cache"
                    ):
                        fn.clear_cache()
        return out

    @tracing.spanned("device.launch")
    def dispatch(self, streams: dict, lengths: dict, status, full: bool = True):
        """Async half of :meth:`match`: stage the batch, launch the
        kernel(s), and return the (device-resident, still-computing)
        fused output WITHOUT a full host transfer. JAX dispatch is
        asynchronous, so the kernels crunch while the caller does other
        host work — the continuous-batching scheduler dispatches batch
        i+1 here before walking batch i's verdicts. :meth:`collect`
        finalizes.

        On the compacted path the only blocking point is the phase-A
        max-survivor scalar read (4 bytes) that picks phase B's ladder
        width; phase B itself is launched asynchronously at that
        width."""
        from swarm_tpu.resilience.faults import fault_point

        # always-on flight-ring record BEFORE the fault point: when a
        # seeded device.dispatch fault fires, the resulting flight dump
        # carries the dispatch that tripped it (docs/OBSERVABILITY.md)
        tracing.flight_event("device.dispatch")
        # device-path chaos lever (docs/RESILIENCE.md): stands in for
        # XLA compile errors / OOM / cache corruption; MatchEngine
        # catches the failure and degrades to the exact CPU oracle
        fault_point("device.dispatch")
        meta, arrays = self._ensure_layout()
        if not (self.compact and len(meta.table_stream)):
            # fused legacy/reference arm (also the no-tables corpus,
            # where there is nothing to compact)
            fn = self._kernel(full)
            s_j, l_j, st_j, staged = self.staging.stage(
                streams, lengths, status
            )
            m = _device_metrics()
            m["staged_batches"].inc(1)
            m["staged_bytes"].inc(staged)
            return self._spied_launch(
                [fn], lambda: fn(arrays, s_j, l_j, st_j)
            )

        donate_streams = self.donate and self._all_host(
            streams, lengths, status
        )
        fa = self._phase_a()
        fb = self._phase_b(full, donate_streams)
        s_j, l_j, st_j, staged = self.staging.stage(
            streams, lengths, status
        )
        budget = self._budget()
        m = _device_metrics()

        # requires-lock: _counter_lock (invoked via _spied_launch)
        def launch():
            cnt, overflow, nmax = fa(arrays, s_j, l_j)
            # the ONE host sync between phases: a scalar read that
            # sizes phase B to live work instead of worst-case budget
            with tracing.span("device.phase_a_wait"):
                # host-sync-ok: the blessed 4-byte phase-A survivor scalar
                n_live = int(nmax)
            kc = fpc.survivor_bucket(n_live, budget)
            out = fb(kc, arrays, s_j, l_j, st_j, cnt, overflow)
            self.last_compact = {
                "survivor_max": n_live,
                "verify_k": kc,
                "budget": budget,
            }
            return out

        out = self._spied_launch([fa, fb], launch)
        m["staged_batches"].inc(1)
        m["staged_bytes"].inc(staged)
        m["compacted"].inc(1)
        if donate_streams:
            m["donated"].inc(1)
        lc = self.last_compact
        m["survivor_max"].set(lc["survivor_max"])
        m["verify_k"].set(lc["verify_k"])
        return out

    def collect(self, out):
        """Blocking half of the full-mode split: one host read of the
        fused plane array, sliced into the engine's six outputs."""
        with tracing.span("device.result_wait"):
            out = np.asarray(out)
        return split_fused(self.db, out)

    # ------------------------------------------------------------------
    def profile_phases(self, streams: dict, lengths: dict, status) -> dict:
        """Per-phase device milliseconds for ONE batch — the
        attribution surface behind ``tools/profile_device.py`` and the
        ``swarm_device_phase_ms`` gauges.

        Runs each phase as its own jitted call with a blocking sync
        between phases, so the numbers attribute where fresh-batch
        milliseconds go (prefilter / compact / gather / verify / regex
        lanes / verdict / transfer). Phase boundaries mirror the
        production split-phase dispatch: ``prefilter`` is the standing
        phase-A rank-plane kernel, ``compact`` the survivor extraction
        at the batch's measured ladder width, and gather/verify run AT
        THAT WIDTH — ``self.last_compact`` records the
        survivor_max/verify_k/budget evidence. ``verify`` is reported
        as (full phase B) − (hash-screen-only phase B).
        """
        import functools as _functools
        import time as _time

        db, k = self.db, self.candidate_k
        meta, arrays = self._ensure_layout()
        s_j = {k2: jnp.asarray(v) for k2, v in streams.items()}
        l_j = {k2: jnp.asarray(v) for k2, v in lengths.items()}
        st_j = jnp.asarray(status)
        ns = db.num_slots

        def run(fn, *a):
            r = fn(*a)
            jax.block_until_ready(r)
            t0 = _time.perf_counter()
            r = fn(*a)  # timed second call: steady-state, post-compile
            jax.block_until_ready(r)
            return r, (_time.perf_counter() - t0) * 1e3

        budget = global_candidate_budget(k, len(meta.table_stream))

        @jax.jit
        def f_pre(arrays, streams, lengths):  # jit-captures: meta, budget
            streams = ensure_all_stream(streams, lengths)
            ctx = _StreamCtx(streams, lengths)
            cnt, _cs = prefilter_counts(meta, arrays["tab"], ctx)
            n_surv = cnt[:, -1]
            K = max(1, min(budget, cnt.shape[1]))
            return cnt, n_surv > K, jnp.max(jnp.minimum(n_surv, K))

        @_functools.partial(jax.jit, static_argnums=(1,))
        def f_compact(cnt, kc):  # jit-captures: budget
            K = max(1, min(budget, cnt.shape[1]))
            return compact_candidates(cnt, kc, K)

        # col_starts is shape-static: rebuild from the (post-"all"-
        # synthesis) stream widths without tracing anything
        s_full = ensure_all_stream(s_j, l_j)
        col_starts = _col_starts_of(meta, s_full)

        def make_verify(byte_verify):
            # jit-captures: meta, col_starts, ns, byte_verify
            @jax.jit
            def f_ver(arrays, streams, lengths, col):
                streams = ensure_all_stream(streams, lengths)
                ctx = _StreamCtx(streams, lengths)
                return verify_candidates(
                    meta,
                    arrays["tab"],
                    arrays["slot_bytes"],
                    arrays["slot_len"],
                    ctx,
                    col,
                    col_starts,
                    ns,
                    byte_verify=byte_verify,
                )
            return f_ver

        @jax.jit
        def f_tiny(arrays, streams, lengths, vbits):  # jit-captures: meta
            streams = ensure_all_stream(streams, lengths)
            ctx = _StreamCtx(streams, lengths)
            return tiny_slot_bits(
                meta, arrays["tiny_bytes"], arrays["tiny_slot"], ctx, vbits
            )

        @jax.jit
        def f_rx(arrays, streams, lengths, vbits):  # jit-captures: db
            from swarm_tpu.ops.regexdev import regex_verify

            streams = ensure_all_stream(streams, lengths)
            B = next(iter(streams.values())).shape[0]
            return regex_verify(
                db, streams, lengths, vbits,
                k_pairs=db.rx_k_pairs(B), arrays=arrays["rx"],
            )

        # jit-captures: db, meta
        @jax.jit
        def f_verdict(arrays, streams, lengths, status, vbits, ubits, rx):
            streams = ensure_all_stream(streams, lengths)
            digest = None
            if meta.has_md5 and "body" in streams:
                from swarm_tpu.ops.md5 import md5_words

                digest = md5_words(streams["body"], lengths["body"])
            planes = eval_verdicts(
                db, vbits, ubits, lengths, status, full=True,
                md5_digest=digest, rx=rx, arrays=arrays["verdict"],
            )
            return fuse_planes(
                planes, jnp.zeros((planes[0].shape[0],), dtype=bool)
            )

        phases: dict = {}
        T = len(meta.table_stream)
        if T:
            (cnt, _ovf, nmax), phases["prefilter"] = run(
                f_pre, arrays, s_j, l_j
            )
            kc = fpc.survivor_bucket(int(nmax), budget)
            # unguarded-ok: profile_phases is an offline single-threaded
            # attribution path (never races dispatch)
            self.last_compact = {
                "survivor_max": int(nmax), "verify_k": kc, "budget": budget,
            }
            col, phases["compact"] = run(f_compact, cnt, kc)
            _, gather_ms = run(make_verify(False), arrays, s_j, l_j, col)
            (vbits, ubits), full_ms = run(
                make_verify(True), arrays, s_j, l_j, col
            )
            phases["gather"] = gather_ms
            phases["verify"] = max(full_ms - gather_ms, 0.0)
        else:
            B = next(iter(s_j.values())).shape[0]
            vbits = jnp.zeros((B, max(ns, 1)), dtype=bool)
            ubits = jnp.zeros((B, max(ns, 1)), dtype=bool)
            phases["prefilter"] = phases["compact"] = 0.0
            phases["gather"] = phases["verify"] = 0.0
        vbits, phases["tiny"] = run(f_tiny, arrays, s_j, l_j, vbits)
        rx = None
        if meta.n_rx:
            rx, phases["regex"] = run(f_rx, arrays, s_j, l_j, vbits)
        else:
            phases["regex"] = 0.0
        fused, phases["verdict"] = run(
            f_verdict, arrays, s_j, l_j, st_j, vbits, ubits, rx
        )
        t0 = _time.perf_counter()
        np.asarray(fused)
        phases["transfer"] = (_time.perf_counter() - t0) * 1e3
        gauge = _device_metrics()["phase_ms"]
        for name, ms in phases.items():
            gauge.labels(phase=name).set(ms)
        return phases


def _lower_stream(arr):
    is_upper = (arr >= 65) & (arr <= 90)
    return jnp.where(is_upper, arr + 32, arr)


def _shifted(stream, q: int):
    """padded shifted views for window ops."""
    B, W = stream.shape
    padded = jnp.pad(stream, ((0, 0), (0, q)))
    return [padded[:, j : j + W] for j in range(q)]


def table_arrays_of(table: fpc.WordTable) -> dict:
    """The traced-array view of one WordTable (jnp constants by default;
    the sharded path passes per-rank slices instead)."""
    return {
        "group_h1": jnp.asarray(table.group_h1),
        "entry_start": jnp.asarray(table.entry_start),
        "entry_count": jnp.asarray(table.entry_count),
        "entry_h2": jnp.asarray(table.entry_h2),
        "entry_slot": jnp.asarray(table.entry_slot),
        "entry_off": jnp.asarray(table.entry_off),
        "entry_len": jnp.asarray(table.entry_len),
        "entry_suf_delta": jnp.asarray(table.entry_suf_delta),
        "entry_suf_h1": jnp.asarray(table.entry_suf_h1),
        "entry_suf_h2": jnp.asarray(table.entry_suf_h2),
        "bloom": jnp.asarray(table.bloom),
    }


def match_slots(
    db: fpc.CompiledDB,
    candidate_k: int,
    streams,
    lengths,
    table_arrays: Optional[list] = None,
    pos_offset: int = 0,
    back_halo: int = 0,
    fwd_halo: int = 0,
):
    """→ (value_bits [B, NS] bool, uncertain_bits [B, NS] bool, overflow [B]).

    Sequence parallelism support: ``streams`` may be halo-extended
    ([B, back_halo + W_local + fwd_halo]). Candidate windows *start*
    only in the W_local middle region (each global window position is
    owned by exactly one shard) but hash/verify reads may reach into
    both halos — a word whose gram sits in this shard can begin in the
    previous shard's bytes (back halo) and end in the next shard's
    (forward halo). Both halos must be ≥ the longest table entry for
    the superset property to survive sharding. ``pos_offset`` is the
    shard's global byte offset; ``lengths`` are always global.
    """
    ns = db.num_slots
    some = next(iter(streams.values()))
    B = some.shape[0]
    value_bits = jnp.zeros((B, max(ns, 1)), dtype=bool)
    uncertain_bits = jnp.zeros((B, max(ns, 1)), dtype=bool)
    overflow = jnp.zeros((B,), dtype=bool)

    # --- cached lowered streams and hash arrays ---
    lowered_cache: dict = {}

    def get_stream(name: str, lowered: bool):
        if not lowered:
            return streams[name]
        if name not in lowered_cache:
            lowered_cache[name] = _lower_stream(streams[name])
        return lowered_cache[name]

    hash_cache: dict = {}

    def get_hashes(name: str, lowered: bool, q: int):
        key = (name, lowered, q)
        if key not in hash_cache:
            hash_cache[key] = hashing.window_hashes_jnp(get_stream(name, lowered), q)
        return hash_cache[key]

    def offset_of(name: str):
        # per-stream global byte offset (streams have different widths,
        # so sequence shards start at different global positions per stream)
        if isinstance(pos_offset, dict):
            return pos_offset[name]
        return pos_offset

    # Slot truth bytes for the fused verify — small ([NW, VERIFY_WIDTH],
    # ci slots pre-lowered) and replicated across shards (slot ids are
    # global even when table groups are model-sharded).
    slot_bytes_j = jnp.asarray(db.slot_bytes)
    slot_len_j = jnp.asarray(db.slot_len)

    # --- q-gram tables ---
    for t_idx, table in enumerate(db.tables):
        arrays = (
            table_arrays[t_idx] if table_arrays is not None else table_arrays_of(table)
        )
        h1, h2 = get_hashes(table.stream, table.lowered, table.q)
        We = h1.shape[1]  # extended width (back halo + local + fwd halo)
        W = We - back_halo - fwd_halo  # windows start only in the middle
        slen = lengths[table.stream]  # global length

        flags = hashing.bloom_probe_jnp(
            arrays["bloom"],
            h1[:, back_halo : back_halo + W],
            h2[:, back_halo : back_halo + W],
        )
        # windows starting past slen - q can't begin a real gram
        positions = jnp.arange(W, dtype=jnp.int32)
        gpositions = positions + offset_of(table.stream)
        flags = flags & (gpositions[None, :] <= (slen - table.q)[:, None])

        k = min(candidate_k, W)
        vals = jnp.where(flags, positions[None, :] + 1, 0)
        top_vals, _ = jax.lax.top_k(vals, k)
        pos = top_vals - 1  # -1 = invalid (local window coordinate)
        valid = pos >= 0
        cpos = jnp.maximum(pos, 0) + back_halo  # extended coordinate
        overflow = overflow | (jnp.sum(flags, axis=1) > k)

        h1c = jnp.take_along_axis(h1, cpos, axis=1)
        h2c = jnp.take_along_axis(h2, cpos, axis=1)

        group_h1 = arrays["group_h1"]
        gidx = jnp.searchsorted(group_h1, h1c)
        G = group_h1.shape[0]
        gidx_c = jnp.minimum(gidx, G - 1)
        found = valid & (group_h1[gidx_c] == h1c)

        e_start = arrays["entry_start"][gidx_c]
        e_count = arrays["entry_count"][gidx_c]
        entry_h2 = arrays["entry_h2"]
        entry_slot = arrays["entry_slot"]
        entry_off = arrays["entry_off"]
        entry_len = arrays["entry_len"]
        entry_sufd = arrays["entry_suf_delta"]
        entry_sufh1 = arrays["entry_suf_h1"]
        entry_sufh2 = arrays["entry_suf_h2"]

        b_idx = jnp.arange(B, dtype=jnp.int32)[:, None] * jnp.ones(
            (1, k), dtype=jnp.int32
        )

        stream_v = get_stream(table.stream, table.lowered)
        offs = jnp.arange(fpc.VERIFY_WIDTH, dtype=jnp.int32)

        # EVERY entry hit is byte-verified (the compile.py:16-17
        # contract): gather the slot's true bytes under the window and
        # compare. Equal and len ≤ VERIFY_WIDTH ⇒ the hit is *certain*
        # (no host confirm). Unequal ⇒ a hash collision: provably no
        # match at this window, so no bit is set at all. Equal prefix of
        # a longer slot ⇒ value + uncertain (host checks the tail).
        # Per-entry (not first-hit-per-window) verification matters:
        # words sharing their chosen gram land in one h1 group and can
        # all pass the hash checks at one window — each needs its own
        # byte compare. max_group (≤ compile.MAX_GROUP normally; up to
        # compile.HARD_GROUP when gram shedding degrades) bounds the
        # extra gathers.
        for g in range(table.max_group):
            e = jnp.minimum(e_start + g, entry_h2.shape[0] - 1)
            in_group = found & (g < e_count)
            h2_ok = entry_h2[e] == h2c
            # suffix-gram check from the same rolling-hash arrays; the
            # suffix may live in the halo region (sequence parallelism)
            spos = cpos + entry_sufd[e]
            spos_c = jnp.clip(spos, 0, We - 1)
            suf_ok = (
                (jnp.take_along_axis(h1, spos_c, axis=1) == entry_sufh1[e])
                & (jnp.take_along_axis(h2, spos_c, axis=1) == entry_sufh2[e])
                & (spos >= 0)
                & (spos < We)
            )
            # global bounds: word fully inside the true part bytes
            gstart = (cpos - back_halo) + offset_of(table.stream) - entry_off[e]
            fits = (gstart >= 0) & (gstart + entry_len[e] <= slen[:, None])
            # extended-view bounds: with halos ≥ max entry length these
            # only bite in the unsharded case (buffer edges)
            fits = fits & (cpos - entry_off[e] >= 0) & (
                cpos - entry_off[e] + entry_len[e] <= We
            )
            hit = in_group & h2_ok & suf_ok & fits
            slot = entry_slot[e]
            start = cpos - entry_off[e]  # extended coord of word start
            lv = jnp.minimum(entry_len[e], fpc.VERIFY_WIDTH)
            idx = start[:, :, None] + offs[None, None, :]  # [B, k, V]
            idx_c = jnp.clip(idx, 0, We - 1)
            gathered = jnp.take_along_axis(
                stream_v, idx_c.reshape(B, -1), axis=1
            ).reshape(B, k, fpc.VERIFY_WIDTH)
            expected = slot_bytes_j[slot]  # [B, k, V]
            pos_ok = offs[None, None, :] < lv[:, :, None]
            eq = ((gathered == expected) | ~pos_ok).all(-1)
            long = slot_len_j[slot] > fpc.VERIFY_WIDTH
            fired = hit & eq
            value_bits = value_bits.at[b_idx, slot].max(fired)
            uncertain_bits = uncertain_bits.at[b_idx, slot].max(fired & long)

    # --- tiny slots: dense shifted compare (exact) ---
    tiny_count = int((np.asarray(db.tiny_len) > 0).sum())
    shift_cache: dict = {}
    for i in range(tiny_count):
        length = int(db.tiny_len[i])
        slot_id = int(db.tiny_slot[i])
        stream_name = STREAMS[int(db.tiny_stream[i])]
        lowered = bool(db.tiny_lowered[i])
        skey = (stream_name, lowered)
        if skey not in shift_cache:
            shift_cache[skey] = _shifted(
                get_stream(stream_name, lowered), hashing.TINY_MAX
            )
        shifts = shift_cache[skey]
        We_t = shifts[0].shape[1]
        # global coordinates (halo positions are valid too — the byte
        # compare is exact and the OR across shards dedupes)
        gpositions = (
            jnp.arange(We_t, dtype=jnp.int32) - back_halo + offset_of(stream_name)
        )
        eq = jnp.ones_like(shifts[0], dtype=bool)
        for j in range(length):
            eq = eq & (shifts[j] == int(db.tiny_bytes[i, j]))
        slen = lengths[stream_name]
        eq = eq & (gpositions[None, :] >= 0)
        eq = eq & (gpositions[None, :] <= (slen - length)[:, None])
        # window must lie inside this view's real bytes (an all-zero tiny
        # pattern must not match the zero padding / zero-filled halo edge)
        local = jnp.arange(We_t, dtype=jnp.int32)
        eq = eq & (local[None, :] + length <= We_t)
        hit = eq.any(axis=1)
        value_bits = value_bits.at[:, slot_id].max(hit)

    return value_bits, uncertain_bits, overflow


# ---------------------------------------------------------------------------
# Two-phase fresh-content kernel (corpus as device-resident ARGUMENTS)
# ---------------------------------------------------------------------------
#
# match_slots above is the legacy/reference kernel: a Python loop over
# word tables, each table's arrays inlined as XLA constants and a dense
# per-table top_k over every window. The functions below are the
# production path (docs/DEVICE_MATCH.md):
#
#   phase A  prefilter_candidates — ONE fused bloom/q-gram probe over
#            the whole batch across ALL tables at once (stacked
#            table-major arrays from compile.stack_tables_np), then a
#            single per-row top_k over the concatenated (table, window)
#            candidate axis;
#   phase B  verify_candidates — only the surviving (row, window,
#            table) candidates are gathered: per-candidate binary
#            search into the stacked h1 groups, 128-bit hash screen,
#            and the byte verify — work sized by the SURVIVOR budget,
#            not by tables × windows.
#
# Every corpus array arrives as a traced argument (the layout pytree),
# so the compiled program is corpus-size-free: one executable serves
# every width bucket of a shape class AND every corpus refresh, and the
# persistent XLA cache (utils/xlacache.py) keys stop covering corpus
# bytes. Candidate-overflow contract: a row whose fired windows exceed
# the global budget K sets ``overflow`` and is re-run exactly on the
# host (engine row redo) — a strict superset of the legacy per-table
# condition, so soundness is unchanged.


def global_candidate_budget(candidate_k: int, n_tables: int) -> int:
    """Per-row candidate budget for the global (cross-table) top_k.

    The legacy kernel budgeted ``candidate_k`` PER TABLE (worst case
    ``candidate_k × T``); phase B's cost is proportional to the budget
    on EVERY batch, so the global budget scales sub-linearly with the
    table count instead: ×1 for ≤2 tables up to ×4 for ≥8. A noisy row
    that fires a moderate number of windows in several tables stays on
    device (no overflow host-redo cliff), while the gather-verify
    stays survivor-sized rather than worst-case-sized."""
    return candidate_k * max(1, min(n_tables, 8) // 2)


class _StreamCtx:
    """Per-trace stream/hash caches shared by both kernel phases."""

    def __init__(self, streams: dict, lengths: dict, pos_offset=0):
        self.streams = streams
        self.lengths = lengths
        self.pos_offset = pos_offset
        self._lowered: dict = {}
        self._hashes: dict = {}

    def stream(self, name: str, lowered: bool):
        if not lowered:
            return self.streams[name]
        if name not in self._lowered:
            self._lowered[name] = _lower_stream(self.streams[name])
        return self._lowered[name]

    def hashes(self, name: str, lowered: bool, q: int):
        key = (name, lowered, q)
        if key not in self._hashes:
            self._hashes[key] = hashing.window_hashes_jnp(
                self.stream(name, lowered), q
            )
        return self._hashes[key]

    def offset(self, name: str):
        if isinstance(self.pos_offset, dict):
            return self.pos_offset[name]
        return self.pos_offset


def _combo_groups(meta: "fpc.DeviceLayoutMeta"):
    """Tables grouped by (stream, lowered, q) — the distinct hash
    passes — in first-appearance order. Static."""
    groups: dict = {}
    for t in range(len(meta.table_stream)):
        key = (meta.table_stream[t], meta.table_lowered[t], meta.table_q[t])
        groups.setdefault(key, []).append(t)
    return groups


def _col_starts_of(meta: "fpc.DeviceLayoutMeta", streams: dict) -> np.ndarray:
    """Per-table start offsets on the concatenated candidate axis,
    rebuilt from the (post-``ensure_all_stream``) stream widths —
    shape-static, so safe to call on tracers inside a jit."""
    T = len(meta.table_stream)
    cs = np.zeros(T + 1, dtype=np.int32)
    for t in range(T):
        cs[t + 1] = cs[t] + streams[meta.table_stream[t]].shape[1]
    return cs


def prefilter_counts(
    meta: "fpc.DeviceLayoutMeta",
    tab: dict,
    ctx: _StreamCtx,
    back_halo: int = 0,
    fwd_halo: int = 0,
):
    """Phase A core: fused stacked bloom probe → survivor RANK plane.

    Returns ``(cnt [B, C] int32, col_starts np[T+1])``: ``cnt`` is the
    inclusive running count of fired windows along the concatenated
    table-major (table, window) candidate axis — ``cnt[b, -1]`` is row
    b's total survivor count, and the j-th survivor's column is the
    first index where ``cnt`` reaches j+1 (compact_candidates' binary
    search). The rank plane replaces the former per-row ``top_k`` over
    the full candidate axis: top_k lowers to a whole-axis sort (the
    dominant fresh-batch phase on the CPU backend, ~70% of the fused
    kernel), while the cumulative count is a single linear scan and the
    extraction cost moves to phase B where it is survivor-sized."""
    T = len(meta.table_stream)
    flags_by_table: list = [None] * T
    w_by_table = [0] * T
    for (sname, lowered, q), tids in _combo_groups(meta).items():
        h1, h2 = ctx.hashes(sname, lowered, q)
        We = h1.shape[1]
        W = We - back_halo - fwd_halo
        h1w = h1[:, back_halo : back_halo + W]
        h2w = h2[:, back_halo : back_halo + W]
        # stacked probe: one gather with a leading table axis instead
        # of a bloom_probe per table
        bloom = tab["bloom"][np.asarray(tids, dtype=np.int32)]  # [Tg, BW]
        mask = jnp.uint32(hashing.BLOOM_BITS - 1)
        i1 = (h1w & mask).astype(jnp.int32)
        i2 = (h2w & mask).astype(jnp.int32)
        w1 = bloom[:, i1 >> 5]  # [Tg, B, W]
        w2 = bloom[:, i2 >> 5]
        b1 = (w1 >> (i1 & 31).astype(jnp.uint32)[None]) & 1
        b2 = (w2 >> (i2 & 31).astype(jnp.uint32)[None]) & 1
        fl = (b1 & b2) == 1  # [Tg, B, W]
        # windows starting past slen - q can't begin a real gram
        positions = jnp.arange(W, dtype=jnp.int32)
        gpositions = positions + ctx.offset(sname)
        slen = ctx.lengths[sname]
        fl = fl & (
            gpositions[None, None, :] <= (slen - q)[None, :, None]
        )
        for j, t in enumerate(tids):
            flags_by_table[t] = fl[j]
            w_by_table[t] = W
    col_starts = np.zeros(T + 1, dtype=np.int32)
    for t in range(T):
        col_starts[t + 1] = col_starts[t] + w_by_table[t]
    flags_cat = jnp.concatenate(
        [flags_by_table[t] for t in range(T)], axis=1
    )  # [B, C]
    cnt = jnp.cumsum(flags_cat.astype(jnp.int32), axis=1)
    return cnt, col_starts


def compact_candidates(cnt, kc: int, budget: int):
    """Survivor compaction: extract the first ``kc`` fired columns per
    row from the phase-A rank plane.

    The j-th survivor's column is the first index where the running
    count reaches j+1 — a vectorized binary search over the
    non-decreasing ``cnt`` rows (~log2(C) gathers of [B, kc] elements,
    survivor-sized work instead of candidate-axis-sized). Entries past
    ``min(n_survivors, budget)`` are -1; rows with more than ``budget``
    fired windows keep their first ``budget`` candidates and are
    flagged for the host row-redo by the caller (selection order
    changed from the former top_k's descending-column to ascending —
    candidate order never reaches the slot planes, and overflow rows
    are re-run exactly on the host either way).

    → ``col [B, kc] int32`` indexing the concatenated table-major
    candidate axis, -1 = no candidate.
    """
    B, C = cnt.shape
    target = jnp.arange(1, kc + 1, dtype=jnp.int32)[None, :]  # [1, kc]
    lo = jnp.zeros((B, kc), dtype=jnp.int32)
    hi = jnp.full((B, kc), C, dtype=jnp.int32)
    for _ in range(max(C, 2).bit_length() + 1):
        mid = (lo + hi) >> 1
        v = jnp.take_along_axis(cnt, jnp.minimum(mid, C - 1), axis=1)
        go_right = v < target
        lo = jnp.where(go_right, mid + 1, lo)
        hi = jnp.where(go_right, hi, mid)
    n_surv = cnt[:, -1:]
    return jnp.where(target <= jnp.minimum(n_surv, budget), lo, -1)


def prefilter_candidates(
    meta: "fpc.DeviceLayoutMeta",
    tab: dict,
    ctx: _StreamCtx,
    candidate_k: int,
    back_halo: int = 0,
    fwd_halo: int = 0,
):
    """Phase A (fused-path form): stacked bloom probe → the first K
    fired windows per row (prefilter_counts + compact_candidates at
    the full budget).

    Returns ``(col [B, K] int32, overflow [B] bool, col_starts
    np[T+1])``: ``col`` indexes the concatenated table-major
    (table, window) candidate axis, -1 = no candidate. ``overflow``
    marks rows with more fired windows than K (host row-redo)."""
    cnt, col_starts = prefilter_counts(meta, tab, ctx, back_halo, fwd_halo)
    c_total = int(col_starts[-1])
    K = max(1, min(candidate_k, c_total))
    col = compact_candidates(cnt, K, K)
    overflow = cnt[:, -1] > K
    return col, overflow, col_starts


def verify_candidates(
    meta: "fpc.DeviceLayoutMeta",
    tab: dict,
    slot_bytes_j,
    slot_len_j,
    ctx: _StreamCtx,
    col,
    col_starts: np.ndarray,
    num_slots: int,
    back_halo: int = 0,
    fwd_halo: int = 0,
    byte_verify: bool = True,
):
    """Phase B: sparse gather-verify over the surviving candidates.

    Per candidate: decode (table, window), fetch the window's rolling
    hashes, binary-search the table's sorted h1 groups (stacked
    [T, Gmax] layout — ~log2(Gmax) scalar gathers instead of a
    searchsorted against a gathered [B, K, Gmax] plane), screen the
    group's entries by the 128 hash bits, byte-verify survivors.

    ``byte_verify=False`` stops after the hash screen (the profiling
    tool's "gather" phase) — the returned planes then over-approximate
    and must not be used for verdicts.

    → (value_bits [B, NS] bool, uncertain_bits [B, NS] bool)
    """
    some = next(iter(ctx.streams.values()))
    B = some.shape[0]
    T = len(meta.table_stream)
    K = col.shape[1]
    value_bits = jnp.zeros((B, max(num_slots, 1)), dtype=bool)
    uncertain_bits = jnp.zeros((B, max(num_slots, 1)), dtype=bool)

    valid = col >= 0
    colc = jnp.maximum(col, 0)
    col_starts_j = jnp.asarray(col_starts)
    tid = (
        jnp.searchsorted(col_starts_j, colc, side="right").astype(jnp.int32)
        - 1
    )
    pos = colc - col_starts_j[tid]  # local window coordinate
    cpos = pos + back_halo  # extended coordinate

    # --- per-candidate static table attributes, via tiny [T] tables ---
    combos = list(_combo_groups(meta))
    t_combo = np.array(
        [
            combos.index(
                (meta.table_stream[t], meta.table_lowered[t], meta.table_q[t])
            )
            for t in range(T)
        ],
        dtype=np.int32,
    )
    vstreams = sorted(
        {(meta.table_stream[t], meta.table_lowered[t]) for t in range(T)}
    )
    t_vs = np.array(
        [
            vstreams.index((meta.table_stream[t], meta.table_lowered[t]))
            for t in range(T)
        ],
        dtype=np.int32,
    )
    t_we = np.array(
        [ctx.streams[meta.table_stream[t]].shape[1] for t in range(T)],
        dtype=np.int32,
    )
    cand_combo = jnp.asarray(t_combo)[tid]
    cand_vs = jnp.asarray(t_vs)[tid]
    cand_we = jnp.asarray(t_we)[tid]
    cand_slen = jnp.take_along_axis(
        jnp.stack(
            [ctx.lengths[meta.table_stream[t]] for t in range(T)], axis=1
        ),
        tid,
        axis=1,
    )
    cand_goff = jnp.stack(
        [
            jnp.asarray(ctx.offset(meta.table_stream[t]), dtype=jnp.int32)
            for t in range(T)
        ]
    )[tid]

    def hash_at(positions):
        """(h1, h2) of each candidate's stream at ``positions`` —
        gather from each combo's hash plane, select by combo id."""
        out1 = jnp.zeros((B, K), dtype=jnp.uint32)
        out2 = jnp.zeros((B, K), dtype=jnp.uint32)
        for ci_, (sname, lowered, q) in enumerate(combos):
            h1, h2 = ctx.hashes(sname, lowered, q)
            p = jnp.clip(positions, 0, h1.shape[1] - 1)
            sel = cand_combo == ci_
            out1 = jnp.where(sel, jnp.take_along_axis(h1, p, axis=1), out1)
            out2 = jnp.where(sel, jnp.take_along_axis(h2, p, axis=1), out2)
        return out1, out2

    h1c, h2c = hash_at(cpos)

    # --- binary search the stacked sorted h1 groups ---
    group_h1 = tab["group_h1"]
    gmax = group_h1.shape[1]
    ng = tab["n_groups"][tid]
    lo = jnp.zeros_like(colc)
    hi = ng
    for _ in range(max(gmax, 1).bit_length() + 1):
        active = lo < hi
        mid = (lo + hi) >> 1
        v = group_h1[tid, jnp.minimum(mid, gmax - 1)]
        right = active & (v < h1c)
        lo = jnp.where(right, mid + 1, lo)
        hi = jnp.where(active & ~right, mid, hi)
    gidx = jnp.minimum(lo, gmax - 1)
    found = valid & (lo < ng) & (group_h1[tid, gidx] == h1c)
    e_start = tab["entry_start"][tid, gidx]
    e_count = tab["entry_count"][tid, gidx]

    emax = tab["entry_h2"].shape[1]
    offs = jnp.arange(fpc.VERIFY_WIDTH, dtype=jnp.int32)
    b_idx = jnp.broadcast_to(jnp.arange(B, dtype=jnp.int32)[:, None], (B, K))

    # EVERY entry hit is byte-verified (the compile.py contract) — see
    # match_slots for the per-entry rationale; max_group here is the
    # global bound (per-candidate e_count masks shorter groups).
    for g in range(meta.max_group):
        e = jnp.minimum(e_start + g, emax - 1)
        in_group = found & (g < e_count)
        h2_ok = tab["entry_h2"][tid, e] == h2c
        # suffix-gram check from the same rolling-hash arrays; the
        # suffix may live in the halo region (sequence parallelism)
        spos = cpos + tab["entry_suf_delta"][tid, e]
        s1, s2 = hash_at(spos)
        suf_ok = (
            (s1 == tab["entry_suf_h1"][tid, e])
            & (s2 == tab["entry_suf_h2"][tid, e])
            & (spos >= 0)
            & (spos < cand_we)
        )
        entry_off_e = tab["entry_off"][tid, e]
        entry_len_e = tab["entry_len"][tid, e]
        # global bounds: word fully inside the true part bytes
        gstart = (cpos - back_halo) + cand_goff - entry_off_e
        fits = (gstart >= 0) & (gstart + entry_len_e <= cand_slen)
        # extended-view bounds (buffer edges / halo limits)
        fits = fits & (cpos - entry_off_e >= 0) & (
            cpos - entry_off_e + entry_len_e <= cand_we
        )
        hit = in_group & h2_ok & suf_ok & fits
        slot = tab["entry_slot"][tid, e]
        if byte_verify:
            start = cpos - entry_off_e  # extended coord of word start
            lv = jnp.minimum(entry_len_e, fpc.VERIFY_WIDTH)
            idx = start[:, :, None] + offs[None, None, :]  # [B, K, V]
            expected = slot_bytes_j[slot]  # [B, K, V]
            pos_ok = offs[None, None, :] < lv[:, :, None]
            eq = jnp.zeros((B, K), dtype=bool)
            for vi, (sname, lowered) in enumerate(vstreams):
                sv = ctx.stream(sname, lowered)
                idx_c = jnp.clip(idx, 0, sv.shape[1] - 1)
                gathered = jnp.take_along_axis(
                    sv, idx_c.reshape(B, -1), axis=1
                ).reshape(B, K, fpc.VERIFY_WIDTH)
                eq_v = ((gathered == expected) | ~pos_ok).all(-1)
                eq = jnp.where(cand_vs == vi, eq_v, eq)
            fired = hit & eq
        else:
            fired = hit
        long = slot_len_j[slot] > fpc.VERIFY_WIDTH
        value_bits = value_bits.at[b_idx, slot].max(fired)
        uncertain_bits = uncertain_bits.at[b_idx, slot].max(fired & long)
    return value_bits, uncertain_bits


def tiny_slot_bits(
    meta: "fpc.DeviceLayoutMeta",
    tiny_bytes_j,
    tiny_slot_j,
    ctx: _StreamCtx,
    value_bits,
    back_halo: int = 0,
):
    """Tiny slots (1–3 bytes): dense shifted compare — exact, same
    logic as the legacy path but with the pattern bytes and slot ids
    as traced arguments."""
    shift_cache: dict = {}
    for i, (length, stream_name, lowered) in enumerate(meta.tiny):
        skey = (stream_name, lowered)
        if skey not in shift_cache:
            shift_cache[skey] = _shifted(
                ctx.stream(stream_name, lowered), hashing.TINY_MAX
            )
        shifts = shift_cache[skey]
        We_t = shifts[0].shape[1]
        # global coordinates (halo positions are valid too — the byte
        # compare is exact and the OR across shards dedupes)
        gpositions = (
            jnp.arange(We_t, dtype=jnp.int32)
            - back_halo
            + ctx.offset(stream_name)
        )
        eq = jnp.ones_like(shifts[0], dtype=bool)
        for j in range(length):
            eq = eq & (shifts[j] == tiny_bytes_j[i, j])
        slen = ctx.lengths[stream_name]
        eq = eq & (gpositions[None, :] >= 0)
        eq = eq & (gpositions[None, :] <= (slen - length)[:, None])
        # window must lie inside this view's real bytes (an all-zero
        # tiny pattern must not match the zero padding / halo edge)
        local = jnp.arange(We_t, dtype=jnp.int32)
        eq = eq & (local[None, :] + length <= We_t)
        hit = eq.any(axis=1)
        value_bits = value_bits.at[:, tiny_slot_j[i]].max(hit)
    return value_bits


def match_slots_args(
    db: fpc.CompiledDB,
    meta: "fpc.DeviceLayoutMeta",
    arrays: dict,
    candidate_k: int,
    streams,
    lengths,
    pos_offset=0,
    back_halo: int = 0,
    fwd_halo: int = 0,
):
    """Two-phase twin of :func:`match_slots`: same contract — (value,
    uncertain, overflow) slot planes with the superset/uncertainty
    invariants — with every corpus array a traced argument and the
    candidate budget global per row instead of per table. Sequence
    parallelism (halo-extended streams, global lengths/offsets) works
    exactly as in the legacy kernel."""
    ns = db.num_slots
    some = next(iter(streams.values()))
    B = some.shape[0]
    ctx = _StreamCtx(streams, lengths, pos_offset)
    if len(meta.table_stream):
        budget = global_candidate_budget(
            candidate_k, len(meta.table_stream)
        )
        col, overflow, col_starts = prefilter_candidates(
            meta, arrays["tab"], ctx, budget, back_halo, fwd_halo
        )
        value_bits, uncertain_bits = verify_candidates(
            meta,
            arrays["tab"],
            arrays["slot_bytes"],
            arrays["slot_len"],
            ctx,
            col,
            col_starts,
            ns,
            back_halo,
            fwd_halo,
        )
    else:
        value_bits = jnp.zeros((B, max(ns, 1)), dtype=bool)
        uncertain_bits = jnp.zeros((B, max(ns, 1)), dtype=bool)
        overflow = jnp.zeros((B,), dtype=bool)
    value_bits = tiny_slot_bits(
        meta, arrays["tiny_bytes"], arrays["tiny_slot"], ctx,
        value_bits, back_halo,
    )
    return value_bits, uncertain_bits, overflow


def _finish_match(
    db: fpc.CompiledDB,
    meta: "fpc.DeviceLayoutMeta",
    arrays: dict,
    streams,
    lengths,
    status,
    value_bits,
    uncertain_bits,
    overflow,
    full=False,
):
    """Shared tail of every args-kernel route — device md5, device
    regex verify, verdict lowering — factored so the fused twin
    (:func:`_match_impl_args`) and the split survivor-compacted path
    (DeviceDB's phase B) run literally the same lowering and parity
    can't drift. ``streams`` must already be post-``ensure_all_stream``."""
    digest = None
    if meta.has_md5 and "body" in streams:
        from swarm_tpu.ops.md5 import md5_words

        digest = md5_words(streams["body"], lengths["body"])
    rx = None
    if meta.n_rx:
        from swarm_tpu.ops.regexdev import regex_verify

        B = next(iter(streams.values())).shape[0]
        rx = regex_verify(
            db,
            streams,
            lengths,
            value_bits,
            k_pairs=db.rx_k_pairs(B),
            arrays=arrays["rx"],
        )
    out = eval_verdicts(
        db,
        value_bits,
        uncertain_bits,
        lengths,
        status,
        full=full,
        md5_digest=digest,
        rx=rx,
        arrays=arrays["verdict"],
    )
    return (*out, overflow)


def _match_impl_args(
    db: fpc.CompiledDB,
    meta: "fpc.DeviceLayoutMeta",
    candidate_k: int,
    arrays: dict,
    streams,
    lengths,
    status,
    full=False,
):
    """Argument-driven twin of :func:`_match_impl` — the fused jitted
    body (corpus pytree first, so the executable is corpus-free).
    DeviceDB's legacy/reference dispatch arm and ShardedMatcher run
    this; the production single-device path splits the same phases
    around survivor compaction (DeviceDB.dispatch)."""
    streams = ensure_all_stream(streams, lengths)
    value_bits, uncertain_bits, overflow = match_slots_args(
        db, meta, arrays, candidate_k, streams, lengths
    )
    return _finish_match(
        db, meta, arrays, streams, lengths, status,
        value_bits, uncertain_bits, overflow, full=full,
    )


def eval_verdicts(
    db: fpc.CompiledDB,
    value_bits,
    uncertain_bits,
    lengths,
    status,
    full=False,
    md5_digest=None,
    rx=None,
    arrays: Optional[dict] = None,
):
    """Slot bits + scalars → (t_value, t_uncertain) [B, NT] bool.

    With ``full=True`` also returns the intermediate planes
    ``(t_value, t_unc, op_value, op_unc, m_unc)`` so the host can
    resolve an uncertain verdict by re-evaluating only the specific
    uncertain matchers (engine.py) instead of the whole template.
    (No m_value plane: an undecided op's certain matchers are neutral
    by the Kleene argument, so the host never reads their values.)
    When the corpus lowered workflow gate tables (``arrays["wf"]``,
    docs/WORKFLOWS.md), four more planes follow: per-condition value/
    uncertainty and per-emit value/uncertainty from the vectorized
    gate-apply stage.

    Uncertainty is refined with three-valued logic at every reduction:
    a verdict already decided by its *certain* inputs (a certain-true
    input under OR, a certain-false one under AND) is exact no matter
    what the uncertain inputs turn out to be, so its uncertain bit is
    cleared. This is what keeps host confirmation sparse — e.g. a
    status-matcher miss certain-falsifies an AND op and no regex
    sibling ever needs host evaluation.

    ``arrays`` is the verdict half of the argument layout
    (``compile.verdict_arrays_np``): pass the device-resident pytree
    (DeviceDB/ShardedMatcher) and the traced program stays corpus-free;
    omit it and the same arrays are baked in as constants — the legacy
    reference path, byte-identical by construction since both routes
    run this one function.
    """
    if arrays is None:
        arrays = jax.tree_util.tree_map(
            jnp.asarray, fpc.verdict_arrays_np(db)
        )
    B = status.shape[0]
    NM = db.m_kind.shape[0]

    len_body = lengths["body"].astype(jnp.float32)
    len_header = lengths["header"].astype(jnp.float32)
    len_all = lengths["all"].astype(jnp.float32)
    svars = jnp.stack(
        [status.astype(jnp.float32), len_body, len_header, len_all, len_body],
        axis=1,
    )  # [B, SCALAR_VARS]

    # --- slot reductions (vacuously true when a matcher has no slots) ---
    slot_red = jnp.ones((B, NM), dtype=bool)
    m_unc = jnp.zeros((B, NM), dtype=bool)
    cond_and = arrays["m_cond_and"]
    for rows, idx in arrays["m_slot_buckets"]:
        gv = value_bits[:, idx]  # [B, nb, w]
        gu = uncertain_bits[:, idx]
        is_and = cond_and[rows][None, :]
        red = jnp.where(is_and, gv.all(-1), gv.any(-1))
        # Kleene: a certain-hit slot decides OR; a missed slot is always
        # certain (uncertainty only attaches to fired q-gram hits), so
        # any miss decides AND
        decided = jnp.where(
            is_and, (~gv).any(-1), (gv & ~gu).any(-1)
        )
        slot_red = slot_red.at[:, rows].set(red)
        m_unc = m_unc.at[:, rows].set(gu.any(-1) & ~decided)

    # --- negated-contains buckets: NONE of the slots may be present ---
    # (dsl conjuncts like !regex('(?i)x-frame-options', all_headers) —
    # the missing-security-headers shape). Slot absence is always
    # certain; an uncertain *fired* slot leaves presence unknown, so
    # the matcher goes uncertain, and a certain-present slot decides
    # the whole conjunction false.
    neg_present = jnp.zeros((B, NM), dtype=bool)
    neg_decided_false = jnp.zeros((B, NM), dtype=bool)
    for rows, idx in arrays["m_negslot_buckets"]:
        gv = value_bits[:, idx]
        gu = uncertain_bits[:, idx]
        neg_present = neg_present.at[:, rows].set(gv.any(-1))
        neg_decided_false = neg_decided_false.at[:, rows].set(
            (gv & ~gu).any(-1)
        )
        m_unc = m_unc.at[:, rows].max(gu.any(-1))

    # --- scalar programs ---
    var_id = arrays["scalar_var"]  # [NM, C]
    cmp_val = arrays["scalar_cmp"]  # [NM, C] f32
    v = svars[:, var_id]  # [B, NM, C]
    checks = [
        v == cmp_val,  # SOP_EQ
        v != cmp_val,
        v < cmp_val,
        v > cmp_val,
        v <= cmp_val,
        v >= cmp_val,
        jnp.ones_like(v, dtype=bool),  # SOP_TRUE
    ]
    # host-precomputed one-hot op selection (compile.scalar_onehot_np):
    # exactly one check is selected per conjunct, so OR-accumulating
    # the masked checks IS the select — with no [NM, C] id-compare
    # planes left for XLA's constant folder to chew on
    onehot = arrays["scalar_onehot"]  # [NCHECKS, NM, C] bool
    conj = jnp.zeros_like(v, dtype=bool)
    for i, c in enumerate(checks):
        conj = conj | (onehot[i][None] & c)
    scalar_ok = conj.all(-1)  # [B, NM]

    # --- status / size matchers ---
    status_ok = (status[:, None, None] == arrays["m_status"][None]).any(-1)
    len_streams = jnp.stack(
        [lengths[name] for name in STREAMS], axis=1
    )  # [B, len(STREAMS)]
    size_sel = len_streams[:, arrays["m_size_stream"]]  # [B, NM]
    size_ok = (size_sel[:, :, None] == arrays["m_size"][None]).any(-1)

    is_regex_prefilter = arrays["is_rx_prefilter"]
    is_words = arrays["is_words"]
    is_scalar = arrays["is_scalar"]
    is_status = arrays["is_status"]
    is_size = arrays["is_size"]

    # device md5 digest equality (md5(body) == "<hex>" dsl conjuncts).
    # Fail CLOSED without a digest: the matcher keeps its superset value
    # but goes uncertain, so a caller that forgets to supply the digest
    # costs host confirms — never silent false hits.
    has_md5 = bool(db.m_md5_check.any())
    if md5_digest is not None:
        md5_ok = (~arrays["m_md5_check"])[None, :] | (
            md5_digest[:, None, :].astype(jnp.uint32)
            == arrays["m_md5"][None]
        ).all(-1)
    else:
        md5_ok = jnp.ones((B, NM), dtype=bool)
        if has_md5:
            m_unc = m_unc | arrays["m_md5_check"][None, :]

    m_value = jnp.zeros((B, NM), dtype=bool)
    m_value = jnp.where(is_words[None, :], slot_red, m_value)
    m_value = jnp.where(
        is_scalar[None, :],
        scalar_ok & slot_red & ~neg_present & md5_ok,
        m_value,
    )
    m_value = jnp.where(is_status[None, :], status_ok, m_value)
    m_value = jnp.where(is_size[None, :], size_ok, m_value)

    # Kleene over the scalar∧slots∧¬neg∧md5 conjunction: a certainly
    # failed exact conjunct decides the matcher false whatever the
    # uncertain slots resolve to
    m_unc = m_unc & ~(
        is_scalar[None, :] & (~scalar_ok | ~md5_ok | neg_decided_false)
    )
    # md5-style residues: a scalar pass still needs host confirmation
    m_unc = m_unc | (arrays["m_residue"][None, :] & m_value)
    # regex prefilters are *semantically* uncertain when fired: the
    # required literal being byte-verified present does not prove the
    # regex matches, so the fired bit always needs host confirmation
    # (absence of the literal stays exact — the regex cannot match).
    m_unc = m_unc | (is_regex_prefilter[None, :] & m_value)
    # ...EXCEPT matchers the device regex verify re-checked exactly
    # (ops/regexdev.py): their value is the true search result and
    # only budget-overflow pairs stay uncertain.
    if rx is not None and len(db.rx_m_ids):
        rx_value, rx_unc = rx
        ids = arrays["rx_m_ids"]
        m_value = m_value.at[:, ids].set(rx_value)
        m_unc = m_unc.at[:, ids].set(rx_unc)
    # negation after uncertainty capture
    m_value = m_value ^ arrays["m_negative"][None, :]

    # --- operations ---
    NOP = db.op_cond_and.shape[0]
    op_value = jnp.zeros((B, NOP), dtype=bool)
    op_unc = jnp.zeros((B, NOP), dtype=bool)
    op_cond = arrays["op_cond_and"]
    for rows, idx in arrays["op_m_buckets"]:
        gv = m_value[:, idx]
        gu = m_unc[:, idx]
        is_and = op_cond[rows][None, :]
        red = jnp.where(is_and, gv.all(-1), gv.any(-1))
        # Kleene: certain-true matcher decides OR; certain-false decides
        # AND (matcher certainty = ~gu post-negation)
        decided = jnp.where(
            is_and, (~gv & ~gu).any(-1), (gv & ~gu).any(-1)
        )
        op_value = op_value.at[:, rows].set(red)
        op_unc = op_unc.at[:, rows].set(gu.any(-1) & ~decided)
    # superset-lowered (prefilter) ops: individual matcher bits inside
    # them are weakened (not per-matcher exact), so the Kleene
    # refinement above does not apply — the op is uncertain exactly when
    # it fired, certain-false otherwise, and fired rows are
    # host-confirmed at op granularity.
    is_pref = arrays["op_prefilter"][None, :]
    op_unc = jnp.where(is_pref, op_value, op_unc)

    # --- templates: OR over their operations ---
    NT = max(db.num_templates, 1)
    t_value = jnp.zeros((B, NT), dtype=bool)
    t_unc = jnp.zeros((B, NT), dtype=bool)
    for rows, idx in arrays["t_op_buckets"]:
        gv = op_value[:, idx]
        gu = op_unc[:, idx]
        t_value = t_value.at[:, rows].set(gv.any(-1))
        # Kleene: any certain-true op decides the template-level OR
        t_unc = t_unc.at[:, rows].set(
            gu.any(-1) & ~(gv & ~gu).any(-1)
        )
    if full:
        wf = arrays.get("wf")
        if wf is not None:
            cond_v, cond_u, emit_v, emit_u = _apply_workflow_gates(
                wf, t_value, t_unc, op_value, op_unc, m_value, m_unc
            )
            return (
                t_value, t_unc, op_value, op_unc, m_unc,
                cond_v, cond_u, emit_v, emit_u,
            )
        return t_value, t_unc, op_value, op_unc, m_unc
    return t_value, t_unc


def _apply_workflow_gates(
    wf: dict, t_value, t_unc, op_value, op_unc, m_value, m_unc
):
    """Vectorized workflow gate-apply over the whole batch (the
    device stage of docs/WORKFLOWS.md).

    Gathers each DNF condition from the verdict planes just built,
    ANDs them per term under Kleene three-valued logic, and ORs terms
    into the emit plane. Host condition kinds (templates/gates the
    device doesn't own) read as (False, uncertain); the runner resolves
    those — and any other uncertain emit — per row at condition
    granularity, never per workflow. ``m_value`` here is post-negation,
    matching cpu_ref's individual-matcher semantics for named gates.
    """
    B = t_value.shape[0]
    ck = wf["cond_kind"]  # [NC]
    ci = wf["cond_idx"]  # [NC], already >= 0
    host = wf["cond_host"]  # [NC]
    is_t = ck == fpc.WFC_HIT_DEV
    is_op = ck == fpc.WFC_OP
    is_m = ck == fpc.WFC_MATCHER
    # pad each source plane with one certain-False column so host-kind
    # (clipped) indices gather in bounds whatever the plane width
    pad = jnp.zeros((B, 1), dtype=bool)
    tv = jnp.concatenate([t_value, pad], axis=1)
    tu = jnp.concatenate([t_unc, pad], axis=1)
    opv = jnp.concatenate([op_value, pad], axis=1)
    opu = jnp.concatenate([op_unc, pad], axis=1)
    mv = jnp.concatenate([m_value, pad], axis=1)
    mu = jnp.concatenate([m_unc, pad], axis=1)
    ti = jnp.where(is_t, ci, tv.shape[1] - 1)
    oi = jnp.where(is_op, ci, opv.shape[1] - 1)
    mi = jnp.where(is_m, ci, mv.shape[1] - 1)
    cond_v = tv[:, ti] | opv[:, oi] | mv[:, mi]  # host kinds → False
    cond_u = tu[:, ti] | opu[:, oi] | mu[:, mi] | host[None, :]

    tc = wf["term_cond"]  # [NTERM, CMAX], pad -1 = vacuously TRUE
    valid = tc >= 0
    tcc = jnp.maximum(tc, 0)
    g_v = jnp.where(valid[None], cond_v[:, tcc], True)  # [B, NTERM, C]
    g_u = jnp.where(valid[None], cond_u[:, tcc], False)
    # Kleene AND: one certain-false cond kills the term (the dominant
    # no-trigger case — decided entirely on device); certain-true
    # requires every cond certain-true
    term_dead = (~g_v & ~g_u).any(-1)
    term_true = (g_v & ~g_u).all(-1)

    te = wf["term_emit"]  # [NTERM]
    NE = wf["emit_pad"].shape[0]
    zeros = jnp.zeros((B, NE), dtype=bool)
    emit_v = zeros.at[:, te].max(term_true)
    emit_p = zeros.at[:, te].max(~term_dead)
    return cond_v, cond_u, emit_v, emit_p & ~emit_v


def ensure_all_stream(streams: dict, lengths: dict):
    """Synthesize the "all" stream (header + CRLF + body) on device.

    The host encode may ship a width-1 placeholder instead of the
    assembled "all" matrix (encode_batch ``build_all=False``) — the
    concatenation is ~half the host encode bytes and half the H2D
    transfer, and on device it is two gathers and a select.
    ``lengths["all_hdr"]`` carries the per-row header-prefix length
    (0 = body-only: banner rows alias the banner, headerless rows the
    body — model.Response.part() semantics). Host-built "all"
    (width > 1, the seq-sharded path) passes through untouched.
    """
    allv = streams.get("all")
    if allv is None or allv.shape[1] > 1 or "all_hdr" not in lengths:
        return streams
    body = streams["body"]
    header = streams["header"]
    B, Wb = body.shape
    Wh = header.shape[1]
    Wa = ((Wb + Wh + 2 + 127) // 128) * 128
    hl = lengths["all_hdr"].astype(jnp.int32)[:, None]  # 0 = body-only
    bl = lengths["body"].astype(jnp.int32)[:, None]
    j = jnp.arange(Wa, dtype=jnp.int32)[None, :]
    off = jnp.where(hl > 0, hl + 2, 0)
    is_hdr = j < hl
    hvals = jnp.take_along_axis(
        header, jnp.broadcast_to(jnp.minimum(j, Wh - 1), (B, Wa)), axis=1
    )
    bpos = j - off
    is_body = (bpos >= 0) & (bpos < bl)
    bvals = jnp.take_along_axis(
        body, jnp.broadcast_to(jnp.clip(bpos, 0, Wb - 1), (B, Wa)), axis=1
    )
    is_crlf = (hl > 0) & (j >= hl) & (j < hl + 2)
    crlf = jnp.where(j == hl, jnp.uint8(13), jnp.uint8(10))
    synth = jnp.where(
        is_hdr,
        hvals,
        jnp.where(is_crlf, crlf, jnp.where(is_body, bvals, jnp.uint8(0))),
    )
    out = dict(streams)
    out["all"] = synth
    return out


def _match_impl(
    db: fpc.CompiledDB, candidate_k: int, streams, lengths, status, full=False
):
    streams = ensure_all_stream(streams, lengths)
    value_bits, uncertain_bits, overflow = match_slots(
        db, candidate_k, streams, lengths
    )
    digest = None
    if bool(db.m_md5_check.any()) and "body" in streams:
        from swarm_tpu.ops.md5 import md5_words

        digest = md5_words(streams["body"], lengths["body"])
    rx = None
    if len(db.rx_m_ids):
        from swarm_tpu.ops.regexdev import regex_verify

        B = next(iter(streams.values())).shape[0]
        rx = regex_verify(
            db, streams, lengths, value_bits, k_pairs=db.rx_k_pairs(B)
        )
    out = eval_verdicts(
        db,
        value_bits,
        uncertain_bits,
        lengths,
        status,
        full=full,
        md5_digest=digest,
        rx=rx,
    )
    return (*out, overflow)
