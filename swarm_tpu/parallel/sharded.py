"""The sharded match step: dp × tp × sp over a device mesh.

This is the multi-chip SERVING path (the reference scaled by adding
droplets; this scales by sharding one batch across a TPU slice):

- **data**: rows sharded; no cross-shard traffic until result gather.
- **model**: every rank probes the same windows against its 1/R slice
  of each word table's sorted h1 range (disjoint group ranges, disjoint
  candidate sets, per-rank blooms). Slot bits combine with one
  ``psum`` over ICI — the collective cost is B × NS bits per step.
- **seq**: response bytes sharded; each rank owns the candidate windows
  starting in its slice and exchanges halos of ``max_entry_len`` bytes
  with both neighbors via ``ppermute`` (the ring/halo pattern of
  context parallelism) so words spanning shard boundaries are found by
  exactly the rank that owns their gram position.

The verdict stage runs replicated on every (model, seq) rank after the
psum — it is tiny next to the probe stage.

Production dispatch is SPLIT-PHASE with survivor compaction and
OVERLAPPED reduction, the mesh twin of ``DeviceDB.dispatch``
(docs/SHARDING.md, docs/DEVICE_MATCH.md), as three executables:

- **phase A** runs every rank's stacked bloom probe into a survivor
  RANK plane. On seq meshes the halo ``ppermute`` is FUSED into this
  probe and the extended ``[B, W + 2·halo]`` stream views are carried
  forward — phase B never re-exchanges, so a seq batch pays ONE halo
  round, not two. Each rank also emits its own clamped max-survivor
  count; the host reads the tiny per-rank vector (R × 4 bytes, no
  cross-rank collective) and maxes it to pick phase B's ladder width
  (``compile.survivor_bucket``). Multi-process meshes keep the
  ``pmax``'d replicated scalar (a host can only read its own shard).
- **phase B probe** extracts/verifies at survivor size and stops at
  the per-rank bit planes — no psum, no verdict tail. One wrapper per
  ladder rung serves every width bucket of the shape class (the cache
  key is the stream NAMES, not shapes), so live rung executables stay
  bounded per mesh shape and AOT-store fetches cover each width.
- **reduction** (psum + replicated verdict tail + fused-plane pack)
  is dispatched SEPARATELY and DEFERRED: ``dispatch`` returns a
  handle holding the launch thunk, and the next ``dispatch`` flushes
  it right after its own phase A enqueues — batch N's cross-rank
  reduction rides the device behind phase A of batch N+1, so the
  host's between-phase read never waits on the previous batch's
  collectives. ``collect`` forces the handle if no later dispatch
  already did. One reduction executable serves EVERY ladder rung.

Per-batch uploads land straight in the mesh layout (accounted in the
dispatch staging pool) and are DONATED to their last consumer together with the inter-phase rank
planes; the fused single-kernel pjit step is kept as the bit-identical
reference twin (``SWARM_SHARD_COMPACT=0`` / ``SWARM_SHARD_DONATE=0``,
or the ``compact=``/``donate=`` args; ``SWARM_SHARD_OVERLAP=0`` keeps
the split kernels but launches the reduction inline).
``dispatch``/``collect`` split the blocking host read out of the
launch, so the continuous-batching scheduler keeps ≥2 mesh batches in
flight exactly as on the single-device path.
"""

from __future__ import annotations

import dataclasses
import functools
import threading
import time
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from swarm_tpu.fingerprints import compile as fpc
from swarm_tpu.ops import hashing
from swarm_tpu.ops.match import (
    MAX_COMPILED,
    _StagingPool,
    _StreamCtx,
    _col_starts_of,
    _env_flag,
    compact_candidates,
    eval_verdicts,
    fuse_planes,
    global_candidate_budget,
    host_batch_leaves,
    lru_fetch,
    lru_store,
    match_slots_args,
    prefilter_counts,
    split_fused,
    tiny_slot_bits,
    verify_candidates,
)
from swarm_tpu.ops.md5 import md5_words


def shard_tables_np(db: fpc.CompiledDB, ranks: int) -> list[dict]:
    """Split every table's sorted h1-group range into ``ranks`` contiguous
    slices with identical padded shapes, one pytree leaf-list per table:
    arrays get a leading [ranks] axis to shard over 'model'.

    Padding uses a sentinel h1 of 0xFFFFFFFF with zero entry counts, so
    a padded group can never be "found" twice (searchsorted may land on
    it, but count 0 yields no entries).
    """
    stacked: list[dict] = []
    for table in db.tables:
        G = table.num_groups
        g_per = max(1, -(-G // ranks))
        gmax = g_per
        emax = 1
        slices = []
        for r in range(ranks):
            lo = min(r * g_per, G)
            hi = min(lo + g_per, G)
            if hi > lo:
                e_lo = int(table.entry_start[lo])
                e_hi = int(
                    table.entry_start[hi - 1] + table.entry_count[hi - 1]
                )
            else:
                e_lo = e_hi = 0
            slices.append((lo, hi, e_lo, e_hi))
            emax = max(emax, e_hi - e_lo)
        arrs = {
            "group_h1": np.full((ranks, gmax), 0xFFFFFFFF, dtype=np.uint32),
            "entry_start": np.zeros((ranks, gmax), dtype=np.int32),
            "entry_count": np.zeros((ranks, gmax), dtype=np.int32),
            "entry_h2": np.zeros((ranks, emax), dtype=np.uint32),
            "entry_slot": np.zeros((ranks, emax), dtype=np.int32),
            "entry_off": np.zeros((ranks, emax), dtype=np.int32),
            "entry_len": np.full((ranks, emax), 1 << 30, dtype=np.int32),
            "entry_suf_delta": np.zeros((ranks, emax), dtype=np.int32),
            "entry_suf_h1": np.zeros((ranks, emax), dtype=np.uint32),
            "entry_suf_h2": np.zeros((ranks, emax), dtype=np.uint32),
            "bloom": np.zeros((ranks, hashing.BLOOM_WORDS), dtype=np.uint32),
        }
        for r, (lo, hi, e_lo, e_hi) in enumerate(slices):
            n_g, n_e = hi - lo, e_hi - e_lo
            if n_g == 0:
                continue
            arrs["group_h1"][r, :n_g] = table.group_h1[lo:hi]
            arrs["entry_start"][r, :n_g] = table.entry_start[lo:hi] - e_lo
            arrs["entry_count"][r, :n_g] = table.entry_count[lo:hi]
            for name, src in (
                ("entry_h2", table.entry_h2),
                ("entry_slot", table.entry_slot),
                ("entry_off", table.entry_off),
                ("entry_len", table.entry_len),
                ("entry_suf_delta", table.entry_suf_delta),
                ("entry_suf_h1", table.entry_suf_h1),
                ("entry_suf_h2", table.entry_suf_h2),
            ):
                arrs[name][r, :n_e] = src[e_lo:e_hi]
            arrs["bloom"][r] = hashing.build_bloom_np(
                np.repeat(table.group_h1[lo:hi], table.entry_count[lo:hi]),
                table.entry_h2[e_lo:e_hi],
            )
        stacked.append(arrs)
    return stacked


def shard_stacked_np(db: fpc.CompiledDB, ranks: int) -> dict:
    """Model-sharded twin of ``compile.stack_tables_np``: one stacked
    table-major pytree per rank, with a leading [ranks] axis to shard
    over 'model'. Built on :func:`shard_tables_np` (same slicing, same
    per-rank blooms, same sentinels) and padded to rank-global
    Gmax/Emax so every rank's executable sees one shape."""
    per_table = shard_tables_np(db, ranks)
    T = len(per_table)
    if T == 0:
        base = fpc.stack_tables_np([])
        return {
            k: np.repeat(v[None], ranks, axis=0) for k, v in base.items()
        }
    gmax = max(t["group_h1"].shape[1] for t in per_table)
    emax = max(t["entry_h2"].shape[1] for t in per_table)
    out = {
        "group_h1": np.full((ranks, T, gmax), 0xFFFFFFFF, dtype=np.uint32),
        "entry_start": np.zeros((ranks, T, gmax), dtype=np.int32),
        "entry_count": np.zeros((ranks, T, gmax), dtype=np.int32),
        "entry_h2": np.zeros((ranks, T, emax), dtype=np.uint32),
        "entry_slot": np.zeros((ranks, T, emax), dtype=np.int32),
        "entry_off": np.zeros((ranks, T, emax), dtype=np.int32),
        "entry_len": np.full((ranks, T, emax), 1 << 30, dtype=np.int32),
        "entry_suf_delta": np.zeros((ranks, T, emax), dtype=np.int32),
        "entry_suf_h1": np.zeros((ranks, T, emax), dtype=np.uint32),
        "entry_suf_h2": np.zeros((ranks, T, emax), dtype=np.uint32),
        "bloom": np.zeros(
            (ranks, T, hashing.BLOOM_WORDS), dtype=np.uint32
        ),
        "n_groups": np.zeros((ranks, T), dtype=np.int32),
    }
    for t_idx, arrs in enumerate(per_table):
        g = arrs["group_h1"].shape[1]
        e = arrs["entry_h2"].shape[1]
        for name in (
            "group_h1", "entry_start", "entry_count",
        ):
            out[name][:, t_idx, :g] = arrs[name]
        for name in (
            "entry_h2", "entry_slot", "entry_off", "entry_len",
            "entry_suf_delta", "entry_suf_h1", "entry_suf_h2",
        ):
            out[name][:, t_idx, :e] = arrs[name]
        out["bloom"][:, t_idx] = arrs["bloom"]
        # real (unpadded) group counts per rank — the binary-search
        # bound. Derived from the slices shard_tables_np actually
        # built (every real group has >= 1 entry, padding has 0), so
        # any future change to its slicing rule stays in lockstep.
        out["n_groups"][:, t_idx] = (arrs["entry_count"] > 0).sum(axis=1)
    return out


def max_entry_len(db: fpc.CompiledDB) -> int:
    out = int(hashing.GRAM_LONG)
    for table in db.tables:
        if table.entry_len.size:
            out = max(out, int(table.entry_len.max()))
    return out


def pad_streams_for_seq(streams: dict, seq_ranks: int, halo: int) -> None:
    """Widen streams IN PLACE so each seq rank's slice is at least one
    halo wide and 128-aligned — the invariant :class:`ShardedMatcher`
    enforces (narrow streams like the width-1 OOB placeholders must
    widen before seq sharding). The single shared implementation: the
    engine's encode path and the multichip dryrun both pad through
    here, so the rule cannot drift between them again."""
    import numpy as np

    from swarm_tpu.ops.encoding import round_up

    seq = max(seq_ranks, 1)
    for name, arr in streams.items():
        per_rank = max(round_up(arr.shape[1], seq) // seq, halo)
        target = round_up(per_rank, 128) * seq
        if target > arr.shape[1]:
            streams[name] = np.pad(arr, ((0, 0), (0, target - arr.shape[1])))


_SHARD_METRICS = None


def _shard_metrics():
    """Lazy ``swarm_shard_*`` family handle (kept out of import time so
    oracle-only users never touch the registry; the families themselves
    register at telemetry import — telemetry/shard_export.py)."""
    global _SHARD_METRICS
    if _SHARD_METRICS is None:
        from swarm_tpu.telemetry import shard_export

        _SHARD_METRICS = shard_export
    return _SHARD_METRICS


class _PendingShard:
    """One compacted batch's DEFERRED cross-rank reduction (psum +
    verdict tail), double-buffered behind the next batch's phase A.

    ``dispatch`` returns this handle with the reduction un-launched;
    whoever needs it next fires it exactly once:

    - the NEXT ``dispatch`` flushes it right after its own phase A
      enqueues (``launched_by == "dispatch"`` — the overlapped case);
    - otherwise ``collect``/``match`` force it (``"collect"``);
    - ``SWARM_SHARD_OVERLAP=0`` and multi-process meshes launch inline
      before ``dispatch`` returns (``"inline"``);
    - a corpus ``refresh`` drains any straggler (``"refresh"``).

    A launch failure is stored and re-raised at ``force`` so the error
    surfaces on the batch that owns it, not on the innocent batch whose
    dispatch happened to flush the buffer. The held rank planes are
    accounted in the staging pool (``hold_plane``/``release_plane``)
    while the reduction is in flight.
    """

    __slots__ = (
        "_matcher", "_thunk", "_lock", "_out", "_exc", "_done",
        "_held_bytes", "launched_by",
    )

    def __init__(self, matcher, thunk, held_bytes: int):
        self._matcher = matcher
        self._thunk = thunk
        self._lock = threading.Lock()
        self._out = None
        self._exc = None
        self._done = False
        self._held_bytes = int(held_bytes)
        self.launched_by: Optional[str] = None
        matcher.staging.hold_plane(self._held_bytes)

    def launch(self, by: str) -> None:
        """Fire the reduction thunk exactly once (idempotent; safe from
        the submit thread, the walk worker, and collect concurrently)."""
        with self._lock:
            if self._done:
                return
            try:
                self._out = self._thunk()
            except BaseException as e:  # surfaced at force()
                self._exc = e
            finally:
                self._done = True
                self._thunk = None
                self.launched_by = by
                self._matcher.staging.release_plane(self._held_bytes)
                self._matcher._clear_pending(self)

    def force(self):
        """Launch if nothing did yet, then yield the (device-resident)
        reduction output — or re-raise the launch failure."""
        self.launch("collect")
        if self._exc is not None:
            raise self._exc
        return self._out


@dataclasses.dataclass
class ShardedMatcher:
    """Builds and caches the pjit'd sharded match step for one mesh.

    Serving surface (docs/SHARDING.md): :meth:`dispatch` launches the
    split-phase compacted kernels asynchronously (the only blocking
    point is the tiny per-rank max-survivor read between phases) and
    returns a :class:`_PendingShard` whose cross-rank reduction stays
    un-launched until the next dispatch's phase A is in the queue;
    :meth:`collect` forces the handle and pays the one fused host
    read. ``MatchEngine.begin_packed``/``finish_packed`` route here
    exactly as they do to ``DeviceDB``, so the scheduler's in-flight
    budget and walk offload apply unchanged on the mesh. The fused
    single-kernel step stays as the bit-identical reference twin
    (``compact=False``); ``donate=False`` keeps the staged uploads
    alive past the launch; ``overlap=False`` launches the reduction
    inline (multi-process meshes always do — deferred collective
    launch order must stay identical on every process).
    """

    db: fpc.CompiledDB
    mesh: Mesh
    candidate_k: int = 128
    compact: Optional[bool] = None
    donate: Optional[bool] = None
    overlap: Optional[bool] = None

    def __post_init__(self):
        if self.compact is None:
            self.compact = _env_flag("SWARM_SHARD_COMPACT", True)
        if self.donate is None:
            self.donate = _env_flag("SWARM_SHARD_DONATE", True)
        if self.overlap is None:
            self.overlap = _env_flag("SWARM_SHARD_OVERLAP", True)
        self.staging = _StagingPool()
        self.compile_seconds = 0.0  # guarded-by: _counter_lock
        self.compile_count = 0  # guarded-by: _counter_lock
        #: AOT executable-cache fetch spy (docs/AOT.md): dispatches
        #: that LOADED a published executable instead of compiling —
        #: counted distinctly so the compile spy stays honest
        self.fetch_seconds = 0.0  # guarded-by: _counter_lock
        self.fetch_count = 0  # guarded-by: _counter_lock
        self._aot = None  # AotClient (attach_aot) — None = compile-only
        #: most recent compacted dispatch: survivor_max / verify_k /
        #: budget (the "phase B launches at survivor size" evidence)
        self.last_compact: dict = {}  # guarded-by: _counter_lock
        self._counter_lock = threading.Lock()
        self.ranks = {name: int(self.mesh.shape[name]) for name in self.mesh.axis_names}
        self.halo = max_entry_len(self.db) if self.ranks.get("seq", 1) > 1 else 0
        # the SAME argument-pytree convention as DeviceDB
        # (docs/DEVICE_MATCH.md): per-rank stacked word tables shard
        # over 'model'; the verdict/rx/slot arrays replicate. Uploaded
        # once here, passed as jit arguments every call — the compiled
        # step is corpus-size-free on the sharded path too.
        self.meta = fpc.layout_meta(self.db)
        self._tab_np = shard_stacked_np(self.db, self.ranks.get("model", 1))
        self._rep_np = {
            "slot_bytes": self.db.slot_bytes,
            "slot_len": self.db.slot_len,
            "tiny_bytes": self.db.tiny_bytes,
            "tiny_slot": self.db.tiny_slot,
            "verdict": fpc.verdict_arrays_np(self.db),
            "rx": fpc.rx_arrays_np(self.db),
        }
        # multi-host (jax.distributed) meshes span devices this process
        # cannot address: inputs must become GLOBAL jax.Arrays (every
        # process holds the full host copy; each device takes its
        # slice) and outputs gather back host-local. Single-process
        # meshes keep the plain local-array path.
        self.multiprocess = any(
            d.process_index != jax.process_index()
            for d in self.mesh.devices.flat
        )
        # deferred reduction launch order is host-controlled; on a
        # multi-controller mesh every process MUST enqueue collectives
        # in the same order, so overlap stays single-controller-only
        self.overlap = bool(self.overlap) and not self.multiprocess
        #: the one un-launched deferred reduction (double buffer depth
        #: 1: each dispatch flushes its predecessor before parking its
        #: own handle)
        self._pending: Optional[_PendingShard] = None  # guarded-by: _counter_lock
        # constant after construction — upload once, not per match
        # call, straight into the mesh layout the kernels consume (an
        # array staged on one device would be resharded every call)
        self._tab_j = {
            k: self._global(v, P("model")) for k, v in self._tab_np.items()
        }
        self._rep_j = jax.tree_util.tree_map(
            lambda a: self._global(a, P()), self._rep_np
        )
        self._fn_cache: dict = {}  # guarded-by: _counter_lock
        for ax, size in self.ranks.items():
            _shard_metrics().MESH_AXIS.labels(axis=ax).set(size)

    def _global(self, arr, spec):
        """Host copy -> array laid out per ``spec`` over the mesh
        (global across processes on a multi-process mesh)."""
        arr = np.asarray(arr)
        sharding = NamedSharding(self.mesh, spec)
        return jax.make_array_from_callback(
            arr.shape, sharding, lambda idx: arr[idx]
        )

    # ------------------------------------------------------------------
    # trace-time building blocks shared by the fused twin and the
    # split-phase kernels — one implementation, so parity can't drift
    # ------------------------------------------------------------------
    # -- AOT executable cache (docs/AOT.md) ----------------------------
    def attach_aot(self, client) -> None:
        """Attach an :class:`~swarm_tpu.aot.AotClient` so every
        subsequently built mesh step fetches published executables
        before compiling. Single-controller multi-device meshes fetch
        exactly like the single-device path — the store digest already
        keys on device count + XLA flags and the trace salt keys on
        the mesh factorization, so every ladder rung of every mesh
        shape loads instead of compiling. ONLY multi-controller
        (jax.distributed) meshes stay compile-only: an executable
        image is only loadable on the topology it was compiled for,
        and cross-host coordination of the load is not worth the
        coupling — the per-host persistent XLA cache already covers
        that deployment. Live wrappers drop so the attach takes
        effect at the next dispatch."""
        with self._counter_lock:
            self._aot = None if self.multiprocess else client
            self._fn_cache.clear()

    def _trace_salt(self) -> str:
        """The sharded twin of ``DeviceDB._trace_salt``: layout
        metadata + kernel statics + the MESH (axis names/sizes — a
        (2,2,2) executable must never serve an (8,1,1) worker)."""
        db = self.db
        return repr(
            (
                self.meta,
                self.candidate_k,
                tuple(sorted(self.ranks.items())),
                self.halo,
                db.num_slots,
                db.num_templates,
                int(db.op_src.shape[0]),
                int(db.m_src.shape[0]),
                int(db.rx_seq_always.sum()),
            )
        )

    def _wrap_jit(self, fun, kernel_id: str, donate_argnums=()):
        if self._aot is None:
            if donate_argnums:
                return jax.jit(fun, donate_argnums=donate_argnums)
            return jax.jit(fun)
        from swarm_tpu.aot.jitcache import AotJit

        return AotJit(
            fun,
            kernel_id=kernel_id,
            salt=self._trace_salt(),
            client=self._aot,
            donate_argnums=donate_argnums,
            cap=4 * MAX_COMPILED,
        )

    def executable_count(self) -> int:
        """Live locally-compiled executables across every cached mesh
        step (the compile spy's cache-size view; fetched loads are
        counted by :meth:`fetched_executable_count` instead)."""
        with self._counter_lock:
            fns = list(self._fn_cache.values())
        return sum(
            int(fn._cache_size())
            for fn in fns
            if hasattr(fn, "_cache_size")
        )

    def fetched_executable_count(self) -> int:
        from swarm_tpu.aot.jitcache import fetched_size_of

        with self._counter_lock:
            fns = list(self._fn_cache.values())
        return sum(fetched_size_of(fn) for fn in fns)

    def aot_prewarm(self) -> int:
        """Pool every published executable for this program group
        (bring-up fetch; see ``DeviceDB.aot_prewarm``)."""
        client = self._aot
        return client.prewarm() if client is not None else 0

    # -- corpus refresh (docs/AOT.md) ----------------------------------
    def refresh(self, db_new: fpc.CompiledDB) -> dict:
        """Zero-downtime corpus refresh on the mesh: recompute the
        per-rank stacked/replicated host pytrees and re-upload ONLY
        the leaves whose bytes changed (byte-equal leaves keep their
        existing device arrays — the rank-sharded stack is rebuilt on
        host but the ICI/H2D traffic is delta-sized). The trace
        signature decides executable retention exactly as on the
        single-device path. Caller quiesces dispatches first."""
        # a still-deferred reduction captured the OLD corpus arrays —
        # drain it before the swap (callers quiesce dispatches, but a
        # parked handle outlives its dispatch by design)
        stale = self._take_pending()
        if stale is not None:
            stale.launch("refresh")
        old_salt = self._trace_salt()
        old_tab_np, old_rep_np = self._tab_np, self._rep_np
        old_tab_j, old_rep_j = self._tab_j, self._rep_j
        self.db = db_new
        self.meta = fpc.layout_meta(db_new)
        self.halo = (
            max_entry_len(db_new) if self.ranks.get("seq", 1) > 1 else 0
        )
        self._tab_np = shard_stacked_np(
            db_new, self.ranks.get("model", 1)
        )
        self._rep_np = {
            "slot_bytes": db_new.slot_bytes,
            "slot_len": db_new.slot_len,
            "tiny_bytes": db_new.tiny_bytes,
            "tiny_slot": db_new.tiny_slot,
            "verdict": fpc.verdict_arrays_np(db_new),
            "rx": fpc.rx_arrays_np(db_new),
        }

        def upload(new_np, old_np_map, old_j_map, spec_of):
            old_host = {
                jax.tree_util.keystr(p): leaf
                for p, leaf in jax.tree_util.tree_flatten_with_path(
                    old_np_map
                )[0]
            }
            old_dev = {
                jax.tree_util.keystr(p): leaf
                for p, leaf in jax.tree_util.tree_flatten_with_path(
                    old_j_map
                )[0]
            }
            flat, _ = jax.tree_util.tree_flatten_with_path(new_np)
            out = []
            n_up = 0
            for path, leaf in flat:
                key = jax.tree_util.keystr(path)
                old = old_host.get(key)
                if (
                    key in old_dev
                    and isinstance(old, np.ndarray)
                    and old.dtype == leaf.dtype
                    and old.shape == leaf.shape
                    and (old is leaf or np.array_equal(old, leaf))
                ):
                    out.append(old_dev[key])
                else:
                    n_up += 1
                    if self.multiprocess:
                        out.append(self._global(leaf, spec_of(path)))
                    else:
                        out.append(jnp.asarray(leaf))
            return (
                jax.tree_util.tree_unflatten(
                    jax.tree_util.tree_structure(new_np), out
                ),
                n_up,
            )

        self._tab_j, up_tab = upload(
            self._tab_np, old_tab_np, old_tab_j, lambda _p: P("model")
        )
        self._rep_j, up_rep = upload(
            self._rep_np, old_rep_np, old_rep_j, lambda _p: P()
        )
        old_leaves = jax.tree_util.tree_leaves((old_tab_np, old_rep_np))
        new_leaves = jax.tree_util.tree_leaves(
            (self._tab_np, self._rep_np)
        )
        keep = (
            old_salt == self._trace_salt()
            and len(old_leaves) == len(new_leaves)
            and all(
                o.shape == n.shape and o.dtype == n.dtype
                for o, n in zip(old_leaves, new_leaves)
            )
        )
        with self._counter_lock:
            if not keep:
                self._fn_cache.clear()
        return {
            "uploaded_leaves": up_tab + up_rep,
            "executables_kept": keep,
        }

    def _specs(self, streams: dict, lengths: dict):
        """(tab, rep, streams, lengths) partition specs for one batch
        shape — corpus slices over 'model', replicated verdict/rx,
        rows over 'data', response bytes over 'seq'."""
        return (
            {name: P("model") for name in self._tab_np},
            jax.tree_util.tree_map(lambda _a: P(), self._rep_np),
            {k: P("data", "seq") for k in streams},
            {k: P("data") for k in lengths},
        )

    def _exchange_halos(self, streams: dict):
        """Halo exchange over 'seq' (trace-time no-op when unsharded):
        each rank borrows ``halo`` bytes from both neighbors via
        ppermute so words spanning shard boundaries verify on exactly
        the rank that owns their gram position. Returns
        ``(streams_ext, offsets, back, fwd)``."""
        seq_ranks = self.ranks.get("seq", 1)
        if seq_ranks <= 1:
            return streams, 0, 0, 0
        halo = self.halo
        seq_index = jax.lax.axis_index("seq")
        ext: dict = {}
        offsets: dict = {}
        for name, local in streams.items():
            fwd_halo = jax.lax.ppermute(
                local[:, :halo],
                "seq",
                [(r, r - 1) for r in range(1, seq_ranks)],
            )
            back_halo = jax.lax.ppermute(
                local[:, -halo:],
                "seq",
                [(r, r + 1) for r in range(seq_ranks - 1)],
            )
            ext[name] = jnp.concatenate([back_halo, local, fwd_halo], axis=1)
            offsets[name] = seq_index * local.shape[1]
        return ext, offsets, halo, halo

    def _combine_finish(
        self, value_bits, uncertain_bits, overflow, streams, lengths,
        status, rep, full,
    ):
        """Shared tail of every sharded route: psum the per-rank bit
        planes over the communicating axes, then the replicated verdict
        stage (device md5, device regex verify, verdict lowering) and
        the fused-plane pack. Runs at trace time inside the step."""
        db = self.db
        seq_ranks = self.ranks.get("seq", 1)
        combine_axes = tuple(
            ax for ax in ("model", "seq") if self.ranks.get(ax, 1) > 1
        )
        if combine_axes:
            value_bits = (
                jax.lax.psum(value_bits.astype(jnp.int32), combine_axes) > 0
            )
            uncertain_bits = (
                jax.lax.psum(uncertain_bits.astype(jnp.int32), combine_axes)
                > 0
            )
            overflow = (
                jax.lax.psum(overflow.astype(jnp.int32), combine_axes) > 0
            )

        # device md5 (ops/md5.py): the block chain is sequential in
        # the byte dimension, so a seq-sharded body is re-gathered
        # (tiled over ICI) just for the digest — cheap next to the
        # probe stage, and only when the corpus compares digests
        def full_stream(name):
            local = streams[name]
            if seq_ranks > 1:
                return jax.lax.all_gather(local, "seq", axis=1, tiled=True)
            return local

        digest = None
        if bool(db.m_md5_check.any()) and "body" in streams:
            digest = md5_words(full_stream("body"), lengths["body"])
        # device regex verify over the combined slot bits: like md5
        # it needs whole rows, so used streams gather over 'seq'
        rx = None
        if len(db.rx_m_ids):
            from swarm_tpu.ops.encoding import STREAMS
            from swarm_tpu.ops.regexdev import regex_verify

            used = {STREAMS[int(s)] for s in db.rx_seq_stream}
            gathered = {n: full_stream(n) for n in used}
            rx = regex_verify(
                db,
                gathered,
                lengths,
                value_bits,
                k_pairs=db.rx_k_pairs(status.shape[0]),
                arrays=rep["rx"],
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
            arrays=rep["verdict"],
        )
        if full:
            # pack bit planes per data-rank (axis 1 is unsharded, so
            # packed bytes concatenate cleanly over 'data') and fuse
            # them with the overflow column into ONE output array —
            # the host then makes a single device read (split_fused)
            return fuse_planes(out, overflow)
        return (*out, overflow)

    # ------------------------------------------------------------------
    # executable builders (one per batch shape, LRU-bounded)
    # ------------------------------------------------------------------
    def _build_fused(self, streams: dict, lengths: dict, full: bool):
        """The fused single-kernel pjit step — the legacy reference
        twin (``compact=False``, or a corpus with no word tables)."""
        db = self.db
        meta = self.meta
        candidate_k = self.candidate_k

        # jit-captures: self, db, meta, candidate_k, full (host metadata
        # + scalars — trace-static; the corpus rides the tab/rep
        # ARGUMENTS, never the closure)
        def step(tab, rep, streams, lengths, status):
            streams_ext, offsets, back, fwd = self._exchange_halos(streams)
            arrays = {
                "tab": {k: v[0] for k, v in tab.items()},
                "slot_bytes": rep["slot_bytes"],
                "slot_len": rep["slot_len"],
                "tiny_bytes": rep["tiny_bytes"],
                "tiny_slot": rep["tiny_slot"],
            }
            value_bits, uncertain_bits, overflow = match_slots_args(
                db,
                meta,
                arrays,
                candidate_k,
                streams_ext,
                lengths,
                pos_offset=offsets,
                back_halo=back,
                fwd_halo=fwd,
            )
            return self._combine_finish(
                value_bits, uncertain_bits, overflow, streams, lengths,
                status, rep, full,
            )

        tab_specs, rep_specs, stream_spec, lengths_spec = self._specs(
            streams, lengths
        )
        out_specs = P("data") if full else (P("data"),) * 3
        fn = jax.shard_map(
            step,
            mesh=self.mesh,
            in_specs=(
                tab_specs, rep_specs, stream_spec, lengths_spec, P("data"),
            ),
            out_specs=out_specs,
            check_vma=False,
        )
        return self._wrap_jit(fn, f"sh.fused.full={full}")

    def _ext_ctx(self, streams: dict, lengths: dict):
        """Trace-time twin of :meth:`_exchange_halos` for kernels that
        receive ALREADY-EXTENDED ``[B, W + 2·halo]`` views carried out
        of phase A: rebuild the stream context (window offsets in
        pre-halo coordinates, recovered from the carried width) and
        the local views WITHOUT a second ppermute round — the fused
        single-round halo exchange. Unsharded seq passes through.
        Returns ``(ctx, local_views, back, fwd)``; the local views are
        lazy slices whose bytes are bit-identical to the pre-exchange
        stream (``ext[:, h:-h] == local`` by construction), and XLA
        DCEs them where only their shapes are consumed."""
        seq_ranks = self.ranks.get("seq", 1)
        if seq_ranks <= 1:
            return _StreamCtx(streams, lengths, 0), streams, 0, 0
        h = self.halo
        seq_index = jax.lax.axis_index("seq")
        local = {k: v[:, h:-h] for k, v in streams.items()}
        offsets = {
            k: seq_index * (v.shape[1] - 2 * h)
            for k, v in streams.items()
        }
        return _StreamCtx(streams, lengths, offsets), local, h, h

    def _reduce_needs_streams(self, streams) -> bool:
        """Whether the reduction tail re-reads response bytes (device
        md5 digest or device regex verify gather whole rows over
        'seq'). When False the deferred reduce takes no stream
        argument at all and the phase-B probe is the streams' last
        consumer (donation moves accordingly)."""
        db = self.db
        return bool(
            (bool(db.m_md5_check.any()) and "body" in streams)
            or len(db.rx_m_ids)
        )

    def _build_phase_a(self, streams: dict, lengths: dict, donate_streams: bool):
        """Standing sharded phase A: per-rank stacked bloom probe →
        survivor RANK plane + per-rank overflow + each rank's clamped
        max survivor count. The rank plane and overflow keep an
        explicit leading (model, seq) axis — every rank's candidate
        space is distinct, and the phase-B probe slices its own plane
        back out.

        On seq meshes the halo ppermute happens HERE, once: the
        extended ``[B, W + 2·halo]`` views ride the output straight
        into phase B (``_ext_ctx`` rebuilds offsets from the carried
        width), so one batch pays one halo round total.

        The survivor count stays per-rank (single 4-byte lane per
        device, specced over every axis) so the host read between
        phases costs R × 4 bytes and NO cross-rank collective; only
        multi-controller meshes keep the ``pmax``'d replicated scalar,
        because a process can only read its own shard of a global
        array."""
        meta = self.meta
        budget = global_candidate_budget(
            self.candidate_k, len(meta.table_stream)
        )
        carry = self.ranks.get("seq", 1) > 1

        # jit-captures: self, meta, budget, carry (layout metadata +
        # python scalars; all trace-static)
        def step_a(tab, streams, lengths):
            streams_ext, offsets, back, fwd = self._exchange_halos(streams)
            ctx = _StreamCtx(streams_ext, lengths, offsets)
            cnt, _cs = prefilter_counts(
                meta, {k: v[0] for k, v in tab.items()}, ctx, back, fwd
            )
            n_surv = cnt[:, -1]
            K = max(1, min(budget, cnt.shape[1]))
            overflow = n_surv > K
            nmax = jnp.max(jnp.minimum(n_surv, K))
            if self.multiprocess:
                # multi-controller: the host can only read its own
                # shard, so keep the replicated pmax'd scalar
                nmax_out = jax.lax.pmax(nmax, tuple(self.mesh.axis_names))
            else:
                nmax_out = nmax[None]  # per-rank lane; host maxes R ints
            if carry:
                return cnt[None], overflow[None], nmax_out, streams_ext
            return cnt[None], overflow[None], nmax_out

        tab_specs, _rep_specs, stream_spec, lengths_spec = self._specs(
            streams, lengths
        )
        rank_spec = P(("model", "seq"), "data")
        nmax_spec = P() if self.multiprocess else P(("data", "model", "seq"))
        out_specs = (rank_spec, rank_spec, nmax_spec)
        if carry:
            out_specs = out_specs + ({k: P("data", "seq") for k in streams},)
        fn = jax.shard_map(
            step_a,
            mesh=self.mesh,
            in_specs=(tab_specs, stream_spec, lengths_spec),
            out_specs=out_specs,
            check_vma=False,
        )
        # streams are donated into phase A only when the extended
        # views replace them as every later kernel's input (seq mesh +
        # matcher-owned staged copies)
        donate = (1,) if donate_streams else ()
        return self._wrap_jit(
            fn,
            f"sh.A2.mp={int(self.multiprocess)}.don={int(donate_streams)}",
            donate_argnums=donate,
        )

    def _build_phase_b_probe(
        self, streams: dict, lengths: dict, kc: int, donate_streams: bool,
    ):
        """Sharded phase-B PROBE at the static ladder rung ``kc``:
        per-rank survivor extraction from the phase-A rank plane,
        gather-verify + tiny at survivor size — stopping at the
        per-rank bit planes. No psum, no verdict tail: the cross-rank
        reduction is a separate deferred executable
        (:meth:`_build_reduce`), which is what lets batch N's
        collectives overlap batch N+1's probe. The phase-A rank plane
        is always DONATED; the (possibly extended) streams are donated
        only when this probe is their last consumer."""
        db = self.db
        meta = self.meta
        budget = global_candidate_budget(
            self.candidate_k, len(meta.table_stream)
        )

        # jit-captures: self, db, meta, budget, kc (metadata and
        # scalars only — kc is the ladder rung this executable serves)
        def step_bp(tab, rep, streams, lengths, cnt_r):
            ctx, local, back, fwd = self._ext_ctx(streams, lengths)
            tabr = {k: v[0] for k, v in tab.items()}
            cnt = cnt_r[0]
            K = max(1, min(budget, cnt.shape[1]))
            col = compact_candidates(cnt, kc, K)
            # candidate axis = LOCAL window coordinates (pre-halo
            # widths), exactly what prefilter_counts concatenated —
            # _col_starts_of only reads shapes, so the local slices
            # cost nothing here
            col_starts = _col_starts_of(meta, local)
            value_bits, uncertain_bits = verify_candidates(
                meta,
                tabr,
                rep["slot_bytes"],
                rep["slot_len"],
                ctx,
                col,
                col_starts,
                db.num_slots,
                back,
                fwd,
            )
            value_bits = tiny_slot_bits(
                meta, rep["tiny_bytes"], rep["tiny_slot"], ctx, value_bits,
                back,
            )
            return value_bits[None], uncertain_bits[None]

        tab_specs, rep_specs, stream_spec, lengths_spec = self._specs(
            streams, lengths
        )
        rank_spec = P(("model", "seq"), "data")
        fn = jax.shard_map(
            step_bp,
            mesh=self.mesh,
            in_specs=(
                tab_specs, rep_specs, stream_spec, lengths_spec, rank_spec,
            ),
            out_specs=(rank_spec, rank_spec),
            check_vma=False,
        )
        donate = (2, 4) if donate_streams else (4,)  # [streams,] cnt plane
        # kc rides the kernel id (it is baked into the step closure
        # here, not a static argnum) so every ladder rung publishes
        # its own artifact
        return self._wrap_jit(
            fn,
            f"sh.Bp.kc={kc}.don={int(donate_streams)}",
            donate_argnums=donate,
        )

    def _build_reduce(
        self, snames, lnames, full: bool, don_streams: bool,
        don_host: bool,
    ):
        """The ONE deferred reduction executable: psum the per-rank
        bit planes over the communicating axes + the replicated
        verdict tail + the fused-plane pack (:meth:`_combine_finish`).
        Rung-independent — EVERY ladder width of a shape class lands
        in this same program, so deferring it adds exactly one live
        executable per mesh shape. ``snames is None`` when the corpus
        needs no response bytes past the probe (no device md5, no
        device regex) — the common case, where the reduce ships only
        the rank planes + lengths/status."""
        full_flag = full
        carry = self.ranks.get("seq", 1) > 1
        h = self.halo

        # jit-captures: self, full_flag, carry, h (python scalars —
        # trace-static; corpus rides the rep ARGUMENT)
        def finish(rep, streams, lengths, status, vb_r, ub_r, ovf_r):
            local = streams
            if carry:
                # carried extended views → slice the exact pre-halo
                # bytes back out for the md5/regex row gathers
                local = {k: v[:, h:-h] for k, v in streams.items()}
            return self._combine_finish(
                vb_r[0], ub_r[0], ovf_r[0], local, lengths, status, rep,
                full_flag,
            )

        rep_specs = jax.tree_util.tree_map(lambda _a: P(), self._rep_np)
        lengths_spec = {k: P("data") for k in lnames}
        rank_spec = P(("model", "seq"), "data")
        out_specs = P("data") if full else (P("data"),) * 3
        if snames is not None:
            stream_spec = {k: P("data", "seq") for k in snames}
            step_r = finish
            in_specs = (
                rep_specs, stream_spec, lengths_spec, P("data"),
                rank_spec, rank_spec, rank_spec,
            )
            donate = (4, 5, 6)  # the matcher-owned rank planes, always
            if don_streams:
                donate = (1,) + donate
            if don_host:
                donate = donate + (2, 3)
        else:

            # jit-captures: finish (the closure above)
            def step_r(rep, lengths, status, vb_r, ub_r, ovf_r):
                return finish(rep, {}, lengths, status, vb_r, ub_r, ovf_r)

            in_specs = (
                rep_specs, lengths_spec, P("data"),
                rank_spec, rank_spec, rank_spec,
            )
            donate = (3, 4, 5)
            if don_host:
                donate = donate + (1, 2)
        fn = jax.shard_map(
            step_r,
            mesh=self.mesh,
            in_specs=in_specs,
            out_specs=out_specs,
            check_vma=False,
        )
        return self._wrap_jit(
            fn,
            (
                f"sh.R.full={full}.s={int(snames is not None)}"
                f".don={int(don_streams)}{int(don_host)}"
            ),
            donate_argnums=tuple(sorted(donate)),
        )

    def _launch_reduce(
        self, streams_j, lengths_j, status_j, vb, ub, ovf, full: bool,
        donate_host: bool, snames, lnames,
    ):
        """Fetch/build + enqueue the deferred reduction for one batch
        (the :class:`_PendingShard` thunk body). ``streams_j`` is None
        when the verdict tail needs no response bytes; on seq meshes
        it is the carried extended views, sliced back to local inside
        the step."""
        t0 = time.perf_counter()
        needs = streams_j is not None
        carry = self.ranks.get("seq", 1) > 1
        # carried extended views are matcher-created inside phase A —
        # donatable regardless of who owns the original host batch
        don_s = bool(carry or donate_host)
        fr, fresh_r = self._get_fn(
            (
                "R", snames if needs else None, lnames, full, don_s,
                bool(donate_host),
            ),
            lambda: self._build_reduce(
                snames if needs else None, lnames, full, don_s,
                bool(donate_host),
            ),
        )
        if needs:
            out = fr(self._rep_j, streams_j, lengths_j, status_j, vb, ub, ovf)
        else:
            out = fr(self._rep_j, lengths_j, status_j, vb, ub, ovf)
        self._note_launch([(fr, fresh_r)], t0)
        return out

    # ------------------------------------------------------------------
    # deferred-reduction double buffer (depth 1; _PendingShard)
    # ------------------------------------------------------------------
    def _take_pending(self) -> Optional[_PendingShard]:
        with self._counter_lock:
            handle, self._pending = self._pending, None
        return handle

    def _set_pending(self, handle: _PendingShard) -> None:
        with self._counter_lock:
            self._pending = handle

    def _clear_pending(self, handle: _PendingShard) -> None:
        """Called by the handle itself once launched, so a handle
        forced by collect() can never be re-taken by a later
        dispatch (launch is idempotent anyway — this is hygiene)."""
        with self._counter_lock:
            if self._pending is handle:
                self._pending = None

    # ------------------------------------------------------------------
    def _get_fn(self, key, builder):
        """(fn, freshly_built) from the LRU-bounded executable cache.
        Ladder rungs multiply the live entries (one pjit per
        (shape, kc) pair), hence the same generous 4x churn bound
        DeviceDB applies to its jit caches. Runs under
        ``_counter_lock``: with the walk offload armed, the submit
        thread (dispatch) and the walk worker (a degraded batch's
        sync-path retry) can reach this cache concurrently, and
        ``lru_fetch``'s refresh pops/reinserts — an unlocked race
        could evict the same key twice or compile twin wrappers.
        Building the wrapper under the lock is cheap (jit/shard_map
        construction only; XLA compiles at first call)."""
        with self._counter_lock:
            fn = lru_fetch(self._fn_cache, key)
            fresh = fn is None
            if fresh:
                fn = builder()
                lru_store(self._fn_cache, key, fn, 4 * MAX_COMPILED)
        return fn, fresh

    def _check_seq_widths(self, streams: dict) -> None:
        seq_ranks = self.ranks.get("seq", 1)
        if seq_ranks <= 1:
            return
        for name, arr in streams.items():
            per_rank = arr.shape[1] // seq_ranks
            if arr.shape[1] % seq_ranks:
                raise ValueError(
                    f"stream {name!r} width {arr.shape[1]} not divisible "
                    f"by seq ranks {seq_ranks}"
                )
            if per_rank < self.halo:
                # the halo slices local[:, :halo] would silently come
                # up short and misalign every window coordinate
                raise ValueError(
                    f"stream {name!r}: per-rank width {per_rank} < halo "
                    f"{self.halo} (longest table entry); widen the "
                    f"stream or lower the seq factor"
                )

    def _stage(self, streams: dict, lengths: dict, status):
        """Upload one batch straight into its mesh layout (rows over
        'data', bytes over 'seq': each device receives only its own
        slice) — always a COPY, so phase-B donation can never corrupt
        caller-owned numpy; the engine's recycled encode planes keep
        rotating untouched."""
        s_j = {
            k: self._global(v, P("data", "seq")) for k, v in streams.items()
        }
        l_j = {k: self._global(v, P("data")) for k, v in lengths.items()}
        st_j = self._global(status, P("data"))
        self.staging.account(
            int(
                sum(getattr(v, "nbytes", 0) for v in streams.values())
                + sum(getattr(v, "nbytes", 0) for v in lengths.values())
                + int(getattr(status, "nbytes", 0))
            )
        )
        return s_j, l_j, st_j

    def _note_launch(self, launches, t0: float) -> None:
        """Compile/fetch accounting at the dispatch boundary (same
        contract as DeviceDB's spy: wall time of dispatches that made
        at least one new executable servable, attributed to the
        compile or the AOT-fetch pair by what the freshly built
        wrappers actually did — a deserialized load is NOT a
        compile). ``launches`` = [(fn, freshly_built), ...]; the
        wrappers have been CALLED by the time this runs."""
        from swarm_tpu.aot.jitcache import fetched_size_of

        fresh_fns = [fn for fn, fresh in launches if fresh]
        if not fresh_fns:
            return
        compiled = sum(
            int(fn._cache_size())
            for fn in fresh_fns
            if hasattr(fn, "_cache_size")
        )
        fetched = sum(fetched_size_of(fn) for fn in fresh_fns)
        dt = time.perf_counter() - t0
        with self._counter_lock:
            if fetched:
                self.fetch_seconds += dt
                self.fetch_count += 1
            if compiled:
                self.compile_seconds += dt
                self.compile_count += 1

    def _dispatch_metrics(
        self, streams: dict, halo_rounds_a: int = 1,
        halo_rounds_b: int = 0, saved_rounds: int = 0,
    ) -> None:
        """Per-dispatch traffic accounting. Halo bytes are labeled by
        PHASE so the bench can attribute the single-round fusion win:
        the compacted path pays one phase-A round and charges the
        round it no longer pays (vs the historical re-exchange in
        phase B) to the saved counter; the fused twin's one in-kernel
        exchange counts as phase a."""
        m = _shard_metrics()
        m.SHARD_DISPATCHES.inc(1)
        B = int(next(iter(streams.values())).shape[0])
        ns = max(self.db.num_slots, 1)
        if any(self.ranks.get(ax, 1) > 1 for ax in ("model", "seq")):
            # value + uncertain + overflow int32 lanes entering the
            # cross-rank psum (docs/SHARDING.md: B × NS bits per step)
            m.PSUM_BYTES.inc(B * (2 * ns + 1) * 4)
        if self.ranks.get("seq", 1) > 1:
            round_bytes = 2 * self.halo * B * len(streams)
            if halo_rounds_a:
                m.HALO_BYTES.labels(phase="a").inc(
                    halo_rounds_a * round_bytes
                )
            if halo_rounds_b:
                m.HALO_BYTES.labels(phase="b").inc(
                    halo_rounds_b * round_bytes
                )
            if saved_rounds:
                m.HALO_SAVED.inc(saved_rounds * round_bytes)

    # ------------------------------------------------------------------
    def dispatch(self, streams: dict, lengths: dict, status, full: bool = True):
        """Async half of :meth:`match`: stage the batch, launch the
        sharded kernels, and return WITHOUT a full host transfer — the
        continuous-batching scheduler dispatches batch i+1 here before
        walking batch i's verdicts; :meth:`collect` finalizes.

        On the compacted path the only blocking point is the phase-A
        max-survivor read (R × 4 bytes of per-rank lanes on a
        single-controller mesh) that picks the probe's ladder width;
        the cross-rank reduction comes back as an un-launched
        :class:`_PendingShard` and rides behind the NEXT dispatch's
        phase A — or behind :meth:`collect` when the window closes."""
        from swarm_tpu.resilience.faults import fault_point

        # same fault point as DeviceDB.dispatch: "the device path
        # failed" is one failure class whichever matcher serves it
        # (MatchEngine degrades to the CPU oracle either way)
        fault_point("device.dispatch")
        self._check_seq_widths(streams)
        # executable cache keys use stream NAMES, not shapes: the
        # builders only consume names (partition specs), so ONE
        # wrapper serves every width bucket of a shape class and the
        # per-shape executables live in the wrapper's own cache —
        # bounded rung count per mesh shape, and AOT fetch covers
        # each width signature under the same kernel id
        snames = tuple(sorted(streams))
        lkey = tuple(sorted(lengths))
        t0 = time.perf_counter()
        s_j, l_j, st_j = self._stage(streams, lengths, status)
        if not (self.compact and len(self.meta.table_stream)):
            # fused legacy/reference arm (also the no-tables corpus,
            # where there is nothing to compact)
            fn, fresh = self._get_fn(
                ("fused", snames, lkey, full),
                lambda: self._build_fused(streams, lengths, full),
            )
            out = fn(self._tab_j, self._rep_j, s_j, l_j, st_j)
            self._note_launch([(fn, fresh)], t0)
            self._dispatch_metrics(streams)
            return out

        donate_streams = self.donate and host_batch_leaves(
            streams, lengths, status
        )
        carry = self.ranks.get("seq", 1) > 1
        don_a = bool(donate_streams and carry)
        fa, fresh_a = self._get_fn(
            ("A", snames, lkey, don_a),
            lambda: self._build_phase_a(streams, lengths, don_a),
        )
        if carry:
            cnt, ovf, nmax, s_ext = fa(self._tab_j, s_j, l_j)
        else:
            cnt, ovf, nmax = fa(self._tab_j, s_j, l_j)
            s_ext = s_j
        # double buffer: with OUR phase A in the queue, flush the
        # previous batch's deferred reduction — its psum/verdict tail
        # executes behind the probe while this host thread blocks on
        # the survivor read below
        prev = self._take_pending()
        if prev is not None:
            prev.launch("dispatch")
            _shard_metrics().OVERLAPPED.inc(1)
        # the ONE host sync between phases: the survivor maxima that
        # size the probe to live work — the second blessed sync of the
        # jit-hygiene contract (tools/swarmlint). Single-controller
        # meshes read the per-rank lanes (R × 4 bytes, no collective);
        # multi-controller meshes read their pmax'd replicated scalar.
        if self.multiprocess:
            n_live = int(nmax)  # host-sync-ok: the blessed sharded 4-byte phase-A survivor scalar
        else:
            n_live = int(np.asarray(nmax).max())  # host-sync-ok: the blessed sharded phase-A survivor lanes (R × 4 bytes)
        budget = global_candidate_budget(
            self.candidate_k, len(self.meta.table_stream)
        )
        kc = fpc.survivor_bucket(n_live, budget)
        needs = self._reduce_needs_streams(streams)
        don_bp = bool((not needs) and (carry or donate_streams))
        fbp, fresh_bp = self._get_fn(
            ("Bp", snames, lkey, kc, don_bp),
            lambda: self._build_phase_b_probe(streams, lengths, kc, don_bp),
        )
        vb, ub = fbp(self._tab_j, self._rep_j, s_ext, l_j, cnt)
        self._note_launch([(fa, fresh_a), (fbp, fresh_bp)], t0)
        with self._counter_lock:
            self.last_compact = {
                "survivor_max": n_live,
                "verify_k": kc,
                "budget": budget,
            }
        m = _shard_metrics()
        m.SURVIVOR_MAX.set(n_live)
        self._dispatch_metrics(streams, saved_rounds=1)
        held = sum(int(getattr(a, "nbytes", 0)) for a in (vb, ub, ovf))
        r_streams = s_ext if needs else None
        handle = _PendingShard(
            self,
            lambda: self._launch_reduce(
                r_streams, l_j, st_j, vb, ub, ovf, full, donate_streams,
                snames, lkey,
            ),
            held,
        )
        if self.overlap:
            self._set_pending(handle)
        else:
            handle.launch("inline")
        return handle

    def collect(self, out):
        """Blocking half of the full-mode split: force the deferred
        reduction if no later dispatch flushed it, then one host read
        of the fused plane array (gathered host-local over DCN first
        on multi-process meshes), sliced into the engine's six
        outputs."""
        deferred = isinstance(out, _PendingShard)
        t0 = time.perf_counter()
        if deferred:
            out = out.force()
        if self.multiprocess:
            from jax.experimental import multihost_utils

            out = multihost_utils.global_array_to_host_local_array(
                out, self.mesh, P()
            )
        res = split_fused(self.db, np.asarray(out))
        if deferred:
            # launch-if-needed + device wait + the host read: how long
            # collect actually stalled on the reduction (≈0 when a
            # later dispatch already overlapped it)
            _shard_metrics().REDUCTION_WAIT.inc(time.perf_counter() - t0)
        return res

    # ------------------------------------------------------------------
    def match(self, streams: dict, lengths: dict, status, full: bool = False):
        """Synchronous convenience: :meth:`dispatch` + the blocking
        read. ``full=False`` returns the (t_value, t_unc, overflow)
        device tuple exactly as before the split."""
        out = self.dispatch(streams, lengths, status, full=full)
        if full:
            return self.collect(out)
        if isinstance(out, _PendingShard):
            out = out.force()
        if self.multiprocess:
            # global -> host-local (replicated) so every process can
            # read the full result; riding DCN once per batch
            from jax.experimental import multihost_utils

            out = tuple(
                multihost_utils.global_array_to_host_local_array(
                    o, self.mesh, P()
                )
                for o in out
            )
        return out
