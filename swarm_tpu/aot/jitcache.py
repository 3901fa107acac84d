"""AotJit: a ``jax.jit``-shaped wrapper with explicit per-shape
executable management.

``jax.jit`` compiles implicitly on first call of each argument-shape
set and offers no hook between "shape is new" and "compile it". This
wrapper makes that moment explicit so the AOT executable cache
(docs/AOT.md) can interpose: on a new shape it first consults the
:class:`~swarm_tpu.aot.store.AotClient` (prewarm pool → store fetch →
deserialize), and only COMPILES (``fn.lower(*args).compile()``) on a
genuine miss — publishing the fresh executable back to the store so
the next joining worker fetches instead.

Spy contract (the DeviceDB/ShardedMatcher compile-count spies,
docs/DEVICE_MATCH.md): ``_cache_size()`` counts LOCALLY COMPILED live
executables only — a deserialized load is counted by
``_fetched_size()`` instead, so ``tools/profile_device.py`` and the
width-bucket-sharing test stay honest on the fetch path. ``lower()``
and ``clear_cache()`` delegate/extend the wrapped jit, so the HLO
inspection path and the shape-churn eviction guard work unchanged.
"""

from __future__ import annotations

import pickle
import threading
import time
from typing import Optional

import jax

#: payload header — versioned so a wire change can never feed stale
#: bytes into the unpickler (the digest salts the same constant, so in
#: practice a mismatch is unreachable; the header is belt-and-braces
#: for artifacts handled outside the store)
_MAGIC = b"SWAOT2\x00"


def serialize_compiled(compiled) -> bytes:
    """One ``jax.stages.Compiled`` → portable bytes (the XLA
    executable image, the in/out pytree defs it was lowered with, and
    the ids of the devices it runs on, in its device-assignment order)."""
    from jax.experimental.serialize_executable import serialize

    payload, in_tree, out_tree = serialize(compiled)
    ids = [d.id for d in compiled.runtime_executable().local_devices()]
    return _MAGIC + pickle.dumps((payload, in_tree, out_tree, ids))


def load_compiled(blob: bytes):
    """Bytes → a callable loaded executable, bound to the devices it
    was compiled for (not every local device). Raises on any mismatch
    (header, unpickle, a device this process lacks) — callers treat
    every failure as a cache miss."""
    from jax.experimental.serialize_executable import deserialize_and_load

    if not blob.startswith(_MAGIC):
        raise ValueError("bad AOT artifact header")
    payload, in_tree, out_tree, ids = pickle.loads(blob[len(_MAGIC):])
    by_id = {d.id: d for d in jax.devices()}
    return deserialize_and_load(
        payload, in_tree, out_tree,
        execution_devices=[by_id[i] for i in ids],
    )


def aval_signature(tree) -> str:
    """Deterministic string of a pytree's structure + leaf avals
    (shape, dtype) — the shape half of the artifact digest. Weak types
    never arise on the dispatch path (every per-batch leaf is staged
    through ``jnp.asarray`` of host numpy), so shape+dtype is the full
    aval story here."""
    leaves, treedef = jax.tree_util.tree_flatten(tree)
    parts = [str(treedef)]
    for leaf in leaves:
        shape = tuple(getattr(leaf, "shape", ()))
        dtype = getattr(leaf, "dtype", None)
        parts.append(f"{dtype}[{','.join(map(str, shape))}]")
    return ";".join(parts)


class _Entry:
    __slots__ = ("compiled", "fetched")

    def __init__(self, compiled, fetched: bool):
        self.compiled = compiled
        self.fetched = fetched


class AotJit:
    """Explicitly managed twin of ``jax.jit(fun, ...)``.

    Presents the slice of the jit wrapper surface the matchers use —
    ``__call__``, ``lower``, ``_cache_size``, ``clear_cache`` — plus
    ``_fetched_size`` (deserialized loads, counted distinctly from
    compiles). Executables are keyed by (static args repr, aval
    signature); the LRU is bounded at ``cap`` (the same generous
    shape-churn guard DeviceDB applies — jit never evicts either, and
    adversarial shape variety must not grow RSS without bound).

    Thread-safe: the matchers already serialize launches under their
    compile-spy locks, but ``profile_phases``/tests may call from
    other threads — materialization runs under ``_lock``.
    """

    def __init__(
        self,
        fun,
        kernel_id: str,
        salt: str = "",
        client=None,
        static_argnums: tuple = (),
        donate_argnums: tuple = (),
        cap: int = 32,
    ):
        self._jit = jax.jit(
            fun,
            static_argnums=static_argnums,
            donate_argnums=donate_argnums,
        )
        self._static = tuple(sorted(int(i) for i in static_argnums))
        self._kernel_id = kernel_id
        # the full trace salt: caller context + this wrapper's own
        # static/donate configuration (two wrappers over one fun with
        # different donation lower DIFFERENT programs)
        self._salt = (
            f"{salt}|static={self._static}"
            f"|donate={tuple(sorted(int(i) for i in donate_argnums))}"
        )
        self._client = client
        self._cap = int(cap)
        self._lock = threading.RLock()  # guards: _exe (reads)
        self._exe: dict = {}

    # -- spy surface (jit-compatible) ---------------------------------
    def _cache_size(self) -> int:
        with self._lock:
            return sum(1 for e in self._exe.values() if not e.fetched)

    def _fetched_size(self) -> int:
        with self._lock:
            return sum(1 for e in self._exe.values() if e.fetched)

    def clear_cache(self) -> None:
        with self._lock:
            self._exe.clear()
        self._jit.clear_cache()

    def lower(self, *args, **kwargs):
        return self._jit.lower(*args, **kwargs)

    # -- call path -----------------------------------------------------
    def _split(self, args):
        """(static values, dynamic args) by position."""
        static = tuple(args[i] for i in self._static)
        dyn = tuple(
            a for i, a in enumerate(args) if i not in self._static
        )
        return static, dyn

    def __call__(self, *args):
        static, dyn = self._split(args)
        akey = (repr(static), aval_signature(dyn))
        with self._lock:
            entry = self._exe.get(akey)
            if entry is None:
                entry = self._materialize(akey, args, static, dyn)
                while len(self._exe) >= self._cap:
                    self._exe.pop(next(iter(self._exe)))
                self._exe[akey] = entry
        # the Compiled call itself is thread-safe and runs outside any
        # serialization concern: static args are baked into the
        # executable, only the dynamic args are passed
        return entry.compiled(*dyn)

    # requires-lock: _lock (only called from __call__'s locked block)
    def _materialize(self, akey, args, static, dyn) -> _Entry:
        client = self._client
        digest = None
        if client is not None:
            digest = client.key_digest(
                self._kernel_id, self._salt, akey[0], akey[1]
            )
            loaded = client.fetch_loaded(digest)
            if loaded is not None:
                return _Entry(loaded, True)
        t0 = time.perf_counter()
        with self._compile_ctx():
            compiled = self._jit.lower(*args).compile()
        dt = time.perf_counter() - t0
        if client is not None:
            client.note_compile_seconds(dt)
            client.publish(
                digest,
                {"k": self._kernel_id, "s": akey[0], "a": akey[1]},
                compiled,
            )
        return _Entry(compiled, False)

    def _compile_ctx(self):
        """Publisher-path compiles bypass jax's PERSISTENT compilation
        cache: an executable that was itself deserialized from that
        cache re-serializes into a non-self-contained image (XLA:CPU
        "Function ... not found" when the loaded copy runs — still so
        on jax 0.9.0), which would poison the store with unloadable
        artifacts. A fresh compile serializes cleanly; non-publishing
        clients keep the cache (their executables never leave the
        process).

        ``is_cache_used`` memoizes its decision per process, so the
        flag flip is paired with ``reset_cache()`` on both edges (a
        concurrent compile on another thread at most loses one cache
        lookup — perf, not correctness)."""
        import contextlib

        client = self._client
        if client is None or not client.publish_enabled:
            return contextlib.nullcontext()

        @contextlib.contextmanager
        def no_persistent_cache():
            from jax.experimental.compilation_cache import compilation_cache

            was = jax.config.jax_enable_compilation_cache
            jax.config.update("jax_enable_compilation_cache", False)
            compilation_cache.reset_cache()
            try:
                yield
            finally:
                jax.config.update("jax_enable_compilation_cache", was)
                compilation_cache.reset_cache()

        return no_persistent_cache()

    def preload(self, args: tuple, compiled, fetched: bool = True) -> None:
        """Register a ready executable for ``args``' shape (tests and
        tooling; the production path pools by digest in the client)."""
        static, dyn = self._split(args)
        with self._lock:
            self._exe[(repr(static), aval_signature(dyn))] = _Entry(
                compiled, fetched
            )


def fetched_size_of(fn) -> int:
    """``_fetched_size`` of a jit-or-AotJit wrapper (plain jit has no
    fetch path → 0)."""
    getter = getattr(fn, "_fetched_size", None)
    return int(getter()) if getter is not None else 0
