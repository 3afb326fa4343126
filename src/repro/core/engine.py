"""Device-sharded, shape-bucketed query execution engine.

Replaces the backend's ad-hoc sequential chunked-``vmap`` loop with a
serving-grade execution layer (ROADMAP: "heavy traffic from millions of
users"), built from three mechanisms:

* **Data-parallel sharding** — the query axis of every stage is sharded
  across all local devices through a 1-D ``("data",)`` mesh
  (:func:`repro.launch.mesh.make_query_mesh`, the serving counterpart of
  the training meshes).  Per-query stage functions are embarrassingly
  parallel along the batch, so each device runs them on its own rows under
  ``shard_map`` (:func:`data_parallel`) with zero collectives.

* **Bucket ladder** — query batches are padded up to a small fixed ladder
  of chunk sizes and executed through a persistent jit cache keyed by
  ``(stage key, bucket, trailing shapes)``.  Recompilation is therefore
  bounded by ``len(ladder)`` per stage/signature instead of scaling with
  the number of distinct query-set sizes an Experiment presents.

* **Async dispatch** — chunks are enqueued without ever blocking (JAX async
  dispatch overlaps host-side dispatch of chunk ``i+1`` with device compute
  of chunk ``i``, and chunks spread across devices run concurrently).  The
  engine never calls ``block_until_ready`` itself; the planner inserts an
  explicit :meth:`barrier` only at stage boundaries it needs timed
  (``ExperimentPlan.execute(record=...)``), so untimed plan executions
  pipeline across stage *and* pipeline boundaries.

A chunk cache makes stage-to-stage handoff cheap: when stage ``i+1``
consumes an array stage ``i`` produced, the engine reuses the per-chunk
sharded pieces directly instead of re-slicing, re-padding, and re-sharding
the concatenated result.
"""
from __future__ import annotations

import contextlib
import dataclasses
import re
import threading
import weakref
from typing import Any, Callable, Sequence

import jax
import jax.extend.core as jex_core
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.common import LRU, select_ladder_bucket
from repro.launch.mesh import make_query_mesh
from repro.obs.metrics import MetricsRegistry
from repro.obs.tracing import NOOP_TRACER


def _key_label(key) -> str:
    """Short printable form of a jit-cache key for trace events (full keys
    embed content digests and param tuples — too long for a span arg)."""
    s = str(key)
    return s if len(s) <= 96 else s[:93] + "..."


def _program_name(label: str) -> str:
    """A stage program's function name, from its key label: the compiled
    module is ``jit_<name>``, so the device trace's "XLA Modules" line
    attributes device time to the stage."""
    return re.sub(r"\W+", "_", label).strip("_")[:64] or "stage"


def default_bucket_ladder(n_devices: int, *, base: int = 8,
                          steps: Sequence[int] = (1, 2, 4)) -> tuple[int, ...]:
    """Geometric bucket ladder, every bucket a multiple of the device count
    (shards must be even).  ``(8, 16, 32)`` on <=8 devices — the largest
    bucket is the steady-state chunk; small query sets pad only up to the
    smallest covering bucket."""
    quantum = max(int(n_devices), base)
    ladder = []
    for s in steps:
        b = s * quantum
        b = -(-b // n_devices) * n_devices      # round up to a device multiple
        if b not in ladder:
            ladder.append(b)
    return tuple(sorted(ladder))


class data_parallel:
    """``jit(vmap(fn))`` with the query axis split over the mesh's ``data``
    axis by ``shard_map``: each device runs the per-query function on its
    own rows.  Explicit SPMD rather than GSPMD partitioning, because a
    Pallas (Mosaic) kernel inside ``fn`` cannot be partitioned
    automatically.

    The arrays ``fn`` closes over (index, embeddings) enter the program as
    replicated arguments, never as constants: embedded, each compiled rung
    would carry its own copy of the index in its executable — gigabytes at
    Robust scale, slow to compile and resident on the device once per
    program.  ``place`` puts each such array on the mesh (default: as is).
    ``name`` names the jitted function (and so the compiled module).
    Callable like the jitted function; :meth:`lower` lowers it."""

    def __init__(self, fn, mesh, place: Callable | None = None,
                 name: str | None = None):
        self.fn, self.mesh = fn, mesh
        self.place = place if place is not None else (lambda x: x)
        self.name = name
        self._programs: dict = {}

    def _program(self, args):
        sig = tuple((tuple(a.shape[1:]), jnp.dtype(a.dtype)) for a in args)
        prog = self._programs.get(sig)
        if prog is None:
            closed, out_shape = jax.make_jaxpr(self.fn, return_shape=True)(
                *[jax.ShapeDtypeStruct(s, d) for s, d in sig])
            jaxpr, out_tree = closed.jaxpr, jax.tree.structure(out_shape)

            def one(consts, *xs):
                flat = jex_core.jaxpr_as_fun(
                    jex_core.ClosedJaxpr(jaxpr, consts))(*xs)
                return jax.tree.unflatten(out_tree, flat)

            if self.name:
                one.__name__ = one.__qualname__ = self.name
            n = len(args)
            f = jax.jit(jax.shard_map(
                jax.vmap(one, in_axes=(None,) + (0,) * n), mesh=self.mesh,
                in_specs=(P(),) + (P("data"),) * n, out_specs=P("data"),
                check_vma=False))
            prog = self._programs[sig] = (
                f, [self.place(c) for c in closed.consts])
        return prog

    def __call__(self, *args):
        f, consts = self._program(args)
        return f(consts, *args)

    def lower(self, *args):
        f, consts = self._program(args)
        return f.lower(consts, *args)


def merge_shard_topk(parts, *, k: int):
    """Cross-shard top-k merge of per-shard ``(docids, scores)`` results
    (each ``[nq, k_s]``, global doc ids, invalid entries ``-1``/``-inf``).

    Host-side with *streaming-merge semantics*: the stable descending sort
    keeps the first-seen entry among score ties, and because shards are
    contiguous ascending doc-id ranges presented in shard order (and
    ``lax.top_k`` inside each shard already breaks ties to the lowest
    local = global id), ties resolve to the lowest global doc id — exactly
    the single-index oracle's rule, making the merge bit-identical to
    ``dense_retrieve_exact`` on the unsharded index."""
    docs = np.concatenate([np.asarray(d) for d, _ in parts], axis=1)
    vals = np.concatenate([np.asarray(v) for _, v in parts], axis=1)
    if docs.shape[1] < k:
        raise ValueError(f"merge width {docs.shape[1]} < k={k}")
    sel = np.argsort(-vals, axis=1, kind="stable")[:, :k]
    rows = np.arange(docs.shape[0])[:, None]
    return docs[rows, sel], vals[rows, sel]


@dataclasses.dataclass(frozen=True)
class StageProgram:
    """The engine's unit of execution: a per-query function plus the key
    that names its persistent jit-cache entry.

    The key must fully determine ``fn``'s behaviour — that is the soundness
    contract the jit cache relies on: two programs presenting the same key
    may share one compiled executable.  A typed-IR content key (``Op.key()``)
    embeds every static param and stateful-stage version marker but NOT the
    backend's array contents (index, embeddings), which ``fn`` closes over —
    so ``JaxBackend.vmap_queries`` scopes the key by a per-backend uid
    before it reaches the engine.  ``key=None`` marks an anonymous program
    that compiles fresh and stays out of the cache.
    """
    key: Any
    fn: Callable


class ShardedQueryEngine:
    """Executes per-query stage functions over the query axis: sharded
    across devices, padded to bucketed shapes, dispatched asynchronously.

    The jit cache requires that a stage function's behaviour is fully
    determined by its ``key`` (plus the backend the engine serves): two
    calls presenting the same key reuse the first call's compiled fn.
    ``Transformer.key()`` provides exactly this for pipeline stages.
    """

    def __init__(self, mesh=None, *, ladder: Sequence[int] | None = None,
                 max_devices: int | None = None,
                 max_jit_entries: int | None = 512,
                 max_chunk_entries: int | None = 64,
                 registry: MetricsRegistry | None = None):
        self.mesh = mesh if mesh is not None else make_query_mesh(
            max_devices=max_devices)
        # on a 2-D (query x doc-shard) mesh only the "data" axis carries
        # the query batch; the "docs" axis groups devices by document shard
        self.n_devices = int(dict(self.mesh.shape).get(
            "data", self.mesh.devices.size))
        self.ladder = (tuple(sorted(int(b) for b in ladder)) if ladder
                       else default_bucket_ladder(self.n_devices))
        if any(b % self.n_devices for b in self.ladder):
            raise ValueError(
                f"bucket ladder {self.ladder} not divisible by device count "
                f"{self.n_devices}")
        self._sharding = NamedSharding(self.mesh, P("data"))
        #: (stage key, bucket, trailing signature) -> jitted vmapped fn.
        #: LRU-bounded: a long-lived server touches unboundedly many stage
        #: keys over its lifetime, and an unbounded dict pins every compiled
        #: executable forever.  The ladder still bounds recompiles per
        #: *resident* stage; an evicted entry recompiles on next use.
        self._jit_cache: LRU = LRU(max_jit_entries)
        #: (stage key, trailing signature) -> number of buckets compiled;
        #: the bucket ladder bounds every entry by len(self.ladder) while
        #: the stage's entries stay resident in the jit cache.  Bounded for
        #: the same stage-key-diversity reason as the jit cache itself;
        #: the lossless total lives in ``n_compiles_total``.
        self.compiles: LRU = LRU(None if max_jit_entries is None
                                 else 4 * max_jit_entries)
        #: id(full array) -> (weakref, chunk plan, [sharded pieces]).
        #: LRU-bounded for the same reason (entries also die eagerly with
        #: their source array via the weakref callback).
        self._chunk_cache: LRU = LRU(max_chunk_entries)
        #: id(closed-over array) -> (weakref, copy replicated on the mesh):
        #: one placement per index array however many programs close over
        #: it (dies with its source array, like the chunk cache)
        self._replicas: dict = {}
        self._replicated = NamedSharding(self.mesh, P())
        # counters are registry series (one source of truth for stats());
        # tracer/recorder are attached by the serving layer or the
        # descriptor's observability flag — NOOP/None by default, so the
        # disabled hot path is one attribute check
        self.metrics = registry if registry is not None else MetricsRegistry()
        self._m_dispatches = self.metrics.counter(
            "engine_dispatches_total", "chunk/pinned program dispatches")
        self._m_compiles = self.metrics.counter(
            "engine_compiles_total", "jit compilations by cause", ("cause",))
        for c in ("cold_rung", "ladder_miss", "pinned"):
            self._m_compiles.touch((c,))
        self._m_chunk = self.metrics.counter(
            "engine_chunk_cache_total", "validated chunk-cache lookups",
            ("result",))
        for r in ("hit", "miss"):
            self._m_chunk.touch((r,))
        self.metrics.gauge(
            "engine_jit_cache_entries",
            "resident compiled executables").set_fn(lambda: len(self._jit_cache))
        # padded work: rows each dispatch carried (live) or padded to its
        # rung (pad)
        self._m_rows = self.metrics.counter(
            "engine_rows_total", "rows dispatched, live or padding",
            ("kind",))
        for kind in ("live", "pad"):
            self._m_rows.touch((kind,))
        self._tls = threading.local()
        self.tracer = NOOP_TRACER
        self.recorder = None
        #: bucket -> EWMA of measured batch service seconds, fed back by the
        #: serving layer (``note_service_time``) after each executed
        #: micro-batch; the deadline-aware scheduler prices its
        #: shed-before-execute decisions off these observations
        self._service_ewma: dict[int, float] = {}
        self._service_alpha = 0.2

    # -- observability ------------------------------------------------------
    def attach_observability(self, tracer=None, recorder=None) -> None:
        """Point the engine's compile/dispatch events at a tracer and/or
        flight recorder (the serving layer calls this when its config opts
        in; several servers sharing one engine share the last attachment)."""
        if tracer is not None:
            self.tracer = tracer
        if recorder is not None:
            self.recorder = recorder

    @contextlib.contextmanager
    def live_rows(self, n: int):
        """Declare, for this thread's dispatches inside the block, that only
        the first ``n`` rows of each batch are live: a caller that pads its
        batch to a rung itself (the server) says where its padding starts.
        Without it every row a caller passes counts as live."""
        prev = getattr(self._tls, "live", None)
        self._tls.live = int(n)
        try:
            yield
        finally:
            self._tls.live = prev

    def row_counts(self) -> dict:
        """Totals of ``engine_rows_total``: ``rows_live``, ``rows_pad``."""
        r = self._m_rows
        return {"rows_live": int(r.value(("live",))),
                "rows_pad": int(r.value(("pad",)))}

    def last_rows(self) -> tuple[int, int]:
        """``(live, dispatched)`` rows of this thread's last
        :meth:`run`/:meth:`submit_chunk`; ``(0, 0)`` before the first."""
        return getattr(self._tls, "rows", (0, 0))

    def _count_rows(self, plan) -> None:
        live = getattr(self._tls, "live", None)
        nq = plan[-1][0] + plan[-1][1]
        live = nq if live is None else max(0, min(live, nq))
        rows = sum(b for _, _, b in plan)
        self._m_rows.inc(live, ("live",))
        self._m_rows.inc(rows - live, ("pad",))
        self._tls.rows = (live, rows)

    @property
    def n_compiles_total(self) -> int:
        return int(sum(self._m_compiles.series().values()))

    @property
    def n_dispatches(self) -> int:
        return int(self._m_dispatches.value())

    @property
    def n_chunk_cache_hits(self) -> int:
        return int(self._m_chunk.value(("hit",)))

    @property
    def n_chunk_cache_misses(self) -> int:
        return int(self._m_chunk.value(("miss",)))

    def _note_compile(self, cause: str, key, bucket) -> None:
        """Count one jit compilation and emit its attributed-cause event:
        ``cold_rung`` (first rung for a never-seen stage/signature),
        ``ladder_miss`` (additional rung for a known stage, or a re-compile
        after LRU eviction), ``pinned`` (fixed-shape decode program)."""
        self._m_compiles.inc(1, (cause,))
        self.tracer.event("engine.jit_compile", "engine", cause=cause,
                          bucket=bucket, key=_key_label(key))
        if self.recorder is not None:
            self.recorder.record("recompile", cause=cause, bucket=bucket,
                                 key=_key_label(key))

    # -- chunk planning -----------------------------------------------------
    def chunk_plan(self, nq: int) -> tuple[tuple[int, int, int], ...]:
        """Split ``nq`` queries into ``(start, n, bucket)`` chunks: full
        chunks of the largest bucket plus one tail padded to the smallest
        covering ladder bucket."""
        if nq <= 0:
            raise ValueError("empty query batch")
        mx = self.ladder[-1]
        plan, s = [], 0
        while nq - s > mx:
            plan.append((s, mx, mx))
            s += mx
        rem = nq - s
        plan.append((s, rem, self.select_bucket(rem)))
        return tuple(plan)

    # -- chunk extraction / caching ----------------------------------------
    def _remember(self, full, plan, pieces) -> None:
        # only cache pieces already laid out the way stage inputs are
        # (P("data")): a differently-sharded piece would silently recompile
        # the consumer jit and break the ladder's recompile bound.  A piece
        # that IS the full array (single exact-fit chunk) would make the
        # entry self-referential and immortal — nothing to cache there.
        if any(p is full for p in pieces):
            return
        if not all(getattr(p, "sharding", None) == self._sharding
                   for p in pieces):
            return
        key = id(full)
        try:
            # death callback evicts the entry, so the strong refs to the
            # sharded pieces never outlive the array they were cut from
            ref = weakref.ref(
                full, lambda _, k=key: self._chunk_cache.pop(k, None))
        except TypeError:
            return                                # non-weakrefable leaf
        self._chunk_cache.put(key, (ref, plan, pieces))

    def _pieces(self, arr, plan):
        """Per-chunk sharded pieces of ``arr``, padded to their buckets.
        Arrays the engine itself produced hit the chunk cache and skip the
        slice/pad/device_put entirely."""
        ent = self._chunk_cache.get(id(arr))
        if ent is not None and ent[0]() is arr and ent[1] == plan:
            self._m_chunk.inc(1, ("hit",))
            return ent[2]
        self._m_chunk.inc(1, ("miss",))
        pad_mod = np if isinstance(arr, np.ndarray) else jnp
        pieces = []
        for start, n, bucket in plan:
            piece = arr[start:start + n]
            if n < bucket:
                piece = pad_mod.pad(
                    piece, ((0, bucket - n),) + ((0, 0),) * (piece.ndim - 1))
            pieces.append(jax.device_put(piece, self._sharding))
        self._remember(arr, plan, pieces)
        return pieces

    def _place(self, x):
        """A stage's closed-over array as a program argument: as is when
        it already lives on exactly the mesh's devices, else replicated
        over them once, while the array lives."""
        devices = set(self.mesh.devices.flat)
        if isinstance(x, jax.Array) and x.sharding.device_set == devices:
            return x
        ent = self._replicas.get(id(x))
        if ent is not None and ent[0]() is x:
            return ent[1]
        rep = jax.device_put(x, self._replicated)
        try:
            ref = weakref.ref(
                x, lambda _, k=id(x): self._replicas.pop(k, None))
        except TypeError:
            return rep                            # non-weakrefable constant
        self._replicas[id(x)] = (ref, rep)
        return rep

    # -- the jit cache ------------------------------------------------------
    def _jitted(self, key, fn, bucket: int, sig) -> Callable:
        jk = (key, bucket, sig)
        vf = self._jit_cache.get(jk)
        if vf is None:
            vf = data_parallel(fn, self.mesh, self._place,
                               name=_program_name(_key_label(key)))
            self._jit_cache.put(jk, vf)
            ck = (key, sig)
            prior = self.compiles.get(ck, 0) or 0
            self.compiles.put(ck, prior + 1)
            self._note_compile("cold_rung" if prior == 0 else "ladder_miss",
                              key, bucket)
        return vf

    def compiled_text(self, key, bucket: int) -> str | None:
        """Optimised HLO of the compiled jit entry for stage ``key`` at
        ``bucket`` — lowered again from the entry's argument signature, so
        a caller can see what the device runs (e.g. whether a Pallas kernel
        is in it as a ``tpu_custom_call``).  None if no such entry."""
        for (k, b, sig), vf in self._jit_cache.items():
            if k == key and b == bucket:
                args = [jax.ShapeDtypeStruct((b,) + shape, jnp.dtype(dtype),
                                             sharding=self._sharding)
                        for shape, dtype in sig]
                return vf.lower(*args).compile().as_text()
        return None

    def max_compiles_per_stage(self) -> int:
        return max(self.compiles.values(), default=0)

    def total_compiles(self) -> int:
        """Total jit compilations across all stages/buckets, monotone even
        when per-stage counter entries age out — the serving layer
        snapshots this at warm-up to assert zero steady-state
        recompilation."""
        return self.n_compiles_total

    # -- execution ----------------------------------------------------------
    @staticmethod
    def _args_of(Q, extra) -> tuple:
        return ((Q["terms"], Q["weights"]) if Q is not None else ()) + extra

    def select_bucket(self, n: int) -> int:
        """Smallest ladder bucket covering an ``n``-query micro-batch — the
        serving scheduler's batch-closure rule (a batch at the largest
        bucket is 'full'; anything smaller pads up to its covering rung).
        One shared implementation (:func:`repro.common.select_ladder_bucket`)
        backs both this and the scheduler's copy, so the ladder policy
        cannot drift between them."""
        return select_ladder_bucket(self.ladder, n)

    # -- service-time feedback ----------------------------------------------
    def note_service_time(self, bucket: int, seconds: float) -> None:
        """Record one measured micro-batch service time for ``bucket``
        (EWMA).  Fed by the serving layer after each executed batch; the
        scheduler's shedding math and the bench's capacity accounting read
        the estimates back via :meth:`service_time_estimate`."""
        prev = self._service_ewma.get(bucket)
        a = self._service_alpha
        self._service_ewma[bucket] = (seconds if prev is None
                                      else (1.0 - a) * prev + a * seconds)

    def service_time_estimate(self, bucket: int | None = None) -> float | None:
        """EWMA service seconds for ``bucket`` (falling back to the nearest
        observed rung), or the worst observed rung when ``bucket`` is None.
        None until the first observation."""
        if not self._service_ewma:
            return None
        if bucket is None:
            return max(self._service_ewma.values())
        if bucket in self._service_ewma:
            return self._service_ewma[bucket]
        near = min(self._service_ewma,
                   key=lambda b: (abs(b - bucket), b))
        return self._service_ewma[near]

    def run(self, program: StageProgram, Q, *extra):
        """Execute one IR stage program over the query axis: vmap
        ``program.fn(terms, weights, *extra_i)`` (or ``fn(*extra_i)`` when Q
        is None) sharded/bucketed/async, with ``program.key`` naming the
        persistent jit-cache entry.  Returns full (concatenated, trimmed)
        arrays; dispatch is fully asynchronous.  Any batch that fits the
        largest bucket — every serving micro-batch — IS a
        :meth:`submit_chunk` call; bigger batches chunk-plan and loop the
        same single-dispatch primitive."""
        args = self._args_of(Q, extra)
        nq = int(args[0].shape[0])
        if 0 < nq <= self.ladder[-1]:
            return self.submit_chunk(program, Q, *extra)
        return self._run_plan(program, args, self.chunk_plan(nq))

    def submit_chunk(self, program: StageProgram, Q, *extra,
                     bucket: int | None = None):
        """Serving entry point: dispatch ONE micro-batch (``n`` <= largest
        bucket) as a single padded chunk, asynchronously — no whole-batch
        chunk planning.  ``bucket`` pins the ladder rung (defaults to
        :meth:`select_bucket`); returns trimmed full arrays like
        :meth:`run`."""
        args = self._args_of(Q, extra)
        nq = int(args[0].shape[0])
        if bucket is None:
            bucket = self.select_bucket(nq)
        elif bucket not in self.ladder or nq > bucket:
            raise ValueError(f"bucket {bucket} not a ladder rung covering "
                             f"{nq} queries (ladder {self.ladder})")
        return self._run_plan(program, args, ((0, nq, bucket),))

    def run_pinned(self, program: StageProgram, *args,
                   donate_argnums: tuple = ()):
        """Execute a *pinned-shape* program (the prefill / decode-step
        bodies of the generate stage) through the persistent jit cache: no
        vmap, no bucket padding — the caller guarantees every array shape
        is drawn from a finite, warmed set (decode batch = a ladder rung,
        prompt/decode lengths fixed by the stage's static params).  The
        entry is keyed ``(key, "pinned", leaf shapes)`` in the same LRU and
        counted by the same compile counters as the bucketed entries, so
        the recompiles-since-warmup invariant sees pinned programs exactly
        like vmapped ones.  ``donate_argnums`` lets a decode step donate
        its KV-cache buffers (the cache is threaded, never reread)."""
        leaves = jax.tree.leaves(args)
        sig = tuple((tuple(getattr(x, "shape", ())),
                     str(getattr(x, "dtype", type(x).__name__)))
                    for x in leaves)
        self._m_dispatches.inc()
        if program.key is None:
            return jax.jit(program.fn, donate_argnums=donate_argnums)(*args)
        jk = (program.key, "pinned", sig)
        vf = self._jit_cache.get(jk)
        if vf is None:
            vf = jax.jit(program.fn, donate_argnums=donate_argnums)
            self._jit_cache.put(jk, vf)
            ck = (program.key, "pinned")
            self.compiles.put(ck, (self.compiles.get(ck, 0) or 0) + 1)
            self._note_compile("pinned", program.key, None)
        return vf(*args)

    def _run_plan(self, program: StageProgram, args, plan):
        key, fn = program.key, program.fn
        sig = tuple((tuple(a.shape[1:]), str(a.dtype)) for a in args)
        pieces = [self._pieces(a, plan) for a in args]
        anon_vf = data_parallel(fn, self.mesh, self._place) if key is None else None
        outs = []
        for i, (start, n, bucket) in enumerate(plan):
            # keyless calls compile fresh and stay out of the persistent
            # cache (an id()-keyed entry could never be reused anyway)
            vf = anon_vf if key is None else self._jitted(key, fn, bucket, sig)
            # span covers host-side dispatch only — JAX dispatch is async,
            # so device compute completes after the span closes
            with self.tracer.span("engine.dispatch", "engine", bucket=bucket,
                                  n=n, key=_key_label(key)):
                outs.append(vf(*[p[i] for p in pieces]))
            self._m_dispatches.inc()
        self._count_rows(plan)
        full = self._materialize(outs, plan)
        self._remember_outputs(full, outs, plan)
        return full

    def map_queries(self, fn, Q, *extra, key=None):
        """Compatibility wrapper over :meth:`run`."""
        return self.run(StageProgram(key=key, fn=fn), Q, *extra)

    def run_doc_sharded(self, programs: Sequence[StageProgram], Q, *extra,
                        k: int):
        """Doc-axis sharded top-k: run one StageProgram per document shard
        (each closing over its contiguous shard and emitting *global* doc
        ids, e.g. built over ``index.dense.shard_dense_index``), then merge
        the per-shard ``(docids, scores)`` across shards on the host with
        :func:`merge_shard_topk`.  Per-shard dispatch stays fully async;
        the merge is the one synchronisation point."""
        parts = [self.run(p, Q, *extra) for p in programs]
        self.barrier(parts)
        return merge_shard_topk(parts, k=k)

    def _materialize(self, outs, plan):
        _, n_tail, b_tail = plan[-1]
        if len(outs) == 1:
            if n_tail == b_tail:
                return outs[0]
            return jax.tree.map(lambda x: x[:n_tail], outs[0])

        def cat(*xs):
            xs = list(xs)
            if n_tail != b_tail:
                xs[-1] = xs[-1][:n_tail]
            return jnp.concatenate(xs, 0)

        return jax.tree.map(cat, *outs)

    def _remember_outputs(self, full, outs, plan) -> None:
        """Seed the chunk cache so the next stage consuming ``full`` reuses
        the already-sharded chunk outputs instead of re-slicing."""
        flat_full, _ = jax.tree.flatten(full)
        flat_outs = [jax.tree.flatten(o)[0] for o in outs]
        for li, leaf in enumerate(flat_full):
            self._remember(leaf, plan, [fo[li] for fo in flat_outs])

    # -- barriers / reporting ----------------------------------------------
    def barrier(self, tree):
        """Block until every array in ``tree`` is computed.  The engine
        itself never blocks — this is for the planner's timed stage
        boundaries and for benchmark harnesses."""
        jax.block_until_ready(tree)
        return tree

    def cache_info(self) -> dict:
        """Sizes/bounds/hit counters of the engine's two bounded caches —
        surfaced by ``PipelineServer.stats()`` so a long-lived server's
        memory profile is observable.  ``chunk`` hit/miss counts are the
        engine's *validated* counters (an LRU entry whose weakref died or
        whose chunk plan changed counts as a miss)."""
        jit = self._jit_cache.info()
        chunk = self._chunk_cache.info()
        chunk["hits"] = self.n_chunk_cache_hits
        chunk["misses"] = self.n_chunk_cache_misses
        return {"jit": jit, "chunk": chunk}

    def stats(self) -> dict:
        return {
            "devices": self.n_devices,
            "ladder": list(self.ladder),
            "dispatches": self.n_dispatches,
            "compiled_variants": self.n_compiles_total,
            "max_compiles_per_stage": self.max_compiles_per_stage(),
            "chunk_cache_hits": self.n_chunk_cache_hits,
            "chunk_cache_misses": self.n_chunk_cache_misses,
            "cache_info": self.cache_info(),
            "service_ms_ewma": {b: round(1000.0 * s, 3)
                                for b, s in sorted(self._service_ewma.items())},
        }
