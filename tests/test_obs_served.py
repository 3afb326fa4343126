"""The served path's batch timeline and padded work, measured inside the
program: ``RequestTrace.phase_ms`` tiling ``service_ms``, the engine's
row and posting-slot counters against a hand computation, program spans
on the profiler's timeline, the collector watch, and stage-named device
programs."""
import gc
import glob
import os
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import BackendDescriptor, DenseRerank, JaxBackend, Retrieve
from repro.core.descriptor import DEFAULT_CAPABILITIES
from repro.index import build_index
from repro.index.corpus import Corpus
from repro import obs
from repro.obs import MetricsRegistry, NOOP_SPAN, Tracer, hostpause
from repro.serve import PipelineServer, ServeConfig


def _row(Q, i):
    return {k: np.asarray(v)[i:i + 1] for k, v in Q.items()}


def _backend(index, dense=None, removed=("pruned_topk",)):
    caps = DEFAULT_CAPABILITIES - frozenset(removed)
    return JaxBackend(index, dense=dense, default_k=60,
                      descriptor=BackendDescriptor.default(caps))


#: the two benchmark cells' chain shapes: one sparse stage; a sparse stage
#: then a dense re-rank (the fused re-rank switched off), with and without
#: the per-stage barrier of stage timing
SHAPES = {
    "bm25": (lambda: Retrieve("BM25") % 10, ("pruned_topk",), False),
    "rerank": (lambda: (Retrieve("BM25", k=30) >> DenseRerank(alpha=0.3))
               % 10, ("pruned_topk", "fused_dense"), False),
    "rerank-staged": (lambda: (Retrieve("BM25", k=30)
                               >> DenseRerank(alpha=0.3)) % 10,
                      ("pruned_topk", "fused_dense"), True),
}


@pytest.mark.parametrize("shape", sorted(SHAPES))
def test_phase_ms_tiles_service_ms(small_ir, shape):
    make, removed, staged = SHAPES[shape]
    be = _backend(small_ir["index"], small_ir["backend"].dense, removed)
    cfg = ServeConfig.default(max_wait_ms=2.0)
    if staged:
        cfg = cfg.with_tracing(stages=True)
    server = PipelineServer(make(), be, cfg)
    n_stages = len(server.chain)
    server.warmup(small_ir["Q"])
    reqs = [server.submit_one(_row(small_ir["Q"], i)) for i in range(5)]
    server.pump()
    for r in reqs:
        r.wait(60)
    for r in reqs:
        tr = r.trace
        names = [n for n, _ in tr.phase_ms]
        assert abs(sum(ms for _, ms in tr.phase_ms) - tr.service_ms) < 1.0
        assert names[:3] == ["close", "cache_lookup", "assemble"]
        stages = [n[len("stage:"):] for n in names if n.startswith("stage:")]
        waits = [n[len("device_wait:"):] for n in names
                 if n.startswith("device_wait:")]
        assert len(stages) == n_stages
        # the final results' cache entries are made row by row with the
        # replies; an intermediate stage's go to the cache after its wait
        assert names[-2:] == [f"device_wait:{stages[-1]}", "reply"]
        assert names.count("cache_store") == n_stages - 1
        # one wait per stage: the barrier of stage timing, or the wait
        # before the intermediate result goes to the cache, and the last
        assert waits == stages
        for label in waits:
            assert (names.index(f"stage:{label}")
                    < names.index(f"device_wait:{label}"))
        if staged:
            assert [lb for lb, _ in tr.stage_ms] == stages
        else:
            assert tr.stage_ms == ()
        # every dispatch carried the 5 requests padded to rung 8
        assert tr.work["rows_live"] >= 5
        assert tr.work["rows_live"] * 3 == tr.work["rows_pad"] * 5
    # one work dict per batch, shared by its requests
    assert len({id(r.trace.work) for r in reqs}) == 1


def _hand_index():
    """300 documents over 6 terms: term 0 in every document, term 1 in the
    first 150, term 2 in the first 5, term 5 in document 7; terms 3 and 4
    in none.  Posting lists pad to 128-posting blocks."""
    docs = []
    for d in range(300):
        terms = [0] + ([1] if d < 150 else []) + ([2] if d < 5 else []) \
            + ([5] if d == 7 else [])
        docs.append(terms)
    doc_terms = np.concatenate([np.asarray(t, np.int32) for t in docs])
    doc_start = np.zeros(301, np.int64)
    np.cumsum([len(t) for t in docs], out=doc_start[1:])
    return build_index(Corpus(doc_terms, doc_start, 6), stop_df_fraction=1.0)


def _queries(rows, maxq=6):
    terms = np.full((len(rows), maxq), -1, np.int32)
    for i, r in enumerate(rows):
        terms[i, :len(r)] = r
    return {"qid": np.arange(len(rows), dtype=np.int32), "terms": terms,
            "weights": (terms >= 0).astype(np.float32)}


def test_padded_work_counts_match_the_hand_computation():
    be = _backend(_hand_index())
    lens = {0: 384, 1: 256, 2: 128, 3: 0, 4: 0, 5: 128}
    assert be.max_postings == 384
    np.testing.assert_array_equal(be.posting_lens, [lens[t] for t in range(6)])
    server = PipelineServer(Retrieve("BM25", k=10), be,
                            ServeConfig.default(max_wait_ms=2.0))
    rows = [[0, 2], [1, 3, 5], [4]]
    Q = _queries(rows)
    server.warmup(Q)
    reqs = [server.submit_one(_row(Q, i)) for i in range(3)]
    server.pump()
    for r in reqs:
        r.wait(60)
    live = sum(lens[t] for r in rows for t in r)             # 896
    work = reqs[0].trace.work
    # three live rows padded to rung 8; the five padding rows repeat the
    # last live row, and their gathers count as padding
    assert work == {"rows_live": 3, "rows_pad": 5, "slots_live": live,
                    "slots_pad": 8 * 6 * 384 - live}
    snap = be.engine.metrics.snapshot()
    assert "retrieve_posting_slots_total" in snap
    assert "engine_rows_total" in snap

    # outside the server every row a caller passes is live; terms on the
    # device are not read, so their slots are not counted
    eng = be.engine
    fn = lambda t, w: w.sum()
    before = be.work_counts()
    be.vmap_queries(fn, _queries(rows[:2]), key=("t", "host"), postings=True)
    assert eng.last_rows() == (2, 8)
    mid = be.work_counts()
    assert mid["rows_live"] - before["rows_live"] == 2
    assert mid["rows_pad"] - before["rows_pad"] == 6
    assert mid["slots_live"] - before["slots_live"] == 384 + 128 + 256 + 128
    Qd = {k: jnp.asarray(v) for k, v in _queries(rows[:2]).items()}
    be.vmap_queries(fn, Qd, key=("t", "device"), postings=True)
    after = be.work_counts()
    assert after["rows_live"] - mid["rows_live"] == 2
    assert after["slots_live"] == mid["slots_live"]
    assert after["slots_pad"] == mid["slots_pad"]
    # a stage that gathers no postings per term slot counts rows only
    be.vmap_queries(fn, _queries(rows[:2]), key=("t", "rows"))
    assert be.work_counts()["slots_live"] == mid["slots_live"]
    with eng.live_rows(1):
        be.vmap_queries(fn, _queries(rows[:2]), key=("t", "host"),
                        postings=True)
    last = be.work_counts()
    assert last["slots_live"] - mid["slots_live"] == 384 + 128


def _start_trace(log_dir) -> None:
    # host annotations only, as the benchmark traces (no Python tracer)
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 2
    jax.profiler.start_trace(str(log_dir), profiler_options=opts)


def _host_events(log_dir) -> list:
    from jax.profiler import ProfileData
    path = glob.glob(os.path.join(log_dir, "plugins", "profile", "*",
                                  "*.xplane.pb"))[0]
    pd = ProfileData.from_file(path)
    return [e.name for plane in pd.planes if plane.name.startswith("/host:")
            for line in plane.lines for e in line.events]


def test_served_batch_spans_reach_the_profiler(small_ir, tmp_path):
    be = _backend(small_ir["index"], small_ir["backend"].dense,
                  ("pruned_topk", "fused_dense"))
    pipe = (Retrieve("BM25", k=30) >> DenseRerank(alpha=0.3)) % 10
    server = PipelineServer(pipe, be, ServeConfig.default(max_wait_ms=2.0))
    server.warmup(small_ir["Q"])
    server.start()
    _start_trace(tmp_path)
    try:
        # an annotation is recorded when it ends: let the serving thread
        # begin (and end) waits inside the trace before the batch closes
        time.sleep(0.2)
        reqs = [server.submit_one(_row(small_ir["Q"], i)) for i in range(3)]
        for r in reqs:
            r.wait(60)
        gc.collect()
    finally:
        # the batch's span ends just after its last reply wakes the
        # caller: join the serving thread before the trace stops
        server.stop()
        jax.profiler.stop_trace()
    names = set(_host_events(str(tmp_path)))
    for want in ("serve.submit", "serve.batch_wait", "serve.batch",
                 "serve.cache_lookup", "serve.assemble", "serve.cache_store",
                 "serve.reply", "engine.dispatch", "host.gc"):
        assert want in names, want
    assert any(n.startswith("serve.stage:") for n in names)
    assert any(n.startswith("serve.device_wait:") for n in names)
    # the tracer itself was off: only the profiler saw the spans
    assert len(server.tracer) == 0


def test_disabled_tracer_spans_only_for_a_profiler(tmp_path):
    tr = Tracer(enabled=False)
    assert tr.span("x") is NOOP_SPAN
    assert obs.span("x") is NOOP_SPAN         # no global tracer installed
    _start_trace(tmp_path)
    try:
        with tr.span("probe.disabled", "t", n=3) as sp:
            assert sp is not NOOP_SPAN
            sp.set(reason="full")
        with obs.span("probe.helper", key="a,b"):
            pass
    finally:
        jax.profiler.stop_trace()
    assert len(tr) == 0
    names = _host_events(str(tmp_path))
    assert "probe.disabled" in names and "probe.helper" in names


def test_dropped_span_leaves_no_record_and_no_stack():
    tr = Tracer(enabled=True)
    with tr.span("outer") as outer:
        with tr.span("wait") as sp:
            sp.drop()
        with tr.span("inner") as inner:
            pass
    names = [r["name"] for r in tr.records()]
    assert names == ["inner", "outer"]
    assert inner.parent_id == outer.span_id


def test_batch_wait_span_carries_the_close_decision(small_ir):
    cfg = ServeConfig.default(max_wait_ms=2.0).with_observability(True)
    server = PipelineServer(Retrieve("BM25") % 10, small_ir["backend"], cfg)
    server.warmup(small_ir["Q"])
    server.tracer.clear()
    assert server.scheduler.next_batch() is None        # nothing queued
    reqs = [server.submit_one(_row(small_ir["Q"], i)) for i in range(3)]
    server.pump()
    for r in reqs:
        r.wait(60)
    recs = server.tracer.records()
    names = [r["name"] for r in recs]
    assert "sched.batch_close" not in names
    waits = [r for r in recs if r["name"] == "serve.batch_wait"]
    assert len(waits) == 1                    # the empty wait is dropped
    assert waits[0]["args"]["reason"] == "drain"
    assert waits[0]["args"]["size"] == 3 and waits[0]["args"]["rung"] == 8
    rids = {r.rid for r in reqs}             # the request tracks
    batch = [r for r in recs if r["name"] == "serve.batch"
             and r["tid"] not in rids]
    assert len(batch) == 1
    kids = {r["name"] for r in recs if r["parent"] == batch[0]["id"]}
    assert {"serve.close", "serve.cache_lookup", "serve.assemble",
            "serve.reply"} <= kids
    assert any(k.startswith("serve.stage:") for k in kids)
    assert any(k.startswith("serve.device_wait:") for k in kids)
    assert "serve.submit" in names
    # the retrospective request tree carries no stage children any more
    assert not any(r["name"].startswith("serve.stage:")
                   and r["tid"] in rids for r in recs)


def test_gc_inside_a_window_shows_in_the_ring_and_counter():
    owner = object()
    reg = MetricsRegistry()
    hostpause.register(reg)
    pause = reg.counter("host_gc_pause_seconds_total")
    before = pause.value(("2",))
    hostpause.install(owner)
    hostpause.install(owner)                  # idempotent
    assert gc.callbacks.count(hostpause._on_gc) == 1
    t0 = time.monotonic()
    gc.collect()
    t1 = time.monotonic()
    hostpause.remove(owner)
    # the callback goes with the last owner
    assert id(owner) not in hostpause._owners
    assert hostpause.installed() == bool(hostpause._owners)
    got = hostpause.pauses(t0, t1)
    assert got and all(g == 2 and d >= 0 for _, d, g in got)
    assert all(t0 <= s and s + d <= t1 for s, d, _ in got)
    assert pause.value(("2",)) - before == pytest.approx(
        sum(d for _, d, _ in got))
    assert "host_gc_pause_seconds_total" in reg.render_text()
    # outside the window nothing is left after clipping
    assert hostpause.pauses(t1 + 1.0, t1 + 2.0) == []


def test_gc_callback_takes_no_lock_a_collecting_thread_holds(small_ir):
    """A collection that starts on a thread holding the tracer's or a
    counter's lock must not wait for that lock: the callback takes none,
    and the collection still reaches the ring and the exported trace."""
    import threading
    cfg = ServeConfig.default().with_observability(True)
    server = PipelineServer(Retrieve("BM25") % 10, small_ir["backend"], cfg)
    server.start()
    done = threading.Event()
    counter = server.metrics.counter("host_gc_pause_seconds_total")

    def collect_holding_locks():
        with server.tracer._lock, counter._lock:
            t0 = time.monotonic()
            gc.collect()
            done.set()
        server._probe = (t0, time.monotonic())

    try:
        th = threading.Thread(target=collect_holding_locks, daemon=True)
        th.start()
        th.join(timeout=30)
        assert done.is_set(), "the collector's callback waited on a lock"
        server.tracer.clear()
        gc.collect()
        exported = server.trace_export()["traceEvents"]
    finally:
        server.stop()
    t0, t1 = server._probe
    assert hostpause.pauses(t0, t1)
    assert any(e["name"] == "host.gc" for e in exported)


def test_watch_owners_from_many_threads():
    """Owners installing and removing the watch from more threads than
    cores, with collections under way, leave exactly one callback while
    any owner holds it and none after the last."""
    import sys
    import threading
    n_threads = 2 * (os.cpu_count() or 1) + 2
    seen = []
    barrier = threading.Barrier(n_threads)

    def worker():
        owner = object()
        barrier.wait(timeout=30)
        for _ in range(20):
            hostpause.install(owner)
            seen.append(gc.callbacks.count(hostpause._on_gc))
            gc.collect(0)
            hostpause.remove(owner)

    old = sys.getswitchinterval()
    sys.setswitchinterval(1e-6)
    try:
        threads = [threading.Thread(target=worker) for _ in range(n_threads)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=60)
    finally:
        sys.setswitchinterval(old)
    assert not any(t.is_alive() for t in threads)
    assert len(seen) == 20 * n_threads and set(seen) == {1}
    assert hostpause.installed() == bool(hostpause._owners)


def test_server_start_installs_the_watch_and_stop_removes_it(small_ir):
    server = PipelineServer(Retrieve("BM25") % 10, small_ir["backend"],
                            ServeConfig.default())
    server.start()
    try:
        assert hostpause.installed()
        server.start()
        assert gc.callbacks.count(hostpause._on_gc) == 1
    finally:
        server.stop()
    assert id(server) not in hostpause._owners
    assert hostpause.installed() == bool(hostpause._owners)


def test_stage_programs_are_named_after_their_stage(small_ir):
    be = _backend(small_ir["index"], small_ir["backend"].dense)
    server = PipelineServer(Retrieve("BM25", k=20), be, ServeConfig.default())
    server.warmup(small_ir["Q"])
    key = (be.uid, server.chain[0].key())
    text = be.engine.compiled_text(key, be.engine.ladder[0])
    assert text is not None
    module = text.split("\n", 1)[0]
    assert module.startswith("HloModule jit_")
    assert "retrieve" in module.lower()
