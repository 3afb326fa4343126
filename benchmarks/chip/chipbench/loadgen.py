"""Load drivers: open loop and closed loop.

One driver thread, few threads in all: the open loop sends on a schedule
from the main thread; the closed loop keeps ``clients`` requests in
flight from the main thread (each completion sends that client's next
request); the server runs in its own thread (``PipelineServer.start``).

The open loop is a copy of ``benchmarks/serve_bench._run_level``: latency
is timed from the request's intended send time, so a stall counts against
every request it delays, and the generator's own lateness is reported.
Every request is a fresh topic.
"""
from __future__ import annotations

import contextlib
import dataclasses
import time

import jax
import numpy as np

from chipbench import datagen

#: seconds past the window's close to wait for an answer before it counts
#: as never having come
GRACE_S = 60.0


class Annotator:
    """``bench.<name>`` host spans in the profiler's trace; free when off."""

    def __init__(self, on: bool):
        self.on = on

    def __call__(self, name: str):
        if not self.on:
            return contextlib.nullcontext()
        return jax.profiler.TraceAnnotation(f"bench.{name}")

    def wrap(self, obj, attr: str, name: str) -> None:
        """Record every call of ``obj.attr`` as a span (traced runs only)."""
        if not self.on:
            return
        fn = getattr(obj, attr)

        def spanned(*a, **k):
            with self(name):
                return fn(*a, **k)

        setattr(obj, attr, spanned)


def instrument(sys_, ann: Annotator) -> None:
    """Spans around the calls into each layer, from outside the program."""
    ann.wrap(sys_.server.scheduler, "next_batch", "batch_wait")
    ann.wrap(sys_.server, "_execute_batch", "execute_batch")
    ann.wrap(sys_.backend.engine, "_run_plan", "dispatch")


@dataclasses.dataclass
class Window:
    t0: float                      # monotonic start of the window
    seconds: float                 # its length
    sent: list                     # [(qrow index, due, ServeRequest|None)]
    lateness_s: list               # generator lateness per send
    rejected: int = 0
    t_end: float = 0.0             # when the last counted work finished


def _submit(server, row, ann):
    from repro.serve.request import ServerOverloaded
    with ann("submit"):
        try:
            return server.submit_one(row, timeout_ms=None)
        except ServerOverloaded:
            return None


def open_loop(server, Q: dict, due: np.ndarray, seconds: float,
              ann: Annotator) -> Window:
    """Send request ``i`` at ``t0 + due[i]`` whatever the server does."""
    rows = [datagen.rows(Q, i, i + 1) for i in range(len(due))]
    sent, late = [], []
    t0 = time.monotonic()
    with ann("window"):
        for i, d in enumerate(due):
            target = t0 + float(d)
            dt = target - time.monotonic()
            if dt > 0:
                with ann("sleep"):
                    time.sleep(dt)
            late.append(max(0.0, time.monotonic() - target))
            sent.append((i, target, _submit(server, rows[i], ann)))
        rest = t0 + seconds - time.monotonic()
        if rest > 0:
            with ann("sleep"):
                time.sleep(rest)
    w = Window(t0, seconds, sent, late,
               rejected=sum(r is None for _, _, r in sent))
    w.t_end = t0 + seconds
    return w


def closed_loop(server, Q: dict, clients: int, seconds: float,
                ann: Annotator,
                rate_ceiling_qps: float | None = None) -> Window:
    """``clients`` callers, each sending its next query when the previous
    one is answered, for ``seconds``; then no caller sends again, and the
    window ends when the last answer is in (served batches take seconds,
    so a window cut at a fixed time would count whole batches or none).

    Every request is a fresh topic of ``Q``, which is sized for
    ``rate_ceiling_qps``: a loop that runs out of topics has answered
    faster than that, and raises rather than report a result."""
    n_pool = int(Q["qid"].shape[0])
    sent, late, live = [], [], []
    nxt = 0

    def send(t):
        nonlocal nxt
        if nxt >= n_pool:
            rate = nxt / max(time.monotonic() - t0, 1e-9)
            ceiling = ("no" if rate_ceiling_qps is None
                       else f"a {rate_ceiling_qps} q/s")
            raise RuntimeError(
                f"closed loop used all {n_pool} topics of its pool at "
                f"{rate:.1f} q/s, drawn for {ceiling} rate ceiling; "
                f"raise the traffic's rate_ceiling_qps")
        req = _submit(server, datagen.rows(Q, nxt, nxt + 1), ann)
        sent.append((nxt, t, req))
        late.append(0.0)
        nxt += 1
        if req is not None:
            live.append((nxt - 1, t, req))

    t0 = time.monotonic()
    t_end = t0 + seconds
    with ann("window"):
        for _ in range(clients):
            send(t0)
        while True:
            now = time.monotonic()
            if now >= t_end or not live:
                break
            with ann("result_wait"):
                live[0][2].done.wait(timeout=t_end - now)
            done = [x for x in live if x[2].done.is_set()]
            if time.monotonic() >= t_end:
                break
            for x in done:
                live.remove(x)
                send(x[2].trace.t_done)
        with ann("result_wait"):
            for x in live:
                x[2].done.wait(timeout=t_end + GRACE_S - time.monotonic())
    w = Window(t0, seconds, sent, late,
               rejected=sum(r is None for _, _, r in sent))
    w.t_end = max([x[2].trace.t_done for x in sent if x[2] is not None
                   and x[2].done.is_set()], default=t_end)
    return w


def wait_all(win: Window, grace_s: float) -> None:
    """Wait for every sent request, at most ``grace_s`` past the window."""
    deadline = win.t0 + win.seconds + grace_s
    for _, _, req in win.sent:
        if req is not None:
            req.done.wait(timeout=max(0.0, deadline - time.monotonic()))
