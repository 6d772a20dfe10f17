"""Drive ``PagedServeEngine`` through set-up, the window and the drain.

One object per run, on one chip or over the cell's mesh.  Set-up warms every
shape the run's traffic will use, and (closed loop) prefills each client's
first request.  The window then offers the planned load on the harness's
clock and records every output token when ``step()`` returns it.  After the
window no new request is sent; the run drains what it owes, waiting at most
``DRAIN_S`` seconds past the close.
"""
from __future__ import annotations

import collections
import contextlib
import time

import numpy as np

from bench import stats, traffic
from bench.stats import Served, Window

DRAIN_S = 60.0
clock = time.perf_counter


class CompileCounter:
    """Counts lowerings and backend compiles while ``armed``."""

    EVENTS = ("/jax/core/compile/jaxpr_to_mlir_module_duration",
              "/jax/core/compile/backend_compile_duration")

    def __init__(self):
        import jax
        self.armed, self.count = False, 0
        jax.monitoring.register_event_duration_secs_listener(self._seen)

    def _seen(self, event: str, duration: float, **kw) -> None:
        if self.armed and event in self.EVENTS:
            self.count += 1


class Call:
    """One call of the engine's jitted step, as the harness saw it."""

    __slots__ = ("kind", "start", "contexts", "chunk")

    def __init__(self, kind, start, contexts=None, chunk=None):
        self.kind, self.start = kind, start
        self.contexts = contexts    # decode: keys each live slot attends
        self.chunk = chunk          # prefill: (first position, tokens, last)


class Run:
    def __init__(self, cell, cfg, weights, *, seed: int, seconds: float,
                 annotate: bool, mesh=None):
        from repro.launch.serve import PagedServeEngine

        self.cell, self.cfg, self.seconds = cell, cfg, seconds
        self.annotate = annotate
        self.closed = cell.traffic["arrivals"] == "closed"
        self.plan = traffic.plan(cell.traffic, cell.load, seed=seed,
                                 seconds=seconds, vocab=cfg.vocab)
        e = cell.engine
        self.engine = PagedServeEngine(
            cfg, weights, slots=e["slots"], max_seq=e["max_seq"],
            page_size=e["page_size"], prefill_chunk=e["prefill_chunk"],
            mesh=mesh, clock=clock)
        self.calls: list[Call] = []
        self.ticks: list[tuple[float, float]] = []   # engine.step, host clock
        self.served: dict[int, Served] = {}     # plan idx -> record
        self.requests: dict[int, object] = {}   # plan idx -> engine Request
        self.inflight: dict[int, int] = {}      # plan idx -> tokens seen
        self.window: Window | None = None
        # the engine's own counters at the window's open and close
        self.counters: dict[str, tuple[int, int]] = {}
        self.compiles = CompileCounter()
        self._rid = 10 ** 9
        self._wall_offset = time.time() - clock()
        self._wrap_steps()

    # -- instrumentation ---------------------------------------------------

    def _span(self, name: str):
        if not self.annotate:
            return contextlib.nullcontext()
        import jax
        return jax.profiler.TraceAnnotation(name)

    def _wrap_steps(self) -> None:
        """Record every call of the engine's jitted step: the batched decode
        (``_decode_batch``) and the chunked prefill (``_decode``, on the
        private cache), as ``chip_smoke.py`` wraps them."""
        eng = self.engine
        batch, chunk = eng._decode_batch, eng._decode
        max_seq = eng.max_seq

        def decode_batch(params, cache, toks, pos, row_map):
            # the host's copy of the positions the engine just uploaded;
            # idle and prefilling slots hold the sentinel max_seq
            live = eng.pos[eng.pos < max_seq]
            t0 = clock()
            with self._span("bench.decode_step"):
                out = batch(params, cache, toks, pos, row_map)
            self.calls.append(Call("decode", t0,
                                   contexts=[int(x) + 1 for x in live]))
            return out

        def prefill(params, cache, toks, pos):
            pf = next(p for p in eng._prefills.values() if p.cache is cache)
            start, n = pf.done, int(toks.shape[1])
            last = start + n == len(pf.req.prompt)
            t0 = clock()
            with self._span("bench.prefill_chunk"):
                out = chunk(params, cache, toks, pos)
            self.calls.append(Call("prefill", t0, chunk=(start, n, last)))
            return out

        eng._decode_batch, eng._decode = decode_batch, prefill

    # -- requests ----------------------------------------------------------

    def _send(self, p: traffic.Planned, due: float | None) -> None:
        from repro.launch.serve import Request

        req = Request(rid=p.idx, prompt=p.prompt, max_new=p.max_new)
        now = clock()
        self.served[p.idx] = Served(due=due, sent=now)
        self.requests[p.idx] = req
        self.inflight[p.idx] = 0
        if not self.engine.submit(req):
            self.served[p.idx].ok = False
            del self.inflight[p.idx]

    def _record(self, now: float) -> list[int]:
        """Stamp the tokens each in-flight request gained in the last step;
        returns the plan indices that ended."""
        ended = []
        for idx, seen in list(self.inflight.items()):
            req, rec = self.requests[idx], self.served[idx]
            if rec.admitted is None and req.admit_time is not None:
                # the engine stamps admission on the wall clock
                rec.admitted = req.admit_time - self._wall_offset
                rec.slot = next((s for s, r in enumerate(
                    self.engine.scheduler.slots) if r is req), None)
            n = len(req.out)
            if n > seen:
                rec.token_times.extend([now] * (n - seen))
                self.inflight[idx] = n
            if req.status != "PENDING":
                rec.ok = req.status == "OK"
                del self.inflight[idx]
                ended.append(idx)
        return ended

    def _step(self) -> list[int]:
        t0 = clock()
        with self._span("engine.step"):
            self.engine.step()
        now = clock()
        self.ticks.append((t0, now))
        with self._span("bench.bookkeeping"):
            return self._record(now)

    # -- set-up --------------------------------------------------------------

    def warm_up(self) -> None:
        """Compile and run every shape the window will use: one request per
        prompt length of the plan, one at a time (a private prefill cache
        per admitted request must fit beside the pool), each decoding one
        step."""
        from repro.launch.serve import Request

        rng = np.random.default_rng(0)
        for n in sorted({len(p.prompt) for p in self.plan}):
            self._rid += 1
            req = Request(rid=self._rid, max_new=2,
                          prompt=rng.integers(0, self.cfg.vocab, n,
                                              dtype=np.int32))
            self.engine.submit(req)
            while self.engine.scheduler.has_work():
                self.engine.step()
            if req.status != "OK":
                raise RuntimeError(f"warm-up request of {n} tokens ended "
                                   f"{req.status}")
        self.calls.clear()

    def open_clients(self) -> collections.deque:
        """Closed loop: send each client's first request and prefill it,
        one client at a time; returns the requests still to send."""
        pool = collections.deque(self.plan)
        for _ in range(self.cell.load["clients"]):
            p = pool.popleft()
            self._send(p, None)
            while not self.served[p.idx].token_times:
                self._replace(self._step(), pool)
        self.calls.clear()
        return pool

    def _replace(self, ended: list[int], pool: collections.deque) -> None:
        """Closed loop: each client whose request ended sends its next."""
        for _ in ended:
            if not pool:
                raise RuntimeError("closed loop ran out of planned requests")
            self._send(pool.popleft(), None)

    # -- the window ----------------------------------------------------------

    def window_open(self, trace_hook=None) -> None:
        """Offer the load for ``seconds``.  ``trace_hook(now, start)`` is
        called between steps (the traced run starts and stops the profiler
        there)."""
        pool = self.open_clients() if self.closed else None
        due = collections.deque(sorted(self.plan, key=lambda p: p.due)) \
            if not self.closed else None
        start = clock()
        self.window = Window(start, start + self.seconds)
        opened = self._counters()
        self.compiles.armed = True
        while True:
            now = clock()
            if now >= self.window.end:
                # due inside the window but not yet sent (the last step ran
                # past its due time): sent now, timed from when it was due
                while due and start + due[0].due < self.window.end:
                    p = due.popleft()
                    self._send(p, start + p.due)
                break
            if trace_hook is not None:
                trace_hook(now, start)
            if due is not None:
                with self._span("bench.submit"):
                    while due and start + due[0].due <= now:
                        p = due.popleft()
                        self._send(p, start + p.due)
            if self.engine.scheduler.has_work():
                ended = self._step()
                if pool is not None:
                    with self._span("bench.submit"):
                        self._replace(ended, pool)
            elif due:
                with self._span("bench.idle"):
                    time.sleep(max(0.0, min(start + due[0].due,
                                            self.window.end) - clock()))
        self.compiles.armed = False
        self.counters = {k: (opened[k], v)
                         for k, v in self._counters().items()}

    def _counters(self) -> dict[str, int]:
        e = self.engine
        return {"generated": e.generated, "decode_steps": e.decode_steps,
                "prefill_tokens": e.prefill_tokens}

    def drain(self) -> None:
        """Serve what was sent until it is done, for at most ``DRAIN_S``."""
        limit = self.window.end + DRAIN_S
        while self.inflight and clock() < limit:
            self._step()

    # -- accounting ----------------------------------------------------------

    def counted(self) -> list[Served]:
        """The requests the run answers for: those due inside the window
        (open loop), or every request in flight during it (closed loop)."""
        if self.closed:
            return list(self.served.values())
        return stats.due_in(list(self.served.values()), self.window)

    def failed(self) -> int:
        """Requests that ended in an error, and (open loop) requests due
        inside the window with no answer a minute past the close.  In a
        closed loop no request is due at a time: one still decoding when the
        drain ends is late, not failed, unless it gave no token in the
        drain's last 10 s."""
        end = self.window.end + DRAIN_S

        def lost(s: Served) -> bool:
            if s.ok is not None:
                return not s.ok
            return not self.closed or not s.token_times \
                or s.token_times[-1] < end - 10.0
        return sum(1 for s in self.counted() if lost(s))

    def free(self) -> None:
        """Drop the engine's device state before the reference runs."""
        self.engine = None
