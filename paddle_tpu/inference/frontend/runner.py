"""EngineRunner: the thread bridge between the async frontend and the
single-threaded LLMEngine.

The engine (inference/serving.py) is deliberately single-threaded — its
scheduler, page pool, and host-side batch buffers are mutated with no
locks.  The frontend, meanwhile, is an asyncio event loop serving many
sockets.  This module owns the seam: ONE dedicated thread steps the
engine forever, and every cross-thread interaction goes through queues
that the stepping thread drains at step boundaries (the only moments the
engine's state is consistent):

    HTTP thread                     engine thread
    -----------                     -------------
    submit()  ──▶ inbox deque  ──▶  engine.add_request(..., sink=)
    abort()   ──▶ abort deque  ──▶  engine.abort(rid, reason)
                                    engine.step()
    put(ev) ... ◀── ONE hand-over ◀── sink(launch), once a commit ◀──┘

Tokens flow OUT a LAUNCH at a time.  Every request is admitted under
the runner's sink (``_take_launch``), which the engine calls ONCE at the
end of a commit with everything the launch emitted, in row order:
``[(rid, tokens, output), ...]`` (a finished request's output after its
last token).  Under ONE hold of the lock the runner checks the
generation, appends every token to its handle's journal, marks the
finished handles and hands the events to each request's ``deliver``:

- a ``LoopDelivery(loop, put)`` (what the HTTP layer passes: ``put`` is
  its stream's ``asyncio.Queue.put_nowait``) names the event loop its
  consumer lives on; the events of all such consumers of one loop cross
  in ONE ``loop.call_soon_threadsafe`` whose callback, on the loop,
  puts each event where it belongs, in order.  One write to the loop's
  wake-up pipe and one wake-up of its thread a launch, where a call a
  token made one of each a token (31 a launch in the benchmark's cells,
  inside the engine's commit);
- a plain callable (``queue.Queue.put_nowait`` of a sync caller, the
  router's settling wrapper) is called an event at a time, with
  ("token", int) events and exactly one terminal
  ("finish", RequestOutput).

A terminal event made outside a step (an abort or a deadline between
steps, a failed admission, recovery giving up) is handed over at once.
``summary()`` counts ``deliver_tokens`` and ``deliver_handovers`` (calls
into the consumers' deliveries) beside ``launches``.  Backpressure is
enforced HERE (not in the engine): ``submit`` refuses work past
``max_pending`` (RunnerSaturated → the HTTP layer's 429) and while
draining (RunnerDraining → 503).

Deadlines are runner-owned: each handle carries an absolute monotonic
deadline covering queue wait AND generation; the stepping thread sweeps
expired handles every iteration and aborts them with reason
``"deadline"`` — so a deadline fires even for a request still sitting in
the admission queue.

``drain()`` is the graceful-shutdown half: stop admitting (submit
refuses), let the engine finish or deadline-out everything in flight,
then park the thread.  ``close(abort_inflight=True)`` is the impatient
variant that aborts the in-flight set instead of finishing it.

Supervised recovery (``engine_factory`` + ``step_deadline_s``): the
runner journals every token a handle has been delivered
(``StreamHandle.emitted``).  When a step CRASHES, the stepping thread
rebuilds the engine via the factory and replays every admitted handle
as a continuation (``add_request(generated=journal)``) — the prefix
cache makes the re-prefill cheap, and because sampling keys derive from
(seed, position) the continuation is byte-identical to the
uninterrupted run.  When a step HANGS past ``step_deadline_s``, a
watchdog thread performs the same recovery and spawns a replacement
stepping thread; the wedged thread becomes a zombie that exits at its
next generation check.  Every sink is bound to its GENERATION and
checked under the runner lock — a zombie's late launch is dropped
whole, before it can duplicate or reorder what the client sees — and
guard + journal append + finished marks + hand-over happen under that
one hold, a launch at a time, so the recovery snapshot is race-free by
construction: it never sees a journal that holds a token no client was
handed.  The engine's
ServingStats object (and any FaultPlan / DegradationController) carries
over to the rebuilt engine, so uptime and counters describe the
SERVICE, not one engine incarnation.

The async engine pipeline (``LLMEngine(overlap=True)``) needs NOTHING
new here, by construction: ``engine.step()`` still contains the
blocking completion of whatever launch it materializes, so the
watchdog's per-call deadline naturally spans dispatch→completion of a
ticket, and the sink is called at the end of a launch's COMMIT —
i.e. only at COMPLETION boundaries, never for a launch still in
flight.  A crash mid-pipeline therefore leaves the journal holding
exactly the tokens of fully completed steps, which is precisely the
state the replay continuation rebuilds; the in-flight launch and any
speculatively pre-staged next step die with the old engine.
"""
from __future__ import annotations

import functools
import itertools
import logging
import threading
import time
from collections import deque
from dataclasses import dataclass, field

_log = logging.getLogger("paddle_tpu.serving")

__all__ = ["EngineRunner", "LoopDelivery", "RunnerSaturated",
           "RunnerDraining", "StreamHandle"]


class RunnerSaturated(RuntimeError):
    """Admission queue full — shed the request (HTTP 429)."""


class RunnerDraining(RuntimeError):
    """Server is draining — no new work (HTTP 503)."""


class LoopDelivery:
    """A ``deliver`` whose consumer lives on an asyncio event loop:
    ``put(event)`` is run ON ``loop``.  Called with one event it crosses
    at once.  The runner, which sees ``loop``, gathers what a launch
    holds for the consumers of one loop and crosses ONCE for all of them
    (``hand_over``): one write to the loop's wake-up pipe and one
    wake-up of its thread a launch, where a call an event makes one of
    each a token."""

    __slots__ = ("loop", "put")

    def __init__(self, loop, put):
        self.loop = loop
        self.put = put

    def __call__(self, event) -> None:
        self.hand_over(self.loop, ((self.put, event),))

    @staticmethod
    def hand_over(loop, batch) -> None:
        """Run ``put(event)`` for every pair of ``batch``, in order, on
        ``loop``.  A loop torn down mid-flight (server stopped) must not
        kill the engine thread."""
        try:
            loop.call_soon_threadsafe(_put_all, batch)
        except RuntimeError:
            pass


def _put_all(batch) -> None:
    for put, event in batch:
        put(event)


@dataclass
class StreamHandle:
    """One submitted request as the frontend sees it."""
    request_id: str                   # runner-scoped id (assigned here)
    deliver: object                   # callable(event) on the engine thread
    deadline: float | None            # absolute time.monotonic() deadline
    params: dict                      # add_request kwargs
    rid: int = -1                     # engine rid once admitted
    done: bool = False
    t_submit: float = field(default_factory=time.monotonic)
    # recovery journal: every token delivered so far.  Appended under
    # the runner lock by the generation-guarded sink (_take_launch); a
    # rebuilt engine replays the request as a continuation of exactly
    # this list.
    emitted: list = field(default_factory=list)


class EngineRunner:
    """Owns the engine's stepping thread and the cross-thread queues.

    Parameters
    ----------
    engine: an LLMEngine (ideally built with ``retain_outputs=False`` so
        a long-running server does not accumulate finished outputs).
    max_pending: admission bound — submitted-but-unfinished requests the
        runner will hold before shedding (queued + running).  Sized a
        few times ``engine.max_num_seqs`` so a burst queues instead of
        shedding, but an overload sheds instead of growing without
        bound.
    idle_wait_s: how long the stepping thread parks when there is no
        work (woken early by submit/abort/drain).
    engine_factory: nullary callable building a replacement engine after
        a crashed or hung step.  None (the default) disables recovery —
        a step exception fails the in-flight set and stops the runner.
    step_deadline_s: watchdog per-step wall budget.  A step running
        longer is treated as hung: the watchdog thread rebuilds the
        engine and spawns a replacement stepping thread.  Must sit above
        the engine's worst-case honest step (first-step XLA compiles
        included).  Under the async pipeline one ``step()`` call spans
        the completion block of the in-flight launch plus the next
        dispatch, so the budget covers dispatch→completion of a ticket
        with no watchdog change.  None disables the watchdog (crash
        recovery still works when a factory is set).
    max_restarts: recovery budget; exceeding it fails the in-flight set
        instead of rebuilding again (a deterministic crash must not loop
        forever).
    name: optional runner name, prefixed onto every request id
        ("r0-req-3") — a replica router recovers the owning runner from
        the id alone, so aborts route without a shared table.
    """

    def __init__(self, engine, *, max_pending: int | None = None,
                 idle_wait_s: float = 0.05, engine_factory=None,
                 step_deadline_s: float | None = None,
                 max_restarts: int = 8, name: str = ""):
        self.engine = engine
        self.name = str(name)
        self._id_prefix = f"{self.name}-" if self.name else ""
        self.max_pending = int(max_pending
                               if max_pending is not None
                               else 4 * engine.max_num_seqs)
        self.idle_wait_s = float(idle_wait_s)
        self._engine_factory = engine_factory
        self.step_deadline_s = None if step_deadline_s is None \
            else float(step_deadline_s)
        self.max_restarts = int(max_restarts)
        self._lock = threading.Lock()
        self._wake = threading.Event()
        self._inbox: deque = deque()          # StreamHandle, FIFO
        self._aborts: deque = deque()         # (request_id, reason)
        self._handles: dict = {}              # request_id -> StreamHandle
        self._by_rid: dict = {}               # engine rid -> StreamHandle
        self._inflight = 0                    # submitted, not yet finished
        self._draining = False
        self._stopped = False
        self._seq = itertools.count()
        # recovery generation: bumped (under _lock) on every engine
        # rebuild.  Callbacks and loop iterations carry the generation
        # they were created under; a mismatch means "your engine is
        # dead — drop everything and exit".
        self._gen = 0
        self._restarts = 0
        self._sink = None         # the live generation's: _admit_one
        # (generation, t_start) of the step currently executing, or None
        # between steps.  Generation-tagged so a zombie's cleanup cannot
        # clear the replacement thread's timer.
        self._step_started = None
        tname = f"llm-engine-{self.name}" if self.name else "llm-engine"
        self._thread = threading.Thread(target=self._loop, args=(0,),
                                        name=tname, daemon=True)
        self._watchdog = None
        self._started = False
        # step-timeline track, registered lazily on first traced event
        # (the engine owns the Tracer; a rebuilt engine keeps it via the
        # factory, so delivery/restart events survive recovery)
        self._trace_track = None

    def _tracer(self):
        """The live engine's Tracer, or None (the zero-cost default)."""
        tr = getattr(self.engine, "tracer", None)
        if tr is not None and self._trace_track is None:
            base = f"runner-{self.name}" if self.name else "runner"
            self._trace_track = tr.register(base)
        return tr

    @property
    def restarts(self) -> int:
        with self._lock:
            return self._restarts

    # ------------------------------------------------------------------
    # any-thread API
    # ------------------------------------------------------------------

    def start(self) -> "EngineRunner":
        if not self._started:
            self._started = True
            self._thread.start()
            if self.step_deadline_s is not None \
                    and self._engine_factory is not None:
                self._watchdog = threading.Thread(
                    target=self._watch, name="llm-watchdog", daemon=True)
                self._watchdog.start()
        return self

    def submit(self, prompt, *, deliver, deadline_s: float | None = None,
               **params) -> str:
        """Queue one generation request.  ``deliver`` receives
        ("token", int) events and exactly one terminal
        ("finish", RequestOutput) event, all on the engine thread.
        ``deadline_s`` is a relative budget from now (queue wait
        included).  Returns the runner request id (the abort() handle).
        Raises RunnerSaturated / RunnerDraining instead of queuing."""
        with self._lock:
            if self._draining or self._stopped:
                raise RunnerDraining("runner is draining")
            if self._inflight >= self.max_pending:
                raise RunnerSaturated(
                    f"{self._inflight} requests in flight >= max_pending "
                    f"{self.max_pending}")
            request_id = f"{self._id_prefix}req-{next(self._seq)}"
            deadline = None if deadline_s is None \
                else time.monotonic() + float(deadline_s)
            h = StreamHandle(request_id=request_id, deliver=deliver,
                             deadline=deadline, params=dict(params))
            h.params["prompt"] = prompt
            self._handles[request_id] = h
            self._inbox.append(h)
            self._inflight += 1
        self._wake.set()
        return request_id

    def abort(self, request_id: str, reason: str = "aborted") -> None:
        """Request cancellation; applied at the next step boundary.  The
        stream still receives its terminal ("finish", output) event (with
        the abort reason) unless it already finished — aborting a
        finished/unknown id is a no-op."""
        with self._lock:
            self._aborts.append((request_id, reason))
        self._wake.set()

    def inflight(self) -> int:
        with self._lock:
            return self._inflight

    @property
    def draining(self) -> bool:
        with self._lock:
            return self._draining

    def drain(self, timeout_s: float | None = None) -> bool:
        """Graceful shutdown: stop admitting, finish (or deadline-out)
        everything in flight, park the thread.  True when the engine
        drained fully inside the timeout."""
        with self._lock:
            self._draining = True
        self._wake.set()
        t0 = time.monotonic()
        while True:
            with self._lock:
                if self._inflight == 0:
                    break
            if timeout_s is not None \
                    and time.monotonic() - t0 > float(timeout_s):
                break
            time.sleep(0.005)
        with self._lock:
            drained = self._inflight == 0
            self._stopped = True
        self._wake.set()
        if self._started:
            self._thread.join(timeout=5.0)
        return drained

    def abort_all(self, reason: str = "shutdown") -> int:
        """Queue an abort for every request still in flight (applied at
        the next step boundary); returns how many were queued.  The CLI's
        second-SIGINT escalation: a graceful drain already in progress
        completes as soon as these aborts land."""
        with self._lock:
            ids = [h.request_id for h in self._handles.values()
                   if not h.done]
        for request_id in ids:
            self.abort(request_id, reason)
        return len(ids)

    def close(self, *, abort_inflight: bool = True) -> None:
        """Impatient shutdown: abort whatever is still in flight (reason
        "shutdown"), then stop the thread."""
        if abort_inflight:
            with self._lock:
                self._draining = True
            self.abort_all("shutdown")
        self.drain(timeout_s=30.0)

    # ------------------------------------------------------------------
    # engine thread
    # ------------------------------------------------------------------

    def _close(self, h) -> None:  # guarded-by: _lock
        """The books of a handle's terminal event."""
        h.done = True
        self._handles.pop(h.request_id, None)
        if h.rid >= 0:
            self._by_rid.pop(h.rid, None)
        self._inflight -= 1

    def _vote_deadline(self, h, out) -> None:
        if h.deadline is not None:
            # deadline attainment: only deadline-carrying requests vote
            self.engine.stats.record_deadline(
                getattr(out, "finish_reason", None) != "deadline"
                and time.monotonic() <= h.deadline)

    def _finish_handle(self, h, out) -> None:
        # a terminal event made OUTSIDE a step (a failed admission, an
        # abort of a request the engine never saw, recovery giving up):
        # handed over at once
        with self._lock:
            if h.done:
                return
            self._close(h)
        self._vote_deadline(h, out)
        self.engine.stats.record_delivery(0, 1)
        try:
            h.deliver(("finish", out))
        except Exception:
            pass                      # a dead consumer must not kill the loop

    def _take_launch(self, gen: int, launch) -> None:
        """The engine's sink: everything one launch emitted for this
        runner's requests, ``[(rid, tokens, output), ...]`` in row
        order, at the end of its commit (or one entry at once, for a
        request the engine ends between steps).

        Guard + journal append + finished marks + hand-over under ONE
        lock hold: the recovery snapshot (which bumps _gen under the
        same lock before reading h.emitted) can therefore never miss a
        delivered token or race a zombie into a duplicate.  Never
        journal under the lock and hand over outside it: a recovery in
        between would replay from tokens no client saw.  A launch of
        another generation's engine is dropped whole.

        Events for a ``LoopDelivery`` are gathered and cross to their
        event loop in one call a loop; a plain callable is called an
        event at a time, here."""
        tr = self._tracer()
        by_loop: dict = {}            # event loop -> [(put, event), ...]
        direct = []                   # [(plain callable, event), ...]
        closed = []
        tokens = 0
        with self._lock:
            if gen != self._gen:
                return
            for rid, toks, out in launch:
                h = self._by_rid.get(rid)
                if h is None or h.done:
                    continue
                send = h.deliver
                loop = getattr(send, "loop", None)
                if loop is None:
                    batch = direct
                else:
                    send = send.put
                    batch = by_loop.get(loop)
                    if batch is None:
                        batch = by_loop[loop] = []
                emitted = h.emitted
                for tok in toks:
                    emitted.append(tok)
                    batch.append((send, ("token", tok)))
                    if tr is not None:
                        # the cross-tier join point, one a token: engine
                        # rid <-> frontend id
                        tr.instant("runner.deliver",
                                   track=self._trace_track,
                                   args={"request_id": h.request_id,
                                         "rid": rid,
                                         "tokens": len(emitted)})
                tokens += len(toks)
                if out is not None:
                    self._close(h)
                    closed.append((h, out))
                    batch.append((send, ("finish", out)))
            # counted before anything crosses: a client that reads the
            # counters on its stream's last frame finds its tokens there
            self.engine.stats.record_delivery(
                tokens, len(direct) + len(by_loop))
            for deliver, ev in direct:
                try:
                    deliver(ev)
                except Exception:
                    pass              # a dead consumer must not kill the loop
            for loop, batch in by_loop.items():
                LoopDelivery.hand_over(loop, batch)
        for h, out in closed:
            self._vote_deadline(h, out)

    def _admit_one(self, eng, h, gen: int, generated=None) -> bool:
        """Admit one handle into ``eng`` under this generation's sink.
        ``generated`` is the recovery journal (continuation replay);
        None for a first admission."""
        sink = self._sink
        if sink is None or sink.args != (gen,):
            # ONE object a generation: the engine calls each sink once a
            # launch, and tells them apart by identity
            sink = self._sink = functools.partial(self._take_launch, gen)
        params = dict(h.params)
        prompt = params.pop("prompt")
        if generated is not None:
            params["generated"] = list(generated)
        try:
            rid = eng.add_request(prompt, sink=sink, **params)
        except Exception as e:
            from ..serving import RequestOutput
            self._finish_handle(h, RequestOutput(
                rid=-1, prompt=list(prompt), generated=list(h.emitted),
                finish_reason=f"error: {type(e).__name__}: {e}"))
            return False
        fl = getattr(eng, "flight", None)
        if fl is not None:
            # the same cross-tier join the tracer instants carry:
            # engine rid <-> frontend request id, plus the remaining
            # deadline budget measured at engine admission (the flight
            # record's t_submit) so slack fields line up
            fl.annotate(rid, request_id=h.request_id,
                        replica=self.name or None,
                        deadline_s=None if h.deadline is None
                        else h.deadline - time.monotonic())
        with self._lock:
            # a zombie that admits after its engine was replaced must
            # not take a rid of the replacement's
            if gen == self._gen:
                h.rid = rid
                self._by_rid[rid] = h
        return True

    def _admit_inbox(self, gen: int) -> int:
        """Hand the inbox to the engine; returns how many it took in."""
        eng = self.engine
        taken = 0
        while True:
            with self._lock:
                if gen != self._gen or not self._inbox:
                    return taken
                h = self._inbox.popleft()
            if h.done:                # aborted while still queued
                continue
            taken += self._admit_one(eng, h, gen)

    def _apply_aborts(self, gen: int) -> None:
        while True:
            with self._lock:
                if gen != self._gen or not self._aborts:
                    return
                request_id, reason = self._aborts.popleft()
                h = self._handles.get(request_id)
            if h is None or h.done:
                continue
            if h.rid >= 0:
                # engine.abort hands the finish to the sink at once
                self.engine.abort(h.rid, finish_reason=reason)
            else:
                # never reached the engine: synthesize the terminal event
                from ..serving import RequestOutput
                self._finish_handle(h, RequestOutput(
                    rid=-1, prompt=[], generated=[], finish_reason=reason))
                self.engine.stats.record_abort(reason)

    def _sweep_deadlines(self, gen: int) -> None:
        now = time.monotonic()
        with self._lock:
            expired = [h.request_id for h in self._handles.values()
                       if h.deadline is not None and now > h.deadline
                       and not h.done]
        for request_id in expired:
            with self._lock:
                self._aborts.append((request_id, "deadline"))
        if expired:
            self._apply_aborts(gen)

    # -- supervised recovery -----------------------------------------------

    def _recover(self, gen: int):
        """Rebuild the engine after a crashed/hung step and replay the
        in-flight set from the journal.  Returns the new generation, or
        None when recovery is off/raced/exhausted (the runner stops).
        Called from the stepping thread (crash) or the watchdog (hang);
        the generation check under the lock makes the two racers safe —
        exactly one wins."""
        with self._lock:
            if gen != self._gen:
                return None           # someone else already recovered
            self._gen += 1
            newgen = self._gen
            self._restarts += 1
            restarts = self._restarts
            live = [h for h in self._handles.values() if not h.done]
            # the journal snapshot: taken AFTER the generation bump, so
            # no old-generation callback can append past this point
            replay = [(h, list(h.emitted)) for h in live if h.rid >= 0]
            self._by_rid.clear()      # the dead engine's rids
            requeue = [h for h in live
                       if h.rid < 0 and h not in self._inbox]
        old = self.engine
        tr = self._tracer()
        if tr is not None:
            t_rec = tr.now()
        if self._engine_factory is None or restarts > self.max_restarts:
            from ..serving import RequestOutput
            for h in live:
                self._finish_handle(h, RequestOutput(
                    rid=-1, prompt=list(h.params.get("prompt", [])),
                    generated=list(h.emitted),
                    finish_reason="engine_error"))
            with self._lock:
                self._stopped = True
            self._wake.set()
            return None
        # detach the shared fault plan / pressure controller from the
        # dead engine FIRST: a hung step finishing on the zombie thread
        # must not consume scheduled faults or feed the controller stale
        # pool readings while the replacement runs
        plan = getattr(old, "fault_plan", None)
        pressure = getattr(old, "pressure", None)
        if plan is not None:
            old.set_fault_plan(None)
        if pressure is not None:
            old.pressure = None
        eng = self._engine_factory()
        # metric continuity: the service's stats (and the flight
        # recorder's forensic window) survive the engine
        eng.stats = old.stats
        eng.stats.record_restart()
        eng.flight = getattr(old, "flight", None)
        if plan is not None:
            eng.set_fault_plan(plan)
        eng.pressure = pressure
        self.engine = eng
        # replay admitted requests in submission order (dict order) as
        # continuations of their journals; failures fail only that handle
        for h, emitted in replay:
            h.rid = -1
            cap = int(h.params.get("max_new_tokens", 32))
            if len(emitted) >= cap:
                # the crash lost only the terminal event — the journal
                # already holds the whole budget
                from ..serving import RequestOutput
                self._finish_handle(h, RequestOutput(
                    rid=-1, prompt=list(h.params.get("prompt", [])),
                    generated=list(emitted), finish_reason="length"))
                continue
            self._admit_one(eng, h, newgen,
                            generated=emitted if emitted else None)
        with self._lock:
            for h in requeue:        # popped from the inbox mid-crash
                self._inbox.append(h)
        self._wake.set()
        if tr is not None:
            tr.complete("runner.restart", t_rec, track=self._trace_track,
                        args={"gen": newgen, "restarts": restarts,
                              "replayed": len(replay)})
        return newgen

    def _watch(self) -> None:
        """Watchdog thread: when the current step has run past
        step_deadline_s, recover and spawn a replacement stepping
        thread.  The wedged thread exits at its next generation check;
        its late callbacks are dropped by the generation guard."""
        poll = min(self.step_deadline_s / 4.0, 0.05)
        while True:
            with self._lock:
                if self._stopped:
                    return
                gen = self._gen
            ss = self._step_started
            if ss is not None and ss[0] == gen \
                    and time.monotonic() - ss[1] > self.step_deadline_s:
                tr = self._tracer()
                if tr is not None:
                    tr.instant("runner.watchdog_fired",
                               track=self._trace_track,
                               args={"gen": gen, "stuck_s": round(
                                   time.monotonic() - ss[1], 3)})
                newgen = self._recover(gen)
                if newgen is not None:
                    t = threading.Thread(target=self._loop, args=(newgen,),
                                         name=f"llm-engine-g{newgen}",
                                         daemon=True)
                    self._thread = t
                    t.start()
            time.sleep(poll)

    def _loop(self, gen: int) -> None:
        t_back = 0          # tracer clock when engine.step() last returned
        while True:
            with self._lock:
                if self._stopped or gen != self._gen:
                    return
            eng = self.engine
            try:
                self._apply_aborts(gen)
                self._sweep_deadlines(gen)
                taken = self._admit_inbox(gen)
                if eng.has_unfinished():
                    tr = self._tracer()
                    if tr is not None and t_back:
                        # the loop's own turn between two steps: aborts,
                        # deadlines, intake.  A stall here is the
                        # runner's, not the engine's (step.stall_s).
                        tr.complete("runner.between_steps", t_back,
                                    track=self._trace_track,
                                    args={"step": eng.launches + 1,
                                          "taken": taken})
                    self._step_started = (gen, time.monotonic())
                    try:
                        eng.step()
                    finally:
                        ss = self._step_started
                        if ss is not None and ss[0] == gen:
                            self._step_started = None
                    t_back = tr.now() if tr is not None else 0
                    continue
            except Exception:
                # the boundary that must keep running: say what failed
                # (a kernel Mosaic refused surfaces here, with its whole
                # message) before recovery replaces or stops the engine
                _log.exception("engine step failed (generation %d)", gen)
                newgen = self._recover(gen)
                if newgen is None:
                    return
                gen = newgen
                continue
            with self._lock:
                idle = not self._inbox and not self._aborts \
                    and not self._stopped
            if idle:
                t_back = 0      # an idle wait is not a turn between steps
                self._wake.wait(self.idle_wait_s)
                self._wake.clear()
