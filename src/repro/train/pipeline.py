"""Fine-grained training pipeline (paper §5) + straggler mitigation.

* ``Prefetcher``: background sampling server (batch generation + neighbor
  sampling + the host phase of feature extraction) running ahead of the
  device — the inter-batch pipeline of Figure 7.  Two build modes:

    batch_fn(step) -> item      one callable builds the whole step
    part_fns=[fn, ...]          one callable per device; the parts of one
                                step build **concurrently** on a worker
                                pool and are delivered as a list in
                                device order

  The pool mode is what keeps a multi-device host phase off the critical
  path: per-device spec builds are independent (each device owns its RNG,
  observer and accounting row; shared tallies take the counter's lock), so
  they fan out across ``workers`` threads, while the step sequence itself
  stays serial — ``pre_batch_hook(step)`` runs strictly *between* steps,
  after every build of step ``i`` has finished (the gather of part futures
  is the barrier) and before any build of step ``i+1`` starts.  That
  serialization is what lets the online cache manager mutate cache
  residency between (never during) spec builds without a lock.

  ``summary()`` reports per-batch host build/pack time *and* queue-dry
  time — how long ``get()`` sat waiting on an empty queue, i.e. the time
  the device would have stalled for host work (the quantity the
  ``pipeline_stall`` benchmark attributes wins to).
* ``LookaheadWindow``: the sample-ahead driver behind the tiered feature
  store's Ginex-style eviction — decouples a builder's sampling sub-phase
  from its feature fill so batch ``N``'s fill runs with batches
  ``N+1..N+W`` already sampled, their store-request sets announced (the
  next-use index eviction reads) and their SSD reads prefetching.
* ``StragglerMonitor``: EWMA step-time tracker flagging outlier steps; at
  fleet scale its per-host summaries feed backup-task dispatch — here it
  drives logging and the queue-depth guard.
"""
from __future__ import annotations

import os
import queue
import threading
import time
from collections import deque
from concurrent.futures import ThreadPoolExecutor, wait
from typing import Callable, List, Optional

# get() polls at this interval so a worker exception raised while the
# consumer is blocked surfaces within ~one tick, not after the full timeout
_POLL_S = 0.05


class Prefetcher:
    def __init__(self, batch_fn: Optional[Callable[[int], dict]] = None,
                 depth: int = 2, limit: Optional[int] = None,
                 pre_batch_hook: Optional[Callable[[int], None]] = None,
                 pack_fn: Optional[Callable[[dict], dict]] = None, *,
                 part_fns: Optional[List[Callable[[int], object]]] = None,
                 part_group_sizes: Optional[List[int]] = None,
                 workers: Optional[int] = None,
                 extra_summary: Optional[Callable[[], dict]] = None,
                 telemetry=None, start_step: int = 0,
                 max_restarts: int = 0, fault_plan=None):
        """``limit`` bounds the total number of batches produced (the train
        loop passes its step count): without it the worker keeps building
        ahead until close(), so side effects in ``batch_fn`` — notably
        traffic accounting — would include a timing-dependent tail of
        batches nobody consumes.

        ``pre_batch_hook(step)`` runs on the coordinator thread immediately
        before building batch ``step`` — serialized with every build (in
        pool mode the futures barrier guarantees no build is in flight),
        which is what lets the online cache manager mutate cache residency
        between (never during) spec builds without a lock.  Hook exceptions
        propagate exactly like build exceptions.

        ``part_fns`` switches to pool mode: each step's batch is the list
        ``[fn(step) for fn in part_fns]`` with the parts built concurrently
        on ``workers`` threads.  The default is CPU-budgeted — one thread
        per part, capped at ``os.cpu_count() - 1`` so the build pool never
        starves the consumer (and, on a CPU-backend simulator, the XLA
        compute itself); on a 2-core box it degrades to a serial build.
        ``workers=1`` builds serially in order.  The delivered list is
        always in ``part_fns`` order regardless of completion order.

        ``part_group_sizes`` nests the delivered parts list: the flat
        ``part_fns`` results (still built concurrently across the whole
        pool) are regrouped into consecutive sublists of these sizes — the
        hierarchical executor passes one group per clique, so ``pack_fn``
        and the consumer see the clique structure directly instead of
        re-slicing a flat device list.

        ``pack_fn`` is an optional second host phase applied to each
        built batch on the coordinator thread (timed separately in
        ``summary()``): the sharded executor packs per-clique specs into
        mesh-sharded arrays here, so the consumer thread dequeues batches
        that are already in device-shardable layout.

        ``extra_summary`` is an optional zero-arg callable merged into
        ``summary()`` at read time — the train loop uses it to surface
        builder-side stats (deferred host-fallback timing) next to the
        queue stats without the Prefetcher knowing about builders.  Its
        keys must not collide with the built-in build-stat keys: a
        collision raises instead of silently overwriting a stat.

        ``telemetry`` (a repro.obs.Telemetry) instruments the pipeline:
        spans around each step's build/pack and the refresh hook (on the
        prefetch thread) and around every ``get()`` (consumer thread,
        carrying the step of the batch it returned).  With the default
        ``None`` not one telemetry instruction runs.

        ``start_step`` is the first step the worker builds (a resumed run
        passes its checkpoint boundary so the batch sequence — and every
        side effect of building it — continues instead of replaying from
        0); ``limit`` still counts batches produced *from there*.

        ``max_restarts`` bounds worker respawns: when the build thread
        dies of an ordinary ``Exception`` a fresh thread re-enters the
        loop at the *same* step (``self._step`` only advances on success,
        and the injected-fault site sits before the hook/build consume
        any RNG, so a respawned build replays nothing) — past the bound,
        or on ``KeyboardInterrupt``-class failures, the exception
        surfaces through ``get()``/``close()`` exactly as before.
        ``fault_plan`` (a ``repro.train.resilience.FaultPlan``) injects
        ``prefetch_build`` faults at the step boundary for tests and the
        chaos bench."""
        if (batch_fn is None) == (part_fns is None):
            raise ValueError("pass exactly one of batch_fn / part_fns")
        self._batch_fn = batch_fn
        self._part_fns = list(part_fns) if part_fns is not None else None
        if self._part_fns is not None and not self._part_fns:
            raise ValueError("part_fns must not be empty")
        self._group_sizes = (list(part_group_sizes)
                             if part_group_sizes is not None else None)
        if self._group_sizes is not None:
            if self._part_fns is None:
                raise ValueError("part_group_sizes needs part_fns")
            if (any(s < 1 for s in self._group_sizes)
                    or sum(self._group_sizes) != len(self._part_fns)):
                raise ValueError(
                    f"part_group_sizes {self._group_sizes} must be positive "
                    f"and sum to len(part_fns) == {len(self._part_fns)}")
        n_parts = len(self._part_fns) if self._part_fns is not None else 1
        if workers is None:
            workers = max(1, (os.cpu_count() or 2) - 1)
        self._workers = max(1, min(int(workers), n_parts))
        self._pool = (ThreadPoolExecutor(max_workers=self._workers,
                                         thread_name_prefix="prefetch-build")
                      if self._part_fns is not None and self._workers > 1
                      else None)
        self._q: queue.Queue = queue.Queue(maxsize=depth)
        self._stop = threading.Event()
        self._step = int(start_step)
        self._start = int(start_step)
        self._limit = limit
        self._max_restarts = int(max_restarts)
        self._fault_plan = fault_plan
        self.worker_deaths = 0
        self.worker_restarts = 0
        self._hook = pre_batch_hook
        self._pack_fn = pack_fn
        self._extra_summary = extra_summary
        self._tele = telemetry
        self._build_s = 0.0
        self._pack_s = 0.0
        self._built = 0
        self._dry_s = 0.0
        self._gets = 0
        self._thread = threading.Thread(target=self._worker, daemon=True)
        self._exc: Optional[BaseException] = None
        self._exc_raised = False
        self._thread.start()

    def _regroup(self, parts: List[object]) -> List[object]:
        """Flat part results -> consecutive sublists of part_group_sizes
        (identity without grouping)."""
        if self._group_sizes is None:
            return parts
        out, i = [], 0
        for sz in self._group_sizes:
            out.append(parts[i:i + sz])
            i += sz
        return out

    def _build(self, step: int):
        if self._part_fns is None:
            return self._batch_fn(step)
        if self._pool is None:
            return self._regroup([fn(step) for fn in self._part_fns])
        futs = [self._pool.submit(fn, step) for fn in self._part_fns]
        # barrier: every part of step i lands before this returns (and so
        # before the next pre_batch_hook), even if one of them failed
        wait(futs)
        # f.result() raises the first part failure
        return self._regroup([f.result() for f in futs])

    def _worker(self):
        """Thread target: run the build loop, respawning (bounded) on an
        ordinary Exception.  The loop re-enters at the step that failed —
        ``self._step`` advances only after a successful build+enqueue, and
        the injection site fires before the hook or build run, so a
        respawned attempt replays no RNG draw and no accounting."""
        try:
            self._worker_loop()
        except Exception as e:
            self.worker_deaths += 1
            if (self.worker_restarts < self._max_restarts
                    and not self._stop.is_set()):
                self.worker_restarts += 1
                t = threading.Thread(target=self._worker, daemon=True)
                self._thread = t
                t.start()
            else:
                self._exc = e  # surfaced on next get()/close()
        except BaseException as e:  # never restarted (interpreter teardown)
            self._exc = e

    def _worker_loop(self):
        tele = self._tele
        while not self._stop.is_set():
            if self._limit is not None \
                    and self._step - self._start >= self._limit:
                return
            if self._fault_plan is not None:
                self._fault_plan.raise_if("prefetch_build", step=self._step)
            if self._hook is not None:
                if tele is not None:
                    with tele.span("refresh_hook", step=self._step):
                        self._hook(self._step)
                else:
                    self._hook(self._step)
            t0 = time.perf_counter()
            if tele is not None:
                with tele.span("prefetch_build", step=self._step):
                    batch = self._build(self._step)
            else:
                batch = self._build(self._step)
            self._build_s += time.perf_counter() - t0
            if self._pack_fn is not None:
                t0 = time.perf_counter()
                if tele is not None:
                    with tele.span("prefetch_pack", step=self._step):
                        batch = self._pack_fn(batch)
                else:
                    batch = self._pack_fn(batch)
                self._pack_s += time.perf_counter() - t0
            item = (self._step, batch)
            self._built += 1
            self._step += 1
            while not self._stop.is_set():
                try:
                    self._q.put(item, timeout=0.1)
                    break
                except queue.Full:
                    continue

    def get(self, timeout: float = 60.0) -> dict:
        """Next prefetched batch.  Polls in short intervals so a worker
        exception surfaces promptly even while this thread is blocked on an
        empty queue (a dead worker used to mean a bare ``queue.Empty``
        after the full timeout).  Wall time spent in here is accumulated as
        queue-dry (device-stall) time for ``summary()`` (and, with
        telemetry, a consumer-thread ``prefetch_get`` span that carries
        the step of the batch it returned)."""
        if self._tele is None:
            return self._get(timeout)[1]
        with self._tele.span("prefetch_get") as sp:
            step, item = self._get(timeout)
            sp.step = step
            return item

    def _get(self, timeout: float) -> tuple:
        """(step, batch) of the next prefetched batch."""
        t0 = time.perf_counter()
        deadline = t0 + timeout
        try:
            while True:
                if self._exc is not None:
                    self._exc_raised = True
                    raise self._exc
                remaining = deadline - time.perf_counter()
                if remaining <= 0:
                    raise queue.Empty
                try:
                    item = self._q.get(timeout=min(_POLL_S, remaining))
                except queue.Empty:
                    continue
                self._gets += 1
                return item
        finally:
            self._dry_s += time.perf_counter() - t0

    def summary(self) -> dict:
        """Host-phase build stats plus what the device actually stalled on:
        ``queue_dry_s_*`` is time ``get()`` spent waiting for the queue —
        with a deep-enough queue and a fast-enough host phase it stays near
        zero, and any growth is directly attributable device idle time."""
        out = {"batches_built": self._built,
               "gets": self._gets,
               "host_build_s_total": self._build_s,
               "host_build_s_mean": self._build_s / max(self._built, 1),
               "host_pack_s_total": self._pack_s,
               "host_pack_s_mean": self._pack_s / max(self._built, 1),
               "queue_dry_s_total": self._dry_s,
               "queue_dry_s_mean": self._dry_s / max(self._gets, 1),
               "build_workers": self._workers,
               "worker_deaths": self.worker_deaths,
               "worker_restarts": self.worker_restarts}
        if self._extra_summary is not None:
            extra = self._extra_summary()
            clash = sorted(set(extra) & set(out))
            if clash:
                # a silent dict.update here used to let a builder-side key
                # shadow a build stat; namespace the extra keys instead
                raise ValueError(
                    f"extra_summary keys collide with build stats: {clash} "
                    "— namespace them (e.g. 'sampling/...')")
            out.update(extra)
        return out

    def publish_metrics(self, reg, base: Optional[dict] = None) -> None:
        """Queue/build tallies for the telemetry registry (repro.obs),
        pulled at snapshot boundaries: totals mirror ``summary()`` (the
        per-observation histograms are fed live from the hot path when
        telemetry is attached).  ``base`` adds the folded totals of
        *closed* predecessor prefetchers (the elastic remesh path replaces
        the pipeline mid-run) so the registry counters stay monotonic
        across the swap — keyed by ``summary()`` names."""
        b = base or {}

        def tot(key, v):
            return v + b.get(key, 0)

        reg.counter("prefetch.batches_built").set_total(
            tot("batches_built", self._built))
        reg.counter("prefetch.gets").set_total(tot("gets", self._gets))
        reg.counter("prefetch.build_s").set_total(
            tot("host_build_s_total", self._build_s))
        reg.counter("prefetch.pack_s").set_total(
            tot("host_pack_s_total", self._pack_s))
        reg.counter("prefetch.queue_dry_s").set_total(
            tot("queue_dry_s_total", self._dry_s))
        reg.counter("fault.worker_deaths").set_total(
            tot("worker_deaths", self.worker_deaths))
        reg.counter("recovery.worker_restarts").set_total(
            tot("worker_restarts", self.worker_restarts))
        reg.gauge("prefetch.queue_depth").set(self._q.qsize())
        reg.gauge("prefetch.build_workers").set(self._workers)

    def close(self):
        """Stop the worker.  A worker exception that was never surfaced via
        ``get()`` re-raises here — a failure in the final prefetched batches
        (or in a refresh hook) must not be silently swallowed at shutdown."""
        self._stop.set()
        t = self._thread
        t.join(timeout=5)
        if self._thread is not t:
            # a respawn raced the stop flag: join the replacement too
            self._thread.join(timeout=5)
        if self._pool is not None:
            self._pool.shutdown(wait=False)
        if self._exc is not None and not self._exc_raised:
            self._exc_raised = True
            raise self._exc


class LookaheadWindow:
    """One device's sample-ahead window over a split batch builder.

    ``build(step)`` is a drop-in replacement for
    ``builder.build_spec(...)`` inside a Prefetcher part function, except
    that before filling step ``N`` it tops the window up through step
    ``N+window``: each future step is *sampled* (``sample_fn(step)`` —
    the per-step seed draw plus ``builder.sample_spec``, i.e. ALL of that
    step's RNG consumption, still executed strictly in step order, so
    batches stay bitwise identical to the unwindowed pipeline), its
    store-request set is announced to the tiered store (feeding the
    next-use index the lookahead eviction policy reads) and its SSD read
    is prefetched onto the store's I/O pool.  Only then does the front
    spec get its RNG-free ``fill_spec`` — with ``window`` batches of
    future knowledge banked.

    ``limit`` caps sampling at the run's final step (exclusive, absolute)
    so the window never draws (or accounts) steps nobody will consume —
    totals stay identical to the unwindowed run.  ``start`` is the first
    step the window samples (a resumed run passes its checkpoint boundary
    so the pre-sampling continues the journaled RNG sequence instead of
    replaying from 0).  One window per device part-fn: the Prefetcher
    pool may run devices concurrently, but each window instance is only
    ever driven by its own device's strictly-sequential steps."""

    def __init__(self, builder, store, sample_fn: Callable[[int], object],
                 window: int = 4, limit: Optional[int] = None, dev: int = 0,
                 start: int = 0):
        if window < 0:
            raise ValueError(f"window must be >= 0, got {window}")
        self.builder = builder
        self.store = store
        self.sample_fn = sample_fn
        self.window = int(window)
        self.limit = limit
        self.dev = dev
        self._pending: deque = deque()  # (step, sampled spec) in step order
        self._next = int(start)  # next step to sample

    def build(self, step: int):
        while (self._next <= step + self.window
               and (self.limit is None or self._next < self.limit)):
            s = self._next
            spec = self.sample_fn(s)
            ids = self.builder.store_request_ids(spec)
            self.store.announce(s, ids)
            self.store.prefetch(s, ids, dev=self.dev)
            self._pending.append((s, spec))
            self._next += 1
        got, spec = self._pending.popleft()
        if got != step:
            raise RuntimeError(
                f"LookaheadWindow fed out of order: asked for step {step}, "
                f"front of window is {got} (one window per device; steps "
                "must arrive sequentially)")
        return self.builder.fill_spec(spec, step=step)


class StragglerMonitor:
    def __init__(self, alpha: float = 0.1, threshold: float = 2.5):
        self.alpha = alpha
        self.threshold = threshold
        self.ewma: Optional[float] = None
        self.stragglers = 0
        self.steps = 0
        self.worst: float = 0.0

    def record(self, step_time: float) -> bool:
        """Returns True if this step is a straggler."""
        self.steps += 1
        self.worst = max(self.worst, step_time)
        if self.ewma is None:
            self.ewma = step_time
            return False
        is_straggler = step_time > self.threshold * self.ewma
        if is_straggler:
            self.stragglers += 1
        else:
            self.ewma = (1 - self.alpha) * self.ewma + self.alpha * step_time
        return is_straggler

    def summary(self) -> dict:
        return {"steps": self.steps, "ewma_s": self.ewma,
                "stragglers": self.stragglers, "worst_s": self.worst}

    def publish_metrics(self, reg) -> None:
        """Straggler verdicts for the telemetry registry (repro.obs):
        flagged/observed step counters (monotonic, so windowed deltas
        telescope) plus the EWMA and worst step time as gauges.  The
        per-step time *histogram* is fed live by the train loop
        (``step.time_s`` / ``straggler.step_time_s``); this mirror runs
        only at snapshot boundaries."""
        reg.counter("straggler.flagged").set_total(self.stragglers)
        reg.counter("straggler.steps").set_total(self.steps)
        reg.gauge("straggler.ewma_s").set(self.ewma or 0.0)
        reg.gauge("straggler.worst_s").set(self.worst)
