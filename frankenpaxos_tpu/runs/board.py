"""Driving a device vote board from a role: the one copy.

A role that counts votes on a ``TpuQuorumTracker`` (the MultiPaxos proxy
leader, the Mencius proxy leader) hands every drain's votes to the
tracker, whose ``drain`` only dispatches. :class:`BoardDriver` does the
rest, the same for every such role:

  * after a drain, publishes the tracker's counts to ``/metrics`` and
    passes what it dispatched on: to ONE daemon collector thread on a
    threaded transport (fetches keep dispatch order, and the event loop
    never blocks on a device fetch), or, on a ``SimTransport``, to a
    flush timer that collects synchronously (tests fire it);
  * the collector thread posts each fetched batch of ``(slot, round)``
    back onto the event loop, where the role's ``emit`` runs.

Stages (``Actor.trace_stage``): ``collect`` on the collector thread,
``dispatch-wait`` for the time a dispatch queued for it and
``handback-wait`` for the time its results waited for the loop. The
role opens ``drain`` and ``fan-out`` itself.

Counters, under the role's ``prefix``: ``<prefix>_tpu_drains_total`` and
``<prefix>_tpu_votes_total`` (one child of ``path``, ``device``),
``<prefix>_tpu_launches_total``, ``<prefix>_tpu_window_violations_total``
and ``<prefix>_dense_votes_total`` (the votes that reached the device in
a dense block); ``<prefix>_tpu_dispatches_total``, the summaries
``<prefix>_tpu_inflight_at_dispatch`` and ``<prefix>_tpu_collect_seconds``.
Colocated roles share one series of each.
"""

from __future__ import annotations

import time

# SimTransport only: how long after a drain the flush timer collects
# what the tracker dispatched, if no further message came first.
TPU_FLUSH_PERIOD_S = 0.005


class BoardDriver:
    def __init__(self, actor, tracker, emit, collectors, prefix: str):
        self.actor = actor
        self.tracker = tracker
        self.emit = emit
        self.metrics_dispatches = collectors.counter(
            f"{prefix}_tpu_dispatches_total")
        # Overlap instrumentation: how many dispatches are in flight
        # when a new one is queued (depth 0 = no overlap, every fetch is
        # serialized behind its drain) and how long each device collect
        # blocks the collector thread.
        self.metrics_inflight = collectors.summary(
            f"{prefix}_tpu_inflight_at_dispatch")
        self.metrics_collect = collectors.summary(
            f"{prefix}_tpu_collect_seconds")
        # The tracker's own counts, published after every drain, in the
        # order publish_counts reads them. The one child of ``path`` is
        # what benchmark/harness/readings.py asks for by name.
        drains = collectors.counter(f"{prefix}_tpu_drains_total",
                                    labels=("path",))
        votes = collectors.counter(f"{prefix}_tpu_votes_total",
                                   labels=("path",))
        self.metrics_work = (
            drains.labels("device"), votes.labels("device"),
            collectors.counter(f"{prefix}_tpu_launches_total"),
            collectors.counter(f"{prefix}_tpu_window_violations_total"),
            collectors.counter(f"{prefix}_dense_votes_total"))
        self._published = (0,) * len(self.metrics_work)
        self.flush_timer = None
        self.collector = None
        # 1 while the collector thread is inside a device collect (that
        # dispatch has left the queue but is still in flight); single
        # writer, read for metrics.
        self.collecting = 0
        # Branch on the transport's CAPABILITY (threaded event loop),
        # not on whether its loop happens to exist yet: a TcpTransport
        # actor constructed before start() must still get the collector
        # thread, and a SimTransport must never (its actors run inline
        # on the caller's thread).
        if actor.transport.threaded:
            import queue
            import threading

            self.collector = queue.Queue()
            # A daemon thread (vs a ThreadPoolExecutor, whose threads
            # are joined at interpreter exit) cannot hold up process
            # shutdown.
            threading.Thread(target=self._collect_loop, daemon=True,
                             name="tpu-collect").start()
        else:
            def flush_pending():
                self.collect_all()
                if self.tracker.has_pending():
                    self.flush_timer.start()

            self.flush_timer = actor.timer("tpuDrainFlush",
                                           TPU_FLUSH_PERIOD_S,
                                           flush_pending)

    def _collect_loop(self) -> None:
        # This thread's own stage accumulators (the loop's belong to the
        # loop), where /metrics is on.
        metrics = self.actor.transport.runtime_metrics
        stages = None if metrics is None else metrics.thread_stages()
        while True:
            dispatch, queued_at = self.collector.get()
            if stages is not None:
                stages.stage("dispatch-wait").add(
                    time.perf_counter() - queued_at)
            self.collecting = 1
            try:
                self._collect_and_post(dispatch, stages)
            finally:
                self.collecting = 0

    def hand_over(self) -> None:
        """After a drain: publish the tracker's counts, and pass what it
        dispatched to the collector thread (or arm the sim's flush
        timer)."""
        self.publish_counts()
        if self.collector is not None:
            while True:
                dispatch = self.tracker.take_dispatch()
                if dispatch is None:
                    break
                self.metrics_dispatches.inc()
                # Depth includes the dispatch the collector thread is
                # currently blocked on (it left the queue but is in
                # flight): a healthy one-deep pipeline must read 1, not
                # 0 -- 0 means every fetch is serialized.
                self.metrics_inflight.observe(self.collector.qsize()
                                              + self.collecting)
                # With the time it was queued: the collector thread
                # accounts the wait as ``dispatch-wait``.
                self.collector.put((dispatch, time.perf_counter()))
        elif self.flush_timer is not None:
            # (Re)arm the quiescence flush while a dispatch is in
            # flight; the timer collects it if no further messages come.
            self.flush_timer.stop()
            if self.tracker.has_pending():
                self.flush_timer.start()

    def publish_counts(self) -> None:
        """The tracker's work counts into /metrics, as increments:
        colocated roles share one series."""
        t = self.tracker
        counts = (t.device_drains, t.device_votes, t.device_launches,
                  t.checker.window_violations, t.dense_votes)
        if counts == self._published:
            return
        for series, now, then in zip(self.metrics_work, counts,
                                     self._published):
            series.inc(now - then)
        self._published = counts

    def _collect_and_post(self, dispatch, stages) -> None:
        """Runs on the collector thread: block on the device fetch, then
        hand the results back to the single-threaded event loop.
        ``stages`` are this thread's stage accumulators, or None."""
        try:
            # Stage ``collect`` and the collect summary share ONE clock
            # pair, taken by hand: between collect() returning and the
            # hand-back nothing is done but the second reading (and,
            # while a device trace runs, closing the annotation); the
            # adds come after the results are on their way.
            stage = span = None
            if stages is not None:
                stage = stages.stage("collect")
                stages.refresh()
                if stages.annotation is not None:
                    span = stage.open_span()
            t0 = time.perf_counter()
            results = self.tracker.collect(dispatch)
            t1 = time.perf_counter()
            if span is not None:
                span.__exit__(None, None, None)
            if results:
                self.actor.transport.loop.call_soon_threadsafe(
                    self._emit_handed_back, results, t1)
            if stage is not None:
                stage.add(t1 - t0)
            self.metrics_collect.observe(t1 - t0)
        except RuntimeError as e:
            # Loop closed during teardown: dropping in-flight results is
            # expected, but say so.
            self.actor.logger.debug(f"tpu collect post skipped: {e!r}")
        except Exception as e:  # noqa: BLE001 - surface, don't swallow
            # A swallowed collector error would silently drop this
            # dispatch's chosen broadcasts and wedge its clients.
            self.actor.logger.error(f"tpu collect failed: {e!r}")

    def _emit_handed_back(self, keys, collected_at: float) -> None:
        """On the event loop: what the collector thread fetched. Stage
        ``handback-wait`` is the time it waited for the loop."""
        metrics = self.actor.transport.runtime_metrics
        if metrics is not None:
            metrics.observe_stage("handback-wait",
                                  time.perf_counter() - collected_at)
        self.emit(keys)

    def collect_all(self) -> None:
        """Collect every dispatch in flight, on the caller's thread (the
        sim's flush timer, and tests)."""
        while True:
            dispatch = self.tracker.take_dispatch()
            if dispatch is None:
                return
            self.emit(self.tracker.collect(dispatch))
