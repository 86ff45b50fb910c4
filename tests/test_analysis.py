"""paxlint self-tests: every rule family catches its seeded violation
class (and stays quiet on the clean twin), pragmas suppress, the
baseline round-trips, and the repo itself gates green.

Fixtures are tiny synthetic packages written to a tmp dir -- paxlint is
purely AST-based, so nothing is imported or executed.
"""

from __future__ import annotations

import subprocess
import sys
import textwrap

from frankenpaxos_tpu.analysis import baseline as baseline_mod
from frankenpaxos_tpu.analysis.core import Project, run_rules


def project(tmp_path, files: dict) -> Project:
    """A throwaway project: {relative path under pkg/: source}."""
    for rel, source in files.items():
        path = tmp_path / "pkg" / rel
        path.parent.mkdir(parents=True, exist_ok=True)
        path.write_text(textwrap.dedent(source))
    return Project(str(tmp_path), package="pkg")


def rules_of(findings) -> set:
    return {f.rule for f in findings}


# --- PAX1xx: actor contract -------------------------------------------------

ACTOR_PREAMBLE = """\
    import threading
    import time

    class Actor:
        def receive(self, src, message): ...
        def on_drain(self): ...
        def timer(self, name, delay_s, f): ...
        def send(self, dst, message): ...
        def broadcast(self, dsts, message): ...
"""


def test_pax101_threading_in_handler(tmp_path):
    findings = run_rules(project(tmp_path, {"a.py": ACTOR_PREAMBLE + """
    class Bad(Actor):
        def receive(self, src, message):
            threading.Thread(target=self.work).start()
    """}))
    assert "PAX101" in rules_of(findings)
    f = next(f for f in findings if f.rule == "PAX101")
    assert f.scope == "Bad.receive"


def test_pax101_reaches_self_call_closure(tmp_path):
    findings = run_rules(project(tmp_path, {"a.py": ACTOR_PREAMBLE + """
    class Bad(Actor):
        def receive(self, src, message):
            self._helper()

        def _helper(self):
            threading.Event().wait()
    """}))
    assert any(f.rule == "PAX101" and f.scope == "Bad._helper"
               for f in findings)


def test_pax101_allows_construction_time_threads(tmp_path):
    """__init__ is not a handler: the ProxyLeader collector-thread
    pattern stays legal."""
    findings = run_rules(project(tmp_path, {"a.py": ACTOR_PREAMBLE + """
    class Fine(Actor):
        def __init__(self):
            threading.Thread(target=lambda: None, daemon=True).start()

        def receive(self, src, message):
            pass
    """}))
    assert "PAX101" not in rules_of(findings)


def test_pax102_lock_in_handler(tmp_path):
    findings = run_rules(project(tmp_path, {"a.py": ACTOR_PREAMBLE + """
    class Bad(Actor):
        def receive(self, src, message):
            self.lock.acquire()
    """}))
    assert "PAX102" in rules_of(findings)


def test_pax103_sleep_in_handler(tmp_path):
    findings = run_rules(project(tmp_path, {"a.py": ACTOR_PREAMBLE + """
    class Bad(Actor):
        def on_drain(self):
            time.sleep(0.1)
    """}))
    assert any(f.rule == "PAX103" and f.scope == "Bad.on_drain"
               for f in findings)


def test_pax103_sleep_in_timer_callback(tmp_path):
    findings = run_rules(project(tmp_path, {"a.py": ACTOR_PREAMBLE + """
    class Bad(Actor):
        def receive(self, src, message):
            self.timer("t", 1.0, self._fire)

        def _fire(self):
            time.sleep(1)
    """}))
    assert any(f.rule == "PAX103" and f.scope == "Bad._fire"
               for f in findings)


def test_pax104_non_transport_timer(tmp_path):
    findings = run_rules(project(tmp_path, {"a.py": ACTOR_PREAMBLE + """
    class Bad(Actor):
        def __init__(self, loop):
            threading.Timer(1.0, self._fire).start()
            loop.call_later(1.0, self._fire)

        def receive(self, src, message):
            pass

        def _fire(self):
            pass
    """}))
    assert sum(f.rule == "PAX104" for f in findings) == 2


def test_pax105_shared_module_state(tmp_path):
    findings = run_rules(project(tmp_path, {"a.py": ACTOR_PREAMBLE + """
    SHARED = {}

    class A(Actor):
        def receive(self, src, message):
            SHARED[src] = message

    class B(Actor):
        def receive(self, src, message):
            return SHARED.get(src)
    """}))
    assert any(f.rule == "PAX105" and f.detail == "SHARED"
               for f in findings)


def test_pax105_single_class_use_is_fine(tmp_path):
    findings = run_rules(project(tmp_path, {"a.py": ACTOR_PREAMBLE + """
    CACHE = {}

    class A(Actor):
        def receive(self, src, message):
            CACHE[src] = message

    class B(Actor):
        def receive(self, src, message):
            pass
    """}))
    assert "PAX105" not in rules_of(findings)


def test_pax106_send_from_thread_target(tmp_path):
    findings = run_rules(project(tmp_path, {"a.py": ACTOR_PREAMBLE + """
    class Bad(Actor):
        def __init__(self):
            threading.Thread(target=self._worker, daemon=True).start()

        def receive(self, src, message):
            pass

        def _worker(self):
            self.send(("h", 1), "result")
    """}))
    assert any(f.rule == "PAX106" and f.scope == "Bad._worker"
               for f in findings)


def test_pax110_acceptor_set_read_in_epoch_role(tmp_path):
    findings = run_rules(project(tmp_path, {"a.py": ACTOR_PREAMBLE + """
    class Bad(Actor):
        def __init__(self, config):
            self.config = config
            self.epochs = object()

        def receive(self, src, message):
            group = self.config.acceptor_addresses[0]
            self.send(group[0], message)
    """}))
    assert any(f.rule == "PAX110" and f.scope == "Bad.receive"
               for f in findings)


def test_pax110_reaches_handler_closure_and_quorum_grid(tmp_path):
    findings = run_rules(project(tmp_path, {"a.py": ACTOR_PREAMBLE + """
    class Bad(Actor):
        def __init__(self, config):
            self.config = config
            self.epochs = None

        def receive(self, src, message):
            self._fanout(message)

        def _fanout(self, message):
            grid = self.config.quorum_grid()
    """}))
    assert any(f.rule == "PAX110" and f.scope == "Bad._fanout"
               for f in findings)


def test_pax110_ignores_roles_without_epoch_store(tmp_path):
    findings = run_rules(project(tmp_path, {"a.py": ACTOR_PREAMBLE + """
    class Frozen(Actor):
        def __init__(self, config):
            self.config = config

        def receive(self, src, message):
            group = self.config.acceptor_addresses[0]
    """}))
    assert "PAX110" not in rules_of(findings)


def test_pax110_init_reads_are_fine(tmp_path):
    # Construction-time reads seed the store itself.
    findings = run_rules(project(tmp_path, {"a.py": ACTOR_PREAMBLE + """
    class Good(Actor):
        def __init__(self, config):
            self.config = config
            self.epochs = list(config.acceptor_addresses[0])

        def receive(self, src, message):
            members = self.epochs
    """}))
    assert "PAX110" not in rules_of(findings)


def test_pax110_pragma_suppresses(tmp_path):
    findings = run_rules(project(tmp_path, {"a.py": ACTOR_PREAMBLE + """
    class Pragmad(Actor):
        def __init__(self, config):
            self.config = config
            self.epochs = object()

        def receive(self, src, message):
            # paxlint: disable=PAX110
            group = self.config.acceptor_addresses[0]
    """}))
    assert "PAX110" not in rules_of(findings)


# --- PAX111: unbounded inbound buffers / sleep-retry loops (paxload) -------


def test_pax111_unbounded_list_inbox_in_handler(tmp_path):
    findings = run_rules(project(tmp_path, {"a.py": ACTOR_PREAMBLE + """
    class Bad(Actor):
        def __init__(self):
            self.inbox = []

        def receive(self, src, message):
            self.inbox.append(message)
    """}))
    assert any(f.rule == "PAX111" and f.detail == "self.inbox"
               for f in findings)


def test_pax111_unbounded_deque_via_closure(tmp_path):
    findings = run_rules(project(tmp_path, {"a.py": ACTOR_PREAMBLE + """
    import collections

    class Bad(Actor):
        def __init__(self):
            self.pending_frames = collections.deque()

        def receive(self, src, message):
            self._stash(message)

        def _stash(self, message):
            self.pending_frames.appendleft(message)
    """}))
    assert any(f.rule == "PAX111" and f.scope == "Bad._stash"
               for f in findings)


def test_pax111_maxlen_deque_and_len_guard_are_fine(tmp_path):
    findings = run_rules(project(tmp_path, {"a.py": ACTOR_PREAMBLE + """
    import collections

    class Capped(Actor):
        def __init__(self):
            self.inbox = collections.deque(maxlen=64)

        def receive(self, src, message):
            self.inbox.append(message)

    class Guarded(Actor):
        def __init__(self):
            self.queue = []

        def receive(self, src, message):
            if len(self.queue) < 64:
                self.queue.append(message)
    """}))
    assert "PAX111" not in rules_of(findings)


def test_pax111_inbox_full_admission_guard_is_fine(tmp_path):
    findings = run_rules(project(tmp_path, {"a.py": ACTOR_PREAMBLE + """
    class Admitted(Actor):
        def __init__(self, admission):
            self.admission = admission
            self.inbound = []

        def receive(self, src, message):
            if not self.admission.inbox_full(len(self.inbound)):
                self.inbound.append(message)
    """}))
    assert "PAX111" not in rules_of(findings)


def test_pax111_sleep_retry_loop_in_transport_code(tmp_path):
    findings = run_rules(project(tmp_path, {
        "runtime/conn.py": """
    import time

    def connect_with_retry(dial):
        while True:
            try:
                return dial()
            except OSError:
                time.sleep(0.5)
    """,
        # The same loop outside role/transport code is out of scope.
        "bench/poll.py": """
    import time

    def poll(ready):
        while not ready():
            time.sleep(0.5)
    """}))
    hits = [f for f in findings if f.rule == "PAX111"]
    assert [f.file for f in hits] == ["pkg/runtime/conn.py"]
    assert hits[0].detail == "time.sleep"


def test_pax111_nested_loops_report_one_finding_per_sleep(tmp_path):
    findings = run_rules(project(tmp_path, {"runtime/conn.py": """
    import time

    def connect_with_retry(dial):
        while True:
            for attempt in range(3):
                try:
                    return dial()
                except OSError:
                    time.sleep(0.5)
    """}))
    hits = [f for f in findings if f.rule == "PAX111"]
    assert len(hits) == 1


def test_pax111_sleep_in_function_defined_inside_loop_is_fine(tmp_path):
    findings = run_rules(project(tmp_path, {"runtime/conn.py": """
    import time

    def make_delayers(delays):
        # The closures are DEFINED in a loop but run elsewhere (on a
        # transport timer, say): not a sleeping retry loop.
        out = []
        for delay in delays:
            def wait(delay=delay):
                time.sleep(delay)
            out.append(wait)
        return out
    """}))
    assert "PAX111" not in rules_of(findings)


def test_pax111_pragma_suppresses(tmp_path):
    findings = run_rules(project(tmp_path, {"a.py": ACTOR_PREAMBLE + """
    class Pragmad(Actor):
        def __init__(self):
            self.inbox = []

        def receive(self, src, message):
            self.inbox.append(message)  # paxlint: disable=PAX111
    """}))
    assert "PAX111" not in rules_of(findings)


def test_pax106_call_soon_threadsafe_is_fine(tmp_path):
    findings = run_rules(project(tmp_path, {"a.py": ACTOR_PREAMBLE + """
    class Fine(Actor):
        def __init__(self, loop):
            self.loop = loop
            threading.Thread(target=self._worker, daemon=True).start()

        def receive(self, src, message):
            pass

        def _worker(self):
            self.loop.call_soon_threadsafe(self._emit, [1, 2])

        def _emit(self, results):
            self.send(("h", 1), results)
    """}))
    assert "PAX106" not in rules_of(findings)


# --- TPU2xx: hot-path rules -------------------------------------------------


def test_tpu201_block_until_ready_in_on_drain(tmp_path):
    findings = run_rules(project(tmp_path, {"a.py": """
    import jax

    class Tracker:
        def drain(self):
            jax.block_until_ready(self.board)

    class Role:
        def on_drain(self):
            self.tracker.drain()
    """}))
    assert any(f.rule == "TPU201" and f.scope == "Tracker.drain"
               for f in findings)


def test_tpu202_device_get_in_run_pipeline_handler(tmp_path):
    findings = run_rules(project(tmp_path, {"a.py": """
    import jax

    class Phase2aRun: ...

    class Role:
        def receive(self, src, message):
            if isinstance(message, Phase2aRun):
                self._handle_run(message)

        def _handle_run(self, run):
            return jax.device_get(run)
    """}))
    assert any(f.rule == "TPU202" and f.scope == "Role._handle_run"
               for f in findings)


def test_tpu203_blocking_fetch_of_async_dispatch(tmp_path):
    findings = run_rules(project(tmp_path, {"ops/kernel.py": """
    import numpy as np

    def fetch(checker, block):
        mask = checker.record_block_async(0, block)
        return np.asarray(mask)
    """}))
    assert any(f.rule == "TPU203" for f in findings)


def test_tpu203_host_asarray_is_fine(tmp_path):
    findings = run_rules(project(tmp_path, {"ops/kernel.py": """
    import numpy as np

    def pack(slots):
        return np.asarray(slots, dtype=np.int64)
    """}))
    assert "TPU203" not in rules_of(findings)


def test_tpu208_file_io_reachable_from_ops_kernel(tmp_path):
    """fsync / open reachable from ops/ kernel code is flagged -- WAL
    I/O must stay on the drain boundary, never inside a kernel."""
    findings = run_rules(project(tmp_path, {"ops/kernel.py": """
    import os

    def persist(path, board):
        f = open(path, "ab")
        f.write(board.tobytes())
        os.fsync(f.fileno())
    """}))
    tpu208 = [f for f in findings if f.rule == "TPU208"]
    assert {f.detail for f in tpu208} >= {"open", "os.fsync"}


def test_tpu208_transitive_through_helper(tmp_path):
    findings = run_rules(project(tmp_path, {
        "ops/kernel.py": """
    from pkg.wal import sync_log

    def drain_kernel(block):
        sync_log()
    """,
        "wal.py": """
    import os

    def sync_log():
        os.fsync(3)
    """}))
    assert any(f.rule == "TPU208" and f.scope == "sync_log"
               for f in findings)


def test_tpu208_fsync_in_on_drain_is_fine(tmp_path):
    """The drain boundary is exactly where WAL I/O belongs: fsync in
    an actor's on_drain (outside ops/) is NOT flagged."""
    findings = run_rules(project(tmp_path, {"roles.py": """
    import os

    class Role:
        def on_drain(self):
            self.wal_file.flush()
            os.fsync(self.wal_file.fileno())
    """}))
    assert "TPU208" not in rules_of(findings)


def test_tpu209_clock_read_in_ops_kernel(tmp_path):
    """A host clock read inside ops/ kernel code is flagged -- span
    timing belongs to the transports/drain (obs/), never kernels."""
    findings = run_rules(project(tmp_path, {"ops/kernel.py": """
    import time

    def check_block(board):
        t0 = time.perf_counter()
        result = board.sum()
        return result, time.perf_counter() - t0
    """}))
    tpu209 = [f for f in findings if f.rule == "TPU209"]
    assert {f.detail for f in tpu209} == {"time.perf_counter"}


def test_tpu209_trace_hook_reachable_from_ops_kernel(tmp_path):
    """Span-emitting hooks (trace_stage & friends) transitively
    reachable from a kernel are flagged at the reached site."""
    findings = run_rules(project(tmp_path, {
        "ops/kernel.py": """
    from pkg.helper import timed_step

    def record_and_check(board):
        return timed_step(board)
    """,
        "helper.py": """
    def timed_step(board):
        with board.owner.trace_stage("quorum-kernel"):
            return board.sum()
    """}))
    assert any(f.rule == "TPU209" and f.scope == "timed_step"
               and f.detail.endswith("trace_stage")
               for f in findings)


def test_tpu209_trace_hook_in_jitted_function(tmp_path):
    """Inside a jitted body the hook would run once at trace time and
    never again -- silently wrong, so it is flagged project-wide."""
    findings = run_rules(project(tmp_path, {"fast.py": """
    import time

    import jax

    @jax.jit
    def step(x):
        t0 = time.monotonic()
        return x + t0
    """}))
    assert any(f.rule == "TPU209" and f.scope == "step"
               for f in findings)


def test_tpu209_spans_in_on_drain_are_fine(tmp_path):
    """The drain path OUTSIDE kernels is exactly where stage spans
    belong: trace_stage/perf_counter in an actor's on_drain (not under
    ops/, not jitted) stays quiet."""
    findings = run_rules(project(tmp_path, {"roles.py": """
    import time

    class Role:
        def on_drain(self):
            with self.trace_stage("wal-fsync"):
                self.wal.sync()
            self.latency = time.perf_counter()
    """}))
    assert "TPU209" not in rules_of(findings)


def test_tpu209_summary_timer_not_a_clock_read(tmp_path):
    """``metrics.time()`` (the Summary timer) and bare ``time()`` are
    not clock reads; only the time-module entry points are."""
    findings = run_rules(project(tmp_path, {"ops/kernel.py": """
    def check(board, metrics):
        with metrics.time():
            return board.sum()
    """}))
    assert "TPU209" not in rules_of(findings)


def test_tpu204_coercion_of_traced_value(tmp_path):
    findings = run_rules(project(tmp_path, {"ops/kernel.py": """
    import jax

    @jax.jit
    def bad(x):
        return float(x)
    """}))
    assert any(f.rule == "TPU204" for f in findings)


def test_tpu205_python_if_on_traced_value(tmp_path):
    findings = run_rules(project(tmp_path, {"ops/kernel.py": """
    import jax
    import jax.numpy as jnp

    @jax.jit
    def bad(x):
        if x > 0:
            return x
        return -x
    """}))
    assert any(f.rule == "TPU205" for f in findings)


def test_tpu205_static_arg_if_is_fine(tmp_path):
    findings = run_rules(project(tmp_path, {"ops/kernel.py": """
    import functools

    import jax

    @functools.partial(jax.jit, static_argnums=(1,))
    def fine(x, flag):
        if flag:
            return x
        return -x
    """}))
    assert "TPU205" not in rules_of(findings)


def test_tpu206_nested_jit(tmp_path):
    findings = run_rules(project(tmp_path, {"ops/kernel.py": """
    import jax

    def hot(x):
        return jax.jit(lambda y: y + 1)(x)
    """}))
    assert any(f.rule == "TPU206" for f in findings)


def test_tpu207_loop_over_traced_shape(tmp_path):
    findings = run_rules(project(tmp_path, {"ops/kernel.py": """
    import jax

    @jax.jit
    def bad(x):
        total = 0
        for i in range(x.shape[0]):
            total = total + x[i]
        return total
    """}))
    assert any(f.rule == "TPU207" for f in findings)


# --- COD3xx: codec rules ----------------------------------------------------

CODEC_PREAMBLE = """\
    import dataclasses
    import struct

    class MessageCodec: ...

    def register_codec(codec): ...

    _I64 = struct.Struct("<q")
"""


def test_cod301_sent_message_without_codec(tmp_path):
    findings = run_rules(project(tmp_path, {
        "proto/messages.py": """
    import dataclasses

    @dataclasses.dataclass(frozen=True)
    class Hot:
        slot: int

    @dataclasses.dataclass(frozen=True)
    class Cold:
        round: int
    """,
        "proto/wire.py": CODEC_PREAMBLE + """
    from pkg.proto.messages import Hot

    class HotCodec(MessageCodec):
        message_type = Hot
        tag = 1

        def encode(self, out, message):
            out += _I64.pack(message.slot)

        def decode(self, buf, at):
            (slot,) = _I64.unpack_from(buf, at)
            return Hot(slot=slot), at + 8

    register_codec(HotCodec())
    """,
        "proto/role.py": """
    from pkg.proto.messages import Cold, Hot

    class Role:
        def receive(self, src, message):
            self.send(src, Hot(slot=1))
            self.send(src, Cold(round=2))
    """}))
    assert any(f.rule == "COD301" and f.detail == "Cold"
               for f in findings)
    assert not any(f.rule == "COD301" and f.detail == "Hot"
                   for f in findings)


def test_cod302_encode_decode_asymmetry(tmp_path):
    findings = run_rules(project(tmp_path, {
        "proto/messages.py": """
    import dataclasses

    @dataclasses.dataclass(frozen=True)
    class Msg:
        slot: int
        round: int
    """,
        "proto/wire.py": CODEC_PREAMBLE + """
    from pkg.proto.messages import Msg

    class MsgCodec(MessageCodec):
        message_type = Msg
        tag = 1

        def encode(self, out, message):
            out += _I64.pack(message.slot)  # forgets round

        def decode(self, buf, at):
            (slot,) = _I64.unpack_from(buf, at)
            return Msg(slot=slot, round=0), at + 8
    """}))
    assert any(f.rule == "COD302" and "round" in f.message
               for f in findings)


def test_cod302_symmetric_codec_is_clean(tmp_path):
    findings = run_rules(project(tmp_path, {
        "proto/messages.py": """
    import dataclasses

    @dataclasses.dataclass(frozen=True)
    class Msg:
        slot: int
    """,
        "proto/wire.py": CODEC_PREAMBLE + """
    from pkg.proto.messages import Msg

    class MsgCodec(MessageCodec):
        message_type = Msg
        tag = 1

        def encode(self, out, message):
            out += _I64.pack(message.slot)

        def decode(self, buf, at):
            (slot,) = _I64.unpack_from(buf, at)
            return Msg(slot=slot), at + 8
    """}))
    assert "COD302" not in rules_of(findings)


def test_cod302_same_named_messages_resolve_per_protocol(tmp_path):
    """Two protocols with same-named messages: each codec is checked
    against ITS protocol's dataclass, not a global name match."""
    findings = run_rules(project(tmp_path, {
        "p1/messages.py": """
    import dataclasses

    @dataclasses.dataclass(frozen=True)
    class Reply:
        a: int
    """,
        "p2/messages.py": """
    import dataclasses

    @dataclasses.dataclass(frozen=True)
    class Reply:
        b: int
    """,
        "p2/wire.py": CODEC_PREAMBLE + """
    from pkg.p2.messages import Reply

    class ReplyCodec(MessageCodec):
        message_type = Reply
        tag = 1

        def encode(self, out, message):
            out += _I64.pack(message.b)

        def decode(self, buf, at):
            (b,) = _I64.unpack_from(buf, at)
            return Reply(b=b), at + 8
    """}))
    assert "COD302" not in rules_of(findings)


# --- pragmas ----------------------------------------------------------------


def test_pragma_suppresses_on_same_line(tmp_path):
    findings = run_rules(project(tmp_path, {"a.py": ACTOR_PREAMBLE + """
    class Curated(Actor):
        def on_drain(self):
            time.sleep(0.1)  # paxlint: disable=PAX103
    """}))
    assert "PAX103" not in rules_of(findings)


def test_pragma_on_preceding_comment_block(tmp_path):
    findings = run_rules(project(tmp_path, {"a.py": ACTOR_PREAMBLE + """
    class Curated(Actor):
        def on_drain(self):
            # paxlint: disable=PAX103 -- justified: measured backoff
            # that the sim transport never executes.
            time.sleep(0.1)
    """}))
    assert "PAX103" not in rules_of(findings)


def test_pragma_on_def_line_scopes_whole_function(tmp_path):
    findings = run_rules(project(tmp_path, {"a.py": ACTOR_PREAMBLE + """
    class Curated(Actor):
        def on_drain(self):  # paxlint: disable=PAX103
            time.sleep(0.1)
            time.sleep(0.2)
    """}))
    assert "PAX103" not in rules_of(findings)


def test_pragma_only_disables_named_rule(tmp_path):
    findings = run_rules(project(tmp_path, {"a.py": ACTOR_PREAMBLE + """
    class Curated(Actor):
        def on_drain(self):
            time.sleep(0.1)  # paxlint: disable=PAX101
    """}))
    assert "PAX103" in rules_of(findings)


# --- baseline ---------------------------------------------------------------


def test_baseline_round_trip(tmp_path):
    proj = project(tmp_path, {"a.py": ACTOR_PREAMBLE + """
    class Bad(Actor):
        def on_drain(self):
            time.sleep(0.1)
    """})
    findings = run_rules(proj)
    assert findings
    path = str(tmp_path / "baseline.json")
    baseline_mod.write(path, findings)
    entries = baseline_mod.load(path)
    new, old, stale = baseline_mod.split(findings, entries)
    assert not new and not stale
    assert [f.key for f in old] == [f.key for f in findings]


def test_baseline_is_line_number_independent(tmp_path):
    proj = project(tmp_path, {"a.py": ACTOR_PREAMBLE + """
    class Bad(Actor):
        def on_drain(self):
            time.sleep(0.1)
    """})
    path = str(tmp_path / "baseline.json")
    baseline_mod.write(path, run_rules(proj))
    # Shift every line down: the finding must still match the baseline.
    src = (tmp_path / "pkg" / "a.py").read_text()
    (tmp_path / "pkg" / "a.py").write_text("# shifted\n# shifted\n" + src)
    shifted = run_rules(Project(str(tmp_path), package="pkg"))
    new, old, stale = baseline_mod.split(shifted,
                                         baseline_mod.load(path))
    assert not new and not stale and old


def test_new_finding_not_masked_by_baseline(tmp_path):
    proj = project(tmp_path, {"a.py": ACTOR_PREAMBLE + """
    class Bad(Actor):
        def on_drain(self):
            time.sleep(0.1)
    """})
    path = str(tmp_path / "baseline.json")
    baseline_mod.write(path, run_rules(proj))
    src = (tmp_path / "pkg" / "a.py").read_text()
    (tmp_path / "pkg" / "a.py").write_text(src + textwrap.dedent("""
    class Worse(Actor):
        def receive(self, src, message):
            time.sleep(1)
    """))
    findings = run_rules(Project(str(tmp_path), package="pkg"))
    new, old, stale = baseline_mod.split(findings,
                                         baseline_mod.load(path))
    assert any(f.scope == "Worse.receive" for f in new)
    assert all(f.scope != "Worse.receive" for f in old)


def test_stale_baseline_entries_reported(tmp_path):
    proj = project(tmp_path, {"a.py": ACTOR_PREAMBLE + """
    class Bad(Actor):
        def on_drain(self):
            time.sleep(0.1)
    """})
    path = str(tmp_path / "baseline.json")
    baseline_mod.write(path, run_rules(proj))
    (tmp_path / "pkg" / "a.py").write_text(
        textwrap.dedent(ACTOR_PREAMBLE))
    new, old, stale = baseline_mod.split(
        run_rules(Project(str(tmp_path), package="pkg")),
        baseline_mod.load(path))
    assert not new and not old and len(stale) == 1


# --- the repo itself gates green --------------------------------------------


def test_repo_passes_paxlint():
    """The acceptance gate: `python -m frankenpaxos_tpu.analysis` exits
    0 on this repository (everything fixed, pragma'd, or baselined)."""
    proc = subprocess.run(
        [sys.executable, "-m", "frankenpaxos_tpu.analysis"],
        capture_output=True, text=True, timeout=300)
    assert proc.returncode == 0, proc.stdout + proc.stderr
    assert "0 new findings" in proc.stdout


def test_exit_code_gates_on_seeded_violation(tmp_path):
    """CLI exit 1 on a repo with a fresh (unbaselined) violation."""
    (tmp_path / "frankenpaxos_tpu").mkdir()
    (tmp_path / "frankenpaxos_tpu" / "bad.py").write_text(
        textwrap.dedent(ACTOR_PREAMBLE) + textwrap.dedent("""
    class Bad(Actor):
        def on_drain(self):
            time.sleep(0.5)
    """))
    proc = subprocess.run(
        [sys.executable, "-m", "frankenpaxos_tpu.analysis",
         "--root", str(tmp_path)],
        capture_output=True, text=True, timeout=300)
    assert proc.returncode == 1, proc.stdout + proc.stderr
    assert "PAX103" in proc.stdout


def test_list_rules():
    proc = subprocess.run(
        [sys.executable, "-m", "frankenpaxos_tpu.analysis",
         "--list-rules"], capture_output=True, text=True, timeout=300)
    assert proc.returncode == 0
    for rule in ("PAX101", "TPU201", "COD301", "COD302"):
        assert rule in proc.stdout


# --- FLOW4xx: message-topology contracts (paxflow) --------------------------

FLOW_PREAMBLE = """\
    import dataclasses

    class Actor:
        def receive(self, src, message): ...
        def on_drain(self): ...
        def timer(self, name, delay_s, f): ...
        def send(self, dst, message): ...
        def broadcast(self, dsts, message): ...
"""


def test_flow401_sent_but_unhandled(tmp_path):
    findings = run_rules(project(tmp_path, {
        "protocols/toy.py": FLOW_PREAMBLE + """
    @dataclasses.dataclass
    class Ping:
        n: int

    class Sender(Actor):
        def receive(self, src, message):
            self.send(src, Ping(n=1))
    """}))
    assert any(f.rule == "FLOW401" and f.scope == "Ping"
               for f in findings)


def test_flow401_quiet_when_handled_outside_protocols(tmp_path):
    """A handler in election/-style code outside the protocol tree
    still counts (the global handler scan)."""
    findings = run_rules(project(tmp_path, {
        "protocols/toy.py": FLOW_PREAMBLE + """
    from pkg.election import Ping

    class Sender(Actor):
        def receive(self, src, message):
            self.send(src, Ping(n=1))
    """,
        "election.py": FLOW_PREAMBLE + """
    @dataclasses.dataclass
    class Ping:
        n: int

    class Participant(Actor):
        def receive(self, src, message):
            if isinstance(message, Ping):
                pass
    """}))
    assert "FLOW401" not in rules_of(findings)


def test_flow401_payload_only_construction_is_not_a_send(tmp_path):
    """A message nested inside another sent message is wire payload,
    not an unhandled dispatch target."""
    findings = run_rules(project(tmp_path, {
        "protocols/toy.py": FLOW_PREAMBLE + """
    @dataclasses.dataclass
    class Inner:
        n: int

    @dataclasses.dataclass
    class Outer:
        inner: Inner

    class Sender(Actor):
        def receive(self, src, message):
            self.send(src, Outer(inner=Inner(n=1)))

    class Receiver(Actor):
        def receive(self, src, message):
            if isinstance(message, Outer):
                pass
    """}))
    assert all(not (f.rule == "FLOW401" and f.scope == "Inner")
               for f in findings)


def test_flow402_handled_but_never_sent(tmp_path):
    findings = run_rules(project(tmp_path, {
        "protocols/toy.py": FLOW_PREAMBLE + """
    @dataclasses.dataclass
    class Dead:
        n: int

    class Receiver(Actor):
        def receive(self, src, message):
            if isinstance(message, Dead):
                pass
    """}))
    assert any(f.rule == "FLOW402" and f.scope == "Dead"
               for f in findings)


def test_flow403_orphan_codec_tag(tmp_path):
    findings = run_rules(project(tmp_path, {
        "protocols/toy.py": FLOW_PREAMBLE + """
    @dataclasses.dataclass
    class Orphan:
        n: int

    class OrphanCodec:
        message_type = Orphan
        tag = 99

        def encode(self, out, message):
            out += bytes([message.n])

        def decode(self, buf, at):
            return Orphan(n=buf[at]), at + 1
    """}))
    assert any(f.rule == "FLOW403" and f.scope == "Orphan"
               for f in findings)


def test_flow404_request_without_reply_or_timer(tmp_path):
    findings = run_rules(project(tmp_path, {
        "protocols/toy.py": FLOW_PREAMBLE + """
    @dataclasses.dataclass
    class FetchRequest:
        n: int

    class Requester(Actor):
        def kick(self):
            self.send("server", FetchRequest(n=1))

        def receive(self, src, message):
            pass

    class Server(Actor):
        def receive(self, src, message):
            if isinstance(message, FetchRequest):
                pass
    """}))
    assert any(f.rule == "FLOW404" and f.scope == "FetchRequest"
               for f in findings)


def test_flow404_quiet_with_reply_path(tmp_path):
    findings = run_rules(project(tmp_path, {
        "protocols/toy.py": FLOW_PREAMBLE + """
    @dataclasses.dataclass
    class FetchRequest:
        n: int

    @dataclasses.dataclass
    class FetchReply:
        n: int

    class Requester(Actor):
        def kick(self):
            self.send("server", FetchRequest(n=1))

        def receive(self, src, message):
            if isinstance(message, FetchReply):
                pass

    class Server(Actor):
        def receive(self, src, message):
            if isinstance(message, FetchRequest):
                self.send(src, FetchReply(n=message.n))
    """}))
    assert "FLOW404" not in rules_of(findings)


def test_flow404_quiet_with_nested_def_resend_timer(tmp_path):
    """The ubiquitous client idiom: a nested ``def resend`` registered
    as a timer callback makes the request timer-resent."""
    findings = run_rules(project(tmp_path, {
        "protocols/toy.py": FLOW_PREAMBLE + """
    @dataclasses.dataclass
    class FetchRequest:
        n: int

    class Requester(Actor):
        def kick(self):
            request = FetchRequest(n=1)
            self.send("server", request)

            def resend():
                self.send("server", request)

            self.timer("resend", 1.0, resend).start()

        def receive(self, src, message):
            pass

    class Server(Actor):
        def receive(self, src, message):
            if isinstance(message, FetchRequest):
                pass
    """}))
    assert "FLOW404" not in rules_of(findings)


_LANES_FIXTURE = """\
    CLIENT_LANE_TYPE_NAMES = frozenset({
        "ClientRequest",
    })
"""


def test_flow405_lane_name_without_codec_tag(tmp_path):
    """A client-lane NAME whose message has no codec: the tag-based
    frame classifier can never shed it."""
    findings = run_rules(project(tmp_path, {
        "serve/lanes.py": _LANES_FIXTURE,
        "protocols/toy.py": FLOW_PREAMBLE + """
    @dataclasses.dataclass
    class ClientRequest:
        n: int

    @dataclasses.dataclass
    class Other:
        n: int

    class OtherCodec:
        message_type = Other
        tag = 98

        def encode(self, out, message):
            out += bytes([message.n])

        def decode(self, buf, at):
            return Other(n=buf[at]), at + 1

    class ToyClient(Actor):
        def kick(self):
            self.send("server", ClientRequest(n=1))
            self.send("server", Other(n=2))

        def receive(self, src, message):
            pass

    class Server(Actor):
        def receive(self, src, message):
            if isinstance(message, (ClientRequest, Other)):
                self.send(src, Other(n=0))
    """}))
    assert any(f.rule == "FLOW405"
               and f.detail == "untagged-lane:ClientRequest"
               for f in findings)


def test_flow405_unclassified_client_edge_message(tmp_path):
    """A codec-tagged *Request* sent only by client-edge roles but
    missing from CLIENT_LANE_TYPE_NAMES."""
    findings = run_rules(project(tmp_path, {
        "serve/lanes.py": _LANES_FIXTURE,
        "protocols/toy.py": FLOW_PREAMBLE + """
    @dataclasses.dataclass
    class FetchRequest:
        n: int

    class FetchRequestCodec:
        message_type = FetchRequest
        tag = 97

        def encode(self, out, message):
            out += bytes([message.n])

        def decode(self, buf, at):
            return FetchRequest(n=buf[at]), at + 1

    class ToyClient(Actor):
        def kick(self):
            request = FetchRequest(n=1)
            self.send("server", request)

            def resend():
                self.send("server", request)

            self.timer("resend", 1.0, resend).start()

        def receive(self, src, message):
            pass

    class Server(Actor):
        def receive(self, src, message):
            if isinstance(message, FetchRequest):
                pass
    """}))
    assert any(f.rule == "FLOW405"
               and f.detail == "unclassified:FetchRequest"
               for f in findings)


# --- DUR5xx: durability dataflow --------------------------------------------

DUR_PREAMBLE = """\
    import dataclasses

    class Actor:
        def receive(self, src, message): ...
        def on_drain(self): ...
        def timer(self, name, delay_s, f): ...
        def send(self, dst, message): ...
        def broadcast(self, dsts, message): ...

    class DurableRole:
        def _wal_init(self, wal): ...
        def _wal_send(self, dst, message): ...
        def _wal_drain(self): ...

    @dataclasses.dataclass
    class Record:
        n: int

    @dataclasses.dataclass
    class Ack:
        n: int

    @dataclasses.dataclass
    class Nack:
        n: int
"""


def test_dur501_direct_send_after_append(tmp_path):
    findings = run_rules(project(tmp_path, {"a.py": DUR_PREAMBLE + """
    class Bad(Actor, DurableRole):
        def receive(self, src, message):
            self.wal.append(Record(n=1))
            self.send(src, Ack(n=1))
    """}))
    assert any(f.rule == "DUR501" and f.detail == "send:Ack"
               for f in findings)


def test_dur501_quiet_for_wal_send(tmp_path):
    findings = run_rules(project(tmp_path, {"a.py": DUR_PREAMBLE + """
    class Good(Actor, DurableRole):
        def receive(self, src, message):
            self.wal.append(Record(n=1))
            self._wal_send(src, Ack(n=1))
    """}))
    assert "DUR501" not in rules_of(findings)


def test_dur501_nack_is_exempt(tmp_path):
    """A nack acknowledges nothing: the early-reject path may send it
    directly even in an appending handler."""
    findings = run_rules(project(tmp_path, {"a.py": DUR_PREAMBLE + """
    class Good(Actor, DurableRole):
        def receive(self, src, message):
            if message.n < 0:
                self.send(src, Nack(n=0))
                return
            self.wal.append(Record(n=1))
            self._wal_send(src, Ack(n=1))
    """}))
    assert "DUR501" not in rules_of(findings)


def test_dur502_wal_use_without_mixin(tmp_path):
    findings = run_rules(project(tmp_path, {"a.py": DUR_PREAMBLE + """
    class Bad(Actor):
        def receive(self, src, message):
            self.wal.append(Record(n=1))
    """}))
    assert any(f.rule == "DUR502" and f.scope == "Bad"
               for f in findings)


def test_dur502_quiet_with_mixin(tmp_path):
    findings = run_rules(project(tmp_path, {"a.py": DUR_PREAMBLE + """
    class Good(Actor, DurableRole):
        def receive(self, src, message):
            self.wal.append(Record(n=1))
            self._wal_send(src, Ack(n=1))

        def on_drain(self):
            self._wal_drain()
    """}))
    assert "DUR502" not in rules_of(findings)


def test_dur503_on_drain_without_wal_drain(tmp_path):
    findings = run_rules(project(tmp_path, {"a.py": DUR_PREAMBLE + """
    class Bad(Actor, DurableRole):
        def receive(self, src, message):
            self.wal.append(Record(n=1))
            self._wal_send(src, Ack(n=1))

        def on_drain(self):
            pass
    """}))
    assert any(f.rule == "DUR503" and f.scope == "Bad.on_drain"
               for f in findings)


def test_dur503_quiet_when_reached_through_helper(tmp_path):
    findings = run_rules(project(tmp_path, {"a.py": DUR_PREAMBLE + """
    class Good(Actor, DurableRole):
        def receive(self, src, message):
            self.wal.append(Record(n=1))
            self._wal_send(src, Ack(n=1))

        def on_drain(self):
            self._finish()

        def _finish(self):
            self._wal_drain()
    """}))
    assert "DUR503" not in rules_of(findings)


# --- SHAPE6xx: abstract shape/dtype interpretation --------------------------

SHAPE_PREAMBLE = """\
    import jax
    import jax.numpy as jnp
"""


def test_shape601_nonzero_without_size(tmp_path):
    findings = run_rules(project(tmp_path, {"k.py": SHAPE_PREAMBLE + """
    @jax.jit
    def kernel(x):
        return jnp.nonzero(x > 0)
    """}))
    assert any(f.rule == "SHAPE601" for f in findings)


def test_shape601_quiet_with_size(tmp_path):
    findings = run_rules(project(tmp_path, {"k.py": SHAPE_PREAMBLE + """
    @jax.jit
    def kernel(x):
        return jnp.nonzero(x > 0, size=8, fill_value=0)
    """}))
    assert "SHAPE601" not in rules_of(findings)


def test_shape601_one_arg_where(tmp_path):
    findings = run_rules(project(tmp_path, {"k.py": SHAPE_PREAMBLE + """
    @jax.jit
    def kernel(x):
        return jnp.where(x > 0)

    @jax.jit
    def fine(x):
        return jnp.where(x > 0, x, 0)
    """}))
    assert sum(f.rule == "SHAPE601" for f in findings) == 1


def test_shape602_builtin_astype(tmp_path):
    findings = run_rules(project(tmp_path, {"k.py": SHAPE_PREAMBLE + """
    @jax.jit
    def kernel(x):
        return x.astype(int)
    """}))
    assert any(f.rule == "SHAPE602" and f.detail == "astype:int"
               for f in findings)


def test_shape602_value_typed_arange(tmp_path):
    findings = run_rules(project(tmp_path, {"k.py": SHAPE_PREAMBLE + """
    @jax.jit
    def kernel(x):
        return jnp.arange(x.shape[0])

    @jax.jit
    def fine(x):
        return jnp.arange(x.shape[0], dtype=jnp.int32)
    """}))
    assert sum(f.rule == "SHAPE602" for f in findings) == 1


def test_shape602_jit_wrapped_module_level(tmp_path):
    """``kernel2 = jax.jit(kernel)`` marks ``kernel`` as jitted even
    without a decorator."""
    findings = run_rules(project(tmp_path, {"k.py": SHAPE_PREAMBLE + """
    def kernel(x):
        return x.astype(float)

    kernel2 = jax.jit(kernel)
    """}))
    assert any(f.rule == "SHAPE602" and f.detail == "astype:float"
               for f in findings)


def test_shape603_undeclared_axis_name(tmp_path):
    findings = run_rules(project(tmp_path, {"k.py": SHAPE_PREAMBLE + """
    from jax import lax
    from jax.sharding import Mesh

    def make(devices):
        return Mesh(devices, ("group", "slot"))

    @jax.jit
    def kernel(x):
        return lax.psum(x, axis_name="grp")
    """}))
    assert any(f.rule == "SHAPE603" and f.detail == "psum:grp"
               for f in findings)


def test_shape603_quiet_when_declared(tmp_path):
    findings = run_rules(project(tmp_path, {"k.py": SHAPE_PREAMBLE + """
    from jax import lax
    from jax.sharding import Mesh

    def make(devices):
        return Mesh(devices, ("group", "slot"))

    @jax.jit
    def kernel(x):
        return lax.psum(x, axis_name="group")
    """}))
    assert "SHAPE603" not in rules_of(findings)


# --- paxflow graph artifacts ------------------------------------------------


def test_flowgraph_covers_every_protocol_unit():
    """Registry completeness: every protocol package yields a
    non-empty flow graph (roles, messages, and at least one edge)."""
    from frankenpaxos_tpu.analysis import flowgraph

    proj = Project(".")
    graphs = flowgraph.build_all(proj)
    units = set(flowgraph.unit_modules(proj))
    assert units == set(graphs)
    assert len(graphs) >= 20
    for unit, graph in graphs.items():
        assert graph.roles, unit
        assert graph.messages, unit
        assert graph.edges(), unit


def test_flowgraph_golden_multipaxos_mencius():
    """The committed docs/flowgraphs artifacts for the two run-pipeline
    protocols match a fresh build byte-for-byte, and a second
    independent build is bit-identical (deterministic, diff-stable)."""
    from frankenpaxos_tpu.analysis import flowgraph

    first = flowgraph.render(Project("."))
    second = flowgraph.render(Project("."))
    assert first == second
    for unit in ("multipaxos", "mencius"):
        for ext in ("json", "dot"):
            with open(f"docs/flowgraphs/{unit}.{ext}",
                      encoding="utf-8") as f:
                assert f.read() == first[f"{unit}.{ext}"], (
                    f"{unit}.{ext} is stale: regenerate with "
                    f"python -m frankenpaxos_tpu.analysis "
                    f"--write-flowgraphs")


def test_flowgraph_topology_golden_epaxos_simplebpaxos():
    """The paxruns port contract, mechanically checked: coalescing
    PreAcceptOk/DependencyReply into DepRun frames must leave the
    epaxos and simplebpaxos role x message topology EXACTLY as it was
    (runs/wire.py codecs are transport_layer; receivers re-expand to
    the original messages). A topology diff here means a run message
    leaked into a protocol's role graph -- update tests/golden/ only
    with a deliberate protocol change, never for a transport one."""
    import json

    from frankenpaxos_tpu.analysis import flowgraph

    graphs = flowgraph.build_all(Project("."))
    for unit in ("epaxos", "simplebpaxos"):
        d = flowgraph.to_json(graphs[unit])
        live = {
            "protocol": unit,
            "edges": sorted(
                d["edges"],
                key=lambda e: (e["message"], e["from"], e["to"],
                               e["kind"])),
            "roles": {role: {"handles": sorted(v["handles"]),
                             "sends": sorted(v["sends"])}
                      for role, v in d["roles"].items()},
        }
        with open(f"tests/golden/flow_topology_{unit}.json",
                  encoding="utf-8") as f:
            golden = json.load(f)
        assert live == golden, (
            f"{unit} role x message topology changed -- the run-layer "
            f"port must be topology-neutral")


# --- import_sort: the tooled import-order pass ------------------------------


def test_import_sort_sections_and_members():
    from frankenpaxos_tpu.analysis.import_sort import sort_source

    src = textwrap.dedent("""\
    \"\"\"doc.\"\"\"

    from frankenpaxos_tpu.utils import BufferMap
    import sys
    from typing import Optional
    import jax
    from frankenpaxos_tpu.runtime import Logger, Actor
    """)
    out = sort_source(src)
    want = textwrap.dedent("""\
    \"\"\"doc.\"\"\"

    import sys
    from typing import Optional

    import jax

    from frankenpaxos_tpu.runtime import Actor, Logger
    from frankenpaxos_tpu.utils import BufferMap
    """)
    assert out == want
    assert sort_source(out) == out  # idempotent


def test_import_sort_preserves_noqa_and_interior_comments():
    from frankenpaxos_tpu.analysis.import_sort import sort_source

    src = textwrap.dedent("""\
    from frankenpaxos_tpu.wal.log import (  # noqa: F401
        Wal,
        MemStorage,
    )
    from frankenpaxos_tpu.obs import (
        Tracer,
        # the flight recorder survives kill -9
        FlightRecorder,
    )
    """)
    out = sort_source(src)
    assert "# noqa: F401" in out
    # The interior-comment statement is kept verbatim (unsorted names
    # and all) -- only its position may change.
    assert "# the flight recorder survives kill -9" in out
    assert out.index("frankenpaxos_tpu.obs") < out.index(
        "frankenpaxos_tpu.wal")


def test_import_sort_repo_gate():
    """The CI gate: the repo's import order is check-clean."""
    proc = subprocess.run(
        [sys.executable, "-m",
         "frankenpaxos_tpu.analysis.import_sort", "--check"],
        capture_output=True, text=True, timeout=300)
    assert proc.returncode == 0, proc.stdout + proc.stderr


# --- NET7xx: paxwire transport contract -------------------------------------


def test_net701_flushing_send_loop_in_on_drain(tmp_path):
    findings = run_rules(project(tmp_path, {"a.py": ACTOR_PREAMBLE + """
    class Bad(Actor):
        def on_drain(self):
            for reply in self.staged:
                self.send(self.leader, reply)
    """}))
    assert "NET701" in rules_of(findings)
    f = next(f for f in findings if f.rule == "NET701")
    assert f.scope == "Bad.on_drain"


def test_net701_reaches_drain_helper_closure(tmp_path):
    findings = run_rules(project(tmp_path, {"a.py": ACTOR_PREAMBLE + """
    class Bad(Actor):
        def on_drain(self):
            self._release()

        def _release(self):
            for ack in self.acks:
                self.send(self.proxy, ack)
    """}))
    assert "NET701" in rules_of(findings)
    assert any(f.rule == "NET701" and f.scope == "Bad._release"
               for f in findings)


def test_net701_chan_send_on_loop_invariant_channel(tmp_path):
    findings = run_rules(project(tmp_path, {"a.py": ACTOR_PREAMBLE + """
    class Bad(Actor):
        def on_drain(self):
            chan = self.chan(self.leader)
            for reply in self.staged:
                chan.send(reply)
    """}))
    assert "NET701" in rules_of(findings)


def test_net701_per_destination_fanout_is_fine(tmp_path):
    findings = run_rules(project(tmp_path, {"a.py": ACTOR_PREAMBLE + """
    class Good(Actor):
        def on_drain(self):
            for client, reply in self.staged.items():
                self.send(client, reply)
    """}))
    assert "NET701" not in rules_of(findings)


def test_net701_send_no_flush_plus_flush_is_fine(tmp_path):
    findings = run_rules(project(tmp_path, {"a.py": ACTOR_PREAMBLE + """
    class Good(Actor):
        def send_no_flush(self, dst, message): ...
        def flush(self, dst): ...
        def on_drain(self):
            for reply in self.staged:
                self.send_no_flush(self.leader, reply)
            self.flush(self.leader)
    """}))
    assert "NET701" not in rules_of(findings)


def test_net701_receive_loops_not_flagged(tmp_path):
    """Only DRAIN-granular handlers are in scope: a receive() handling
    one inbound message sends per message by definition."""
    findings = run_rules(project(tmp_path, {"a.py": ACTOR_PREAMBLE + """
    class Good(Actor):
        def receive(self, src, message):
            for dst in range(3):
                self.send(self.leader, message)
    """}))
    assert "NET701" not in rules_of(findings)


def test_net701_pragma_suppresses(tmp_path):
    findings = run_rules(project(tmp_path, {"a.py": ACTOR_PREAMBLE + """
    class Tolerated(Actor):
        def on_drain(self):
            for reply in self.staged:
                self.send(self.leader, reply)  # paxlint: disable=NET701
    """}))
    assert "NET701" not in rules_of(findings)


def test_flow403_transport_layer_codec_excluded(tmp_path):
    """A codec marked ``transport_layer = True`` (paxwire batch
    envelopes: encoded by the transport's flush planner, never by a
    role) is not an orphan tag; the unmarked twin still is."""
    files = {
        "serve/lanes.py": "CLIENT_LANE_TYPE_NAMES = frozenset()\n",
        "wire.py": """
    import dataclasses

    @dataclasses.dataclass(frozen=True)
    class Envelope:
        segments: tuple

    @dataclasses.dataclass(frozen=True)
    class Orphan:
        segments: tuple

    class MessageCodec: ...

    class EnvelopeCodec(MessageCodec):
        message_type = Envelope
        tag = 150
        transport_layer = True

    class OrphanCodec(MessageCodec):
        message_type = Orphan
        tag = 151
    """}
    findings = run_rules(project(tmp_path, files))
    flow403 = {f.scope for f in findings if f.rule == "FLOW403"}
    assert "Orphan" in flow403
    assert "Envelope" not in flow403


# --- GEO8xx: paxgeo determinism contract ------------------------------------


def test_geo801_wall_clock_in_geo_layer(tmp_path):
    findings = run_rules(project(tmp_path, {"geo/topology.py": """
    import time

    def sample_delay(src, dst):
        return time.time() * 0.001
    """}))
    assert "GEO801" in rules_of(findings)
    f = next(f for f in findings if f.rule == "GEO801")
    assert "time.time" in f.detail


def test_geo801_unseeded_random_in_geo_layer(tmp_path):
    findings = run_rules(project(tmp_path, {"geo/jitter.py": """
    import random

    def jitter():
        return random.random()
    """}))
    assert "GEO801" in rules_of(findings)


def test_geo801_os_entropy_in_geo_layer(tmp_path):
    findings = run_rules(project(tmp_path, {"geo/seed.py": """
    import os

    def fresh():
        return os.urandom(8)
    """}))
    assert "GEO801" in rules_of(findings)


def test_geo801_seeded_random_is_fine(tmp_path):
    findings = run_rules(project(tmp_path, {"geo/topology.py": """
    import random

    def sample_delay(seed, src, dst, frame_id):
        return random.Random(f"{seed}|{src}|{dst}|{frame_id}").random()
    """}))
    assert "GEO801" not in rules_of(findings)


def test_geo801_scoped_to_geo_tree(tmp_path):
    # The same construct OUTSIDE geo/ (a bench's wall-clock timing) is
    # not this rule's business.
    findings = run_rules(project(tmp_path, {"bench/geo_lt.py": """
    import time

    def measure():
        return time.time()
    """}))
    assert "GEO801" not in rules_of(findings)


def test_geo801_repo_is_clean():
    from frankenpaxos_tpu.analysis.core import Project as _P
    from frankenpaxos_tpu.analysis.geo_rules import check as _geo_check

    import frankenpaxos_tpu
    import os as _os

    root = _os.path.dirname(_os.path.dirname(
        frankenpaxos_tpu.__file__))
    findings = list(_geo_check(_P(root, package="frankenpaxos_tpu")))
    assert findings == []


# --- SAFE9xx: Paxos safety disciplines (paxsafe) ----------------------------

ROLE_PREAMBLE = """\
    class Actor:
        def receive(self, src, message): ...
        def on_drain(self): ...
        def timer(self, name, delay_s, f): ...
        def send(self, dst, message): ...
        def broadcast(self, dsts, message): ...
"""


def role_project(tmp_path, source: str) -> "Project":
    """A throwaway project whose one module lives under protocols/
    (the SAFE9xx/ALIAS10xx self-scope)."""
    return project(tmp_path, {"protocols/a.py": ROLE_PREAMBLE + source})


def test_safe901_unguarded_round_adoption(tmp_path):
    findings = run_rules(role_project(tmp_path, """
    class Bad(Actor):
        def receive(self, src, message):
            self.round = message.round
            self.send(src, message)
    """))
    assert any(f.rule == "SAFE901" and f.detail == "self.round"
               for f in findings)


def test_safe901_compare_guard_is_clean(tmp_path):
    findings = run_rules(role_project(tmp_path, """
    class Fine(Actor):
        def receive(self, src, message):
            if message.round < self.round:
                return
            self.round = message.round
    """))
    assert "SAFE901" not in rules_of(findings)


def test_safe901_guard_in_caller_clears_helper(tmp_path):
    """Cross-method: the round compare in the dispatching handler
    clears the adoption inside the helper it calls."""
    findings = run_rules(role_project(tmp_path, """
    class Fine(Actor):
        def receive(self, src, message):
            if message.round < self.round:
                return
            self._adopt(message)

        def _adopt(self, message):
            self.round = message.round
    """))
    assert "SAFE901" not in rules_of(findings)


def test_safe901_helper_without_any_guard_flagged(tmp_path):
    findings = run_rules(role_project(tmp_path, """
    class Bad(Actor):
        def receive(self, src, message):
            self._adopt(message)

        def _adopt(self, message):
            self.ballot = message.ballot
    """))
    assert any(f.rule == "SAFE901" and f.scope == "Bad._adopt"
               for f in findings)


def test_safe901_max_and_bump_are_clean(tmp_path):
    findings = run_rules(role_project(tmp_path, """
    class Fine(Actor):
        def receive(self, src, message):
            self.round = max(self.round, message.round)
            self.ballot += 1
    """))
    assert "SAFE901" not in rules_of(findings)


def test_safe901_pragma_suppresses(tmp_path):
    findings = run_rules(role_project(tmp_path, """
    class Odd(Actor):
        def receive(self, src, message):
            # the round space is partitioned per proposer: no two
            # proposers share a round, so adoption cannot regress.
            # paxlint: disable=SAFE901
            self.round = message.round
    """))
    assert "SAFE901" not in rules_of(findings)


def test_safe901_out_of_scope_module_is_ignored(tmp_path):
    findings = run_rules(project(tmp_path, {"runtime/a.py": """\
    class Actor:
        def receive(self, src, message): ...
        def send(self, dst, message): ...

    class Elsewhere(Actor):
        def receive(self, src, message):
            self.round = message.round
    """}))
    assert "SAFE901" not in rules_of(findings)


def test_safe902_vote_overwrite_without_check(tmp_path):
    findings = run_rules(role_project(tmp_path, """
    class Bad(Actor):
        def receive(self, src, message):
            self.votes[message.slot] = (message.round, message.value)
    """))
    assert any(f.rule == "SAFE902" and f.detail == "self.votes"
               for f in findings)


def test_safe902_round_compare_is_clean(tmp_path):
    findings = run_rules(role_project(tmp_path, """
    class Fine(Actor):
        def receive(self, src, message):
            if message.round < self.round:
                return
            self.round = message.round
            self.votes[message.slot] = (message.round, message.value)
    """))
    assert "SAFE902" not in rules_of(findings)


def test_safe902_existing_entry_get_is_clean(tmp_path):
    findings = run_rules(role_project(tmp_path, """
    class Fine(Actor):
        def receive(self, src, message):
            existing = self.votes.get(message.slot)
            if existing is None:
                self.votes[message.slot] = (message.round, message.value)
    """))
    assert "SAFE902" not in rules_of(findings)


def test_safe902_guard_in_caller_clears_helper(tmp_path):
    findings = run_rules(role_project(tmp_path, """
    class Fine(Actor):
        def receive(self, src, message):
            if message.round < self.round:
                return
            self._store(message)

        def _store(self, message):
            self.vote_value = message.value
    """))
    assert "SAFE902" not in rules_of(findings)


def test_safe902_pragma_suppresses(tmp_path):
    findings = run_rules(role_project(tmp_path, """
    class Odd(Actor):
        def receive(self, src, message):
            # single-proposer unit: one value per slot by construction.
            # paxlint: disable=SAFE902
            self.votes[message.slot] = message.value
    """))
    assert "SAFE902" not in rules_of(findings)


def test_safe903_unclamped_next_slot(tmp_path):
    findings = run_rules(role_project(tmp_path, """
    class Bad(Actor):
        def receive(self, src, message):
            max_slot = max(v.slot for v in message.votes)
            self.next_slot = max_slot + 1
    """))
    assert any(f.rule == "SAFE903" and f.detail == "self.next_slot"
               for f in findings)


def test_safe903_watermark_clamp_is_clean(tmp_path):
    findings = run_rules(role_project(tmp_path, """
    class Fine(Actor):
        def receive(self, src, message):
            max_slot = max(v.slot for v in message.votes)
            self.next_slot = max(max_slot + 1, self.chosen_watermark)
    """))
    assert "SAFE903" not in rules_of(findings)


def test_safe903_flags_unclamped_helper_call_site(tmp_path):
    """Cross-method: the cursor is written in a helper; the voted-max
    flows in at the call site, which is where the clamp is missing."""
    findings = run_rules(role_project(tmp_path, """
    class Bad(Actor):
        def receive(self, src, message):
            max_slot = max(v.slot for v in message.votes)
            start = max_slot + 1
            self._set_slots(start)

        def _set_slots(self, start_slot):
            self.next_slot = start_slot
    """))
    assert any(f.rule == "SAFE903" and f.scope == "Bad.receive"
               for f in findings)


def test_safe903_clamped_helper_call_site_is_clean(tmp_path):
    findings = run_rules(role_project(tmp_path, """
    class Fine(Actor):
        def receive(self, src, message):
            max_slot = max(v.slot for v in message.votes)
            start = max(max_slot + 1, self.chosen_watermark)
            self._set_slots(start)

        def _set_slots(self, start_slot):
            self.next_slot = start_slot
    """))
    assert "SAFE903" not in rules_of(findings)


def test_safe903_monotone_guard_is_clean(tmp_path):
    findings = run_rules(role_project(tmp_path, """
    class Fine(Actor):
        def receive(self, src, message):
            max_slot = max(v.slot for v in message.votes)
            if max_slot + 1 > self.next_slot:
                self.next_slot = max_slot + 1
    """))
    assert "SAFE903" not in rules_of(findings)


def test_safe903_pragma_suppresses(tmp_path):
    findings = run_rules(role_project(tmp_path, """
    class Odd(Actor):
        def receive(self, src, message):
            max_slot = max(v.slot for v in message.votes)
            # the cursor trails the watermark by construction here.
            # paxlint: disable=SAFE903
            self.next_slot = max_slot + 1
    """))
    assert "SAFE903" not in rules_of(findings)


def test_safe904_plain_watermark_assignment(tmp_path):
    findings = run_rules(role_project(tmp_path, """
    class Bad(Actor):
        def receive(self, src, message):
            self.chosen_watermark = message.slot
    """))
    assert any(f.rule == "SAFE904"
               and f.detail == "self.chosen_watermark"
               for f in findings)


def test_safe904_max_update_is_clean(tmp_path):
    findings = run_rules(role_project(tmp_path, """
    class Fine(Actor):
        def receive(self, src, message):
            self.chosen_watermark = max(self.chosen_watermark,
                                        message.slot)
    """))
    assert "SAFE904" not in rules_of(findings)


def test_safe904_guard_compare_is_clean(tmp_path):
    findings = run_rules(role_project(tmp_path, """
    class Fine(Actor):
        def receive(self, src, message):
            if message.slot > self.chosen_watermark:
                self.chosen_watermark = message.slot
    """))
    assert "SAFE904" not in rules_of(findings)


def test_safe904_walked_forward_copy_is_clean(tmp_path):
    """The wm = self.W; while ...: wm += 1; self.W = wm walk reads the
    field first: monotone by construction."""
    findings = run_rules(role_project(tmp_path, """
    class Fine(Actor):
        def receive(self, src, message):
            wm = self.chosen_watermark
            while wm in self.log:
                wm += 1
            self.chosen_watermark = wm
    """))
    assert "SAFE904" not in rules_of(findings)


def test_safe904_pragma_suppresses(tmp_path):
    findings = run_rules(role_project(tmp_path, """
    class Odd(Actor):
        def receive(self, src, message):
            # snapshots install a complete replacement state.
            # paxlint: disable=SAFE904
            self.chosen_watermark = message.slot
    """))
    assert "SAFE904" not in rules_of(findings)


def test_safe905_promise_mutated_after_phase1b_send(tmp_path):
    findings = run_rules(role_project(tmp_path, """
    class Phase1b:
        pass

    class Bad(Actor):
        def receive(self, src, message):
            if message.round < self.round:
                return
            self.send(src, Phase1b(round=message.round))
            self.round = message.round
    """))
    assert any(f.rule == "SAFE905" and f.detail == "self.round"
               for f in findings)


def test_safe905_update_then_send_is_clean(tmp_path):
    findings = run_rules(role_project(tmp_path, """
    class Phase1b:
        pass

    class Fine(Actor):
        def receive(self, src, message):
            if message.round < self.round:
                return
            self.round = message.round
            self.send(src, Phase1b(round=self.round))
    """))
    assert "SAFE905" not in rules_of(findings)


def test_safe905_sibling_branch_is_not_post_send(tmp_path):
    """A Phase2a elif branch below the Phase1a branch's send is NOT
    control-flow-after it (the caspaxos shape)."""
    findings = run_rules(role_project(tmp_path, """
    class Phase1b:
        pass

    class Fine(Actor):
        def receive(self, src, message):
            if message.kind == 1:
                if message.round < self.round:
                    return
                self.round = message.round
                self.send(src, Phase1b(round=self.round))
            elif message.kind == 2:
                if message.round < self.round:
                    return
                self.round = message.round
                self.vote_round = message.round
    """))
    assert "SAFE905" not in rules_of(findings)


def test_safe905_nack_is_not_a_promise(tmp_path):
    findings = run_rules(role_project(tmp_path, """
    class Phase1bNack:
        pass

    class Fine(Actor):
        def receive(self, src, message):
            if message.round <= self.round:
                self.send(src, Phase1bNack(round=self.round))
                return
            self.round = message.round
    """))
    assert "SAFE905" not in rules_of(findings)


def test_safe905_local_alias_send_flagged(tmp_path):
    findings = run_rules(role_project(tmp_path, """
    class Phase1b:
        pass

    class Bad(Actor):
        def receive(self, src, message):
            if message.round < self.round:
                return
            reply = Phase1b(round=message.round)
            self.send(src, reply)
            self.round = message.round
    """))
    assert "SAFE905" in rules_of(findings)


def test_safe905_pragma_suppresses(tmp_path):
    findings = run_rules(role_project(tmp_path, """
    class Phase1b:
        pass

    class Odd(Actor):
        def receive(self, src, message):
            if message.round < self.round:
                return
            self.send(src, Phase1b(round=message.round))
            # the transport serializes at send in BOTH arms here.
            # paxlint: disable=SAFE905
            self.round = message.round
    """))
    assert "SAFE905" not in rules_of(findings)


# --- ALIAS10xx: sim-vs-deployed mutable aliasing (paxsafe) ------------------


def test_alias1001_live_list_in_message(tmp_path):
    findings = run_rules(role_project(tmp_path, """
    class Batch:
        pass

    class Bad(Actor):
        def __init__(self):
            self.pending = []

        def receive(self, src, message):
            self.pending.append(message)
            self.send(src, Batch(values=self.pending))
    """))
    assert any(f.rule == "ALIAS1001" and f.detail == "self.pending"
               for f in findings)


def test_alias1001_tuple_copy_is_clean(tmp_path):
    findings = run_rules(role_project(tmp_path, """
    class Batch:
        pass

    class Fine(Actor):
        def __init__(self):
            self.pending = []

        def receive(self, src, message):
            self.pending.append(message)
            self.send(src, Batch(values=tuple(self.pending)))
            self.pending.clear()
    """))
    assert "ALIAS1001" not in rules_of(findings)


def test_alias1001_unmutated_field_is_clean(tmp_path):
    """A mutable field no handler mutates cannot race the send."""
    findings = run_rules(role_project(tmp_path, """
    class Batch:
        pass

    class Fine(Actor):
        def __init__(self):
            self.static_config = {}

        def receive(self, src, message):
            self.send(src, Batch(values=self.static_config))
    """))
    assert "ALIAS1001" not in rules_of(findings)


def test_alias1001_resolves_sender_helper(tmp_path):
    """The alias leaks at the call site of a sender helper whose
    parameter flows into the message construction."""
    findings = run_rules(role_project(tmp_path, """
    class Batch:
        pass

    class Bad(Actor):
        def __init__(self):
            self.pending = []

        def receive(self, src, message):
            self.pending.append(message)
            self._reply(src, self.pending)

        def _reply(self, dst, values):
            self.send(dst, Batch(values=values))
    """))
    assert any(f.rule == "ALIAS1001" and f.scope == "Bad.receive"
               for f in findings)


def test_alias1001_locally_constructed_message(tmp_path):
    findings = run_rules(role_project(tmp_path, """
    class Batch:
        pass

    class Bad(Actor):
        def __init__(self):
            self.pending = []

        def on_drain(self):
            batch = Batch(values=self.pending)
            self.send("dst", batch)

        def receive(self, src, message):
            self.pending.append(message)
    """))
    assert "ALIAS1001" in rules_of(findings)


def test_alias1001_pragma_suppresses(tmp_path):
    findings = run_rules(role_project(tmp_path, """
    class Batch:
        pass

    class Odd(Actor):
        def __init__(self):
            self.pending = []

        def receive(self, src, message):
            self.pending.append(message)
            # ownership transfer: the field is rebound, never
            # mutated, after this send.
            # paxlint: disable=ALIAS1001
            self.send(src, Batch(values=self.pending))
    """))
    assert "ALIAS1001" not in rules_of(findings)


def test_alias1002_mutates_received_message(tmp_path):
    findings = run_rules(role_project(tmp_path, """
    class Bad(Actor):
        def receive(self, src, message):
            message.values.append(1)
    """))
    assert any(f.rule == "ALIAS1002"
               and f.detail == "message.values.append"
               for f in findings)


def test_alias1002_attribute_assignment_flagged(tmp_path):
    findings = run_rules(role_project(tmp_path, """
    class Bad(Actor):
        def receive(self, src, message):
            message.round = 7
    """))
    assert "ALIAS1002" in rules_of(findings)


def test_alias1002_taint_reaches_dispatch_helper(tmp_path):
    """Cross-method: receive's dispatch passes the message into a
    _handle_* helper, whose mutation is the same race."""
    findings = run_rules(role_project(tmp_path, """
    class Bad(Actor):
        def receive(self, src, message):
            self._handle_write(src, message)

        def _handle_write(self, src, write):
            write.entries.pop()
    """))
    assert any(f.rule == "ALIAS1002"
               and f.scope == "Bad._handle_write"
               for f in findings)


def test_alias1002_local_alias_of_message_state(tmp_path):
    findings = run_rules(role_project(tmp_path, """
    class Bad(Actor):
        def receive(self, src, message):
            values = message.values
            values.append(1)
    """))
    assert "ALIAS1002" in rules_of(findings)


def test_alias1002_copy_before_mutate_is_clean(tmp_path):
    findings = run_rules(role_project(tmp_path, """
    class Fine(Actor):
        def receive(self, src, message):
            values = list(message.values)
            values.append(1)
            self.send(src, values)
    """))
    assert "ALIAS1002" not in rules_of(findings)


def test_alias1002_pragma_suppresses(tmp_path):
    findings = run_rules(role_project(tmp_path, """
    class Odd(Actor):
        def receive(self, src, message):
            # the sender constructs a fresh message per destination.
            # paxlint: disable=ALIAS1002
            message.values.append(1)
    """))
    assert "ALIAS1002" not in rules_of(findings)


def test_safe_alias_repo_is_clean_or_justified():
    """The repo gate: SAFE9xx/ALIAS10xx produce zero unsuppressed
    findings, and every suppressing pragma carries a justification
    comment (the safety argument), not a bare disable."""
    import os as _os
    import re as _re

    import frankenpaxos_tpu
    from frankenpaxos_tpu.analysis.alias_rules import (
        check as _alias_check,
    )
    from frankenpaxos_tpu.analysis.core import (
        _suppressed,
        Project as _P,
    )
    from frankenpaxos_tpu.analysis.safety_rules import (
        check as _safety_check,
    )

    root = _os.path.dirname(_os.path.dirname(frankenpaxos_tpu.__file__))
    proj = _P(root, package="frankenpaxos_tpu")
    findings = list(_safety_check(proj)) + list(_alias_check(proj))
    live = [f for f in findings if not _suppressed(proj, f)]
    assert live == [], [f.render() for f in live]
    # Every SAFE/ALIAS pragma line must sit in a comment block with
    # more to say than the directive itself.
    pragma_re = _re.compile(r"#\s*paxlint:\s*disable=((?:SAFE|ALIAS)[0-9]+)")
    for mod in proj:
        for i, line in enumerate(mod.lines):
            m = pragma_re.search(line)
            if not m:
                continue
            # Justification: comment text beyond the directive on this
            # line, or a comment line directly above.
            before = line[:m.start()].strip()
            after = line[m.end():].strip(" -#")
            above = mod.lines[i - 1].strip() if i > 0 else ""
            justified = (before.startswith("#") and len(before) > 5) \
                or len(after) > 5 or above.startswith("#")
            assert justified, (
                f"{mod.path}:{i + 1}: bare {m.group(1)} pragma without "
                f"a justification comment")


def test_paxlint_runtime_budget():
    """The full project run stays under the CI budget. PR 7 cut the
    run from 124s to 15s with project-level caches; the paxsafe
    interprocedural passes (SAFE9xx guard closures, ALIAS10xx taint)
    must stay inside that cached-namespace/callgraph infrastructure
    rather than re-walking the tree per rule. The paxown families
    (OWN11xx escape fixpoint, DEV12xx transfer discipline) ride the
    same memoized callgraph and are included in this budget; the
    diff-aware (<10s) twin lives in tests/test_analysis_cli.py."""
    import os as _os
    import time as _time

    import frankenpaxos_tpu

    root = _os.path.dirname(_os.path.dirname(frankenpaxos_tpu.__file__))
    start = _time.monotonic()
    proj = Project(root, package="frankenpaxos_tpu")
    run_rules(proj)
    elapsed = _time.monotonic() - start
    assert elapsed < 30.0, (
        f"paxlint full-project run took {elapsed:.1f}s; the CI budget "
        f"is 30s (docs/ANALYSIS.md)")


def test_format_json_emits_finding_records(tmp_path):
    """--format=json: one JSON document of file/line/rule/scope/
    detail/message/baselined records, exit code still gating; --output
    writes the same document to a file while stdout keeps the human
    report."""
    import json as _json

    (tmp_path / "frankenpaxos_tpu").mkdir()
    (tmp_path / "frankenpaxos_tpu" / "bad.py").write_text(
        textwrap.dedent(ACTOR_PREAMBLE) + textwrap.dedent("""
    class Bad(Actor):
        def on_drain(self):
            time.sleep(0.5)
    """))
    proc = subprocess.run(
        [sys.executable, "-m", "frankenpaxos_tpu.analysis",
         "--root", str(tmp_path), "--format", "json"],
        capture_output=True, text=True, timeout=300)
    assert proc.returncode == 1, proc.stdout + proc.stderr
    document = _json.loads(proc.stdout)
    assert document["new"] == 1
    (record,) = document["findings"]
    assert record["rule"] == "PAX103"
    assert record["file"] == "frankenpaxos_tpu/bad.py"
    assert record["scope"] == "Bad.on_drain"
    assert record["baselined"] is False
    assert record["line"] > 0 and record["message"]
    # --output keeps the human report on stdout and writes the file.
    out = tmp_path / "paxlint.json"
    proc = subprocess.run(
        [sys.executable, "-m", "frankenpaxos_tpu.analysis",
         "--root", str(tmp_path), "--output", str(out)],
        capture_output=True, text=True, timeout=300)
    assert proc.returncode == 1
    assert "PAX103" in proc.stdout  # human text
    on_disk = _json.loads(out.read_text())
    assert on_disk["findings"] == document["findings"]


def test_list_rules_includes_paxsafe_families():
    proc = subprocess.run(
        [sys.executable, "-m", "frankenpaxos_tpu.analysis",
         "--list-rules"], capture_output=True, text=True, timeout=300)
    assert proc.returncode == 0
    for rule in ("SAFE901", "SAFE902", "SAFE903", "SAFE904", "SAFE905",
                 "ALIAS1001", "ALIAS1002"):
        assert rule in proc.stdout


def test_safe905_nested_resend_def_is_not_post_send(tmp_path):
    """The repo's resend-timer idiom: a Phase1b send inside a nested
    ``def resend()`` has no post-send region in the ENCLOSING handler
    (the outer statements run before the timer ever fires)."""
    findings = run_rules(role_project(tmp_path, """
    class Phase1b:
        pass

    class Fine(Actor):
        def receive(self, src, message):
            if message.round < self.round:
                return
            def resend():
                self.send(src, Phase1b(round=self.round))
            self.timer("resend", 1.0, resend)
            self.round = message.round
            self.send(src, Phase1b(round=self.round))
    """))
    assert "SAFE905" not in rules_of(findings)


def test_safe901_tuple_unpacking_write_is_visible(tmp_path):
    """``self.round, self.vote_round = m.round, m.round`` is the same
    unguarded adoption as the plain assignment."""
    findings = run_rules(role_project(tmp_path, """
    class Bad(Actor):
        def receive(self, src, message):
            self.round, self.vote_round = message.round, message.round
    """))
    assert any(f.rule == "SAFE901" and f.detail == "self.round"
               for f in findings)
    assert any(f.rule == "SAFE902" and f.detail == "self.vote_round"
               for f in findings)
