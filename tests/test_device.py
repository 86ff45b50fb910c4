"""One process per chip: who claims the TPU, who is pinned to the CPU,
where compiled executables go, and what a launch may look like."""

import dataclasses
import hashlib
import os

import jax
import pytest

from frankenpaxos_tpu import device, native
from frankenpaxos_tpu.bench.deploy_suite import (
    chip_process_env,
    launch_plan,
    role_process_env,
)
from frankenpaxos_tpu.bench.harness import _ephemeral_floor, free_port
from frankenpaxos_tpu.deploy import get_protocol


def _multipaxos():
    protocol = get_protocol("multipaxos")
    ports = iter(range(20000, 21000))
    config = protocol.load_config(
        protocol.cluster(1, lambda: ["127.0.0.1", next(ports)]))
    return protocol, config


def test_launch_plan_colocates_the_device_role_in_one_unpinned_process():
    protocol, config = _multipaxos()
    plan = launch_plan(protocol, config, {"quorum_backend": "tpu"},
                       supernode=False)
    owners = [entry for entry in plan if entry[2]]
    assert owners == [("proxy_leader", "0,1", True)]
    # Everyone else: one CPU-pinned process per instance.
    assert ("leader", "0", False) in plan and ("leader", "1", False) in plan
    assert sum(name == "acceptor" for name, _, _ in plan) == 3
    assert not any(name == "proxy_leader" and not owns
                   for name, _, owns in plan)


def test_launch_plan_without_a_device_backend_pins_everything():
    protocol, config = _multipaxos()
    plan = launch_plan(protocol, config, {"quorum_backend": "dict"},
                       supernode=False)
    assert not any(owns for _, _, owns in plan)
    assert ("proxy_leader", "0", False) in plan
    assert ("proxy_leader", "1", False) in plan


def test_launch_plan_refuses_two_chip_owners():
    protocol, config = _multipaxos()
    overrides = {"quorum_backend": "tpu", "phase1_backend": "tpu"}
    with pytest.raises(ValueError, match="leader.*proxy_leader"):
        launch_plan(protocol, config, overrides, supernode=False)
    # One process hosting every role is the way to run both.
    assert launch_plan(protocol, config, overrides, supernode=True) == [
        ("supernode", "0", True)]


def test_only_the_chip_owner_inherits_the_platform(monkeypatch):
    monkeypatch.delenv("JAX_PLATFORMS")
    assert "JAX_PLATFORMS" not in chip_process_env()
    assert role_process_env()["JAX_PLATFORMS"] == "cpu"
    monkeypatch.setenv("JAX_PLATFORMS", "cpu")
    assert chip_process_env()["JAX_PLATFORMS"] == "cpu"


@pytest.mark.parametrize("index", ["2", "0,7", "x"])
def test_cli_rejects_a_bad_index_before_starting_anything(tmp_path, index):
    import json

    from frankenpaxos_tpu import cli

    protocol = get_protocol("multipaxos")
    ports = iter(range(20000, 21000))
    config_path = tmp_path / "config.json"
    config_path.write_text(json.dumps(
        protocol.cluster(1, lambda: ["127.0.0.1", next(ports)])))
    with pytest.raises(SystemExit, match="--index"):
        cli.main(["--protocol", "multipaxos", "--role", "proxy_leader",
                  "--index", index, "--config", str(config_path)])


@pytest.fixture
def cache_config():
    """The three cache settings, restored after the test."""
    names = ("jax_compilation_cache_dir",
             "jax_persistent_cache_min_compile_time_secs",
             "jax_persistent_cache_min_entry_size_bytes")
    before = {name: getattr(jax.config, name) for name in names}
    yield
    for name, value in before.items():
        jax.config.update(name, value)


def test_compile_cache_goes_to_the_fixed_path_in_the_checkout(
        monkeypatch, cache_config):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    device.configure_compile_cache()
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    assert jax.config.jax_compilation_cache_dir == os.path.join(
        repo, ".jax_cache")
    assert jax.config.jax_persistent_cache_min_compile_time_secs == 0.0
    assert jax.config.jax_persistent_cache_min_entry_size_bytes == -1


def test_compile_cache_env_var_is_left_to_jax(monkeypatch, cache_config):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/some/dir")
    before = jax.config.jax_compilation_cache_dir
    device.configure_compile_cache()
    assert jax.config.jax_compilation_cache_dir == before
    assert jax.config.jax_persistent_cache_min_compile_time_secs == 0.0


def test_claim_tpu_under_an_explicit_cpu_pin(cache_config):
    assert device.explicit_cpu()  # conftest sets it
    before = jax.config.jax_compilation_cache_dir
    found = device.claim_tpu()
    assert found["platform"] == "cpu" and found["count"] == len(
        jax.devices())
    assert jax.config.jax_compilation_cache_dir == before


def test_native_library_is_keyed_on_the_source_content():
    lib = native.require()
    assert lib is native.load()
    with open(native._SRC, "rb") as f:
        digest = hashlib.sha256(f.read()).hexdigest()[:16]
    built = native._lib_path()
    assert digest in os.path.basename(built) and os.path.exists(built)
    leftovers = [name for name in os.listdir(native._DIR)
                 if name.startswith("libfpxcodec")
                 and os.path.join(native._DIR, name) != built]
    assert leftovers == []


def test_native_require_raises_where_load_falls_back(monkeypatch):
    monkeypatch.setattr(native, "_lib", None)
    monkeypatch.setattr(native, "_load_failed", True)
    monkeypatch.setattr(native, "_load_error", "g++ not found")
    assert native.load() is None
    with pytest.raises(RuntimeError, match="g\\+\\+ not found"):
        native.require()


def test_free_ports_are_distinct_and_below_the_ephemeral_range():
    ports = [free_port() for _ in range(200)]
    assert len(set(ports)) == 200
    assert max(ports) < _ephemeral_floor()


def test_tracker_counts_where_its_work_went():
    from frankenpaxos_tpu.protocols.multipaxos.quorum_tracker import (
        TpuQuorumTracker,
    )

    _, config = _multipaxos()
    tracker = TpuQuorumTracker(config, window=1 << 10)
    # A narrow drain is a device drain like any other: one launch.
    for acceptor in (0, 1):
        tracker.record_range(0, 8, 0, 0, acceptor)
    assert tracker.drain() == []
    assert len(tracker.collect(tracker.take_dispatch())) == 8
    assert (tracker.device_drains, tracker.device_votes) == (1, 16)
    assert tracker.device_launches == 1
    # A wide one with 36 slots a vote short: the board keeps them, and
    # their second votes complete them in a later drain.
    tracker.record_range(100, 200, 0, 0, 0)
    tracker.record_range(100, 164, 0, 0, 1)
    assert tracker.drain() == []
    assert len(tracker.collect(tracker.take_dispatch())) == 64
    assert (tracker.device_drains, tracker.device_votes) == (2, 180)
    tracker.record_range(164, 200, 0, 0, 2)
    assert tracker.drain() == []
    assert len(tracker.collect(tracker.take_dispatch())) == 36
    assert (tracker.device_drains, tracker.device_votes) == (3, 216)
    assert tracker.device_launches == 3
    # What benchmark/harness/role_entry.py still reads: no host tally.
    assert (tracker.host_drains, tracker.host_votes,
            tracker.spilled_votes) == (0, 0, 0)


def test_overrides_of_the_tracker_mode_that_went_are_ignored_and_listed():
    """``benchmark/configs/*.json`` still pass ``tpu_pipelined``, and an
    old deployment may pass ``tpu_min_device_slots``: no options class
    declares either now, so the proxy leader is built on the board
    without them and the role's start-up lists them as unused
    (``cli.py`` logs ``unmatched_overrides()`` and goes on)."""
    from frankenpaxos_tpu.deploy import DeployCtx
    from frankenpaxos_tpu.protocols.multipaxos import ProxyLeaderOptions
    from frankenpaxos_tpu.protocols.multipaxos.quorum_tracker import (
        TpuQuorumTracker,
    )
    from frankenpaxos_tpu.runtime import FakeLogger, SimTransport

    protocol, config = _multipaxos()
    logger = FakeLogger()
    ctx = DeployCtx(
        config=config, transport=SimTransport(logger), logger=logger,
        overrides={"quorum_backend": "tpu", "tpu_window": "256",
                   "tpu_pipelined": "true", "tpu_min_device_slots": "1",
                   "coalesce_writes": "true"})
    role = protocol.roles["proxy_leader"]
    proxy_leader = role.make(ctx, role.addresses(config)[0], 0)
    assert ctx.unmatched_overrides() == [
        "coalesce_writes", "tpu_min_device_slots", "tpu_pipelined"]
    assert {"quorum_backend", "tpu_window"} <= ctx.consumed
    assert not {"tpu_pipelined", "tpu_min_device_slots",
                "tpu_flush_period_s"} & {
        f.name for f in dataclasses.fields(ProxyLeaderOptions)}
    assert type(proxy_leader.tracker) is TpuQuorumTracker
    assert proxy_leader.tracker.checker.window == 256
    # Built for a SimTransport: the flush timer, which collects.
    assert proxy_leader._flush_timer is not None
