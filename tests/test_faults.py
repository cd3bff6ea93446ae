"""Chaos/property tests for the fault-injection + recovery loop.

The invariant the property sweep enforces: for ANY seeded fault schedule,
a resumable run either **completes bit-identically** to a fault-free run
or raises an **explicit** error (``SegmentRetriesExhausted`` /
``NoHealthyHostsError``) — never a silently wrong histogram. Schedules are
pure functions of their seed, so every swept case is exactly replayable
(and the sweep asserts that too).

Plus the NodeDoctor wiring: a persistently failing host must alarm via the
paper's own SPM/CUSUM machinery and get its shards re-assigned to healthy
hosts instead of being retried forever.
"""

import jax
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.core.resume import ResumableRunner
from repro.faults import (
    FaultInjector,
    FaultPlan,
    NoHealthyHostsError,
    RetryPolicy,
    SegmentRetriesExhausted,
    SimulatedKill,
    TelemetryBuffer,
    TransientWorkerError,
)
from repro.launch.mesh import make_mesh
from repro.malgen import MalGenConfig, make_seed_streaming

CFG = MalGenConfig(num_sites=301, num_entities=1000,
                   marked_site_fraction=0.2, marked_event_fraction=0.3)
NUM_CHUNKS, CHUNK = 8, 512
NUM_HOSTS = 4
FAST_RETRY = RetryPolicy(max_attempts=4, backoff_s=0.0)

# hypothesis replays property bodies many times per test and cannot hand
# them function-scoped fixtures, so the shared runner + fault-free
# reference live in a module-level cache
_STATE: dict = {}


def _runner_and_ref():
    if not _STATE:
        mesh = make_mesh((1,), ("data",))
        seed = make_seed_streaming(jax.random.key(7), CFG, NUM_CHUNKS, CHUNK)
        runner = ResumableRunner(
            seed, CFG, mesh=mesh, num_chunks=NUM_CHUNKS, chunk_records=CHUNK,
            segment_chunks=2, backend="streams", statistic="B")
        _STATE["runner"] = runner
        _STATE["ref"] = runner.run()
    return _STATE["runner"], _STATE["ref"]


def _assert_identical(out, ref, msg):
    np.testing.assert_array_equal(np.asarray(out.result.total),
                                  np.asarray(ref.result.total), err_msg=msg)
    np.testing.assert_array_equal(np.asarray(out.result.marked),
                                  np.asarray(ref.result.marked), err_msg=msg)
    np.testing.assert_array_equal(np.asarray(out.result.rho),
                                  np.asarray(ref.result.rho), err_msg=msg)


# ------------------------------------------------------------ property sweep
@settings(max_examples=10, deadline=None)
@given(st.integers(0, 10_000),   # schedule seed
       st.integers(0, 40),       # transient failure rate, percent
       st.integers(0, NUM_HOSTS))  # 0 = no bad host, else host (n-1) is down
def test_any_schedule_completes_identically_or_raises(plan_seed, rate_pct,
                                                      bad_sel):
    runner, ref = _runner_and_ref()
    plan = FaultPlan(seed=plan_seed, transient_rate=rate_pct / 100.0,
                     bad_hosts=(bad_sel - 1,) if bad_sel else (),
                     kill_mode="raise")
    msg = f"schedule {plan}"

    def attempt():
        try:
            return runner.run(faults=plan, retry=FAST_RETRY,
                              num_hosts=NUM_HOSTS)
        except (SegmentRetriesExhausted, NoHealthyHostsError) as e:
            return e  # explicit failure — allowed; silent loss is not

    first = attempt()
    if isinstance(first, Exception):
        # exactly replayable: the same schedule fails the same way
        assert type(attempt()) is type(first), msg
        return
    _assert_identical(first, ref, msg)
    assert first.report.fault_events >= first.report.segments_retried, msg
    # replay: same schedule, same accounting, same bits
    second = attempt()
    assert not isinstance(second, Exception), msg
    _assert_identical(second, ref, msg)
    assert (second.report.segments_retried
            == first.report.segments_retried), msg
    assert second.report.fault_events == first.report.fault_events, msg


# --------------------------------------------------------- doctor rerouting
def test_persistent_bad_host_alarms_and_shards_reroute():
    runner, ref = _runner_and_ref()
    out = runner.run(faults=FaultPlan(bad_hosts=(0,), kill_mode="raise"),
                     retry=RetryPolicy(max_attempts=6, backoff_s=0.0),
                     num_hosts=NUM_HOSTS)
    _assert_identical(out, ref, "bad host 0")
    rep = out.report
    assert 0 in rep.alarmed_hosts, rep
    assert rep.rerouted_shards >= 1, rep
    assert rep.segments_retried >= 1, rep


def test_all_hosts_bad_raises_no_healthy_hosts():
    runner, _ = _runner_and_ref()
    with pytest.raises((NoHealthyHostsError, SegmentRetriesExhausted)):
        runner.run(faults=FaultPlan(bad_hosts=(0, 1), kill_mode="raise"),
                   retry=RetryPolicy(max_attempts=8, backoff_s=0.0),
                   num_hosts=2)


def test_retry_budget_exhaustion_is_explicit():
    # one host, always down, nowhere to reroute when it alarms
    runner, _ = _runner_and_ref()
    with pytest.raises((SegmentRetriesExhausted, NoHealthyHostsError)):
        runner.run(faults=FaultPlan(bad_hosts=(0,), kill_mode="raise"),
                   retry=RetryPolicy(max_attempts=3, backoff_s=0.0),
                   num_hosts=1)


def test_straggler_completes_identically():
    runner, ref = _runner_and_ref()
    sleeps = []
    plan = FaultPlan(straggler_host=0, straggler_delay_s=0.01)
    injector = FaultInjector(plan, sleep=sleeps.append)
    out = runner.run(faults=injector, num_hosts=NUM_HOSTS)
    _assert_identical(out, ref, "straggler")
    assert sleeps and all(s == 0.01 for s in sleeps)
    assert out.report.alarmed_hosts == []  # slow is not failed


# ------------------------------------------------------------ telemetry unit
def test_telemetry_buckets_and_validation():
    buf = TelemetryBuffer(2, num_buckets=4, bucket_width_s=0.1)
    assert buf.bucket(0.0) == 0
    assert buf.bucket(0.25) == 2
    assert buf.bucket(99.0) == 3  # clamped to the last bucket
    with pytest.raises(ValueError, match="out of range"):
        buf.record(2, 0, 0.0, False)
    buf.record(0, 0, 0.0, False)
    buf.record(1, 0, 0.0, True)
    assert len(buf) == 2 and buf.failures == 1


def test_telemetry_clean_fleet_never_alarms():
    buf = TelemetryBuffer(NUM_HOSTS)
    for seg in range(8):
        for h in range(NUM_HOSTS):
            buf.record(h, seg, 0.01, False)
    assert buf.alarmed_hosts() == []


def test_telemetry_single_transient_stays_quiet():
    # the fixed 5% baseline exists exactly for this: one transient on an
    # otherwise clean host must NOT alarm it (a data-derived median
    # baseline would clip to ~0 and fire immediately)
    buf = TelemetryBuffer(NUM_HOSTS)
    buf.record(1, 0, 0.0, True)
    for seg in range(6):
        for h in range(NUM_HOSTS):
            buf.record(h, seg, 0.01, False)
    assert buf.alarmed_hosts() == []


def test_telemetry_persistent_failures_alarm_only_that_host():
    buf = TelemetryBuffer(NUM_HOSTS)
    for seg in range(6):
        buf.record(0, seg, 0.0, True)          # host 0: fails every segment
        for h in range(1, NUM_HOSTS):
            buf.record(h, seg, 0.01, False)
    assert buf.alarmed_hosts() == [0]


# ----------------------------------------------------------- fault plan unit
def test_fault_plan_parse_roundtrip():
    plan = FaultPlan.parse("transient_rate=0.25,seed=5,bad_hosts=1+3,"
                           "kill_at_segment=2,kill_mode=raise,"
                           "straggler_host=0,straggler_delay_s=0.5")
    assert plan.transient_rate == 0.25 and plan.seed == 5
    assert plan.bad_hosts == (1, 3)
    assert plan.kill_at_segment == 2 and plan.kill_mode == "raise"
    assert plan.straggler_host == 0 and plan.straggler_delay_s == 0.5
    assert plan.any_kill


def test_fault_plan_parse_rejects_garbage():
    with pytest.raises(ValueError, match="unknown fault key"):
        FaultPlan.parse("frobnicate=1")
    with pytest.raises(ValueError, match="key=value"):
        FaultPlan.parse("justakey")
    with pytest.raises(ValueError, match="transient_rate"):
        FaultPlan(transient_rate=1.5)
    with pytest.raises(ValueError, match="kill_mode"):
        FaultPlan(kill_mode="sigterm")


def test_injector_coin_is_deterministic_and_uniform_range():
    inj = FaultInjector(FaultPlan(seed=9))
    a = inj._coin(1, 2, 3, 4)
    assert a == FaultInjector(FaultPlan(seed=9))._coin(1, 2, 3, 4)
    assert a != FaultInjector(FaultPlan(seed=10))._coin(1, 2, 3, 4)
    assert 0.0 <= a < 1.0


def test_injector_kill_points():
    inj = FaultInjector(FaultPlan(kill_at_segment=3, kill_mode="raise"))
    inj.before_segment(2)  # no kill
    with pytest.raises(SimulatedKill):
        inj.before_segment(3)
    inj2 = FaultInjector(FaultPlan(kill_mid_checkpoint_step=2,
                                   kill_mode="raise"))
    assert inj2.checkpoint_hook(1) is None
    hook = inj2.checkpoint_hook(2)
    import pathlib
    with pytest.raises(SimulatedKill):
        hook(pathlib.Path("/tmp/.tmp_step_2_x"))


def test_injector_shard_attempt_faults_and_audit():
    inj = FaultInjector(FaultPlan(bad_hosts=(1,)), sleep=lambda s: None)
    assert inj.shard_attempt(0, 0, 0, 1) == 0.0
    with pytest.raises(TransientWorkerError) as e:
        inj.shard_attempt(0, 0, 1, 1)
    assert e.value.host == 1 and e.value.segment == 0
    assert inj.fault_count == 1
    assert ("fail_bad_host", 0, 0, 1) in inj.events


# ---------------------------------------------------------------- retry unit
def test_retry_policy_backoff_schedule():
    p = RetryPolicy(max_attempts=5, backoff_s=0.1, backoff_factor=2.0,
                    max_backoff_s=0.35)
    assert [p.backoff(a) for a in (1, 2, 3, 4)] == [0.1, 0.2, 0.35, 0.35]
    assert RetryPolicy(backoff_s=0.0).backoff(3) == 0.0
    with pytest.raises(ValueError, match="max_attempts"):
        RetryPolicy(max_attempts=0)


def test_retry_policy_wait_uses_injected_sleep():
    p = RetryPolicy(backoff_s=0.5)
    slept = []
    assert p.wait(1, sleep=slept.append) == 0.5
    assert slept == [0.5]
    assert RetryPolicy(backoff_s=0.0).wait(1, sleep=slept.append) == 0.0
    assert slept == [0.5]  # zero backoff never calls sleep


# ------------------------------------------------------------ bench wiring
def test_resume_scenarios_registered_and_in_smoke_preset():
    from repro.bench.registry import SCENARIOS, preset_scenario_names
    names = {"resume_overhead_nockpt", "resume_overhead_ckpt",
             "resume_overhead_resume", "faulty_run_transient",
             "faulty_run_badhost"}
    assert names <= set(SCENARIOS)
    assert names <= set(preset_scenario_names("smoke"))
    for n in names:
        assert SCENARIOS[n].group == "resume"
