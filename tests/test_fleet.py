"""Multi-tenant fleet controller tests (docs/fleet.md).

Unit tier: spec validation, the pure placement functions, the
``set_target_np`` multi-caller lever, and the FleetController control
logic against fake drivers (spike → preemption-by-elasticity,
preempt-to-zero → suspend/resume, host death → fleet-wide blacklist,
resize-storm debounce, journaled controller restart without
double-preemption).

Integration tier: a REAL 2-proc elastic training job suspended at a
commit boundary by :meth:`ElasticDriver.suspend` — workers self-abort
cleanly, the job resumes from the journal + last elastic commit, and
the batch sequence continues from the committed step (the ISSUE 13
acceptance assertion).
"""

import json
import os
import sys
import textwrap
import threading
import time

import pytest

from horovod_tpu.fleet import (
    FleetController, PENDING, RUNNING, SUSPENDED,
    assign_hosts, parse_spec, size_jobs,
)

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


# ---------------------------------------------------------------------------
# spec

def _spec(pool=None, jobs=None, options=None):
    doc = {"pool": pool or {"a": 2, "b": 2},
           "jobs": jobs or [
               {"name": "serve", "kind": "serving", "min_np": 1,
                "max_np": 2, "priority": 10, "command": ["s"],
                "slo": {"p99_ms": 50, "queue_high": 4}},
               {"name": "train", "kind": "training", "min_np": 1,
                "max_np": 3, "command": ["t"]},
           ]}
    if options:
        doc["options"] = options
    return parse_spec(json.dumps(doc))


def test_spec_parses_jobs_pool_and_options():
    spec = _spec(options={"reconcile_seconds": 1.0,
                          "settle_ticks": 3, "cooldown_ticks": 7,
                          "blacklist_ticks": 9})
    assert spec.pool_hosts == ["a", "b"]
    assert [j.name for j in spec.jobs] == ["serve", "train"]
    assert spec.job("serve").slo["p99_ms"] == 50
    assert spec.options.cooldown_ticks == 7
    assert spec.options.blacklist_ticks == 9


@pytest.mark.parametrize("mutate,frag", [
    (lambda d: d.pop("pool"), "pool"),
    (lambda d: d.pop("jobs"), "jobs"),
    (lambda d: d["jobs"][0].pop("command"), "command"),
    (lambda d: d["jobs"][0].update(kind="batch"), "kind"),
    (lambda d: d["jobs"][0].update(min_np=3, max_np=2), "min_np"),
    (lambda d: d["jobs"][1].update(name="serve"), "duplicate"),
    (lambda d: d["jobs"][1].update(slo={"p99_ms": 9}), "slo"),
    (lambda d: d["pool"].update(a=0), "slot"),
])
def test_spec_validation_rejects(mutate, frag):
    doc = {"pool": {"a": 2},
           "jobs": [
               {"name": "serve", "kind": "serving", "min_np": 1,
                "max_np": 1, "command": ["s"]},
               {"name": "train", "kind": "training", "min_np": 1,
                "max_np": 1, "command": ["t"]},
           ]}
    mutate(doc)
    with pytest.raises(ValueError, match=frag):
        parse_spec(json.dumps(doc))


# ---------------------------------------------------------------------------
# placement (pure functions)

def _jobs_in(*rows):
    out = []
    for name, kind, lo, hi, demand, prio in rows:
        out.append({"name": name, "kind": kind, "min_np": lo,
                    "max_np": hi, "demand": demand, "priority": prio,
                    "active": True})
    return out


def test_size_jobs_serving_min_guaranteed_first():
    sizes = size_jobs(4, _jobs_in(
        ("train", "training", 2, 4, 4, 0),
        ("serve", "serving", 2, 4, 2, 0)))
    # serving's min claims before training's greedy demand
    assert sizes == {"serve": 2, "train": 2}


def test_size_jobs_training_soaks_surplus_and_suspends_on_scarcity():
    sizes = size_jobs(6, _jobs_in(
        ("serve", "serving", 1, 4, 1, 10),
        ("train", "training", 2, 8, 8, 0)))
    assert sizes == {"serve": 1, "train": 5}
    # serving demand spike squeezes training toward min...
    sizes = size_jobs(6, _jobs_in(
        ("serve", "serving", 1, 4, 4, 10),
        ("train", "training", 2, 8, 8, 0)))
    assert sizes == {"serve": 4, "train": 2}
    # ...and under real scarcity training suspends (0), never partial
    # below min
    sizes = size_jobs(3, _jobs_in(
        ("serve", "serving", 2, 4, 2, 10),
        ("train", "training", 2, 8, 8, 0)))
    assert sizes == {"serve": 2, "train": 0}


def test_size_jobs_suspension_surplus_reaches_later_serving_claims():
    """Chips freed by suspending a training job must not strand while
    a LATER serving claim is still unmet — every unmet serving claim
    drains the running surplus before (and after) suspensions."""
    sizes = size_jobs(8, _jobs_in(
        ("A", "serving", 1, 6, 6, 20),
        ("B", "serving", 1, 3, 3, 10),
        ("T", "training", 4, 4, 4, 0)))
    # A's claim suspends T (frees 4): A tops up to 6, the remaining
    # freed chip flows to B — capacity fully spent, nothing stranded
    assert sizes == {"A": 6, "B": 2, "T": 0}
    assert sum(sizes.values()) == 8


def test_size_jobs_is_deterministic_in_spec_order():
    jobs = _jobs_in(
        ("t1", "training", 1, 4, 4, 0),
        ("t2", "training", 1, 4, 4, 0))
    # mins first for everyone, then surplus greedily in claim order —
    # and training demand can never suspend a sibling training job
    assert size_jobs(5, jobs) == {"t1": 4, "t2": 1}
    assert size_jobs(5, jobs) == size_jobs(5, jobs)


def test_size_jobs_serving_demand_preempts_training_min_to_zero():
    # surplus exhausted: the serving claim suspends the training job
    # entirely (never a partial below min_np)
    sizes = size_jobs(3, _jobs_in(
        ("serve", "serving", 1, 2, 2, 10),
        ("train", "training", 2, 2, 2, 0)))
    assert sizes == {"serve": 2, "train": 0}
    # ...but training demand never suspends another training job
    sizes = size_jobs(3, _jobs_in(
        ("t1", "training", 1, 8, 8, 10),
        ("t2", "training", 2, 2, 2, 0)))
    assert sizes == {"t1": 1, "t2": 2}


def test_assign_hosts_contiguous_serving_first():
    sizes = {"serve": 2, "train": 3}
    alloc = assign_hosts({"a": 2, "b": 2, "c": 2}, ["a", "b", "c"],
                         sizes, ["serve", "train"])
    assert alloc["serve"] == {"a": 2}
    assert alloc["train"] == {"b": 2, "c": 1}


# ---------------------------------------------------------------------------
# set_target_np multi-caller lever (ISSUE 13 satellite)

def _bare_driver(hosts=None, min_np=1, max_np=4):
    from horovod_tpu.runner.elastic.discovery import (
        FixedHosts, HostManager,
    )
    from horovod_tpu.runner.elastic.driver import ElasticDriver

    driver = ElasticDriver.__new__(ElasticDriver)
    driver._host_manager = HostManager(
        FixedHosts(hosts or {"a": 2, "b": 2}), None)
    driver._host_manager.update_available_hosts()
    driver._min_np = min_np
    driver._max_np = max_np
    driver._target_np = max_np
    driver._round = 0
    driver._assignments = {}
    driver._lock = threading.RLock()
    driver._shutdown = threading.Event()
    driver._on_event = None
    driver._lever_owner = None
    driver._lever_epoch = -1
    driver._suspended = False
    return driver


def test_lever_owner_excludes_other_callers():
    driver = _bare_driver()
    driver.acquire_target_lever("fleet")
    # the autoscaler racing the fleet is serialized out
    assert driver.set_target_np(1, owner="autoscale") == 4
    assert driver._target_np == 4
    # the owner's write lands
    assert driver.set_target_np(2, owner="fleet", epoch=5) == 2
    # un-tagged writers (legacy callers) are excluded too
    assert driver.set_target_np(3) == 2
    driver.release_target_lever()
    assert driver.set_target_np(3) == 3


def test_lever_epoch_last_writer_wins():
    driver = _bare_driver()
    driver.acquire_target_lever("fleet")
    assert driver.set_target_np(3, owner="fleet", epoch=10) == 3
    # a delayed write from an older reconcile tick is stale: dropped
    assert driver.set_target_np(1, owner="fleet", epoch=9) == 3
    assert driver._target_np == 3
    # same-epoch re-assertion and newer epochs apply
    assert driver.set_target_np(2, owner="fleet", epoch=10) == 2
    assert driver.set_target_np(4, owner="fleet", epoch=11) == 4


def test_noop_effective_change_does_not_reform_round():
    """PR 6 hardening extended to multi-caller: a target move whose
    EFFECTIVE size (min(slots, target)) is unchanged must not re-form
    the round, whichever caller issued it."""
    driver = _bare_driver(hosts={"a": 2}, max_np=4)  # 2 slots only
    driver._round = 3
    driver._assignments = {"a:0": 0, "a:1": 1}
    calls = []
    driver._start_round = lambda: calls.append(1)
    # 4 -> 3: effective stays min(2 slots, target) = 2 — no round
    assert driver.set_target_np(3) == 3
    assert calls == []
    # racing second caller re-asserts the same effective size
    driver.acquire_target_lever("fleet")
    assert driver.set_target_np(4, owner="fleet", epoch=1) == 4
    assert calls == []
    # a move that changes the effective size DOES re-form
    assert driver.set_target_np(1, owner="fleet", epoch=2) == 1
    assert calls == [1]


def test_suspended_driver_forms_no_rounds():
    driver = _bare_driver()
    driver._round = 1
    driver._assignments = {"a:0": 0}
    driver._suspended = True
    # _start_round's own suspension guard must refuse: a discovery
    # blip or late set_target_np on a suspended job must not form a
    # round behind the controller's back
    driver._start_round()
    assert driver._round == 1          # unchanged: no new round


# ---------------------------------------------------------------------------
# controller logic against fake drivers

class FakeDriver:
    def __init__(self):
        self.calls = []
        self.suspended = False
        self.started = False
        self._fin = False
        self._err = False
        self.lever_owner = None

    def acquire_target_lever(self, owner):
        self.lever_owner = owner

    def set_target_np(self, n, owner=None, epoch=None):
        self.calls.append((n, owner, epoch))
        return n

    def start(self, start_timeout=None):
        self.started = True

    def suspend(self):
        self.suspended = True

    def unsuspend(self):
        self.suspended = False

    def finished(self):
        return self._fin

    @property
    def _error(self):
        return self._err

    def stop(self):
        pass


def _controller(spec, **kwargs):
    drivers = {}

    def factory(job_spec, discovery, on_event):
        d = FakeDriver()
        drivers[job_spec.name] = d
        return None, d

    c = FleetController(spec, driver_factory=factory, **kwargs)
    return c, drivers


def test_controller_places_and_owns_every_lever():
    c, drivers = _controller(_spec())
    c.start()
    snap = c.snapshot()
    assert snap["jobs"]["serve"]["np"] == 1
    assert snap["jobs"]["train"]["np"] == 3
    assert drivers["serve"].lever_owner == "fleet"
    assert drivers["train"].lever_owner == "fleet"
    assert drivers["train"].calls[-1] == (3, "fleet", 1)


def test_controller_spike_preempts_training_and_returns_chips():
    c, drivers = _controller(
        _spec(options={"cooldown_ticks": 3, "settle_ticks": 1}))
    c.start()
    # SLO breach raises the serving demand (policy output); the
    # reconcile must grow serve AND shrink train through the lever
    c._by_name["serve"].demand = 2
    c.reconcile()
    snap = c.snapshot()["jobs"]
    assert snap["serve"]["np"] == 2 and snap["train"]["np"] == 2
    assert drivers["train"].calls[-1][0] == 2
    assert {"e": "place", "job": "train", "np": 2,
            "cause": "capacity"} in c.decisions
    # spike over: serve gives back immediately, train reclaim is
    # debounced by cooldown_ticks — then the chips return
    c._by_name["serve"].demand = 1
    c.reconcile()
    assert c.snapshot()["jobs"]["serve"]["np"] == 1
    assert c.snapshot()["jobs"]["train"]["np"] == 2   # still cooling
    for _ in range(4):
        c.reconcile()
    assert c.snapshot()["jobs"]["train"]["np"] == 3
    assert drivers["train"].calls[-1][0] == 3


def test_controller_preempt_to_zero_suspends_not_kills():
    spec = _spec(pool={"a": 3},
                 jobs=[{"name": "serve", "kind": "serving",
                        "min_np": 1, "max_np": 2, "priority": 10,
                        "command": ["s"]},
                       {"name": "train", "kind": "training",
                        "min_np": 2, "max_np": 2, "command": ["t"]}],
                 options={"settle_ticks": 1, "cooldown_ticks": 1})
    c, drivers = _controller(spec)
    c.start()
    assert c.snapshot()["jobs"]["train"]["np"] == 2
    # serving demand takes the pool below train's min -> suspend
    c._by_name["serve"].demand = 2
    c.reconcile()
    snap = c.snapshot()["jobs"]
    assert snap["train"]["state"] == SUSPENDED
    assert snap["train"]["np"] == 0
    assert drivers["train"].suspended
    assert {"e": "suspend", "job": "train"} in c.decisions
    # capacity returns -> resume through the SAME reconcile loop
    c._by_name["serve"].demand = 1
    c.reconcile()
    snap = c.snapshot()["jobs"]
    assert snap["train"]["state"] == RUNNING
    assert not drivers["train"].suspended
    assert {"e": "resume", "job": "train", "np": 2} in c.decisions


def test_controller_host_death_blacklists_for_all_jobs():
    """A host failure observed by ONE job's driver must remove the
    host from EVERY job's placement (the fault-tolerance composition
    claim)."""
    spec = _spec(pool={"a": 2, "b": 2},
                 jobs=[{"name": "j1", "kind": "training", "min_np": 1,
                        "max_np": 2, "command": ["x"]},
                       {"name": "j2", "kind": "training", "min_np": 1,
                        "max_np": 2, "command": ["y"]}],
                 options={"blacklist_ticks": 100, "settle_ticks": 1,
                          "cooldown_ticks": 1})
    c, drivers = _controller(spec)
    c.start()
    assert c.snapshot()["jobs"]["j1"]["np"] == 2
    assert c.snapshot()["jobs"]["j2"]["np"] == 2
    # j2's driver reports a worker death on host b
    c._on_job_event(c._by_name["j2"])(
        {"event": "worker_dead", "host": "b"})
    c.reconcile()
    snap = c.snapshot()
    assert "b" in snap["blacklisted"]
    # BOTH jobs lost their b slots: 2 remaining slots, one each
    assert snap["jobs"]["j1"]["np"] == 1
    assert snap["jobs"]["j2"]["np"] == 1
    assert {"e": "blacklist", "host": "b"} in c.decisions
    for j in ("j1", "j2"):
        assert "b" not in snap["jobs"][j]["alloc"]


def test_controller_revoke_restore_storm_is_debounced():
    """Chaos revoke_host/restore_host flapping inside the settle
    window must produce at most ONE shrink + ONE grow (hysteresis —
    the no-thrash half of the day-in-the-life gate)."""
    spec = _spec(options={"settle_ticks": 3, "cooldown_ticks": 2})
    c, drivers = _controller(spec)
    c.start()
    for _ in range(3):
        c.reconcile()                 # past start-up cooldowns
    before = [d for d in c.decisions if d["e"] == "place"]
    # storm: flap host b on consecutive ticks
    for _ in range(3):
        c.revoke_host("b")
        c.reconcile()
        c.restore_host("b")
        c.reconcile()
    for _ in range(6):                # settle + reclaim
        c.reconcile()
    places = [d for d in c.decisions if d["e"] == "place"][len(before):]
    train_places = [d for d in places if d["job"] == "train"]
    # one shrink when the host first vanished, one grow after the
    # storm settled — never one round per flap
    assert len(train_places) <= 3, train_places
    assert c.snapshot()["jobs"]["train"]["np"] == 3


def test_controller_journal_restart_reconciles_without_double_preempt(
        tmp_path):
    journal = str(tmp_path / "fleet.jsonl")
    spec = _spec(pool={"a": 3},
                 jobs=[{"name": "serve", "kind": "serving",
                        "min_np": 1, "max_np": 2, "priority": 10,
                        "command": ["s"]},
                       {"name": "train", "kind": "training",
                        "min_np": 2, "max_np": 2, "command": ["t"]}],
                 options={"settle_ticks": 1, "cooldown_ticks": 1})
    c1, _d1 = _controller(spec, journal_path=journal)
    c1.start()
    c1._by_name["serve"].demand = 2
    c1.reconcile()                    # preempts train to zero
    assert c1.snapshot()["jobs"]["train"]["state"] == SUSPENDED
    # controller "crashes"; a new one resumes from the journal
    c2, d2 = _controller(spec, journal_path=journal, resume=True)
    c2.start()
    snap = c2.snapshot()["jobs"]
    # train restored SUSPENDED (not re-preempted, not spuriously
    # resumed while serve still holds its chips), serve restored at 2
    assert snap["train"]["state"] == SUSPENDED
    assert snap["serve"]["np"] == 2
    assert not d2["train"].suspended   # no NEW suspend was issued
    assert not any(d["e"] in ("suspend", "blacklist")
                   for d in c2.decisions), c2.decisions
    # and the restored demand keeps driving: spike ends -> train
    # resumes through the ordinary path
    c2._by_name["serve"].demand = 1
    c2.reconcile()
    assert c2.snapshot()["jobs"]["train"]["state"] == RUNNING
    assert d2["train"].started


def test_controller_tick_triggered_chaos_plan(tmp_path):
    """A seeded plan's revoke_host/restore_host fire at their named
    reconcile ticks, identically across two same-seed controllers."""
    plan = json.dumps({"seed": 7, "events": [
        {"kind": "revoke_host", "host": "b", "after": 3},
        {"kind": "restore_host", "host": "b", "after": 5},
    ]})
    logs = []
    for _run in (1, 2):
        c, _ = _controller(
            _spec(options={"settle_ticks": 1, "cooldown_ticks": 1}),
            env={"HOROVOD_FAULT_PLAN": plan})
        c.start()
        for _ in range(7):
            c.reconcile()
        logs.append(json.dumps(
            [d for d in c.decisions
             if d["e"] in ("revoke_host", "restore_host")],
            sort_keys=True))
        assert "b" not in c.snapshot()["revoked"]
    assert logs[0] == logs[1]
    assert json.loads(logs[0]) == [
        {"e": "revoke_host", "host": "b", "event": 0, "n": 3.0},
        {"e": "restore_host", "host": "b", "event": 1, "n": 5.0}]


def test_fleet_fault_plan_rejects_out_of_pool_targets():
    """A typo'd revoke_host target must fail the LAUNCH loudly, never
    silently drill a wrapped/wrong host."""
    for plan in (
            {"seed": 1, "events": [{"kind": "revoke_host",
                                    "host": "nope", "after": 1}]},
            {"seed": 1, "events": [{"kind": "revoke_host",
                                    "proc": 5, "after": 1}]}):
        with pytest.raises(ValueError, match="pool"):
            _controller(_spec(),
                        env={"HOROVOD_FAULT_PLAN": json.dumps(plan)})


def test_fleet_goodput_and_chips_families_exported():
    from horovod_tpu import telemetry

    c, _ = _controller(_spec())
    c.start()
    snap = c.registry.snapshot()
    fam = snap[telemetry.FLEET_CHIPS_FAMILY]
    by_job = {s["labels"]["job"]: s["value"] for s in fam["samples"]}
    assert by_job == {"serve": 1.0, "train": 3.0}
    assert telemetry.FLEET_JOB_RUNNING_FAMILY in snap


# ---------------------------------------------------------------------------
# bypass-vote × graceful-resize deadlock regression (found by the
# fleet smoke's resize storm)

WEDGE_WORKER = textwrap.dedent("""
    import os
    import numpy as np
    import horovod_tpu as hvd
    import horovod_tpu.elastic as elastic

    LOG = os.environ["HVD_TEST_LOG"]
    hvd.init()

    def log(msg):
        with open(LOG, "a") as f:
            f.write(msg + "\\n")

    state = elastic.ObjectState(
        bcast_object=hvd.broadcast_object, get_rank=hvd.rank,
        batch=0, at_small=0, grown=0)

    @elastic.run
    def train(state):
        while True:
            # ONE fixed-name tensor per step so the negotiation bypass
            # ARMS (a per-batch name would change the cycle
            # fingerprint and dodge the seam under test); no value
            # assertion — the property under test is CONVERGENCE
            # through the resize cycle, and a strict equality at a
            # resize edge would turn a transient into a crash
            hvd.allreduce(np.ones(16, np.float32), op=hvd.Sum,
                          name="wedge.step")
            state.batch += 1
            if hvd.size() == 1:
                state.at_small += 1
            if state.at_small > 0 and hvd.size() > 1:
                state.grown += 1
            if state.at_small >= 2 and state.grown >= 2:
                log(f"done rank {hvd.rank()} batch {state.batch}")
                return
            state.commit()

    train(state)
""")


@pytest.mark.integration
@pytest.mark.slow
def test_resize_with_armed_bypass_does_not_deadlock(tmp_path):
    """A graceful shrink racing an ARMED negotiation bypass used to
    deadlock: one worker blocks in the bypass agreement collective
    while its peers block in the clean-teardown coordination barrier
    waiting for it.  The bounded barrier
    (HOROVOD_TEARDOWN_BARRIER_SECONDS) + exec-restart escape must let
    the job ride a shrink-to-one and a grow-back to completion.

    Slow tier: the recovery path under test is exec-restart churn
    whose wall time balloons under CI load; ``ci.sh fleet`` exercises
    the same seam end-to-end (its storm phase is what found the
    deadlock) on every run of the fleet gate."""
    import secrets as _secrets

    from horovod_tpu.runner.elastic.discovery import FixedHosts
    from horovod_tpu.runner.elastic.driver import ElasticDriver
    from horovod_tpu.runner.http.http_server import RendezvousServer

    log = tmp_path / "log.txt"
    log.write_text("")
    worker = tmp_path / "worker.py"
    worker.write_text(WEDGE_WORKER)

    server = RendezvousServer(secret=_secrets.token_bytes(16),
                              world_size=0)
    server.start()
    driver = ElasticDriver(
        server, FixedHosts({"localhost": 1, "127.0.0.1": 2}),
        min_np=1, max_np=3,
        command=[sys.executable, str(worker)],
        env={"PYTHONPATH": REPO, "HVD_TEST_LOG": str(log),
             "JAX_NUM_CPU_DEVICES": "1",
             # arm the bypass quickly, keep the wedge escape tight
             "HOROVOD_BYPASS_AFTER_CYCLES": "3",
             "HOROVOD_TEARDOWN_BARRIER_SECONDS": "3"},
        platform="cpu", verbose=False)
    try:
        driver.start(start_timeout=240)
        deadline = time.monotonic() + 90
        while time.monotonic() < deadline and \
                driver.current_world_size() != 3:
            time.sleep(0.2)
        time.sleep(3.0)                      # let the bypass arm
        # shrink to ONE through the fleet's lever — the two departing
        # workers hit the teardown barrier while the survivor may sit
        # in a bypass vote
        driver.set_target_np(1)
        deadline = time.monotonic() + 120
        while time.monotonic() < deadline and \
                driver.current_world_size() != 1:
            time.sleep(0.2)
        assert driver.current_world_size() == 1
        # grow back; the job finishes only after running small AND
        # big again (see worker), proving both transitions converged
        time.sleep(2.0)
        driver.set_target_np(3)
        ok = driver.join(timeout=240)
        assert ok, "job did not converge after the resize cycle"
    finally:
        driver.stop()
        try:
            driver.join(timeout=30)
        except Exception:  # noqa: BLE001 — teardown
            pass
        server.stop()
    assert "done rank" in log.read_text(), log.read_text()


# ---------------------------------------------------------------------------
# suspend/resume against a REAL elastic job (ISSUE 13 acceptance)

SUSPEND_WORKER = textwrap.dedent("""
    import os
    import numpy as np
    import horovod_tpu as hvd
    import horovod_tpu.elastic as elastic

    LOG = os.environ["HVD_TEST_LOG"]
    hvd.init()

    def log(msg):
        with open(LOG, "a") as f:
            f.write(msg + "\\n")

    state = elastic.ObjectState(
        bcast_object=hvd.broadcast_object, get_rank=hvd.rank,
        batch=0, acc=0.0)

    @elastic.run
    def train(state):
        while state.batch < 10:
            out = hvd.allreduce(np.ones(2, np.float32), op=hvd.Sum,
                                name=f"b{state.batch}")
            # "loss": a deterministic accumulator over committed steps
            state.acc += float(state.batch)
            log(f"batch {state.batch} rank {hvd.rank()} "
                f"size {hvd.size()} acc {state.acc}")
            state.batch += 1
            state.commit()

    train(state)
    log(f"done rank {hvd.rank()} acc {state.acc}")
""")


@pytest.mark.integration
def test_driver_suspend_resume_real_job(tmp_path):
    """Preempt a REAL 2-proc training job to zero and resume it:
    workers drain at a commit boundary and SELF-ABORT cleanly (no
    kill), no worker process survives the suspension, and the resumed
    job continues from the journal + last elastic commit — every batch
    runs exactly once and the committed accumulator ends at the exact
    deterministic value."""
    import secrets as _secrets

    from horovod_tpu.runner.elastic.discovery import FixedHosts
    from horovod_tpu.runner.elastic.driver import ElasticDriver
    from horovod_tpu.runner.http.http_server import RendezvousServer

    log = tmp_path / "log.txt"
    log.write_text("")
    worker = tmp_path / "worker.py"
    worker.write_text(SUSPEND_WORKER)
    journal = tmp_path / "coord.jsonl"

    server = RendezvousServer(secret=_secrets.token_bytes(16),
                              world_size=0,
                              journal_path=str(journal))
    server.start()
    driver = ElasticDriver(
        server, FixedHosts({"localhost": 2}), min_np=2, max_np=2,
        command=[sys.executable, str(worker)],
        env={"PYTHONPATH": REPO, "HVD_TEST_LOG": str(log),
             "JAX_NUM_CPU_DEVICES": "1"},
        platform="cpu", verbose=False)
    try:
        driver.start(start_timeout=240)
        deadline = time.monotonic() + 120
        while time.monotonic() < deadline:
            if "batch 2" in log.read_text():
                break
            time.sleep(0.2)
        assert "batch 2" in log.read_text(), log.read_text()

        # (the driver's monitor drops a drained worker from _procs as
        # soon as it sees the exit: hold the processes themselves)
        procs = dict(driver._procs)
        assert len(procs) == 2, procs
        driver.suspend()
        assert driver.suspended
        # every worker must drain at its next commit and exit CLEANLY
        deadline = time.monotonic() + 90
        while time.monotonic() < deadline:
            if all(p.poll() is not None for p in procs.values()):
                break
            time.sleep(0.2)
        codes = {k: p.poll() for k, p in procs.items()}
        assert all(c == 0 for c in codes.values()), (
            f"workers did not self-abort cleanly: {codes}")
        batches_at_suspend = log.read_text().count("batch")
        # suspension is a PAUSE: nothing runs while suspended
        time.sleep(2.0)
        assert log.read_text().count("batch") == batches_at_suspend

        driver.unsuspend()
        assert not driver.suspended
        ok = driver.join(timeout=180)
        assert ok, "resumed job did not finish"
    finally:
        driver.stop()
        try:
            driver.join(timeout=30)
        except Exception:  # noqa: BLE001 — teardown
            pass
        server.stop()

    content = log.read_text()
    assert "done rank 0" in content, content
    # continuity from the committed step: rank 0 ran every batch
    # exactly once — the suspension neither lost nor re-ran steps
    rank0 = [line for line in content.splitlines()
             if " rank 0 " in line and line.startswith("batch")]
    seq = [int(line.split()[1]) for line in rank0]
    assert seq == list(range(10)), seq
    # the committed accumulator ("loss") continued exactly:
    # sum(range(10)) = 45.0
    assert "done rank 0 acc 45.0" in content, content
