"""Multi-tenant scheduling: fair-share DRR, quotas, priority preemption.

The controller's per-tenant queue groups (ray_tpu/_private/tenants.py +
Controller._try_dispatch_locked) are pinned here end-to-end:

- two saturating tenants' steady-state dispatch shares track the
  configured weights within 10%;
- an over-quota tenant PARKS at lease grant (no autoscale hint) and
  resumes when the quota is raised;
- a starved higher-priority tenant drain-migrates a lower-priority gang
  (zero failed tasks, restart budget uncharged) via the creation-lease
  re-placement path — driven against the scripted FakeAgent harness from
  test_actor_lease, so every wire interaction is the real protocol;
- tenant identity propagates to nested submits;
- autoscaler demand is attributed per tenant;
- head-restart snapshots round-trip configured tenant policy;
- the new ops are chaos-injectable through RAY_testing_rpc_failure.
"""

import time

import pytest

import ray_tpu
from ray_tpu.util.state.api import set_tenant_quota, tenant_stats

from tests.test_actor_lease import FakeAgent, _controller, _wait


def _rows():
    return {r["tenant"]: r for r in tenant_stats()}


@pytest.fixture
def thread_cluster():
    def start(num_cpus=2, **config):
        ray_tpu.init(num_cpus=num_cpus, mode="thread", config=config or None)

    yield start
    ray_tpu.shutdown()


# --------------------------------------------------------------- fair share


def test_two_tenant_saturation_shares_follow_weights(thread_cluster):
    """Saturate 2 CPU slots from two tenants with 3:1 weights: the DRR pop
    must keep steady-state dispatch shares within 10% of the configured
    split (24:8 out of every 32)."""
    thread_cluster(num_cpus=2)
    set_tenant_quota("heavy", weight=3.0)
    set_tenant_quota("light", weight=1.0)

    @ray_tpu.remote(num_cpus=1)
    def work():
        time.sleep(0.02)
        return 1

    n = 60
    refs = []
    for _ in range(n):
        refs.append(work.options(tenant="heavy").remote())
        refs.append(work.options(tenant="light").remote())

    def total_dispatched():
        rows = _rows()
        return (
            rows.get("heavy", {}).get("dispatched", 0)
            + rows.get("light", {}).get("dispatched", 0)
        )

    # sample mid-drain, while BOTH tenants still have queued work (heavy
    # exhausts its 60 only once ~80 total have dispatched at a 3:1 ratio)
    _wait(lambda: total_dispatched() >= 40, msg="steady-state dispatches")
    rows = _rows()
    h = rows["heavy"]["dispatched"]
    l = rows["light"]["dispatched"]
    share = h / (h + l)
    # configured share 0.75; within 10% relative
    assert 0.675 <= share <= 0.825, f"heavy share {share:.3f} ({h}:{l})"

    assert ray_tpu.get(refs, timeout=120) == [1] * (2 * n)
    # charge/credit symmetry: all work done -> both tenants' usage drains
    _wait(
        lambda: not _rows()["heavy"]["usage"]
        and not _rows()["light"]["usage"],
        msg="tenant usage returns to zero",
    )


def test_nested_submit_inherits_tenant(thread_cluster):
    """A task's nested submits bill to the parent's tenant — the whole
    task tree stays in one fair-share queue group."""
    thread_cluster(num_cpus=2)
    # configured tenants persist after their work drains (unconfigured
    # idle ones are reaped — see test_idle_unconfigured_tenant_reaped)
    set_tenant_quota("nest", weight=1.0)

    @ray_tpu.remote(num_cpus=1)
    def child():
        return 1

    @ray_tpu.remote(num_cpus=1)
    def parent():
        return ray_tpu.get(child.remote())

    assert ray_tpu.get(parent.options(tenant="nest").remote(), timeout=60) == 1
    assert _rows()["nest"]["dispatched"] >= 2  # parent AND child


def test_idle_unconfigured_tenant_reaped(thread_cluster):
    """Auto-created tenants (per driver/job) vanish from the registry once
    idle — nothing queued, nothing charged, no configured policy — so a
    long-lived head's scheduler state stays bounded. Configured tenants
    persist."""
    thread_cluster(num_cpus=2)
    set_tenant_quota("keeper", weight=2.0)

    @ray_tpu.remote(num_cpus=1)
    def one():
        return 1

    assert ray_tpu.get(one.options(tenant="ephemeral").remote(), timeout=60) == 1
    from tests.test_actor_lease import _wait as wait

    wait(
        lambda: "ephemeral" not in _rows(),
        msg="idle unconfigured tenant reaped",
    )
    assert "keeper" in _rows()


# -------------------------------------------------------------------- quota


def test_quota_parks_and_resumes_on_raise(thread_cluster):
    """An over-quota tenant's work parks at lease grant (usage never
    exceeds the cap, no autoscale demand is advertised) and resumes the
    moment the quota is raised."""
    thread_cluster(num_cpus=4)
    set_tenant_quota("capped", quota={"CPU": 1.0})

    @ray_tpu.remote(num_cpus=1)
    def nap():
        time.sleep(0.4)
        return "done"

    refs = [nap.options(tenant="capped").remote() for _ in range(3)]
    _wait(
        lambda: _rows()["capped"]["usage"].get("CPU") == 1.0
        and _rows()["capped"]["queued"] == 2,
        msg="two tasks parked behind the CPU=1 cap",
    )
    row = _rows()["capped"]
    # counts TASKS that parked (not scheduler wakeups): at most the two
    # queued tasks can have parked by now
    assert 1 <= row["quota_parked"] <= 2
    # parked-over-quota demand must NOT drive the autoscaler
    assert row["pending_demand"] == []
    ctrl = _controller()
    assert not any(t == "capped" for (t, _s) in ctrl.pending_demand)
    # a fully quota-parked tenant contends for nothing: it must not cost
    # other tenants the pipelining fast path (and a disjoint-resource
    # backlog would not contend for CPU leases either)
    with ctrl.lock:
        assert not ctrl._tenant_contending(
            ctrl.tenants["capped"], {"CPU": 1.0}
        )

    set_tenant_quota("capped", quota={"CPU": 3.0})
    # both parked tasks admit (>= 2 concurrent proves the resume, whatever
    # the first task's completion raced to)
    _wait(
        lambda: _rows()["capped"]["usage"].get("CPU", 0.0) >= 2.0,
        msg="parked work resumed after quota raise",
    )
    assert ray_tpu.get(refs, timeout=60) == ["done"] * 3


# -------------------------------------------------- priority preemption


@pytest.fixture
def preempt_cluster():
    ray_tpu.init(
        num_cpus=1,
        mode="process",
        config={"tcp_port": 0, "preemption_wait_s": 0.3},
    )
    agents: list = []

    def add(resources):
        agent = FakeAgent(_controller(), resources)
        agents.append(agent)
        _wait(
            lambda: agent.node_id in _controller().agents,
            msg="fake agent registration",
        )
        return agent

    yield add
    for a in agents:
        a.close()
    ray_tpu.shutdown()


@ray_tpu.remote(resources={"slot": 1}, max_restarts=2)
class _Pin:
    def ping(self):
        return "pong"


def test_priority_preemption_drain_migrates_low_priority_gang(preempt_cluster):
    """A starved high-priority tenant drains a low-priority restartable
    gang member via the creation-lease migration path: zero failed tasks,
    restart budget uncharged, the victim queues (never dies) and re-places
    once capacity frees."""
    ctrl = _controller()
    a1 = preempt_cluster({"CPU": 1, "slot": 1})
    a2 = preempt_cluster({"CPU": 1, "slot": 1})
    by_node = {a1.node_id: a1, a2.node_id: a2}

    # low-priority gang fills every slot
    low = [_Pin.options(tenant="batch").remote() for _ in range(2)]
    _wait(lambda: len(a1.leases) + len(a2.leases) == 2, msg="gang leases")
    for agent in (a1, a2):
        for lease in agent.leases:
            agent.place(lease)
    for actor in low:
        _wait(
            lambda a=actor: ctrl.actors[a._actor_id].state == "ALIVE",
            msg="gang ALIVE",
        )
    assert ray_tpu.get([a.ping.remote() for a in low], timeout=30) == [
        "pong",
        "pong",
    ]

    # a high-priority tenant arrives with nowhere to go
    high = _Pin.options(tenant="urgent", priority=5).remote()
    _wait(lambda: a1.killed or a2.killed, msg="preemption kill", timeout=30)
    kills = list(a1.killed) + list(a2.killed)
    assert len(kills) == 1  # smallest victim set: exactly one gang member
    victim_agent = a1 if a1.killed else a2

    def queued():  # (the agent records the kill before the controller requeues the actor)
        return [a for a in low if ctrl.actors[a._actor_id].state in ("RESTARTING", "PENDING")]

    _wait(queued, msg="the victim queued")
    victim = queued()[0]
    survivor = next(a for a in low if a is not victim)

    # the freed slot must serve the HIGH-priority creation first (priority
    # tier beats the victim's re-place in the same queue round)
    _wait(
        lambda: any(
            lease.spec.actor_id == high._actor_id
            for lease in victim_agent.leases
        ),
        msg="high-priority lease on the freed node",
    )
    high_lease = next(
        lease
        for lease in victim_agent.leases
        if lease.spec.actor_id == high._actor_id
    )
    victim_agent.place(high_lease)
    _wait(
        lambda: ctrl.actors[high._actor_id].state == "ALIVE",
        msg="high-priority actor ALIVE",
    )

    vstate = ctrl.actors[victim._actor_id]
    # controlled migration: the restart budget is NOT charged and the
    # victim is queued, not dead
    assert vstate.restarts_left == 2
    assert vstate.state == "RESTARTING"
    # zero failed tasks: a call queued on the displaced victim survives the
    # migration (held, replayed on the new incarnation) ...
    pending_ping = victim.ping.remote()
    # ... and the survivor keeps serving throughout
    assert ray_tpu.get(survivor.ping.remote(), timeout=30) == "pong"

    # read the arbitration counters while "urgent" still holds its slot
    # (an idle unconfigured tenant is reaped from the registry)
    rows = _rows()
    assert rows["urgent"]["preemptions"] == 1
    assert rows["batch"]["preempted"] == 1
    events = [e["event"] for e in ctrl.task_events]
    # one starved head == one victim, end to end: later scheduler rounds
    # must not have drained the second gang member too
    assert events.count("PREEMPTED") == 1
    assert ctrl.actor_creation_stats["preempt_migrations"] == 1

    # capacity frees -> the victim re-places through the normal lease path
    before = {
        agent: len(agent.leases) for agent in (a1, a2)
    }
    ray_tpu.kill(high)
    _wait(
        lambda: any(
            len(agent.leases) > before[agent]
            and agent.leases[-1].spec.actor_id == victim._actor_id
            for agent in (a1, a2)
        ),
        msg="victim re-lease after capacity freed",
    )
    agent = next(
        ag
        for ag in (a1, a2)
        if len(ag.leases) > before[ag]
        and ag.leases[-1].spec.actor_id == victim._actor_id
    )
    agent.place(agent.leases[-1])
    assert ray_tpu.get(pending_ping, timeout=30) == "pong"


def test_starvation_clock_survives_sibling_dispatches(preempt_cluster):
    """A starved head's preemption clock belongs to THAT head: a sibling
    shape of the same tenant dispatching successfully every round must
    not keep resetting it (priority inversion forever), and victim
    selection must skip actors whose holds contribute nothing to the
    starved demand — the CPU-only bystander survives, only the slot
    holder migrates."""
    ctrl = _controller()
    # generous CPU so the slot stays the only unmet dimension; "bslot"
    # pins the bystander onto the agent (the head also has a CPU)
    agent = preempt_cluster({"CPU": 6, "slot": 1, "bslot": 1})

    @ray_tpu.remote(num_cpus=1, resources={"bslot": 1}, max_restarts=2)
    class CpuOnly:
        def ping(self):
            return "pong"

    # low-priority: a cheap CPU-only bystander AND the slot holder
    bystander = CpuOnly.options(tenant="batch").remote()
    holder = _Pin.options(tenant="batch").remote()
    _wait(lambda: len(agent.leases) == 2, msg="low-priority leases")
    for lease in agent.leases:
        agent.place(lease)
    for a in (bystander, holder):
        _wait(
            lambda a=a: ctrl.actors[a._actor_id].state == "ALIVE",
            msg="low-priority ALIVE",
        )

    # urgent tenant: the slot head starves while its own CPU-task stream
    # keeps dispatching (leased + instantly completed by the fake agent)
    high = _Pin.options(tenant="urgent", priority=5).remote()

    @ray_tpu.remote(num_cpus=1)
    def cpu_task():
        return 1

    deadline = time.monotonic() + 8.0
    while time.monotonic() < deadline and not agent.killed:
        cpu_task.options(tenant="urgent", priority=5).remote()
        time.sleep(0.05)
    assert agent.killed, "sibling dispatches starved out the preemption"
    # smallest USEFUL victim set: the CPU-only bystander (which frees no
    # slot) is never drained — exactly one kill, the slot holder's worker
    time.sleep(0.5)
    assert len(agent.killed) == 1
    assert ctrl.actors[bystander._actor_id].state == "ALIVE"
    assert ctrl.actors[holder._actor_id].state in ("RESTARTING", "PENDING")


def test_no_preemption_within_one_priority_tier(preempt_cluster):
    """Equal-priority starvation never preempts: the newcomer queues."""
    ctrl = _controller()
    agent = preempt_cluster({"CPU": 1, "slot": 1})
    holder = _Pin.options(tenant="t1").remote()
    _wait(lambda: agent.leases, msg="lease")
    agent.place(agent.leases[0])
    _wait(
        lambda: ctrl.actors[holder._actor_id].state == "ALIVE", msg="ALIVE"
    )

    waiter = _Pin.options(tenant="t2").remote()
    time.sleep(1.2)  # >> preemption_wait_s
    assert not agent.killed
    assert ctrl.actors[holder._actor_id].state == "ALIVE"
    assert ctrl.actors[waiter._actor_id].state == "PENDING"


# ------------------------------------------------- demand attribution


def test_pending_demand_attributes_tenant(thread_cluster):
    """Unplaceable demand reaches the autoscaler tagged with the tenant
    driving it (per-tenant scale-up attribution + dashboard view)."""
    thread_cluster(num_cpus=1)

    @ray_tpu.remote(resources={"TPU": 4.0})
    def big():
        return 1

    big.options(tenant="tpu-team").remote()

    def demanded():
        state = _controller()._dispatch_request("autoscaler_state", None)
        return [
            d
            for d in state["pending_demand"]
            if d["tenant"] == "tpu-team"
            and d["resources"].get("TPU") == 4.0
        ]

    _wait(lambda: demanded(), msg="tenant-attributed demand")
    row = _rows()["tpu-team"]
    assert any(d.get("TPU") == 4.0 for d in row["pending_demand"])


# ---------------------------------------------------- snapshot round trip


def test_head_restart_roundtrips_tenant_state(tmp_path):
    """Configured tenant policy (weights/quota/priority) survives a head
    restart through the state snapshot."""
    snap = str(tmp_path / "gcs-tenants.pkl")
    ray_tpu.init(
        num_cpus=2, mode="thread", config={"gcs_snapshot_path": snap}
    )
    try:
        set_tenant_quota(
            "gold", quota={"CPU": 2.0}, weight=2.5, priority=3
        )
        set_tenant_quota("bronze", weight=0.5)
    finally:
        ray_tpu.shutdown()  # final synchronous snapshot flush

    ray_tpu.init(
        num_cpus=2, mode="thread", config={"gcs_snapshot_path": snap}
    )
    try:
        rows = _rows()
        gold = rows["gold"]
        assert gold["weight"] == 2.5
        assert gold["priority"] == 3
        assert gold["quota"] == {"CPU": 2.0}
        assert gold["configured"]
        assert rows["bronze"]["weight"] == 0.5
    finally:
        ray_tpu.shutdown()


# ------------------------------------------------------------ fault chaos


def test_tenant_ops_chaos_injectable():
    """The new ops ride the same RAY_testing_rpc_failure table as every
    other controller op (catalog-validated, so a typo'd key would have
    raised at init)."""
    ray_tpu.init(
        num_cpus=1,
        mode="thread",
        config={"testing_rpc_failure": "tenant_stats=1.0"},
    )
    try:
        with pytest.raises(Exception, match="injected rpc failure"):
            tenant_stats()
        # the sibling op is NOT injected and still works
        assert set_tenant_quota("ok-tenant", weight=2.0)["weight"] == 2.0
    finally:
        ray_tpu.shutdown()
