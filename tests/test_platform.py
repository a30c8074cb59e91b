"""Platform tests: state API, timeline, metrics, jobs, autoscaler, CLI.

Coverage modeled on the reference's ``python/ray/tests/test_state_api.py``,
``dashboard/modules/job/tests``, ``autoscaler/v2/tests``, and
``test_metrics_agent.py`` surfaces.
"""

import json
import sys
import time

import pytest

import ray_tpu

pytestmark = pytest.mark.timeout(300) if hasattr(pytest.mark, "timeout") else []


def test_state_api_lists(ray_start_thread):
    from ray_tpu.util import state

    @ray_tpu.remote
    class A:
        def ping(self):
            return 1

    @ray_tpu.remote
    def f():
        return 2

    a = A.options(name="state-test-actor").remote()
    ray_tpu.get(a.ping.remote())
    ray_tpu.get([f.remote() for _ in range(3)])

    actors = state.list_actors()
    assert any(x["name"] == "state-test-actor" and x["state"] == "ALIVE" for x in actors)
    nodes = state.list_nodes()
    assert len(nodes) >= 1
    workers = state.list_workers()
    assert len(workers) >= 1
    objs = state.list_objects()
    assert objs["num_objects_in_memory_store"] >= 1
    summary = state.summarize_tasks()
    assert summary.get("f", {}).get("FINISHED", 0) >= 3


def test_timeline_export(ray_start_thread, tmp_path):
    from ray_tpu.util.state.api import timeline

    @ray_tpu.remote
    def work():
        time.sleep(0.01)
        return 1

    ray_tpu.get([work.remote() for _ in range(5)])
    path = str(tmp_path / "trace.json")
    trace = timeline(path)
    assert len([e for e in trace if e["name"] == "work"]) == 5
    loaded = json.load(open(path))
    assert all(e["ph"] == "X" and e["dur"] > 0 for e in loaded)


def test_tracing_spans(ray_start_thread, tmp_path):
    from ray_tpu.util import tracing

    tracing.clear()
    with tracing.span("outer", run="x"):
        with tracing.span("inner"):
            pass
    spans = tracing.get_spans()
    assert [s["name"] for s in spans] == ["inner", "outer"]
    assert spans[0]["parent_id"] == spans[1]["span_id"]
    trace = tracing.export_chrome_trace(str(tmp_path / "t.json"))
    assert any(e["name"] == "outer" for e in trace)


def test_metrics_counter_gauge_histogram():
    from ray_tpu.util import metrics

    metrics._clear_registry()
    c = metrics.Counter("requests_total", "reqs", tag_keys=("route",))
    c.inc(tags={"route": "/a"})
    c.inc(2, tags={"route": "/a"})
    c.inc(tags={"route": "/b"})
    g = metrics.Gauge("queue_depth", "depth")
    g.set(7)
    h = metrics.Histogram("latency_ms", "lat", boundaries=[1, 10, 100])
    for v in (0.5, 5, 50, 500):
        h.observe(v)
    text = metrics.export_prometheus()
    assert 'requests_total{route="/a"} 3.0' in text
    assert "queue_depth 7.0" in text
    assert 'latency_ms_bucket{le="+Inf"} 4' in text
    assert "latency_ms_sum 555.5" in text
    with pytest.raises(ValueError):
        c.inc(-1)


def test_job_submission_lifecycle(tmp_path):
    from ray_tpu.job_submission import JobStatus, JobSubmissionClient

    client = JobSubmissionClient()
    job_id = client.submit_job(
        entrypoint=f"{sys.executable} -c \"print('job says hi')\"",
    )
    status = client._manager.wait_until_finished(job_id, timeout=60)
    assert status is JobStatus.SUCCEEDED
    assert "job says hi" in client.get_job_logs(job_id)
    assert any(j["job_id"] == job_id for j in client.list_jobs())

    bad = client.submit_job(entrypoint=f"{sys.executable} -c \"raise SystemExit(3)\"")
    assert client._manager.wait_until_finished(bad, timeout=60) is JobStatus.FAILED
    assert client.get_job_info(bad)["return_code"] == 3


def test_job_stop(tmp_path):
    from ray_tpu.job_submission import JobStatus, JobSubmissionClient

    client = JobSubmissionClient()
    job_id = client.submit_job(
        entrypoint=f"{sys.executable} -c \"import time; time.sleep(60)\""
    )
    time.sleep(0.5)
    assert client.get_job_status(job_id) is JobStatus.RUNNING
    assert client.stop_job(job_id)
    assert client._manager.wait_until_finished(job_id, timeout=30) is JobStatus.STOPPED


def test_autoscaler_scales_up_and_down():
    from ray_tpu.autoscaler import Autoscaler, AutoscalerConfig, NodeGroup

    # own cluster: the head must have NO TPUs (autodetection would otherwise
    # satisfy the demand locally on a TPU machine)
    ray_tpu.init(num_cpus=8, num_tpus=0, mode="thread")

    cfg = AutoscalerConfig(
        node_groups=[
            NodeGroup(
                name="tpu-v5e-16",
                resources_per_node={"CPU": 8, "TPU": 4},
                nodes_per_group=4,  # 4 hosts per slice, atomic
                max_groups=2,
            )
        ],
        idle_timeout_s=0.5,
    )
    scaler = Autoscaler(cfg)

    # unfulfillable demand: a TPU task with no TPU nodes
    @ray_tpu.remote(num_tpus=4)
    def tpu_task():
        return 1

    ref = tpu_task.remote()
    time.sleep(0.3)  # let the scheduler record the unfulfilled demand
    actions = scaler.update()
    assert actions["scaled_up"] == ["tpu-v5e-16"]
    # the WHOLE slice came up (4 hosts), never a partial slice
    assert len(scaler.launched["tpu-v5e-16"][0]) == 4
    assert ray_tpu.cluster_resources().get("TPU", 0) == 16
    assert ray_tpu.get(ref, timeout=60) == 1

    # idle long enough -> the slice is removed atomically
    deadline = time.time() + 30
    while time.time() < deadline:
        actions = scaler.update()
        if actions["scaled_down"]:
            break
        time.sleep(0.2)
    assert actions["scaled_down"] == ["tpu-v5e-16"]
    assert ray_tpu.cluster_resources().get("TPU", 0) == 0
    ray_tpu.shutdown()


def test_autoscaler_reap_requires_sustained_death():
    """A previously-registered launch is only terminated after the all-dead
    observation persists for dead_reap_s; one blip tick (controller restart,
    heartbeat hiccup) must not kill healthy slices. A launch that never
    registered is reaped as soon as the boot grace lapses."""
    from ray_tpu.autoscaler import Autoscaler, AutoscalerConfig, NodeGroup
    from ray_tpu.autoscaler.autoscaler import NodeProvider

    class RecordingProvider(NodeProvider):
        def __init__(self):
            self.terminated = []

        def create_node_group(self, group):
            return ["n1"]

        def terminate_nodes(self, node_ids):
            self.terminated.append(list(node_ids))

        def non_terminated_nodes(self):
            return []

    cfg = AutoscalerConfig(
        node_groups=[NodeGroup(name="g", resources_per_node={"CPU": 1})],
        launch_grace_s=0.05,
        dead_reap_s=0.4,
    )
    provider = RecordingProvider()
    scaler = Autoscaler(cfg, provider=provider)
    scaler.launched["g"].append(["n1"])
    scaler._launch_t["n1"] = time.time()

    alive = {"nodes": [{"node_id": "n1", "alive": True, "labels": {}}]}
    dead = {"nodes": [{"node_id": "n1", "alive": False, "labels": {}}]}
    gone = {"nodes": []}
    actions = {"scaled_up": [], "scaled_down": []}

    scaler._reap_failed_launches(alive, actions)  # registers the launch
    time.sleep(0.1)  # past boot grace
    scaler._reap_failed_launches(dead, actions)  # blip tick 1: dwell starts
    scaler._reap_failed_launches(gone, actions)  # blip tick 2 (empty table)
    assert provider.terminated == []
    scaler._reap_failed_launches(alive, actions)  # recovered: dwell resets
    scaler._reap_failed_launches(dead, actions)
    time.sleep(0.45)
    assert provider.terminated == []  # dwell restarted after recovery
    scaler._reap_failed_launches(dead, actions)  # sustained past dead_reap_s
    assert provider.terminated == [["n1"]]
    assert scaler.launched["g"] == []

    # never-registered launch: immediate reap once grace lapses
    provider.terminated.clear()
    scaler.launched["g"].append(["n2"])
    scaler._launch_t["n2"] = time.time() - 1.0
    scaler._reap_failed_launches(gone, actions)
    assert provider.terminated == [["n2"]]


def test_runtime_env_working_dir(tmp_path):
    """Tasks with runtime_env working_dir run with cwd + import path there."""
    mod = tmp_path / "my_wd_module.py"
    mod.write_text("VALUE = 'from-working-dir'\n")

    ray_tpu.init(num_cpus=2, mode="process")
    try:

        @ray_tpu.remote(runtime_env={"working_dir": str(tmp_path)})
        def probe():
            import os

            import my_wd_module

            return my_wd_module.VALUE, os.getcwd()

        value, cwd = ray_tpu.get(probe.remote(), timeout=120)
        assert value == "from-working-dir"
        assert cwd == str(tmp_path)
    finally:
        ray_tpu.shutdown()


def test_job_visibility_across_processes(tmp_path):
    """CLI use case: submit in one process, query from another."""
    import subprocess

    from ray_tpu.job_submission import JobManager, JobStatus

    log_dir = str(tmp_path / "jobs")
    m1 = JobManager(log_dir=log_dir)
    jid = m1.submit_job(entrypoint=[sys.executable, "-c", "print('xp ok')"])
    assert m1.wait_until_finished(jid, timeout=60) is JobStatus.SUCCEEDED

    code = (
        "from ray_tpu.job_submission import JobManager\n"
        f"m = JobManager(log_dir={log_dir!r})\n"
        f"print(m.get_job_status({jid!r}).value)\n"
        f"assert 'xp ok' in m.get_job_logs({jid!r})\n"
    )
    r = subprocess.run(
        [sys.executable, "-c", code], capture_output=True, text=True, timeout=60
    )
    assert r.returncode == 0, r.stderr
    assert "SUCCEEDED" in r.stdout


def test_cli_status_and_job(tmp_path):
    import subprocess

    script = tmp_path / "job.py"
    script.write_text("print('cli job output')\n")
    r = subprocess.run(
        [sys.executable, "-m", "ray_tpu.scripts.cli", "job", "submit",
         "--timeout", "120", sys.executable, str(script)],
        capture_output=True, text=True, timeout=180,
    )
    assert r.returncode == 0, r.stderr
    assert "cli job output" in r.stdout
    assert "status: SUCCEEDED" in r.stdout


def test_dashboard_web_ui(ray_start_process):
    """Dashboard HTTP server: UI page, JSON state endpoints, prometheus
    metrics, and the on-demand worker stack dump (py-spy analog)."""
    import json as _json
    import time
    import urllib.request

    import ray_tpu
    from ray_tpu.dashboard import start_dashboard, stop_dashboard

    @ray_tpu.remote
    class Sleeper:
        def nap(self, s):
            import time as _t

            _t.sleep(s)
            return "awake"

    sleeper = Sleeper.remote()
    # ensure the actor's worker is fully up before profiling it
    assert ray_tpu.get(sleeper.nap.remote(0.01), timeout=60) == "awake"
    ref = sleeper.nap.remote(8.0)  # a live in-flight task to profile
    time.sleep(0.5)

    port = start_dashboard(port=0)
    base = f"http://127.0.0.1:{port}"
    try:
        with urllib.request.urlopen(base + "/", timeout=10) as r:
            page = r.read().decode()
        assert "ray_tpu dashboard" in page

        with urllib.request.urlopen(base + "/api/overview", timeout=10) as r:
            ov = _json.loads(r.read())
        assert "CPU" in ov["resources"]
        assert ov["store"]["num_objects"] >= 0

        with urllib.request.urlopen(base + "/api/nodes", timeout=10) as r:
            nodes = _json.loads(r.read())
        assert len(nodes) >= 1

        with urllib.request.urlopen(base + "/api/actors", timeout=10) as r:
            actors = _json.loads(r.read())
        assert any("Sleeper" in str(a) for a in actors)

        # on-demand profiling: the sleeping task's frame shows up
        with urllib.request.urlopen(base + "/api/stacks", timeout=30) as r:
            stacks = _json.loads(r.read())
        assert stacks, "no workers responded"
        joined = "\n".join(stacks.values())
        assert "nap" in joined or "sleep" in joined, joined[:2000]

        with urllib.request.urlopen(base + "/metrics", timeout=10) as r:
            assert r.status == 200
    finally:
        stop_dashboard()
    assert ray_tpu.get(ref, timeout=60) == "awake"


def test_pubsub_actor_and_node_events(ray_start_thread):
    """GCS-pubsub analog: subscribers observe actor lifecycle and node
    membership events; custom channels work for user events."""
    import threading
    import time

    import ray_tpu
    from ray_tpu.util.pubsub import Subscriber, publish

    sub_actors = Subscriber("actors")
    sub_nodes = Subscriber("nodes")

    @ray_tpu.remote
    class A:
        def ping(self):
            return 1

    a = A.remote()
    assert ray_tpu.get(a.ping.remote(), timeout=60) == 1
    events = sub_actors.poll(timeout=10)
    assert any(e["state"] == "ALIVE" for e in events), events

    ray_tpu.kill(a)
    deadline = time.time() + 15
    dead = []
    while time.time() < deadline and not dead:
        dead = [e for e in sub_actors.poll(timeout=2) if e["state"] == "DEAD"]
    assert dead, "no DEAD event observed"

    import ray_tpu._private.worker as w

    node_id = w.global_worker().controller.add_node({"CPU": 2})
    ev = sub_nodes.poll(timeout=10)
    assert any(e["event"] == "added" for e in ev), ev
    w.global_worker().controller.remove_node(node_id)
    ev = sub_nodes.poll(timeout=10)
    assert any(e["event"] == "removed" for e in ev), ev

    # custom channel + long-poll blocking (publisher fires mid-poll)
    sub_custom = Subscriber("my-channel")
    t = threading.Thread(
        target=lambda: (time.sleep(0.4), publish("my-channel", {"k": 42}))
    )
    t0 = time.monotonic()
    t.start()
    got = sub_custom.poll(timeout=10)
    assert [e["k"] for e in got] == [42]
    assert 0.3 < time.monotonic() - t0 < 5.0  # actually blocked, then woke
    t.join()


# ------------------------------------------------------- one process per chip


def test_chip_grant_check_refuses_wrong_platform_or_count():
    """A worker process spawned for a TPU grant checks what JAX sees before
    it takes work. Here JAX is on the CPU, so any grant is refused."""
    from ray_tpu.tpu.accelerator import verify_chip_grant

    with pytest.raises(RuntimeError, match="granted 1 TPU chip"):
        verify_chip_grant(1)


def test_chip_grant_check_passes_on_matching_devices(monkeypatch):
    import jax

    from ray_tpu.tpu.accelerator import verify_chip_grant

    class Dev:
        platform = "tpu"

    monkeypatch.setattr(jax, "local_devices", lambda: [Dev(), Dev()])
    verify_chip_grant(2)
    with pytest.raises(RuntimeError, match="granted 4 TPU chip"):
        verify_chip_grant(4)

    def no_backend():
        raise RuntimeError("Unable to initialize backend 'tpu'")

    monkeypatch.setattr(jax, "local_devices", no_backend)
    with pytest.raises(RuntimeError, match="no backend"):
        verify_chip_grant(2)


def test_poisoned_worker_fails_new_work_with_the_reason():
    """worker_main hands a failed grant check to the runtime, which raises
    it for every task and actor creation (never for calls on an actor that
    already lives)."""
    from ray_tpu._private.ids import WorkerID
    from ray_tpu._private.task_spec import TaskType
    from ray_tpu._private.worker_runtime import WorkerRuntime

    class Spec:
        task_type = TaskType.NORMAL_TASK
        name = "f"

    rt = WorkerRuntime(WorkerID.from_random(), conn=None, in_process=True)
    rt.startup_error = RuntimeError("worker was granted 1 TPU chip(s) but ...")
    with pytest.raises(RuntimeError, match="granted 1 TPU chip"):
        rt._invoke(Spec(), (), {})


def test_chip_worker_env_overrides_inherited_platform():
    """A driver that keeps itself off the chip with JAX_PLATFORMS=cpu must
    not hand that to a worker granted TPU."""
    from ray_tpu.tpu.accelerator import chip_worker_env

    whole_host = chip_worker_env([0, 1, 2, 3], 4)
    assert whole_host == {"JAX_PLATFORMS": "tpu"}
    one = chip_worker_env([1], 4)
    assert one["JAX_PLATFORMS"] == "tpu"
    assert one["TPU_VISIBLE_CHIPS"] == "1"
    assert one["TPU_CHIPS_PER_HOST_BOUNDS"] == "1,1,1"
    # what the task set itself is left to it (the grant check judges it)
    assert "JAX_PLATFORMS" not in chip_worker_env([1], 4, {"JAX_PLATFORMS": "cpu"})


def test_chip_pool_waits_for_the_previous_holder_to_exit():
    from ray_tpu.tpu.accelerator import ChipPool

    import subprocess

    class Proc:
        def __init__(self):
            self.rc = None

        def poll(self):
            return self.rc

        def wait(self, timeout=None):
            if self.rc is None:
                raise subprocess.TimeoutExpired("worker", timeout)
            return self.rc

        def kill(self):
            self.rc = -9

    pool = ChipPool(4)
    with pytest.raises(RuntimeError, match="this host has 4"):
        pool.acquire(8, 0.1)
    first = pool.acquire(1, 0.1)
    second = pool.acquire(1, 0.1)
    assert first == [0] and second == [1]  # two live one-chip workers: disjoint
    a, b = Proc(), Proc()
    pool.bind(first, a)
    pool.bind(second, b)
    pair = pool.acquire(2, 0.1)
    assert pair == [2, 3]  # an aligned group
    pool.bind(pair, None)  # that spawn failed: the chips come back
    # all four need both holders gone; evict() is how idle ones are retired
    with pytest.raises(RuntimeError, match="have not exited"):
        pool.acquire(4, 0.2)

    def evict():
        a.rc = b.rc = 0

    whole = pool.acquire(4, 1.0, evict=evict)
    assert whole == [0, 1, 2, 3]
    # shutdown: a holder that does not exit in time is killed and waited
    # for, so the chips are free for whatever the host runs next
    last = Proc()
    pool.bind(whole, last)
    pool.drain(grace_s=0.1)
    assert last.rc == -9


def test_chip_count_comes_from_device_nodes(monkeypatch):
    """init() counts chips without a JAX backend: device nodes first (a
    one-chip machine cut from a four-chip host keeps the host's GKE
    variables), the variables only where no node is exposed."""
    import glob as glob_mod

    from ray_tpu.tpu import accelerator
    from ray_tpu.tpu.accelerator import TPUAcceleratorManager as M

    monkeypatch.setenv("TPU_CHIPS_PER_HOST_BOUNDS", "2,2,1")
    nodes = {"/dev/accel[0-9]*": [], "/dev/vfio/*": ["/dev/vfio/2", "/dev/vfio/vfio"]}
    monkeypatch.setattr(accelerator.glob, "glob", lambda pat: nodes[pat])
    assert M.get_current_node_num_accelerators() == 1
    nodes["/dev/vfio/*"] = []
    assert M.get_current_node_num_accelerators() == 4
    monkeypatch.delenv("TPU_CHIPS_PER_HOST_BOUNDS")
    monkeypatch.delenv("TPU_ACCELERATOR_TYPE", raising=False)
    assert M.get_current_node_num_accelerators() == 0
    assert glob_mod.glob is not None  # the real module is untouched


def test_env_fingerprint_separates_chip_counts():
    """A worker that sees one chip is not reused for a four-chip grant."""
    from ray_tpu._private.controller import Controller

    class Spec:
        runtime_env = None

        def __init__(self, resources):
            self.resources = resources

    fp = Controller._env_fingerprint
    assert fp(Spec({"CPU": 1.0}))[0] == 0
    assert fp(Spec({"TPU": 1.0}))[0] == 1
    assert fp(Spec({"TPU": 4.0})) != fp(Spec({"TPU": 1.0}))


def test_compile_cache_dir_choice(monkeypatch, tmp_path):
    """JAX_COMPILATION_CACHE_DIR set: JAX's own handling, no directory is
    set in code. Not set: the fixed <checkout>/.jax_cache, never a temporary
    name. Either way names and source lines go into the cache key, so that a
    profile shows this checkout's ``jax.named_scope`` names (PR 26)."""
    import os

    import jax

    from ray_tpu._private import jax_cache

    was = jax.config.jax_compilation_cache_dir
    updates = []
    monkeypatch.setattr(
        jax.config, "update", lambda k, v: updates.append((k, v))
    )
    monkeypatch.setenv(jax_cache.ENV_VAR, str(tmp_path))
    names_in_key = ("jax_compilation_cache_include_metadata_in_key", True)
    every_compile = ("jax_persistent_cache_min_compile_time_secs", 0)
    assert jax_cache.configure() == str(tmp_path)
    assert updates == [names_in_key, every_compile]
    del updates[:]
    monkeypatch.delenv(jax_cache.ENV_VAR)
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    fixed = os.path.join(repo, ".jax_cache")
    assert jax_cache.configure() == fixed == jax_cache.cache_dir()
    assert updates == [("jax_compilation_cache_dir", fixed), names_in_key, every_compile]
    assert jax.config.jax_compilation_cache_dir == was


_EVERY_COMPILE = """
import jax, jax.numpy as jnp
from ray_tpu._private import jax_cache
jax_cache.configure()
floor = jax.config.jax_persistent_cache_min_compile_time_secs
x = jnp.ones((8, 8))
empty = jax_cache.entry_count()
jax.jit(lambda x: x @ x + 1)(x).block_until_ready()
outside = jax_cache.entry_count() - empty
with jax_cache.bypassed():
    jax.jit(lambda x: x @ x + 2)(x).block_until_ready()
print("RESULT", floor, outside, jax_cache.entry_count() - empty - outside)
"""


def test_every_compile_is_kept_but_one_inside_the_bypass(tmp_path):
    """``configure()`` leaves JAX's floor on what it persists at 0 seconds: a
    program that compiles in milliseconds is written (a serving start runs
    some 75 of them, and compiled them again at every start), and one
    compiled inside ``bypassed()`` is still not."""
    import os
    import subprocess

    from ray_tpu._private import jax_cache

    out = subprocess.run(
        [sys.executable, "-c", _EVERY_COMPILE], capture_output=True, text=True, timeout=120,
        check=True, env={**os.environ, "JAX_PLATFORMS": "cpu", jax_cache.ENV_VAR: str(tmp_path)},
    )
    floor, outside, inside = [ln for ln in out.stdout.splitlines()
                              if ln.startswith("RESULT")][-1].split()[1:]
    assert float(floor) == 0
    assert int(outside) >= 1
    assert int(inside) == 0


_CACHED_NAMES = """
import os, re, sys
import jax, jax.numpy as jnp
jax.config.update("jax_platforms", "cpu")
jax.config.update("jax_compilation_cache_dir", sys.argv[1])
jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
if sys.argv[3] == "1":
    jax.config.update("jax_compilation_cache_include_metadata_in_key", True)
def f(x):
    with jax.named_scope(sys.argv[2]):
        return jnp.sin(x) @ x
text = jax.jit(f).lower(jnp.ones((64, 64))).compile().as_text()
print("NAMED" if re.search(r'op_name="[^"]*/ffn/', text) else "UNNAMED")
"""


@pytest.mark.parametrize("names_in_key", ["0", "1"])
def test_a_cached_program_keeps_the_names_it_was_compiled_with(tmp_path, names_in_key):
    """Why ``jax_cache.configure`` puts names into the cache key: the same
    program under another ``jax.named_scope`` is found again in a cache keyed
    without them, and comes back with the names of its first compilation."""
    import os
    import subprocess

    def run(scope):
        out = subprocess.run(
            [sys.executable, "-c", _CACHED_NAMES, str(tmp_path), scope, names_in_key],
            capture_output=True, text=True, timeout=120, check=True,
            env={**os.environ, "JAX_PLATFORMS": "cpu"},
        )
        return out.stdout.split()[-1]

    assert run("before") == "UNNAMED"
    assert run("ffn") == ("NAMED" if names_in_key == "1" else "UNNAMED")


_RELAID = r"""
import sys
import jax
jax.config.update("jax_compilation_cache_dir", sys.argv[1])
jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
import jax.numpy as jnp
from jax.experimental.layout import Format, Layout
from ray_tpu.llm import EngineConfig, JaxEngine, LLMConfig, ModelConfig, SamplingParams

x = jnp.ones((2, 16, 4, 8))
plain = jax.device_put(x, Format(Layout(major_to_minor=(0, 2, 1, 3)), x.sharding))
eng = JaxEngine(LLMConfig(
    model=ModelConfig(model_id="tiny", tokenizer="byte", seed=0),
    engine=EngineConfig(max_num_seqs=2, max_seq_len=64, prefill_buckets=(16, 32, 64))))
out = eng.generate("hello", sampling_params=SamplingParams(
    max_tokens=4, temperature=0.0, ignore_eos=True)).token_ids
eng.shutdown()
print("RESULT", tuple(plain.format.layout.major_to_minor) == (0, 2, 1, 3),
      eng.get_stats()["params_relaid"]["leaves"], "-".join(map(str, out)))
"""


def test_the_engine_holds_its_layout_in_a_process_that_reads_the_compile_cache(tmp_path):
    """A second process, which finds every program in the persistent cache,
    still holds 3 leaves head-major and gives the first one's tokens: the
    engine's programs, compiled for the head-major leaves they are handed,
    come back whole, and the relayout itself is compiled around the cache
    (``jax_cache.bypassed``). Why: in jax 0.9 a program whose result has
    another device layout than the default comes back from the cache handing
    out the default one (the second process's plain ``device_put`` below; on
    a v5e the first benchmark run of a checkout held its weights head-major
    and the later ones did not). That observation is JAX's to change, so it
    warns and does not fail."""
    import os
    import subprocess
    import warnings

    def run():
        out = subprocess.run(
            [sys.executable, "-c", _RELAID, str(tmp_path)],
            capture_output=True, text=True, timeout=300, check=True,
            env={**os.environ, "JAX_PLATFORMS": "cpu"},
        )
        return [ln for ln in out.stdout.splitlines() if ln.startswith("RESULT")][-1].split()[1:]

    first, second = run(), run()
    assert first[:2] == ["True", "3"]
    assert second[1] == "3"
    assert second[2] == first[2]
    if second[0] == "True":
        warnings.warn("a cached relayout keeps its layout in this JAX: "
                      "jax_cache.bypassed can go")


def test_the_cache_bypass_nests_and_the_last_one_out_restores_the_flag():
    """Two engines may assign parameters on two threads: the cache stays off
    until the last relayout is done, and comes back as the first found it."""
    import threading

    import jax

    from ray_tpu._private import jax_cache

    def enabled():
        return jax.config.jax_enable_compilation_cache

    was = enabled()
    inside, leave = threading.Event(), threading.Event()
    seen = []

    def other():
        with jax_cache.bypassed():
            inside.set()
            leave.wait(30)
        seen.append(enabled())

    try:
        jax.config.update("jax_enable_compilation_cache", True)
        t = threading.Thread(target=other)
        with jax_cache.bypassed():
            assert not enabled()
            t.start()
            assert inside.wait(30)
        assert not enabled()  # the other thread's relayout is still compiling
        leave.set()
        t.join(30)
        assert seen == [True] and enabled()
    finally:
        leave.set()
        jax.config.update("jax_enable_compilation_cache", was)
