"""A warm start restores its executables (PR 46).

An engine of one device, in a process whose compile cache is configured,
keeps each form of the programs its loop launches in
``_private/program_store.py``: the first start of a cache directory lowers and
compiles them and writes the executables, a later process finds each by a key
that nothing is traced for and loads it.

- a second process restores every form, lowers no engine program, and
  generates the first start's tokens to the token, greedy and seeded;
- the key moves with a package file's bytes, the model configuration, a bucket
  list, an argument's dtype and a library's version, and an engine whose key
  moved compiles and never loads;
- a file cut short or unreadable falls back, is written anew and is counted,
  and an executable that refuses its arguments gives way to its ``jit``;
- donation survives the round trip;
- with no cache directory configured the engine launches executables all the
  same, the store finds none and keeps none, a burst compiles nothing more,
  and an executable that refuses its arguments gives way to its ``jit`` once
  and the request is served.

The starts run once, in fresh subprocesses on a temporary cache directory
(``starts``); each test reads what they printed.
"""

import json
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import pytest

from ray_tpu._private import jax_cache, program_store

_START = r"""
import glob, json, os, sys
import jax, jax.monitoring
from ray_tpu._private import jax_cache, program_store
from ray_tpu.llm import EngineConfig, JaxEngine, LLMConfig, ModelConfig, SamplingParams

mode = sys.argv[1]
if mode != "unconfigured":
    jax_cache.configure()
lowered = []
jax.monitoring.register_event_duration_secs_listener(
    lambda event, seconds, **kw: lowered.append(str(kw.get("fun_name")))
    if event.endswith("jaxpr_to_mlir_module_duration") else None)
answered = []  # what the store said to each load and save
for name in ("load", "save"):
    def spy(*args, _real=getattr(program_store, name), _name=name):
        answered.append((_name, _real(*args)))
        return answered[-1][1]
    setattr(program_store, name, spy)


def config(buckets=(16, 64), dtype="bfloat16", **model_kwargs):
    return LLMConfig(
        model=ModelConfig(model_id="tiny", tokenizer="byte", seed=0, model_kwargs=model_kwargs),
        engine=EngineConfig(max_num_seqs=2, max_seq_len=64, prefill_buckets=buckets,
                            prefill_chunk=16, max_concurrent_admissions=2, dtype=dtype))


def tokens(eng, **sampling):
    return eng.generate("a prompt long enough for two middle chunks", sampling_params=SamplingParams(
        max_tokens=6, ignore_eos=True, **sampling)).token_ids


eng = JaxEngine(config())
said = {
    "init": eng.get_stats()["init"],
    "greedy": tokens(eng, temperature=0.0),
    "seeded": tokens(eng, temperature=0.9, top_k=20, seed=11),
    "relaid": eng.get_stats()["params_relaid"]["leaves"],
}
# a burst of unlike lengths, greedy and sampled, at once: nothing is left to compile
burst = [eng.submit(prompt_token_ids=list(range(1, n)), sampling_params=SamplingParams(
    max_tokens=3, ignore_eos=True, temperature=t)) for n, t in ((4, 0.0), (20, 0.8), (45, 0.0))]
for req in burst:
    eng._await_done(req)
said["burst"] = {"errors": [str(req.error) for req in burst if req.error],
                 "programs": dict(eng._program_counts)}
said["forms"] = sorted(":".join(map(str, form)) for form in eng._programs)
said["executables"] = sum(isinstance(p, jax.stages.Compiled) for p in eng._programs.values())
if mode == "unconfigured":
    # an executable that refuses its arguments (here: another form's) while
    # the loop serves: the jit takes its place once and the request is served
    eng._programs[("decode", 64)] = eng._programs[("new_stripe", 64)]
    said["refused_in_the_loop"] = {
        "tokens": [tokens(eng, temperature=0.0) for _ in range(2)],
        "fallbacks": eng._program_counts["fallback"] - said["burst"]["programs"]["fallback"],
        "compiled": eng._program_counts["compiled"] - said["burst"]["programs"]["compiled"],
        "runs_the_jit": not isinstance(eng._programs[("decode", 64)], jax.stages.Compiled),
    }
eng.shutdown()
# donation: a decode step leaves the pool's cache updated in place
pool = eng._pools[0]
before = pool.cache["k"]
_, pool.cache, pool.keys, _ = eng._decode(pool, pool.dev_tokens, *pool.sampler(), pool.keys)
jax.block_until_ready(pool.cache)
said["donated"] = bool(before.is_deleted()) and not pool.cache["k"].is_deleted()
said["lowered"] = [name for name in lowered if any(
    program in name for program in ("decode_fn", "chunk_mid", "chunk_final", "new_stripe",
                                    "seed_prefix", "store_snapshot"))]
said["answered"] = {name: sorted({repr(got) for asked, got in answered if asked == name})
                    for name in ("load", "save")}
folder = program_store.directory()
said["files"] = {os.path.basename(p): os.path.getsize(p) for p in glob.glob(f"{folder}/*.bin")} \
    if folder else None

if mode == "moved":
    # an executable that refuses its arguments (here: another form's) gives
    # way to the jit, before anything ran or was donated
    eng._programs[("decode", 64)] = eng._programs[("new_stripe", 64)]
    held = pool.cache["k"]
    out, pool.cache, pool.keys, _ = eng._decode(pool, pool.dev_tokens, *pool.sampler(), pool.keys)
    said["refused"] = {
        "fallbacks": eng._program_counts["fallback"] - said["init"]["programs"]["fallback"],
        "runs_the_jit": not isinstance(eng._programs[("decode", 64)], jax.stages.Compiled),
        "stepped": list(out.shape) == [1, 2] and bool(held.is_deleted()),
    }
    # engines whose key moved: each is stopped where it keeps its first
    # program, which is after it looked for one and compiled one
    class Kept(BaseException):
        pass

    def stop(*args):
        raise Kept

    said["moved"] = {}
    real_digest = program_store.package_digest
    for what, kwargs in (("bucket list", dict(buckets=(16, 32, 64))),
                         ("model configuration", dict(n_layers=3)),
                         ("dtype", dict(dtype="float32")),
                         ("package bytes", {})):
        del lowered[:]
        program_store.save = stop
        if what == "package bytes":
            program_store.package_digest = lambda: "another source"
        program_store.environment.cache_clear()
        found = []
        program_store.load = lambda *args, _real=program_store.load: (
            found.append(_real(*args)), found[-1])[1]
        try:
            JaxEngine(config(**kwargs))
        except Kept:
            pass
        program_store.package_digest = real_digest
        said["moved"][what] = {"looked": len(found), "found": sum(p is not None for p in found),
                               "lowered": len(lowered)}
print("SAID", json.dumps(said))
"""


def _start(cache_dir, mode="start"):
    env = {k: v for k, v in os.environ.items() if k != jax_cache.ENV_VAR}
    env.update(JAX_PLATFORMS="cpu", PYTHONPATH=os.pathsep.join(sys.path))
    if cache_dir is not None:
        env[jax_cache.ENV_VAR] = str(cache_dir)
    out = subprocess.run([sys.executable, "-c", _START, mode], capture_output=True, text=True,
                         timeout=300, env=env, cwd=str(cache_dir or "/"))
    assert out.returncode == 0, out.stderr[-3000:]
    return json.loads([ln for ln in out.stdout.splitlines() if ln.startswith("SAID ")][-1][5:])


@pytest.fixture(scope="module")
def starts(tmp_path_factory):
    """first: an empty directory; second: behind it; damaged: behind a file
    cut short and a file of noise, with the engines whose key moved; again:
    behind that; unconfigured: no ``configure()``, no directory."""
    cache_dir = tmp_path_factory.mktemp("jax_cache")
    said = {"first": _start(cache_dir), "second": _start(cache_dir)}
    files = sorted((cache_dir / "programs").glob("*.bin"))
    cut, noise = files[0], files[-1]
    cut.write_bytes(cut.read_bytes()[: cut.stat().st_size // 2])
    noise.write_bytes(b"not a pickle")
    said["damaged_files"], said["folder"] = [cut.name, noise.name], str(cache_dir / "programs")
    said["damaged"] = _start(cache_dir, "moved")
    said["unconfigured"] = _start(None, "unconfigured")
    return said


FORMS = 6  # new_stripe, chunk_mid at 1 and 2 rows, chunk_final, seed_prefix, decode


@pytest.mark.parametrize("start,restored,compiled,fallback", [
    ("first", 0, FORMS, 0), ("second", FORMS, 0, 0), ("damaged", FORMS - 2, 0, 2),
])
def test_a_start_counts_what_it_restored_and_what_it_compiled(
        starts, start, restored, compiled, fallback):
    said = starts[start]
    assert said["init"]["programs"] == {
        "restored": restored, "compiled": compiled, "fallback": fallback}
    assert len(said["forms"]) == said["executables"] == FORMS
    assert len(said["files"]) == FORMS


def test_a_second_start_lowers_no_engine_program(starts):
    assert starts["first"]["lowered"]
    assert starts["second"]["lowered"] == []
    phases = starts["second"]["init"]["warm_programs_phases_s"]
    assert set(phases) == set(starts["second"]["init"]["warm_programs_by_program_s"])
    assert all(by.get("restore_s", 0) > 0 for program, by in phases.items())
    first = starts["first"]["init"]["warm_programs_phases_s"]
    assert all(by["lower_s"] > 0 and by["trace_s"] > 0 for by in first.values())


@pytest.mark.parametrize("sampling", ["greedy", "seeded"])
@pytest.mark.parametrize("start", ["second", "damaged", "unconfigured"])
def test_a_restored_program_gives_the_first_starts_tokens(starts, start, sampling):
    assert len(starts["first"][sampling]) == 6
    assert starts[start][sampling] == starts["first"][sampling]


@pytest.mark.parametrize("start", ["first", "second", "damaged", "unconfigured"])
def test_donation_and_the_weights_layout_survive(starts, start):
    assert starts[start]["donated"]
    assert starts[start]["relaid"] == 3


def test_a_damaged_file_is_written_anew(starts):
    import pickle

    from jax.experimental.serialize_executable import deserialize_and_load

    assert sorted(starts["damaged"]["files"]) == sorted(starts["second"]["files"])
    for name in starts["damaged_files"]:
        with open(os.path.join(starts["folder"], name), "rb") as f:
            key, payload, in_tree, out_tree = pickle.load(f)
        assert key[:40] == name.rsplit("-", 1)[1][:40]
        program = deserialize_and_load(payload, in_tree, out_tree,
                                       execution_devices=[jax.devices()[0]])
        assert isinstance(program, jax.stages.Compiled)


@pytest.mark.parametrize("what", ["bucket list", "model configuration", "dtype", "package bytes"])
def test_an_engine_whose_key_moved_compiles_and_never_loads(starts, what):
    moved = starts["damaged"]["moved"][what]
    assert moved["looked"] >= 1 and moved["found"] == 0
    assert moved["lowered"] >= 1


def test_an_executable_that_refuses_its_arguments_gives_way_to_the_jit(starts):
    assert starts["damaged"]["refused"] == {"fallbacks": 1, "runs_the_jit": True, "stepped": True}


def test_without_a_cache_directory_an_engine_launches_executables_and_keeps_none(starts):
    """The engine asks the store all the same (it does not ask whether there
    is one); the store finds nothing and keeps nothing, anywhere."""
    said = starts["unconfigured"]
    assert said["answered"] == {"load": ["None"], "save": ["False"]} and said["files"] is None
    assert len(said["forms"]) == said["executables"] == FORMS
    assert said["init"]["programs"] == {"restored": 0, "compiled": FORMS, "fallback": 0}
    assert said["lowered"]  # each form is lowered once, for its executable


@pytest.mark.parametrize("start", ["first", "second", "damaged", "unconfigured"])
def test_a_burst_behind_the_warm_up_compiles_and_restores_nothing_more(starts, start):
    said = starts[start]
    assert said["burst"] == {"errors": [], "programs": said["init"]["programs"]}


def test_an_executable_refused_while_the_loop_serves_gives_way_once_and_the_request_is_served(
        starts):
    """In an engine with no cache directory, as tier-1's engines are."""
    refused = starts["unconfigured"]["refused_in_the_loop"]
    assert refused == {"tokens": [starts["first"]["greedy"]] * 2, "fallbacks": 1, "compiled": 0,
                       "runs_the_jit": True}


# -- the key, with no engine ---------------------------------------------------


def _key(dtype=jnp.bfloat16, context=None, donate=(1,), form="decode:64"):
    args = ({"w": jnp.ones((4, 8), dtype)}, {"k": jnp.ones((2, 8), dtype)}, jnp.int32(3))
    return program_store.key(form, args, {}, donate, context or {"buckets": [16, 64]})


def test_the_key_is_the_same_for_the_same_program():
    assert _key() == _key()


@pytest.mark.parametrize("moved", [
    dict(dtype=jnp.float32), dict(context={"buckets": [16, 32, 64]}), dict(donate=(1, 2)),
    dict(form="decode:128"),
], ids=["dtype", "bucket list", "donation", "form"])
def test_the_key_moves_with_what_the_program_is_made_from(moved):
    assert _key(**moved) != _key()


@pytest.mark.parametrize("entry", ["package", "jax", "jaxlib", "runtime", "devices", "XLA_FLAGS",
                                   "LIBTPU_INIT_ARGS"])
def test_the_key_moves_with_the_environment(monkeypatch, entry):
    was, env = _key(), dict(program_store.environment())
    assert entry in env
    env[entry] = "another"
    monkeypatch.setattr(program_store, "environment", lambda: env)
    assert _key() != was


def test_the_key_moves_with_an_arguments_layout():
    from jax.experimental.layout import Format, Layout

    x = jnp.ones((2, 4, 8))
    relaid = jax.device_put(x, Format(Layout(major_to_minor=(1, 0, 2)), x.sharding))
    keys = [program_store.key("f", (leaf,), {}, (), None) for leaf in (x, relaid)]
    assert keys[0] != keys[1]


def test_the_package_digest_moves_with_one_byte_of_one_file(tmp_path):
    (tmp_path / "sub").mkdir()
    (tmp_path / "a.py").write_text("x = 1\n")
    (tmp_path / "sub" / "b.py").write_text("y = 2\n")
    (tmp_path / "notes.txt").write_text("not source")
    first = program_store.package_digest(str(tmp_path))
    (tmp_path / "notes.txt").write_text("still not source")
    program_store.package_digest.cache_clear()
    assert program_store.package_digest(str(tmp_path)) == first
    (tmp_path / "sub" / "b.py").write_text("y = 3\n")
    program_store.package_digest.cache_clear()
    assert program_store.package_digest(str(tmp_path)) != first


def test_the_store_is_off_where_the_compile_cache_is(tmp_path):
    """No directory, the cache disabled, or inside ``bypassed()``: nothing is
    read and nothing is written."""
    was = jax.config.jax_compilation_cache_dir
    compiled = jax.jit(lambda x: x + 1).lower(jnp.ones(3)).compile()
    try:
        jax.config.update("jax_compilation_cache_dir", None)  # as the suite runs: none is set
        assert program_store.directory() is None
        assert program_store.load("f", "k", jax.devices()[0]) is None
        assert program_store.save("f", "k", compiled) is False
        jax.config.update("jax_compilation_cache_dir", str(tmp_path))
        assert program_store.directory() == str(tmp_path / "programs")
        with jax_cache.bypassed():
            assert program_store.directory() is None
            assert program_store.save("f", "k", compiled) is False
            assert program_store.load("f", "k", jax.devices()[0]) is None
        assert program_store.load("f", "k", jax.devices()[0]) is None  # a miss
        assert program_store.save("f", "k", compiled) is True
        restored = program_store.load("f", "k", jax.devices()[0])
        assert float(restored(jnp.ones(3))[0]) == 2.0
    finally:
        jax.config.update("jax_compilation_cache_dir", was)
