"""What the engine tests share: tiny engines by family, JAX's compile events
while a block runs, and a program of a live engine replaced the way
``JaxEngine._launch`` itself replaces one (an entry of ``_programs``)."""

import collections
import contextlib

import jax
import pytest

from ray_tpu.llm import EngineConfig, JaxEngine, LLMConfig, ModelConfig

# layers alike under one loop (the dense model, and the same with routed
# experts in every layer), a stack of several traced bodies (window and full
# attention with expert layers; state-space blocks) and a latent pool
FAMILIES = {
    "dense": dict(model_id="tiny"),
    "routed": dict(model_id="tiny", model_kwargs=dict(moe_experts=4, moe_top_k=2)),
    "routed-window": dict(model_id="laguna-tiny"),
    "state-space": dict(model_id="nemotron-tiny"),
    "latent": dict(model_id="kanana-tiny"),
    # generation by diffusion over blocks of 4: the pool's step is a forward of a block a slot
    "blocks": dict(model_id="sdar-tiny"),
}
# the families whose pool carries its decode step in a chunk launch: every
# stack does, layers alike under one loop or several traced bodies, with or
# without a state a slot, and a pool that generates by blocks (its step a
# forward of a block a slot); a latent pool does not (``JaxEngine.__init__``)
CARRYING = ("dense", "routed", "routed-window", "state-space", "blocks")
# ``tests/test_chunk_rows*.py`` knew this family as "patterned-moe": the cases keep that name
ROUTED_WINDOW = pytest.param("routed-window", id="patterned-moe")


def tiny_engine(family, **engine_kw):
    kw = dict(max_num_seqs=4, max_seq_len=128, prefill_chunk=16, prefill_buckets=(8, 16, 32),
              max_concurrent_admissions=4, enable_prefix_caching=False, dtype="float32")
    kw.update(engine_kw)
    return JaxEngine(LLMConfig(model=ModelConfig(seed=3, **FAMILIES[family]),
                               engine=EngineConfig(**kw)))


class Compiles:
    """Programs JAX compiled, or fetched from its compile cache, while open
    (as ``benchmark/trace.py CompileCounter`` counts them in a window)."""

    def __init__(self):
        self.names = []

    def _on_event(self, event, duration, **kw):
        if event == "/jax/core/compile/backend_compile_duration":
            self.names.append(str(kw.get("fun_name")))

    def __enter__(self):
        jax.monitoring.register_event_duration_secs_listener(self._on_event)
        return self

    def __exit__(self, *exc):
        jax.monitoring.unregister_event_duration_listener(self._on_event)


@contextlib.contextmanager
def programs_replaced(eng, name, make):
    """While open, every form of the program ``name`` that ``eng`` holds
    (``chunk_mid``, ``chunk_final``, ``decode``...) is ``make(the form's
    program)``: what its launches then call, on the path every engine of one
    device takes."""
    was = {form: program for form, program in eng._programs.items() if form[0] == name}
    assert was, (name, sorted(eng._programs))
    eng._programs.update({form: make(program) for form, program in was.items()})
    try:
        yield
    finally:
        eng._programs.update(was)


def launched_forms(eng) -> collections.Counter:
    """How many forms of each program ``eng`` holds, by the program's name."""
    return collections.Counter(form[0] for form in eng._programs)


def decoding(eng, ids, sampling_params):
    """Submit a request and come back once it decodes (two tokens out): what
    is admitted after that finds a live row to decode beside its chunks."""
    req = eng.submit(prompt_token_ids=ids, sampling_params=sampling_params)
    while len(req.out_tokens) < 2:
        assert not req.done.wait(0.001)
    return req


def together(eng, requests):
    """Submit while the loop takes nothing in, so that one pass admits all."""
    eng._pull_waiting = lambda: False  # the loop looks its stages up each pass
    try:
        reqs = [eng.submit(prompt_token_ids=ids, sampling_params=sp, lora=lora)
                for ids, sp, lora in requests]
    finally:
        del eng._pull_waiting
    for req in reqs:
        eng._await_done(req)
    return reqs
