"""The comparison that decides ``correct``: numbers that come out of the
program's own compiled programs against the plain reference's, on the same
weights and inputs, both made by the benchmark from ``--seed``. The limits
stand in each configuration file (``run.limits``: every key there decides),
set from readings on the chip: PERF.md gives, for each, the largest reading
of sound runs, the smallest of the int8 control, and the limit between them.
``benchmark/tools/calibrate.py`` takes those readings.

Train cells compare the gradient the measured step program itself computed:
after the first step, Adam's first moment is ``(1 - b1)`` times the clipped
gradient, so ``grad_rel_rms`` reads every ``stride``-th element of every
moment leaf out of the step's own output state and sets it against the
reference's per-layer ``vjp`` gradient, clipped by the reference's own norm.
That number passes through the forward pass, the fused loss, the remat
backward, the clip and the optimizer's moment update as the window runs
them. ``logits_rel_rms`` (``models/llama.py forward`` under the cell's model
settings) stands beside it. The loss and the gradient norm are means over
thousands of tokens in which lost precision cancels: they are printed, and
decide nothing.

Serving cells send the probe prompts through the engine's own loop
(``submit``: scheduler, ``chunk_mid``, ``chunk_final``, ``decode_fn``) and
read the keys and values those programs left in the engine's own cache, for
every layer, against the reference's on the same tokens: the seeded prompt
(``kv_prefill_rel_rms``) and the positions the decode program wrote
(``kv_decode_rel_rms``). The engine's programs sample in-program and hand
out tokens only, so the last layer's output and the head are compared
through ``models/llama.py prefill`` and ``decode_step`` under the engine's
model config (``logits_rel_rms``)."""

from __future__ import annotations

import time

import numpy as np

from benchmark import reference


def rel_err(got: float, want: float) -> float:
    return abs(got - want) / abs(want)


# ------------------------------------------------------------------- training


class GradSample:
    """Every ``stride``-th element of each gradient leaf, flattened. The
    reference's side is filled by ``Reference.loss_and_grad_norm(visit=)``;
    the program's side is read out of a parameter-shaped tree (stacked leaves
    carry a leading layer axis, as in ``benchmark/weights.py``)."""

    def __init__(self, stride: int):
        import jax
        import jax.numpy as jnp

        self.ref = {}
        self._whole = jax.jit(lambda g: g.reshape(-1)[::stride].astype(jnp.float32))
        self._of_layer = jax.jit(
            lambda g, i: jax.lax.dynamic_index_in_dim(g, i, 0, keepdims=False)
            .reshape(-1)[::stride].astype(jnp.float32)
        )

    def visit(self, name, layer, grad) -> None:
        self.ref[(name, layer)] = self._whole(grad)

    def program(self, tree: dict) -> dict:
        return {
            (name, layer): self._whole(tree[name]) if layer is None
            else self._of_layer(tree[name], layer)
            for name, layer in self.ref
        }


def grad_errors(sample: GradSample, moments: dict, b1: float, clip: float,
                ref_norm: float) -> dict:
    """``moments``: Adam's first moment after the program's first step."""
    got = sample.program(moments)
    ref_scale = min(1.0, clip / ref_norm)
    sums = {}
    for (name, _), want in sample.ref.items():
        a = np.asarray(got[(name, _)], np.float64) / (1.0 - b1)
        b = np.asarray(want, np.float64) * ref_scale
        s = sums.setdefault(name, np.zeros(4))
        s += [np.sum((a - b) ** 2), np.sum(b * b), np.sum(a * b), np.sum(a * a)]
    total = sum(sums.values())
    return {
        "grad_rel_rms": float(np.sqrt(total[0] / total[1])),
        # the same after the best-fitting common factor: what is left when the
        # clip's own error (one factor on every element) is taken out
        "grad_rel_rms_direction": float(np.sqrt(max(0.0, 1.0 - total[2] ** 2 / (total[3] * total[1])))),
        "grad_rel_rms_by_leaf": {k: float(np.sqrt(s[0] / s[1])) for k, s in sorted(sums.items())},
    }


def train_reference(ref, params, tokens, run: dict, rows: int) -> dict:
    sample = GradSample(run["grad_sample_stride"])
    loss, gnorm = ref.loss_and_grad_norm(params, tokens, visit=sample.visit)
    logits = ref.logits(params, tokens[:rows, :-1], last=run["logit_positions"])
    return {"loss": loss, "grad_norm": gnorm, "logits": logits, "grads": sample}


def train_program_logits(params, tokens, cfg, mesh, run: dict, rows: int):
    import jax

    from ray_tpu.models.llama import forward
    from ray_tpu.models.training import batch_sharding

    last = run["logit_positions"]
    fed = jax.device_put(tokens[: max(rows, mesh.size), :-1], batch_sharding(mesh))
    out = jax.jit(lambda p, t: forward(p, t, cfg, mesh)[:, -last:])(params, fed)
    return np.asarray(out)[:rows]


def train_errors(first_metrics: dict, moments: dict, logits, want: dict, run: dict,
                 b1: float) -> dict:
    return {
        "logits_rel_rms": reference.rel_rms(logits, want["logits"]),
        **grad_errors(want["grads"], moments, b1, run["grad_clip"], want["grad_norm"]),
        "loss_rel_err": rel_err(float(first_metrics["loss"]), want["loss"]),
        "grad_norm_rel_err": rel_err(float(first_metrics["grad_norm"]), want["grad_norm"]),
    }


# -------------------------------------------------------------------- serving


def probe_rows(seed: int, probe: dict) -> list:
    """Seeded token rows: each prompt and the tokens fed to the decode steps
    after it (seeded too, not the program's own, so that the program and a
    control see the same inputs)."""
    rng = np.random.default_rng(seed)
    return [
        rng.integers(0, 256, n + probe["decode_steps"], dtype=np.int32)
        for n in probe["prompt_lens"]
    ]


def forget_prefixes(engine) -> None:
    """Empty the engine's prefix cache: a probe must compute every key and
    value itself, not copy those an earlier probe of the same prompt left
    (a control after a sound run read 1.8% where it computes 3.3%)."""
    engine._prefix_cache.clear()
    engine._prefix_bytes = 0


def engine_probe(engine, prompts: list, steps: int, timeout_s: float = 600.0) -> list:
    """The prompts through the engine's own loop, greedy, ``steps`` + 1
    tokens each, all at once (one decode batch). Returns for each the tokens
    whose keys and values the engine's programs wrote (the prompt, then the
    engine's own tokens but the last) and those keys and values
    [L, T, KV, D], read out of the engine's cache."""
    from ray_tpu.llm.config import SamplingParams

    placed = {}
    inner = engine._start_admission

    def recording(pool, slot, req):
        placed[req.request_id] = (pool, slot)
        return inner(pool, slot, req)

    engine._start_admission = recording  # the engine does not say which slot
    try:
        params = SamplingParams(max_tokens=steps + 1, temperature=0.0, ignore_eos=True)
        reqs = [
            engine.submit(prompt_token_ids=[int(t) for t in p], sampling_params=params)
            for p in prompts
        ]
        for req in reqs:
            engine._await_done(req)
            if req.error is not None:
                raise req.error
            if req.prefix_hit_tokens:
                raise RuntimeError("a probe prompt was served from the prefix cache")
    finally:
        del engine._start_admission
    deadline = time.perf_counter() + timeout_s
    pools = {id(pool): pool for pool, _ in placed.values()}.values()
    while any(p.inflight or p.first_pending or p.admitting or any(p.slots) for p in pools):
        if time.perf_counter() > deadline:
            raise TimeoutError("the engine did not come to rest after the probe")
        time.sleep(0.002)
    out = []
    for req in reqs:
        pool, slot = placed[req.request_id]
        tokens = np.asarray(list(req.prompt_token_ids) + list(req.out_tokens[:-1]), np.int32)
        k, v = (
            np.asarray(pool.cache[name][:, slot, :, : len(tokens)].astype("float32"))
            .transpose(0, 2, 1, 3)
            for name in ("k", "v")
        )
        out.append({"tokens": tokens, "k": k, "v": v, "generated": len(req.out_tokens)})
    return out


def serve_program_logits(params, cfg, rows: list, probe: dict):
    import jax
    import jax.numpy as jnp

    from ray_tpu.models.llama import decode_step, init_kv_cache, prefill

    lens, width, steps = probe["prompt_lens"], probe["stripe"], probe["decode_steps"]
    tokens = np.zeros((len(lens), width), np.int32)
    for i, (r, n) in enumerate(zip(rows, lens)):
        tokens[i, :n] = r[:n]
    cache = init_kv_cache(cfg, len(lens), width + steps)
    pre = jax.jit(lambda p, c, t, n: prefill(p, c, t, cfg, lengths=n))
    dec = jax.jit(lambda p, c, t: decode_step(p, c, t, cfg), donate_argnums=(1,))
    logits, cache = pre(params, cache, jnp.asarray(tokens), jnp.asarray(lens, jnp.int32))
    got = [np.asarray(logits)]
    for step in range(steps):
        fed = np.asarray([r[n + step] for r, n in zip(rows, lens)], np.int32)
        logits, cache = dec(params, cache, jnp.asarray(fed))
        got.append(np.asarray(logits))
    return np.stack(got, axis=1)  # [rows, steps + 1, V]


def serve_program(engine, rows: list, probe: dict) -> dict:
    """Everything the program's side gives, before the reference runs."""
    lens, steps = probe["prompt_lens"], probe["decode_steps"]
    forget_prefixes(engine)
    return {
        "engine": engine_probe(engine, [r[:n] for r, n in zip(rows, lens)], steps),
        "logits": serve_program_logits(engine.params, engine.model_cfg, rows, probe),
    }


def serve_errors(got: dict, ref, params, rows: list, probe: dict) -> dict:
    """One pass of the reference over the seeded rows (logits) and the rows
    the engine made of the same prompts (keys and values)."""
    lens, steps = probe["prompt_lens"], probe["decode_steps"]
    engine_rows = got["engine"]
    n = len(rows)
    want = ref.forward_rows(
        params, list(rows) + [e["tokens"] for e in engine_rows], last=steps + 1,
        kv_rows=range(n, n + len(engine_rows)),
    )
    want_logits = np.stack(want["logits"][:n])
    sq = {"prefill": np.zeros(2), "decode": np.zeros(2)}
    for i, (e, p) in enumerate(zip(engine_rows, lens)):
        for have, ref_kv in zip((e["k"], e["v"]), want["kv"][n + i]):
            d = (have.astype(np.float64) - ref_kv) ** 2
            r = ref_kv.astype(np.float64) ** 2
            sq["prefill"] += [d[:, :p].sum(), r[:, :p].sum()]
            sq["decode"] += [d[:, p:].sum(), r[:, p:].sum()]
    logits = got["logits"]
    return {
        "logits_rel_rms": reference.rel_rms(logits, want_logits),
        "kv_prefill_rel_rms": float(np.sqrt(sq["prefill"][0] / sq["prefill"][1])),
        "kv_decode_rel_rms": float(np.sqrt(sq["decode"][0] / sq["decode"][1])),
        "per_row": [reference.rel_rms(g, w) for g, w in zip(logits, want_logits)],
        "engine_generated": [e["generated"] for e in engine_rows],
        "top1_agree": float(np.mean(np.argmax(logits, -1) == np.argmax(want_logits, -1))),
    }
