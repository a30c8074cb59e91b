"""Traffic kind ``looped_closed_loop``: the closed loop of
``benchmark/kinds/closed_loop.py`` (``clients`` callers, each sending its next
request when the last returns; completion tokens per second over the window)
for a model whose stack runs several times a token (family ``looped_dense``),
with two things of its own:

- every request's body holds ``ignore_eos`` (the traffic file's), so that an
  answer is its ``max_tokens`` long: ``serving.completion_body`` does not
  pass the field on, so it is wrapped while the loop runs (as
  ``benchmark/kinds/docs_shared.py body_of`` adds it);
- the replica is the harness's with another ``bench_check_reference``
  (``LoopedServer``, swapped in as ``benchmark/kinds/block_closed_loop.py``
  swaps its own). A token of this family holds a cache row for every pass and
  layer (1.5 MB at the published sizes), so the probe that fills every slot of
  the engine cannot also go through ``models/llama.py prefill`` on a cache of
  its own beside the resident engine: ``probe.prompt_lens`` prompts (as many
  as the engine has slots, one of each prefill bucket among them) go through
  the engine's own loop at once and decode ``probe.decode_steps`` steps as one
  batch, and the first ``probe.logit_rows`` of them through ``prefill`` and
  ``decode_step``. The numbers are ``benchmark/compare.py``'s
  (``kv_prefill_rel_rms``, ``kv_decode_rel_rms`` over all passes' rows of the
  engine's own cache; ``logits_rel_rms``), against one pass of the reference;
  beside them, not limited, the keys' and values' error by pass (how it grows
  from pass 0 to the last) and the passes and exits the engine's counters
  took over the probe."""

from __future__ import annotations

import time
from unittest import mock

import numpy as np

from benchmark import compare, families, reference, serving
from benchmark.kinds import closed_loop


def kv_errors(pairs, passes: int) -> dict:
    """Keys' and values' relative error over ``pairs`` of (the program's
    [rows, T, K, D], the reference's pass-major [passes * layers, T, K, D], the
    prompt's length): over the prompts' positions and over the decoded ones,
    every row the program's cache has, and of each pass's rows alone (prefill
    and decode together), a number a pass so that a limit can be set on each:
    the last pass holds nearly all of the sums over all rows, and a fault in
    an early pass would hide under it."""
    # [passes, (prefill, decode), (difference, reference)] sums of squares
    sq = np.zeros((passes, 2, 2))
    for have, ref_kv, p in pairs:
        per = ref_kv.shape[0] // passes
        for t in range(have.shape[0] // per):  # (a stack cut short has fewer)
            a = have[t * per:(t + 1) * per].astype(np.float64)
            b = ref_kv[t * per:(t + 1) * per].astype(np.float64)
            d = (a - b) ** 2
            sq[t, 0] += [d[:, :p].sum(), (b[:, :p] ** 2).sum()]
            sq[t, 1] += [d[:, p:].sum(), (b[:, p:] ** 2).sum()]
    whole = sq.sum(axis=0)
    return {
        "kv_prefill_rel_rms": float(np.sqrt(whole[0, 0] / whole[0, 1])),
        "kv_decode_rel_rms": float(np.sqrt(whole[1, 0] / whole[1, 1])),
        **{f"kv_pass{t}_rel_rms": float(np.sqrt(s[:, 0].sum() / s[:, 1].sum()))
           for t, s in enumerate(sq) if s[:, 1].sum()},
    }


def probe_errors(got: dict, ref, params, rows: list, probe: dict, passes: int) -> dict:
    """One pass of the reference over the seeded rows (logits) and the rows
    the engine made of its prompts (keys and values, pass-major)."""
    lens, steps = probe["prompt_lens"], probe["decode_steps"]
    engine_rows, n = got["engine"], len(rows)
    want = ref.forward_rows(
        params, list(rows) + [e["tokens"] for e in engine_rows], last=steps + 1,
        kv_rows=range(n, n + len(engine_rows)))
    logits, want_logits = got["logits"], np.stack(want["logits"][:n])
    return {
        "logits_rel_rms": reference.rel_rms(logits, want_logits),
        **kv_errors([(have, ref_kv, p) for i, (e, p) in enumerate(zip(engine_rows, lens))
                     for have, ref_kv in zip((e["k"], e["v"]), want["kv"][n + i])], passes),
        "per_row": [reference.rel_rms(g, w) for g, w in zip(logits, want_logits)],
        "engine_generated": [e["generated"] for e in engine_rows],
        "top1_agree": float(np.mean(np.argmax(logits, -1) == np.argmax(want_logits, -1))),
        "exit_passes": sorted({int(t) for row in want["exit"] for t in row}),
    }


class LoopedServer(serving.BenchLLMServer):
    """The replica with the check of a model whose cache has a row a pass and layer."""

    def bench_check_reference(self, seed: int, config: dict, control=None) -> dict:
        import jax

        t = time.perf_counter()
        family = families.load(config)
        probe = config["run"]["probe"]
        rows = compare.probe_rows(seed, probe)
        lens, steps, n = probe["prompt_lens"], probe["decode_steps"], probe["logit_rows"]
        before = self.engine.get_stats()["counters"]
        if control == "int8":
            self.engine.params = family.int8_roundtrip(self.engine.params)
        compare.forget_prefixes(self.engine)
        got = {
            "engine": compare.engine_probe(
                self.engine, [r[:p] for r, p in zip(rows, lens)], steps),
            "logits": compare.serve_program_logits(
                self.engine.params, self.engine.model_cfg, rows[:n],
                dict(probe, prompt_lens=lens[:n])),
        }
        after = self.engine.get_stats()["counters"]
        if control == "int8":
            self.bench_load_weights(seed, config)
        ref = family.Reference(config, jax.local_devices()[:1])
        errors = probe_errors(got, ref, self.engine.params, rows[:n], probe, family.passes(config))
        grown = {k: after[k] - before[k] for k in ("loop_forwards", "loop_stack_passes")}
        return dict(errors, loop_counts=grown, seconds=time.perf_counter() - t,
                    memory=self.bench_memory())


def run(ctx: dict) -> dict:
    plain = serving.completion_body

    def body(model: str, req: dict, traffic: dict, stream: bool) -> dict:
        return dict(plain(model, req, traffic, stream), ignore_eos=traffic["ignore_eos"])

    # ``serving.build_app`` deploys the class this name holds when it is called
    with mock.patch.object(serving, "BenchLLMServer", LoopedServer), \
            mock.patch.object(serving, "completion_body", body):
        return closed_loop.run(ctx)
