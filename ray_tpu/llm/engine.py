"""JaxEngine: continuous-batching LLM inference on TPU.

The TPU-native replacement for the reference's delegated vLLM engine
(``python/ray/llm/_internal/serve/deployments/llm/vllm/vllm_engine.py``).
Where vLLM's paged attention uses dynamic block tables (a GPU-pointer idiom),
the TPU engine keeps everything static for XLA:

- a fixed decode batch of ``max_num_seqs`` SLOTS, each owning a
  ``max_seq_len`` stripe of the KV cache — one compiled decode program,
  [slots, 1] tokens/step, runs forever regardless of admission/eviction;
- prompt prefill compiles once per length BUCKET (powers of two) and
  scatters the resulting K/V into the idle slot's stripe;
- continuous batching = host-side slot bookkeeping between device steps:
  finished slots free instantly, waiting requests prefill into free slots
  while other slots keep decoding (no global barrier on admission);
- sampling (greedy / temperature / top-k) runs in-program; only sampled
  token ids cross back to the host each step. A row's 64 candidates are the
  one-stage ``lax.top_k``'s, values and indices; over a wide vocabulary they
  are found in two exact stages (``ops/topk.py``: a maximum a 128-lane
  block, then the 64 best among the 64 winning blocks' 8,192 logits), so no
  program sorts a whole vocabulary.

- a model that generates by diffusion over blocks
  (``LlamaConfig.block_length``) runs a forward of a block a slot where the
  others run a decode step (``programs``' ``block_step``): the block's
  tokens, which of them are masked and the denoising step live on the device
  between launches (``_Pool.block``), so steps chain without the host as a
  token a step does; a fetch brings a slot's committed block, up to
  ``block_length`` tokens at once, or nothing (``_take_blocks``). Such a
  pool's chunk launches carry its step as every other pool's that is not
  latent do (``JaxEngine.__init__``): the one-row middle chunk and the final
  chunk take the block state with the cache and run one forward of every
  slot's block beside the chunk's tokens, the rows a block wide
  (``models/patterned.py decode_forward``), so the prompt's read of the
  weights and the expert banks is the slots' as well; what the forward hands
  out is ``block_step``'s, row for row, and reaches ``_take_blocks`` the same
  way. A latent pool still launches its chunks alone: its answers are held
  to be the same to the token whatever runs beside them.

TP/SP: params and cache shard over a mesh via the model's logical rules
(``parallel/mesh.py``) when ``tensor_parallel_degree > 1``.
"""

from __future__ import annotations

import bisect
import dataclasses
import logging
import queue
import threading
import time
import uuid
from collections import deque
from contextlib import contextmanager
from typing import Any, Callable, Iterator, Optional

import numpy as np

from ray_tpu._private import program_store
from ray_tpu.llm.config import (
    CHUNK_ROWS_MAX, EngineConfig, LLMConfig, ModelConfig, SamplingParams,
)
from ray_tpu.llm.pacing import TokenPacer
from ray_tpu.llm.tokenizer import get_tokenizer
from ray_tpu.util import metrics as app_metrics
from ray_tpu.util import tracing

logger = logging.getLogger(__name__)

# Cumulative counters of one engine (``get_stats()["counters"]``; mirrored
# into ``util.metrics`` as ``llm_engine_<name>`` once per finished request, so
# the ``/metrics`` scrape shows them). A name with a ``:`` is one label of a
# family: ``requests_failed:decode`` is ``requests_failed`` with stage
# ``decode``.
# The routed experts' counts by program (``models/patterned.py
# moe_stats_names``): a program of a model that holds a share of its experts
# hands out the fifth and the sixth, the assignments that fell on the experts
# held here of those the router made, and the blocks of sorted rows its expert
# layers worked through (one a layer run, so ``moe_passes`` is
# ``moe_layer_steps`` unless a run's held assignments overflowed its block and
# took another); both stay 0 for every other model.
_MOE_COUNTERS = ("moe_layer_steps", "moe_assignments", "moe_experts_touched",
                 "moe_max_expert_load_sum", "moe_assignments_held", "moe_passes")
_MOE_PROGRAMS = ("decode", "chunk_mid", "chunk_final")
# the most passes a looped model's exits are counted over (a label each)
LOOP_PASSES_MAX = 8
# why a chunk launch that takes the pool's decode rows carried no step
DEAD_CAUSES = ("step_carried", "runahead_full", "no_slot")
COUNTERS = (
    "requests_submitted",
    "requests_finished:stop", "requests_finished:length",
    "requests_empty",  # finished with zero tokens (first token was a stop)
    "requests_failed:submit", "requests_failed:admission",
    "requests_failed:decode", "requests_failed:loop_exit",
    "prompt_tokens", "prompt_tokens_from_prefix",
    "tokens_generated",
    "first_tokens",  # of tokens_generated, those a final prefill chunk sampled
    # decoded for a slot that had finished or was re-bound (run-ahead)
    "tokens_discarded",
    # a model that generates by blocks (``LlamaConfig.block_length``): a step
    # is one forward of every slot's block, and these count what the fetch of
    # a step found for the slots still bound to the request that launched it
    # (a forward a run-ahead launched for a request that had ended counts
    # nowhere): forwards by kind (one that unmasked, one that committed the
    # clean block's keys and values), the positions the denoise forwards
    # unmasked, the blocks committed, their tokens that reached a request
    # (less than a block's where it ended inside one; the rest are
    # ``tokens_discarded``), and the prompt tokens that rode in a first block
    # because they filled no whole block of their own
    "block_forwards:denoise", "block_forwards:commit", "block_tokens_unmasked",
    "blocks_committed", "block_tokens_emitted", "block_prompt_tail_tokens",
    "decode_steps", "decode_slot_steps",  # slot_steps: sum of active slots
    # of decode_steps, those a prompt chunk's launch carried (no ``decode_fn``
    # ran for them: the rows rode through ``chunk_mid`` or ``chunk_final``)
    "decode_steps_in_chunk",
    # launches of a chunk program that takes the pool's decode rows
    # (``_takes_rows``) and carried no step: the rows' arithmetic ran for no
    # token (``_decode_rows``: no row is live), by what kept the step from
    # riding: an earlier launch of the pass had carried it, the run-ahead was
    # full, or no slot held a request. With ``decode_steps_in_chunk`` they are
    # the launches of such a program
    *(f"decode_steps_dead_in_chunk:{cause}" for cause in DEAD_CAUSES),
    # a prompt's chunks by kind, one a prompt chunk: a row of a chunk program
    "prefill_chunks:mid", "prefill_chunks:final",
    # launches of the chunk programs: admissions whose next chunks are of one
    # program run as rows of one launch, so chunks over programs is the mean
    # number of rows a launch carried
    "prefill_programs:mid", "prefill_programs:final",
    # (the loop's passes and idle sleeps are its clock's: ``get_stats()["loop"]``)
    # keys and values a decode step has to read: over decode steps and active
    # slots, the slot's length, and what a sliding-window layer needs of it
    # (min(length, window); stays 0 where the model has no window)
    "decode_kv_tokens_global", "decode_kv_tokens_window",
    # keys and values a decode step does read: over the same steps and slots,
    # the positions the decode kernel's blocks cover between a slot's bounds
    # (``ops/decode_attention.py positions_read``) in a full layer and in a
    # sliding-window one (0 where the model has none); the slot's whole
    # stripe in a pool whose steps keep the einsum (``_Pool.reads_blocks``).
    # Like the tokens beside them they are counted from the lengths the loop
    # holds at a launch: behind the device's by the run-ahead, one length for
    # all of a launch's steps, active slots only (the kernel also walks a
    # freed slot's stripe up to the length it ran on to: PERF.md section 7).
    # tokens over positions is the read's efficiency, positions over
    # decode_slot_steps x stripe what it still touches of the stripe
    "decode_kv_positions_read", "decode_kv_positions_read_window",
    # the same pair for the layers of a latent-attention model (a token there
    # is one shared rotated key and one latent; the kernel's blocks follow the
    # stripe alone there, ``ops/decode_attention.py block_size``); such a model has no
    # full or window layers, so the four above stay 0 for it
    "decode_kv_tokens_latent", "decode_kv_positions_read_latent",
    # a model whose latent layers attend what an indexer picks
    # (``LlamaConfig.index_topk``): the positions a decode launch's rows
    # scored in an indexed layer (their lengths) and those they then attended
    # (each row's length or ``index_topk``, the smaller), from the host's own
    # lengths like the pairs above; 0 for every other model
    "index_positions_scored", "index_positions_selected",
    # a prompt chunk's attention, by program: the chunk's real tokens, and
    # over them the cached positions each attends to (a token at position p
    # sees p + 1: what came before the chunk and the chunk up to itself)
    "prefill_query_tokens:chunk_mid", "prefill_query_tokens:chunk_final",
    "prefill_attended_positions:chunk_mid", "prefill_attended_positions:chunk_final",
    # tokens whose keys and values ``prefix_seed`` programs copied into a
    # scratch stripe (``prompt_tokens_from_prefix`` counts the same tokens at
    # admission; this one counts the copies)
    "prefix_seed_tokens",
    # a pool that keeps a state a slot (``_Pool.stateful``) stores and seeds
    # a prefix as a snapshot of a slot (``_snapshot_store``): entries stored,
    # seeded from and evicted, the bytes stored and the bytes seeded
    "snapshots_stored", "snapshots_hit", "snapshots_evicted",
    "snapshot_store_bytes", "snapshot_seed_bytes",
    # routed experts (``models/patterned.py moe_stats_names``), summed over expert
    # layers and over the runs of each program: the decode program hands its
    # counts out beside its tokens, a prompt's middle chunks add theirs up on
    # the device and its final chunk hands both out beside the first token,
    # fetched with them. Every row a program routes counts, a dead slot's and
    # a padded chunk's too: they touch experts as live ones do
    *(f"{name}:{program}" for name in _MOE_COUNTERS for program in _MOE_PROGRAMS),
    # a model whose stack runs several times a token (``LlamaConfig.loop_passes``;
    # ``models/patterned.py LOOP_STATS``), as its programs hand them out beside
    # their tokens: the forwards that reported (a decode step, a prompt's chunk
    # with whatever step it carried), the passes their stacks ran
    # (``loop_passes`` a forward while every pass is run: the name says
    # ``stack`` because the engine loop's own passes are ``get_stats()["loop"]``'s),
    # and the rows whose head read pass t: a final chunk's sampled row and a
    # step's rows in the slots that held a request at its launch (``live``; a
    # step of ``decode_steps`` > 1 counts every slot's). 0 for every other model
    "loop_forwards", "loop_stack_passes",
    *(f"loop_exit_rows:{t}" for t in range(LOOP_PASSES_MAX)),
)
_LABEL = {"requests_finished": "reason", "requests_failed": "stage",
          "prefill_chunks": "kind", "prefill_programs": "kind",
          "decode_steps_dead_in_chunk": "cause", "block_forwards": "kind",
          "loop_exit_rows": "pass",
          **dict.fromkeys((*_MOE_COUNTERS, "prefill_query_tokens",
                           "prefill_attended_positions"), "program")}
# request latencies: 1 ms to 200 s, a quarter more each bucket, so a median
# read from the bucket counts is within an eighth of the truth
LATENCY_BOUNDS = tuple(1e-3 * 1.25**i for i in range(56))
LATENCIES = ("queue_wait_s", "prefill_s", "token_gap_s")
# the loop's own clock (``get_stats()["loop"]``): the stages of a pass in the
# order it runs them, the bounds of its histogram of pass durations (the
# latency bounds' rule, from 0.1 ms to 200 s), and how many wall seconds keep
# their longest pass: ten minutes, because whoever asks may ask late (a
# profiler that stops after a busy window of a model of many small operations
# took 161 s to hand back its trace, and the seconds asked about came before)
LOOP_STAGES = ("pull_waiting", "advance_admissions", "launch_decodes", "drain", "idle_sleep")
PASS_BOUNDS = tuple(1e-4 * 1.25**i for i in range(66))
LONGEST_PASS_SECONDS = 600
_registered: dict = {}
_registered_lock = threading.Lock()


def _metric(kind: str, name: str, **kw):
    """The process's one ``util.metrics`` object of that name (engines of
    one process share it; creating it twice would drop the first's counts)."""
    with _registered_lock:
        if name not in _registered:
            cls = getattr(app_metrics, kind)
            _registered[name] = cls(f"llm_engine_{name}", **kw)
        return _registered[name]


def _order_of(leaf) -> Optional[tuple]:
    """The axes of a device array from major to minor as the device holds
    them; ``None`` for a host array, which has no layout. (Row-major is not
    every leaf's default on a TPU: a narrow last axis is moved inward.)"""
    layout = getattr(getattr(leaf, "format", None), "layout", None)
    return tuple(layout.major_to_minor) if layout is not None else None


# the donated positions of the programs the loop launches (``JaxEngine._compile``)
_DONATED = {
    "_decode_jit": (1,), "_decode_multi_jit": (1,), "_chunk_mid_jit": (1, 5),
    "_chunk_final_jit": (1, 2), "_new_stripe_jit": (), "_seed_prefix_jit": (0,),
    "_store_snapshot_jit": (), "_block_step_jit": (1, 5), "_seed_block_jit": (0,),
}

# What a start spends deriving its programs, by phase: JAX's own duration
# events, and ``restore_s`` from ``JaxEngine._program``, summed into the dict
# ``_warm_programs`` hangs here for the thread it runs on (JAX fires an event
# on the thread that traced or compiled). A ``jit`` traced inside another
# fires its own trace event before the outer one's, which holds its seconds
# again: ``_traced`` keeps (start, seconds) of the traces booked so that an
# outer one takes back what it encloses.
_PHASE_OF_EVENT = {
    "/jax/core/compile/jaxpr_trace_duration": "trace_s",
    "/jax/core/compile/jaxpr_to_mlir_module_duration": "lower_s",
    "/jax/core/compile/backend_compile_duration": "compile_or_fetch_s",
}
_phases = threading.local()
_listen_lock = threading.Lock()
_listening = False


def _book_phase(phase: str, seconds: float) -> None:
    into = getattr(_phases, "into", None)
    if into is None:
        return
    if phase == "trace_s":
        start, whole, traced = time.time() - seconds, seconds, _phases.traced
        while traced and traced[-1][0] >= start:
            seconds -= traced.pop()[1]
        traced.append((start, whole))
    into[phase] = into.get(phase, 0.0) + seconds


def _listen_to_jax() -> None:
    """Once a process: JAX has no way to take a listener back."""
    global _listening
    import jax.monitoring

    with _listen_lock:
        if _listening:
            return
        _listening = True
    jax.monitoring.register_event_duration_secs_listener(
        lambda event, seconds, **_: _book_phase(_PHASE_OF_EVENT[event], seconds)
        if event in _PHASE_OF_EVENT else None)


def _latency_histogram(name: str) -> "app_metrics.Histogram":
    """One series an engine (tag ``engine``): ``get_stats()["latency"]`` is
    read back from it, so there is one set of bucket counts."""
    return _metric("Histogram", name, boundaries=LATENCY_BOUNDS, tag_keys=("engine",),
                   description="seconds, per finished request")


@dataclasses.dataclass
class RequestOutput:
    request_id: str
    prompt_token_ids: list
    token_ids: list
    text: str
    finish_reason: str  # "stop" | "length"
    metrics: dict


class _Request:
    def __init__(
        self,
        request_id: str,
        token_ids: list[int],
        params: SamplingParams,
        lora_idx: int = 0,
    ):
        self.request_id = request_id
        self.prompt_token_ids = token_ids
        self.params = params
        self.out_tokens: list[int] = []
        self.finish_reason: Optional[str] = None
        self.done = threading.Event()
        self.stream_queue: "queue.Queue" = queue.Queue()
        # stamped where each happens; the request's spans and latency
        # histograms are made from them once, when it ends
        self.submitted_t = time.time()
        self.admitted_t: Optional[float] = None
        self.first_token_t: Optional[float] = None
        self.finished_t: Optional[float] = None
        self.pool_stripe: Optional[int] = None
        self.slot: Optional[int] = None
        self.chunks_run = 0
        self.trace_ctx: Optional[tuple] = None  # the caller's (trace, span)
        self.error: Optional[BaseException] = None
        self.lora_idx = lora_idx
        self.prefix_hit_tokens = 0
        self.prefix_key = None  # of the snapshot it was seeded from
        self.pacer = TokenPacer()  # smooths multi-step token bursts for SSE
        # prompt tokens at the front of its first block (block generation)
        self.block_tail = 0


def _between(start: Optional[float], end: Optional[float]) -> Optional[float]:
    return None if start is None or end is None else end - start


class _LoopClock:
    """The engine loop's own time, kept whether or not a profiler runs: plain
    numbers that the loop thread alone writes (no lock and no ``util.metrics``
    object on the pass path; ``view`` copies them from any thread). Seconds by
    stage since the first pass and, of them, the seconds inside fetches and
    inside launch calls; a histogram of pass durations; and the longest pass
    of each wall second that had one, for the last ``LONGEST_PASS_SECONDS``:
    a pass of 12 s is one record followed by eleven seconds that have none."""

    def __init__(self):
        self.started_t: Optional[float] = None  # ``perf_counter`` at the first pass
        self.passes = 0
        self.idle_sleeps = 0
        self.stage_s = dict.fromkeys(LOOP_STAGES, 0.0)
        self.fetch_s = 0.0
        self.launch_s = 0.0
        self.pass_counts = [0] * (len(PASS_BOUNDS) + 1)
        self.longest: deque = deque(maxlen=LONGEST_PASS_SECONDS)
        self._call_s, self._call = 0.0, None  # this pass's longest fetch or launch

    def call(self, kind: str, program: str, seconds: float) -> None:
        """A fetch or a launch call of this pass took ``seconds``."""
        if kind == "fetch":
            self.fetch_s += seconds
        else:
            self.launch_s += seconds
        if seconds > self._call_s:
            self._call_s, self._call = seconds, f"{kind}:{program}"

    def end_pass(self, wall_t: float, marks: tuple) -> None:
        """``marks``: ``perf_counter`` at the pass's start and after each of
        its stages; ``wall_t``: ``time.time()`` at its start."""
        took = marks[-1] - marks[0]
        stages = {name: b - a for name, a, b in zip(LOOP_STAGES, marks, marks[1:])}
        for name, s in stages.items():
            self.stage_s[name] += s
        self.pass_counts[bisect.bisect_left(PASS_BOUNDS, took)] += 1
        last = self.longest[-1] if self.longest else None
        same_second = last is not None and int(last["t"]) == int(wall_t)
        if not same_second or took > last["s"]:
            record = {"t": wall_t, "s": took, "stage_s": stages,
                      "call": self._call, "call_s": self._call_s}
            if same_second:
                self.longest[-1] = record
            else:
                self.longest.append(record)
        self._call_s, self._call = 0.0, None
        self.passes += 1  # last: whoever reads it finds the pass in all the rest

    def view(self) -> dict:
        return {
            "passes": self.passes, "idle_sleeps": self.idle_sleeps,
            # since the first pass; the stages add up to it less the pass in progress
            "elapsed_s": _between(self.started_t, time.perf_counter()),
            "stage_s": dict(self.stage_s),
            "fetch_s": self.fetch_s, "launch_s": self.launch_s,
            "pass_s": {"boundaries": list(PASS_BOUNDS), "counts": list(self.pass_counts)},
            "longest_pass_by_second": list(self.longest),
        }


class _Admission:
    """Chunked-prefill state for one slot being filled (reference: vLLM
    chunked prefill — bounded prompt work interleaved with decode steps)."""

    def __init__(self, req: _Request, slot: int, one, chunks: list, prefix_m: int):
        self.req = req
        self.slot = slot
        self.one = one  # scratch [L, 1, K, stripe, D] KV being extended
        self.chunks = chunks  # [(tokens_np [1, C], eff_len, start, is_final)]
        self.idx = 0
        self.prefix_m = prefix_m
        # of a model that generates by blocks: the prompt's last tokens, fewer
        # than a block, which stand clean at the front of the first block
        self.tail: list = []


class _Pool:
    """One KV stripe class: ``n_slots`` decode slots of ``stripe_len``
    positions each, with its own compiled decode program. Short requests
    route to short pools so they never pin max_seq_len-sized KV memory."""

    def __init__(self, stripe_len: int, n_slots: int, model_cfg, params):
        import jax

        from ray_tpu.models.llama import init_kv_cache
        from ray_tpu.models.patterned import (
            STATE_LEAVES, reads_blocks, stripe_cache_shapes, writes_rows,
        )
        from ray_tpu.ops.decode_attention import block_size, cache_position_bytes

        self.stripe_len = stripe_len
        self.n_slots = n_slots
        self.latent = bool(model_cfg.kv_latent_rank)
        # > 0: the pool's step is a forward of a block a slot (``programs``'
        # ``block_step``), and ``block`` is what rides on the device between
        # launches as ``dev_tokens`` does for a token a step: each slot's block
        # (``tokens``, ``masked`` [slots, B]), the denoising step within it
        # (``step``) and the request's ``steps`` [slots]
        self.block_length = model_cfg.block_length
        self.block = None
        if self.block_length and stripe_len % self.block_length:
            raise ValueError(f"a stripe of {stripe_len} positions is no whole number of "
                             f"blocks of {self.block_length}")
        device = jax.local_devices()[0]
        before = (device.memory_stats() or {}).get("bytes_in_use")
        self.cache = jax.block_until_ready(init_kv_cache(model_cfg, n_slots, stripe_len))
        after = (device.memory_stats() or {}).get("bytes_in_use")
        # bytes a token of all layers: as the arrays' shapes give them, and as
        # the device holds them (a minor axis narrower than the chip's 128
        # lanes is padded to them); None where the backend reports no memory
        tokens = n_slots * stripe_len
        # every stripe a slot holds: ``k`` and ``v``, and whatever the model's
        # layers keep beside them a token (``models/llama.py init_kv_cache``)
        self.stripes = tuple(stripe_cache_shapes(model_cfg, 1, 1))
        self.kv_bytes_per_token = sum(self.cache[name].nbytes for name in self.stripes) / tokens
        # what a slot holds whatever its length (the state and convolution
        # tails of the layers that keep one, whether such a layer has a stripe
        # of keys and values as well or none): 0 for a model whose slots are
        # stripes alone. ``stateful`` is asked of the leaves, never of a kind
        state_bytes = sum(self.cache[k].nbytes for k in STATE_LEAVES if k in self.cache)
        self.state_bytes_per_slot = state_bytes // n_slots
        self.stateful = state_bytes > 0
        self.kv_bytes_per_token_held = (
            None if before is None or after is None
            else (after - before - state_bytes) / tokens
        )
        self.slots: list[Optional[_Request]] = [None] * n_slots
        self.temps = np.zeros((n_slots,), np.float32)
        self.top_ks = np.full((n_slots,), 50, np.int32)
        self.sampler_dev = None  # the two on the device, until a slot's change (``sampler``)
        self.keys = None  # per-slot PRNG keys, set by the engine loop
        self.adapter_ids = np.zeros((n_slots,), np.int32)
        self.adapter_ids_dev = None
        # device-resident next-token inputs: decode programs chain on these
        # without a host round trip (run-ahead)
        self.dev_tokens = None  # [n_slots] int32 on device
        self.admitting: dict[int, _Admission] = {}
        # launched decode steps whose sampled tokens are still being fetched:
        # (out_dev [K, slots], {slot: _Request} binding snapshot, routing
        # counts or None); a step that a chunk launch carried: ([slots], .., None);
        # a forward of a block a slot, alone or carried: [slots, B + 2]
        self.inflight: "deque" = deque()
        # first tokens from final prefill chunks awaiting host arrival
        self.first_pending: list = []
        self.chunk_rows = 1  # the most rows of one middle-chunk launch (the engine sets it)
        # whether this pool's chunk programs take its decode rows (the engine
        # sets it), and whether a chunk launch of this pass carried its step
        self.carries = False
        self.step_carried = False
        # whether this pool's decode steps read its stripes through the
        # decode kernel: asked once, of the layer that decides it, with the
        # arrays the steps run on
        self.reads_blocks = reads_blocks(
            stripe_len, self.cache["k"], *jax.tree.leaves(params), latent=self.latent
        )
        # whether its steps write their new keys and values through the write
        # kernel (a token a row; a block pool's rows are a block wide), asked
        # the same way
        self.writes_rows = writes_rows(
            self.block_length or 1, False, self.cache["k"], *jax.tree.leaves(params),
            latent=self.latent,
        )
        # what a position of a row holds in a layer of the cache the kernel is
        # handed, and the positions a block of its walk takes of such a cache
        # (``ops/decode_attention.py block_size``; None where the steps keep
        # the einsum): the kernel's own rule, asked with the pool's own arrays
        self.position_bytes = cache_position_bytes(self.cache["k"], self.cache["v"])
        self.decode_block = (
            block_size(stripe_len, self.position_bytes, self.latent) if self.reads_blocks else None
        )

    def sampler(self) -> tuple:
        """The slots' temperatures and top-k on the device: transferred when
        a slot's have changed (``sampler_dev`` dropped), not once a launch."""
        import jax.numpy as jnp

        if self.sampler_dev is None:
            self.sampler_dev = (jnp.asarray(self.temps), jnp.asarray(self.top_ks))
        return self.sampler_dev

    def positions_read(self, lo, hi) -> int:
        """Positions a decode step reads of the stripes of slots bounded
        ``[lo, hi)`` (arrays, an entry a slot) in a layer: the kernel's
        blocks, or whole stripes where the steps keep the einsum."""
        if not self.reads_blocks:
            return self.stripe_len * len(hi)
        from ray_tpu.ops.decode_attention import positions_read

        return int(
            positions_read(lo, hi, self.stripe_len, self.position_bytes, self.latent).sum())


def programs(cfg, decode_steps: int = 1) -> dict:
    """The bodies of an engine's device programs for model ``cfg``, by the
    name each is jitted under (``JaxEngine._compile``; a profile names a
    module ``jit_<name>``): plain functions, so that a test can lower them for
    a described chip without building an engine."""
    import jax
    import jax.numpy as jnp

    from ray_tpu.models.llama import (
        block_forward, block_schedule, block_unmask, decode_step, init_kv_cache, prefill,
    )
    from ray_tpu.models.patterned import (
        LOOP_STATS, moe_stats_names, state_cache_shapes, stripe_cache_shapes,
    )
    from ray_tpu.ops import topk

    # what a slot holds, each leaf with the slot on axis 1: its stripes of keys
    # and values, and for a model with layers that keep a state their states
    # and convolution tails (``STATE_LEAVES``), which are stacked, unstacked,
    # zeroed and copied into a slot with the stripes
    slot_leaves = (*stripe_cache_shapes(cfg, 1, 1), *state_cache_shapes(cfg, 1))

    # one static top-K for the decode program AND the prefill first-token
    # sampler — they must agree or seeded runs diverge at token 2
    K = top_k_static(cfg)

    # a model with routed experts: each program takes a zeroed
    # ``moe_stats`` leaf in with its cache and hands the counts out
    # beside its tokens (``models/llama.py _ride_stats``). A dense
    # model's programs hand out ``None`` there, which is no output. A model
    # whose stack runs several times a token hands out its passes and exits
    # the same way, in a leaf of its own (``models/patterned.py _loop_stats``;
    # it has no routed experts: ``plan``).
    stats_leaf = "moe_stats" if cfg.moe_experts else "loop_stats" if cfg.loop_passes > 1 else None
    n_stats = len(moe_stats_names(cfg)) if cfg.moe_experts else len(LOOP_STATS) + cfg.loop_passes

    def stats_in(cache):
        if stats_leaf is None:
            return cache
        return {**cache, stats_leaf: jnp.zeros((n_stats,), jnp.int32)}

    def candidates(logits):
        """Of ``[..., V]`` fp32 logits the greedy token and the ``K`` largest
        with their indices (``ops/topk.py``: the one-stage ``lax.top_k``'s
        values and indices, from two stages where the vocabulary is wide)."""
        return (jnp.argmax(logits, -1), *topk.top_k(logits, K))

    def draw(greedy, vals, idxs, temp, top_k, key):
        """One row's token from its candidates: greedy where temp<=0, else
        top-k/temperature categorical over the first ``top_k`` of them."""
        rank_ok = jnp.arange(K) < top_k
        scaled = jnp.where(rank_ok, vals / jnp.maximum(temp, 1e-6), -jnp.inf)
        key, sub = jax.random.split(key)
        sampled = idxs[jax.random.categorical(sub, scaled)]
        tok = jnp.where(temp <= 0.0, greedy, sampled).astype(jnp.int32)
        return tok, key

    def sample_row(logits_row, temp, top_k, key):
        """Sample one token from [V] fp32 logits. The ONE sampler — the
        decode program runs it over its rows (``sample_rows``) and the
        prefill first token calls it directly, so seeded runs cannot diverge
        at token 2."""
        return draw(*candidates(logits_row), temp, top_k, key)

    def sample_rows(logits, temps, top_ks, keys):
        """``sample_row`` of each of ``[rows, V]``: the candidates of all rows
        at once (the selection tiles the rows as the chip holds them), then a
        draw a row."""
        return jax.vmap(draw)(*candidates(logits), temps, top_ks, keys)

    def decode_fn(params, cache, tokens, temps, top_ks, keys,
                  loras=None, adapter_ids=None, live=None):
        """Decode + in-program sampling with per-slot PRNG keys: a request's
        key is its own, so what its seed draws does not depend on what else
        is in the batch. Its logits can: on a chip a row's numbers differ in
        the last bit by what shares its launch (the other slots of a step,
        and in a pool whose chunk launches carry the step, a prompt's chunk).
        ``live`` [slots] (a looped model's alone): the slots whose exits count."""
        cache = stats_in(cache)
        if live is not None:
            cache = {**cache, "loop_live": live}
        logits, cache = decode_step(
            params, cache, tokens, cfg,
            loras=loras, adapter_ids=adapter_ids,
        )
        stats = cache.pop(stats_leaf, None)
        with jax.named_scope("sampling"):
            next_tokens, new_keys = sample_rows(logits, temps, top_ks, keys)
        return next_tokens, cache, new_keys, stats

    def sample_riders(logits, rows):
        """The next input tokens and keys of a pool whose decode step rode
        through a chunk program (``rows``: what ``decode_fn`` takes beside the
        cache, and ``live``): each live row sampled by the one ``sample_row``,
        every other row's token and key as they came in. Named as the rows'
        other work is (``models/patterned.py decode_forward``): ``beside`` in
        front of ``sampling``."""
        with jax.named_scope("beside"), jax.named_scope("sampling"):
            next_tokens, new_keys = sample_rows(
                logits, rows["temps"], rows["top_ks"], rows["keys"]
            )
            live = rows["live"]
            return (jnp.where(live, next_tokens, rows["tokens"]),
                    jnp.where(live[:, None], new_keys, rows["keys"]))

    # a model that generates by diffusion over blocks (``cfg.block_length``)
    blocks = cfg.block_length

    def commits(block):
        """The slots whose block is clean: their forward keeps its keys and values."""
        return ~block["masked"].any(axis=1)

    def block_after(logits, block, temps, top_ks, keys, live=None):
        """What a forward of every slot's block does behind its logits
        ([slots * B, V], a row a position), for the step launched alone
        (``block_step``) and for one that rode through a prompt's chunk
        (``ride_block``): a slot whose block was clean committed it and goes
        on to a block of masks; any other unmasks what its step of the
        schedule gives it (``block``: ``_Pool.block``). Every position's token
        goes through the one ``draw``, under a key of its own split off the
        slot's. Returns, a slot, the block after the forward, whether it
        committed and how many positions it unmasked ([slots, B + 2] int32:
        what the host fetches), the next state and the keys. A slot that is
        not ``live`` [slots] keeps its state and its key (its hand-out is
        nobody's: the launch's binding holds no such slot)."""
        masked, commit = block["masked"], commits(block)
        split = jax.vmap(lambda key: jax.random.split(key, blocks + 1))(keys)

        def sample(logits):  # [slots * B, V]: a slot's positions under its temperature
            return jax.vmap(draw)(
                *candidates(logits), jnp.repeat(temps, blocks), jnp.repeat(top_ks, blocks),
                split[:, 1:].reshape(-1, *split.shape[2:]))[0]

        tokens, still, _ = block_unmask(
            logits, block["tokens"], masked,
            block_schedule(block["step"], block["steps"], blocks).astype(jnp.int32), cfg, sample)
        new_keys = split[:, 0]
        with jax.named_scope("sampling"), jax.named_scope("unmask"):
            out = jnp.concatenate([
                tokens, commit[:, None].astype(jnp.int32),
                (masked & ~still).sum(axis=1, dtype=jnp.int32)[:, None],
            ], axis=1)
            fresh = commit[:, None]
            after = dict(
                block,
                tokens=jnp.where(fresh, jnp.int32(cfg.mask_token_id), tokens),
                masked=still | fresh,
                step=jnp.where(commit, 0, block["step"] + 1),
            )
            if live is not None:
                after = {name: jnp.where(live[:, None] if x.ndim > 1 else live, x, block[name])
                         for name, x in after.items()}
                new_keys = jnp.where(live[:, None], new_keys, keys)
        return out, after, new_keys

    def block_step(params, cache, block, temps, top_ks, keys):
        """One forward of every slot's block (``models/llama.py block_step``)
        and what the next forward starts from, with no word from the host
        (``block_after``). Hands out the slots' [slots, B + 2] rows, then the
        cache, the next state, the keys and the routing counts."""
        logits, cache = block_forward(params, stats_in(cache), block["tokens"], commits(block), cfg)
        stats = cache.pop(stats_leaf, None)
        out, block, keys = block_after(logits, block, temps, top_ks, keys)
        return out, cache, block, keys, stats

    def riders(cache, rows):
        """What ``prefill`` takes of the pool's rows (``beside``): the rows of
        a decode step, or of a model that generates by blocks the rows of a
        block step with the slots that commit."""
        if rows is None:
            return None
        if blocks:
            with jax.named_scope("beside"):
                commit = commits(rows["block"])
            return cache, rows["block"]["tokens"], rows["live"], commit
        return cache, rows["tokens"], rows["live"]

    def ride_block(logits, rows):
        """``sample_riders`` of a pool that generates by blocks: what
        ``block_step`` does behind its logits, for the live rows, named as the
        rows' other work is (``beside`` in front of ``sampling/confidence`` and
        ``sampling/unmask``). -> (hand-out [slots, B + 2], next state, keys)"""
        with jax.named_scope("beside"):
            return block_after(logits, rows["block"], rows["temps"], rows["top_ks"],
                               rows["keys"], rows["live"])

    def seed_block(cache, block, slot, length, tokens, masked, steps):
        """Bind ``slot`` to a request that generates by blocks: its cache
        holds ``length`` positions (the prompt's whole blocks, as the chunk
        programs left them), and its first block starts as ``tokens`` [B] (the
        prompt's last tokens in front, masks behind them: ``masked``) at step
        0 of the request's ``steps``."""
        cache = {**cache, "length": cache["length"].at[slot].set(length)}
        new = dict(tokens=tokens, masked=masked, step=jnp.zeros((), jnp.int32), steps=steps)
        return cache, {name: block[name].at[slot].set(new[name]) for name in block}

    def new_block(n_slots: int) -> dict:
        """``_Pool.block`` of a pool with no request: every slot a block of
        masks at step 0 of the model's own steps."""
        return dict(
            tokens=jnp.full((n_slots, blocks), cfg.mask_token_id, jnp.int32),
            masked=jnp.ones((n_slots, blocks), bool),
            step=jnp.zeros((n_slots,), jnp.int32),
            steps=jnp.full((n_slots,), cfg.denoise_steps or blocks, jnp.int32),
        )

    n_steps = max(1, decode_steps)

    def decode_multi(params, cache, tokens, temps, top_ks, keys,
                     loras=None, adapter_ids=None):
        """K decode steps in one program (lax.scan): one host round
        trip per K tokens."""
        def body(carry, _):
            toks, cache, keys = carry
            nt, cache, keys, stats = decode_fn(
                params, cache, toks, temps, top_ks, keys,
                loras=loras, adapter_ids=adapter_ids,
            )
            return (nt, cache, keys), (nt, stats)

        (toks, cache, keys), (out, stats) = jax.lax.scan(
            body, (tokens, cache, keys), None, length=n_steps
        )
        if stats is not None:
            stats = stats.sum(axis=0)
        return out, cache, keys, stats  # out: [K, slots]

    def chunk_mid(params, ones, tokens, lengths, starts, cache=None, rows=None,
                  loras=None, adapter_ids=None):
        """Extend each row's scratch stripe with its prompt's next chunk
        — no LM head (mid-chunks of chunked prefill never need logits).
        ``ones``: a stripe a row; ``tokens`` [rows, C]. A single row's
        stripe is the cache ``prefill`` extends where it lies; several rows'
        are stacked into one (a copy of each) and handed back a row each (a
        second copy; both under ``kv_write``: PERF.md section 6, PR 34). The
        launch's routing counts ride with the first row's.

        With the pool's ``cache`` and its ``rows`` (``tokens``, ``temps``,
        ``top_ks``, ``keys`` as ``decode_fn`` takes them, and ``live`` [slots]:
        the slots that decode in this launch) the launch carries the pool's
        decode step: the rows that decode ride through the same read of the
        weights and banks as the chunk's tokens (``models/patterned.py
        decode_forward``), and the result ends with what ``decode_fn`` hands
        back: ``(stripes, next tokens [slots], cache, keys)``. A launch that
        carries none passes no live row; the counts of all its rows, the
        decode rows' too, ride with the first stripe.

        A pool that generates by blocks hands over its block state where the
        others hand over tokens (``rows["block"]``: ``_Pool.block``), and the
        launch carries one forward of every slot's block: ``(stripes, the
        slots' hand-outs [slots, B + 2], cache, keys, next block state)``, the
        first as ``block_step`` hands them out (``ride_block``)."""
        if len(ones) == 1:
            stripes = ones[0]
        else:
            with jax.named_scope("kv_write"):
                stripes = {
                    k: jnp.concatenate([one[k] for one in ones], axis=0 if k == "length" else 1)
                    for k in (*slot_leaves, "length")
                }
            if stats_leaf:
                stripes[stats_leaf] = ones[0][stats_leaf]
        _, stripes, *rode = prefill(
            params, stripes, tokens, cfg, lengths=lengths, start_pos=starts,
            loras=loras, adapter_ids=adapter_ids, with_logits=False,
            beside=riders(cache, rows),
        )
        if len(ones) == 1:
            out = (stripes,)
        else:
            with jax.named_scope("kv_write"):
                out = tuple(
                    {**one, **{k: stripes[k][:, i:i + 1] for k in slot_leaves},
                     "length": stripes["length"][i:i + 1],
                     **({stats_leaf: stripes[stats_leaf]} if stats_leaf and i == 0 else {})}
                    for i, one in enumerate(ones)
                )
        if rows is None:
            return out
        logits, cache = rode
        if blocks:
            handed, block, new_keys = ride_block(logits, rows)
            return out, handed, cache, new_keys, block
        next_tokens, new_keys = sample_riders(logits, rows)
        return out, next_tokens, cache, new_keys

    def chunk_final(params, cache, one, tokens, length, start, slot,
                    temp, top_k, key, rows=None, loras=None, adapter_ids=None):
        """Last prompt chunk: prefill it, sample the first generated
        token IN-PROGRAM (no host sync on the admission path), and
        copy the finished stripe into the pool slot. One prompt a launch: two
        prompts' final chunks are not rows of one program (PERF.md section 6,
        PR 34).

        With the pool's ``rows`` (as ``chunk_mid`` takes them) the launch
        carries the pool's decode step as well: the live rows decode on
        ``cache`` beside the chunk's tokens, and the result ends with ``(..,
        next tokens [slots], keys)``, in which ``slot`` holds the request's
        first token and its key (the tokens are also what the step's fetch
        reads, so no later program may overwrite them): the slot this chunk
        activates is no decode row of the same launch (``live[slot]`` is
        false), and whatever a dead row left in its stripe the copy
        overwrites; behind a carried step the copy is a plain update (below).
        A row's logits can then differ in the last bit by what shares its
        launch; a pool whose answers have to be the same to the token
        whatever runs beside them (a latent pool: ``JaxEngine.__init__``)
        passes no rows and runs the chunk alone.

        In a pool that generates by blocks the rows are a forward of every
        slot's block (``chunk_mid``), and the result ends with ``(..,
        hand-outs [slots, B + 2], keys, next block state)``: ``slot``'s key is
        the request's, and its block is seeded after the launch
        (``seed_block``) on the cache and the state handed back here."""
        mid_stats = one.get(stats_leaf)  # the prompt's middle chunks'
        last_logits, one, *rode = prefill(
            params, one, tokens, cfg, lengths=length, start_pos=start,
            loras=loras, adapter_ids=adapter_ids, with_logits=not blocks,
            beside=riders(cache, rows),
        )
        if rode:
            logits, cache = rode
        stats = one.pop(stats_leaf, None)
        if stats is not None:  # rows: chunk_mid, chunk_final
            stats = jnp.stack([mid_stats, stats - mid_stats])
        total = start[0] + length[0]
        with jax.named_scope("kv_write"):  # (a scatter keeps what it drops: two copies of a cache a step wrote)
            put = (lambda x, row: x.at[:, slot].set(row)) if rows is None else (
                lambda x, row: jax.lax.dynamic_update_index_in_dim(x, row, slot, 1))
            cache = {**{k: put(cache[k], one[k][:, 0]) for k in slot_leaves},
                     "length": cache["length"].at[slot].set(total)}
        if blocks:
            # nothing is sampled from a prompt: its whole blocks are kept, and
            # the slot's first block is seeded by ``seed_block``
            out = (jnp.zeros((), jnp.int32), key, cache, one, stats)
            if rows is None:
                return out
            handed, block, new_keys = ride_block(logits, rows)
            return (*out, handed, new_keys.at[slot].set(key), block)
        with jax.named_scope("sampling"):
            tok, new_key = sample_row(last_logits[0], temp, top_k, key)
        if rows is None:
            return tok, new_key, cache, one, stats
        next_tokens, new_keys = sample_riders(logits, rows)
        return (tok, new_key, cache, one, stats,
                next_tokens.at[slot].set(tok), new_keys.at[slot].set(new_key))

    def new_stripe(stripe_len):
        """A zeroed scratch stripe (a program, so that it can be placed:
        ``JaxEngine._compile``)."""
        one = init_kv_cache(cfg, 1, stripe_len)
        if stats_leaf:  # the prompt's chunks add their counts up in here
            one[stats_leaf] = jnp.zeros((n_stats,), jnp.int32)
        return one

    @jax.named_scope("prefix_seed")
    def seed_prefix(one, pk, pv, state=None, more=None):
        """Copy a cached prefix KV [L, K, m, D] into the scratch stripe (``state``:
        below; ``more``: the same cut of every further stripe the cache holds, by name)."""
        m = pk.shape[2]
        return {
            **one, **(state or {}),
            "k": one["k"].at[:, 0, :, :m].set(pk),
            "v": one["v"].at[:, 0, :, :m].set(pv),
            **{name: one[name].at[:, 0, :, :m].set(cut) for name, cut in (more or {}).items()},
        }

    # A pool whose slots hold a state stores a prefix as a snapshot of the
    # scratch stripe a prompt's final chunk handed back: the state leaves are
    # that stripe's own arrays (nothing is copied), and of its keys and values
    # the first ``positions`` are cut out, a length of a few (static: one
    # program a length, ``JaxEngine._snapshot_lengths``). ``seed_prefix`` with
    # ``state`` starts a fresh stripe where the stored prompt ended: its state
    # leaves are the entry's, as they are held (float32 the state); the
    # prompt's tail then runs from the stored prompt's own length (what lies
    # between it and the entry's ``m`` the tail overwrites or no query sees).
    @jax.named_scope("prefix_store")
    def store_snapshot(k, v, positions: int):
        return k[:, 0, :, :positions], v[:, 0, :, :positions]

    return dict(decode_fn=decode_fn, decode_multi=decode_multi, chunk_mid=chunk_mid,
                chunk_final=chunk_final, new_stripe=new_stripe, seed_prefix=seed_prefix,
                store_snapshot=store_snapshot,
                **(dict(block_step=block_step, seed_block=seed_block, new_block=new_block)
                   if blocks else {}))


def top_k_static(cfg) -> int:
    return min(64, cfg.vocab_size)


class JaxEngine:
    def __init__(self, config: LLMConfig, mesh=None):
        import jax

        self._t_init = time.time()
        self.config = config
        self.tokenizer = get_tokenizer(config.model.tokenizer)
        self._mesh = mesh
        self._n = dict.fromkeys(COUNTERS, 0)
        # _n is the engine thread's, but for what callers' threads count
        # (submitted, failed at submit or at loop exit) and for closing a
        # request, which either side may do: those hold this lock
        self._count_lock = threading.Lock()
        self._mirrored: dict = {}
        # this engine's series of the process's latency histograms
        self._tag = {"engine": uuid.uuid4().hex[:8]}
        self._loop_first_pass_t: Optional[float] = None
        self._loop = _LoopClock()
        # the constructor's phases, seconds (``get_stats()["init"]``), and of
        # ``_warm_programs`` the seconds by program
        self._init_s: dict = {}
        self._warm_s: dict = {}
        self._warm_phases_s: dict = {}
        self._init_phase(self._build_model)
        self._init_phase(self._build_pools)
        # the most rows a pool's middle-chunk program runs: what is alive in
        # a pass decides how many it has, up to a pool's admissions (and the
        # rows ``prefill`` writes as blocks). A latent pool's chunks stay one
        # to a launch: their attention walks key blocks as far as the
        # furthest row has cached with every row, in plain XLA, and on a v5e
        # rows of long documents ran longer in one launch than one after
        # another (PERF.md section 6, PR 34)
        rows = max(1, min(config.engine.max_concurrent_admissions, CHUNK_ROWS_MAX))
        # A pool's chunk programs take its decode rows, so that a chunk launch
        # can carry the pool's decode step (``_advance_admissions``): one form
        # of each program a pool, the rows an input. Decided here, once, from
        # what the pool and the engine are, whatever the stack (layers alike
        # under one loop or several traced bodies, with or without a state a
        # slot: ``models/patterned.py decode_forward`` walks any of them once
        # for a chunk's rows and the pool's):
        # - not a latent pool: a row's logits differ in the last bit by what
        #   shares its matmuls (PERF.md section 6, PR 34), and this pool's
        #   answers are held to be the same to the token for a prompt seeded
        #   from the prefix store and computed (a hit and a miss sent at once
        #   would decode beside different chunks); its launches stay the
        #   chunk's alone until that comparison allows a rounding (ROADMAP D12);
        # - not with adapters loaded (a row's adapter is indexed by row of a
        #   batch), ``decode_steps`` over 1 (a carried step is one step) or
        #   over a mesh.
        # A pool whose step is a forward of a block a slot carries under the
        # same conditions (it is never latent): its chunk launch runs one
        # forward of every slot's block beside the chunk's tokens, the rows a
        # block wide where another pool's are one token, so a prompt's read of
        # the expert banks serves the slots' blocks too (SDAR's 128 banks of
        # six layers, 7.25 GB a read, for 64 slots that waited behind it until
        # PR 56). Its four limits stand 1.4 to 4.3 times over the cell's
        # readings, so a row's last bit may follow what shares its launch; the
        # latent pool's comparison is to the token, which is why it still
        # does not.
        # Until PR 49 a stack of several traced bodies (Laguna's five,
        # Nemotron's eleven) did not carry either: a form that also holds the
        # decode step is a decode program's worth of tracing and lowering
        # more, which every start paid in Python for each form, compile cache
        # or not. A start now restores each form as an executable with no
        # trace and no lowering (``_launch``, ``_private/program_store.py``,
        # PR 46), so a carrying form costs a warm start the larger file and a
        # checkout's first start one compile (PERF.md section 6, PR 49).
        carries = (self.loras is None and config.engine.decode_steps <= 1
                   and not self._spans_devices())
        for pool in self._pools:
            pool.chunk_rows = 1 if pool.latent else rows
            pool.carries = carries and not pool.latent
        self._init_phase(self._compile)
        self._init_phase(self._warm_programs)
        self._waiting: "queue.Queue[_Request]" = queue.Queue()
        self._backlog: list[_Request] = []  # engine-thread-owned FIFO
        self._stop = threading.Event()
        # prefix cache: sha1(prompt[:bucket]) -> {k, v} device stripes
        # (bucket-aligned lengths only, so jit specializations stay bounded);
        # in a pool that keeps a state a slot sha1(prompt) -> a snapshot of
        # the slot behind the prompt (``_snapshot_store``)
        from collections import OrderedDict

        self._prefix_cache: "OrderedDict[bytes, dict]" = OrderedDict()
        self._prefix_bytes = 0
        self._prefix_hits = 0
        self._prefix_misses = 0
        self._thread = threading.Thread(
            target=self._engine_loop, daemon=True, name="llm-engine"
        )
        self._thread.start()

    def _spans_devices(self) -> bool:
        return self._mesh is not None and self._mesh.size > 1

    def _held(self, tree):
        """``tree``'s arrays committed where they lie (no copy), on an engine
        of one device; over a mesh they are left for the programs to place."""
        import jax

        if self._spans_devices():
            return tree
        return jax.tree.map(
            lambda x: x if x.committed else jax.device_put(x, x.sharding), tree)

    def _init_phase(self, phase: Callable[[], None]) -> None:
        t = time.perf_counter()
        phase()
        self._init_s[phase.__name__.lstrip("_") + "_s"] = time.perf_counter() - t

    def _build_pools(self):
        ec = self.config.engine
        buckets = tuple(ec.seq_len_buckets) or (ec.max_seq_len,)
        if sorted(buckets)[-1] != ec.max_seq_len:
            raise ValueError(
                f"seq_len_buckets must end at max_seq_len={ec.max_seq_len}"
            )
        if ec.seqs_per_bucket:
            counts = tuple(ec.seqs_per_bucket)
            if len(counts) != len(buckets) or sum(counts) != ec.max_num_seqs:
                raise ValueError(
                    "seqs_per_bucket must parallel seq_len_buckets and sum "
                    "to max_num_seqs"
                )
        else:
            base = ec.max_num_seqs // len(buckets)
            counts = list(
                base + (1 if i < ec.max_num_seqs % len(buckets) else 0)
                for i in range(len(buckets))
            )
            # the max_seq_len class must always exist: without it, long
            # requests silently truncate to a shorter stripe
            ordered = sorted(range(len(buckets)), key=lambda i: buckets[i])
            if counts[ordered[-1]] == 0:
                donor = max(ordered, key=lambda i: counts[i])
                counts[donor] -= 1
                counts[ordered[-1]] = 1
        if dict(zip(buckets, counts)).get(ec.max_seq_len, 0) <= 0:
            raise ValueError(
                "seqs_per_bucket must give the max_seq_len bucket at least "
                "one slot (long requests would silently truncate)"
            )
        self._pools = [
            _Pool(b, n, self.model_cfg, self.params)
            for b, n in sorted(zip(buckets, counts))
            if n > 0
        ]

    # -- model setup --------------------------------------------------------

    @property
    def params(self):
        return self._params

    @params.setter
    def params(self, tree):
        """Whatever tree is assigned (``init_params``', a restored one, one
        made outside; ``None`` to drop the weights first) has the leaves
        ``models/llama.py serving_layouts`` names relaid on the device once,
        before a program sees them. The caller's arrays are copied, not
        donated: a tree shared with the engine stays whole in the caller's
        hands. Names, shapes, dtypes, shardings and values stay, and every
        other leaf is the caller's own buffer, committed where it lies; a
        program is compiled for the layout and the kind of argument it is
        handed, so a swap compiles nothing. ``get_stats()["params_relaid"]`` counts what is held so,
        from the arrays themselves."""
        import jax
        from jax.experimental.layout import Format, Layout

        from ray_tpu._private import jax_cache
        from ray_tpu.models.llama import param_shardings, serving_layouts

        self._params = None  # the old tree goes first: two do not fit
        rule = serving_layouts(tree or {})
        due = [k for k, order in rule.items() if _order_of(tree[k]) != order]
        if due:
            tree = dict(tree)
            with jax_cache.bypassed():  # read back from it, a relayout is none
                for name in due:  # a leaf at a time: one leaf's copy is the peak
                    x = tree[name]
                    if not isinstance(x, jax.Array):  # a restored leaf is the host's
                        x = jax.device_put(x, self._mesh and param_shardings(
                            self.model_cfg, self._mesh)[name])
                    tree[name] = jax.device_put(
                        x, Format(Layout(major_to_minor=rule[name]), x.sharding)
                    )
        if tree:
            # every leaf committed to where it lies (no copy): a program is
            # compiled for the kind of argument it is handed, and a tree made
            # eagerly and one made by a program with ``out_shardings`` would
            # each compile the whole set once
            tree = {
                k: x if not isinstance(x, jax.Array) or x.committed
                else jax.device_put(x, x.sharding)
                for k, x in tree.items()
            }
        self._params = tree
        # read back from the arrays: what is held, not what was asked
        relaid = [tree[k] for k, order in rule.items() if _order_of(tree[k]) == order]
        if len(relaid) != len(rule):
            logger.warning(
                "%d of %d parameter leaves did not take their device layout: "
                "the programs copy a layer's slice of them before they multiply",
                len(rule) - len(relaid), len(rule),
            )
        self._params_relaid = {"leaves": len(relaid), "bytes": sum(x.nbytes for x in relaid)}
        for k, v in self._params_relaid.items():
            _metric("Gauge", "params_relaid_" + k).set(v)

    def _build_model(self):
        import jax

        from ray_tpu.models.llama import init_params
        from ray_tpu.train.checkpoint import restore_pytree

        from ray_tpu.llm.config import resolve_llama_config

        mc, ec = self.config.model, self.config.engine
        self.model_cfg = resolve_llama_config(
            mc, ec, min_vocab=self.tokenizer.vocab_size
        )
        sharded = ec.tensor_parallel_degree > 1 or ec.sequence_parallel_degree > 1
        if sharded or (self._mesh is not None and self._mesh.size > 1):
            from ray_tpu.llm.config import (
                refuse_blocks, refuse_latent, refuse_looped, refuse_stateful,
            )

            refuse_latent(self.model_cfg, "llm/engine.py over a mesh")
            refuse_stateful(self.model_cfg, "llm/engine.py over a mesh")
            refuse_blocks(self.model_cfg, "llm/engine.py over a mesh")
            refuse_looped(self.model_cfg, "llm/engine.py over a mesh")
        if self.model_cfg.loop_passes > LOOP_PASSES_MAX:
            raise ValueError(f"loop_passes={self.model_cfg.loop_passes}: the engine counts a "
                             f"looped model's exits over at most {LOOP_PASSES_MAX} passes")
        if sharded:
            from ray_tpu.parallel.mesh import MeshSpec, build_mesh

            if self._mesh is None:
                self._mesh = build_mesh(
                    MeshSpec(
                        tp=ec.tensor_parallel_degree,
                        sp=ec.sequence_parallel_degree,
                    )
                )
        if mc.checkpoint_path:
            self.params = restore_pytree(mc.checkpoint_path)
        else:
            self.params = init_params(
                jax.random.PRNGKey(mc.seed), self.model_cfg, mesh=self._mesh
            )
        # multi-LoRA: stacked adapters (slot 0 = base/zero), name registry,
        # per-decode-slot adapter index (kept per pool)
        self.loras = None
        self._lora_ids: dict[str, int] = {}
        if ec.max_loras > 0:
            from ray_tpu.models.llama import init_lora_stack

            if self.model_cfg.layer_types:
                raise ValueError("LoRA adapters need layers that are alike")

            self.loras = init_lora_stack(
                self.model_cfg, ec.max_loras, ec.lora_rank
            )
        if self.model_cfg.block_length and ec.decode_steps > 1:
            raise ValueError("decode_steps > 1: a model that generates by blocks runs a forward "
                             "of a block a launch (llm/engine.py programs block_step)")
        # under the block mask a query reads its whole block: a chunk or a
        # stored prefix that ended inside one would have its last queries read
        # keys nobody wrote yet (a former tenant's), and keep what came of it
        cut = [n for n in (ec.prefill_chunk, *ec.prefill_buckets)
               if self.model_cfg.block_length and n % self.model_cfg.block_length]
        if cut:
            raise ValueError(
                f"prefill_chunk and prefill_buckets {cut}: no whole number of blocks of "
                f"{self.model_cfg.block_length} (a model that generates by blocks)")

    def _compile(self):
        import jax

        cfg = self.model_cfg
        self._top_k_static = top_k_static(cfg)
        self._decode_n_steps = max(1, self.config.engine.decode_steps)
        fns = programs(cfg, self._decode_n_steps)
        self._decode_jit = jax.jit(fns["decode_fn"], donate_argnums=_DONATED["_decode_jit"])
        self._decode_multi_jit = jax.jit(
            fns["decode_multi"], donate_argnums=_DONATED["_decode_multi_jit"])
        # (5: the pool's cache, where a launch takes the pool's decode rows)
        self._chunk_mid_jit = jax.jit(fns["chunk_mid"], donate_argnums=_DONATED["_chunk_mid_jit"])
        # donate the scratch stripe too and hand it back (the caller drops
        # it): a stripe the program may not overwrite is copied before the
        # chunk is written into it, and the v5e compiler then moved a whole
        # 33 MB stripe between memory spaces once a layer (0.64 ms a run at
        # 7B widths; PERF.md section 6, PR 27)
        self._chunk_final_jit = jax.jit(
            fns["chunk_final"], donate_argnums=_DONATED["_chunk_final_jit"])
        from jax.sharding import NamedSharding, PartitionSpec, SingleDeviceSharding

        # a scratch stripe is committed where it is made: a chunk program then
        # sees the same kind of argument in a prompt's first chunk as behind
        # another, and in any mix of the two among its rows (an uncommitted
        # one was a second compilation of each program)
        self._new_stripe_jit = jax.jit(
            fns["new_stripe"], static_argnums=(0,),
            out_shardings=(
                NamedSharding(self._mesh, PartitionSpec())
                if self._spans_devices()
                else SingleDeviceSharding(jax.local_devices()[0])
            ),
        )
        self._seed_prefix_jit = jax.jit(
            fns["seed_prefix"], donate_argnums=_DONATED["_seed_prefix_jit"])
        if cfg.block_length:  # the step of a model that generates by blocks, and a slot's binding
            self._block_step_jit = jax.jit(
                fns["block_step"], donate_argnums=_DONATED["_block_step_jit"])
            self._seed_block_jit = jax.jit(
                fns["seed_block"], donate_argnums=_DONATED["_seed_block_jit"])
            self._new_block = fns["new_block"]
        from ray_tpu.models.patterned import state_cache_shapes

        self._state_leaves = tuple(state_cache_shapes(cfg, 1))
        self._store_snapshot_jit = jax.jit(fns["store_snapshot"], static_argnums=(2,))
        # tiny device-side updates that keep the decode chain host-free
        self._set_tok_jit = jax.jit(
            lambda toks, slot, tok: toks.at[slot].set(tok), donate_argnums=(0,)
        )
        self._set_key_jit = jax.jit(
            lambda keys, slot, key: keys.at[slot].set(key), donate_argnums=(0,)
        )
        self._rng_key = jax.random.PRNGKey(self.config.model.seed)
        # The forms of those programs the loop launches (``_launch``): an engine
        # of one device runs each as an executable, restored from
        # ``_private/program_store.py`` or lowered and compiled ahead of time
        # and offered to it for the next start (whether anything is kept is
        # the store's to say). Over a mesh the ``jit``s above run as they
        # are: an executable is loaded for one device, no cell runs a mesh
        # and nothing could check one there (ROADMAP D17).
        self._device = jax.local_devices()[0]
        self._programs: dict = {}
        self._program_counts = {"restored": 0, "compiled": 0, "fallback": 0}
        # what shaped the programs beside their arguments
        self._program_context = {
            "model": dataclasses.asdict(cfg), "engine": dataclasses.asdict(self.config.engine),
        }

    def _launch(self, form: tuple, jit_name: str, *args, static: tuple = (), **kwargs):
        """Run the program ``form`` names (the program, its pool's stripe
        length and whatever else tells two compilations of it apart; its
        static arguments, ``static``, among them: an executable takes none)
        on ``args``: the form's executable, loaded or compiled on its first
        launch (``_program``). Two calls of the ``jit`` stay, each the only
        way on its input: an engine that spans devices has no executables
        (``_compile``), and an executable that refuses its arguments (it does
        so before anything runs or is donated) gives way to the ``jit`` for
        good."""
        if self._spans_devices():
            return getattr(self, jit_name)(*args, *static, **kwargs)
        program = self._programs.get(form)
        if program is None:
            program = self._program(form, jit_name, args, static, kwargs)
        try:
            return program(*args, **kwargs)
        except (TypeError, ValueError) as e:
            import jax

            if not isinstance(program, jax.stages.Compiled):
                raise
            logger.warning("program %s refused its arguments and gives way to its jit: %s",
                           form, str(e).splitlines()[0])
            self._program_counts["fallback"] += 1
            jitted = getattr(self, jit_name)
            self._programs[form] = program = lambda *a, **kw: jitted(*a, *static, **kw)
            return program(*args, **kwargs)

    def _program(self, form: tuple, jit_name: str, args: tuple, static: tuple, kwargs: dict):
        """The executable of ``form`` for arguments like ``args``: the store's,
        found by a key that nothing is traced for, else lowered and compiled
        here (through the compile cache) and offered to the store for the
        next start. A store with nowhere to keep programs (no cache
        directory, or inside ``jax_cache.bypassed()``) finds none and keeps
        none: the executable runs all the same. A file that cannot be loaded
        counts as a fallback and is written anew."""
        name = ":".join(map(str, form))
        t = time.perf_counter()
        key = program_store.key(name, args, kwargs, _DONATED[jit_name], self._program_context)
        outcome, program = "compiled", None
        try:
            program = program_store.load(name, key, self._device)
        except Exception as e:  # noqa: BLE001 - a file cut short, another version's pickle
            logger.warning("program %s: the kept file does not load (%s: %s); compiling",
                           name, type(e).__name__, e)
            outcome = "fallback"
        _book_phase("restore_s", time.perf_counter() - t)
        if program is None:
            program = getattr(self, jit_name).lower(*args, *static, **kwargs).compile()
            program_store.save(name, key, program)
        else:
            outcome = "restored"
        self._program_counts[outcome] += 1
        self._programs[form] = program
        return program

    def _new_stripe(self, stripe_len: int):
        return self._launch(("new_stripe", stripe_len), "_new_stripe_jit", static=(stripe_len,))

    def _seed_prefix(self, one: dict, k, v, state: Optional[dict] = None,
                     more: Optional[dict] = None):
        """``one`` seeded with a stored prefix's keys and values (of a
        snapshot, its ``state`` leaves too; of a cache with further stripes,
        ``more``: their cuts by name)."""
        form = ("seed_prefix", one["k"].shape[3], k.shape[2], state is not None)
        return self._launch(form, "_seed_prefix_jit", one, k, v,
                            *(() if state is None else (state,)), **({"more": more} if more else {}))

    def _store_snapshot(self, one: dict, positions: int):
        return self._launch(("store_snapshot", one["k"].shape[3], positions),
                            "_store_snapshot_jit", one["k"], one["v"], static=(positions,))

    def _chunk_widths(self, pool: _Pool) -> tuple[Optional[int], list[int]]:
        """The chunk programs a pool's admissions can ask for: the middle
        chunk's width (None where no prompt has one) and the final chunk's
        widths, as ``_start_admission`` plans them."""
        chunk = self.config.engine.prefill_chunk
        longest = pool.stripe_len - 1  # a prompt leaves room for one token
        piece = min(chunk, longest) if chunk else longest
        reach = [b for b in sorted(self.config.engine.prefill_buckets) if b < piece] + [piece]
        finals = sorted({min(self._bucket(n), pool.stripe_len) for n in reach})
        return (chunk if 0 < chunk < longest else None), finals

    def _chunk_walks(self, pool: _Pool) -> dict:
        """width -> kind -> ``"kernel"`` or ``"einsum"``: which walk each of a
        latent pool's chunk programs was built with (``models/patterned.py
        chunk_walks``, the one place that decides it; a chunk runs on a stripe
        of the pool's length, placed as the pool's cache is)."""
        import jax

        from ray_tpu.models.patterned import chunk_walks

        if not pool.latent:
            return {}
        mid, finals = self._chunk_widths(pool)
        return {str(width): chunk_walks(self.model_cfg, pool.stripe_len, width, pool.cache["k"],
                                        *jax.tree.leaves(self.params))
                for width in sorted({*finals, *([mid] if mid else [])})}

    def _warm_programs(self) -> None:
        """``_warm_pass``, and where the compile cache is in use what keeps a
        start that compiled its forms and one that restored them alike to
        everything compiled after them. The compile cache's key holds names and source
        lines (``jax_cache.configure``), and a function that JAX traces once
        and reuses (``jnp.take``, the samplers' helpers: every inner ``jit``)
        keeps the lines of the call site that traced it first. A start that
        traced its forms has left such functions behind with the forms' lines,
        a start that restored has not, so a program compiled later (a
        deployment's next engine; the benchmark's weights and reference) got
        another key in the first restored start and was compiled again: 20-36
        s of that start's set-up on a v5e (PERF.md section 6, PR 46). So both
        kinds of start drop JAX's trace caches before the pass, and one that
        compiled drops them again and runs the pass once more, on its
        executables: after either, the caches hold what one pass over
        executables leaves. Whether a compile cache is there for a key to
        matter to is ``jax_cache``'s to say (``forget_traces``): without one
        every start compiles and one pass is all, as it is over a mesh, whose
        ``jit``s no start restores."""
        from ray_tpu._private import jax_cache

        mark = time.perf_counter()  # the dropping is booked to the pass's first program
        dropped = not self._spans_devices() and jax_cache.forget_traces()
        self._warm_pass(mark)
        if dropped and (self._program_counts["compiled"] or self._program_counts["fallback"]):
            mark = time.perf_counter()
            jax_cache.forget_traces()
            self._warm_pass(mark)

    def _warm_pass(self, mark: float) -> None:
        """Run every program the loop can launch once, on throwaway rows,
        before the loop takes requests: a pool at a time, the middle chunk at
        every row count, each final width, the prefix store's cuts and seeds,
        and the decode program. Running is what fills a ``jit``'s own cache,
        so nothing is left for the first burst of requests to compile
        (warm-up traffic that sends a request at a time never reaches a
        program of two rows, and a window that compiles is not measured). A
        checkout's first start compiles them all here
        (``LLMConfig.compile_budget_s``); later starts restore them
        (``_launch``) or fetch them from the compile cache. The rows write one token at position 0 of slot 0,
        which holds no request and which an admission overwrites whole; a
        pool that ``carries`` hands its chunk programs its decode rows as the
        loop does, none of them live. Each program is waited for where it was
        run, so that ``_warm_s`` holds the seconds by program since ``mark``
        (a pool's own set-up goes to its first)."""
        import jax
        import jax.numpy as jnp

        _listen_to_jax()
        _phases.into, _phases.traced = phases, traced = {}, []

        def book(program: str, out) -> None:
            nonlocal mark
            jax.block_until_ready(out)
            now = time.perf_counter()
            self._warm_s[program] = self._warm_s.get(program, 0.0) + now - mark
            mark = now
            by_phase = self._warm_phases_s.setdefault(program, {})
            for phase, seconds in phases.items():
                by_phase[phase] = by_phase.get(phase, 0.0) + seconds
            phases.clear()
            traced.clear()

        # an executable takes an array committed or not alike, so its one run
        # is every kind's; a ``jit`` compiles once for each kind it is handed
        kinds = 2 if self._spans_devices() else 1
        rng_key = self._rng_key
        jax.random.PRNGKey(0)  # a seeded request's key is a program too
        for i, pool in enumerate(self._pools):
            # what every program of the pool takes and hands back, of the kind
            # a program hands it back: the first run of each is then the form
            # the loop runs (an eager array is not committed, a program's
            # result is, and each kind of argument is a compilation)
            pool.cache = self._held(pool.cache)
            pool.keys = self._held(jax.random.split(
                jax.random.PRNGKey(self.config.model.seed ^ (0x5EED + i)),
                pool.n_slots,
            ))
            pool.dev_tokens = self._held(jnp.zeros((pool.n_slots,), jnp.int32))
            if pool.block_length:
                pool.block = self._held(self._new_block(pool.n_slots))
            self._sync_adapter_ids(pool)
            mid, finals = self._chunk_widths(pool)
            stripe = pool.stripe_len

            def throwaway(rows: int) -> dict:  # rows of one token at position 0
                return dict(
                    ones=tuple(self._new_stripe(stripe) for _ in range(rows)),  # noqa: B023
                    toks=np.zeros((rows, mid), np.int32), lens=[1] * rows,  # noqa: B023
                    starts=[0] * rows, adapters=[0] * rows, pool=pool,  # noqa: B023
                )

            for rows in range(1, pool.chunk_rows + 1 if mid else 1):
                args = throwaway(rows)
                for _ in range(kinds):  # fresh stripes, then a chunk program's own
                    args["ones"] = self._run_chunk_mid(**args)
                book(f"chunk_mid:rows={rows}", args["ones"])
            for width in finals:
                # a first chunk's stripe is fresh, a later one's comes out of
                # a chunk program: both kinds of argument
                stripes = [self._new_stripe(stripe)]
                if mid and kinds == 2:
                    stripes += self._run_chunk_mid(**throwaway(1))
                for one in stripes:
                    self._run_chunk_final(
                        pool, one, np.zeros((1, width), np.int32), 1, 0, 0, 0.0, 1, None, 0)
                book(f"chunk_final:width={width}", pool.cache)
            if self.config.engine.enable_prefix_caching and pool.stateful:
                # a snapshot's cut at each length, and the seed from each
                for m in self._snapshot_lengths(pool):
                    one = self._new_stripe(stripe)
                    k, v = self._store_snapshot(one, m)
                    state = {name: one[name] for name in self._state_leaves}
                    book("snapshot", self._seed_prefix(self._new_stripe(stripe), k, v, state))
            elif self.config.engine.enable_prefix_caching:
                # the store's cut of a slot at each bucket, and the program
                # that seeds a stripe with one
                for b in self.config.engine.prefill_buckets:
                    if b < pool.stripe_len:
                        book("seed_prefix", self._seed_prefix(
                            self._new_stripe(stripe), **self._prefix_cut(pool, 0, b)))
            if pool.block_length:
                # (with a key, as a prompt shorter than a block binds its slot:
                # no final chunk has set one)
                self._bind_block(pool, 0, SamplingParams(), 0, [], self._request_key(None))
                book("seed_block", pool.block)
                self._block_step(pool)
                book("block_step", (pool.cache, pool.block))
                continue
            for _ in range(kinds):  # the cache, keys and tokens as a chunk left them, then as a step did
                out, pool.cache, pool.keys, _ = self._decode(
                    pool, pool.dev_tokens, *pool.sampler(), pool.keys,
                )
                pool.dev_tokens = out[-1]
            book("decode", (pool.cache, pool.dev_tokens))
        self._rng_key = rng_key
        _phases.into = None

    def _decode(self, pool: _Pool, tokens, temps, top_ks, keys):
        """Returns ([K, slots] tokens, cache, keys, routing counts or None)
        — K = decode_steps."""
        # (a no-LoRA configuration's program has no adapter arguments)
        adapters = {} if self.loras is None else dict(
            loras=self.loras, adapter_ids=pool.adapter_ids_dev)
        if self.model_cfg.loop_passes > 1 and self._decode_n_steps == 1:
            import jax.numpy as jnp

            # a free slot's row picks a pass as well: not one to count
            adapters["live"] = jnp.asarray([req is not None for req in pool.slots])
        out, cache, keys, stats = self._launch(
            ("decode", pool.stripe_len),
            "_decode_multi_jit" if self._decode_n_steps > 1 else "_decode_jit",
            self.params, pool.cache, tokens, temps, top_ks, keys, **adapters)
        if self._decode_n_steps == 1:
            out = out[None]  # unify to [K, slots]
        return out, cache, keys, stats

    def _block_step(self, pool: _Pool):
        """One forward of every slot's block in ``pool``, chained on the block
        state the last one left on the device. Returns ([slots, B + 2]: a
        slot's block after the forward, whether it committed, the positions
        it unmasked; the routing counts), both still on the device."""
        out, pool.cache, pool.block, pool.keys, stats = self._launch(
            ("block_step", pool.stripe_len), "_block_step_jit",
            self.params, pool.cache, pool.block, *pool.sampler(), pool.keys)
        return out, stats

    def _bind_block(self, pool: _Pool, slot: int, params: SamplingParams, length: int,
                    tail: list, key=None) -> None:
        """``slot`` of a pool that generates by blocks starts a request's
        first block behind ``length`` cached positions: the prompt's ``tail``
        clean in front, masks behind it, the request's denoising steps (the
        model's own where it names none), and with ``key`` the slot's key (a
        final chunk's launch has set it otherwise)."""
        import jax.numpy as jnp

        cfg, B = self.model_cfg, pool.block_length
        tokens = np.full((B,), cfg.mask_token_id, np.int32)
        tokens[:len(tail)] = tail
        steps = params.denoise_steps or cfg.denoise_steps or B
        slot_dev = jnp.int32(slot)
        if key is not None:
            pool.keys = self._set_key_jit(pool.keys, slot_dev, key)
        pool.cache, pool.block = self._launch(
            ("seed_block", pool.stripe_len), "_seed_block_jit", pool.cache, pool.block,
            slot_dev, jnp.int32(length), jnp.asarray(tokens), jnp.asarray(np.arange(B) >= len(tail)),
            jnp.int32(min(max(1, steps), B)))

    def _lora_kw(self, adapter_ids: list) -> dict:
        """A chunk program's adapter arguments, an id a row (none in a
        no-LoRA configuration: the compiled program has no adapter args)."""
        import jax.numpy as jnp

        if self.loras is None:
            return {}
        return dict(loras=self.loras, adapter_ids=jnp.asarray(adapter_ids, jnp.int32))

    def _sync_adapter_ids(self, pool: _Pool):
        if self.loras is not None:
            import jax.numpy as jnp

            pool.adapter_ids_dev = jnp.asarray(pool.adapter_ids)

    # -- prefix cache --------------------------------------------------------

    def _prefix_key(self, ids: list[int], m: int) -> bytes:
        import hashlib

        return hashlib.sha1(
            np.asarray(ids[:m], np.int32).tobytes()
        ).digest()

    def _prefix_lookup(self, ids: list[int], stripe: int):
        """Longest bucket-aligned cached prefix strictly shorter than the
        prompt (>=1 suffix token must remain to produce last-logits); in a
        pool that keeps a state a slot, the longest stored prompt of any
        length (``_snapshot_lookup``). ``stripe``: the length of the stripe
        to be seeded. Returns (entry, tokens, key)."""
        if not self.config.engine.enable_prefix_caching:
            return None, 0, None
        if self._pools[0].stateful:
            return self._snapshot_lookup(ids, stripe)
        for b in sorted(self.config.engine.prefill_buckets, reverse=True):
            if b >= len(ids):
                continue
            key = self._prefix_key(ids, b)
            entry = self._prefix_cache.get(key)
            if entry is not None:
                self._prefix_cache.move_to_end(key)
                self._prefix_hits += 1
                return entry, b, key
        self._prefix_misses += 1
        return None, 0, None

    def _prefix_store(self, pool: _Pool, slot: int, ids: list[int]):
        """After a miss prefill: cache this prompt's KV at every bucket
        length it covers, bounded by BOTH an entry count and an HBM byte
        budget (long-context entries are tens of MB each; an entry-only
        cap could pin gigabytes)."""
        ec = self.config.engine
        if not ec.enable_prefix_caching:
            return
        for b in ec.prefill_buckets:
            if b >= len(ids) or b > pool.stripe_len:
                continue
            key = self._prefix_key(ids, b)
            if key in self._prefix_cache:
                self._prefix_cache.move_to_end(key)
                continue
            entry = self._prefix_cut(pool, slot, b)
            entry["nbytes"] = sum(
                int(x.nbytes) for x in (entry["k"], entry["v"], *entry.get("more", {}).values()))
            self._prefix_cache[key] = entry
            self._prefix_bytes += entry["nbytes"]
        self._prefix_evict()

    @staticmethod
    def _prefix_cut(pool: _Pool, slot: int, b: int) -> dict:
        """The first ``b`` positions of every stripe of ``slot``, each
        [layers, K, b, D]: ``k`` and ``v``, and under ``more`` by name the
        further stripes of a cache that has any (``_seed_prefix`` takes them
        so)."""
        cut = {name: pool.cache[name][:, slot, :, :b] for name in pool.stripes}
        k, v = cut.pop("k"), cut.pop("v")
        return {"k": k, "v": v, **({"more": cut} if cut else {})}

    def _prefix_evict(self) -> int:
        """Drop entries from the front until both budgets hold; how many went."""
        ec, gone = self.config.engine, 0
        while self._prefix_cache and (
            len(self._prefix_cache) > ec.prefix_cache_entries
            or self._prefix_bytes > ec.prefix_cache_max_bytes
        ):
            _, old = self._prefix_cache.popitem(last=False)
            self._prefix_bytes -= old.get("nbytes", 0)
            gone += 1
        return gone

    # A pool whose slots hold a state (``_Pool.stateful``: every cache with
    # ``models/patterned.py STATE_LEAVES``) cannot be seeded at a bucket's
    # boundary: the state exists only where a chunk ended. It stores what a
    # finished prompt left instead, under the whole prompt and with its length
    # ``P``: the state leaves of the scratch stripe the final chunk handed
    # back (the stripe's own arrays: no copy) and the stripe's keys and values
    # up to ``P``, rounded up to one of a few lengths. A later prompt that
    # starts with the stored one is seeded from it and runs its tail from
    # ``P``: a session's turn k + 1 behind its turn k. Stored after a hit as
    # after a miss.

    def _snapshot_lengths(self, pool: _Pool) -> list[int]:
        """The lengths a snapshot's keys and values are held at in ``pool``:
        multiples of the widest prompt chunk (and of a quarter of the longest
        stripe where that is more: four forms a pool at most), the stripe's
        own length the last. One store and one seed program each."""
        ec = self.config.engine
        step = max(max(ec.prefill_buckets), self._pools[-1].stripe_len // 4)
        return [min(m, pool.stripe_len) for m in range(step, pool.stripe_len + step, step)]

    def _snapshot_lookup(self, ids: list[int], stripe: int):
        """The longest stored prompt that ``ids`` starts with and is strictly
        longer than, at any length: one pass of the hash over the prompt, read
        at every length an entry has (an entry counts where its keys and
        values fit a stripe of ``stripe`` positions: another pool's may not)."""
        import hashlib

        lengths = sorted({e["length"] for e in self._prefix_cache.values()})
        raw, sha, at, found = np.asarray(ids, np.int32).tobytes(), hashlib.sha1(), 0, None
        for length in lengths:
            if length >= len(ids):
                break
            sha.update(raw[4 * at:4 * length])
            at = length
            key = sha.copy().digest()
            if key in self._prefix_cache and self._prefix_cache[key]["k"].shape[2] <= stripe:
                found = key
        if found is None:
            self._prefix_misses += 1
            return None, 0, None
        entry = self._prefix_cache[found]
        entry["uses"] += 1
        self._prefix_cache.move_to_end(found)
        self._prefix_hits += 1
        return entry, entry["length"], found

    def _snapshot_store(self, pool: _Pool, one: dict, req: _Request) -> None:
        """``one``: the scratch stripe ``req``'s final chunk handed back.

        Eviction is the budgets', from the front, with one change of order: a
        hit touches the entry it used and the request then stores a longer
        one, so plain recency would keep every session's dead turn fresh and
        push live sessions out. The entry this request was seeded from, if no
        other request has used it, goes to the front; one that several
        prompts started from (a system prompt) keeps its place."""
        if not self.config.engine.enable_prefix_caching:
            return
        ids = req.prompt_token_ids
        key = self._prefix_key(ids, len(ids))
        if key in self._prefix_cache:
            self._prefix_cache.move_to_end(key)
            return
        m = next(m for m in self._snapshot_lengths(pool) if m >= len(ids))
        k, v = self._store_snapshot(one, m)
        state = {name: one[name] for name in self._state_leaves}
        nbytes = int(k.nbytes + v.nbytes + sum(x.nbytes for x in state.values()))
        self._prefix_cache[key] = {"k": k, "v": v, "state": state, "nbytes": nbytes,
                                   "length": len(ids), "uses": 0}
        self._prefix_bytes += nbytes
        seeded_from = self._prefix_cache.get(req.prefix_key)
        if seeded_from is not None and seeded_from["uses"] == 1:
            self._prefix_cache.move_to_end(req.prefix_key, last=False)
        self._count({"snapshots_stored": 1, "snapshot_store_bytes": nbytes,
                     "snapshots_evicted": self._prefix_evict()})

    # -- multi-LoRA ----------------------------------------------------------

    def add_lora(self, name: str, adapters: dict) -> int:
        """Load a LoRA adapter into a free stack slot. ``adapters``:
        {wq_a: [L, e, r], wq_b: [L, r, h, hd], wv_a: [L, e, r],
        wv_b: [L, r, kv, hd]} (a pytree checkpoint). Returns the slot index."""
        import jax.numpy as jnp

        if self.loras is None:
            raise ValueError("engine built with max_loras=0")
        if name in self._lora_ids:
            return self._lora_ids[name]
        used = set(self._lora_ids.values())
        free = [
            i
            for i in range(1, self.config.engine.max_loras + 1)
            if i not in used
        ]
        if not free:
            raise RuntimeError(
                f"all {self.config.engine.max_loras} LoRA slots in use"
            )
        idx = free[0]
        new = {}
        for k in ("wq_a", "wq_b", "wv_a", "wv_b"):
            stack = self.loras[k]
            a = jnp.asarray(adapters[k], stack.dtype)
            if a.shape != stack.shape[:1] + stack.shape[2:]:
                raise ValueError(
                    f"{name}.{k}: shape {a.shape} != {stack.shape[:1] + stack.shape[2:]}"
                )
            new[k] = stack.at[:, idx].set(a)
        self.loras = new
        self._lora_ids[name] = idx
        return idx

    def remove_lora(self, name: str) -> None:
        import jax.numpy as jnp

        idx = self._lora_ids.pop(name, None)
        if idx is None:
            return
        self.loras = {
            k: v.at[:, idx].set(jnp.zeros_like(v[:, idx]))
            for k, v in self.loras.items()
        }

    def list_loras(self) -> list[str]:
        return sorted(self._lora_ids)

    # -- public API ---------------------------------------------------------

    def generate(
        self,
        prompt: Optional[str] = None,
        *,
        prompt_token_ids: Optional[list[int]] = None,
        sampling_params: Optional[SamplingParams] = None,
        lora: Optional[str] = None,
        trace_ctx: Optional[tuple] = None,
    ) -> RequestOutput:
        req = self.submit(
            prompt, prompt_token_ids=prompt_token_ids,
            sampling_params=sampling_params, lora=lora, trace_ctx=trace_ctx,
        )
        self._await_done(req)
        if req.error is not None:
            raise req.error
        return self._output(req)

    def generate_stream(
        self,
        prompt: Optional[str] = None,
        *,
        prompt_token_ids: Optional[list[int]] = None,
        sampling_params: Optional[SamplingParams] = None,
        lora: Optional[str] = None,
    ) -> Iterator[dict]:
        """Yields {'token_id', 'text', 'done'} increments."""
        req = self.submit(
            prompt, prompt_token_ids=prompt_token_ids,
            sampling_params=sampling_params, lora=lora,
        )
        yield from self.drain(req)

    def drain(self, req: "_Request") -> Iterator[dict]:
        """Token increments of a submitted request until its end sentinel;
        raises the request's error, if any, after the stream ends. Bursts
        from multi-step decode are paced into spaced emissions (see
        ``llm/pacing.py``) so SSE clients observe a steady token cadence."""
        while True:
            try:
                item = req.stream_queue.get(timeout=1.0)
            except queue.Empty:
                # liveness re-check (same contract as _await_done): a dead
                # or stopped decode loop never pushes the None sentinel, and
                # an untimed get here hung the SSE consumer forever
                if not (self._stop.is_set() or not self._thread.is_alive()):
                    continue
                try:
                    # the loop may have pushed in the race window on its way
                    # out — sweep once before declaring the stream dead
                    item = req.stream_queue.get_nowait()
                except queue.Empty:
                    self._close_request(
                        req, "loop_exit",
                        RuntimeError("engine decode loop exited mid-stream"),
                    )
                    break
            if item is None:
                break
            req.pacer.gate(backlog=not req.stream_queue.empty())
            yield item
        if req.error is not None:
            raise req.error

    def submit(
        self, prompt=None, *, prompt_token_ids=None, sampling_params=None,
        lora: Optional[str] = None, trace_ctx: Optional[tuple] = None,
    ) -> _Request:
        """``trace_ctx``: the caller's ``tracing.current_context()``; the
        request's spans then share its trace id and nest under its span."""
        with self._count_lock:
            self._count({"requests_submitted": 1})
        try:
            if prompt_token_ids is None:
                if prompt is None:
                    raise ValueError("prompt or prompt_token_ids required")
                prompt_token_ids = self.tokenizer.encode(prompt)
            if not len(prompt_token_ids):
                raise ValueError("empty prompt: there is no token to prefill")
            max_prompt = self.config.engine.max_seq_len - 1
            if len(prompt_token_ids) > max_prompt:
                prompt_token_ids = prompt_token_ids[-max_prompt:]
            lora_idx = 0
            if lora:
                if lora not in self._lora_ids:
                    raise KeyError(f"unknown LoRA adapter: {lora!r}")
                lora_idx = self._lora_ids[lora]
        except Exception:
            with self._count_lock:
                self._count({"requests_failed:submit": 1})
            raise
        req = _Request(
            uuid.uuid4().hex[:12], list(prompt_token_ids),
            sampling_params or SamplingParams(),
            lora_idx=lora_idx,
        )
        req.trace_ctx = trace_ctx
        self._waiting.put(req)
        return req

    def _output(self, req: _Request) -> RequestOutput:
        return RequestOutput(
            request_id=req.request_id,
            prompt_token_ids=req.prompt_token_ids,
            token_ids=list(req.out_tokens),
            text=self.tokenizer.decode(req.out_tokens),
            finish_reason=req.finish_reason or "stop",
            metrics={
                # None where no token came (the first sampled was a stop)
                "ttft_s": _between(
                    req.submitted_t, req.first_token_t if req.out_tokens else None
                ),
                "queue_wait_s": _between(req.submitted_t, req.admitted_t),
                "prefill_s": _between(req.admitted_t, req.first_token_t),
                "slot": req.slot,
                "num_generated": len(req.out_tokens),
                "prefix_hit_tokens": req.prefix_hit_tokens,
            },
        )

    def shutdown(self):
        self._stop.set()
        self._thread.join(timeout=5)

    def _await_done(self, req) -> None:
        """Bounded wait with a liveness re-check: a dead or stopped decode
        loop must surface as a request error, not hang the caller forever
        (an untimed ``done.wait()`` here survived every engine crash)."""
        while not req.done.wait(1.0):
            if self._stop.is_set() or not self._thread.is_alive():
                # the loop may have finished THIS request on its way out —
                # re-check done before declaring it dead, or a completed
                # decode gets discarded as an error
                if req.done.wait(0.1):
                    return
                self._close_request(
                    req, "loop_exit",
                    RuntimeError(
                        "engine decode loop exited while the request was pending"
                    ),
                )
                return

    def get_stats(self) -> dict:
        from ray_tpu.models.patterned import state_mixer_forms
        from ray_tpu.tpu.accelerator import device_report

        return {
            # what this replica really runs on, as its own process sees it
            "device": device_report(),
            "model": {
                "model_id": self.config.model.model_id,
                "n_layers": self.model_cfg.n_layers,
                "num_params": self.model_cfg.num_params(),
            },
            "active_slots": sum(
                s is not None for p in self._pools for s in p.slots
            ),
            "admitting": sum(len(p.admitting) for p in self._pools),
            "waiting": self._waiting.qsize() + len(self._backlog),
            "max_num_seqs": sum(p.n_slots for p in self._pools),
            "pools": [
                {"stripe_len": p.stripe_len, "n_slots": p.n_slots,
                 "active": sum(s is not None for s in p.slots),
                 "kv_bytes_per_token": p.kv_bytes_per_token,
                 "kv_bytes_per_token_held": p.kv_bytes_per_token_held,
                 "state_bytes_per_slot": p.state_bytes_per_slot,
                 # whether its chunk launches take its decode rows and carry its
                 # step (``decode_steps_in_chunk``): which scheduler a run measured
                 "carries": p.carries,
                 # positions a block of the decode kernel's walk takes of this
                 # pool's cache (None: its steps keep the einsum over the stripe)
                 "decode_block": p.decode_block,
                 # the form its steps' write of their new keys and values takes:
                 # ``kernel`` (``ops/cache_write.py``, one call a layer) or ``scatter``
                 "decode_write": "kernel" if p.writes_rows else "scatter",
                 # which form its state mixers take for a chunk and a step
                 # (``kernel`` or ``plain``): static a shape, asked where the trace asks
                 "state_mixer_forms": state_mixer_forms(self.model_cfg),
                 # how its chunk programs read a latent cache, by the chunk's
                 # width and the layers' kind (``kernel`` or ``einsum``; {} for
                 # any other pool): asked of what the trace asks, as ``reads_blocks``
                 "chunk_walks": self._chunk_walks(p)}
                for p in self._pools
            ],
            "prefix_cache_hits": self._prefix_hits,
            "prefix_cache_misses": self._prefix_misses,
            "prefix_cache_entries": len(self._prefix_cache),
            "prefix_cache_bytes": self._prefix_bytes,
            # cumulative since the engine started
            "counters": self._counters_view(),
            # prompt plus generated tokens of the bound slots: what a decode
            # step has to read
            "live_tokens": self._live_tokens(),
            # bucket counts of the finished requests' latencies, seconds
            "latency": {
                "boundaries": list(LATENCY_BOUNDS),
                **{k: _latency_histogram(k).read(self._tag) for k in LATENCIES},
            },
            # constructor entered to the loop thread's first pass, and the
            # seconds of its phases, which add up to it (the last also by
            # program: a middle chunk by rows, a final chunk by width)
            "engine_init_s": _between(self._t_init, self._loop_first_pass_t),
            "init": {**self._init_s, "warm_programs_by_program_s": dict(self._warm_s),
                     "warm_programs_phases_s": {p: dict(by) for p, by in self._warm_phases_s.items()},
                     "programs": dict(self._program_counts)},
            # the loop's own clock: where its time went, and the longest pass
            # of every second (``_LoopClock``)
            "loop": self._loop.view(),
            # parameter leaves held in the device layout the model's rule names
            # (the stacked attention input projections, head-major): 0 says
            # the programs copy a layer's slice before they multiply
            "params_relaid": dict(self._params_relaid),
        }

    def _live_tokens(self) -> int:
        return sum(
            len(r.prompt_token_ids) + len(r.out_tokens)
            for pool in self._pools for r in list(pool.slots) if r is not None
        )

    def _counters_view(self) -> dict:
        """``_n`` with each labelled family as a dict: ``requests_failed``
        is ``{"submit": .., "admission": .., "decode": .., "loop_exit": ..}``."""
        out: dict = {}
        for name, value in self._n.items():
            family, _, label = name.partition(":")
            if label:
                out.setdefault(family, {})[label] = value
            else:
                out[name] = value
        return out

    def _count(self, deltas: dict) -> None:
        """The one place a counter grows: ``deltas`` (counter name -> growth)
        go into ``_n`` and, in the same call, onto the profiler's clock as one
        instant event ``engine.counts`` whose attributes are the deltas under
        the counters' own names. So a trace holds each count where its work
        happened, and the events of a profiler session sum to the counters'
        growth over it by construction. A launch's counts are written right
        after the launch, what the device hands back at its fetch. The loop
        thread's calls need no lock; the names that callers' threads also
        count (``submit``, ``_close_request``) are counted under
        ``_count_lock``."""
        deltas = {name: value for name, value in deltas.items() if value}
        if not deltas:
            return
        n = self._n
        for name, value in deltas.items():
            n[name] += value
        tracing.mark("engine.counts", **deltas)

    # -- the end of a request ------------------------------------------------

    def _close_request(
        self, req: _Request, failed_stage: Optional[str] = None,
        error: Optional[BaseException] = None, at: Optional[float] = None,
    ) -> None:
        """The one place a request ends, finished or failed. Counts it once
        (the loop and a caller's thread that found the loop dead may both
        come here), records its latencies and spans from the timestamps it
        carries, and only then wakes whoever waits for it. ``at``: when it
        ended, where that was earlier than this call (``_emit`` stamps a
        finished request at its last token; ``_drain`` closes it once the
        fetch's block is counted)."""
        with self._count_lock:
            if req.finished_t is not None:
                return
            req.finished_t = time.time() if at is None else at
            if failed_stage is not None:
                if req.error is None:
                    req.error = error
                self._count({"requests_failed:" + failed_stage: 1})
            else:
                self._count({"requests_finished:" + req.finish_reason: 1,
                             "requests_empty": int(not req.out_tokens)})
                self._observe_latencies(req)
            self._mirror_metrics()
        self._record_request_spans(req)
        req.stream_queue.put(None)
        req.done.set()

    def _observe_latencies(self, req: _Request) -> None:
        n = len(req.out_tokens)
        values = {
            "queue_wait_s": _between(req.submitted_t, req.admitted_t),
            "prefill_s": _between(req.admitted_t, req.first_token_t),
            "token_gap_s": (
                (req.finished_t - req.first_token_t) / (n - 1)
                if n > 1 and req.first_token_t is not None else None
            ),
        }
        for name, v in values.items():
            if v is not None:
                _latency_histogram(name).observe(v, tags=self._tag)

    def _mirror_metrics(self) -> None:
        """Fold the counters' growth, and the loop's seconds by stage and its
        passes by duration, into ``util.metrics`` (under ``_count_lock``:
        ``_mirrored`` is shared with callers' threads).
        ``llm_engine_loop_stage_seconds`` says without a profiler whether the
        loop waits for the chip (``drain``) or the chip for the loop;
        ``llm_engine_loop_pass_seconds`` what a pass takes."""
        for name, value in self._n.items():
            family, _, label = name.partition(":")
            counter = _metric(
                "Counter", family,
                tag_keys=(_LABEL[family],) if label else (),
            )
            app_metrics.fold_counter_delta(
                counter, self._mirrored, name, value,
                {_LABEL[family]: label} if label else None,
            )
        stage_seconds = _metric("Counter", "loop_stage_seconds", tag_keys=("stage",))
        for stage, seconds in list(self._loop.stage_s.items()):
            app_metrics.fold_counter_delta(
                stage_seconds, self._mirrored, "loop_stage_seconds:" + stage, seconds,
                {"stage": stage},
            )
        # the passes since the last fold, by duration: the loop's rhythm beside
        # the decode step, and whether a stall was one pass or many
        counts, total = list(self._loop.pass_counts), sum(self._loop.stage_s.values())
        had, had_total = self._mirrored.get("loop_pass_seconds", ((0,) * len(counts), 0.0))
        _metric("Histogram", "loop_pass_seconds", boundaries=PASS_BOUNDS).add(
            [c - h for c, h in zip(counts, had)], total - had_total)
        self._mirrored["loop_pass_seconds"] = (counts, total)
        _metric("Gauge", "live_tokens").set(self._live_tokens())

    def _record_request_spans(self, req: _Request) -> None:
        """``engine.request`` and its phases in the operator's ring, under
        the caller's context: queue wait, prefill (admitted to the first
        token) and decode tile the request's time; a request that failed
        has the phases it reached, the last one ending with it."""
        if not tracing.enabled():
            return
        ctx = req.trace_ctx
        trace_id = ctx[0] if ctx else tracing.new_trace_id()
        sid = tracing.new_span_id()
        tracing.record_span(
            "engine.request", req.submitted_t, req.finished_t,
            trace_id=trace_id, span_id=sid, parent_id=ctx[1] if ctx else None,
            plane="engine", request_id=req.request_id,
            prompt_tokens=len(req.prompt_token_ids),
            prefix_hit_tokens=req.prefix_hit_tokens,
            tokens=len(req.out_tokens), chunks=req.chunks_run,
            pool=req.pool_stripe, slot=req.slot,
            finish_reason=req.finish_reason,
            error=repr(req.error) if req.error is not None else None,
        )
        marks = (req.submitted_t, req.admitted_t, req.first_token_t, req.finished_t)
        names = ("engine.queue_wait", "engine.prefill", "engine.decode")
        for name, start, end in zip(names, marks, marks[1:]):
            tracing.record_span(
                name, start, end if end is not None else req.finished_t,
                trace_id=trace_id, parent_id=sid, plane="engine",
                request_id=req.request_id,
            )
            if end is None:  # it ended before it reached the next phase
                break

    # -- engine loop --------------------------------------------------------

    def _bucket(self, n: int) -> int:
        for b in self.config.engine.prefill_buckets:
            if n <= b and b <= self.config.engine.max_seq_len:
                return b
        return self.config.engine.max_seq_len

    def _pool_for(self, req: _Request) -> "_Pool":
        """Smallest stripe class covering prompt + generation budget; if
        none fits, the largest pool (out_of_room truncates there)."""
        budget = len(req.prompt_token_ids) + req.params.max_tokens + 1
        for pool in self._pools:  # sorted ascending by stripe_len
            if pool.stripe_len >= budget:
                return pool
        return self._pools[-1]

    def _start_admission(self, pool: "_Pool", slot: int, req: _Request) -> None:
        """Build the chunked-prefill plan for a slot (device work starts on
        the next _advance_admissions pass)."""
        req.admitted_t = time.time()
        req.pool_stripe, req.slot = pool.stripe_len, slot
        ids = req.prompt_token_ids
        if len(ids) > pool.stripe_len - 1:
            ids = ids[-(pool.stripe_len - 1):]
            req.prompt_token_ids = ids
        # LoRA'd requests never reuse base-model KV (the cached V lacks
        # the adapter delta) — and their prefixes are never stored either
        if req.lora_idx == 0:
            with tracing.annotate("engine.prefix_lookup"):
                prefix, m, req.prefix_key = self._prefix_lookup(ids, pool.stripe_len)
        else:
            prefix, m = None, 0
        # a pool that generates by blocks prefills the prompt's whole blocks;
        # the tokens left, fewer than a block, stand clean at the front of
        # the first block it generates. (A stored prefix that leaves no whole
        # block to run is passed over: the chunk that copies a stripe into
        # its slot has to have a block to run.)
        whole = len(ids) - len(ids) % pool.block_length if pool.block_length else len(ids)
        if pool.block_length and m == whole:
            prefix, m, req.prefix_key = None, 0, None
        suffix = ids[m:whole]
        req.prefix_hit_tokens = m
        self._count({"prompt_tokens": len(ids), "prompt_tokens_from_prefix": m})
        chunk = self.config.engine.prefill_chunk or max(1, len(suffix))
        pieces = [suffix[i : i + chunk] for i in range(0, len(suffix), chunk)]
        chunks = []
        start = m
        for j, piece in enumerate(pieces):
            is_final = j == len(pieces) - 1
            width = (
                min(self._bucket(len(piece)), pool.stripe_len)
                if is_final
                else len(piece)
            )
            toks = np.zeros((1, width), np.int32)
            toks[0, : len(piece)] = piece
            chunks.append((toks, len(piece), start, is_final))
            start += len(piece)
        with tracing.annotate("engine.new_stripe"):
            one = self._new_stripe(pool.stripe_len)
        if prefix is not None:
            with self._device_call("launch", "seed_prefix", "engine.prefix_seed"):
                # (a snapshot: the state leaves with the keys and values)
                one = self._seed_prefix(
                    one, prefix["k"], prefix["v"], prefix.get("state"), prefix.get("more"))
                if "state" in prefix:
                    self._count({"snapshots_hit": 1, "snapshot_seed_bytes": prefix["nbytes"]})
                self._count({"prefix_seed_tokens": m})
        pool.admitting[slot] = adm = _Admission(req, slot, one, chunks, m)
        adm.tail = list(ids[whole:])

    @contextmanager
    def _device_call(self, kind: str, program: str, span: str):
        """A ``fetch`` of what ``program`` handed back, or the ``launch`` of
        it: under its profiler span, and timed on the loop's own clock."""
        t = time.perf_counter()
        try:
            with tracing.annotate(span):
                yield
        finally:
            self._loop.call(kind, program, time.perf_counter() - t)

    @staticmethod
    def _next_chunk(adm: _Admission) -> tuple:
        """Take ``adm``'s next chunk off its plan."""
        toks, eff_len, start, _ = adm.chunks[adm.idx]
        adm.idx += 1
        adm.req.chunks_run += 1
        return toks, eff_len, start

    def _count_chunks(self, kind: str, plan: list) -> None:
        """Count one launch of the ``kind`` (``mid``, ``final``) chunk program
        whose rows are ``plan``, after it was dispatched: one a prompt chunk,
        whatever launch carries it, and the launch itself."""
        program = "chunk_" + kind
        self._count({
            "prefill_programs:" + kind: 1,
            "prefill_chunks:" + kind: len(plan),
            "prefill_query_tokens:" + program: sum(n for _, n, _ in plan),
            "prefill_attended_positions:" + program: sum(
                n * start + n * (n + 1) // 2 for _, n, start in plan),
        })

    def _launch_mid_chunks(self, pool: "_Pool", adms: list,
                           carry: "dict | str | None" = None) -> None:
        """Dispatch ONE ``chunk_mid`` (device-async) whose rows are the next
        middle chunks of ``adms``: each row's stripe comes back extended.
        ``carry``: the slots (slot -> request) whose decode step the launch
        carries, or why it carries none (``_advance_admissions``)."""
        plan = [self._next_chunk(adm) for adm in adms]
        ones = self._run_chunk_mid(
            ones=tuple(adm.one for adm in adms),
            toks=np.concatenate([toks for toks, _, _ in plan]),
            lens=[eff_len for _, eff_len, _ in plan],
            starts=[start for _, _, start in plan],
            adapters=[adm.req.lora_idx for adm in adms],
            pool=pool, carry=carry,
        )
        self._count_chunks("mid", plan)
        for adm, one in zip(adms, ones):
            adm.one = one

    @staticmethod
    def _takes_rows(pool: "_Pool", rows: int = 1) -> bool:
        """Whether ``pool``'s chunk program of ``rows`` prompt rows takes the
        pool's decode rows: in a pool that ``carries``, the final chunk and
        the middle chunk of one row. A launch of several rows stays the
        chunks' alone: each row count is a form and would hold the decode
        program as well, a larger file to restore at every start; a later
        launch of the pass that takes rows carries instead (ROADMAP S2 c).
        A launch that takes rows runs them whether or not a step rides: with
        no step to carry no row is live and their arithmetic is done for no
        token (``decode_steps_dead_in_chunk``)."""
        return pool.carries and rows == 1

    def _decode_rows(self, pool: "_Pool", carry: "dict | str | None") -> dict:
        """What a chunk program of a pool that ``carries`` takes of the pool's
        decode step beside the cache: the inputs ``decode_fn`` takes (of a pool
        that generates by blocks ``block_step``'s: its block state where the
        others hand over tokens), and the rows that decode in this launch
        (none unless ``carry`` is slots: the program runs every row all the
        same, for no token)."""
        import jax.numpy as jnp

        live = np.zeros((pool.n_slots,), bool)
        if isinstance(carry, dict):
            live[list(carry)] = True
        temps, top_ks = pool.sampler()
        step = dict(block=pool.block) if pool.block_length else dict(tokens=pool.dev_tokens)
        return dict(step, temps=temps, top_ks=top_ks, keys=pool.keys, live=jnp.asarray(live))

    def _carried(self, pool: "_Pool", carry: "dict | str | None", handed, block=None) -> None:
        """After a launch of a chunk program that took ``pool``'s decode rows:
        the pool's next input is the program's (``handed``, the next tokens;
        of a pool that generates by blocks ``block``, the next block state,
        and ``handed`` the slots' [slots, B + 2] rows), and a step it carried
        goes on ``pool.inflight`` with its binding as a decode launch's does
        (its routing counts are among the chunk program's). A launch of the
        loop's that carried none is counted by what kept the step from riding
        (``carry`` is then that one of ``DEAD_CAUSES``): its rows ran dead."""
        if pool.block_length:
            pool.block = block
        else:
            pool.dev_tokens = handed
        if not isinstance(carry, dict):
            if carry is not None:
                self._count({"decode_steps_dead_in_chunk:" + carry: 1})
            return
        try:
            handed.copy_to_host_async()
        except Exception:  # noqa: BLE001
            pass
        pool.inflight.append((handed, carry, None))
        pool.step_carried = True
        self._count({**self._decode_counts(pool, carry, 1), "decode_steps_in_chunk": 1})

    def _run_chunk_mid(self, ones: tuple, toks, lens: list, starts: list, adapters: list,
                       pool: Optional["_Pool"] = None,
                       carry: "dict | str | None" = None) -> tuple:
        """The device side of a middle-chunk launch, a row an entry. ``pool``:
        the stripes' pool (None: found by their length); where it ``carries``
        the one-row program takes its cache and decode rows, and with slots
        for ``carry`` they decode in this launch (a launch of several rows is
        the chunks' alone: ``_takes_rows``)."""
        import jax.numpy as jnp

        if pool is None:
            pool = next(p for p in self._pools if p.stripe_len == ones[0]["k"].shape[3])
        takes = self._takes_rows(pool, len(ones))
        with tracing.annotate("engine.chunk_transfer"):  # the host's arrays
            args = (jnp.asarray(toks), jnp.asarray(lens, jnp.int32),
                    jnp.asarray(starts, jnp.int32))
            lora_kw = self._lora_kw(adapters)
            if takes:
                args += (pool.cache, self._decode_rows(pool, carry))
        with tracing.annotate("engine.chunk_call"):
            out = self._launch(("chunk_mid", pool.stripe_len, len(ones)), "_chunk_mid_jit",
                               self.params, ones, *args, **lora_kw)
        if not takes:
            return out
        ones, handed, pool.cache, pool.keys, *block = out
        self._carried(pool, carry, handed, *block)
        return ones

    def _launch_final_chunk(self, pool: "_Pool", adm: _Admission,
                            carry: "dict | str | None" = None) -> None:
        """Dispatch a prompt's final chunk (device-async), one prompt a launch
        (``programs``' ``chunk_final`` says why): it samples the first token
        in-program and activates the slot. ``carry``: the slots (slot ->
        request) whose decode step the launch carries (or why it carries
        none); the slot it activates is bound after the launch and is none of
        them."""
        toks, eff_len, start = self._next_chunk(adm)
        req, slot = adm.req, adm.slot
        # decode truncates to the program's static top-K; clamp here so
        # first token and all later tokens agree
        top_k = min(max(1, req.params.top_k), self._top_k_static)
        pool.adapter_ids[slot] = req.lora_idx
        self._sync_adapter_ids(pool)
        first_tok, stats, one = self._run_chunk_final(
            pool, adm.one, toks, eff_len, start, slot,
            req.params.temperature, top_k, req.params.seed, req.lora_idx, carry=carry,
        )
        self._count_chunks("final", [(toks, eff_len, start)])
        self._bind_slot(pool, adm, length=start + eff_len)
        # LoRA'd prefixes are adapter-specific: never shared
        if req.lora_idx == 0 and pool.stateful:  # after a hit as after a miss
            with self._device_call("launch", "store_snapshot", "engine.prefix_store"):
                self._snapshot_store(pool, one, req)
        elif req.lora_idx == 0 and req.prefix_hit_tokens == 0:
            with tracing.annotate("engine.prefix_store"):
                self._prefix_store(pool, slot, req.prompt_token_ids)
        try:
            first_tok.copy_to_host_async()
            if stats is not None:
                stats.copy_to_host_async()
        except Exception:  # noqa: BLE001 — platform without async copy
            pass
        pool.first_pending.append((slot, req, first_tok, stats))

    def _bind_slot(self, pool: "_Pool", adm: _Admission, length: int, key=None) -> None:
        """The host's side of an admission's end: the slot holds the request
        from now on, under its own sampler. In a pool that generates by blocks
        the slot's first block is seeded as well (``_bind_block``; ``key``:
        the request's where no final chunk has set it)."""
        req, slot = adm.req, adm.slot
        if pool.block_length:
            self._bind_block(pool, slot, req.params, length, adm.tail, key)
            req.block_tail = len(adm.tail)
            self._count({"block_prompt_tail_tokens": len(adm.tail)})
        pool.slots[slot] = req
        pool.temps[slot] = req.params.temperature
        pool.top_ks[slot] = min(max(1, req.params.top_k), self._top_k_static)
        pool.sampler_dev = None
        del pool.admitting[slot]

    def _request_key(self, seed: Optional[int]):
        """The key a request's draws start from: its seed's, or the engine's next."""
        import jax

        if seed is not None:
            return jax.random.PRNGKey(seed)
        self._rng_key, key = jax.random.split(self._rng_key)
        return key

    def _run_chunk_final(self, pool: "_Pool", one, toks, eff_len: int, start: int, slot: int,
                         temperature: float, top_k: int, seed: Optional[int], adapter: int,
                         carry: "dict | str | None" = None):
        """The device side of a final-chunk launch: the pool's cache, keys
        and next input tokens take the slot's new values (and, in a pool that
        ``carries``, with slots for ``carry`` those of the rows that decode in
        this launch). Returns the first token and the routing counts (or None),
        both still on the device, and the scratch stripe as the chunk left it
        (what a snapshot of the prompt is cut from)."""
        import jax
        import jax.numpy as jnp

        with tracing.annotate("engine.chunk_transfer"):  # the host's arrays and scalars
            req_key = self._request_key(seed)
            slot_dev = jnp.int32(slot)
            args = (jnp.asarray(toks), jnp.asarray([eff_len], jnp.int32),
                    jnp.asarray([start], jnp.int32), slot_dev,
                    jnp.float32(temperature), jnp.int32(top_k), req_key)
            lora_kw = self._lora_kw([adapter])
            if pool.carries:
                args += (self._decode_rows(pool, carry),)
        with tracing.annotate("engine.chunk_call"):
            first_tok, new_key, pool.cache, one, stats, *rode = self._launch(
                ("chunk_final", pool.stripe_len, toks.shape[1]), "_chunk_final_jit",
                self.params, pool.cache, one, *args, **lora_kw)
        if rode:  # the program set the slot's key and next input token itself
            handed, pool.keys, *block = rode
            self._carried(pool, carry, handed, *block)
            return first_tok, stats, one
        with tracing.annotate("engine.slot_set"):  # the slot's key and next input token
            pool.keys = self._set_key_jit(pool.keys, slot_dev, new_key)
            pool.dev_tokens = self._set_tok_jit(pool.dev_tokens, slot_dev, first_tok)
        return first_tok, stats, one

    def _fail_admission(
        self, pool: "_Pool", adm: _Admission, e: BaseException,
        stage: str = "admission",
    ):
        pool.admitting.pop(adm.slot, None)
        self._close_request(adm.req, stage, e)

    def _pull_waiting(self) -> bool:
        """Route waiting requests to free slots and build admission plans.
        The backlog is engine-thread-owned and order-preserving: a head
        request whose stripe class is full must NOT starve shorter
        requests that fit other pools' free slots."""
        try:
            while True:
                self._backlog.append(self._waiting.get_nowait())
        except queue.Empty:
            pass
        if not self._backlog:
            return False
        progressed = False
        still_waiting = []
        for req in self._backlog:
            preferred = self._pool_for(req)
            budget = len(req.prompt_token_ids) + req.params.max_tokens + 1
            target = None
            candidates = [preferred] + [
                p for p in self._pools
                if p is not preferred and p.stripe_len >= min(
                    budget, preferred.stripe_len
                )
            ]
            for pool in candidates:
                # cap concurrent admissions: each holds a live stripe-sized
                # scratch KV (unbounded, 16 free slots would transiently
                # DOUBLE the pool's HBM footprint), and per-pass prefill
                # work must stay bounded for chunking to protect decode
                if len(pool.admitting) >= self.config.engine.max_concurrent_admissions:
                    continue
                for slot in range(pool.n_slots):
                    if pool.slots[slot] is None and slot not in pool.admitting:
                        target = (pool, slot)
                        break
                if target:
                    break
            if target is None:
                still_waiting.append(req)
                continue
            try:
                self._start_admission(target[0], target[1], req)
                progressed = True
            except BaseException as e:  # noqa: BLE001
                self._close_request(req, "admission", e)
        self._backlog = still_waiting
        return progressed

    def _advance_admissions(self) -> bool:
        """One chunk of every admission. A pool's admissions whose next
        chunk is a middle chunk (all are ``prefill_chunk`` wide) run as rows
        of ONE ``chunk_mid``, as many rows as are due: a read of the weights
        then serves every prompt that waits for it. A final chunk is a
        launch of its own. A launch that raises fails its rows' requests,
        and no others.

        In a pool that ``carries``, the pass's first chunk launch carries the
        pool's decode step where one is due (``_decode_due``: the rule
        ``_launch_decodes`` launches by, which then launches none for that
        pool in this pass): the rows that decode ride through the chunk's read
        of the weights, one program where there were two. A later one-row
        launch of the pass, or one that finds no step due, runs the same
        program with no row live: the rows' kernels and sampler run (their
        write starts no copy) and nothing reads them
        (``decode_steps_dead_in_chunk``, by cause). A launch of several rows
        and a pass with no chunk run as they did."""
        progressed = False
        for pool in self._pools:
            launches, mids = [], None  # mids: the middle-chunk launch with room left
            for adm in list(pool.admitting.values()):
                try:
                    if pool.block_length and not adm.chunks:
                        # a prompt shorter than a block prefills nothing: the
                        # slot starts at length 0 with the prompt in its block
                        with self._device_call("launch", "seed_block", "engine.block_bind"):
                            self._bind_slot(pool, adm, length=0, key=self._request_key(adm.req.params.seed))
                        progressed = True
                        continue
                    # an admission with no chunk (an empty prompt) fails that
                    # request, not the loop
                    is_final = adm.chunks[adm.idx][3]
                except BaseException as e:  # noqa: BLE001
                    self._fail_admission(pool, adm, e)
                    continue
                if is_final:
                    launches.append((True, [adm]))
                    continue
                if mids is None or len(mids) == pool.chunk_rows:
                    mids = []
                    launches.append((False, mids))
                mids.append(adm)
            for is_final, adms in launches:
                carry = None
                if self._takes_rows(pool, len(adms)):
                    carry = "step_carried" if pool.step_carried else self._decode_due(pool)
                try:
                    with self._device_call(
                        "launch", "chunk_final" if is_final else "chunk_mid",
                        "engine.prefill_chunk",
                    ):
                        if is_final:
                            self._launch_final_chunk(pool, adms[0], carry)
                        else:
                            self._launch_mid_chunks(pool, adms, carry)
                    progressed = True
                except BaseException as e:  # noqa: BLE001
                    for adm in adms:
                        self._fail_admission(pool, adm, e)
        return progressed

    def _decode_due(self, pool: "_Pool") -> "dict | str":
        """The slots (slot -> request) of ``pool``'s next decode step, or
        where none is due the reason, one of ``DEAD_CAUSES``: no slot holds a
        request, or the run-ahead is full."""
        active = {s: r for s, r in enumerate(pool.slots) if r is not None}
        if not active:
            return "no_slot"
        if len(pool.inflight) > max(0, self.config.engine.decode_runahead):
            return "runahead_full"
        return active

    def _decode_counts(self, pool: "_Pool", active: dict, steps: int) -> dict:
        """What ``steps`` decode steps over the slots ``active`` count, from
        the lengths the loop holds at their launch."""
        lengths = np.fromiter(
            (len(r.prompt_token_ids) + len(r.out_tokens) for r in active.values()),
            np.int64, len(active),
        )
        if pool.block_length:
            # a step is a forward of a block: it reads the slot's whole blocks
            # (the prompt's tail rides in the first) and the block itself
            lengths = lengths // pool.block_length * pool.block_length + pool.block_length
        tokens, read = (
            ("decode_kv_tokens_latent", "decode_kv_positions_read_latent")
            if pool.latent else ("decode_kv_tokens_global", "decode_kv_positions_read")
        )
        topk = self.model_cfg.index_topk
        counts = {
            "decode_steps": steps, "decode_slot_steps": steps * len(active),
            tokens: steps * int(lengths.sum()),
            # (an indexed layer scores the index keys of a slot's whole stripe)
            read: steps * (pool.stripe_len * len(active) if topk
                           else pool.positions_read(0, lengths)),
        }
        if topk:
            counts["index_positions_scored"] = steps * int(lengths.sum())
            counts["index_positions_selected"] = steps * int(np.minimum(lengths, topk).sum())
        window = self.model_cfg.sliding_window
        if window:  # a model without one has no window layers to count for
            counts["decode_kv_tokens_window"] = steps * int(
                np.minimum(lengths, window).sum())
            counts["decode_kv_positions_read_window"] = steps * pool.positions_read(
                lengths - window, lengths)
        return counts

    def _launch_decodes(self) -> bool:
        """One decode program per pool with active slots, chained on
        device-resident tokens (no host sync on the launch path). None for a
        pool whose step a chunk launch of this pass carried."""
        launched = False
        for pool in self._pools:
            carried, pool.step_carried = pool.step_carried, False
            active = None if carried else self._decode_due(pool)
            if not isinstance(active, dict):
                continue
            try:
                with self._device_call(
                    "launch", *(("block_step", "engine.block_launch") if pool.block_length
                                else ("decode", "engine.decode_launch"))):
                    if pool.block_length:
                        out, stats = self._block_step(pool)
                    else:
                        out, pool.cache, pool.keys, stats = self._decode(
                            pool,
                            pool.dev_tokens,
                            *pool.sampler(),
                            pool.keys,
                        )
                        pool.dev_tokens = out[-1]
                    try:
                        out.copy_to_host_async()
                        if stats is not None:
                            stats.copy_to_host_async()
                    except Exception:  # noqa: BLE001
                        pass
                pool.inflight.append((out, active, stats))
                self._count(self._decode_counts(pool, active, self._decode_n_steps))
                launched = True
            except BaseException as e:  # noqa: BLE001 — device failure
                self._fail_pool(pool, e)
        return launched

    def _fail_pool(self, pool: "_Pool", e: BaseException):
        """Device failure: fail every in-flight request of THIS pool
        (callers must never hang on a dead engine loop) and reset it."""
        import jax

        logger.error("decode step failed: %r", e)
        from ray_tpu.models.llama import init_kv_cache

        for slot, req in enumerate(pool.slots):
            if req is not None:
                pool.slots[slot] = None
                self._close_request(req, "decode", e)
        for adm in list(pool.admitting.values()):
            self._fail_admission(pool, adm, e, stage="decode")
        pool.inflight.clear()
        pool.first_pending.clear()
        pool.step_carried = False
        pool.cache = self._held(init_kv_cache(self.model_cfg, pool.n_slots, pool.stripe_len))
        pool.dev_tokens = self._held(jax.numpy.zeros((pool.n_slots,), jax.numpy.int32))
        if pool.block_length:
            pool.block = self._held(self._new_block(pool.n_slots))
        # keys may already point at the failed program's poisoned output
        # (reassigned in _launch_decodes before the error surfaced at
        # fetch): without fresh keys every future admission fails too
        pool.keys = self._held(jax.random.split(
            jax.random.PRNGKey(self.config.model.seed ^ int(time.time())),
            pool.n_slots,
        ))

    def _drain(self) -> bool:
        """Fetch arrived tokens (first tokens + completed decode programs)
        and run finish bookkeeping. Keeps up to ``decode_runahead`` decode
        programs in flight; over-decoded tokens of finished or re-admitted
        slots are discarded via the per-program binding snapshot. What a
        fetch brought is counted once, as one event, and the requests it
        finished are closed after that, with the time ``_emit`` stamped at
        their last token: whoever a close wakes finds the counters holding
        its tokens, and ``finished_t`` (so ``token_gap_s`` and the ring's
        ``engine.decode``) ends where it always did. The stream's terminator
        and ``done`` follow by the rest of the block's emits. A first token is
        waited for where it was launched, but in a pool whose chunk launches
        carry the decode step: there it is taken once it has arrived."""
        progressed = False
        runahead = max(0, self.config.engine.decode_runahead)
        for pool in self._pools:
            if pool.first_pending:
                pending, pool.first_pending = pool.first_pending, []
                if pool.carries:
                    pending, pool.first_pending = self._arrived(pool, pending, runahead)
                for slot, req, tok, stats in pending:
                    try:
                        with self._device_call("fetch", "first_token", "engine.fetch"):
                            t = int(np.asarray(tok))
                            counts = self._routing_counts(stats, ("chunk_mid", "chunk_final"))
                    except BaseException as e:  # noqa: BLE001
                        self._fail_pool(pool, e)
                        break
                    ended = None
                    try:
                        # (a prompt of a pool that generates by blocks samples
                        # no token: what came is its chunks' routing counts)
                        if pool.slots[slot] is req and not pool.block_length:
                            req.first_token_t = time.time()
                            # one token, or none where the first sampled was a stop
                            made, ended = self._emit(pool, slot, t)
                            counts.update(tokens_generated=made, first_tokens=made)
                            progressed = True
                        self._count(counts)
                    finally:
                        if ended is not None:
                            self._close_request(req, at=ended)
            has_active = any(r is not None for r in pool.slots)
            keep = runahead if has_active else 0
            while len(pool.inflight) > keep:
                out, binding, stats = pool.inflight.popleft()
                try:
                    with self._device_call("fetch", "decode", "engine.fetch"):
                        arr = np.atleast_2d(np.asarray(out))  # [K, slots] (a carried step's: [slots])
                        counts = self._routing_counts(stats, ("decode",))
                except BaseException as e:  # noqa: BLE001
                    self._fail_pool(pool, e)
                    break
                applied: dict[int, list] = {}
                made = discarded = 0
                done = []  # (request, when its last token was emitted)
                try:
                    if pool.block_length:  # a forward of a block a slot: [slots, B + 2]
                        counts.update(self._take_blocks(pool, arr, binding, applied, done))
                    else:
                        for k in range(arr.shape[0]):
                            for slot, req in binding.items():
                                if pool.slots[slot] is req:
                                    appended, ended = self._emit(pool, slot, int(arr[k, slot]))
                                    made += appended
                                    if ended is not None:
                                        done.append((req, ended))
                                    entry = applied.setdefault(id(req), [req, 0])
                                    entry[1] += 1
                                else:
                                    discarded += 1
                        counts.update(tokens_generated=made, tokens_discarded=discarded)
                    self._count(counts)
                finally:  # a slot that was freed is closed, whatever came after it
                    for req, ended in done:
                        self._close_request(req, at=ended)
                for req, n in applied.values():
                    req.pacer.note_block(n)
                progressed = True
        return progressed

    def _take_blocks(self, pool: "_Pool", arr, binding: dict, applied: dict, done: list) -> dict:
        """What one fetched forward of a pool that generates by blocks brought
        (``arr`` [slots, B + 2]: a slot's block after the forward, whether it
        committed, the positions it unmasked), for the slots still bound to
        the request the launch ran for: a denoise forward is counted; a
        committed block's tokens are the request's next ones, but for the
        prompt's tail at the front of its first block, each through ``_emit``,
        which cuts the block at ``max_tokens``, at a stop token and at the
        stripe's end (what is left of it is discarded, as is a block committed
        for a request that had ended). ``applied`` and ``done`` as ``_drain``
        keeps them. Returns the counts."""
        B = pool.block_length
        got = dict.fromkeys(("block_forwards:denoise", "block_forwards:commit",
                           "block_tokens_unmasked", "blocks_committed", "block_tokens_emitted",
                           "tokens_discarded"), 0)
        for slot, req in binding.items():
            *block, committed, unmasked = (int(x) for x in arr[slot])
            if pool.slots[slot] is not req:
                got["tokens_discarded"] += B * committed
                continue
            if not committed:
                got["block_forwards:denoise"] += 1
                got["block_tokens_unmasked"] += unmasked
                continue
            got["block_forwards:commit"] += 1
            got["blocks_committed"] += 1
            tokens, req.block_tail = block[req.block_tail:], 0
            if req.first_token_t is None:
                req.first_token_t = time.time()
            emitted = 0
            for at, token in enumerate(tokens):
                appended, ended = self._emit(pool, slot, token)
                emitted += appended
                if ended is not None:
                    done.append((req, ended))
                    got["tokens_discarded"] += len(tokens) - at - 1
                    break
            got["block_tokens_emitted"] += emitted
            applied.setdefault(id(req), [req, 0])[1] += emitted
        got["tokens_generated"] = got["block_tokens_emitted"]
        return got

    @staticmethod
    def _arrived(pool: "_Pool", pending: list, runahead: int) -> tuple:
        """``pending`` first tokens of a pool that carries, as (those to take
        now, those left for a later pass). Nothing is queued behind a chunk
        launch that carried the decode step, so a wait for its first token
        would let the chip run dry while the loop comes round: a token is
        taken in the pass that finds it arrived, and at the latest before the
        fetch of a step that decoded its slot (the steps this pass fetches:
        all but ``runahead``)."""
        fetched = list(pool.inflight)[:max(0, len(pool.inflight) - runahead)]
        decoded = {(slot, id(req)) for _, binding, _ in fetched for slot, req in binding.items()}
        now, later = [], []
        for entry in pending:
            slot, req, tok, _ = entry
            due = (slot, id(req)) in decoded or tok.is_ready()
            (now if due else later).append(entry)
        return now, later

    def _routing_counts(self, stats, programs: tuple) -> dict:
        """The routing counts a program handed out (None for a dense model; a
        row a program in ``programs``), by counter name. Called inside the
        fetch of the tokens they came out beside; their own copy to the host
        was started with the tokens', so this waits for nothing the tokens did
        not wait for. Of a model whose stack runs several times a token the
        rows are its forwards, passes and exits (``models/patterned.py
        _loop_stats``), counted whatever program ran them."""
        if stats is None:
            return {}
        if self.model_cfg.loop_passes > 1:
            forwards, passes, *exits = (int(n) for n in np.atleast_2d(np.asarray(stats)).sum(axis=0))
            return {"loop_forwards": forwards, "loop_stack_passes": passes,
                    **{f"loop_exit_rows:{t}": n for t, n in enumerate(exits)}}
        return {
            f"{name}:{program}": int(value)
            for program, row in zip(programs, np.atleast_2d(np.asarray(stats)))
            for name, value in zip(_MOE_COUNTERS, row)
        }

    def _engine_loop(self):
        # the four stages stay attributes looked up on ``self`` each pass:
        # the benchmark wraps them by name. Loop spans go to the profiler
        # only (``tracing.annotate``), never to the ring; the loop's own
        # clock (``_LoopClock``) is read at the same five boundaries, and a
        # pass starts where the last one ended.
        self._loop_first_pass_t = time.time()
        clock, now = self._loop, time.perf_counter
        t0 = clock.started_t = now()
        while not self._stop.is_set():
            wall_t = time.time()
            with tracing.annotate("engine.pull_waiting"):
                progressed = self._pull_waiting()
            t1 = now()
            with tracing.annotate("engine.advance_admissions"):
                progressed |= self._advance_admissions()
            t2 = now()
            with tracing.annotate("engine.launch_decodes"):
                progressed |= self._launch_decodes()
            t3 = now()
            with tracing.annotate("engine.drain"):
                progressed |= self._drain()
            t4 = t5 = now()
            if not progressed:
                clock.idle_sleeps += 1
                with tracing.annotate("engine.idle_sleep"):
                    time.sleep(0.002)
                t5 = now()
            clock.end_pass(wall_t, (t0, t1, t2, t3, t4, t5))
            t0 = t5

    def _emit(self, pool: "_Pool", slot: int, token: int) -> tuple:
        """Record a generated token for the request in `slot`; finish on
        eos/max_tokens/stripe-full. Returns (tokens appended: 1, or 0 for a
        stop token; ``time.time()`` where the request finished, else None:
        its slot is free then, and the caller closes it at that time once it
        has counted)."""
        req = pool.slots[slot]
        if req is None:
            return 0, None
        p = req.params
        eos = self.tokenizer.eos_id
        stop_ids = set(p.stop_token_ids or [])
        if not p.ignore_eos:
            stop_ids.add(eos)
        is_stop = token in stop_ids
        made = int(not is_stop)
        if made:
            req.out_tokens.append(token)
            req.stream_queue.put(
                {
                    "token_id": token,
                    "text": self.tokenizer.decode([token]),
                    "done": False,
                }
            )
        total = len(req.prompt_token_ids) + len(req.out_tokens)
        out_of_room = total >= pool.stripe_len
        if is_stop or len(req.out_tokens) >= p.max_tokens or out_of_room:
            req.finish_reason = "stop" if is_stop else "length"
            pool.slots[slot] = None
            if pool.adapter_ids[slot]:
                pool.adapter_ids[slot] = 0
                self._sync_adapter_ids(pool)
            return made, time.time()
        return made, None
