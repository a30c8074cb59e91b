"""Gang-scheduled multi-process LLM serving: replicas that span hosts.

Reference: ``llm/_internal/serve/deployments/llm/vllm/vllm_models.py:176-190``
— the reference's LLMServer asks serve for a placement group sized
``tensor_parallel_degree * pipeline_parallel_degree`` and scatters vLLM
engine workers over it; its engine does continuous batching at ANY TP×PP
(``vllm_engine.py``). Here the replica owns a STRICT_PACK placement group of
``EngineWorker`` actors; workers rendezvous into one ``jax.distributed``
world (coordinator address brokered through the control plane, the same
pattern as ``train/_internal/worker_group.py``) and each hosts the SAME
lockstep SPMD engine (``llm/spmd.py``) over the global mesh.

Continuous batching under the lockstep rule: the replica runs the ONE
scheduler (admission, chunked prefill pacing, prefix-cache bookkeeping,
finish detection) and broadcasts a StepPlan per iteration; every worker
executes the plan's programs identically and rank 0 reports sampled tokens.
A request is admitted chunk-by-chunk while other slots keep decoding —
mid-decode admission, per-token SSE streaming, and prefix-cache TTFT hits
all work at gang scale, matching the single-host ``JaxEngine`` feature set.

Throughput: the three single-host decode knobs apply at gang scale too.
``decode_steps`` packs K scanned decode steps into ONE broadcast program
(one actor round trip per K tokens — the dominant gang cost is RPC, not
TPU compute); ``decode_runahead`` keeps a bounded window of plans in
flight with strictly ordered apply, so workers never idle waiting for the
host to fetch tokens (sampled tokens chain device-side on the workers);
``max_concurrent_admissions`` interleaves several chunked prefills per
plan so arrival waves stop serializing behind one admission. Stop/EOS is
honored host-side after the fact: over-decoded tail tokens of finished
requests are discarded at apply, and sampling keys stay
``(seed, token_index)``-derived so the stream is byte-identical at any
knob setting.

Fault tolerance: sampling keys are derived from ``(request seed, token
index)``, so after a gang worker dies the replica kills the gang, respawns
it INTO THE HELD placement group, and replays in-flight requests — the
regenerated tokens are byte-identical, already-streamed prefixes are
skipped, and no controller-level replica replacement happens.
"""

from __future__ import annotations

import dataclasses
import hashlib
import itertools
import queue
import threading
import time
from collections import OrderedDict, deque
from typing import Optional

import ray_tpu
from ray_tpu._private import locktrace
from ray_tpu.llm.config import LLMConfig, SamplingParams
from ray_tpu.llm.pacing import TokenPacer
from ray_tpu.llm.server import sampling_from_body
from ray_tpu.util.placement_group import placement_group, remove_placement_group
from ray_tpu.util.scheduling_strategies import PlacementGroupSchedulingStrategy


class EngineWorker:
    """One process of the gang: joins the jax.distributed world, hosts the
    sharded params + compiled programs, executes broadcast step plans."""

    def reserve_coordinator(self) -> str:
        import socket

        from ray_tpu._private.protocol import routable_host

        s = socket.socket()
        try:
            s.bind(("", 0))
            port = s.getsockname()[1]
        finally:
            s.close()
        return f"{routable_host()}:{port}"

    def setup(self, config: LLMConfig, rank: int, world: int, coordinator: str):
        import os

        import jax

        if world > 1:
            platform = (os.environ.get("JAX_PLATFORMS") or "").split(",")[0]
            if platform.strip().lower() == "cpu":
                # CPU gangs (tests / dev hosts): XLA's default CPU client
                # cannot execute cross-process programs ("Multiprocess
                # computations aren't implemented on the CPU backend");
                # the gloo collectives backend can. Must be set before the
                # backend initializes. TPU/GPU worlds are unaffected.
                jax.config.update("jax_cpu_collectives_implementation", "gloo")
            # must precede this process's first backend use; afterwards
            # jax.devices() is the GLOBAL device set across the gang
            jax.distributed.initialize(
                coordinator_address=coordinator,
                num_processes=world,
                process_id=rank,
            )
        from ray_tpu.llm.spmd import SPMDEngineWorker, SPMDGenerator

        self.rank = rank
        self.gen = SPMDGenerator(config)
        self.eng = SPMDEngineWorker(config, self.gen)
        return {
            "rank": rank,
            "global_devices": jax.device_count(),
            "local_devices": jax.local_device_count(),
            "mesh": {k: int(v) for k, v in self.gen.mesh.shape.items()},
        }

    def generate_batch(self, token_lists, params_dict: Optional[dict]):
        """Legacy lockstep whole-batch generation (offline batch path)."""
        sp = SamplingParams(**params_dict) if params_dict else None
        out = self.gen.generate_batch(token_lists, sampling_params=sp)
        # every process computed the same replicated tokens; only rank 0's
        # payload travels back through the object store
        return out if self.rank == 0 else True

    def engine_step(self, plan: dict):
        """One continuous-batching lockstep step (see SPMDEngineWorker)."""
        out = self.eng.step(plan)
        return out if self.rank == 0 else True

    def ping(self) -> bool:
        return True


class _GangRequest:
    _seq = itertools.count()

    def __init__(self, request_id: str, prompt_ids: list, params: SamplingParams):
        self.seq = next(self._seq)
        self.request_id = request_id
        self.prompt_ids = prompt_ids
        self.params = params  # seed is always concrete (replay determinism)
        self.out_tokens: list[int] = []  # emitted (streamed) tokens
        self.gen_count = 0  # tokens APPLIED in the CURRENT run (replay-aware)
        # tokens DISPATCHED in the current run: run-ahead plans are built
        # against this future view; keys stay (seed, token_index)-derived
        self.disp_count = 0
        self.last_token = 0
        self.finish_reason: Optional[str] = None
        self.error: Optional[BaseException] = None
        self.done = threading.Event()
        self.stream_queue: "queue.Queue" = queue.Queue()
        self.submitted_t = time.time()
        self.first_token_t: Optional[float] = None
        self.done_t: Optional[float] = None
        self.prefix_hit_tokens = 0
        self.pacer = TokenPacer()  # smooths K-token bursts for SSE


class GangLLMServer:
    """Serve deployment whose ONE replica is a gang of N engine-worker
    processes (tp/sp sharded). API mirrors ``LLMServer``'s OpenAI-shaped
    methods (unary + streaming) so the OpenAI router and proxy work
    unchanged."""

    _PREFIX_CAP = 8  # cached prompt prefixes per gang (mirrored on workers)

    def __init__(
        self,
        llm_config: LLMConfig,
        num_workers: int = 2,
        resources_per_worker: Optional[dict] = None,
        worker_env: Optional[dict] = None,
        pg_timeout: float = 120.0,
    ):
        from ray_tpu.llm.config import (
            refuse_blocks, refuse_latent, refuse_looped, refuse_stateful, resolve_llama_config,
        )
        from ray_tpu.llm.tokenizer import get_tokenizer

        # before a placement group or a worker exists
        model_cfg = resolve_llama_config(llm_config.model, llm_config.engine)
        refuse_latent(model_cfg, "llm/gang.py")
        refuse_stateful(model_cfg, "llm/gang.py")
        refuse_blocks(model_cfg, "llm/gang.py")
        refuse_looped(model_cfg, "llm/gang.py")
        self.llm_config = llm_config
        self.tokenizer = get_tokenizer(llm_config.model.tokenizer)
        self.num_workers = num_workers
        self._resources_per_worker = resources_per_worker
        self._worker_env = worker_env
        # one broadcast at a time: two in-flight lockstep programs could
        # reach workers in different per-actor orders — collective deadlock
        self._lockstep = threading.Lock()
        bundles = [dict(resources_per_worker or {"CPU": 1}) for _ in range(num_workers)]
        self._bundles = bundles
        # STRICT_PACK: the gang must land in one ICI domain (one slice)
        self.pg = placement_group(bundles, strategy="STRICT_PACK")
        if not self.pg.wait(timeout_seconds=pg_timeout):
            remove_placement_group(self.pg)
            raise TimeoutError(
                f"placement group for {num_workers} engine workers not ready"
            )
        self.workers: list = []
        try:
            self._spawn_gang()
        except BaseException:
            # a failed replica construction must not pin a slice's worth of
            # reserved resources (actors + STRICT_PACK pg) across retries
            self.shutdown()
            raise
        # ---- scheduler state (the gang's single brain) ----
        ec = llm_config.engine
        self.n_slots = ec.max_num_seqs
        self.max_len = ec.max_seq_len
        self.chunk = min(ec.prefill_buckets)
        # decode-throughput knobs, lifted from the single-host engine: K
        # scanned decode steps per broadcast program, a bounded in-flight
        # dispatch window, and pipelined chunked admissions. Host-side
        # only (workers jit-specialize per K), so they are retunable live.
        self._decode_steps = max(1, ec.decode_steps)
        self._decode_runahead = max(1, ec.decode_runahead)
        self._max_admissions = max(1, ec.max_concurrent_admissions)
        self._cv = threading.Condition()
        self._queue: deque = deque()
        # DISPATCH-view slot table: bound when a final prefill chunk is
        # dispatched, freed when the finish is applied OR when every
        # budgeted token has been dispatched (predictable length finishes
        # free the slot early; the stripe handoff is safe because worker
        # plan order matches dispatch order)
        self._slots: list = [None] * self.n_slots
        self._adms: "OrderedDict[int, dict]" = OrderedDict()  # slot -> admission
        # dispatched plans whose results have not been fetched yet (run-
        # ahead window; apply is strictly in dispatch order)
        self._inflight: deque = deque()
        self._max_inflight_seen = 0
        self._max_admissions_seen = 0
        self._prefix_index: "OrderedDict[str, int]" = OrderedDict()
        # prefix-KV snapshots owed to the NEXT plan — a list, because up to
        # max_concurrent_admissions final chunks can land in one plan
        self._pending_stores: list = []
        self._pending_evict: list = []
        self._prefix_hits = 0
        self._prefix_misses = 0
        self._rebuilds = 0
        self._need_rebuild = False
        self._fatal: Optional[BaseException] = None
        self._stop = False
        self._loop_thread = threading.Thread(
            target=self._loop, daemon=True, name="gang-scheduler"
        )
        self._loop_thread.start()

    def set_perf_knobs(
        self,
        decode_steps: Optional[int] = None,
        decode_runahead: Optional[int] = None,
        max_concurrent_admissions: Optional[int] = None,
    ):
        """Retune the gang's throughput knobs live (bench sweeps / ops).
        Safe between requests: plans already in flight keep their shape;
        new plans pick up the new values. Workers compile one decode
        program per distinct decode_steps value (shape-specialized jit)."""
        with self._cv:
            if decode_steps is not None:
                self._decode_steps = max(1, int(decode_steps))
            if decode_runahead is not None:
                self._decode_runahead = max(1, int(decode_runahead))
            if max_concurrent_admissions is not None:
                self._max_admissions = max(1, int(max_concurrent_admissions))
            self._cv.notify_all()

    def _spawn_gang(self):
        """(Re)create the full worker gang inside the held placement group
        and rendezvous a fresh jax.distributed world."""
        cls = ray_tpu.remote(EngineWorker)
        opts = {}
        if self._worker_env:
            opts["runtime_env"] = {"env_vars": dict(self._worker_env)}
        workers = []
        try:
            # append as each handle is created: if creation fails partway,
            # the cleanup must see (and kill) every actor actually spawned —
            # remove_placement_group only releases bundle resources, it does
            # not reap actors on the pg.
            for i in range(self.num_workers):
                workers.append(
                    cls.options(
                        num_cpus=self._bundles[i].get("CPU", 1),
                        resources={
                            k: v
                            for k, v in self._bundles[i].items()
                            if k != "CPU"
                        },
                        scheduling_strategy=PlacementGroupSchedulingStrategy(
                            placement_group=self.pg,
                            placement_group_bundle_index=i,
                        ),
                        name=f"llm-gang-{self.llm_config.served_name}-{i}-{time.time_ns()}",
                        **opts,
                    ).remote()
                )
            coordinator = ray_tpu.get(
                workers[0].reserve_coordinator.remote(), timeout=60
            )
            # all setups in flight together: jax.distributed.initialize
            # blocks until the whole world has connected
            infos = ray_tpu.get(
                [
                    w.setup.remote(self.llm_config, rank, self.num_workers, coordinator)
                    for rank, w in enumerate(workers)
                ],
                timeout=300,
            )
        except BaseException:
            for w in workers:
                try:
                    ray_tpu.kill(w)
                except Exception:  # noqa: BLE001
                    pass
            raise
        self.workers = workers
        self.gang_info = infos[0]

    # -- scheduler loop ------------------------------------------------------

    def submit(self, prompt: str, params: SamplingParams) -> _GangRequest:
        ids = self.tokenizer.encode(prompt)
        if len(ids) > self.max_len - 1:
            raise ValueError(
                f"prompt length {len(ids)} exceeds the maximum "
                f"{self.max_len - 1} (max_seq_len)"
            )
        if params.seed is None:
            import random as _random

            # every request gets a concrete seed: replay after a gang
            # rebuild must regenerate the exact streamed tokens
            params = dataclasses.replace(params, seed=_random.getrandbits(31))
        req = _GangRequest(f"gang-{time.time_ns()}", ids, params)
        with self._cv:
            # checked under _cv so it cannot race _fail_outstanding's final
            # queue snapshot: after shutdown() or a scheduler crash no
            # thread drains the queue, so a late submit must fail loudly,
            # not strand its consumer (_fatal is set before the snapshot,
            # so one of the two sides always sees the other)
            if self._stop:
                raise RuntimeError("gang is shut down")
            if self._fatal is not None:
                raise RuntimeError(f"gang is down: {self._fatal}")
            self._queue.append(req)
            self._cv.notify_all()
        return req

    def _loop(self):
        try:
            self._loop_body()
        finally:
            # ANY scheduler exit — clean shutdown or a crash — must fail
            # the requests still owed tokens, or streaming consumers block
            # forever on a stream_queue that never gets its sentinel
            err = self._fatal or RuntimeError(
                "gang is shut down" if self._stop else "gang scheduler crashed"
            )
            if self._fatal is None and not self._stop:
                # a crashed loop serves nothing: late submits must fail
                # loudly (submit checks _fatal), not strand their consumer
                self._fatal = err
            self._fail_outstanding(err)

    def _loop_body(self):
        while not self._stop:
            with self._cv:
                while (
                    not self._stop
                    and not self._need_rebuild
                    and not self._adms
                    and not any(self._slots)
                    and not self._queue
                    and not self._inflight
                ):
                    self._cv.wait(timeout=1.0)
                if self._stop:
                    return
            if self._need_rebuild:
                self._do_rebuild()
                continue
            plan, record = self._build_plan()
            # ordered apply with a bounded run-ahead window: at most
            # decode_runahead plans are ever in flight. Before dispatching
            # a new plan the window is drained to make room; with nothing
            # new to dispatch, drain one record and rebuild the plan (its
            # apply may free a slot / finish a request).
            window = self._decode_runahead - 1 if plan is not None else 0
            failed = False
            while len(self._inflight) > window:
                rec = self._inflight.popleft()
                try:
                    outs = ray_tpu.get(rec["refs"], timeout=600)
                except Exception as e:  # noqa: BLE001 — worker died mid-step
                    # the popped record — and the freshly built one, whose
                    # dispatch state already advanced in _build_plan — may
                    # be the ONLY references to a request whose slot was
                    # freed at dispatch (budget fully in flight); put both
                    # back so the rebuild's live scan replays them
                    self._inflight.appendleft(rec)
                    if record is not None:
                        self._inflight.append(record)
                    self._do_rebuild(cause=e)
                    failed = True
                    break
                self._apply(rec, outs[0])
                if plan is None:
                    break  # state changed — try to build again
            if failed or plan is None:
                continue  # a stale plan must not reach the rebuilt gang
            try:
                # one dispatcher thread + per-actor FIFO mailboxes keep
                # every worker executing plans in the same order; the
                # lock only guards against a concurrent rebuild swap
                with self._lockstep:
                    record["refs"] = [
                        w.engine_step.remote(plan) for w in self.workers
                    ]
            except Exception as e:  # noqa: BLE001 — submit to a dead gang
                # same: the record's requests advanced at build time and
                # may no longer be visible via slots/admissions
                self._inflight.append(record)
                self._do_rebuild(cause=e)
                continue
            self._inflight.append(record)
            self._max_inflight_seen = max(
                self._max_inflight_seen, len(self._inflight)
            )

    def _build_plan(self):
        """Build the next lockstep plan against the DISPATCH view and the
        record needed to apply its results later. Admission chunk cursors,
        slot bindings, token counts, and prefix-cache bookkeeping all
        advance here (dispatch time) so run-ahead plans stack correctly;
        apply only accounts sampled tokens against the record."""
        import numpy as np

        plan: dict = {}
        record: dict = {"admits": [], "decode": None}
        if self._pending_evict:
            plan["evict"] = self._pending_evict
            self._pending_evict = []
        if self._pending_stores:
            plan["stores"] = self._pending_stores
            self._pending_stores = []
        # top up the admission pipeline: every free slot can start admitting
        # as long as the concurrency cap allows (arrival waves stop
        # serializing behind one in-flight prefill)
        while len(self._adms) < self._max_admissions:
            with self._cv:
                free = next(
                    (
                        i
                        for i, r in enumerate(self._slots)
                        if r is None and i not in self._adms
                    ),
                    None,
                )
                req = (
                    self._queue.popleft()
                    if (free is not None and self._queue)
                    else None
                )
            if req is None:
                break
            self._start_admission(req, free)
        self._max_admissions_seen = max(
            self._max_admissions_seen, len(self._adms)
        )
        # one chunk per in-flight admission per plan (chunked prefill keeps
        # per-plan prompt work bounded so decode latency stays flat)
        if self._adms:
            admits = []
            for slot, a in list(self._adms.items()):
                ch = a["chunks"][a["idx"]]
                admits.append(
                    {
                        "slot": slot,
                        "tokens": ch["tokens"],
                        "eff": ch["eff"],
                        "start": ch["start"],
                        "final": ch["final"],
                        "fresh": a["idx"] == 0,
                        "seed_prefix": a["prefix_key"] if a["idx"] == 0 else None,
                        "temp": float(a["req"].params.temperature),
                        "top_k": int(a["req"].params.top_k),
                        "key": np.asarray(
                            [a["req"].params.seed & 0xFFFFFFFF, 0], np.uint32
                        ),
                    }
                )
                a["idx"] += 1
                record["admits"].append(a)
                if ch["final"]:
                    del self._adms[slot]
                    req = a["req"]
                    # bind the dispatch view now: the NEXT plan (possibly
                    # dispatched before this one is applied) decodes this
                    # slot starting from the in-program first token
                    self._slots[slot] = req
                    req.disp_count = 1
                    if a["store_key"]:
                        # prompt KV complete in the slot: snapshot it in the
                        # next plan (store precedes admits worker-side, so a
                        # later admission reusing the slot cannot race it)
                        self._pending_stores.append(
                            {
                                "slot": slot,
                                "m": a["store_m"],
                                "key": a["store_key"],
                            }
                        )
                        self._prefix_index[a["store_key"]] = a["store_m"]
                        while len(self._prefix_index) > self._PREFIX_CAP:
                            old_key, _ = self._prefix_index.popitem(last=False)
                            self._pending_evict.append(old_key)
            plan["admits"] = admits
        # decode: K scanned steps for every slot that still has budgeted
        # tokens to dispatch. Keys are (seed, token_index)-derived per step,
        # so the stream is byte-identical at any K and replay-deterministic.
        K = self._decode_steps
        binding = {}
        S = self.n_slots
        temps = np.zeros((S,), np.float32)
        top_ks = np.full((S,), 50, np.int32)
        keys = np.zeros((K, S, 2), np.uint32)
        for i, r in enumerate(self._slots):
            if r is None:
                continue
            budget = min(
                r.params.max_tokens, self.max_len - len(r.prompt_ids)
            )
            if r.disp_count >= budget:
                continue
            temps[i] = r.params.temperature
            top_ks[i] = r.params.top_k
            seed = r.params.seed & 0xFFFFFFFF
            base = r.disp_count
            for k in range(K):
                keys[k, i] = (seed, base + k)
            binding[i] = (r, base)
            r.disp_count += K
            if r.disp_count >= budget:
                # every budgeted token is now in flight: free the dispatch
                # slot for the next admission (the finish itself is applied
                # when the tokens land; stripe reuse is ordered after the
                # last decode program that reads it)
                self._slots[i] = None
        if binding:
            plan["decode"] = {
                "steps": K,
                "temps": temps,
                "top_ks": top_ks,
                "keys": keys,
            }
            record["decode"] = {"binding": binding, "steps": K}
        if not plan:
            return None, None
        return plan, record

    def _start_admission(self, req: _GangRequest, slot: int):
        import numpy as np

        ids = req.prompt_ids
        C = self.chunk
        L = len(ids)
        m = C * ((L - 1) // C)  # bucket-aligned strict-prefix length
        prefix_key = None
        store_key = None
        if m > 0:
            key = hashlib.sha1(np.asarray(ids[:m], np.int32).tobytes()).hexdigest()
            if self._prefix_index.get(key) == m:
                prefix_key = key
                self._prefix_index.move_to_end(key)
                req.prefix_hit_tokens = m
                self._prefix_hits += 1
            else:
                store_key = key
                self._prefix_misses += 1
        start = m if prefix_key is not None else 0
        chunks = []
        pos = start
        while pos < L:
            eff = min(C, L - pos)
            tok = np.zeros((1, C), np.int32)
            tok[0, :eff] = ids[pos : pos + eff]
            chunks.append(
                {"tokens": tok, "eff": eff, "start": pos, "final": pos + eff >= L}
            )
            pos += eff
        self._adms[slot] = {
            "req": req,
            "slot": slot,
            "chunks": chunks,
            "idx": 0,
            "prefix_key": prefix_key,
            "store_key": store_key,
            "store_m": m,
        }

    def _apply(self, record: dict, res: dict):
        """Account one fetched plan's sampled tokens, strictly in dispatch
        order. Requests that finished earlier (EOS/stop applied from a
        previous record) simply discard their over-decoded tail tokens —
        the run-ahead/multi-step analog of the engine's binding-snapshot
        discard."""
        admit_toks = res.get("admit_toks") or {}
        for a in record["admits"]:
            slot = a["slot"]
            if slot not in admit_toks:
                continue  # mid chunk — KV-only, nothing to account
            req = a["req"]
            if req.finish_reason is not None:
                continue  # failed/finished while the chunk was in flight
            if req.first_token_t is None:
                req.first_token_t = time.time()
            if not self._process_token(req, int(admit_toks[slot])):
                # finished on its very first token: unbind the dispatch
                # view if no later admission already took the slot
                if self._slots[slot] is req:
                    self._slots[slot] = None
        dec = record.get("decode")
        if dec is not None and res.get("toks") is not None:
            toks = res["toks"]  # [K][S]
            n_applied: dict[int, int] = {}
            for k in range(dec["steps"]):
                for slot, (r, base) in dec["binding"].items():
                    if r.finish_reason is not None:
                        continue  # over-decoded tail — discard
                    n_applied[slot] = n_applied.get(slot, 0) + 1
                    if not self._process_token(r, int(toks[k][slot])):
                        if self._slots[slot] is r:
                            self._slots[slot] = None
            # pacing: a block of n tokens landed at once for each request;
            # the SSE drain spreads them over the observed block interval
            for slot, n in n_applied.items():
                dec["binding"][slot][0].pacer.note_block(n)

    def _process_token(self, req: _GangRequest, t: int) -> bool:
        """Account one sampled token; returns False when the request
        finished (replay-aware: regenerated tokens are not re-streamed)."""
        p = req.params
        idx = req.gen_count
        req.gen_count += 1
        eos = self.tokenizer.eos_id
        stop = set(p.stop_token_ids or ())
        if (t == eos and not p.ignore_eos) or t in stop:
            self._finish(req, "stop")
            return False
        req.last_token = t
        if idx >= len(req.out_tokens):
            req.out_tokens.append(t)
            req.stream_queue.put(t)
        if req.gen_count >= p.max_tokens:
            self._finish(req, "length")
            return False
        if len(req.prompt_ids) + req.gen_count >= self.max_len:
            self._finish(req, "length")
            return False
        return True

    def _finish(self, req: _GangRequest, reason: str):
        req.finish_reason = reason
        req.done_t = time.time()
        req.stream_queue.put(None)
        req.done.set()

    def _fail_request(self, req: _GangRequest, exc: BaseException):
        req.error = exc
        req.finish_reason = "error"
        req.stream_queue.put(None)
        req.done.set()

    # -- fault tolerance -----------------------------------------------------

    def _outstanding(self) -> list:
        """Every unfinished request the scheduler still owes tokens:
        dispatch-view slots, in-flight admissions, AND requests only
        referenced by undelivered run-ahead records (their slots were
        freed at dispatch when the budget filled). Queue NOT included."""
        seen: dict[int, _GangRequest] = {}
        for r in self._slots:
            if r is not None:
                seen[id(r)] = r
        for a in self._adms.values():
            seen[id(a["req"])] = a["req"]
        for record in self._inflight:
            for a in record["admits"]:
                seen[id(a["req"])] = a["req"]
            if record["decode"] is not None:
                for r, _ in record["decode"]["binding"].values():
                    seen[id(r)] = r
        return [r for r in seen.values() if r.finish_reason is None]

    def _fail_outstanding(self, err: BaseException):
        """Fail every request still owed tokens, queued ones included, so
        streaming consumers always get their sentinel (shutdown/crash
        paths — a request must never be silently stranded)."""
        live = self._outstanding()
        self._inflight.clear()
        self._slots = [None] * self.n_slots
        self._adms = OrderedDict()
        with self._cv:
            queued = list(self._queue)
            self._queue.clear()
        for r in live + [q for q in queued if q.finish_reason is None]:
            self._fail_request(r, err)

    def _do_rebuild(self, cause: Optional[BaseException] = None):
        """A gang worker died: the jax.distributed world is broken for every
        survivor, so kill the whole gang, respawn it into the HELD placement
        group, and replay in-flight requests (deterministic seeds make the
        replayed prefix byte-identical; already-streamed tokens are
        skipped). No controller-level replica replacement happens."""
        self._need_rebuild = False
        if self._stop:
            # shutdown() is reaping the gang — a get() failure here is the
            # teardown itself, not a death to recover from; respawning
            # would leak actors into a released placement group. Stranded
            # requests must still be failed, or streaming consumers block
            # forever on a stream_queue that never gets its sentinel.
            self._fail_outstanding(
                cause or RuntimeError("gang shut down mid-request")
            )
            return
        live = self._outstanding()
        self._inflight.clear()
        self._rebuilds += 1
        self._slots = [None] * self.n_slots
        self._adms = OrderedDict()
        # worker-side prefix stores died with the gang — reset the mirror
        self._prefix_index.clear()
        self._pending_stores = []
        self._pending_evict = []
        with self._lockstep:
            old = self.workers
            self.workers = []
            for w in old:
                try:
                    ray_tpu.kill(w)
                except Exception:  # noqa: BLE001
                    pass
            try:
                self._spawn_gang()
            except Exception as e:  # noqa: BLE001 — slice truly gone
                self._fatal = e
                with self._cv:
                    queued = list(self._queue)
                    self._queue.clear()
                for r in live + queued:
                    self._fail_request(r, e)
                return
        for r in live:
            # replay from the prompt; emitted prefix skipped on re-stream
            r.gen_count = 0
            r.disp_count = 0
        with self._cv:
            for r in sorted(live, key=lambda r: r.seq, reverse=True):
                self._queue.appendleft(r)
            self._cv.notify_all()

    # -- OpenAI surface ------------------------------------------------------

    def _wait_unary(self, req: _GangRequest) -> None:
        if not req.done.wait(timeout=600):
            raise TimeoutError("gang generation timed out")
        if req.error is not None:
            raise req.error

    def completions(self, body: dict) -> dict:
        prompt = body.get("prompt", "")
        params = sampling_from_body(body)
        try:
            req = self.submit(prompt, params)
            self._wait_unary(req)
        except (ValueError, RuntimeError, TimeoutError) as e:
            return {"error": {"message": str(e), "code": 400}}
        text = self.tokenizer.decode(req.out_tokens)
        return {
            "id": f"cmpl-{req.request_id}",
            "object": "text_completion",
            "created": int(req.submitted_t),
            "model": self.llm_config.served_name,
            "choices": [
                {
                    "index": 0,
                    "text": text,
                    "finish_reason": req.finish_reason,
                }
            ],
            "usage": {
                "prompt_tokens": len(req.prompt_ids),
                "completion_tokens": len(req.out_tokens),
                "total_tokens": len(req.prompt_ids) + len(req.out_tokens),
            },
        }

    def chat(self, body: dict) -> dict:
        from ray_tpu.llm.server import LLMServer

        prompt = LLMServer._render_chat(body.get("messages", []))
        res = self.completions({**body, "prompt": prompt})
        if "error" in res:
            return res
        res["object"] = "chat.completion"
        res["choices"] = [
            {
                "index": 0,
                "message": {
                    "role": "assistant",
                    "content": res["choices"][0]["text"],
                },
                "finish_reason": res["choices"][0]["finish_reason"],
            }
        ]
        return res

    def _drain(self, req: _GangRequest):
        """Incremental text chunks as tokens stream out of the scheduler.

        Multi-step decode delivers tokens in K-sized bursts; the pacer
        spreads each burst over the observed inter-block interval so an SSE
        client sees K spaced chunks, not one blob per dispatch (intertoken
        p50 stays > 0 instead of collapsing to the intra-burst 0)."""
        emitted = 0
        prev = ""
        while True:
            tok = req.stream_queue.get()
            if tok is None:
                break
            req.pacer.gate(backlog=not req.stream_queue.empty())
            emitted += 1
            text = self.tokenizer.decode(req.out_tokens[:emitted])
            inc = text[len(prev):]
            prev = text
            if inc:
                yield inc
        if req.error is not None:
            raise req.error

    def completions_stream(self, body: dict):
        """Generator of OpenAI ``text_completion`` chunk dicts — one per
        generated token, pumped by rank 0's scheduler (SSE at gang scale)."""
        prompt = body.get("prompt", "")
        params = sampling_from_body(body)
        try:
            req = self.submit(prompt, params)
        except (ValueError, RuntimeError) as e:
            yield {"error": {"message": str(e), "code": 400}}
            return
        created = int(time.time())
        for inc in self._drain(req):
            yield {
                "id": f"cmpl-{req.request_id}",
                "object": "text_completion",
                "created": created,
                "model": self.llm_config.served_name,
                "choices": [
                    {"index": 0, "text": inc, "finish_reason": None}
                ],
            }
        yield {
            "id": f"cmpl-{req.request_id}",
            "object": "text_completion",
            "created": created,
            "model": self.llm_config.served_name,
            "choices": [
                {"index": 0, "text": "", "finish_reason": req.finish_reason}
            ],
        }

    def chat_stream(self, body: dict):
        """Generator of OpenAI ``chat.completion.chunk`` dicts."""
        from ray_tpu.llm.server import LLMServer

        prompt = LLMServer._render_chat(body.get("messages", []))
        first = True
        for chunk in self.completions_stream({**body, "prompt": prompt}):
            if "error" in chunk:
                yield chunk
                return
            delta = {}
            text = chunk["choices"][0]["text"]
            finish = chunk["choices"][0]["finish_reason"]
            if finish is None:
                delta = {"content": text}
                if first:
                    delta["role"] = "assistant"
                    first = False
            yield {
                "id": chunk["id"].replace("cmpl-", "chatcmpl-"),
                "object": "chat.completion.chunk",
                "created": chunk["created"],
                "model": chunk["model"],
                "choices": [
                    {"index": 0, "delta": delta, "finish_reason": finish}
                ],
            }

    def __call__(self, request) -> dict:
        """Direct-proxy entrypoint (a gang deployment can also sit behind
        the OpenAI router, which calls completions/chat explicitly)."""
        path = request.path or ""
        if path.endswith("/models") or path.endswith("/model_info"):
            return self.model_info()
        try:
            body = request.json() or {}
        except Exception:  # noqa: BLE001
            return {"error": {"message": "invalid JSON body", "code": 400}}
        if path.endswith("/chat/completions") or path.endswith("/chat"):
            return self.chat(body)
        if path.endswith("/completions"):
            return self.completions(body)
        return {"error": {"message": f"unknown route {path}", "code": 404}}

    # -- ops -----------------------------------------------------------------

    def model_info(self) -> dict:
        return {
            "id": self.llm_config.served_name,
            "object": "model",
            "owned_by": "ray_tpu",
            "gang": self.gang_info,
        }

    def stats(self) -> dict:
        # active = unfinished requests the gang still owes tokens: the
        # dispatch-view slot table PLUS requests whose slot was freed at
        # dispatch but whose tokens are still riding undelivered run-ahead
        # records — without the latter, a request with max_tokens <=
        # decode_steps reads as idle while it is mid-stream. Lock-free
        # snapshot racing the scheduler thread: counts may be transiently
        # stale (monitoring surface), but never miss a live request that
        # stays live across the read.
        active: set = {
            id(r)
            for r in list(self._slots)
            if r is not None and r.finish_reason is None
        }
        try:
            for rec in list(self._inflight):
                dec = rec.get("decode")
                if dec is not None:
                    for r, _ in dec["binding"].values():
                        if r.finish_reason is None:
                            active.add(id(r))
        except RuntimeError:  # deque mutated mid-iteration — keep snapshot
            pass
        return {
            "gang": self.gang_info,
            "num_workers": self.num_workers,
            "active_slots": len(active),
            "admitting": len(self._adms),
            "queued": len(self._queue),
            "inflight_plans": len(self._inflight),
            "max_inflight_seen": self._max_inflight_seen,
            "max_admissions_seen": self._max_admissions_seen,
            "decode_steps": self._decode_steps,
            "decode_runahead": self._decode_runahead,
            "max_concurrent_admissions": self._max_admissions,
            "prefix_hits": self._prefix_hits,
            "prefix_misses": self._prefix_misses,
            "rebuilds": self._rebuilds,
        }

    def check_health(self):
        """Serve health probe. A dead worker triggers an IN-PLACE gang
        rebuild (the replica heals itself); only an unrecoverable gang
        (respawn failed) reports unhealthy so the controller replaces the
        replica."""
        if self._fatal is not None:
            raise RuntimeError(f"gang is down: {self._fatal}")
        try:
            ray_tpu.get([w.ping.remote() for w in self.workers], timeout=30)
        except Exception:  # noqa: BLE001
            with self._cv:
                self._need_rebuild = True
                self._cv.notify_all()

    def shutdown(self):
        self._stop = True
        # shutdown may run as __init__'s cleanup BEFORE the scheduler state
        # exists (a failed gang spawn) — it must still reap workers + pg
        # instead of masking the original failure with an AttributeError
        if hasattr(self, "_cv"):
            with self._cv:
                self._cv.notify_all()
        # bounded: the loop re-checks _stop on every cv wakeup above
        # (getattr: shutdown may run as a failed __init__'s cleanup)
        locktrace.join_if_alive(getattr(self, "_loop_thread", None), timeout=2.0)
        for w in self.workers:
            try:
                ray_tpu.kill(w)
            except Exception:  # noqa: BLE001
                pass
        self.workers = []
        if self.pg is not None:
            try:
                remove_placement_group(self.pg)
            except Exception:  # noqa: BLE001
                pass
            self.pg = None
